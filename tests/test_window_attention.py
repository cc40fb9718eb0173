"""Window and full attention layers mixed on the serving path, CPU, small
sizes, seeded weights: head counts, rope and cache rows that go by the
layer's type (a ring of the window's rows in a window layer, `max_len` rows
in a full one), a per-head output gate, under gated experts with a shared
expert after a leading gated dense layer. The oracle is the benchmark's
plain reference (`benchmark/references/laguna_window_moe.py`: float32 at
HIGHEST, one full forward, every layer holding every position's k and v,
the window as a mask), the ONE copy that the cell's `correct` imports too."""

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import manifest as mf
from dml_tpu.inference import generate as g
from dml_tpu.inference import lm_backend as lb
from dml_tpu.inference import lm_server as ls
from dml_tpu.inference.lm_server import LMServer
from dml_tpu.observability import METRICS
from dml_tpu.ops.decode_attention import decode_attention
from dml_tpu.ops.flash_attention import (band_blocks, band_visits,
                                         flash_attention)
from dml_tpu.tracing import TRACER

fa = importlib.import_module("dml_tpu.ops.flash_attention")

ref = mf.load_module("references", "laguna_window_moe")

W = 8  # the window, shorter than most prompts below
YARN = {"factor": 64.0, "original_max_position": 16, "beta_fast": 64.0,
        "beta_slow": 1.0}
TYPES = {
    "full": {"n_heads": 6, "gate": "per_head",
             "rope": {"theta": 500000.0, "rotary_dim": 8, "yarn": YARN}},
    "window": {"n_heads": 8, "window": W, "gate": "per_head",
               "rope": {"theta": 10000.0}},
}
SPEC = {
    "vocab_size": 256, "d_model": 64, "n_heads": 6, "n_kv_heads": 2,
    "head_dim": 16, "n_layers": 5, "d_ff": 96,
    "attention_layers": {
        "layers": ["full", "window", "window", "window", "full"],
        "types": TYPES},
    "norm_eps": 1e-6, "num_experts": 16, "experts_per_token": 4,
    "expert_d_ff": 24, "gated": True, "experts_held": [0, 4],
    "router": {"scoring": "sigmoid", "scale": 2.5},
    "shared_expert_d_ff": 24, "dense_layers": 1, "activation": "silu",
    "dtype": "float32", "param_dtype": "float32",
}
#: float32 against float32 at HIGHEST: what is left is the order of the
#: sums (flash blocks against one softmax, the ring's rows in another
#: order than the positions', the grouped matmul). Measured 3e-6 to 5e-6
#: on logits of spread ~1; bfloat16 reads 1e-2 and more (the control)
F32_TOL = 5e-5


@pytest.fixture(scope="module")
def model():
    _, cfg = lb.lm_spec_parts(SPEC)
    return ref.make_params(SPEC, 7), cfg


def _tokens(n, seed=0):
    return np.random.RandomState(seed).randint(0, 256, n).astype(np.int32)


def _through_the_ring(params, cfg, toks, split, max_len=64):
    """Logits of positions split-1 .. len-1: prefill (the banded kernel,
    the ring filled at the prompt's own length), then one decode step a
    token against the planes and the rings."""
    lg, cache = g.prefill(params, cfg, jnp.asarray(toks[None, :split]),
                          max_len)
    out = [np.asarray(lg[0])]
    for t in range(split, len(toks)):
        lg, cache = g.batched_decode_step(
            params, cfg, cache, jnp.asarray(toks[t:t + 1]), jnp.asarray([t]))
        out.append(np.asarray(lg[0]))
    return np.stack(out)


# prompts shorter than, equal to and longer than the window; 40 positions,
# so every one decodes across the ring's wrap (several times)
@pytest.mark.parametrize("split", [3, W, 24])
def test_prefill_then_decode_through_the_ring_equals_one_full_forward(
        model, split):
    params, cfg = model
    toks = _tokens(40, seed=split)
    want = ref.logits_rows(params, SPEC, toks, 0, 40, pad_to=40)
    with jax.default_matmul_precision("highest"):
        got = _through_the_ring(params, cfg, toks, split)
    assert np.abs(got - want[split - 1:]).max() < F32_TOL
    # the control: the same program in bfloat16 is far outside it
    _, low = lb.lm_spec_parts({**SPEC, "dtype": "bfloat16"})
    assert np.abs(_through_the_ring(params, low, toks, split)
                  - want[split - 1:]).max() > 100 * F32_TOL


ONE_WINDOW = {**SPEC, "n_layers": 1, "dense_layers": 0, "attention_layers": {
    "layers": ["window"], "types": TYPES}}


# the band's blocks: the kernel's own (one block here), and blocks that
# put the edge and the diagonal in pieces of their own (W = 8: three
# pieces of 4 rows with an inside one, five of 2, two of 8 over 20 rows)
@pytest.mark.parametrize("block", [None, 4, 2, 8])
@pytest.mark.parametrize("split", [20, 12])
def test_the_edge_is_seen_at_w_minus_one_back_and_not_at_w(
        split, block, monkeypatch):
    """One window layer, so that a position's logits see W keys and no
    further: the token W - 1 back moves them, the token W back does not
    (bit for bit), through the banded prefill (split 20: position 19 is a
    prompt's) and through the ring (split 12: position 19 is decoded, and
    the ring has wrapped over position 19 - W)."""
    if block is not None:
        monkeypatch.setattr(fa, "live_blocks", lambda *a: (block, block))
    _, cfg = lb.lm_spec_parts(ONE_WINDOW)
    params = ref.make_params(ONE_WINDOW, 3)
    toks = _tokens(20, seed=5)
    at = lambda t: _through_the_ring(params, cfg, t, split)[19 - (split - 1)]
    base = at(toks)

    def moved(back):
        other = toks.copy()
        other[19 - back] = (other[19 - back] + 1) % 256
        return at(other)

    assert np.array_equal(moved(W), base)            # i - j = W: not seen
    assert np.array_equal(moved(W + 3), base)
    assert np.abs(moved(W - 1) - base).max() > 1e-4  # i - j = W - 1: seen
    assert np.abs(moved(0) - base).max() > 1e-4


def test_the_ring_holds_the_last_window_rows_at_a_rows_own_length(model):
    """A padded group: rows of different lengths in one prefill call,
    each with its own `logits_index`. A row's ring is the ring of the
    same prompt prefilled alone and unpadded, and so are its logits."""
    params, cfg = model
    lens = [5, W, 13, 30]
    rows = np.stack([np.pad(_tokens(n, seed=n), (0, 32 - n),
                            constant_values=_tokens(n, seed=n)[-1])
                     for n in lens])
    with jax.default_matmul_precision("highest"):
        lg, cache = g.prefill(params, cfg, jnp.asarray(rows), 64,
                              logits_index=jnp.asarray(lens) - 1)
        for b, n in enumerate(lens):
            alone_lg, alone = g.prefill(
                params, cfg, jnp.asarray(rows[b:b + 1, :n]), 64)
            np.testing.assert_allclose(lg[b], alone_lg[0], atol=F32_TOL)
            live = min(n, W)
            at = np.asarray(g.ring_positions(jnp.asarray([n]), W))[0]
            assert sorted(at[at >= 0]) == list(range(n - live, n))
            for key in ("k_ring", "v_ring"):
                got = np.asarray(cache["block_2"][key][b])[:, at >= 0]
                want = np.asarray(alone["block_2"][key][0])[:, at >= 0]
                np.testing.assert_allclose(got, want, atol=F32_TOL)
    assert cache["block_2"]["k_ring"].shape == (4, 2, W, 16)
    assert cache["block_0"]["k"].shape == (4, 2, 64, 16)


def _serve(params, cfg, prompts, budgets, slots=3, **kw):
    srv = LMServer(params, cfg, max_slots=slots, max_len=64, chunk=4, **kw)
    rids = [srv.submit(p, b) for p, b in zip(prompts, budgets)]
    done = srv.run()
    return srv, [done[r] for r in rids]


def _alone(params, cfg, prompt, n):
    return np.asarray(g.generate(params, cfg, jnp.asarray(prompt[None]), n))[0]


def test_a_padded_group_with_riders_and_a_reused_slot_equal_prompts_alone(
        model):
    """Seven prompts over three slots: a round's prompts are one padded
    group whose short prompts ride beside long ones, and later rounds take
    slots that LONGER occupants left, whose rings are full of their rows."""
    params, cfg = model
    prompts = [_tokens(n, seed=n) for n in (44, 9, 40, 5, 13, W, 30)]
    budgets = [12 + i for i in range(7)]
    TRACER.reset()
    _, got = _serve(params, cfg, prompts, budgets)
    for p, n, toks in zip(prompts, budgets, got):
        np.testing.assert_array_equal(toks, _alone(params, cfg, p, n))
    groups = TRACER.loop_spans("lm_prefill_group")
    assert groups and any(s["lb"]["rows"] > 1 for s in groups)


def test_a_slot_reused_after_a_longer_occupant_leaks_nothing(model):
    """One slot: a prompt of 3 tokens after an occupant of 56 rows. Its
    ring's rows 3 .. 7 still hold the occupant's positions; none is
    attended before this request's own steps overwrite it."""
    params, cfg = model
    long_, short = _tokens(44, seed=1), _tokens(3, seed=2)
    _, got = _serve(params, cfg, [long_, short], [12, 20], slots=1)
    np.testing.assert_array_equal(got[1], _alone(params, cfg, short, 20))


# ----------------------------------------------------------------------
# the kernels
# ----------------------------------------------------------------------


def _ring_oracle(q, k, v, lengths):
    """[B, 1, H, D] against ring planes [B, KV, R, D], numpy float64: the
    first min(length, R) rows, whatever order they are in."""
    b, _, h, d = q.shape
    kv, r = k.shape[1], k.shape[2]
    out = np.zeros((b, 1, h, d))
    for i in range(b):
        n = min(int(lengths[i]), r)
        for j in range(h):
            if not n:
                continue
            kk = np.asarray(k[i, j // (h // kv), :n], np.float64)
            vv = np.asarray(v[i, j // (h // kv), :n], np.float64)
            s = kk @ np.asarray(q[i, 0, j], np.float64) * d ** -0.5
            p = np.exp(s - s.max())
            out[i, 0, j] = p / p.sum() @ vv
    return out


@pytest.mark.parametrize("heads", [6, 8])  # 3 and 4 query rows a KV head
def test_the_decode_kernel_over_a_ring_equals_the_oracle(heads):
    """The Pallas decode kernel (interpret mode) over a ring plane of 32
    rows in blocks of 16: ragged lengths, a slot whose ring has wrapped
    (its length past the ring's rows: every row live), a last block partly
    dead, an empty slot."""
    ring = 32
    ks = jax.random.split(jax.random.PRNGKey(heads), 3)
    q = jax.random.normal(ks[0], (5, 1, heads, 16))
    k = jax.random.normal(ks[1], (5, 2, ring, 16))
    v = jax.random.normal(ks[2], (5, 2, ring, 16))
    lengths = np.asarray([5, 0, 21, 32, 57])
    got = decode_attention(
        q, k, v, jnp.minimum(jnp.asarray(lengths), ring), block_k=16,
        interpret=True)
    np.testing.assert_allclose(got, _ring_oracle(q, k, v, lengths), atol=2e-6)
    assert not np.asarray(got[1]).any()  # the empty slot: zeros


def test_the_kernel_route_serves_what_the_einsum_route_serves(
        model, monkeypatch):
    """`batched_decode_step` with cache attention handed to the kernel
    (interpret mode here) against the einsum oracle, layers of 6 and of 8
    heads, planes and rings, ragged positions past the wrap, an empty
    slot."""
    params, cfg = model
    toks = np.stack([_tokens(30, seed=s) for s in range(4)])
    with jax.default_matmul_precision("highest"):
        _, cache = g.prefill(
            params, cfg, jnp.asarray(toks), 64,
            logits_index=jnp.asarray([4, 29, 11, 20]))
        pos = jnp.asarray([5, 30, 12, 63])
        lengths = jnp.asarray([6, 31, 13, 0])  # slot 3 empty, pos clamped
        cur = jnp.asarray(toks[:, 0])
        want, want_cache = g.batched_decode_step(
            params, cfg, cache, cur, pos, lengths=lengths)
        monkeypatch.setattr(g, "uses_decode_kernel", lambda: True)
        got, got_cache = g.batched_decode_step(
            params, cfg, cache, cur, pos, lengths=lengths)
    np.testing.assert_allclose(got[:3], want[:3], atol=F32_TOL)
    for a, b in zip(jax.tree.leaves(got_cache), jax.tree.leaves(want_cache)):
        np.testing.assert_allclose(a, b, atol=F32_TOL)
    # the row went to pos mod W of the ring, and nowhere else
    k_new, k_old = got_cache["block_1"]["k_ring"], cache["block_1"]["k_ring"]
    for slot, p in enumerate((5, 30, 12, 63)):
        changed = np.flatnonzero(
            np.abs(np.asarray(k_new[slot] - k_old[slot])).sum((0, 2)))
        assert list(changed) == [p % W]


def test_no_multi_row_step_runs_over_a_ring(model):
    params, cfg = model
    with pytest.raises(ValueError, match="window layer's ring"):
        g.batched_block_step(
            params, cfg, g.init_cache(cfg, 1, 64),
            jnp.zeros((1, 4), jnp.int32), jnp.asarray([0]))


def _masked_softmax(q, k, v, window):
    t = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * q.shape[-1] ** -0.5
    back = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    s = jnp.where((back >= 0) & (back < window), s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                      precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("t,window,block,visited", [
    (64, 16, 16, (7, 10)),    # two blocks a q-block: its own and one back
    (100, 16, 32, (7, 10)),   # a ragged last block
    (96, 40, 16, (18, 21)),   # a band of four blocks
    (70, 200, 32, (6, 6)),    # a window past the sequence: causal
    (50, 8, 1024, (1, 1)),    # one block
    (12, 16, 16, (1, 1)),     # T < W, one ragged block
    (16, 16, 16, (1, 1)),     # T = W, one block
    (32, 32, 16, (3, 3)),     # T = W, two blocks: the band skips nothing
    (100, 48, 16, (22, 28)),  # four pieces, two inside, a pad tail of 12
    (90, 24, 8, (42, 78)),    # a band of four blocks of 8, a pad tail of 6
    (300, 64, None, (5, 6)),  # the kernel's own blocks (128), a pad tail
    (600, 512, None, (6, 6)),  # ... of 256: pieces 0, 0-1, 0-2, one inside
])
def test_banded_flash_equals_a_masked_softmax_and_skips_the_blocks_below(
        t, window, block, visited):
    ks = jax.random.split(jax.random.PRNGKey(t), 3)
    q, k, v = (jax.random.normal(kk, (2, t, 3, 16)) for kk in ks)
    blocks = {} if block is None else {"block_q": block, "block_k": block}
    got = flash_attention(q, k, v, window=window, **blocks)
    np.testing.assert_allclose(got, _masked_softmax(q, k, v, window),
                               atol=2e-6)
    # K and V by KV head (three query heads to one) are the same rows
    np.testing.assert_allclose(
        flash_attention(q, k[:, :, :1], v[:, :, :1], window=window,
                        **blocks),
        _masked_softmax(q, jnp.repeat(k[:, :, :1], 3, 2),
                        jnp.repeat(v[:, :, :1], 3, 2), window), atol=2e-6)
    # the grid's innermost extent is the band, not the sequence
    assert band_visits(t, window, block, head_dim=16) == visited
    if block is not None:
        assert band_blocks(window, block) == -(-(window - 1) // block) + 1


def test_the_band_at_the_real_sizes_and_the_backward_refuses():
    # W 512 at blocks of 512: a q-block visits two k-blocks of eight
    assert band_blocks(512, 512) == 2
    assert band_visits(4096, 512, 512) == (15, 36)
    assert band_visits(512, 512, 512) == (1, 1)
    # at the kernel's own blocks (64 heads of 128 over 8): three pieces of
    # 256 rows a q-block, the middle one all inside
    assert fa.live_blocks(512, 4096, 128, 8) == (256, 256)
    assert band_visits(4096, 512, head_dim=128, group=8) == (45, 136)
    assert fa.band_masked(4096, 512, head_dim=128, group=8) == 30
    assert band_visits(512, 512, head_dim=128, group=8) == (3, 3)
    assert fa._band_edges(512, 256, 3) == (True, False, True)
    assert fa._band_edges(512, 128, 5) == (True, False, False, False, True)
    assert fa._band_edges(40, 16, 4) == (True, True, False, True)
    q = jnp.ones((1, 16, 1, 8))
    with pytest.raises(NotImplementedError, match="know no window"):
        jax.grad(lambda x: flash_attention(x, q, q, window=4).sum())(q)
    with pytest.raises(ValueError, match="band is causal"):
        flash_attention(q, q, q, window=4, causal=False)


# ----------------------------------------------------------------------
# rope, the gate, the types
# ----------------------------------------------------------------------


def test_yarns_table_is_the_formulas():
    """The published full layers' numbers: 64 rotated columns of 128,
    base 500,000, factor 64 over 4,096 original positions, beta 64 / 1:
    low 5, high 16."""
    rope = g.RopeConfig(500000.0, 64, g.YarnConfig(
        64.0, 4096, 64.0, 1.0, 1.4158883083359672))
    freqs, factor = g.rope_table(rope, 128)
    assert freqs.shape == (32,) and freqs.dtype == np.float32
    c = lambda r: 64 * math.log(4096 / (2 * math.pi * r)) / (
        2 * math.log(500000))
    assert (math.floor(c(64)), math.ceil(c(1))) == (5, 16)
    f = 500000.0 ** (-np.arange(32) / 32)
    ramp = np.clip((np.arange(32) - 5) / 11, 0, 1)
    np.testing.assert_allclose(freqs, f / 64 * ramp + f * (1 - ramp),
                               rtol=1e-6)
    np.testing.assert_array_equal(freqs[:6], f[:6].astype(np.float32))
    np.testing.assert_allclose(freqs[16:], f[16:] / 64, rtol=1e-6)
    assert factor == 1.4158883083359672
    assert g.rope_table(g.RopeConfig(500000.0, 64, g.YarnConfig(64.0, 4096)),
                        128)[1] == pytest.approx(0.1 * math.log(64) + 1)
    # the reference's table, from its own copy of the formulas
    want, want_factor = ref.rope_frequencies(
        64, 500000.0, (64.0, 4096, 64.0, 1.0, 1.4158883083359672))
    np.testing.assert_array_equal(freqs, want)
    assert factor == want_factor
    # a plain rope is theta^(-2i/d) over the whole head
    plain, one = g.rope_table(g.RopeConfig(10000.0), 128)
    np.testing.assert_allclose(plain, 10000.0 ** (-np.arange(64) / 64),
                               rtol=1e-6)
    assert one == 1.0 and g.RopeConfig(10000.0).plain and not rope.plain
    with pytest.raises(ValueError, match="rotated columns"):
        g.rope_table(g.RopeConfig(1e4, 256), 128)


def test_partial_rotary_turns_the_first_columns_and_passes_the_rest():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 16))
    pos = jnp.arange(9)
    freqs, _ = g.rope_table(g.RopeConfig(1e4, 8), 16)
    got = g.rope_by_table(x, pos, freqs, 1.5)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    # the rotated part is `rope` over those columns alone, times the factor
    np.testing.assert_allclose(
        got[..., :8], 1.5 * g.rope(x[..., :8], pos, 1e4), atol=1e-6)
    # per-example positions (continuous batching)
    each = jnp.stack([pos, pos + 5])
    np.testing.assert_allclose(
        g.rope_by_table(x, each, freqs)[1],
        g.rope_by_table(x[1:], pos + 5, freqs)[0], atol=1e-6)
    np.testing.assert_allclose(
        got[0], ref.rope_half(x[0], freqs, 1.5), atol=1e-6)


def test_heads_rope_window_and_gate_go_by_the_layers_type(model):
    params, cfg = model
    full, window = cfg.attn(0), cfg.attn(1)
    assert (full.n_heads, window.n_heads) == (6, 8)
    assert (full.window, window.window) == (None, W)
    assert full.rope.rotary_dim == 8 and full.rope.yarn.factor == 64.0
    assert window.rope.plain and window.rope.theta == 10000.0
    assert full.gate and window.gate and cfg.has_ring
    assert [cfg.layer_rows(i, 64) for i in range(5)] == [64, W, W, W, 64]
    assert cfg.layer_rows(1, 4) == 4  # a window past max_len: a full plane
    # layers of 6 and of 8 heads in one tree, the gate a number a head
    shapes = jax.tree.map(lambda x: x.shape, params)
    assert shapes["block_0"]["qkv"]["kernel"] == (64, (6 + 4) * 16)
    assert shapes["block_1"]["qkv"]["kernel"] == (64, (8 + 4) * 16)
    assert shapes["block_0"]["head_gate"]["kernel"] == (64, 6)
    assert shapes["block_1"]["proj"]["kernel"] == (8 * 16, 64)
    assert shapes == jax.tree.map(
        lambda x: x.shape, jax.eval_shape(lambda: lb.lm_spec_parts(SPEC)[0]))
    # a config without typed layers answers with its one type
    _, dense = lb.lm_spec_parts({"vocab_size": 64, "d_model": 32,
                                 "n_heads": 4, "n_layers": 2})
    assert dense.attn(1) == g.AttentionType(4, g.RopeConfig(10000.0))
    assert not dense.has_ring and dense.layer_rows(1, 99) == 99


def test_the_gate_scales_each_heads_output(model):
    """A gate matrix of zeros is sigmoid(0) = a half on every head: the
    attention's contribution is halved, exactly as the ungated layer's."""
    params, cfg = model
    blk = params["block_1"]
    y = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 64))
    attn = lambda q, k, v: jnp.repeat(v, 4, axis=2) + q  # any closure
    run = lambda b, lay: g._attention(b, cfg, y, jnp.arange(5), attn,
                                      lay=lay)[0]
    typ = cfg.attn(1)
    ungated = g.AttentionType(typ.n_heads, typ.rope, typ.window, gate=False)
    with jax.default_matmul_precision("highest"):
        halves = run({**blk, "head_gate": {
            "kernel": jnp.zeros((64, 8))}}, typ)
        plain = run(blk, ungated)
        np.testing.assert_allclose(halves, 0.5 * plain, atol=1e-5)
        # one head's gate shut: as if that head's output were zeros
        shut = jnp.zeros_like(blk["head_gate"]["kernel"])
        got = run({**blk, "head_gate": {"kernel": shut.at[:, 3].set(
            -1e3 * jnp.sign(y[0, 0]))}}, typ)[0, 0]
        proj = blk["proj"]["kernel"].at[3 * 16:4 * 16].set(0.0)
        want = run({**blk, "proj": {"kernel": proj}, "head_gate": {
            "kernel": shut}}, typ)[0, 0]
        np.testing.assert_allclose(got, want, atol=1e-5)


# ----------------------------------------------------------------------
# the share test
# ----------------------------------------------------------------------


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Sixteen routed experts in four shares of four: the program's
    expert layer of each share, the shared expert counted ONCE, adds up to
    the reference's layer with every expert held."""
    whole = {**SPEC, "experts_held": [0, 16]}
    moe = ref.make_params(whole, 11)["block_2"]["moe"]
    y = jax.random.normal(jax.random.PRNGKey(2), (1, 40, 64))
    dims = ref._dims(whole)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(y[0], moe, dims, "f32")
        shared = want - ref.experts(y[0], moe, dims, "f32", shared=False)
        total, counts = 0.0, 0
        for first in (0, 4, 8, 12):
            share = {**moe, **{w: moe[w][first:first + 4]
                               for w in ("w_up", "w_gate", "w_down")}}
            out, n = g.expert_ffn(
                share, y, jnp.float32, 4, first, scoring="sigmoid",
                scale=2.5)
            total = total + out[0] - shared  # each share adds it; keep one
            counts = counts + n
        total = total + shared
    np.testing.assert_allclose(total, want, atol=F32_TOL)
    assert int(counts.sum()) == 4 * 40 * 4  # every share counts all routed


# ----------------------------------------------------------------------
# refusals
# ----------------------------------------------------------------------


@pytest.mark.parametrize("change,match", [
    ({"kv_quant": True}, "ring of rows is cached unquantized"),
    ({"attention": "latent", "latent_attention": {
        "q_lora_rank": 8, "kv_lora_rank": 8, "qk_nope_head_dim": 8,
        "qk_rope_head_dim": 8, "v_head_dim": 8}}, "latent_attention under"),
    ({"attention_mask": "block_causal", "block_length": 4,
      "denoising_steps": 2, "mask_token_id": 0}, "denoising_steps under"),
    ({"attention_mask": "block_causal", "block_length": 4}, "causal mask"),
    ({"layer_pattern": "*E*E*"}, "layer_pattern under"),
    ({"attention_layers": {"layers": ["full"] * 5, "types": {
        "full": {"conv_kernel": 3, "n_heads": 6}}}}, "no other key"),
    ({"rope_pairing": "interleaved"}, "rope in halves"),
    ({"rope": "none"}, "rope in halves"),
    ({"n_kv_heads": None}, "n_kv_heads says"),
    ({"n_kv_heads": 4}, "4 KV heads do not divide"),
    ({"n_layers": 4}, "names 5 layers"),
    ({"attention_layers": {"layers": ["full"] * 5}}, "`layers`"),
    ({"attention_layers": {"layers": ["full"] * 4 + ["ring"],
                           "types": TYPES}}, "names 5 layers of types"),
    ({"attention_layers": {"layers": ["full"] * 5, "types": {
        "full": {"rope": {}}}}}, "n_heads, and of"),
    ({"attention_layers": {"layers": ["full"] * 5, "types": {
        "full": {"n_heads": 6, "heads": 6}}}}, "no other key"),
    ({"attention_layers": {"layers": ["full"] * 5, "types": {
        "full": {"n_heads": 6, "gate": "per_token"}}}}, "gate 'per_token'"),
    ({"attention_layers": {"layers": ["full"] * 5, "types": {
        "full": {"n_heads": 6, "rope": {"base": 1.0}}}}}, "rope"),
    ({"attention_layers": {"layers": ["full"] * 5, "types": {
        "full": {"n_heads": 6, "rope": {"yarn": {"factor": 2.0}}}}}}, "yarn"),
    ({"attention_layers": {"layers": ["full"] * 5, "types": {
        "full": {"n_heads": 6, "rope": {"rotary_dim": 32}}}}},
     "32 rotated columns in a head of 16"),
    ({"attention_layers": {"layers": ["full"] * 5, "types": {
        "full": {"n_heads": 6, "window": 0}}}}, "attention layer type"),
])
def test_lm_arch_raises_on_what_it_cannot_honour(change, match):
    with pytest.raises(ValueError, match=match):
        lb.lm_spec_parts({**SPEC, **change})


def test_what_needs_a_row_a_token_refuses_the_ring(model):
    """The prefix cache (a cut of rows by token), `submit_prefilled` and
    the prefill worker's slab (rows by token), speculation (a rejected
    draft's rows rolled back) and the sharded forms refuse at
    construction; nothing is extended to the ring here."""
    from dml_tpu.inference.kv_cache import KVPrefixCache
    from dml_tpu.inference.lm_sharded import LMPrefillBackend

    params, cfg = model
    srv = LMServer(params, cfg, max_slots=2, max_len=64, chunk=4)
    with pytest.raises(ValueError, match="cache a ring"):
        srv.enable_kv_cache(KVPrefixCache(1 << 20))
    with pytest.raises(ValueError, match="cache a ring"):
        srv.enable_spec_decode(2)
    with pytest.raises(ValueError, match="cache a ring"):
        srv.submit_prefilled(_tokens(4), 4, {}, 0)
    with pytest.raises(ValueError, match="window layer's ring"):
        LMPrefillBackend(params, cfg, 64)
    with pytest.raises(ValueError, match="cache a ring"):
        lb.LMBackend.from_spec({**SPEC, "max_len": 64, "kv_cache_mb": 1})
    # typed layers WITHOUT a window could be cut by token; what cuts them
    # reads one head count for the stack, so it is refused with the others
    types = {"a": {"n_heads": 6}, "b": {"n_heads": 8}}
    _, flat = lb.lm_spec_parts({**SPEC, "attention_layers": {
        "layers": ["a", "b", "b", "b", "a"], "types": types}})
    assert not flat.has_ring
    tree = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: lb.lm_spec_parts({
            **SPEC, "attention_layers": {
                "layers": ["a", "b", "b", "b", "a"], "types": types}})[0]))
    srv = LMServer(tree, flat, max_slots=2, max_len=64, chunk=4)
    with pytest.raises(ValueError, match="go by type"):
        srv.enable_spec_decode(2)
    # under a mesh: no rule divides 6 and 8 heads over tp
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match="one device"):
        g.prefill(params, cfg, jnp.zeros((1, 8), jnp.int32), 64, mesh=mesh)


def test_a_config_refuses_what_the_spec_refuses():
    typed = g.AttentionLayers(
        (("w", g.AttentionType(4, window=8)),), ("w", "w"))
    base = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                n_kv_heads=2, attention_layers=typed)
    assert g.LMConfig(**base).has_ring
    for change, match in (
            ({"kv_quant": True}, "no kv_quant"),
            ({"rope_pairing": "interleaved"}, "grouped attention under"),
            ({"rope": False}, "grouped attention under"),
            ({"n_layers": 3}, "names 2 layers"),
            ({"n_kv_heads": 3}, "3 KV heads do not divide"),
    ):
        with pytest.raises(ValueError, match=match):
            g.LMConfig(**{**base, **change})
    with pytest.raises(ValueError, match="of types"):
        g.AttentionLayers((("w", g.AttentionType(4)),), ("w", "x"))


# ----------------------------------------------------------------------
# bytes, spans, counters
# ----------------------------------------------------------------------


def test_a_window_layers_bytes_do_not_grow_with_max_len(model):
    _, cfg = model
    short, long_ = g.init_cache(cfg, 3, 64), g.init_cache(cfg, 3, 256)
    row = 2 * 2 * 16 * 4  # K and V, 2 KV heads of 16, float32
    assert g.state_bytes(short) == {
        "kv": 2 * 3 * 64 * row, "kv_window": 3 * 3 * W * row,
        "latent": 0, "conv": 0, "scan": 0}
    assert g.state_bytes(long_)["kv"] == 4 * g.state_bytes(short)["kv"]
    assert g.state_bytes(long_)["kv_window"] == 3 * 3 * W * row
    assert g.cache_rows(long_) == 256
    assert [g.cache_rows(long_, f"block_{i}") for i in range(5)] == [
        256, W, W, W, 256]
    assert set(long_["block_1"]) == {"k_ring", "v_ring"}
    assert g.decode_block_rows(cfg, 256) is None  # the einsum route


def test_block_rows_go_by_the_layers_plane(model, monkeypatch):
    _, cfg = model
    monkeypatch.setattr(g, "uses_decode_kernel", lambda: True)
    big = g.LMConfig(
        vocab_size=64, d_model=2048, n_heads=48, n_layers=4, d_ff=64,
        n_kv_heads=8, d_head=128, attention_layers=g.AttentionLayers(
            (("f", g.AttentionType(48)),
             ("w", g.AttentionType(64, window=512))), ("f", "w", "w", "w")))
    # 1 MB of copies a step: 256 rows at 8 KV heads of 128 in bfloat16,
    # which divides the ring's 512 rows
    assert g.decode_block_rows(big, 4096, layer=0) == 256
    assert g.decode_block_rows(big, 4096, layer=1) == 256
    assert g.decode_block_rows(cfg, 64, layer=1) == W  # never past the plane


def test_spans_and_counters_tell_the_layer_types_apart(model):
    params, cfg = model
    rows = METRICS.counter("lm_server_decode_kv_rows_total")
    read = lambda: {(k, t): rows.value(kind=k, layers=t)
                    for k in ("live", "read", "grid") for t in (
                        "full", "window")}
    before = read()
    TRACER.reset()
    srv, _ = _serve(params, cfg, [_tokens(10)], [9], slots=2)
    state = METRICS.gauge("lm_server_state_bytes")
    row = 2 * 2 * 16 * 4
    assert state.value(kind="kv") == 2 * 2 * 64 * row
    assert state.value(kind="kv_window") == 3 * 2 * W * row
    steps = TRACER.loop_spans("lm_step")
    # a slot at its i-th step attends prompt + i rows of a full layer and
    # min(prompt + i, W) of a window layer; two dispatches of 4 steps
    # deliver tokens 2..9
    full = sum(10 + i for i in range(1, 9))
    assert sum(s["lb"]["kv_rows_live"] for s in steps) == full
    assert sum(s["lb"]["kv_window_rows_live"] for s in steps) == 8 * W
    delta = {k: v - before[k] for k, v in read().items()}
    assert delta[("live", "full")] == full
    assert delta[("live", "window")] == 8 * W
    # the einsum route streams every row of every plane: the grid
    assert delta[("read", "full")] == delta[("grid", "full")] == 8 * 2 * 64
    assert delta[("read", "window")] == delta[("grid", "window")] == 8 * 2 * W
    assert srv._kv_layers == (("full", 0), ("window", 1))
    # the band's skipped share of k-blocks, on every prefill group's span
    # (one block here: nothing to skip); absent without a window layer
    groups = TRACER.loop_spans("lm_prefill_group")
    assert groups and all(s["lb"]["band_skipped"] == 0.0 for s in groups)
    srv.cfg = g.LMConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=64,
        n_kv_heads=2, attention_layers=g.AttentionLayers(
            (("w", g.AttentionType(4, window=512)),), ("w",)))
    assert srv._band_label(4096)["band_skipped"] == round(1 - 45 / 136, 4)
    assert srv._band_label(512)["band_skipped"] == 0.0
    # the resident tree holds the gate with the other matrices
    span = TRACER.loop_spans("lm_weights_resident")[-1]
    assert span["lb"]["resident_bytes"] == sum(
        x.nbytes for x in jax.tree.leaves(srv.params))


def test_a_prefill_groups_span_says_how_the_band_engages(model):
    """Beside `band_skipped`: `band_masked`, the share of the k-blocks a
    window layer's kernel visits that an edge crosses (under 1: the band
    has an inside, which builds no mask), and `kv_group`, the query heads
    that share a copy of a K and V block; all three the kernel's own
    arithmetic at its own blocks, and none without a window layer."""
    params, cfg = model
    TRACER.reset()
    srv, _ = _serve(params, cfg, [_tokens(10)], [3], slots=2)
    lb_ = TRACER.loop_spans("lm_prefill_group")[-1]["lb"]
    # one block of the bucket's rows: the diagonal crosses it
    assert (lb_["band_skipped"], lb_["band_masked"], lb_["kv_group"]) == (
        0.0, 1.0, 4)
    # laguna's window layers: 64 heads over 8, W 512, pieces of 256 rows
    srv.cfg = g.LMConfig(
        vocab_size=64, d_model=8192, n_heads=64, n_layers=1, d_ff=64,
        n_kv_heads=8, attention_layers=g.AttentionLayers(
            (("w", g.AttentionType(64, window=512)),), ("w",)))
    assert srv._band_label(4096) == {
        "band_skipped": round(1 - 45 / 136, 4),
        "band_masked": round(30 / 45, 4), "kv_group": 8}
    assert srv._band_label(1024) == {
        "band_skipped": round(1 - 9 / 10, 4),
        "band_masked": round(6 / 9, 4), "kv_group": 8}
    assert srv._band_label(4096)["band_masked"] < 1
    srv.cfg = g.LMConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=1,
                         d_ff=64, n_kv_heads=2)
    assert srv._band_label(4096) == {}


def _flash_calls(fn, *args):
    """(q heads, k heads) of every flash kernel call in fn's JAXPR and
    whether the trace holds a `jnp.repeat`'s gather of K."""
    calls = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                q, k = (v.aval.shape for v in eqn.invars[:2])
                # [B, H, T, D], or [B, KV, group, T, D] by KV head
                calls.append((q[1] * q[2] if len(q) == 5 else q[1], k[1]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return calls


def test_typed_layers_hand_k_and_v_by_kv_head_and_untyped_ones_repeat(model):
    """`generate.prefill` of a stack whose layers have a type calls the
    flash kernel with K and V as `_apply_block` returned them (2 KV heads
    under 6 and 8 query heads: no repeat); a stack without types still
    repeats K and V to the query heads and calls the kernel it called."""
    params, cfg = model
    toks = jnp.asarray(_tokens(16)[None])
    typed = _flash_calls(lambda p: g.prefill(p, cfg, toks, 32)[0], params)
    assert typed == [(6, 2), (8, 2), (8, 2), (8, 2), (6, 2)]
    plain = g.LMConfig(vocab_size=256, d_model=64, n_heads=8, n_layers=2,
                       d_ff=96, n_kv_heads=2)
    from dml_tpu.models.transformer import TransformerLM

    lm = TransformerLM(vocab_size=256, d_model=64, n_heads=8, n_layers=2,
                       d_ff=96, n_kv_heads=2)
    pp = lm.init(jax.random.PRNGKey(0), toks)["params"]
    assert _flash_calls(
        lambda p: g.prefill(p, plain, toks, 32)[0], pp) == [(8, 8), (8, 8)]


def test_the_gate_is_resident_in_the_compute_dtype_and_quantizes():
    from dml_tpu.inference.quantize import (quantize_lm_params,
                                            resident_params)

    params = ref.make_params(SPEC, 1)
    held = resident_params(params, jnp.bfloat16)
    assert held["block_1"]["head_gate"]["kernel"].dtype == jnp.bfloat16
    q = quantize_lm_params(params)["block_1"]["head_gate"]["kernel"]
    assert q["q"].dtype == jnp.int8 and q["scale"].shape == (1, 8)


def test_kv_rows_by_hand_on_the_kernel_route(model, monkeypatch):
    """`_kv_rows` of a window layer at a block of 4 rows: two slots at
    lengths 5 and 21, a chunk of 4 steps."""
    params, cfg = model
    monkeypatch.setattr(ls, "decode_block_rows", lambda *a: 4)
    srv = LMServer(params, cfg, max_slots=3, max_len=64, chunk=4)

    class Req:
        def __init__(self, n, emitted):
            self.prompt, self.emitted = np.zeros(n), emitted

    srv._slot_req = [Req(4, 1), None, Req(20, 1)]
    # full layer: lengths 5..8 and 21..24
    live = sum(range(5, 9)) + sum(range(21, 25))
    read = (8 + 8 + 8 + 8) + (24 + 24 + 24 + 24)
    assert srv._kv_rows(0) == (live, read, 4 * 3 * 64, 2 * 4 + 6 * 4)
    # window layer: min(length, 8) rows of a ring of 8
    assert srv._kv_rows(1) == (5 + 6 + 7 + 8 + 4 * 8,
                               4 * 8 + 4 * 8, 4 * 3 * W, 4 * 2 + 4 * 2)
