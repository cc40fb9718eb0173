"""Latent attention (MLA) on the serving path, CPU, small sizes, seeded
weights: one narrow cache row a token, the expanded form at prefill and the
absorbed form against the cache, under gated experts with a gated shared
expert after a leading gated dense layer. The oracle is the benchmark's
plain reference (`benchmark/references/joyai_mla_moe.py`: float32 at
HIGHEST, the expanded form at every position, no cache), the ONE copy that
the cell's `correct` imports too."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import manifest as mf
from dml_tpu.inference import generate as g
from dml_tpu.inference import lm_backend as lb
from dml_tpu.inference.lm_server import LMServer
from dml_tpu.observability import METRICS
from dml_tpu.tracing import TRACER

ref = mf.load_module("references", "joyai_mla_moe")
HI = jax.lax.Precision.HIGHEST

LATENT = {"q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
          "qk_rope_head_dim": 8, "v_head_dim": 16}
SPEC = {
    "vocab_size": 256, "d_model": 64, "n_heads": 4, "n_layers": 3, "d_ff": 96,
    "attention": "latent", "latent_attention": LATENT,
    "rope_theta": 32000000.0, "rope_pairing": "interleaved", "norm_eps": 1e-6,
    "num_experts": 16, "experts_per_token": 4, "expert_d_ff": 24,
    "gated": True, "experts_held": [0, 4],
    "router": {"scoring": "sigmoid", "bias": True, "scale": 2.5},
    "shared_expert_d_ff": 24, "dense_layers": 1, "activation": "silu",
    "dtype": "float32", "param_dtype": "float32",
}
#: float32 against float32 at HIGHEST: what is left is the order of the
#: sums (flash blocks, the absorbed form's other association, the grouped
#: matmul). Measured 2e-6 to 4e-6 on logits of spread ~1; bfloat16 reads
#: 1e-2 and more (the control below)
F32_TOL = 5e-5


@pytest.fixture(scope="module")
def model():
    _, cfg = lb.lm_spec_parts(SPEC)
    return ref.make_params(SPEC, 7), cfg


def _tokens(n, seed=0):
    return np.random.RandomState(seed).randint(0, 256, n).astype(np.int32)


def _through_the_cache(params, cfg, toks, split):
    """Logits of positions split-1 .. len-1: prefill (expanded), then one
    decode step a token (absorbed) against the cached rows."""
    lg, cache = g.prefill(params, cfg, jnp.asarray(toks[None, :split]), 64)
    out = [np.asarray(lg[0])]
    for t in range(split, len(toks)):
        lg, cache = g.batched_decode_step(
            params, cfg, cache, jnp.asarray(toks[t:t + 1]), jnp.asarray([t]))
        out.append(np.asarray(lg[0]))
    return np.stack(out)


def test_prefill_then_decode_equals_the_references_one_full_forward(model):
    params, cfg = model
    toks = _tokens(40)
    want = ref.logits_rows(params, SPEC, toks, 0, 40, pad_to=40)
    with jax.default_matmul_precision("highest"):
        got = _through_the_cache(params, cfg, toks, 24)
    assert np.abs(got - want[23:]).max() < F32_TOL
    # the control: the same program in bfloat16 is far outside it
    _, low = lb.lm_spec_parts({**SPEC, "dtype": "bfloat16"})
    assert np.abs(_through_the_cache(params, low, toks, 24)
                  - want[23:]).max() > 100 * F32_TOL


def test_the_absorbed_form_equals_the_expanded_form(model):
    """Every position through the multi-token cached step from an empty
    cache (absorbed: the key up-projection folded into the query, the
    value up-projection after attention) against the prefill of the same
    tokens and against the reference (both expanded)."""
    params, cfg = model
    toks = _tokens(32, seed=1)
    with jax.default_matmul_precision("highest"):
        absorbed, cache_a = g.batched_block_step(
            params, cfg, g.init_cache(cfg, 1, 64), jnp.asarray(toks[None]),
            jnp.asarray([0]))
        last, cache_e = g.prefill(params, cfg, jnp.asarray(toks[None]), 64)
    want = ref.logits_rows(params, SPEC, toks, 0, 32, pad_to=32)
    assert np.abs(np.asarray(absorbed[0]) - want).max() < F32_TOL
    assert np.abs(np.asarray(last[0]) - want[-1]).max() < F32_TOL
    # both forms cache the same rows
    for name in cache_a:
        np.testing.assert_allclose(
            np.asarray(cache_a[name]["latent"][:, :, :32]),
            np.asarray(cache_e[name]["latent"][:, :, :32]), atol=1e-5)


def test_rope_in_pairs_is_the_published_rule():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 8))
    pos = jnp.arange(9)
    want = jnp.stack([ref.rope_pairs(x[b], 32000000.0) for b in range(2)])
    np.testing.assert_allclose(
        g.rope_interleaved(x, pos, 32000000.0), want, atol=1e-6)
    # per-example positions (continuous batching), and exact in bfloat16
    each = jnp.stack([pos, pos + 5])
    got = g.rope_interleaved(x, each, 1e4)
    np.testing.assert_allclose(got[0], g.rope_interleaved(x[:1], pos, 1e4)[0],
                               atol=1e-6)
    assert g.rope_interleaved(x.astype(jnp.bfloat16), pos).dtype == jnp.bfloat16


def _serve(params, cfg, prompts, budget=12, **kw):
    srv = LMServer(params, cfg, max_slots=4, max_len=64, chunk=4, **kw)
    rids = [srv.submit(p, budget) for p in prompts]
    done = srv.run()
    return srv, [done[r] for r in rids]


def _alone(params, cfg, prompt, n=12):
    return np.asarray(g.generate(params, cfg, jnp.asarray(prompt[None]), n))[0]


def test_a_padded_group_with_riders_and_a_reused_slot_equal_prompts_alone(
        model):
    """Seven prompts over four slots: the first wave is one padded group
    whose short prompts ride beside long ones; the second wave takes slots
    that LONGER occupants left (an insert writes the new rows alone, so
    what lies past them is the last occupant's)."""
    params, cfg = model
    prompts = [_tokens(n, seed=n) for n in (44, 9, 40, 17, 5, 12, 7)]
    TRACER.reset()
    _, got = _serve(params, cfg, prompts)
    for p, toks in zip(prompts, got):
        np.testing.assert_array_equal(toks, _alone(params, cfg, p))
    groups = TRACER.loop_spans("lm_prefill_group")
    assert groups and all(s["lb"]["attn"] == "expanded" for s in groups)
    assert any(s["lb"]["rows"] > 1 for s in groups)
    steps = TRACER.loop_spans("lm_step")
    assert steps and all(s["lb"]["attn"] == "absorbed" for s in steps)


def test_spans_and_counters_count_latent_rows(model):
    params, cfg = model
    live0 = METRICS.counter("lm_server_decode_kv_rows_total").value(
        kind="live", layers="full")
    TRACER.reset()
    prompt = _tokens(10)
    srv, _ = _serve(params, cfg, [prompt], budget=9)
    # 4 slots x 64 rows x 3 layers x one row of 128 columns (40 values
    # and zeros to a lane tile) x 4 bytes
    assert METRICS.gauge("lm_server_state_bytes").value(
        kind="latent") == 4 * 64 * 3 * 128 * 4
    assert METRICS.gauge("lm_server_state_bytes").value(kind="kv") == 0
    steps = TRACER.loop_spans("lm_step")
    # one row a token a layer: a slot at its i-th step attends prompt + i
    # rows; two dispatches of 4 steps deliver tokens 2..9
    want = sum(10 + i for i in range(1, 9))
    assert sum(s["lb"]["kv_rows_live"] for s in steps) == want
    assert METRICS.counter("lm_server_decode_kv_rows_total").value(
        kind="live", layers="full") - live0 == want
    assert all(s["lb"]["kv_rows_read"] >= s["lb"]["kv_rows_live"]
               for s in steps)
    span = TRACER.loop_spans("lm_weights_resident")[-1]
    assert span["lb"]["resident_bytes"] == sum(
        x.nbytes for x in jax.tree.leaves(srv.params))
    assert METRICS.counter("moe_assignments_total").value(where="held") > 0


def test_the_cache_holds_one_narrow_row_a_token():
    _, cfg = lb.lm_spec_parts({**SPEC, "dtype": "bfloat16"})
    cache = g.init_cache(cfg, 4, 64)
    assert set(cache) == {"block_0", "block_1", "block_2"}
    leaf = cache["block_1"]["latent"]
    assert cfg.latent.row_width == 32 + 8 and cfg.latent.row_stride == 128
    assert leaf.shape == (4, 1, 64, 128) and leaf.dtype == jnp.bfloat16
    assert g.cache_rows(cache) == 64
    assert g.state_bytes(cache) == {
        "kv": 0, "kv_window": 0, "latent": 3 * 4 * 64 * 128 * 2,
        "conv": 0, "scan": 0}
    # the published widths: 576 values a token a layer, in 640 columns
    big = g.LatentConfig(1536, 512, 128, 64, 128)
    assert (big.row_width, big.row_stride, big.key_width) == (576, 640, 192)
    with pytest.raises(KeyError):
        g.state_bytes({"block_0": {"rows": leaf}})  # no guessing by name


def _plain_latent_attention(q, plane, lengths, scale, v_width, mask_block=1):
    """[B, Q, H, W] queries over [B, 1, T, W] rows, numpy, float64."""
    b, n_q, h, _ = q.shape
    t = plane.shape[2]
    out = np.zeros((b, n_q, h, v_width))
    for bi in range(b):
        for i in range(n_q):
            limit = lengths[bi] - (n_q - (i // mask_block + 1) * mask_block)
            if lengths[bi] <= 0 or limit <= 0:
                continue
            rows = plane[bi, 0, :min(limit, t)].astype(np.float64)
            s = q[bi, i].astype(np.float64) @ rows.T * scale
            p = np.exp(s - s.max(-1, keepdims=True))
            out[bi, i] = (p / p.sum(-1, keepdims=True)) @ rows[:, :v_width]
    return out


@pytest.mark.parametrize("n_q,mask_block", [(1, 1), (4, 1), (4, 4)])
def test_the_kernel_reads_one_plane_as_keys_and_values(n_q, mask_block):
    """The Pallas kernel (interpret mode) over a shared plane against a
    plain softmax: ragged lengths, a full slot, an EMPTY slot (zeros,
    whatever its rows hold), several query rows a slot."""
    from dml_tpu.ops.decode_attention import decode_attention

    b, h, w, vw, t = 5, 4, 128, 96, 300
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    q = jax.random.normal(ks[0], (b, n_q, h, w))
    plane = jax.random.normal(ks[1], (b, 1, t, w))
    plane = plane.at[3].set(jnp.nan)  # the empty slot's rows are never read
    lengths = jnp.asarray([7, 300, 131, 0, 64])
    got = decode_attention(q, plane, None, lengths, scale=0.11, v_width=vw,
                           block_k=128, interpret=True, mask_block=mask_block)
    want = _plain_latent_attention(
        np.asarray(q), np.asarray(plane), np.asarray(lengths), 0.11, vw,
        mask_block)
    assert got.shape == (b, n_q, h, vw)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert not np.asarray(got[3]).any()
    with pytest.raises(ValueError, match="v_width"):
        decode_attention(q, plane, None, lengths, scale=0.11)
    with pytest.raises(ValueError, match="shared plane"):
        decode_attention(q, plane, None, lengths, v_width=vw)


def test_the_cached_step_takes_the_kernel_where_it_is_told_to(
        model, monkeypatch):
    """`batched_decode_step` and `batched_block_step` on the kernel route
    (interpret mode here) equal the einsum oracle, an empty slot among
    them."""
    params, cfg = model
    toks = _tokens(3 * 20).reshape(3, 20)
    with jax.default_matmul_precision("highest"):
        _, cache = g.prefill(params, cfg, jnp.asarray(toks), 64)
        cur, pos = jnp.asarray(toks[:, 0]), jnp.asarray([20, 20, 20])
        lengths = jnp.asarray([21, 0, 21])
        want, _ = g.batched_decode_step(params, cfg, cache, cur, pos,
                                        lengths=lengths)
        blk = jnp.asarray(toks[:, :4])
        want4, _ = g.batched_block_step(params, cfg, cache, blk, pos)
        monkeypatch.setattr(g, "uses_decode_kernel", lambda: True)
        got, _ = g.batched_decode_step(params, cfg, cache, cur, pos,
                                       lengths=lengths)
        got4, _ = g.batched_block_step(params, cfg, cache, blk, pos)
    np.testing.assert_allclose(got[jnp.asarray([0, 2])],
                               want[jnp.asarray([0, 2])], atol=F32_TOL)
    np.testing.assert_allclose(got4, want4, atol=F32_TOL)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_with_values_narrower_than_keys(causal):
    from dml_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (2, 70, 3, 24))
    k = jax.random.normal(ks[1], (2, 70, 3, 24))
    v = jax.random.normal(ks[2], (2, 70, 3, 16))

    def plain(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * 24 ** -0.5
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((70, 70), bool)), s, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                          precision=HI)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=32,
                               block_k=32, interpret=True)

    got = flash(q, k, v)
    assert got.shape == (2, 70, 3, 16)
    np.testing.assert_allclose(got, plain(q, k, v), atol=2e-5)
    # the backward kernels carry the two widths too
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))
    for a, b in zip(jax.grad(loss(flash), (0, 1, 2))(q, k, v),
                    jax.grad(loss(plain), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_gated_dense_mlp_and_gated_shared_expert_equal_the_reference(model):
    params, cfg = model
    m = ref._dims(SPEC)
    y = jax.random.normal(jax.random.PRNGKey(2), (1, 21, 64))
    with jax.default_matmul_precision("highest"):
        dense = g._feed_forward(params["block_0"], cfg, y, None, None)
        layer = g._feed_forward(params["block_1"], cfg, y, None, None)
    np.testing.assert_allclose(
        dense[0], ref.dense(y[0], params["block_0"], "f32"), atol=1e-5)
    np.testing.assert_allclose(
        layer[0], ref.experts(y[0], params["block_1"]["moe"], m, "f32"),
        atol=1e-5)
    # a dense MLP needs no experts beside it to be gated
    plain, _ = lb.lm_spec_parts({
        "vocab_size": 64, "d_model": 32, "n_heads": 2, "n_layers": 1,
        "d_ff": 48, "gated": True})
    assert set(plain["block_0"]) >= {"gate", "up", "down"}


def test_the_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_layer(
        model):
    """One expert layer cut sixteen ways (one routed expert a share, as
    sixteen chips would hold 16 of 256 each): the shares' routed parts,
    and the shared expert counted ONCE, add up to the reference's layer
    over all sixteen experts."""
    uncut = {**SPEC, "experts_held": [0, 16]}
    m = ref._dims(uncut)
    whole = ref.make_params(uncut, 11)["block_1"]["moe"]
    y = jax.random.normal(jax.random.PRNGKey(4), (1, 33, 64))
    want = ref.experts(y[0], whole, m, "f32")
    alone = ref.experts(y[0], whole, m, "f32") - ref.experts(
        y[0], whole, m, "f32", shared=False)
    routed = {k: v for k, v in whole.items() if not k.startswith("shared")}
    total = alone
    with jax.default_matmul_precision("highest"):
        for e in range(16):
            share = {**routed, **{w: whole[w][e:e + 1]
                                  for w in ("w_up", "w_gate", "w_down")}}
            out, counts = g.expert_ffn(
                share, y, jnp.float32, 4, first=e, scoring="sigmoid",
                scale=2.5)
            total = total + out[0]
            assert int(counts.sum()) == 33 * 4
    np.testing.assert_allclose(total, want, atol=2e-5)


# ----------------------------------------------------------------------
# the expert layer over windows of the rows it holds
# ----------------------------------------------------------------------


def _share(held, seed=11):
    """A gated expert layer of 16 routed experts top-4 with its gated
    shared expert, cut to the first `held` experts."""
    whole = ref.make_params({**SPEC, "experts_held": [0, 16]}, seed)
    moe = whole["block_1"]["moe"]
    return {**moe, **{w: moe[w][:held] for w in ("w_up", "w_gate", "w_down")}}


def _layer(moe, y, monkeypatch, window, **kw):
    """(out, counts, windows past the first or None) of `expert_ffn` with
    every call laid out over `window` rows (None: all its rows, the form
    without a loop; 0: the size its shapes give)."""
    if window != 0:
        monkeypatch.setattr(
            g, "moe_window",
            lambda n, k, e, held: n * k if window is None else window)
    past = []
    out, counts = g.expert_ffn(
        moe, y, jnp.float32, 4, scoring="sigmoid", scale=2.5, windows=past,
        **kw)
    monkeypatch.undo()
    return np.asarray(out), np.asarray(counts), (
        int(past[0]) if past else None)


def _assert_the_same_float32_sums(got, want):
    """Equal up to the ORDER of float32 additions: the loop adds a
    token's held terms in the sort's order (by expert), the form without
    a loop along the token's top-k list (measured 5e-7 on outputs of
    spread ~1; a sum carried in bfloat16 reads 1e-2)."""
    np.testing.assert_allclose(
        got, want, rtol=0, atol=4e-6 * max(1.0, np.abs(want).max()))


@pytest.mark.parametrize("live", [False, True])
@pytest.mark.parametrize("held", [1, 4, 16])
def test_windows_of_held_rows_give_the_one_windows_sums(
        monkeypatch, held, live):
    """Held shares 1/16, 1/4 and 1: windows of 16 rows against ONE window
    under the same loop, against the form without a loop over all 400
    rows, and against the size the shapes give (128 rows for a share, all
    rows where every expert is held). Not bit for bit: XLA's CPU
    `ragged_dot` rounds a row otherwise in a call of another row count."""
    moe = _share(held)
    y = jax.random.normal(jax.random.PRNGKey(6), (2, 50, 64))
    kw = {"live": jnp.asarray([True, False])} if live else {}
    want, counts, none = _layer(moe, y, monkeypatch, None, **kw)
    assert none is None and int(counts.sum()) == (50 if live else 100) * 4
    got, c16, past = _layer(moe, y, monkeypatch, 16, **kw)
    one, _, none = _layer(moe, y, monkeypatch, 399, **kw)
    assert none == (held == 16)  # all 400 rows held: a second window of 1
    _assert_the_same_float32_sums(got, one)
    _assert_the_same_float32_sums(got, want)
    np.testing.assert_array_equal(c16, counts)
    # the held assignments of ALL rows are computed, counted or not
    _, every, _ = _layer(moe, y, monkeypatch, None)
    assert past == max(-(-int(every[:held].sum()) // 16), 1) - 1
    assert past > 0
    by_shape, _, past = _layer(moe, y, monkeypatch, 0, **kw)
    _assert_the_same_float32_sums(by_shape, want)
    assert past == (None if held == 16 else 0)


@pytest.mark.parametrize("push,windows", [(10.0, 4), (-10.0, 1)])
def test_a_routing_forced_onto_or_off_the_held_experts_drops_nothing(
        monkeypatch, push, windows):
    """A selection bias that sends every token to the 4 held experts: 800
    held rows over windows of 256 (the size 200 tokens top-4 of 16 with 4
    held give) are four windows, every assignment computed. The opposite
    bias: no held row, one window of nothing, the shared expert alone."""
    moe = _share(4)
    bias = np.zeros(16, np.float32)
    bias[:4] = push
    moe = {**moe, "router": {**moe["router"], "bias": jnp.asarray(bias)}}
    y = jax.random.normal(jax.random.PRNGKey(8), (1, 200, 64))
    assert g.moe_layout(200, 4, 16, 4) == (1, 200, 256)
    want, counts, _ = _layer(moe, y, monkeypatch, None)
    got, c, past = _layer(moe, y, monkeypatch, 0)
    assert int(counts[:4].sum()) == (800 if push > 0 else 0)
    assert past == windows - 1
    _assert_the_same_float32_sums(got, want)
    np.testing.assert_array_equal(c, counts)
    # ... and both are the reference's layer over the held experts
    m = ref._dims({**SPEC, "experts_held": [0, 4]})
    np.testing.assert_allclose(
        got[0], ref.experts(y[0], moe, m, "f32"), atol=2e-5)
    if push < 0:
        routed = {k: v for k, v in moe.items() if not k.startswith("shared")}
        zeros, _, _ = _layer(routed, y, monkeypatch, 0)
        assert not zeros.any()


def test_the_window_is_a_size_taken_from_shapes():
    # the benchmark's cells: a 4,096-token prefill row and a decode step
    # of 16 slots at 16 of 256 experts top-8; a chunk of 1,408 tokens and
    # a decode step of 64 slots at 128 of 512 top-22; every expert held
    assert g.moe_layout(4096, 8, 256, 16) == (1, 4096, 2560)
    assert g.moe_layout(512, 8, 256, 16) == (1, 512, 384)
    assert g.moe_layout(16, 8, 256, 16) == (1, 16, 128)  # one tile: all
    assert g.moe_layout(2048, 22, 512, 128) == (2, 1408, 9728)
    assert g.moe_layout(64, 22, 512, 128) == (1, 64, 512)
    assert g.moe_layout(8 * 2048, 8, 128, 128) == (4, 4096, 32768)
    assert g.moe_layout(128, 8, 128, 128) == (1, 128, 1024)


def test_windows_are_counted_and_a_prefill_group_says_what_it_lays_out(
        model, monkeypatch):
    """Windows of 8 rows, so that the decode step (4 slots x top-4 = 16
    rows) loops too: the prefills' windows past the first ride the next
    packed readback, the decode steps' ride beside the routing's numbers."""
    params, cfg = model
    monkeypatch.setattr(
        g, "moe_window", lambda n, k, e, held: min(8, n * k))
    ran = METRICS.counter("moe_windows_total")
    before = {w: ran.value(kind=w) for w in ("first", "further")}
    TRACER.reset()
    prompts = [_tokens(n, seed=n) for n in (30, 9)]
    srv, got = _serve(params, cfg, prompts, budget=9)
    monkeypatch.undo()
    for p, toks in zip(prompts, got):
        np.testing.assert_array_equal(toks, _alone(params, cfg, p, 9))
    groups = TRACER.loop_spans("lm_prefill_group")
    steps = TRACER.loop_spans("lm_step")
    for s in groups:  # 2 expert layers top-4; one chunk a call
        assert s["lb"]["moe_rows"] == s["lb"]["padded_tokens"] * 4
        assert s["lb"]["moe_rows_laid"] == 8
    # a layer's first window: every prefill group, every decode step
    first = ran.value(kind="first") - before["first"]
    assert first == 2 * len(groups) + 2 * 4 * len(steps)
    # 4 of 16 experts held: about a quarter of the rows, in windows of 8
    further = ran.value(kind="further") - before["further"]
    rows = sum(s["lb"]["moe_rows"] for s in groups)
    assert rows / 4 / 8 * 2 * 0.3 < further < rows / 4 / 8 * 2 * 3
    assert int(np.asarray(srv._windows_dev)[0]) == 0  # all read


def test_the_int8_weight_path_serves_the_latent_tree(model):
    from dml_tpu.inference.quantize import quantize_lm_params

    params, cfg = model
    q = quantize_lm_params(params)
    assert set(q["block_1"]["w_uk"]) == {"q", "scale"}
    assert q["block_1"]["w_uk"]["scale"].shape == (4, 1, 16)
    toks = _tokens(12)
    lg, _ = g.prefill(q, cfg, jnp.asarray(toks[None]), 16)
    want, _ = g.prefill(params, cfg, jnp.asarray(toks[None]), 16)
    assert 1e-4 < np.abs(np.asarray(lg) - np.asarray(want)).max() < 0.5


@pytest.mark.parametrize("change,match", [
    ({"kv_quant": True}, "kv_quant under latent"),
    ({"qk_norm": True}, "qk_norm under latent"),
    ({"n_kv_heads": 2}, "n_kv_heads under latent"),
    ({"head_dim": 16}, "head_dim under latent"),
    ({"attention_mask": "block_causal", "block_length": 4,
      "denoising_steps": 1, "mask_token_id": 1}, "causal mask"),
    ({"rope": "none"}, "causal mask with rope"),
    ({"attention": "grouped"}, "come together"),
    ({"latent_attention": None}, "come together"),
    ({"latent_attention": {**LATENT, "head": 1}}, "exactly"),
    ({"latent_attention": {**LATENT, "qk_rope_head_dim": 7}}, "widths"),
    ({"latent_attention": {**LATENT, "v_head_dim": 64}}, "v_dim"),
    ({"attention": "sliding"}, "unknown attention"),
    ({"rope_pairing": "thirds"}, "unknown rope_pairing"),
    ({"dense_layers": -1}, "dense_layers"),
    ({"dense_layers": 1, "num_experts": 0, "experts_held": None,
      "router": None, "shared_expert_d_ff": 0}, "dense_layers"),
])
def test_lm_arch_refuses_what_it_cannot_honour(change, match):
    with pytest.raises(ValueError, match=match):
        lb.lm_spec_parts({**SPEC, **change})


def test_what_refuses_the_latent_leaf_says_so_at_construction(model):
    from dml_tpu.inference.kv_cache import KVPrefixCache
    from dml_tpu.inference.lm_sharded import LMPrefillBackend

    params, cfg = model
    srv = LMServer(params, cfg, max_slots=2, max_len=64, chunk=4)
    with pytest.raises(ValueError, match="latent attention's rows"):
        srv.enable_kv_cache(KVPrefixCache(1 << 20))
    with pytest.raises(ValueError, match="latent"):
        LMPrefillBackend(params, cfg, max_len=64)
    with pytest.raises(ValueError, match="latent"):
        lb.lm_spec_parts({**SPEC, "layer_pattern": "***"})


def test_a_prefilled_slab_of_latent_rows_is_adopted_like_any(model):
    """`submit_prefilled` is generic over leaves: a slab {block: {latent:
    [1, n, S]}} cut from a prefill's rows decodes to what a local prefill
    gives."""
    params, cfg = model
    prompt = _tokens(17, seed=17)
    lg, cache = g.prefill(params, cfg, jnp.asarray(prompt[None]), prompt.size)
    slab = {name: {k: np.asarray(v[0]) for k, v in lay.items()}
            for name, lay in cache.items()}
    srv = LMServer(params, cfg, max_slots=4, max_len=64, chunk=4)
    rid = srv.submit_prefilled(prompt, 12, slab, int(np.argmax(lg[0])))
    np.testing.assert_array_equal(
        srv.run()[rid], _alone(params, cfg, prompt))


def test_speculation_verifies_over_the_latent_leaf(model):
    """The verify step is the multi-token cached forward (absorbed, Q > 1
    rows a slot) and a rejected draft's rows are simply written again:
    greedy outputs are the plain path's."""
    from dml_tpu.config import draft_lm_spec

    params, cfg = model
    dp, dcfg = lb.lm_spec_parts(draft_lm_spec({**SPEC, "name": "t"}))
    prompts = [_tokens(n, seed=n) for n in (5, 17, 30, 9, 22)]
    srv = LMServer(params, cfg, max_slots=4, max_len=64, chunk=4)
    srv.enable_spec_decode(3, draft_params=dp, draft_cfg=dcfg)
    rids = [srv.submit(p, 12) for p in prompts]
    done = srv.run()
    assert srv.spec_stats()["rounds"] > 0
    for p, rid in zip(prompts, rids):
        np.testing.assert_array_equal(done[rid], _alone(params, cfg, p))


def test_a_mesh_is_refused(model):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    params, cfg = model
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    placed = jax.device_put(params, NamedSharding(mesh, P()))
    with pytest.raises(ValueError, match="one device"):
        LMServer(placed, cfg, max_slots=2, max_len=64, chunk=4)
