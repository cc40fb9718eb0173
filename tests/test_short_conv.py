"""A stack whose layers' operators go by TYPE with a type that is no
attention: the gated short convolution (the LFM2 family's operator)
beside grouped attention with q/k norms, under leading dense layers and
sigmoid-routed experts, a head tied to the embedding; on the serving path
(`LMBackend.from_spec` -> `LMServer` -> `LMDriver`), against the
benchmark's plain reference, loaded by its path as
`benchmark/harness/manifest.load_module` loads it. Small sizes, seeded
random weights, float32, on the CPU.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import manifest as mf  # noqa: E402
from dml_tpu.inference import generate as G  # noqa: E402
from dml_tpu.inference import lm_server as LS  # noqa: E402
from dml_tpu.inference.kv_cache import KVPrefixCache  # noqa: E402
from dml_tpu.inference.lm_backend import (  # noqa: E402
    LMBackend, lm_arch, lm_spec_parts)
from dml_tpu.observability import METRICS  # noqa: E402
from dml_tpu.ops.decode_attention import decode_attention  # noqa: E402
from dml_tpu.tracing import TRACER  # noqa: E402

REF = mf.load_module("references", "lfm2_conv_moe")
VOCAB = 97
TOL = 2e-4  # float32 programs of different shapes, logits of unit spread
LAYERS = ["conv", "conv", "full_attention", "conv", "full_attention"]


def _spec(layers=LAYERS, **over):
    return {
        "vocab_size": VOCAB, "d_model": 32, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 8, "n_layers": len(layers), "d_ff": 48,
        "attention_layers": {"layers": list(layers), "types": {
            "conv": {"conv_kernel": 3},
            "full_attention": {"n_heads": 4, "rope": {"theta": 1e6}}}},
        "qk_norm": True, "tied_head": True, "norm_eps": 1e-5,
        "dense_layers": 2, "num_experts": 8, "experts_per_token": 2,
        "expert_d_ff": 24, "gated": True, "experts_held": [2, 4],
        "router": {"scoring": "sigmoid", "bias": True, "scale": 1.0},
        "activation": "silu", "dtype": "float32", "param_dtype": "float32",
        "max_len": 64, "max_slots": 4, "max_new_tokens": 10, "chunk": 4,
        "seed": 5, **over,
    }


def _parts(spec, seed=11):
    """(the REFERENCE's weights in the tree the program declares, cfg):
    the trees must agree leaf for leaf, or `tree.map` raises."""
    params, cfg = lm_spec_parts(spec)
    assert (jax.tree.map(lambda x: tuple(x.shape), params)
            == REF.param_shapes(spec))
    return jax.tree.map(lambda x, d: x.astype(d.dtype),
                        REF.make_params(spec, seed), params), cfg


def _backend(spec):
    params, cfg = _parts(spec)
    be = LMBackend.from_spec(spec)
    be.server.params = params
    return be, params, cfg


def _prompts(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, n).astype(np.int32) for n in lengths]


def _serve(be, prompts, budgets):
    return [[int(t) for t in ts]
            for ts in be.driver.serve(prompts, budgets)]


def _assert_the_references_choice(spec, params, prompt, budget, got):
    assert len(got) == budget
    g = REF.served_gaps(params, spec, prompt, got, pad_to=64, rows_pad=32)
    assert g["gap_max"] <= TOL, g


# ----------------------------------------------------------------------
# the layer mathematics, against the reference's one plain pass
# ----------------------------------------------------------------------


@pytest.mark.parametrize("layers,head_dim", [
    (LAYERS, 8), (["full_attention", "conv", "conv"], 8),
    (["conv", "conv", "conv"], 8), (LAYERS, 64)])
def test_prefill_then_decode_gives_the_references_logits_everywhere(
        layers, head_dim):
    """A padded prefill over a prompt of 17 tokens (no multiple of
    anything), then one step a token through the windows and the rows:
    the logits at EVERY position are the reference's, which runs three
    shifted multiplies over the whole sequence and a full score matrix.
    Heads of 64 are cached two a row (`LMConfig.kv_pack`)."""
    spec = _spec(layers, head_dim=head_dim)
    params, cfg = _parts(spec)
    assert cfg.has_state and cfg.has_conv and not cfg.has_ring
    assert cfg.kv_pack == (2 if head_dim == 64 else 1)
    toks = _prompts([30], seed=3)[0]
    n_prompt = 17
    ref = REF.logits_rows(params, spec, toks, 0, 30, pad_to=30)
    run = jax.jit(lambda x, i: G.prefill(params, cfg, x, 64, logits_index=i))
    for i in range(n_prompt):  # every prompt position, the pad behind it
        logits, cache = run(jnp.asarray(toks[None, :n_prompt]), jnp.int32(i))
        np.testing.assert_allclose(logits[0], ref[i], atol=TOL)
    step = jax.jit(lambda c, t, p: G.batched_decode_step(params, cfg, c, t, p))
    for pos in range(n_prompt, 30):
        logits, cache = step(cache, jnp.asarray(toks[pos:pos + 1]),
                             jnp.asarray([pos], jnp.int32))
        np.testing.assert_allclose(logits[0], ref[pos], atol=TOL)


@pytest.mark.parametrize("length", [1, 2, 3, 5, 9, 17])
def test_a_padded_rows_window_is_the_unpadded_runs(length):
    """A row padded to a bucket (with its last token, as the server pads)
    beside a longer row: with the rows' own lengths the prefill hands back
    the convolution window of the UNPADDED prompt (a prompt of 1 or 2
    tokens, shorter than the window, has zeros on its left), and the same
    K/V rows."""
    spec = _spec()
    params, cfg = _parts(spec)
    short, longer = _prompts([length, 24], seed=length)
    padded = np.stack([np.pad(short, (0, 24 - length), mode="edge"), longer])
    _, alone = G.prefill(params, cfg, jnp.asarray(short[None]), 64)
    logits, both = G.prefill(
        params, cfg, jnp.asarray(padded), 64,
        logits_index=jnp.asarray([length - 1, 23], jnp.int32))
    assert set(alone) == {f"block_{i}" for i in range(5)}
    for name, lay in alone.items():
        assert set(lay) == ({"conv"} if "short_conv" in params[name]
                            else {"k", "v"})
        for key, leaf in lay.items():
            got = both[name][key][0]
            if key in ("k", "v"):  # rows past the prompt hold the pad's
                got, leaf = got[:, :length], leaf[:, :, :length]
            np.testing.assert_allclose(got, leaf[0], atol=1e-5,
                                       err_msg=f"{name}.{key}")
    if length < 3:  # the window's left is the sequence's start: zeros
        np.testing.assert_array_equal(
            both["block_0"]["conv"][0, :3 - 1 - length], 0.0)
    ref = REF.logits_rows(params, spec, short, length - 1, 1, pad_to=24)
    np.testing.assert_allclose(logits[0], ref[0], atol=TOL)


def test_a_conv_layer_caches_its_window_and_nothing_else():
    spec = _spec()
    _, cfg = _parts(spec)
    cache = G.init_cache(cfg, 4, 64)
    assert cache["block_0"]["conv"].shape == (4, 2, 32)
    assert set(cache["block_0"]) == {"conv"}
    assert set(cache["block_2"]) == {"k", "v"}
    assert G.cache_rows(cache, "block_0") == 0
    assert G.cache_rows(cache, "block_2") == G.cache_rows(cache) == 64
    assert [cfg.layer_rows(i, 64) for i in range(5)] == [0, 0, 64, 0, 64]
    assert G.state_bytes(cache) == {
        "kv": 2 * 2 * 4 * 2 * 64 * 8 * 4, "kv_window": 0, "latent": 0,
        "conv": 3 * 4 * 2 * 32 * 4, "scan": 0}
    only = G.init_cache(lm_spec_parts(_spec(["conv", "conv"]))[1], 2, 64)
    assert G.cache_rows(only) == 0


def test_one_position_is_the_cached_rows_and_the_new_one():
    """`conv_mixer` over T positions at once, and a position at a time
    through the window it hands on: the same numbers."""
    spec = _spec()
    params, cfg = _parts(spec)
    p = params["block_0"]["short_conv"]
    y = jax.random.normal(jax.random.PRNGKey(2), (2, 7, 32))
    whole, end = G.conv_mixer(p, cfg, y)
    state, outs = None, []
    for t in range(7):
        out, state = G.conv_mixer(p, cfg, y[:, t:t + 1], state)
        outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), whole, atol=1e-5)
    np.testing.assert_allclose(state["conv"], end["conv"], atol=1e-6)
    want = REF.short_conv(y[0], p, {"d": 32}, "f32")
    np.testing.assert_allclose(whole[0], want, atol=1e-4)


def test_heads_of_64_share_a_cached_row_and_the_kernel_reads_them_so():
    """Two KV heads of 64 a row of 128: the decode kernel (interpreted
    here) over the packed planes gives what it gives over a plane a head,
    for live, short and empty slots."""
    al = G.AttentionLayers((("a", G.AttentionType(8)),), ("a",))
    cfg = G.LMConfig(
        vocab_size=11, d_model=32, n_heads=8, n_layers=1, d_ff=8,
        dtype=jnp.float32, n_kv_heads=4, d_head=64, attention_layers=al)
    assert cfg.kv_pack == 2
    assert dataclasses.replace(cfg, attention_layers=None).kv_pack == 1
    assert dataclasses.replace(cfg, d_head=128).kv_pack == 1
    assert dataclasses.replace(cfg, n_kv_heads=1).kv_pack == 1
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (3, 1, 8, 64))
    k = jax.random.normal(jax.random.fold_in(key, 1), (3, 256, 4, 64))
    v = jax.random.normal(jax.random.fold_in(key, 2), (3, 256, 4, 64))
    lengths = jnp.asarray([5, 200, 0], jnp.int32)
    ck, cv = G.pack_rows(cfg, k), G.pack_rows(cfg, v)
    assert ck.shape == (3, 2, 256, 128)
    assert G.init_cache(cfg, 3, 256)["block_0"]["k"].shape == ck.shape
    np.testing.assert_array_equal(
        G.unpack_rows(cfg, ck), jnp.swapaxes(k, 1, 2))
    got = G.packed_attention(
        cfg, lambda *a: decode_attention(*a, scale=64 ** -0.5,
                                         interpret=True),
        q, ck, cv, lengths)
    want = decode_attention(q, jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
                            lengths, interpret=True)
    np.testing.assert_allclose(got, want, atol=1e-6)


# ----------------------------------------------------------------------
# one helper for both stateful mixers' convolution
# ----------------------------------------------------------------------


def _conv_as_the_state_space_mixer_had_it(x, kernel, state=None, lengths=None,
                                          bias=None, activation=None):
    """The convolution and its window as `ssm_mixer` wrote them inline
    before `causal_conv` (kernel 4, a bias and a SiLU there), operation
    for operation."""
    b, t, c = x.shape
    kk = kernel.shape[0]
    f32 = jnp.float32
    left = (jnp.zeros((b, kk - 1, c), x.dtype)
            if state is None else state.astype(x.dtype))
    full = jnp.concatenate([left, x], axis=1)
    w = kernel.astype(f32)
    conv = bias.astype(f32) + sum(
        full[:, i:i + t].astype(f32) * w[i] for i in range(kk))
    conv = activation(conv)
    if lengths is None:
        window = full[:, t:]
    else:
        window = jax.vmap(
            lambda row, n: jax.lax.dynamic_slice_in_dim(row, n, kk - 1, 0)
        )(full, lengths.astype(jnp.int32))
    return conv, window


def _nemotron_rehearsal_logits():
    """Logits and final cache of `nemotron3_super_l11_ep4`'s rehearsal
    model: a padded prefill of two rows at their own lengths, then six
    decode steps through the state."""
    config = mf.load_json("configs", "nemotron3_super_l11_ep4")
    spec = {**config["rehearsal"]["lm_spec"], "seed": 7}
    ref = mf.load_module("references", config["reference"])
    params, cfg = lm_spec_parts(spec)
    params = jax.tree.map(lambda x, d: x.astype(d.dtype),
                          ref.make_params(spec, 7), params)
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, cfg.vocab_size, (2, 24)).astype(np.int32)
    logits, cache = jax.jit(lambda x, i: G.prefill(
        params, cfg, x, 64, logits_index=i))(
            jnp.asarray(prompt), jnp.asarray([2, 23], jnp.int32))
    out = [logits]
    step = jax.jit(lambda c, t, p: G.batched_decode_step(params, cfg, c, t, p))
    pos = jnp.asarray([3, 24], jnp.int32)
    for i in range(6):
        logits, cache = step(cache, jnp.argmax(out[-1], -1).astype(jnp.int32),
                             pos + i)
        out.append(logits)
    return [np.asarray(x) for x in out + jax.tree.leaves(cache)]


def test_the_state_space_mixer_is_bit_identical_through_the_shared_helper(
        monkeypatch):
    """`ssm_mixer` takes its convolution from `causal_conv`, the helper
    the gated short convolution shares: `nemotron3_super_l11_ep4`'s
    rehearsal logits (and its state) are the same BITS as with the
    arithmetic the mixer held inline before."""
    jax.clear_caches()
    after = _nemotron_rehearsal_logits()
    monkeypatch.setattr(G, "causal_conv",
                        _conv_as_the_state_space_mixer_had_it)
    jax.clear_caches()
    before = _nemotron_rehearsal_logits()
    assert len(before) == len(after) > 7
    for a, b in zip(after, before):
        np.testing.assert_array_equal(a, b)


def test_the_helper_at_kernel_3_without_bias_or_activation():
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 6, 5))
    w = jax.random.normal(jax.random.PRNGKey(5), (3, 5))
    state = jax.random.normal(jax.random.PRNGKey(6), (2, 2, 5))
    conv, window = G.causal_conv(x, w, state)
    full = np.concatenate([state, x], 1)
    want = sum(full[:, i:i + 6] * np.asarray(w)[i] for i in range(3))
    np.testing.assert_allclose(conv, want, atol=1e-6)
    np.testing.assert_array_equal(window, x[:, -2:])
    _, own = G.causal_conv(x, w, state, lengths=jnp.asarray([1, 4]))
    np.testing.assert_array_equal(own[0], full[0, 1:3])  # a state row, x_0
    np.testing.assert_array_equal(own[1], x[1, 2:4])


# ----------------------------------------------------------------------
# the expert layer: the four chips' shares
# ----------------------------------------------------------------------


def _moe_spec(held):
    # block_2 is the first expert layer (two dense layers lead)
    return _spec(num_experts=32, experts_per_token=4, experts_held=held)


def _expert_layer(spec, moe, y):
    cfg = lm_spec_parts(spec)[1]
    return G.expert_ffn(
        moe, y, jnp.float32, cfg.experts_per_token, cfg.experts_first,
        scoring=cfg.router_scoring, scale=cfg.router_scale,
        activation=cfg.activation)


def test_the_shares_of_four_chips_add_up_to_the_uncut_layer():
    """model-configs section 4: four shares of a 32-expert layer top-4,
    each computed by the program for the 8 experts it holds (routing
    over all 32, under the selection bias), add up to what the reference
    gives for the whole layer. There is no shared expert to count once;
    what every chip computes alike is the routing."""
    whole_spec = _moe_spec([0, 32])
    whole = REF.make_params(whole_spec, 3)
    moe = whole["block_2"]["moe"]
    assert float(jnp.abs(moe["router"]["bias"]).max()) > 0  # balanced
    y = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 32))
    dims = REF._dims(whole_spec)
    want = REF.experts(y.reshape(-1, 32), moe, dims, "f32")
    total = jnp.zeros_like(want)
    for first in (0, 8, 16, 24):
        spec = _moe_spec([first, 8])
        share = {**moe, **{w: moe[w][first:first + 8]
                           for w in ("w_up", "w_gate", "w_down")}}
        out, counts = _expert_layer(spec, share, y)
        # every share routes over ALL 32 experts, and counts them so
        assert int(counts.sum()) == 2 * 9 * 4
        total = total + out.reshape(-1, 32)
        # ... and is the reference's own share
        np.testing.assert_allclose(
            out.reshape(-1, 32),
            REF.experts(y.reshape(-1, 32), share, REF._dims(spec), "f32"),
            atol=1e-4)
    np.testing.assert_allclose(total, want, atol=2e-4)


def test_the_head_is_the_embedding_and_q_and_k_are_normed():
    spec = _spec()
    params, cfg = _parts(spec)
    assert "lm_head" not in params and cfg.qk_norm
    assert params["block_2"]["q_norm"]["scale"].shape == (8,)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 1, 32))
    want = G._rms_norm(x, params["ln_out"]["scale"], jnp.float32,
                       cfg.norm_eps)[:, 0] @ params["embed"]["embedding"].T
    np.testing.assert_allclose(G._head(params, cfg, x), want, atol=1e-5)
    # the norms' scales count: another scale, other logits
    toks = jnp.asarray(_prompts([9])[0][None])
    base, _ = G.prefill(params, cfg, toks, 64)
    scaled = {**params, "block_2": {**params["block_2"], "q_norm": {
        "scale": 2.0 * params["block_2"]["q_norm"]["scale"]}}}
    other, _ = G.prefill(scaled, cfg, toks, 64)
    assert float(jnp.abs(base - other).max()) > 1e-3


# ----------------------------------------------------------------------
# through the server: slots, placement, reuse
# ----------------------------------------------------------------------


def test_served_tokens_are_the_references_choice():
    """Prompts shorter than the convolution's window (1, 2), of no whole
    number of anything, more of them than slots: every served token is
    the reference's argmax given what came before it."""
    spec = _spec()
    be, params, _ = _backend(spec)
    prompts = _prompts([5, 2, 11, 17, 9, 1, 30])
    budgets = [10, 7, 9, 12, 4, 10, 20]
    try:
        results = _serve(be, prompts, budgets)
    finally:
        be.close()
    for prompt, budget, got in zip(prompts, budgets, results):
        _assert_the_references_choice(spec, params, prompt, budget, got)


def test_a_request_alone_equals_the_same_request_in_a_full_grid():
    spec = _spec()
    be, params, _ = _backend(spec)
    prompts = _prompts([9, 3, 14, 6], seed=4)
    try:
        alone = _serve(be, prompts[:1], [12])[0]
        full = _serve(be, prompts, [12, 5, 9, 12])
    finally:
        be.close()
    assert full[0] == alone
    for prompt, got in zip(prompts, full):
        _assert_the_references_choice(spec, params, prompt, len(got), got)


def test_a_slot_reused_after_a_longer_occupant_starts_clean():
    """One slot: a long request, then a short one in the same slot. The
    second's windows are overwritten whole at placement (nothing of the
    first's windows or rows is read)."""
    spec = _spec(max_slots=1)
    be, params, _ = _backend(spec)
    long_, short = _prompts([28, 2], seed=6)
    try:
        first = _serve(be, [long_], [30])[0]
        second = _serve(be, [short], [8])[0]
    finally:
        be.close()
    _assert_the_references_choice(spec, params, long_, 30, first)
    _assert_the_references_choice(spec, params, short, 8, second)


def test_joins_and_leaves_while_neighbours_decode():
    """Seven requests of spread budgets over two slots, heads of 64 two
    a cached row: each slot is left and joined at dispatches where the
    other is in mid-answer."""
    spec = _spec(max_slots=2, chunk=2, head_dim=64)
    be, params, _ = _backend(spec)
    prompts = _prompts([7, 12, 3, 20, 5, 9, 2], seed=8)
    budgets = [21, 3, 9, 5, 14, 2, 11]
    try:
        results = _serve(be, prompts, budgets)
    finally:
        be.close()
    for prompt, budget, got in zip(prompts, budgets, results):
        _assert_the_references_choice(spec, params, prompt, budget, got)


def test_spans_and_counters_carry_the_windows_and_the_routing():
    spec = _spec()
    be, _, _ = _backend(spec)
    assert be.server._group_tokens == LS._STATE_GROUP_TOKENS
    # one attention layer stands for its type in the rows' account: the
    # first that has rows, not layer 0 (a convolution)
    assert be.server._kv_layers == (("full", 2),)
    n0 = len(TRACER.loop_spans("lm_step"))
    try:
        _serve(be, _prompts([6, 9]), [6, 6])
    finally:
        be.close()
    steps = [d for d in TRACER.loop_spans("lm_step")[n0:]
             if "state_slots" in d["lb"]]
    assert steps and all(
        d["lb"]["state_slots"] == d["lb"]["occupancy"] for d in steps)
    assert all(d["lb"]["kv_rows_live"] > 0 for d in steps)
    assert all(0 <= d["lb"]["experts_touched_held"]
               <= min(4, d["lb"]["experts_touched"]) for d in steps)
    groups = [d for d in TRACER.loop_spans("lm_prefill_group")
              if "state_rows" in d["lb"]]
    assert groups and groups[-1]["lb"]["state_rows"] >= 1
    state = METRICS.gauge("lm_server_state_bytes")
    assert state.value(kind="conv") == 3 * 4 * 2 * 32 * 4
    assert state.value(kind="scan") == 0
    assert state.value(kind="kv") == 2 * 2 * 4 * 2 * 64 * 8 * 4


# ----------------------------------------------------------------------
# what cannot hold the window refuses it
# ----------------------------------------------------------------------


def test_the_prefix_cache_refuses_the_window():
    be, _, _ = _backend(_spec())
    try:
        with pytest.raises(ValueError, match="carry a convolution window"):
            be.server.enable_kv_cache(KVPrefixCache(1 << 20))
    finally:
        be.close()
    with pytest.raises(ValueError, match="cut by token"):
        LMBackend.from_spec(_spec(kv_cache_mb=1))


def test_submit_prefilled_refuses_the_window():
    be, _, _ = _backend(_spec())
    try:
        with pytest.raises(ValueError, match="submit_prefilled"):
            be.server.submit_prefilled(
                np.arange(5, dtype=np.int32), {}, np.zeros(VOCAB), 4)
    finally:
        be.close()


def test_speculation_refuses_the_window():
    with pytest.raises(ValueError, match="speculative decoding"):
        LMBackend.from_spec(_spec(spec_k=2))


def test_diffusion_refuses_the_window():
    with pytest.raises(ValueError, match="convolution's window"):
        lm_arch(_spec(attention_mask="block_causal", block_length=4,
                      denoising_steps=2, mask_token_id=96))
    params, cfg = _parts(_spec())
    with pytest.raises(ValueError, match="block_causal"):
        dataclasses.replace(
            cfg, attention_mask="block_causal", block_length=4)
    with pytest.raises(ValueError, match="convolution's window back"):
        G.batched_block_step(
            params, cfg, G.init_cache(cfg, 2, 64),
            jnp.zeros((2, 4), jnp.int32), jnp.zeros(2, jnp.int32))


def test_the_sharded_forms_refuse_the_window():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from dml_tpu.inference.lm_sharded import LMPrefillBackend

    params, cfg = _parts(_spec())
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    # a server finds its mesh on the tree it is handed
    placed = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
    with pytest.raises(ValueError, match="sharded forms"):
        LS.LMServer(placed, cfg, max_slots=2, max_len=64)
    with pytest.raises(ValueError, match="slab of K/V rows"):
        LMPrefillBackend(params, cfg)


def _typed(**types):
    return {"layers": LAYERS, "types": {
        "conv": {"conv_kernel": 3},
        "full_attention": {"n_heads": 4, "rope": {"theta": 1e6}}, **types}}


@pytest.mark.parametrize("bad,match", [
    ({"attention_layers": _typed(conv={"conv_kernel": 1})},
     "convolution layer type"),
    ({"attention_layers": _typed(conv={"conv_kernel": 3, "n_heads": 4})},
     "no other key"),
    ({"attention_layers": _typed(conv={"conv_kernel": 3, "window": 8})},
     "no other key"),
    ({"attention_layers": _typed(conv={"kernel": 3})}, "n_heads"),
    ({"attention_layers": _typed(full_attention={"n_heads": 3})},
     "do not divide"),
    ({"layer_pattern": "M*M*M"}, "layer_pattern under attention_layers"),
    ({"n_kv_heads": None}, "n_kv_heads"),
    ({"attention": "latent", "latent_attention": {
        "q_lora_rank": 8, "kv_lora_rank": 8, "qk_nope_head_dim": 4,
        "qk_rope_head_dim": 4, "v_head_dim": 4}}, "latent_attention"),
    ({"rope": "none"}, "causal mask"),
    ({"dense_layers": -1}, "dense_layers"),
    ({"router": {"scoring": "softmax", "bias": True}}, "selection bias"),
    ({"n_layers": 4}, "n_layers is 4"),
])
def test_lm_arch_rejects_what_it_cannot_honour(bad, match):
    with pytest.raises(ValueError, match=match):
        lm_spec_parts(_spec(**bad))


def test_absent_keys_mean_what_the_tree_did_before():
    """A spec without the new keys declares what it declared: an untied
    head, no convolution, no state; typed attention layers alone carry
    no state either."""
    params, cfg = lm_spec_parts({
        "vocab_size": 64, "d_model": 32, "n_heads": 4, "n_layers": 2,
        "num_experts": 4, "experts_per_token": 2, "dtype": "float32"})
    assert "lm_head" in params and not cfg.has_state and not cfg.has_conv
    assert cfg.kv_pack == 1
    layers = ["full_attention"] * 3
    _, typed = lm_spec_parts({**_spec(layers), "attention_layers": {
        "layers": layers, "types": {
            "full_attention": {"n_heads": 4, "rope": {"theta": 1e6}}}}})
    assert not typed.has_state and typed.qk_norm
