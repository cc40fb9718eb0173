"""Block diffusion with gated top-k experts on the serving path
(`LMBackend.from_spec` -> `LMServer` -> `LMDriver`), against the
benchmark's plain reference, loaded by its path as
`benchmark/harness/manifest.load_module` loads it. Small sizes, seeded
random weights, float32, on the CPU.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dml_tpu.inference import generate as G
from dml_tpu.inference.lm_backend import LMBackend, lm_spec_parts
from dml_tpu.inference.lm_server import BlockDiffusion, LMServer
from dml_tpu.observability import METRICS
from dml_tpu.tracing import TRACER

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MASK = 96


def _reference():
    path = os.path.join(ROOT, "benchmark", "references",
                        "sdar_moe_block_diffusion.py")
    spec = importlib.util.spec_from_file_location("ref_sdar", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _spec(steps=2, **over):
    return {
        "vocab_size": 97, "d_model": 32, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "n_layers": 2, "rope_theta": 1e6, "qk_norm": True,
        "num_experts": 8, "experts_per_token": 3, "expert_d_ff": 24,
        "gated": True, "attention_mask": "block_causal", "block_length": 4,
        "denoising_steps": steps, "remasking": "low_confidence_static",
        "mask_token_id": MASK, "dtype": "float32", "param_dtype": "float32",
        "max_len": 64, "max_slots": 4, "max_new_tokens": 10, "chunk": 8,
        "seed": 5, **over,
    }


def _backend(spec):
    """The program's stack around the REFERENCE's weights (the trees
    must agree leaf for leaf, or `tree.map` raises)."""
    params, cfg = lm_spec_parts(spec)
    ref_params = jax.tree.map(lambda x, d: x.astype(d.dtype),
                              REF.make_params(spec, 11), params)
    be = LMBackend.from_spec(spec)
    be.server.params = ref_params
    return be, ref_params, cfg


def _prompts(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, MASK, n).astype(np.int32) for n in lengths]


def _serve(be, prompts, budgets):
    fixed = []
    toks = be.driver.serve(prompts, budgets, fixed_at=fixed)
    return [{"tokens": [int(t) for t in ts], **f}
            for ts, f in zip(toks, fixed)]


def _stats(spec, params, prompt, got):
    """The reference's view of every denoising step of one request."""
    rows = REF.request_rows(
        spec, prompt, got["tokens"] + got["beyond_budget"]["tokens"],
        got["fixed_at"] + got["beyond_budget"]["fixed_at"])
    copies = len(rows["copies"])
    return rows, REF.served_rows(
        params, spec, rows, pad_final=rows["final_rows"], pad_copies=copies)


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_served_tokens_are_the_references_choice_at_every_step(steps):
    """Prompts whose lengths are and are not multiples of 4, budgets that
    end inside a block: every token is the reference's argmax at the step
    that fixed it, given the block's state at that step; the positions a
    step fixed are the reference's most confident; every block follows
    the static schedule."""
    spec = _spec(steps)
    be, params, _ = _backend(spec)
    prompts = _prompts([5, 8, 11, 3, 6, 16])
    budgets = [10, 7, 9, 12, 4, 10]
    try:
        results = _serve(be, prompts, budgets)
    finally:
        be.close()
    for prompt, budget, got in zip(prompts, budgets, results):
        _assert_the_references_choice(spec, params, prompt, budget, got)


def _assert_the_references_choice(spec, params, prompt, budget, got):
    steps = spec["denoising_steps"]
    assert len(got["tokens"]) == budget == len(got["fixed_at"])
    assert (len(prompt) + budget
            + len(got["beyond_budget"]["tokens"])) % 4 == 0
    rows, st = _stats(spec, params, prompt, got)
    for steps_of_block in rows["steps_of_blocks"]:
        masked = sum(1 for s in steps_of_block if s > 0)
        assert [steps_of_block.count(s) for s in range(1, steps + 1)] \
            == REF.schedule(masked, steps)
    n_final = rows["final_rows"]
    for c in rows["copies"]:
        at = c["row0"] - n_final
        for j in c["fixed_now"]:
            gap = st["best"][at + j] - st["scored"][at + j]
            assert gap <= 1e-4, (c, j, gap)
        left = [j for j in c["masked"] if j not in c["fixed_now"]]
        if c["fixed_now"] and left:
            assert (min(st["log_conf"][at + j] for j in c["fixed_now"])
                    >= max(st["log_conf"][at + j] for j in left) - 1e-4)


def test_logits_at_every_step_and_committed_rows_match_one_full_forward():
    """Prefill, then denoising and commit forwards through the cache
    (the program's own primitives, as `_diffuse_impl` calls them), against
    the reference's ONE forward over the final sequence and its noisy
    copies: the logits of every denoising step, and the K/V rows the
    commits left in the cache."""
    spec = _spec(2)
    be, params, cfg = _backend(spec)
    prompt = _prompts([7], seed=3)[0]
    try:
        got = _serve(be, [prompt], [9])[0]
        served_cache = be.server.cache
    finally:
        be.close()
    rows = REF.request_rows(
        spec, prompt, got["tokens"] + got["beyond_budget"]["tokens"],
        got["fixed_at"] + got["beyond_budget"]["fixed_at"])
    n = rows["final_rows"]
    hidden, kvs = REF.forward(
        params, spec, rows["tokens"], rows["positions"], rows["block_of"],
        rows["copy_of"], keep_kv=True)
    ref_logits = np.asarray(REF.logits_of(params, hidden))
    # the program: a bucket-padded block-causal prefill into a cache...
    padded = np.full((1, 16), prompt[-1], np.int32)
    padded[0, :len(prompt)] = prompt
    _, cache = G.prefill(params, cfg, jnp.asarray(padded), 64, head=False)
    for c in rows["copies"]:
        lo = rows["positions"][c["row0"]]
        x = jnp.asarray([rows["tokens"][c["row0"]:c["row0"] + 4]], jnp.int32)
        pos = jnp.asarray([lo], jnp.int32)
        logits, cache = G.batched_block_step(
            params, cfg, cache, x, pos, mask_block=4)
        np.testing.assert_allclose(
            np.asarray(logits)[0], ref_logits[c["row0"]:c["row0"] + 4],
            atol=2e-4, err_msg=f"block {c['block']} step {c['step']}")
        if c["step"] == 2:  # ... then the commit of the final tokens
            final = jnp.asarray([rows["tokens"][lo:lo + 4]], jnp.int32)
            none, cache = G.batched_block_step(
                params, cfg, cache, final, pos, mask_block=4, head=False)
            assert none is None
    for i, (k, v) in enumerate(kvs):
        for name, want in (("k", k), ("v", v)):
            want = np.swapaxes(np.asarray(want)[:n], 0, 1)  # [KV, n, D]
            np.testing.assert_allclose(
                np.asarray(cache[f"block_{i}"][name])[0, :, :n], want,
                atol=2e-4, err_msg=f"layer {i} {name} (replayed)")
            # and the rows the SERVER's own dispatches committed (slot 0)
            np.testing.assert_allclose(
                np.asarray(served_cache[f"block_{i}"][name])[0, :, :n],
                want, atol=2e-4, err_msg=f"layer {i} {name} (served)")


def test_a_request_alone_equals_the_same_request_in_a_full_grid():
    spec = _spec(2)
    be, _, _ = _backend(spec)
    prompts = _prompts([9, 5, 14, 4, 6, 12, 3], seed=1)
    budgets = [10, 6, 12, 9, 5, 10, 8]
    try:
        together = _serve(be, prompts, budgets)  # 7 requests, 4 slots
        alone = [_serve(be, [p], [b])[0] for p, b in zip(prompts, budgets)]
    finally:
        be.close()
    assert together == alone


@pytest.mark.parametrize("lengths,shapes", [
    # the short three share one group of the floor's bucket
    ([5, 40, 70, 600], [(512, 4, 3, 0), (1024, 1, 1, 0)]),
    # the short one takes the spare row of three of bucket 1024
    ([6, 600, 801, 702], [(1024, 4, 4, 1)]),
])
def test_a_short_prompt_in_a_longer_group_is_served_as_alone(lengths, shapes):
    """max_len 1024, so that groups take power-of-two rows and a bucket
    over the floor exists: a prompt that shares a longer group (its
    length no multiple of the block: it keeps its own tail and its own
    `tps // b * b`) gets the tokens it gets alone, and they are the
    reference's choice."""
    spec = _spec(2, max_len=1024)
    be, params, _ = _backend(spec)
    prompts, budgets = _prompts(lengths, seed=3), [10, 7, 9, 12]
    groups0 = len(TRACER.loop_spans("lm_prefill_group"))
    try:
        together = _serve(be, prompts, budgets)
        groups = TRACER.loop_spans("lm_prefill_group")[groups0:]
        alone = [_serve(be, [p], [b])[0] for p, b in zip(prompts, budgets)]
    finally:
        be.close()
    assert [(g["lb"]["bucket"], g["lb"]["padded_rows"], g["lb"]["rows"],
             g["lb"]["riders"]) for g in groups] == shapes
    assert together == alone
    _assert_the_references_choice(
        spec, params, prompts[0], budgets[0], together[0])


def test_requests_join_and_leave_at_block_boundaries():
    """Seven requests over four slots with budgets that end in different
    dispatches: each leaves after the block that met its budget, a waiting
    one joins at the next dispatch's start, and every committed block is
    delivered whole (streamed tokens are the result's)."""
    spec = _spec(2)
    be, _, _ = _backend(spec)
    prompts = _prompts([4, 4, 8, 8, 4, 12, 4], seed=2)
    budgets = [4, 20, 8, 12, 16, 4, 8]
    streamed = [[] for _ in prompts]
    fixed = []
    spans0 = len(TRACER.loop_spans("lm_step"))
    try:
        toks = be.driver.serve(
            prompts, budgets, fixed_at=fixed,
            on_token=[s.append for s in streamed])
    finally:
        be.close()
    assert [len(t) for t in toks] == budgets
    assert [list(t) for t in toks] == streamed
    steps = TRACER.loop_spans("lm_step")[spans0:]
    assert steps and all(d["lb"]["mode"] == "diffusion" for d in steps)
    # 2 blocks a dispatch, 3 forwards a block
    assert all(d["lb"]["forwards"] == 6 for d in steps)
    assert sum(d["lb"]["tokens_fixed"] for d in steps) == sum(budgets)
    assert max(d["lb"]["occupancy"] for d in steps) == 4
    # the grid was refilled while others were still generating
    assert len(steps) < sum(-(-b // 8) for b in budgets)
    assert all(0 < d["lb"]["experts_touched"] <= 8 for d in steps)


def test_counters_count_forwards_blocks_and_assignments():
    def value(name, **labels):
        return METRICS.counter(name).value(**labels)

    spec = _spec(2)
    be, _, _ = _backend(spec)
    before = {k: value(*k[:1], **dict(k[1:])) for k in (
        ("lm_server_forwards_total", ("kind", "denoise")),
        ("lm_server_forwards_total", ("kind", "commit")),
        ("lm_server_tokens_fixed_total",),
        ("lm_server_blocks_committed_total",),
        ("moe_assignments_total", ("where", "held")),
        ("moe_assignments_total", ("where", "absent")))}
    try:
        _serve(be, _prompts([8]), [8])  # one dispatch of 2 blocks
    finally:
        be.close()
    delta = {k: value(*k[:1], **dict(k[1:])) - v for k, v in before.items()}
    assert delta[("lm_server_forwards_total", ("kind", "denoise"))] == 4
    assert delta[("lm_server_forwards_total", ("kind", "commit"))] == 2
    assert delta[("lm_server_tokens_fixed_total",)] == 8
    assert delta[("lm_server_blocks_committed_total",)] == 2
    # 6 forwards x 2 layers x 4 tokens of the one occupied slot x top-3,
    # every one to an expert this tree holds (it holds them all)
    assert delta[("moe_assignments_total", ("where", "held"))] == 6 * 2 * 4 * 3
    assert delta[("moe_assignments_total", ("where", "absent"))] == 0


@pytest.mark.parametrize("bad,match", [
    ({"attention_mask": "sliding"}, "attention_mask"),
    ({"remasking": "dynamic"}, "remasking"),
    ({"experts_held": [6, 4]}, "experts_held"),
    ({"experts_held": [0, 0]}, "experts_held"),
    ({"head_dim": 15}, "head_dim"),
    ({"experts_per_token": 9}, "experts_per_token"),
    ({"mask_token_id": 97}, "mask_token_id"),
    ({"denoising_steps": 0}, "denoising_steps"),
    ({"param_dtype": "float16"}, "param_dtype"),
    ({"attention_mask": "causal"}, "block_length"),
])
def test_lm_spec_parts_rejects_what_it_cannot_honour(bad, match):
    with pytest.raises(ValueError, match=match):
        lm_spec_parts(_spec(2, **bad))


def test_an_older_spec_is_declared_as_before():
    """No architecture key: TransformerLM's tree, float32 storage; keys
    of other layers pass."""
    params, cfg = lm_spec_parts({
        "name": "LM", "vocab_size": 64, "d_model": 32, "n_heads": 4,
        "n_kv_heads": 2, "n_layers": 1, "max_slots": 4, "kv_cache_mb": 1,
        "spec_k": 2})
    assert set(params["block_0"]) == {
        "ln_attn", "qkv", "proj", "ln_mlp", "up", "down"}
    assert params["block_0"]["qkv"]["kernel"].dtype == jnp.float32
    assert (cfg.head_dim, cfg.rope_theta, cfg.mask_block) == (8, 10000.0, 1)


def test_param_dtype_stores_matrices_and_not_norms():
    params, _ = lm_spec_parts(_spec(2, param_dtype="bfloat16"))
    blk = params["block_0"]
    assert blk["moe"]["w_gate"].dtype == jnp.bfloat16
    assert blk["qkv"]["kernel"].shape == (32, 4 * 16 + 2 * 2 * 16)
    assert params["lm_head"]["kernel"].dtype == jnp.bfloat16
    assert blk["moe"]["router"]["kernel"].dtype == jnp.float32
    assert blk["q_norm"]["scale"].dtype == jnp.float32


def _expert_tree(rng, e, d, f, gated=True):
    moe = {"router": {"kernel": jnp.asarray(rng.randn(d, e), jnp.float32)},
           "w_up": jnp.asarray(rng.randn(e, d, f) / d ** 0.5, jnp.float32),
           "w_down": jnp.asarray(rng.randn(e, f, d) / f ** 0.5, jnp.float32)}
    if gated:
        moe["w_gate"] = jnp.asarray(rng.randn(e, d, f) / d ** 0.5, jnp.float32)
    return moe


@pytest.mark.parametrize("gated", [True, False])
def test_the_shares_of_the_experts_add_up_to_the_uncut_layer(gated):
    """32 routed experts over 4 chips of 8: every share routes over all
    32 and computes its own experts' part; the parts add up to the whole
    layer, which is the reference's plain loop."""
    rng = np.random.RandomState(0)
    d, f, e, k = 16, 24, 32, 8
    moe = _expert_tree(rng, e, d, f, gated)
    y = jnp.asarray(rng.randn(3, 7, d), jnp.float32)
    whole, counts = G.expert_ffn(moe, y, jnp.float32, k)
    assert int(counts.sum()) == 3 * 7 * k
    parts = []
    for first in range(0, e, 8):
        share = {**moe, **{n: moe[n][first:first + 8] for n in moe
                           if n.startswith("w_")}}
        part, c = G.expert_ffn(share, y, jnp.float32, k, first)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(counts))
        parts.append(np.asarray(part))
        want = REF._experts(y.reshape(-1, d), share, k=k, first=first,
                            precision="f32")
        np.testing.assert_allclose(part.reshape(-1, d), np.asarray(want),
                                   atol=2e-5)
    np.testing.assert_allclose(sum(parts), np.asarray(whole), atol=2e-5)
    want = REF._experts(y.reshape(-1, d), moe, k=k, first=0, precision="f32")
    np.testing.assert_allclose(np.asarray(whole).reshape(-1, d),
                               np.asarray(want), atol=2e-5)


def test_expert_counts_leave_out_rows_that_are_not_live():
    rng = np.random.RandomState(1)
    moe = _expert_tree(rng, 8, 16, 24)
    y = jnp.asarray(rng.randn(4, 4, 16), jnp.float32)
    live = jnp.asarray([True, False, True, False])
    out_all, _ = G.expert_ffn(moe, y, jnp.float32, 2)
    out, counts = G.expert_ffn(moe, y, jnp.float32, 2, live=live)
    assert int(counts.sum()) == 2 * 4 * 2
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_all))


def _plain_attention(q, k, v, mask_block):
    """Masked softmax, float32: i attends j iff j // B <= i // B."""
    t = q.shape[1]
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    allowed = j // mask_block <= i // mask_block
    s = np.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    s = np.where(allowed[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("t", [8, 32, 37, 64, 70])
@pytest.mark.parametrize("mask_block", [4, 8])
def test_block_causal_flash_attention_matches_a_plain_masked_softmax(
        t, mask_block):
    """Lengths that are and are not multiples of the kernel's blocks (16),
    block skipping on."""
    from dml_tpu.ops.flash_attention import flash_attention

    rng = np.random.RandomState(t)
    q, k, v = (rng.randn(2, t, 2, 8).astype(np.float32) for _ in range(3))
    out = flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True,
        mask_block=mask_block, block_q=16, block_k=16)
    np.testing.assert_allclose(
        np.asarray(out), _plain_attention(q, k, v, mask_block), atol=2e-5)


def test_flash_attention_refuses_a_block_mask_without_causal():
    from dml_tpu.ops.flash_attention import flash_attention

    x = jnp.zeros((1, 8, 1, 8))
    with pytest.raises(ValueError, match="mask_block"):
        flash_attention(x, x, x, causal=False, mask_block=4)


@pytest.mark.parametrize("mask_block,quant", [
    (1, False), (4, False), (2, False), (4, True)])
def test_decode_attention_with_several_query_rows_a_slot(mask_block, quant):
    """The cache-attention kernel with Q = 4 query rows a slot (interpret
    mode) against the einsum route of `batched_block_step`: causal rows
    (speculation's verify), one block of 4, two blocks of 2; an empty
    slot returns zeros."""
    from dml_tpu.ops.decode_attention import decode_attention

    rng = np.random.RandomState(7)
    b, kv, g, d, t, n_q = 3, 2, 4, 16, 96, 4
    q = jnp.asarray(rng.randn(b, n_q, kv * g, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, kv, t, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, kv, t, d), jnp.float32)
    lengths = jnp.asarray([40, 0, 96], jnp.int32)
    scales = {}
    if quant:
        kq, ks = G._kv_quantize(k)
        vq, vs = G._kv_quantize(v)
        scales = {"k_scale": jnp.swapaxes(ks, 2, 3),
                  "v_scale": jnp.swapaxes(vs, 2, 3)}
        k, v = G._kv_dequant(kq, ks), G._kv_dequant(vq, vs)
        got = decode_attention(q, kq, vq, lengths, mask_block=mask_block,
                               block_k=32, interpret=True, **scales)
    else:
        got = decode_attention(q, k, v, lengths, mask_block=mask_block,
                               block_k=32, interpret=True)
    back = np.asarray(G._rows_back(n_q, mask_block))
    limit = np.asarray(lengths)[:, None] - back[None, :]  # [B, Q]
    valid = np.arange(t)[None, None, :] < limit[:, :, None]
    qg = np.asarray(q).reshape(b, n_q, kv, g, d)
    s = np.einsum("bqkgd,bktd->bkgqt", qg, np.asarray(k)) * d ** -0.5
    s = np.where(valid[:, None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = np.where(valid[:, None, None], p / p.sum(-1, keepdims=True), 0.0)
    want = np.einsum("bkgqt,bktd->bqkgd", p, np.asarray(v)).reshape(
        b, n_q, kv * g, d)
    # an int8 cache's dots take bf16 operands (p is rounded to bf16)
    np.testing.assert_allclose(np.asarray(got), want,
                               atol=5e-3 if quant else 2e-5)
    assert not np.asarray(got)[1].any()


def test_a_request_is_priced_by_forwards():
    from dml_tpu.jobs.cost_model import lm_request_forwards

    assert lm_request_forwards(256) == 256
    # 64 blocks of 4, two denoising forwards and a commit each
    assert lm_request_forwards(256, block_length=4, denoising_steps=2) == 192
    assert lm_request_forwards(10, block_length=4, denoising_steps=1) == 6
    spec = _spec(2)
    be = LMBackend.from_spec(spec)
    try:
        assert be.forwards_per_request() == 3 * 3  # 10 tokens, 3 blocks
        dense = be.cost().per_query
        be._per_forward = 0.5  # as if a dispatch had been measured
        assert be.cost().per_query == pytest.approx(0.5 * 9 / 4)
        assert be.cost().per_query != dense
    finally:
        be.close()


def test_a_server_refuses_what_block_diffusion_cannot_serve():
    spec = _spec(2)
    params, cfg = lm_spec_parts(spec)
    df = BlockDiffusion(steps=2, mask_token_id=MASK)
    with pytest.raises(ValueError, match="greedy"):
        LMServer(params, cfg, max_len=64, diffusion=df, temperature=0.7)
    with pytest.raises(ValueError, match="block_causal"):
        LMServer(params, cfg, max_len=64)
    with pytest.raises(ValueError, match="blocks"):
        LMServer(params, cfg, max_len=62, diffusion=df)
    srv = LMServer(params, cfg, max_len=64, diffusion=df, chunk=8)
    with pytest.raises(ValueError, match="mask token"):
        srv.submit(np.asarray([1, MASK, 3], np.int32), 4)
    with pytest.raises(ValueError, match="block-diffusion"):
        srv.enable_spec_decode(2)
