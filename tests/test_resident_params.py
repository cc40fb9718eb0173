"""The tree an `LMServer` keeps (`quantize.resident_params`): the block
matrices and expert tensors of a float32 checkpoint served in bfloat16
are cast once, when the server is built, and in no program; every other
leaf, and every tree that needs no cast, is the caller's own array. What
is served is what casting at use served: the same roundings of the same
numbers.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dml_tpu.inference.lm_backend import lm_spec_parts
from dml_tpu.inference.lm_server import BlockDiffusion, LMServer
from dml_tpu.inference.quantize import (
    is_quantized,
    kernel_of,
    quantize_lm_params,
    quantized_bytes,
    resident_params,
)
from dml_tpu.observability import METRICS
from dml_tpu.tracing import TRACER

BF16, F32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)

DENSE = {
    "vocab_size": 61, "d_model": 32, "n_heads": 4, "n_kv_heads": 2,
    "n_layers": 2, "d_ff": 64, "dtype": "bfloat16", "seed": 3,
}
#: an expert-layer tree (gated top-3 of 8), stored in float32
EXPERTS = {
    **DENSE, "head_dim": 16, "qk_norm": True, "num_experts": 8,
    "experts_per_token": 3, "expert_d_ff": 24, "gated": True,
    "param_dtype": "float32",
}
MASK = 60
DIFFUSION = {
    **EXPERTS, "attention_mask": "block_causal", "block_length": 4,
    "denoising_steps": 2, "mask_token_id": MASK,
}


def _leaves(tree):
    """{path: leaf}, a quantized pair counted as one leaf."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_quantized)
    return {tuple(getattr(p, "key", p) for p in path): leaf
            for path, leaf in flat}


def _cast_at_use(path):
    """Whether `kernel_of(node, cfg.dtype)` reads this leaf on its way
    into a block's matmul: the serving code's own access points
    (`generate._apply_block`, `generate.expert_ffn`)."""
    if not path[0].startswith("block_"):
        return False
    return (path[1] in ("qkv", "proj", "up", "down") and path[-1] == "kernel"
            ) or (path[1] == "moe" and path[2] in ("w_up", "w_gate", "w_down"))


@pytest.mark.parametrize("spec", [DENSE, EXPERTS], ids=["dense", "experts"])
def test_casts_the_leaves_kernel_of_would_and_no_other(spec):
    params, cfg = lm_spec_parts(spec)
    assert cfg.dtype == jnp.bfloat16
    held = _leaves(resident_params(params, cfg.dtype))
    handed = _leaves(params)
    assert held.keys() == handed.keys()
    cast = {p for p in handed if _cast_at_use(p)}
    experts = "num_experts" in spec
    assert len(cast) == cfg.n_layers * (5 if experts else 4)
    for path, leaf in handed.items():
        assert leaf.dtype == F32, path
        if path in cast:
            assert held[path].dtype == BF16, path
            # the rounding `astype` makes at use, made once
            np.testing.assert_array_equal(
                np.asarray(held[path], np.float32),
                np.asarray(leaf.astype(jnp.bfloat16), np.float32))
        else:
            # embedding, norms, router, head: the caller's own arrays
            assert held[path] is leaf, path
    kept = {p[2] if p[1] == "moe" else p[1] if p[0].startswith("block_")
            else p[0] for p in set(handed) - cast}
    assert kept == {"embed", "ln_attn", "ln_mlp", "ln_out", "lm_head"} | (
        {"router", "q_norm", "k_norm"} if experts else set())


def _same_leaves(a, b):
    a, b = _leaves(a), _leaves(b)
    assert a.keys() == b.keys()
    for path in a:
        if is_quantized(a[path]):
            assert a[path]["q"] is b[path]["q"], path
            assert a[path]["scale"] is b[path]["scale"], path
        else:
            assert a[path] is b[path], path


@pytest.mark.parametrize("case", [
    "stored_in_the_compute_dtype", "float32_under_float32",
    "bfloat16_under_float32", "int8", "int8_experts"])
def test_a_tree_that_needs_no_cast_comes_back_leaf_for_leaf(case):
    if case == "stored_in_the_compute_dtype":
        params, cfg = lm_spec_parts({**EXPERTS, "param_dtype": "bfloat16"})
    elif case == "float32_under_float32":
        params, cfg = lm_spec_parts({**DENSE, "dtype": "float32"})
    elif case == "bfloat16_under_float32":
        # stored narrower than computed: widened at use, never held wider
        params, cfg = lm_spec_parts(
            {**EXPERTS, "dtype": "float32", "param_dtype": "bfloat16"})
    else:
        params, cfg = lm_spec_parts(DENSE if case == "int8" else EXPERTS)
        params = quantize_lm_params(params)
    _same_leaves(resident_params(params, cfg.dtype), params)
    # and what is already resident stays so
    once = resident_params(lm_spec_parts(DENSE)[0], jnp.bfloat16)
    _same_leaves(resident_params(once, jnp.bfloat16), once)


def test_a_cast_leaf_keeps_its_sharding():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    params, cfg = lm_spec_parts(DENSE)
    col = NamedSharding(mesh, P(None, "tp"))
    params["block_0"]["qkv"]["kernel"] = jax.device_put(
        params["block_0"]["qkv"]["kernel"], col)
    held = resident_params(params, cfg.dtype)["block_0"]["qkv"]["kernel"]
    assert held.dtype == BF16
    assert held.sharding.is_equivalent_to(col, held.ndim)


def _prompts(lengths, vocab, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, n).astype(np.int32) for n in lengths]


def _serve(srv, prompts, budgets):
    rids = [srv.submit(p, max_new_tokens=b) for p, b in zip(prompts, budgets)]
    out = srv.run()
    return [[int(t) for t in out[r]] for r in rids]


def _server(mode, params, cfg):
    kw = dict(max_slots=4, max_len=64, chunk=8)
    if mode == "diffusion":
        kw["diffusion"] = BlockDiffusion(steps=2, mask_token_id=MASK)
    srv = LMServer(params, cfg, **kw)
    if mode == "speculation":
        srv.enable_spec_decode(3, draft_params=params, draft_cfg=cfg)
    return srv


@pytest.mark.parametrize("mode", ["chunk", "speculation", "diffusion"])
def test_serves_what_casting_at_use_served(mode):
    """A server built on a float32 tree under bfloat16 compute against
    the same server made to run its programs on the handed tree, as
    every server did before (each `kernel_of` casts inside the
    program): the same token ids, request for request."""
    params, cfg = lm_spec_parts(DIFFUSION if mode == "diffusion" else DENSE)
    vocab = MASK if mode == "diffusion" else cfg.vocab_size
    prompts = _prompts([5, 8, 11, 3, 6, 16], vocab)
    budgets = [10, 7, 9, 12, 4, 10]

    resident = _server(mode, params, cfg)
    assert resident.params["block_0"]["qkv"]["kernel"].dtype == BF16
    at_use = _server(mode, params, cfg)
    at_use.params = params
    if mode == "speculation":
        assert resident._spec.draft_params["block_1"]["proj"][
            "kernel"].dtype == BF16
        at_use._spec.draft_params = params
    got, want = (_serve(s, prompts, budgets) for s in (resident, at_use))
    assert [len(t) for t in got] == budgets
    assert got == want
    if mode == "speculation":
        # a self-draft proposes the target's own tokens in both forms
        assert resident.spec_stats()["accepted"] \
            == at_use.spec_stats()["accepted"] > 0


def _f32_to_bf16_converts(text):
    """The shapes of every float32 -> bfloat16 convert in a lowered
    program's text."""
    return {tuple(int(n) for n in m.group(1).split("x"))
            for m in re.finditer(
                r"stablehlo\.convert[^\n]*\(tensor<([0-9x]+)xf32>\)"
                r" -> tensor<\1xbf16>", text)}


@pytest.mark.parametrize("spec", [DENSE, EXPERTS], ids=["dense", "experts"])
def test_the_chunk_program_is_handed_bfloat16_matrices_and_casts_none(spec):
    params, cfg = lm_spec_parts(spec)
    srv = LMServer(params, cfg, max_slots=4, max_len=64, chunk=4)
    matrices = {leaf.shape for path, leaf in _leaves(params).items()
                if _cast_at_use(path)}
    vec = jax.ShapeDtypeStruct((srv.max_slots,), jnp.int32)

    def lowered(tree):
        return srv._chunk_fn.lower(tree, srv.cache, vec, vec, vec)

    mine = lowered(srv.params)
    operands = _leaves(mine.args_info[0][0])
    for path, leaf in _leaves(params).items():
        assert operands[path].dtype == (
            BF16 if _cast_at_use(path) else leaf.dtype), path
    assert not _f32_to_bf16_converts(mine.as_text()) & matrices
    # the handed tree, as every dispatch took it before: one cast a matrix
    assert _f32_to_bf16_converts(lowered(params).as_text()) >= matrices


def test_the_gauge_and_the_span_read_handed_and_resident_bytes():
    params, cfg = lm_spec_parts(DENSE)
    gauge = METRICS.gauge("lm_server_weight_bytes")
    handed = quantized_bytes(params)[0]
    matrices = sum(leaf.nbytes for path, leaf in _leaves(params).items()
                   if _cast_at_use(path))
    LMServer(params, cfg, max_slots=2, max_len=64, chunk=4)
    assert gauge.value(form="handed") == handed
    assert gauge.value(form="resident") == handed - matrices // 2
    span = TRACER.loop_spans("lm_weights_resident")[-1]
    assert span["lb"] == {"tree": "target", "handed_bytes": handed,
                          "resident_bytes": handed - matrices // 2}
    # a tree that is multiplied as handed: equal counts
    stored, cfg = lm_spec_parts({**EXPERTS, "param_dtype": "bfloat16"})
    LMServer(stored, cfg, max_slots=2, max_len=64, chunk=4)
    assert gauge.value(form="handed") == gauge.value(form="resident") \
        == quantized_bytes(stored)[0]


def test_kernel_of_returns_a_resident_leaf_as_it_is():
    params, cfg = lm_spec_parts(DENSE)
    held = resident_params(params, cfg.dtype)
    node = held["block_0"]["up"]
    assert kernel_of(node, cfg.dtype) is node["kernel"]
