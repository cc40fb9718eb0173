"""Ahead-of-time compiles for a DESCRIBED TPU v5e (no chip attached):
the main path's Pallas kernels at their real widths, `interpret=False`,
through the chip's own compiler. Interpret-mode tests cannot see what
Mosaic refuses (unaligned slices, VMEM overrun, a kernel GSPMD cannot
partition); these can, at ~1-3 s each and no chip time. A compile that
passes is not a chip run — results and times come from `chip_smoke.py`.

Describing the chip takes libtpu's process lock, and a second process
that asks while one holds it is refused. No chip is attached or
contended here, so the module tells libtpu to allow it (test workers
may run side by side); where the topology still cannot be had, the
whole module skips. The persistent compile cache is off around it (an
entry written for a described chip cannot be read back without one,
and warns)."""

import functools
import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")  # compile-only use

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here, or it refused
        pytest.skip(f"cannot describe a TPU v5e topology here: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compile_on_chip(topo, fn, *shapes):
    """Compile `fn` for one described chip; returns (text, seconds)."""
    one = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in shapes]
    t0 = time.monotonic()
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text, time.monotonic() - t0


B, H, D, T = 8, 16, 64, 4096  # the LM phase's decode shapes


@pytest.mark.parametrize("kv,int8", [(4, False), (16, False), (1, False),
                                     (4, True)])
def test_decode_attention_compiles(topo, kv, int8):
    from dml_tpu.ops.decode_attention import decode_attention

    q = ((B, 1, H, D), jnp.float32)
    pos = ((B,), jnp.int32)
    if int8:
        cache, scale = ((B, kv, T, D), jnp.int8), ((B, kv, 1, T), jnp.float32)
        text, _ = compile_on_chip(
            topo,
            lambda q, k, ks, v, vs, p: decode_attention(
                q, k, v, p, k_scale=ks, v_scale=vs, interpret=False),
            q, cache, scale, cache, scale, pos,
        )
    else:
        cache = ((B, kv, T, D), jnp.bfloat16)
        text, _ = compile_on_chip(
            topo, functools.partial(decode_attention, interpret=False),
            q, cache, cache, pos,
        )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("backward", [False, True])
def test_flash_attention_compiles(topo, backward):
    from dml_tpu.ops.flash_attention import flash_attention

    x = ((4, 2048, 16, 64), jnp.bfloat16)

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else fwd
    text, _ = compile_on_chip(topo, fn, x, x, x)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape,mode", [
    ((32, 224, 224, 3), "caffe"),  # ResNet50 serving
    ((32, 299, 299, 3), "tf"),     # InceptionV3: 299 rows, a ragged block
    ((8, 380, 380, 3), "unit"),    # EfficientNet-B4
])
def test_fused_normalize_compiles_fast(topo, shape, mode):
    """The [N*H, W*3] view this kernel once took cost 38 s of XLA
    compile at [32, 299, 299, 3] (223 s at batch 128): the uint8
    relayout, not the kernel. The [N, H, W*3] view must stay cheap."""
    from dml_tpu.ops.preprocess import fused_normalize

    text, secs = compile_on_chip(
        topo, lambda x: fused_normalize(x, mode, interpret=False),
        (shape, jnp.uint8),
    )
    assert "tpu_custom_call" in text
    assert secs < 10.0, f"{shape} {mode} took {secs:.1f}s to compile"


@pytest.mark.parametrize("kv_quant", [False, True])
def test_tp_sharded_lm_programs_compile(topo, monkeypatch, kv_quant):
    """The LM's prefill and decode step on a tp=4 mesh of the described
    chips, LM-phase widths (depth cut to 2 layers). GSPMD refuses to
    partition a Mosaic kernel, so both kernels must sit in a shard_map
    — the CPU mesh, where kernels interpret to plain ops, never asked."""
    from dml_tpu.config import MeshSpec
    from dml_tpu.inference.generate import (
        LMConfig, batched_decode_step, init_cache, prefill,
    )
    from dml_tpu.models.transformer import TransformerLM
    from dml_tpu.parallel.mesh import make_mesh
    from dml_tpu.parallel.sharding import partition_params

    # the kernel switches ask the backend; the test answers for the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = make_mesh(MeshSpec(dp=1, tp=4), devices=list(topo.devices))
    cfg = LMConfig(32000, 1024, 16, 2, 4096, dtype=jnp.bfloat16,
                   n_kv_heads=4, kv_quant=kv_quant)
    model = TransformerLM(
        vocab_size=cfg.vocab_size, d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_layers=cfg.n_layers, d_ff=cfg.d_ff, dtype=cfg.dtype,
        n_kv_heads=cfg.n_kv_heads,
    )
    pshape = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    params = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        pshape, partition_params(pshape, mesh),
    )
    heads = NamedSharding(mesh, P(None, "tp"))
    cache = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=heads),
        jax.eval_shape(lambda: init_cache(cfg, 8, 4096)),
    )
    rep = NamedSharding(mesh, P())
    vec = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=rep)
    prompt = jax.ShapeDtypeStruct((8, 64), jnp.int32, sharding=rep)

    text = jax.jit(
        lambda p, x, i: prefill(p, cfg, x, 4096, logits_index=i, mesh=mesh)
    ).lower(params, prompt, vec).compile().as_text()
    assert "tpu_custom_call" in text and "all-gather" in text

    text = jax.jit(
        lambda p, c, t, q: batched_decode_step(p, cfg, c, t, q, mesh=mesh)
    ).lower(params, cache, vec, vec).compile().as_text()
    # grouped bf16 caches stay on the einsum; int8 caches take the kernel
    assert ("tpu_custom_call" in text) == kv_quant
