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


@pytest.fixture
def chip_routes(monkeypatch):
    """The kernel switches ask the backend; this answers for the chip.
    `monkeypatch` puts the function back, but a trace made meanwhile
    keeps the route it took (`uses_decode_kernel` baked in) in jax's
    caches, where a later CPU lowering in this worker would find it
    (tests/test_parts.py did): clear what the test leaves."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    jax.clear_caches()


def compile_on_chip(topo, fn, *shapes):
    """Compile `fn` for one described chip; returns (text, seconds)."""
    one = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in shapes]
    t0 = time.monotonic()
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text, time.monotonic() - t0


SMOKE = (8, 16, 64, 4096)  # B, H, D, T: the LM phase's decode shapes
CELL = (16, 32, 128, 4096)  # the benchmark's mistral7b_widths_l8
HYBRID = (64, 32, 128, 4096)  # nemotron3_super_l11_ep4: 64 slots, 2 KV heads


@pytest.mark.parametrize("shape,kv,int8", [
    (SMOKE, 4, False), (SMOKE, 16, False), (SMOKE, 1, False),
    (SMOKE, 4, True),
    (CELL, 8, False),
    (HYBRID, 2, False),
    ((8, 16, 64, 1000), 4, True),  # a ragged last k-block, no padded copy
])
def test_decode_attention_compiles(topo, shape, kv, int8):
    from dml_tpu.ops.decode_attention import decode_attention

    B, H, D, T = shape
    # bf16 is what `rope` hands the kernel under a bf16 config
    q = ((B, 1, H, D),
         jnp.bfloat16 if shape in (CELL, HYBRID) else jnp.float32)
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


@pytest.mark.parametrize("n_q,mask_block,int8", [
    (4, 4, False),  # one block of 4: block diffusion's forwards
    (4, 1, False),  # causal rows: speculation's verify
    (4, 4, True),
])
def test_decode_attention_with_query_rows_compiles(
        topo, n_q, mask_block, int8):
    """The cache-attention kernel with several query rows a slot, at
    sdar30b_a3b_l6's grid: 32 slots x 4,096 rows, 32 heads / 4 KV x 128
    (n_q x 8 query rows a KV head)."""
    from dml_tpu.ops.decode_attention import decode_attention

    B, H, D, T, kv = 32, 32, 128, 4096, 4
    q = ((B, n_q, H, D), jnp.bfloat16)
    pos = ((B,), jnp.int32)
    if int8:
        cache, scale = ((B, kv, T, D), jnp.int8), ((B, kv, 1, T), jnp.float32)
        text, _ = compile_on_chip(
            topo,
            lambda q, k, ks, v, vs, p: decode_attention(
                q, k, v, p, k_scale=ks, v_scale=vs, interpret=False,
                mask_block=mask_block),
            q, cache, scale, cache, scale, pos,
        )
    else:
        cache = ((B, kv, T, D), jnp.bfloat16)
        text, _ = compile_on_chip(
            topo, functools.partial(decode_attention, interpret=False,
                                    mask_block=mask_block),
            q, cache, cache, pos,
        )
    assert "tpu_custom_call" in text


def test_block_causal_flash_attention_compiles(topo):
    from dml_tpu.ops.flash_attention import flash_attention

    x = ((2, 2048, 32, 128), jnp.bfloat16)
    text, _ = compile_on_chip(
        topo, lambda q, k, v: flash_attention(
            q, k, v, causal=True, mask_block=4, interpret=False), x, x, x)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", [True, False])
def test_expert_layer_compiles_to_grouped_matmuls(topo, request, kernel):
    """`expert_ffn` at sdar30b_a3b_l6's widths (128 gated experts of
    2048 -> 768, top-8, bf16) for the 128 tokens of a diffusion forward:
    on one TPU three calls of jax's Pallas grouped matmul; elsewhere
    `ragged_dot`, which the TPU compiler also takes (it lowers it to a
    grouped-matmul call of its own, fed by group metadata)."""
    from dml_tpu.inference.generate import expert_ffn

    if kernel:
        request.getfixturevalue("chip_routes")
    e, d, f = 128, 2048, 768
    text, _ = compile_on_chip(
        topo,
        lambda r, g, u, dn, y: expert_ffn(
            {"router": {"kernel": r}, "w_gate": g, "w_up": u, "w_down": dn},
            y, jnp.bfloat16, 8)[0],
        ((d, e), jnp.float32), ((e, d, f), jnp.bfloat16),
        ((e, d, f), jnp.bfloat16), ((e, f, d), jnp.bfloat16),
        ((32, 4, d), jnp.bfloat16),
    )
    assert text.count("tpu_custom_call") >= 3
    assert ("ragged-dot-metadata" in text) is not kernel


def _all_held(r, gt, u, dn, y):
    from dml_tpu.inference.generate import expert_ffn

    return expert_ffn(
        {"router": {"kernel": r}, "w_gate": gt, "w_up": u, "w_down": dn},
        y, jnp.bfloat16, 8)


def _a_share(r, b, gt, u, dn, sg, su, sd, y):
    from dml_tpu.inference.generate import expert_ffn

    return expert_ffn(
        {"router": {"kernel": r, "bias": b}, "w_gate": gt, "w_up": u,
         "w_down": dn, "shared_gate": {"kernel": sg},
         "shared_up": {"kernel": su}, "shared_down": {"kernel": sd}},
        y, jnp.bfloat16, 8, scoring="sigmoid", scale=2.5)


def _share_shapes(e, held, y, d=2048, f=768):
    bf = jnp.bfloat16
    return (((d, e), jnp.float32), ((e,), jnp.float32), ((held, d, f), bf),
            ((held, d, f), bf), ((held, f, d), bf), ((d, f), bf),
            ((d, f), bf), ((f, d), bf), (y + (d,), bf))


@pytest.mark.parametrize("fn,shapes,sha", [
    # sdar30b_a3b_l6: every expert held, a diffusion forward's 128 tokens
    (_all_held, (((2048, 128), jnp.float32),)
     + (((128, 2048, 768), jnp.bfloat16),) * 2
     + (((128, 768, 2048), jnp.bfloat16), ((32, 4, 2048), jnp.bfloat16)),
     "ae2472d56dcc6fcbc866d0f81e4880be811c0c2424afc9bf8209b5e39cfc1a1a"),
    # joyai_llm_flash_ep16's decode step: 16 slots x top-8 = one tile
    (_a_share, _share_shapes(256, 16, (16, 1)),
     "e8275a3558b86fd3988387d706286f5c60f3f33be5374b722136c3db47a0a042"),
])
def test_one_window_of_all_rows_lowers_to_the_text_it_had(
        topo, fn, shapes, sha):
    """Where `moe_window` gives all the rows (every expert held; the
    assignments one tile) `expert_ffn` is the program it was before
    windows: the sha256 of its lowered text for the described chip,
    `ragged_dot` form, is commit f8941f8's (PR 37 computed both sides
    with this very function; the Pallas form differs from any other
    commit's in the source lines its serialized kernel body carries)."""
    import hashlib

    one = SingleDeviceSharding(topo.devices[0])
    text = jax.jit(lambda *a: fn(*a)).lower(*(
        jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in shapes)
    ).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == sha


def test_expert_layer_over_windows_compiles_with_its_loop(topo, chip_routes):
    """A 1,024-token prefill row at joyai_llm_flash_ep16's widths (16 of
    256 gated experts held, top-8): windows of 640 rows under a loop whose
    trip count is read from the routing, three Pallas grouped matmuls in
    its body, and no [8,192, .] float32 array anywhere."""
    text, _ = compile_on_chip(
        topo, lambda *a: _a_share(*a), *_share_shapes(256, 16, (1, 1024)))
    assert text.count("tpu_custom_call") >= 3
    assert "f32[640,2048]" in text and "f32[8192," not in text


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
def test_tp_sharded_lm_programs_compile(topo, chip_routes, kv_quant):
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
    # every cache layout takes the kernel, each device on its own heads
    assert "tpu_custom_call" in text


def _chunk_program_text(topo, n_layers):
    """`LMServer._chunk_impl` itself at mistral7b_widths_l8's widths
    and slot grid, `n_layers` deep, compiled for the chip. A server
    cannot be built here (it allocates its cache on a device), so the
    method runs on a bare instance holding what it reads."""
    from dml_tpu.inference.generate import LMConfig, init_cache
    from dml_tpu.inference.lm_server import LMServer
    from dml_tpu.models.transformer import TransformerLM

    cfg = LMConfig(32000, 4096, 32, n_layers, 14336, dtype=jnp.bfloat16,
                   n_kv_heads=8)
    slots, max_len = 16, 4096
    srv = object.__new__(LMServer)
    srv.cfg, srv.max_len, srv.chunk, srv.temperature = cfg, max_len, 32, 0.0
    srv._mesh = None
    srv._routed, srv._held = (0, 0), (0, 0)  # no expert layer
    model = TransformerLM(
        vocab_size=cfg.vocab_size, d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_layers=cfg.n_layers, d_ff=cfg.d_ff, dtype=cfg.dtype,
        n_kv_heads=cfg.n_kv_heads,
    )
    one = SingleDeviceSharding(topo.devices[0])
    on_chip = functools.partial(jax.tree_util.tree_map, lambda s: (
        jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)))
    params = on_chip(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, slots, max_len)))
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    return jax.jit(srv._chunk_impl).lower(
        params, cache, vec, vec, vec).compile().as_text()


def test_chunk_program_of_a_grouped_bf16_config_holds_the_kernel(
        topo, chip_routes):
    """The benchmark cell's decode program (depth cut to 1 layer)
    compiles for the chip under the name the cell's `trace_modules`
    looks for and holds the cache-attention kernel: what
    `LMServer.kernel_report()["decode"]` reports on a chip."""
    text = _chunk_program_text(topo, 1)
    assert "tpu_custom_call" in text
    assert text.lstrip().startswith("HloModule jit__chunk_impl")


def test_chunk_program_makes_its_work_list_once_a_step(topo, chip_routes):
    """`decode_attention` builds its work list in every call, one a
    layer, from lengths that are a step's: the compiler merges the
    copies, so every layer's kernel takes the SAME count, lengths,
    slots and blocks (its four scalar-prefetch operands) and a step
    pays for one list, not one a layer."""
    import re

    text = _chunk_program_text(topo, 2)
    calls = re.findall(
        r"custom-call\(([^)]*)\)[^\n]*tpu_custom_call", text)
    assert len(calls) == 2
    lists = {tuple(c.split(", ")[:4]) for c in calls}
    assert len(lists) == 1, lists


def test_chunk_program_of_the_state_space_config_fits_the_chip(
        topo, chip_routes):
    """The third benchmark configuration's decode program —
    `LMServer._chunk_impl` at nemotron3_super_l11_ep4's published widths,
    pattern and slot grid (9.3 GB of weights, 64 slots of K/V rows, conv
    windows and float32 scan states) — compiles for the chip, updates
    the grid in place (its temporaries stay under 1 GiB beside 10 GiB of
    arguments) and holds its kernels: the cache-attention kernel of the
    one attention layer and two grouped matmuls an expert layer."""
    import json
    import os

    from dml_tpu.inference.generate import init_cache
    from dml_tpu.inference.lm_backend import lm_spec_parts
    from dml_tpu.inference.lm_server import LMServer

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "nemotron3_super_l11_ep4.json")) as f:
        spec = json.load(f)["lm_spec"]
    made = {}

    def declared():
        params, made["cfg"] = lm_spec_parts(spec)
        return params

    one = SingleDeviceSharding(topo.devices[0])
    on_chip = functools.partial(jax.tree_util.tree_map, lambda s: (
        jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)))
    params = on_chip(jax.eval_shape(declared))
    cfg = made["cfg"]
    slots, max_len = spec["max_slots"], spec["max_len"]
    srv = object.__new__(LMServer)
    srv.cfg, srv.max_len, srv.max_slots = cfg, max_len, slots
    srv.chunk, srv.temperature, srv._mesh = spec["chunk"], 0.0, None
    srv._routed = (cfg.layer_pattern.count("E"), spec["num_experts"])
    srv._held = (0, spec["experts_held"][1])
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, slots, max_len)))
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    compiled = jax.jit(srv._chunk_impl, donate_argnums=(1, 2, 3)).lower(
        params, cache, vec, vec, vec).compile()
    text = compiled.as_text()
    assert text.lstrip().startswith("HloModule jit__chunk_impl")
    assert text.count("tpu_custom_call") == 1 + 2 * 5
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes > 10 * 2 ** 30
    assert m.temp_size_in_bytes < 2 ** 30
    assert m.alias_size_in_bytes >= 1.5 * 2 ** 30  # the grid, in place


def test_diffusion_dispatch_of_the_sdar_config_holds_the_kernels(
        topo, chip_routes):
    """The other benchmark configuration's program: `LMServer.
    _diffuse_impl` itself at sdar30b_a3b_l6's widths and slot grid (depth
    cut to 1 layer, 2 blocks a dispatch), declared by `lm_spec_parts` as
    an operator's spec declares it. It compiles for the chip under the
    name the cell's `trace_modules` looks for, and holds the
    cache-attention kernel and the grouped matmuls."""
    from dml_tpu.inference.generate import init_cache
    from dml_tpu.inference.lm_backend import lm_spec_parts
    from dml_tpu.inference.lm_server import BlockDiffusion, LMServer

    spec = {
        "vocab_size": 151936, "d_model": 2048, "n_heads": 32,
        "n_kv_heads": 4, "head_dim": 128, "n_layers": 1,
        "rope_theta": 1e6, "qk_norm": True, "num_experts": 128,
        "experts_per_token": 8, "expert_d_ff": 768, "gated": True,
        "attention_mask": "block_causal", "block_length": 4,
        "denoising_steps": 2, "mask_token_id": 151669,
        "dtype": "bfloat16", "param_dtype": "bfloat16",
    }
    made = {}

    def declared():
        params, made["cfg"] = lm_spec_parts(spec)
        return params

    one = SingleDeviceSharding(topo.devices[0])
    on_chip = functools.partial(jax.tree_util.tree_map, lambda s: (
        jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)))
    params = on_chip(jax.eval_shape(declared))
    cfg = made["cfg"]
    slots, max_len = 32, 4096
    srv = object.__new__(LMServer)
    srv.cfg, srv.max_len, srv.max_slots = cfg, max_len, slots
    srv._mesh = None
    srv.diffusion = BlockDiffusion(steps=2, mask_token_id=151669)
    srv.blocks_per_dispatch = 2
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, slots, max_len)))
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    blk = jax.ShapeDtypeStruct((slots, 4), jnp.int32, sharding=one)
    text = jax.jit(srv._diffuse_impl).lower(
        params, cache, blk, vec, vec).compile().as_text()
    assert text.lstrip().startswith("HloModule jit__diffuse_impl")
    # attention and three grouped matmuls a forward, three forwards; the
    # commit forward reads no logits, so its LAST layer's expert matmuls
    # are dead code (its K/V rows depend on the layer's input alone)
    assert text.count("tpu_custom_call") == 4 * 3 - 3
    assert "ragged-dot" not in text



def test_decode_attention_over_a_shared_plane_compiles(topo):
    """Latent attention's absorbed form at joyai_llm_flash_ep16's grid: 16
    slots x 4,096 cached rows of 640 columns (576 values), all 32 heads'
    rows against ONE plane that is keys and, in its first 512 columns,
    values; one and four query rows a slot."""
    from dml_tpu.ops.decode_attention import decode_attention

    for n_q in (1, 4):
        text, _ = compile_on_chip(
            topo,
            lambda q, k, n: decode_attention(
                q, k, None, n, scale=192 ** -0.5, v_width=512,
                interpret=False),
            ((16, n_q, 32, 640), jnp.bfloat16),
            ((16, 1, 4096, 640), jnp.bfloat16), ((16,), jnp.int32))
        assert "tpu_custom_call" in text


def test_flash_attention_with_narrower_values_compiles(topo):
    """The expanded form's prefill at the published widths: keys of 192
    (128 | 64), values of 128, nothing padded to the other."""
    from dml_tpu.ops.flash_attention import flash_attention

    text, _ = compile_on_chip(
        topo, functools.partial(flash_attention, causal=True, interpret=False),
        ((1, 4096, 32, 192), jnp.bfloat16), ((1, 4096, 32, 192), jnp.bfloat16),
        ((1, 4096, 32, 128), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_latent_attention_programs_of_the_joyai_config_fit_the_chip(
        topo, chip_routes):
    """The fourth benchmark configuration's two programs at
    joyai_llm_flash_ep16's published widths, slot grid and chunk, the depth
    cut to the dense layer and two expert layers (the full depth compiles
    in 35 s and 70 s: `benchmark/tools`' way, PERF.md section 4):
    `LMServer._chunk_impl` holds the shared-plane kernel a layer and three
    grouped matmuls an expert layer, updates the grid in place and hands
    the cache on in the layout it took it in (no copy of a leaf: a
    576-column leaf was copied whole twice a dispatch); a 1 x 2,048
    prefill holds the flash kernel a layer."""
    import json
    import os

    from dml_tpu.inference.generate import init_cache, prefill
    from dml_tpu.inference.lm_backend import lm_spec_parts
    from dml_tpu.inference.lm_server import LMServer

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "joyai_llm_flash_ep16.json")) as f:
        spec = {**json.load(f)["lm_spec"], "n_layers": 3}
    made = {}

    def declared():
        params, made["cfg"] = lm_spec_parts(spec)
        return params

    one = SingleDeviceSharding(topo.devices[0])
    on_chip = functools.partial(jax.tree_util.tree_map, lambda s: (
        jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)))
    params = on_chip(jax.eval_shape(declared))
    cfg = made["cfg"]
    slots, max_len = spec["max_slots"], spec["max_len"]
    srv = object.__new__(LMServer)
    srv.cfg, srv.max_len, srv.max_slots = cfg, max_len, slots
    srv.chunk, srv.temperature, srv._mesh = spec["chunk"], 0.0, None
    srv._routed = (2, spec["num_experts"])
    srv._held = (0, spec["experts_held"][1])
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, slots, max_len)))
    leaf = cache["block_0"]["latent"]
    assert leaf.shape == (16, 1, 4096, 640)
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    compiled = jax.jit(srv._chunk_impl, donate_argnums=(1, 2, 3)).lower(
        params, cache, vec, vec, vec).compile()
    text = compiled.as_text()
    assert text.lstrip().startswith("HloModule jit__chunk_impl")
    assert text.count("tpu_custom_call") == 3 + 3 * 2
    assert "bf16[16,1,4096,640]{3,2,1,0:T(8,128)(2,1)} copy(" not in text
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 2 ** 28
    assert m.alias_size_in_bytes >= 3 * leaf.size * 2  # the grid, in place
    prompt = jax.ShapeDtypeStruct((1, 2048), jnp.int32, sharding=one)
    at = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one)
    text = jax.jit(
        lambda p, x, i: prefill(p, cfg, x, x.shape[1], logits_index=i)
    ).lower(params, prompt, at).compile().as_text()
    assert text.count("tpu_custom_call") == 3 + 3 * 2


def test_banded_flash_attention_compiles(topo):
    """The window layers' prefill kernel at laguna_xs2_ep16's widths: 64
    heads of 128 over a 4,096-token row, K and V repeated to the query
    heads as a caller without types would hand them, the band of 512 in
    pieces of 256 rows."""
    from dml_tpu.ops.flash_attention import flash_attention

    text, _ = compile_on_chip(
        topo, functools.partial(flash_attention, causal=True, window=512,
                                interpret=False),
        *[((1, 4096, 64, 128), jnp.bfloat16)] * 3)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("heads,window", [(64, 512), (48, None)])
def test_the_typed_layers_prefill_kernels_compile_by_kv_head(
        topo, heads, window):
    """laguna_xs2_ep16's two layer types as `generate.prefill` calls
    them, a 4,096-token row: 64 query heads under the band of 512 and 48
    under the causal mask, K and V with the 8 KV heads they have. The
    chip's compiler takes both, nowhere in the program is a K or a V of
    the query heads' count (no repeat, in or around the kernel), and a
    compile stays under 20 s (1.3-3.4 s here, PR 42)."""
    from dml_tpu.ops.flash_attention import flash_attention

    bf = jnp.bfloat16
    text, secs = compile_on_chip(
        topo, functools.partial(flash_attention, causal=True, window=window,
                                interpret=False),
        ((1, 4096, heads, 128), bf), ((1, 4096, 8, 128), bf),
        ((1, 4096, 8, 128), bf))
    assert text.count("tpu_custom_call") == 1
    # the repeat was a broadcast of [4096, 8, 128] to [4096, 8, g, 128]
    # ahead of the kernel (the parent's program holds two)
    repeated = [ln for ln in text.splitlines()
                if " broadcast(" in ln and "bf16[" in ln]
    assert not repeated, repeated[:2]
    assert secs < 20.0, f"{heads} heads, window {window}: {secs:.1f}s"


def test_window_attention_programs_of_the_laguna_config_fit_the_chip(
        topo, chip_routes):
    """The fifth benchmark configuration's two programs at
    laguna_xs2_ep16's published widths, slot grid and chunk, the depth cut
    to one period of its pattern (full, window x 3: the dense layer and
    three expert layers; the full depth compiles in 40 s and 50 s:
    `benchmark/tools/aot_memory_window.py`, PERF.md section 4):
    `LMServer._chunk_impl` holds the decode kernel a layer (6 query rows a
    KV head in the full layer, 8 in a window layer, whose plane is its
    512-row ring) and three grouped matmuls an expert layer, and updates
    planes and rings in place; a 1 x 4,096 prefill holds a flash kernel a
    layer, the window layers' banded."""
    import json
    import os

    from dml_tpu.inference.generate import init_cache, prefill
    from dml_tpu.inference.lm_backend import lm_spec_parts
    from dml_tpu.inference.lm_server import LMServer

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "laguna_xs2_ep16.json")) as f:
        spec = json.load(f)["lm_spec"]
    al = spec["attention_layers"]
    spec = {**spec, "n_layers": 4, "attention_layers": {
        **al, "layers": al["layers"][:4]}}
    made = {}

    def declared():
        params, made["cfg"] = lm_spec_parts(spec)
        return params

    one = SingleDeviceSharding(topo.devices[0])
    on_chip = functools.partial(jax.tree_util.tree_map, lambda s: (
        jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)))
    params = on_chip(jax.eval_shape(declared))
    cfg = made["cfg"]
    slots, max_len = spec["max_slots"], spec["max_len"]
    srv = object.__new__(LMServer)
    srv.cfg, srv.max_len, srv.max_slots = cfg, max_len, slots
    srv.chunk, srv.temperature, srv._mesh = spec["chunk"], 0.0, None
    srv._routed = (3, spec["num_experts"])
    srv._held = (0, spec["experts_held"][1])
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, slots, max_len)))
    assert cache["block_0"]["k"].shape == (16, 8, 4096, 128)
    assert cache["block_1"]["k_ring"].shape == (16, 8, 512, 128)
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    compiled = jax.jit(srv._chunk_impl, donate_argnums=(1, 2, 3)).lower(
        params, cache, vec, vec, vec).compile()
    text = compiled.as_text()
    assert text.lstrip().startswith("HloModule jit__chunk_impl")
    assert text.count("tpu_custom_call") == 4 + 3 * 3
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 2 ** 28
    grid = sum(x.size * 2 for x in jax.tree_util.tree_leaves(cache))
    assert m.alias_size_in_bytes >= grid  # planes and rings, in place
    prompt = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one)
    at = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one)
    compiled = jax.jit(
        lambda p, x, i: prefill(p, cfg, x, max_len, logits_index=i)
    ).lower(params, prompt, at).compile()
    assert compiled.as_text().count("tpu_custom_call") == 4 + 3 * 3
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 31


def test_both_attention_kernels_compile_at_heads_of_64(topo):
    """lfm2_8b_a1b_ep4's attention layers: 32 query heads over 8 KV heads
    of 64, half a lane tile. The prefill kernel takes a 2,048-token row by
    KV head as it is; the decode kernel takes the cache as the program
    lays it out (`LMConfig.kv_pack`: two heads a row of 128, a plane
    [128, 4, 4096, 128]) with the queries widened to the row
    (`packed_attention`), over the 128-slot grid."""
    from dml_tpu.inference import generate as G
    from dml_tpu.ops.decode_attention import decode_attention
    from dml_tpu.ops.flash_attention import flash_attention

    bf = jnp.bfloat16
    text, secs = compile_on_chip(
        topo, functools.partial(flash_attention, causal=True,
                                interpret=False),
        ((1, 2048, 32, 64), bf), ((1, 2048, 8, 64), bf),
        ((1, 2048, 8, 64), bf))
    assert text.count("tpu_custom_call") == 1
    assert secs < 20.0, f"flash at D 64: {secs:.1f}s"
    al = G.AttentionLayers((("a", G.AttentionType(32)),), ("a",))
    cfg = G.LMConfig(65536, 2048, 32, 1, 7168, dtype=bf, n_kv_heads=8,
                     d_head=64, attention_layers=al)
    assert cfg.kv_pack == 2

    def step(q, k, v, n):
        return G.packed_attention(
            cfg, lambda *a: decode_attention(*a, scale=64 ** -0.5,
                                             interpret=False), q, k, v, n)

    plane = ((128, 4, 4096, 128), bf)
    text, secs = compile_on_chip(
        topo, step, ((128, 1, 32, 64), bf), plane, plane,
        ((128,), jnp.int32))
    assert text.count("tpu_custom_call") == 1
    # no copy of a plane in or around the kernel
    assert "bf16[128,4,4096,128]{3,2,1,0} copy(" not in text
    assert secs < 20.0, f"decode at D 64, two heads a row: {secs:.1f}s"


def test_short_convolution_programs_of_the_lfm2_config_fit_the_chip(
        topo, chip_routes):
    """The sixth benchmark configuration's two programs at
    lfm2_8b_a1b_ep4's published widths, 128-slot grid and chunk, the depth
    cut to its first eight layers (two dense, conv conv attention conv
    twice over; the full depth compiles in 34 s and 25-28 s:
    `benchmark/tools/aot_memory_window.py`, PERF.md section 4):
    `LMServer._chunk_impl` holds the decode kernel an attention layer over
    planes of two heads a row and three grouped matmuls an expert layer,
    updates planes and convolution windows in place, and copies no plane
    (a [., 64]-column plane was laid out in 128 and copied whole every
    dispatch: 12 GiB of temporaries at full depth, PR 44); a 16 x 512
    prefill group holds a flash kernel an attention layer."""
    import json
    import os

    from dml_tpu.inference.generate import init_cache, prefill
    from dml_tpu.inference.lm_backend import lm_spec_parts
    from dml_tpu.inference.lm_server import LMServer

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "lfm2_8b_a1b_ep4.json")) as f:
        spec = json.load(f)["lm_spec"]
    al = spec["attention_layers"]
    assert al["layers"][:8] == ["conv", "conv", "full_attention", "conv"] * 2
    spec = {**spec, "n_layers": 8, "attention_layers": {
        **al, "layers": al["layers"][:8]}}
    made = {}

    def declared():
        params, made["cfg"] = lm_spec_parts(spec)
        return params

    one = SingleDeviceSharding(topo.devices[0])
    on_chip = functools.partial(jax.tree_util.tree_map, lambda s: (
        jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)))
    params = on_chip(jax.eval_shape(declared))
    assert "lm_head" not in params
    cfg = made["cfg"]
    slots, max_len = spec["max_slots"], spec["max_len"]
    srv = object.__new__(LMServer)
    srv.cfg, srv.max_len, srv.max_slots = cfg, max_len, slots
    srv.chunk, srv.temperature, srv._mesh = spec["chunk"], 0.0, None
    srv._routed = (6, spec["num_experts"])
    srv._held = (0, spec["experts_held"][1])
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, slots, max_len)))
    assert cache["block_0"]["conv"].shape == (128, 2, 2048)
    assert cache["block_2"]["k"].shape == (128, 4, 4096, 128)
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    compiled = jax.jit(srv._chunk_impl, donate_argnums=(1, 2, 3)).lower(
        params, cache, vec, vec, vec).compile()
    text = compiled.as_text()
    assert text.lstrip().startswith("HloModule jit__chunk_impl")
    assert text.count("tpu_custom_call") == 2 + 6 * 3
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 2 ** 28
    grid = sum(x.size * 2 for x in jax.tree_util.tree_leaves(cache))
    assert m.alias_size_in_bytes >= grid  # planes and windows, in place
    prompt = jax.ShapeDtypeStruct((16, 512), jnp.int32, sharding=one)
    at = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one)
    compiled = jax.jit(
        lambda p, x, i: prefill(p, cfg, x, max_len, logits_index=i)
    ).lower(params, prompt, at).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2 + 6 * 3
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30
