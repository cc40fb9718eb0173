"""Pallas kernels (dml_tpu.ops) vs their pure-JAX oracles.

Runs in interpreter mode on the CPU test mesh (the kernels
auto-select `interpret=True` off-TPU); the same code compiles via
Mosaic on the real chip.
"""

import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dml_tpu.models.preprocess import normalize_on_device
from dml_tpu.ops import flash_attention, fused_normalize
from dml_tpu.parallel.ring_attention import reference_attention

# the module (`dml_tpu.ops.flash_attention` names the function it exports)
fa = importlib.import_module("dml_tpu.ops.flash_attention")


def _qkv(b=2, t=128, h=2, d=32, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, t, h, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_unpadded_vs_padded_seq():
    # T=100 forces q/k padding (blocks of 64); result must match the
    # oracle on the true rows
    q, k, v = _qkv(t=100)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_multiblock_noncausal_cross():
    # cross-attention: kv longer than q, non-causal
    b, h, d = 2, 2, 32
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(kq, (b, 64, h, d))
    k = jax.random.normal(kk, (b, 192, h, d))
    v = jax.random.normal(kv_, (b, 192, h, d))
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64)
    ref = reference_attention(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_gradients(causal):
    q, k, v = _qkv(b=1, t=96, h=2, d=32, seed=3)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
        return jnp.sum(jnp.sin(o.astype(jnp.float32)))

    def loss_ref(q, k, v):
        o = reference_attention(q, k, v, causal=causal)
        return jnp.sum(jnp.sin(o.astype(jnp.float32)))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            gf, gr, atol=5e-5, rtol=5e-4, err_msg=f"d{name} mismatch"
        )


def test_flash_bf16_io():
    q, k, v = _qkv(dtype=jnp.bfloat16, seed=5)
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        out.astype(np.float32), ref.astype(np.float32), atol=2e-2
    )


def test_flash_under_jit():
    q, k, v = _qkv(t=64)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    np.testing.assert_allclose(
        f(q, k, v), reference_attention(q, k, v, causal=True),
        atol=2e-5, rtol=2e-5,
    )


def test_flash_lse_values_and_merge_identity():
    """flash_attention_lse: lse matches logsumexp of the true scores,
    and merging two KV halves via the (out, lse) recurrence equals
    attention over the full KV — the ring-attention contract."""
    from dml_tpu.ops.flash_attention import flash_attention_lse

    q, k, v = _qkv(b=1, t=64, h=2, d=32, seed=9)
    out, lse = flash_attention_lse(q, k, v, causal=False,
                                   block_q=32, block_k=32)
    ref = reference_attention(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (32 ** -0.5)
    np.testing.assert_allclose(
        lse, jax.nn.logsumexp(s, axis=-1), atol=2e-5, rtol=2e-5
    )
    # two-block merge
    o1, l1 = flash_attention_lse(q, k[:, :32], v[:, :32], causal=False,
                                 block_q=32, block_k=32)
    o2, l2 = flash_attention_lse(q, k[:, 32:], v[:, 32:], causal=False,
                                 block_q=32, block_k=32)
    m = jnp.maximum(l1, l2)
    a1, a2 = jnp.exp(l1 - m), jnp.exp(l2 - m)
    w1 = jnp.einsum("bhq->bqh", a1 / (a1 + a2))[..., None]
    merged = o1 * w1 + o2 * (1 - w1)
    np.testing.assert_allclose(merged, ref, atol=2e-5, rtol=2e-5)


def test_flash_lse_gradients_include_lse_cotangent():
    """Loss depending on BOTH outputs (out and lse) must match the
    oracle gradient — exercises the p*g_lse term in the backward."""
    from dml_tpu.ops.flash_attention import flash_attention_lse

    q, k, v = _qkv(b=1, t=64, h=2, d=32, seed=11)

    def loss_flash(q, k, v):
        o, lse = flash_attention_lse(q, k, v, causal=False,
                                     block_q=32, block_k=32)
        return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(lse))

    def loss_ref(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (32 ** -0.5)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        return jnp.sum(jnp.sin(o)) + jnp.sum(
            jnp.cos(jax.nn.logsumexp(s, axis=-1))
        )

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            a, b, atol=5e-5, rtol=5e-4, err_msg=f"d{name} mismatch"
        )


@pytest.mark.parametrize("mode", ["caffe", "tf", "unit"])
def test_fused_normalize_matches_oracle(mode):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(0, 256, size=(3, 17, 24, 3), dtype=np.uint8))
    got = fused_normalize(x, mode, dtype=jnp.float32, block_rows=16)
    want = normalize_on_device(x, mode, jnp.float32)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_fused_normalize_bf16_and_raw():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randint(0, 256, size=(2, 8, 8, 3), dtype=np.uint8))
    got = fused_normalize(x, "tf", dtype=jnp.bfloat16)
    assert got.dtype == jnp.bfloat16 and got.shape == x.shape
    raw = fused_normalize(x, "raw", dtype=jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(raw, np.float32), np.asarray(x, np.float32)
    )


def test_lm_uses_flash_when_not_seq_sharded():
    # sp=1 mesh: make_lm routes attention through the flash kernel
    # under shard_map (dp batch, tp heads); loss must be finite and the
    # step must actually update params
    from dml_tpu.parallel.long_context import LongContextLM
    from dml_tpu.parallel.mesh import local_mesh

    mesh = local_mesh(dp=4, tp=2, sp=1)
    lm = LongContextLM(
        mesh, seq_len=64, vocab_size=128, d_model=64, n_heads=4,
        n_layers=2, d_ff=128, dtype=jnp.float32,
    )
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, 128, size=(4, 64), dtype=np.int32)
    l1 = lm.train_step(tokens)
    l2 = lm.train_step(tokens)
    assert np.isfinite(l1) and np.isfinite(l2) and l2 < l1


def test_normalize_sharded_mesh_path_compiles(monkeypatch):
    """The shard_map(pallas) branch of normalize_sharded — the REAL
    TPU mesh path the Trainer takes — exercised on the CPU mesh via
    interpret mode (regression: jax>=0.8's shard_map rejects a
    pallas_call out_shape under its default check_vma=True, which
    crashed the on-chip train bench while every CPU test silently
    took the jnp fallback)."""
    import numpy as np

    from dml_tpu.ops import preprocess as pre

    monkeypatch.setattr(pre.jax, "default_backend", lambda: "tpu")
    # force the pallas kernel to interpret on CPU
    monkeypatch.setattr(pre, "_interpret_default", lambda: True)
    from dml_tpu.parallel.mesh import local_mesh

    mesh = local_mesh(dp=jax.device_count())
    x = jnp.asarray(
        np.random.RandomState(0).randint(
            0, 255, (jax.device_count() * 2, 8, 8, 3), np.uint8
        )
    )
    got = pre.normalize_sharded(x, "tf", jnp.float32, mesh)
    want = normalize_on_device(x, "tf", jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


# ---------------------------------------------------------------------------
# the flash forward kernel: the calls of the untyped stacks are what they
# were; the live-block kernel of the typed ones (K and V by KV head, the
# band, masks where an edge crosses)
# ---------------------------------------------------------------------------

def forward_kernels(fn, *shapes):
    """The `pallas_call`s of fn's JAXPR, each as text: the grid, every
    block's shape and index map, the kernel's body (no source lines, so
    the text of one commit can be held against another's)."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                gm = eqn.params["grid_mapping"]
                found.append("\n".join(
                    [f"grid {gm.grid}"]
                    + [f"{bm.block_shape} {bm.index_map_jaxpr}"
                       for bm in gm.block_mappings]
                    + [str(eqn.params["jaxpr"])]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*(
        jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes)).jaxpr)
    return found


_X = (1, 2048, 32, 128)


@pytest.mark.parametrize("kwargs,shapes,sha", [
    # mistral7b_widths_l8, nemotron3_super_l11_ep4: causal, K and V
    # repeated to the query heads by the caller
    ({}, (_X, _X, _X),
     "9841d62fa9a393222bda62b264209446bf92194eb0904078e25e8f90dde7898c"),
    # sdar30b_a3b_l6: block-causal, blocks of 4
    ({"mask_block": 4}, (_X, _X, _X),
     "71b10cfc4921420e3289646e8960c5e42e2317b51cdd2736aa2e3fc8f8cc8328"),
    # joyai_llm_flash_ep16: keys of 192, values of 128, a K and V a head
    ({}, ((1, 2048, 32, 192), (1, 2048, 32, 192), _X),
     "9f49c9e4b2d70dc9cc1ceda5fd89cc550a68ef319a4403ebfe3157eb0bcb559a"),
])
def test_the_untyped_forward_kernels_trace_to_the_jaxpr_they_had(
        kwargs, shapes, sha):
    """The forward kernel of a call with a K and V a query head and no
    window is the program it was before the live-block kernel: the
    sha256 of its JAXPR (grid, index maps, body) is commit a08a29f's,
    which PR 42 computed with this very function. (The lowered text
    cannot be held: a Pallas kernel's serialized body carries source
    lines: `tests/test_tpu_compile.py`.)"""
    kernels = forward_kernels(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False, **kwargs), *shapes)
    assert len(kernels) == 1
    assert hashlib.sha256(kernels[0].encode()).hexdigest() == sha


def _grouped_qkv(t, g, kv=2, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (2, t, kv * g, d)),
            jax.random.normal(ks[1], (2, t, kv, d)),
            jax.random.normal(ks[2], (2, t, kv, d)))


def _masked_softmax(q, k, v, window=None):
    """The oracle: every score, the mask on the whole matrix."""
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    t = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * q.shape[-1] ** -0.5
    back = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    keep = back >= 0 if window is None else (back >= 0) & (back < window)
    return jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(jnp.where(keep, s, -1e30), -1), v,
                      precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("window", [None, 24])
@pytest.mark.parametrize("g", [1, 4, 6, 8])
def test_k_and_v_by_kv_head_equal_the_repeated_call_bit_for_bit(g, window):
    """K and V handed with the KV heads they have against the same K and
    V repeated to the query heads, float32, several blocks, a pad tail
    (80 positions at blocks of 32): the same bits. Without a window the
    repeated call is the untyped kernel (a mask in every block, a block
    above the diagonal a skipped step), so a block the served kernel
    leaves unmasked is one the mask left whole."""
    q, k, v = _grouped_qkv(80, g, seed=g)
    blocks = dict(block_q=32, block_k=32, window=window)
    got = flash_attention(q, k, v, **blocks)
    rep = flash_attention(q, jnp.repeat(k, g, axis=2),
                          jnp.repeat(v, g, axis=2), **blocks)
    assert np.array_equal(got, rep)
    np.testing.assert_allclose(got, _masked_softmax(q, k, v, window),
                               atol=2e-6)


@pytest.mark.parametrize("t,bq,bk", [
    (100, 16, 32), (100, 32, 16), (64, 16, 64), (70, 64, 16), (37, 1024, 1024),
])
def test_a_full_layers_grouped_call_at_blocks_that_are_not_square(t, bq, bk):
    q, k, v = _grouped_qkv(t, 6, seed=t)
    got = flash_attention(q, k, v, block_q=bq, block_k=bk)
    np.testing.assert_allclose(got, _masked_softmax(q, k, v), atol=2e-6)


@pytest.mark.parametrize("t,window,bq,bk", [
    (64, 16, 16, 16), (100, 16, 32, 32), (96, 40, 16, 16), (96, 48, 16, 16),
    (70, 200, 32, 32), (30, 8, 8, 8),            # the band, square blocks
    (100, None, 16, 32), (100, None, 32, 16), (64, None, 16, 64),
    (4096, 512, 256, 256), (4096, None, 256, 1024),  # laguna's
])
def test_a_block_left_unmasked_is_one_the_mask_leaves_whole(
        t, window, bq, bk):
    """Every (q-block, k-block) against the positions: a block the
    served kernels compute without a mask holds no pair the rule drops,
    a block they do not compute holds no pair it keeps, and a block they
    compute holds one. The band's blocks by `_band_edges` (piece j of
    q-block i is k-block i - (pieces - 1) + j), a full layer's by the
    diagonal test of `_live_fwd_kernel`."""
    nq, nk = -(-t // bq), -(-t // bk)
    pieces = None if window is None else min(nq, fa.band_blocks(window, bq))
    edges = None if window is None else fa._band_edges(window, bq, pieces)
    for i in range(nq):
        rows = np.arange(i * bq, i * bq + bq)[:, None]
        for j in range(nk):
            back = rows - np.arange(j * bk, j * bk + bk)[None, :]
            keep = back >= 0 if window is None else (
                (back >= 0) & (back < window))
            if window is None:
                computed = j * bk <= i * bq + bq - 1
                masked = j * bk + bk - 1 > i * bq
            else:
                piece = j - (i - (pieces - 1))
                computed = 0 <= piece < pieces
                masked = computed and edges[piece]
            assert computed == bool(keep.any()), (i, j)
            if computed and not masked:
                assert keep.all(), (i, j)
    if window is not None:
        # and the counters a server's span labels carry are this walk
        visited = sum(min(i + 1, pieces) for i in range(nq))
        assert fa.band_visits(t, window, bq) == (visited, nq * (nq + 1) // 2)
        assert fa.band_masked(t, window, bq) == sum(
            edges[j - (i - (pieces - 1))] for i in range(nq)
            for j in range(max(0, i - pieces + 1), i + 1))


def test_kv_heads_that_do_not_divide_raise_and_the_backward_refuses():
    q, k, v = _grouped_qkv(16, 3)
    with pytest.raises(ValueError, match="KV heads divide the query heads"):
        flash_attention(q[:, :, :5], k, v)
    with pytest.raises(ValueError, match="KV heads divide the query heads"):
        flash_attention(q, k, v[:, :, :1])
    # K and V by KV head is the served form: forward only, causal
    with pytest.raises(NotImplementedError, match="by KV head"):
        jax.grad(lambda x: flash_attention(x, k, v).sum())(q)
    with pytest.raises(NotImplementedError, match="know no window"):
        jax.grad(lambda x: flash_attention(
            x, k, v, window=4).sum())(q)
    with pytest.raises(ValueError, match="band is causal"):
        flash_attention(q, k, v, causal=False)
    with pytest.raises(ValueError, match="band is causal"):
        flash_attention(q, k, v, mask_block=4)
