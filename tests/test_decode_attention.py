"""Pallas decode-step cache attention (ops/decode_attention.py):
parity with the einsum path it replaces on TPU, both cache forms,
per-slot lengths (live rows only: dead blocks skipped, empty slots
zero). Runs the Mosaic interpreter on the CPU test mesh (same
`interpret` convention as the flash kernel tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dml_tpu.ops.decode_attention import block_rows, decode_attention


def oracle(q, ck, cv, lengths):
    """The einsum route: slot b attends cache rows < lengths[b]."""
    b, _, h, d = q.shape
    kv, t = ck.shape[1], ck.shape[2]
    grp = h // kv
    valid = jnp.arange(t)[None, :] < lengths[:, None]
    qg = q.astype(jnp.float32).reshape(b, 1, kv, grp, d)
    s = jnp.einsum(
        "bqkgd,bktd->bkgqt", qg, ck.astype(jnp.float32)
    ) * (d ** -0.5)
    s = jnp.where(valid[:, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqt,bktd->bqkgd", p, cv.astype(jnp.float32))
    return o.reshape(b, 1, h, d)


def quantize(x):
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


@pytest.mark.parametrize("kv,h", [(2, 4), (1, 4), (4, 4)])
def test_parity_bf16(kv, h):
    """GQA / MQA / MHA head layouts against the einsum oracle."""
    b, t, d = 2, 40, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    ck = jax.random.normal(ks[1], (b, kv, t, d), jnp.float32)
    cv = jax.random.normal(ks[2], (b, kv, t, d), jnp.float32)
    lengths = jnp.asarray([t, 8], jnp.int32)
    got = decode_attention(q, ck, cv, lengths)
    want = oracle(q, ck, cv, lengths)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5
    )


def test_parity_int8_inline_dequant():
    b, kv, t, h, d = 2, 2, 64, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    ck = jax.random.normal(ks[1], (b, kv, t, d), jnp.float32)
    cv = jax.random.normal(ks[2], (b, kv, t, d), jnp.float32)
    lengths = jnp.asarray([t - 1, 12], jnp.int32)
    ckq, cks = quantize(ck)
    cvq, cvs = quantize(cv)
    got = decode_attention(
        q, ckq, cvq, lengths,
        k_scale=jnp.swapaxes(cks, 2, 3),
        v_scale=jnp.swapaxes(cvs, 2, 3),
    )
    want = oracle(
        q, ckq.astype(jnp.float32) * cks,
        cvq.astype(jnp.float32) * cvs, lengths,
    )
    # int8 path folds scales into score rows and dots via bf16 —
    # tolerance covers the summation-order difference, which is far
    # below the ~0.4% the quantization itself costs
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=5e-3
    )


def test_per_slot_positions_mask_stale_cache():
    """Cache rows past a slot's length must be invisible: garbage
    there cannot change the output (the continuous-batching contract
    — slots at different positions share one program)."""
    b, kv, t, h, d = 2, 2, 32, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    ck = jax.random.normal(ks[1], (b, kv, t, d), jnp.float32)
    cv = jax.random.normal(ks[2], (b, kv, t, d), jnp.float32)
    lengths = jnp.asarray([6, 21], jnp.int32)
    base = decode_attention(q, ck, cv, lengths)
    poisoned_k = ck.at[0, :, 6:].set(1e4).at[1, :, 21:].set(-1e4)
    poisoned_v = cv.at[0, :, 6:].set(7e3).at[1, :, 21:].set(-7e3)
    got = decode_attention(q, poisoned_k, poisoned_v, lengths)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(base), atol=1e-6
    )


def test_blocked_path_matches_single_block():
    """T spanning multiple k-blocks (online softmax across blocks)
    must equal the one-block result."""
    b, kv, t, h, d = 1, 2, 96, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    ck = jax.random.normal(ks[1], (b, kv, t, d), jnp.float32)
    cv = jax.random.normal(ks[2], (b, kv, t, d), jnp.float32)
    lengths = jnp.asarray([t], jnp.int32)
    one = decode_attention(q, ck, cv, lengths, block_k=128)
    many = decode_attention(q, ck, cv, lengths, block_k=32)
    np.testing.assert_allclose(
        np.asarray(many), np.asarray(one), atol=2e-5
    )


def test_validation_errors():
    q = jnp.zeros((2, 1, 4, 8))
    ck = jnp.zeros((2, 3, 16, 8))  # 4 heads % 3 kv != 0
    with pytest.raises(ValueError, match="not divisible"):
        decode_attention(q, ck, ck, jnp.zeros(2, jnp.int32))
    ok = jnp.zeros((2, 2, 16, 8))
    # several query rows a slot are legal since PR 28; a block mask that
    # does not divide them is not
    with pytest.raises(ValueError, match="query rows under blocks"):
        decode_attention(
            jnp.zeros((2, 3, 4, 8)), ok, ok, jnp.zeros(2, jnp.int32),
            mask_block=2,
        )
    with pytest.raises(ValueError, match="both k_scale"):
        decode_attention(
            q, ok, ok, jnp.zeros(2, jnp.int32),
            k_scale=jnp.zeros((2, 2, 1, 16)),
        )


# -- per-slot lengths: only live k-blocks are read -----------------------

BK = 32  # k-block rows in the cases below (block_k; T spans 3 blocks)


def _case(seed, b, kv, h, t, d, cache):
    """q and a cache in one of the three storage forms; returns
    (q, kernel args, kernel kwargs, the f32 cache the oracle reads)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    dt = jnp.float32 if cache == "f32" else jnp.bfloat16
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32).astype(dt)
    ck = jax.random.normal(ks[1], (b, kv, t, d), jnp.float32).astype(dt)
    cv = jax.random.normal(ks[2], (b, kv, t, d), jnp.float32).astype(dt)
    if cache != "int8":
        return q, (ck, cv), {}, (ck, cv)
    ckq, cks = quantize(ck)
    cvq, cvs = quantize(cv)
    kw = {"k_scale": jnp.swapaxes(cks, 2, 3),
          "v_scale": jnp.swapaxes(cvs, 2, 3)}
    deq = (ckq.astype(jnp.float32) * cks, cvq.astype(jnp.float32) * cvs)
    return q, (ckq, cvq), kw, deq


# f32 differs by summation order; bf16 rounds p for the p.v dot; int8
# folds its scales into bf16-dotted rows (as test_parity_int8 above)
ATOL = {"f32": 2e-5, "bf16": 1e-2, "int8": 2e-2}


@pytest.mark.parametrize("cache", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("kv,h", [(2, 8), (8, 32)])
def test_ragged_lengths_match_oracle(kv, h, cache):
    """The benchmark cell's grouping (4 query heads a KV head) with
    every slot at its own length, empty slots at the head, in the
    middle and at the tail of the grid."""
    t, d = 3 * BK, 16
    lengths = jnp.asarray([0, 0, 5, BK, 0, t, 1, 2 * BK + 7, 0], jnp.int32)
    b = lengths.shape[0]
    q, kvs, kw, deq = _case(10, b, kv, h, t, d, cache)
    got = np.asarray(decode_attention(q, *kvs, lengths, block_k=BK, **kw))
    want = np.asarray(oracle(q, *deq, lengths))
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(got[live], want[live], atol=ATOL[cache])
    # an empty slot attends nothing: zeros, not the softmax of nothing
    assert np.array_equal(got[~live], np.zeros_like(got[~live]))


@pytest.mark.parametrize("n", [BK - 1, BK, BK + 1, 3 * BK])
def test_lengths_at_block_edges(n):
    """One row short of a block, a whole block, one row into the next
    block, and the whole cache."""
    b, kv, h, t, d = 2, 2, 8, 3 * BK, 16
    lengths = jnp.asarray([n, 1], jnp.int32)
    q, kvs, kw, deq = _case(11, b, kv, h, t, d, "f32")
    got = decode_attention(q, *kvs, lengths, block_k=BK)
    want = oracle(q, *deq, lengths)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5
    )


def test_all_slots_empty_returns_finite_zeros():
    q, kvs, _, _ = _case(12, 3, 2, 8, 3 * BK, 16, "bf16")
    got = np.asarray(decode_attention(
        q, *kvs, jnp.zeros(3, jnp.int32), block_k=BK))
    assert got.dtype == np.float32 and np.array_equal(
        got, np.zeros_like(got))


@pytest.mark.parametrize("cache", ["bf16", "int8"])
def test_nan_past_length_leaves_output_bit_equal(cache):
    """NaN in EVERY row at or past each slot's length — the tail of
    the partly-live block, whole skipped blocks, whole empty slots —
    changes no bit of the output (a select, not a multiply by 0)."""
    t, d, kv, h = 3 * BK, 16, 2, 8
    lengths = jnp.asarray([0, 5, BK, BK + 1, 0, t - 1], jnp.int32)
    q, kvs, kw, _ = _case(13, lengths.shape[0], kv, h, t, d, cache)
    base = decode_attention(q, *kvs, lengths, block_k=BK, **kw)
    dead = jnp.arange(t)[None, :] >= lengths[:, None]  # [B, T]
    if cache == "int8":  # int8 holds no NaN: its scales do
        kw = {k: jnp.where(dead[:, None, None, :], jnp.nan, s)
              for k, s in kw.items()}
        kvs = tuple(jnp.where(dead[:, None, :, None], 127, x) for x in kvs)
    else:
        kvs = tuple(
            jnp.where(dead[:, None, :, None], jnp.nan, x) for x in kvs)
    got = decode_attention(q, *kvs, lengths, block_k=BK, **kw)
    assert np.isfinite(np.asarray(got)).all()
    assert np.array_equal(np.asarray(got), np.asarray(base))


def test_ragged_last_block_needs_no_padding():
    """T that no block size divides: the out-of-range rows of the
    last block lie past every length."""
    b, kv, h, t, d = 2, 2, 8, 2 * BK + 8, 16
    lengths = jnp.asarray([t, BK + 3], jnp.int32)
    q, kvs, _, deq = _case(14, b, kv, h, t, d, "f32")
    got = decode_attention(q, *kvs, lengths, block_k=BK)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(oracle(q, *deq, lengths)), atol=2e-5
    )


def test_block_rows_at_the_cell_widths():
    """KV 8 x D 128 x bf16: ~1 MB a stream is 512 rows, so a live slot
    at the cells' median length (~350 rows) is one block of eight."""
    assert block_rows(8, 128, jnp.bfloat16, 4096) == 512
    assert block_rows(8, 128, jnp.int8, 4096) == 512  # bf16 temporaries
    assert block_rows(1, 64, jnp.bfloat16, 4096) == 2048  # block_k
    assert block_rows(2, 16, jnp.float32, 40) == 40  # never past T
