"""Pallas decode-step cache attention (ops/decode_attention.py):
parity with the einsum path it replaces on TPU, both cache forms,
per-slot lengths (live rows only: dead blocks skipped, empty slots
zero). Runs the Mosaic interpreter on the CPU test mesh (same
`interpret` convention as the flash kernel tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dml_tpu.ops.decode_attention import (
    block_rows, decode_attention, work_list,
)


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


@pytest.mark.parametrize("lengths", [
    [0, 0, 5, BK, 0, 3 * BK, 1, 2 * BK + 7, 0],  # the ragged case's
    [BK - 1, BK, BK + 1],
    [0, 0, 2 * BK],  # empty slots at the head
    [3 * BK, 0, 0],  # and at the tail
    [1],
])
def test_work_list_by_hand(lengths):
    """`sum cdiv(len, bk)` items in slot order, a slot's first item its
    block 0 and its last `cdiv(len, bk) - 1`, nothing for an empty
    slot; the room past the count repeats the last item, so the
    pipeline's look-ahead names a block it already holds."""
    room = len(lengths) * 3
    slot_of, block_of, n = jax.jit(
        lambda x: work_list(x, BK, room))(jnp.asarray(lengths, jnp.int32))
    slot_of, block_of, n = np.asarray(slot_of), np.asarray(block_of), int(n)
    want = [(s, j) for s, x in enumerate(lengths) for j in range(-(-x // BK))]
    assert n == len(want) == sum(-(-x // BK) for x in lengths)
    assert list(zip(slot_of[:n], block_of[:n])) == want
    assert slot_of.shape == block_of.shape == (room,)
    assert (slot_of[n:] == slot_of[n - 1]).all()
    assert (block_of[n:] == block_of[n - 1]).all()
    for s, x in enumerate(lengths):
        mine = block_of[:n][slot_of[:n] == s]
        if x == 0:
            assert mine.size == 0
        else:  # first and last marks, as the kernel reads them
            assert mine[0] == 0 and (mine[-1] + 1) * BK >= x > mine[-1] * BK


def test_work_list_of_an_empty_grid_is_one_dead_block():
    """Every slot empty: one item of no live rows (a slot of length 0,
    block 0), so the grid has a step and the kernel an output."""
    slot_of, block_of, n = work_list(jnp.zeros(4, jnp.int32), BK, 12)
    assert int(n) == 1
    assert (np.asarray(block_of) == 0).all()
    assert (np.asarray(slot_of) == 3).all()  # any slot: all are empty


def test_work_list_under_jit_feeds_a_traced_grid():
    """The grid's bound is a traced scalar: one compiled program serves
    every set of lengths, and agrees with the eager call."""
    q, kvs, _, deq = _case(15, 4, 2, 8, 3 * BK, 16, "f32")
    fn = jax.jit(lambda n: decode_attention(q, *kvs, n, block_k=BK))
    for lengths in ([0, 5, 0, 3 * BK], [BK + 1, 0, 0, 0], [0, 0, 0, 0]):
        n = jnp.asarray(lengths, jnp.int32)
        got = np.asarray(fn(n))
        live = np.asarray(lengths) > 0
        np.testing.assert_allclose(
            got[live], np.asarray(oracle(q, *deq, n))[live], atol=2e-5)
        assert np.array_equal(got[~live], np.zeros_like(got[~live]))
    assert fn._cache_size() == 1


def test_block_rows_at_the_cell_widths():
    """The power of two nearest 1 MB of copies a step (K and V, or the
    one shared plane), at the four cells' shapes: what measured
    fastest at the cells' lengths AND no slower than the parent's
    blocks at full context (`block_rows` has the readings). A live
    slot at the dense cells' median length (~350 rows) is two blocks
    of 256, and only its live blocks are grid steps."""
    bf16 = jnp.bfloat16
    assert block_rows(8, 128, bf16, 4096) == 256  # mistral7b_widths_l8
    assert block_rows(4, 128, bf16, 4096) == 512  # sdar30b_a3b_l6
    assert block_rows(2, 128, bf16, 4096) == 1024  # nemotron3_super_l11_ep4
    # joyai_llm_flash_ep16's plane is one stream of 1,280 B a row
    assert block_rows(1, 640, bf16, 4096, shared=True) == 1024
    assert block_rows(8, 128, jnp.int8, 4096) == 512  # half the bytes a row
    assert block_rows(1, 64, bf16, 4096) == 2048  # MQA: the ceiling
    assert block_rows(32, 128, bf16, 4096) == 128  # MHA: the floor,
    assert block_rows(32, 128, jnp.int8, 4096) % 128 == 0  # a lane tile
    assert block_rows(2, 16, jnp.float32, 40) == 40  # never past T


@pytest.mark.parametrize("kv,d,dtype,t", [
    (8, 128, jnp.bfloat16, 4096), (4, 128, jnp.bfloat16, 4096),
    (2, 128, jnp.bfloat16, 4096), (1, 640, jnp.bfloat16, 4096),
    (4, 64, jnp.int8, 1000), (16, 64, jnp.float32, 4096),
])
def test_block_rows_is_a_block_mosaic_takes(kv, d, dtype, t):
    """Whole sublane tiles of the cache's dtype and whole lane tiles of
    the int8 scale row, or the whole of T; the streams' double buffers
    well inside the 16 MiB of VMEM a v5e kernel may scope."""
    shared = kv == 1 and d == 640
    bk = block_rows(kv, d, dtype, t, shared)
    assert bk == t or bk % 128 == 0
    assert 128 <= bk <= 2048 or bk == t
    # K and V (or the plane), double-buffered, in bf16 at the least
    assert 2 * 2 * kv * bk * d * max(jnp.dtype(dtype).itemsize, 2) <= 8 * 2**20
