"""Flash attention as a Pallas TPU kernel (forward + custom-VJP backward).

Blockwise attention with the online-softmax recurrence: the [T, T]
score matrix never materializes; each (batch, head, q-block) streams
over k-blocks accumulating output, running max, and running
denominator in VMEM scratch. The grid's innermost dimension is the
k-block index — TPU grids execute sequentially, so scratch carries
the accumulation across k-steps and the output block is written once
on the last step.

Backward is two more kernels with the standard recomputation split:
`dq` accumulates over k-blocks, `dk`/`dv` accumulate over q-blocks,
both driven by the saved per-row logsumexp and the precomputed
`delta = rowsum(dO * O)`.

This is the single-device analog of parallel/ring_attention.py: the
ring rotates KV chunks across chips via ppermute, this kernel streams
KV blocks through VMEM within a chip. Layout convention matches the
rest of the framework: [batch, seq, heads, head_dim] ("BTHD").

Performance notes (v5e, B4 T4096 H8 D128 bf16 causal, slope-timed):
the MXU dots take bf16 inputs with f32 accumulation — casting to f32
before the dot forces the ~4x slower f32 matmul path. Block sizes are
the other lever: 128x128 blocks run at ~10 TF/s (grid overhead
dominates), the 1024x1024 defaults at ~84 TF/s — 5.4x faster than
XLA's naive attention (8.9 ms -> 1.65 ms), which is HBM-bound on the
materialized [B,H,T,T] score tensor. Blocks are min'd to the actual
sequence length, so small-T callers are unaffected by the defaults.

Served layers (`generate.prefill` of a stack whose attention layers
have a type) take two forward-only kernels that do their live work and
nothing else, K and V by KV head: `_band_fwd_kernel` (a window layer:
the band's k-blocks handed side by side, one softmax pass a q-block, a
mask where an edge crosses) and `_live_fwd_kernel` (a full layer: this
kernel's grid, the mask in diagonal blocks alone, no copy above the
diagonal). The untyped call below is the program it was: its JAXPR is
pinned in tests/test_ops.py.

The reference has no attention anywhere (SURVEY §0 — its models are
CNNs over single images); this is part of the net-new long-context
path, written per /opt/skills/guides/pallas_guide.md.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._util import interpret_default as _interpret_default

NEG_INF = -1e30
# lane width: scratch for the per-row running stats is kept
# (block_q, 128) so every read/write is a full native tile
LANES = 128


def _causal_mask(s, iq, ik, block_q, block_k, causal=1):
    """`causal` is the mask rule as one number (True counts as 1):
    1 = position i attends j <= i; B > 1 = block-causal, i attends j
    iff j // B <= i // B (full inside a block of B, causal across)."""
    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if causal > 1:
        qpos = qpos // causal * causal + (causal - 1)
    return jnp.where(qpos >= kpos, s, NEG_INF)


def _below_diagonal(iq, ik, block_q, block_k, causal=1):
    """Whether k-block `ik` holds a position some row of q-block `iq`
    attends (blocks wholly above the diagonal are skipped)."""
    last = iq * block_q + block_q - 1
    if causal > 1:
        last = last // causal * causal + (causal - 1)
    return ik * block_k <= last


def band_blocks(window: int, block: int) -> int:
    """k-blocks a q-block visits under the banded mask `0 <= i - j <
    window` at square blocks of `block` rows: its own and the ones that
    hold the `window - 1` positions before its first row."""
    return -(-(window - 1) // block) + 1


def live_blocks(window: Optional[int], t: int, head_dim: int,
                group: int) -> tuple:
    """(q rows, k rows) of a block of the served layers' forward kernels:
    ONE rule of the layer's window (None: a full layer), the sequence,
    the head size and the query heads a KV head; no knob. A full layer
    keeps the causal kernel's 1,024 (a head a grid step). A window
    layer's are square, HALF the window in 128s, so that the band is
    three pieces and the middle one all inside (at W 512: 256; chip
    readings of the kernel alone, PERF.md §6, PR 42: 1.28 ms a 4,096-token
    row at 256 and at 512, 1.56 at 128, the parent's form 2.98), and no
    more than keeps the group's q and output blocks, double-buffered,
    within 4 MiB (512 rows at 8 heads of 128)."""
    if window is None:
        return (min(1024, t),) * 2
    half = -(-(window // 2) // 128) * 128
    fit = (1 << 19) // (group * head_dim) // 128 * 128
    return (min(max(128, min(half, fit)), t),) * 2


def _band_edges(window: int, block: int, pieces: int) -> tuple:
    """Which of a q-block's `pieces` k-blocks under the band an edge
    crosses, oldest first: the last (the diagonal) and the ones that hold
    a key `window` and more before some row. The others are all inside,
    and the kernel builds no mask for them."""
    return tuple(j == pieces - 1 or (pieces - j) * block - 1 >= window
                 for j in range(pieces))


def _band_walk(t, window, block, head_dim, group):
    """(block, q-blocks, k-blocks a q-block visits at most) of the banded
    kernel over `t` positions."""
    block = min(block, t) if block else live_blocks(
        window, t, head_dim, group)[0]
    nq = -(-t // block)
    return block, nq, min(nq, band_blocks(window, block))


def band_visits(t: int, window: int, block: Optional[int] = None, *,
                head_dim: int = 128, group: int = 1) -> tuple:
    """(k-blocks the banded kernel computes, k-blocks the causal rule
    leaves at the same blocks) over a sequence of `t` positions: what the
    band skips of the causal triangle (a server's span labels are this
    arithmetic). Square blocks of `block` rows, else the kernel's own
    (`live_blocks` of the head size and the group). q-block i computes
    blocks max(0, i - pieces + 1) .. i, the causal rule leaves 0 .. i."""
    _, nq, pieces = _band_walk(t, window, block, head_dim, group)
    return (sum(min(i + 1, pieces) for i in range(nq)), nq * (nq + 1) // 2)


def band_masked(t: int, window: int, block: Optional[int] = None, *,
                head_dim: int = 128, group: int = 1) -> int:
    """Of the k-blocks `band_visits` counts under the band, those an edge
    crosses, for which the kernel builds a mask (`_band_edges`); the
    others are all inside."""
    block, nq, pieces = _band_walk(t, window, block, head_dim, group)
    edges = _band_edges(window, block, pieces)
    return sum(sum(edges[pieces - min(i + 1, pieces):]) for i in range(nq))


def _kv_valid_mask(s, ik, block_k, t_kv):
    """Mask k positions past the true sequence length (pad columns)."""
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(kpos < t_kv, s, NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_k, t_kv, padded_kv):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    pl.when(ik == 0)(functools.partial(_reset, m_scr, l_scr, acc_scr))

    def _body():
        # MXU wants the dot inputs in their native (bf16) dtype with
        # f32 accumulation — casting to f32 FIRST forces the ~4x
        # slower f32 matmul path (measured 9 -> 60+ TF/s on v5e)
        s = jax.lax.dot_general(
            q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk] f32
        if causal:
            s = _causal_mask(s, iq, ik, block_q, block_k, causal)
        if padded_kv:
            s = _kv_valid_mask(s, ik, block_k, t_kv)
        _online_softmax(s, v_ref, m_scr, l_scr, acc_scr)

    if causal:
        # skip blocks entirely above the diagonal
        pl.when(_below_diagonal(iq, ik, block_q, block_k, causal))(_body)
    else:
        _body()

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[:] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[:] = m_scr[:, :1] + jnp.log(l)  # [bq, 1]


def _reset(m_scr, l_scr, acc_scr):
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)


def _online_softmax(s, v_ref, m_scr, l_scr, acc_scr):
    """One k-block of the online-softmax recurrence: scores `s` [rows,
    bk] f32 (masked already) against the block's values, into the
    running max, denominator and accumulator."""
    m_prev = m_scr[:, :1]  # [rows, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)  # [rows, 1]
    l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot(
        p.astype(v_ref.dtype), v_ref[:],
        preferred_element_type=jnp.float32,
    )
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)


def _scores(q, k_ref, scale, bias=None):
    """[bq, bk] f32 scores of one head's rows against a K block; `bias`
    (0 where a pair is kept, -1e30 where the mask drops it; None: every
    pair kept) is ADDED, one plane for all the heads of a group: s + 0 is
    s and s - 1e30 is -1e30 in float32, what a select would have left."""
    s = jax.lax.dot_general(  # bf16 in, f32 accum (MXU)
        q, k_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    return s if bias is None else s + bias


def _band_fwd_kernel(q_ref, *refs, scale, window, block, pieces):
    """The forward kernel of a WINDOW layer that is served (the banded
    mask `0 <= i - j < window`, equal lengths, forward only). A grid step
    is a q-block of `block` rows of the `group` query heads of one KV
    head; K and V come as `pieces` blocks of `block` rows each, the
    q-block's own and the ones before it that hold the band (the index
    maps of `_flash_live_impl`), so the softmax over the band is ONE
    pass: no running statistics, no rescaled accumulator, nothing carried
    from step to step. A piece an edge crosses (`_band_edges`) adds its
    mask, a constant plane; the pieces between are all inside and add
    nothing. The first q-blocks have fewer live pieces (a block before
    the sequence's start is no block): each count is a body of its own
    that reads its live pieces alone. A pad tail needs no mask of its
    own: a key past the sequence's end is after every row that is kept."""
    k_refs, v_refs, o_ref = refs[:pieces], refs[pieces:-1], refs[-1]
    iq = pl.program_id(2)
    edges = _band_edges(window, block, pieces)

    def _body(live):
        def plane(j):  # piece j's rows back from each query to each key
            shape = (block, block)
            back = (pieces - 1 - j) * block + (
                jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                - jax.lax.broadcasted_iota(jnp.int32, shape, 1))
            return jnp.where((back >= 0) & (back < window), 0.0, NEG_INF)

        bias = [plane(j) if edges[j] else None
                for j in range(pieces - live, pieces)]
        ks, vs = k_refs[pieces - live:], v_refs[pieces - live:]

        # the group's heads one after another, unrolled: one chain a
        # head, side by side for the scheduler (1.05 ms a 4,096-token
        # row against 1.28 under a rolled loop: PERF.md §6, PR 42)
        for h in range(q_ref.shape[0]):
            s = [_scores(q_ref[h], k, scale, b) for k, b in zip(ks, bias)]
            m = functools.reduce(jnp.maximum, [
                jnp.max(x, axis=-1, keepdims=True) for x in s])
            p = [jnp.exp(x - m) for x in s]
            l = sum(jnp.sum(x, axis=-1, keepdims=True) for x in p)
            acc = sum(jax.lax.dot(x.astype(v.dtype), v[:],
                                  preferred_element_type=jnp.float32)
                      for x, v in zip(p, vs))
            o_ref[h] = (acc / l).astype(o_ref.dtype)

    for live in range(1, pieces):
        pl.when(iq == live - 1)(functools.partial(_body, live))
    pl.when(iq >= pieces - 1)(functools.partial(_body, pieces))


def _live_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                     scale, block_q, block_k):
    """The forward kernel of a FULL layer that is served (causal, equal
    lengths, forward only) with K and V by KV head: `_fwd_kernel`'s grid
    and recurrence, a head's K and V block named by its KV head (the
    index maps of `_flash_live_impl`), the mask built where the diagonal
    crosses the block and nowhere else, and a k-block wholly above the
    diagonal no block (its step names the diagonal's, already held, so
    no copy is issued for it). A pad tail needs no mask of its own: a
    key past the sequence's end is after every row that is kept."""
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    pl.when(ik == 0)(functools.partial(_reset, m_scr, l_scr, acc_scr))

    def _body(masked):
        s = _scores(q_ref[:], k_ref, scale)
        if masked:  # one head a step: a select, no plane to share
            s = _causal_mask(s, iq, ik, block_q, block_k)
        _online_softmax(s, v_ref, m_scr, l_scr, acc_scr)

    below = ik * block_k <= iq * block_q + block_q - 1
    crossed = ik * block_k + block_k - 1 > iq * block_q
    pl.when(below & crossed)(functools.partial(_body, True))
    pl.when(jnp.logical_not(crossed))(functools.partial(_body, False))

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[:] = (acc_scr[:] / l).astype(o_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   *rest, scale, causal, block_q, block_k, t_kv,
                   padded_kv, has_glse):
    # rest = (glse_ref?, dq_ref, dq_scr): the lse-cotangent input only
    # exists for flash_attention_lse — the plain path must not stream
    # an all-zeros buffer through the kernel on every training step
    if has_glse:
        glse_ref, dq_ref, dq_scr = rest
    else:
        glse_ref, (dq_ref, dq_scr) = None, rest
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _body():
        lse = lse_ref[:]                    # [bq, 1]
        delta = delta_ref[:]                # [bq, 1]
        s = jax.lax.dot_general(            # bf16 in, f32 accum (MXU)
            q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            s = _causal_mask(s, iq, ik, block_q, block_k, causal)
        if padded_kv:
            s = _kv_valid_mask(s, ik, block_k, t_kv)
        p = jnp.exp(s - lse)                   # [bq, bk]
        dp = jax.lax.dot_general(              # dO @ V^T: [bq, bk]
            do_ref[:], v_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dL/ds = p*(dp - delta) from the out path + p*g_lse from the
        # lse output (d lse_i/d s_ij = p_ij) — the lse term exists
        # only for flash_attention_lse (e.g. the ring merge)
        row = (dp - delta + glse_ref[:]) if has_glse else (dp - delta)
        ds = p * row * scale
        dq_scr[:] += jax.lax.dot(
            ds.astype(k_ref.dtype), k_ref[:],
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(_below_diagonal(iq, ik, block_q, block_k, causal))(_body)
    else:
        _body()

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *rest, scale, causal, block_q, block_k, t_kv,
                    padded_kv, has_glse):
    # rest = (glse_ref?, dk_ref, dv_ref, dk_scr, dv_scr) — see
    # _bwd_dq_kernel for why glse is statically optional
    if has_glse:
        glse_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest
    else:
        glse_ref, (dk_ref, dv_ref, dk_scr, dv_scr) = None, rest
    # note the transposed grid: (b, h, k-block, q-block)
    ik, iq = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _body():
        lse = lse_ref[:]    # [bq, 1]
        delta = delta_ref[:]  # [bq, 1]
        s = jax.lax.dot_general(            # bf16 in, f32 accum (MXU)
            q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            s = _causal_mask(s, iq, ik, block_q, block_k, causal)
        if padded_kv:
            s = _kv_valid_mask(s, ik, block_k, t_kv)
        p = jnp.exp(s - lse)  # [bq, bk] f32
        pb = p.astype(do_ref.dtype)
        dv_scr[:] += jax.lax.dot_general(  # P^T @ dO: [bk, D]
            pb, do_ref[:], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do_ref[:], v_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        row = (dp - delta + glse_ref[:]) if has_glse else (dp - delta)
        ds = (p * row * scale).astype(q_ref.dtype)
        dk_scr[:] += jax.lax.dot_general(  # dS^T @ Q: [bk, D]
            ds, q_ref[:], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(_below_diagonal(iq, ik, block_q, block_k, causal))(_body)
    else:
        _body()

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _pad_seq(x, block):
    """Pad the seq axis (axis 2 of [B,H,T,D] / [B,H,T]) to a block
    multiple."""
    t = x.shape[2]
    pad = (-t) % block
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[2] = (0, pad)
    return jnp.pad(x, widths)


def _bhtd(x):
    return jnp.transpose(x, (0, 2, 1, 3))  # BTHD <-> BHTD (involution)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    out, _ = _flash_fwd_impl(
        q, k, v, causal, scale, block_q, block_k, interpret
    )
    return out


def _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret):
    """q,k: [B,H,T,D], v: [B,H,T,Dv] (Dv = D as a rule; a model whose
    values are narrower than its keys hands them as they are). Returns
    (out [B,H,T,Dv], lse [B,H,T]) f32 lse."""
    b, h, t, d = q.shape
    t_kv, dv = k.shape[2], v.shape[3]
    bq = min(block_q, t)
    bk = min(block_k, t_kv)
    qp = _pad_seq(q, bq)
    kp = _pad_seq(k, bk)
    vp = _pad_seq(v, bk)
    nq = qp.shape[2] // bq
    nk = kp.shape[2] // bk
    padded_kv = kp.shape[2] != t_kv

    q_spec = pl.BlockSpec((None, None, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    kv_spec = pl.BlockSpec((None, None, bk, d), lambda b_, h_, i, j: (b_, h_, j, 0))
    v_spec = pl.BlockSpec((None, None, bk, dv), lambda b_, h_, i, j: (b_, h_, j, 0))
    o_spec = pl.BlockSpec((None, None, bq, dv), lambda b_, h_, i, j: (b_, h_, i, 0))
    # rows stored [B, H, T, 1]: trailing singleton lane dim keeps the
    # block's last-two-dims (bq, 1) legal for Mosaic (bs0 == as0)
    lse_spec = pl.BlockSpec((None, None, bq, 1), lambda b_, h_, i, j: (b_, h_, i, 0))

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        t_kv=t_kv, padded_kv=padded_kv,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[q_spec, kv_spec, v_spec],
        out_specs=[o_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((*qp.shape[:3], dv), q.dtype),
            jax.ShapeDtypeStruct((*qp.shape[:3], 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),  # running max
            pltpu.VMEM((bq, LANES), jnp.float32),  # running denominator
            pltpu.VMEM((bq, dv), jnp.float32),     # output accumulator
        ],
        interpret=interpret,
        # the kernel's name in a profiler trace (a part: tracing.PARTS)
        name="flash_attention",
    )(qp, kp, vp)
    return out[:, :, :t], lse[:, :, :t, 0]


def _flash_band_impl(q, k, v, window, scale, block, interpret):
    """q: [B,H,T,D], k: [B,KV,T,D], v: [B,KV,T,Dv], H = group x KV, the
    heads of a group side by side: out [B,H,T,Dv] under the banded mask
    by `_band_fwd_kernel` at square blocks of `block` rows."""
    b, h, t, d = q.shape
    kv, dv = k.shape[1], v.shape[3]
    group = h // kv
    block = min(block, t)
    qp, kp, vp = (_pad_seq(x, block) for x in (q, k, v))
    nq = qp.shape[2] // block
    pieces = min(nq, band_blocks(window, block))
    # a q-block holds a KV head's query heads, [group, block, D] (a block
    # of the head axis: q and the output keep their [B, H, T, D], and no
    # reshape stands between the kernel and what XLA fuses around it);
    # piece j of q-block i is k-block i - (pieces - 1) + j, and one
    # before the start is named 0 and not read
    rows = lambda width: pl.BlockSpec(
        (None, group, block, width), lambda b_, h_, i: (b_, h_, i, 0))
    piece = lambda width, back: pl.BlockSpec(
        (None, None, block, width),
        lambda b_, h_, i: (b_, h_, jnp.maximum(i - back, 0), 0))
    backs = range(pieces - 1, -1, -1)
    out = pl.pallas_call(
        functools.partial(_band_fwd_kernel, scale=scale, window=window,
                          block=block, pieces=pieces),
        grid=(b, kv, nq),
        in_specs=[rows(d)] + [piece(d, x) for x in backs]
        + [piece(dv, x) for x in backs],
        out_specs=rows(dv),
        out_shape=jax.ShapeDtypeStruct((*qp.shape[:3], dv), q.dtype),
        interpret=interpret,
        name="flash_attention",  # the same part in a profiler trace
    )(qp, *[kp] * pieces, *[vp] * pieces)
    return out[:, :, :t]


def _flash_live_impl(q, k, v, scale, block_q, block_k, interpret):
    """q: [B,H,T,D], k: [B,KV,T,D], v: [B,KV,T,Dv], H = group x KV, the
    heads of a group side by side: out [B,H,T,Dv] under the causal mask
    by `_live_fwd_kernel`."""
    b, h, t, d = q.shape
    group, dv = h // k.shape[1], v.shape[3]
    bq, bk = min(block_q, t), min(block_k, t)
    qp, kp, vp = _pad_seq(q, bq), _pad_seq(k, bk), _pad_seq(v, bk)
    # a head's K and V are its KV head's; a step above the diagonal
    # names the diagonal's block
    kv_spec = lambda width: pl.BlockSpec(
        (None, None, bk, width),
        lambda b_, h_, i, j: (
            b_, jax.lax.div(h_, jnp.int32(group)),
            jnp.minimum(j, jax.lax.div(i * bq + bq - 1, jnp.int32(bk))), 0))
    out = pl.pallas_call(
        functools.partial(_live_fwd_kernel, scale=scale, block_q=bq,
                          block_k=bk),
        grid=(b, h, qp.shape[2] // bq, kp.shape[2] // bk),
        in_specs=[
            pl.BlockSpec((None, None, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            kv_spec(d), kv_spec(dv),
        ],
        out_specs=pl.BlockSpec((None, None, bq, dv), lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((*qp.shape[:3], dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),  # running max
            pltpu.VMEM((bq, LANES), jnp.float32),  # running denominator
            pltpu.VMEM((bq, dv), jnp.float32),     # output accumulator
        ],
        interpret=interpret,
        name="flash_attention",
    )(qp, kp, vp)
    return out[:, :, :t]


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = _flash_fwd_impl(
        q, k, v, causal, scale, block_q, block_k, interpret
    )
    return out, (q, k, v, out, lse)


def _flash_bwd_impl(q, k, v, out, lse, g, g_lse, causal, scale, block_q,
                    block_k, interpret):
    """Shared backward: dq/dk/dv given out-cotangent `g` and optional
    lse-cotangent `g_lse` ([B,H,T] f32, or None for plain attention —
    the g_lse input stream is then omitted from the kernels
    entirely)."""
    has_glse = g_lse is not None
    b, h, t, d = q.shape
    t_kv, dv = k.shape[2], v.shape[3]
    bq = min(block_q, t)
    bk = min(block_k, t_kv)
    # delta_i = sum_d dO_i O_i — the rowwise correction term
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # [B,H,T]

    qp, gp = _pad_seq(q, bq), _pad_seq(g, bq)
    kp, vp = _pad_seq(k, bk), _pad_seq(v, bk)
    # rows as [B, H, T, 1] (see forward); pad lse with +big so pad
    # q-rows produce p = exp(s - big) = 0
    lsep = _pad_seq(lse[..., None], bq)
    if lsep.shape[2] != t:
        pad_rows = (
            jax.lax.broadcasted_iota(jnp.int32, lsep.shape, 2) >= t
        )
        lsep = jnp.where(pad_rows, jnp.float32(-NEG_INF), lsep)
    deltap = _pad_seq(delta[..., None], bq)
    glsep = (
        _pad_seq(g_lse.astype(jnp.float32)[..., None], bq)
        if has_glse else None
    )
    nq = qp.shape[2] // bq
    nk = kp.shape[2] // bk
    padded_kv = kp.shape[2] != t_kv

    q_spec = pl.BlockSpec((None, None, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    kv_spec = pl.BlockSpec((None, None, bk, d), lambda b_, h_, i, j: (b_, h_, j, 0))
    # v and the output's cotangent are Dv wide (see the forward)
    v_spec = pl.BlockSpec((None, None, bk, dv), lambda b_, h_, i, j: (b_, h_, j, 0))
    g_spec = pl.BlockSpec((None, None, bq, dv), lambda b_, h_, i, j: (b_, h_, i, 0))
    row_spec = pl.BlockSpec((None, None, bq, 1), lambda b_, h_, i, j: (b_, h_, i, 0))

    ins = [qp, kp, vp, gp, lsep, deltap] + ([glsep] if has_glse else [])
    in_specs = [q_spec, kv_spec, v_spec, g_spec, row_spec, row_spec] + (
        [row_spec] if has_glse else []
    )
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, block_q=bq,
            block_k=bk, t_kv=t_kv, padded_kv=padded_kv,
            has_glse=has_glse,
        ),
        grid=(b, h, nq, nk),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dq",
    )(*ins)[:, :, :t]

    # transposed grid: q-block innermost so dk/dv accumulate in scratch
    q_spec_t = pl.BlockSpec((None, None, bq, d), lambda b_, h_, j, i: (b_, h_, i, 0))
    kv_spec_t = pl.BlockSpec((None, None, bk, d), lambda b_, h_, j, i: (b_, h_, j, 0))
    v_spec_t = pl.BlockSpec((None, None, bk, dv), lambda b_, h_, j, i: (b_, h_, j, 0))
    g_spec_t = pl.BlockSpec((None, None, bq, dv), lambda b_, h_, j, i: (b_, h_, i, 0))
    row_spec_t = pl.BlockSpec((None, None, bq, 1), lambda b_, h_, j, i: (b_, h_, i, 0))
    in_specs_t = [q_spec_t, kv_spec_t, v_spec_t, g_spec_t, row_spec_t,
                  row_spec_t] + ([row_spec_t] if has_glse else [])
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal, block_q=bq,
            block_k=bk, t_kv=t_kv, padded_kv=padded_kv,
            has_glse=has_glse,
        ),
        grid=(b, h, nk, nq),
        in_specs=in_specs_t,
        out_specs=[kv_spec_t, v_spec_t],
        out_shape=[
            jax.ShapeDtypeStruct(kp.shape, k.dtype),
            jax.ShapeDtypeStruct(vp.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_dkv",
    )(*ins)
    return dq, dk[:, :, :t_kv], dv[:, :, :t_kv]


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_bwd_impl(
        q, k, v, out, lse, g, None, causal, scale,
        block_q, block_k, interpret,
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_live_forward(q, k, v, window, scale, block_q, block_k, interpret):
    """The served layers' forward kernels: a window layer's band
    (`_band_fwd_kernel`), a full layer's causal mask with K and V by KV
    head (`_live_fwd_kernel`). Serving calls the forward alone; the
    backward kernels know no lower edge, skip no block below one and sum
    no group's gradients into a KV head, so differentiating this raises
    instead of handing back the causal kernels' gradients."""
    if window is not None:
        return _flash_band_impl(q, k, v, window, scale, block_q, interpret)
    return _flash_live_impl(q, k, v, scale, block_q, block_k, interpret)


def _flash_live_fwd(q, k, v, window, scale, block_q, block_k, interpret):
    raise NotImplementedError(
        "flash attention's backward kernels know no window and no K and V "
        "by KV head: the banded mask and the grouped call are forward only "
        "(serving); train under them with another attention, or teach "
        "_bwd_dq_kernel and _bwd_dkv_kernel the band and the group")


def _flash_live_bwd(window, scale, block_q, block_k, interpret, res, g):
    raise NotImplementedError("unreachable: the forward rule raises")


_flash_live_forward.defvjp(_flash_live_fwd, _flash_live_bwd)
# one traced function a (shapes, window, blocks): a program whose layers
# unroll in Python (`generate.prefill`: thirty window layers, ten full)
# traces and lowers the kernel once a layer TYPE, not once a layer
_flash_live = jax.jit(_flash_live_forward, static_argnums=(3, 4, 5, 6, 7))


# ---------------------------------------------------------------------------
# lse-returning variant (the ring-attention building block)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret):
    return _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret)


def _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = _flash_fwd_impl(
        q, k, v, causal, scale, block_q, block_k, interpret
    )
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    g_out, g_lse = g
    return _flash_bwd_impl(
        q, k, v, out, lse, g_out, g_lse, causal, scale, block_q,
        block_k, interpret,
    )


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
):
    """Flash attention that ALSO returns the per-row log-sum-exp.

    q, k, v: [B, T, H, D] -> (out [B, Tq, H, D], lse [B, H, Tq] f32).
    Differentiable in both outputs (the lse cotangent feeds `ds` as
    `p * g_lse`), which is what lets ring attention merge per-block
    flash results across devices and still train. Layout matches
    `flash_attention`; `lse` stays [B, H, T] (the merge consumes it
    head-major)."""
    if q.ndim != 4:
        raise ValueError(f"expected [B,T,H,D], got {q.shape}")
    if causal and q.shape[1] != k.shape[1]:
        raise ValueError("causal attention needs equal q/k lengths")
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    interpret = _interpret_default() if interpret is None else interpret
    out, lse = _flash_lse(
        _bhtd(q), _bhtd(k), _bhtd(v), causal, scale, block_q, block_k,
        interpret,
    )
    return _bhtd(out), lse


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
    mask_block: int = 1,
    window: Optional[int] = None,
) -> jax.Array:
    """Blockwise (flash) attention. q, k, v: [B, T, H, D] (T of k/v may
    differ from q's); returns [B, Tq, H, D] in q's dtype. v may be
    narrower (or wider) than q and k, [B, T, H, Dv]: the output then is
    [B, Tq, H, Dv], and nothing is padded to the wider of the two.

    K and V may come BY KV HEAD, [B, T, H_kv, D] where q has H = group x
    H_kv heads (query head h reads KV head h // group, as `jnp.repeat`
    on the head axis would have laid them; a count that does not divide
    raises): the served form of a grouped layer, causal and forward only
    (differentiating it raises). Nothing is repeated in or around the
    kernel, a block the diagonal does not cross builds no mask, and a
    block above it is no copy and no work (`_live_fwd_kernel`).

    `window` W (with `causal`, `mask_block` 1) is the banded mask of a
    sliding-window layer: position i attends j iff 0 <= i - j < W, its
    own key among the W; K and V by KV head or repeated. A q-block reads
    the k-blocks that hold its band and no other, in ONE pass, and
    builds a mask for the ones an edge crosses (`_band_fwd_kernel`);
    blocks are square, `live_blocks`' and no more than `block_q` and
    `block_k`. Forward only: differentiating it raises.

    `mask_block` B > 1 (with `causal`) is the block-causal rule of
    block-diffusion models: position i attends j iff j // B <= i // B.
    k-blocks wholly above that stepped diagonal are skipped as under
    the causal rule; `mask_block` 1 is the causal kernel unchanged.

    Otherwise differentiable (custom VJP, both passes are Pallas
    kernels). `interpret=None` auto-selects: compiled on TPU, interpreter
    elsewhere (the CPU test mesh).
    """
    if q.ndim != 4:
        raise ValueError(f"expected [B,T,H,D], got {q.shape}")
    if causal and q.shape[1] != k.shape[1]:
        raise ValueError("causal attention needs equal q/k lengths")
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    interpret = _interpret_default() if interpret is None else interpret
    if mask_block < 1 or (mask_block > 1 and not causal):
        raise ValueError(f"mask_block {mask_block} with causal={causal}")
    group, ragged = divmod(q.shape[2], k.shape[2])
    if ragged or v.shape[2] != k.shape[2]:
        raise ValueError(
            f"{k.shape[2]} K and {v.shape[2]} V heads under {q.shape[2]} "
            f"query heads: the KV heads divide the query heads")
    if window is not None or group > 1:
        if (window is not None and window < 1) or not causal or (
                mask_block != 1):
            raise ValueError(
                f"window {window}, {group} query heads a KV head with "
                f"causal={causal}, mask_block {mask_block}: the band is "
                f"causal, by positions, and so is the call with K and V by "
                f"KV head")
        bq, bk = live_blocks(window, q.shape[1], q.shape[3], group)
        bq, bk = min(block_q, bq), min(block_k, bk)
        if window is not None:  # the band's blocks are square
            bq = bk = min(bq, bk)
        return _bhtd(_flash_live(
            _bhtd(q), _bhtd(k), _bhtd(v), window, scale, bq, bk, interpret))
    out = _flash(
        _bhtd(q), _bhtd(k), _bhtd(v), mask_block if mask_block > 1 else causal,
        scale, block_q, block_k, interpret,
    )
    return _bhtd(out)
