"""Decode-step attention over the KV cache as a Pallas TPU kernel.

One autoregressive step attends a [B, 1, H, D] query against a
[B, KV, T, D] cache of which slot b holds `lengths[b]` live rows —
pure HBM streaming, ~zero FLOPs per byte, so the bytes fetched are the
whole cost. Under continuous batching most of the grid is dead: a slot
at its 350th token of a 4,096-row cache is 8% live, an empty slot 0%.
The XLA einsum (inference/generate.py `batched_decode_step`, the CPU
and test oracle) streams every row of every slot behind a mask; this
kernel fetches only the k-blocks that hold live rows.

The query may hold Q > 1 rows a slot (speculative decoding's verify,
block diffusion's forwards: `generate.batched_block_step`): a KV
head's Q * G rows then share every fetched block, each row masked to
its own limit (causal, or by blocks of `mask_block`).

Structure: grid (B, k-blocks), online-softmax accumulation across
k-blocks in VMEM scratch (the decode-shaped sibling of
flash_attention.py's forward kernel — G = H/KV query rows instead of
a q-block); one grid instance streams ALL kv heads' blocks. The
per-slot lengths are a scalar-prefetch operand
(`pltpu.PrefetchScalarGridSpec`), read by both halves:

- the k/v (and int8 scale) `index_map`s clamp the block index to the
  slot's last live block, and an empty slot names the block the slot
  before it ended on. Pallas issues a DMA only when the block index
  changes between grid steps, so blocks past a slot's length and whole
  empty slots are never fetched (a run of empty slots at the head of
  the grid shares one block);
- the body runs under `pl.when(block_start < length)`; inside the one
  partly-live block validity is `iota < length`: scores of dead rows
  are replaced (a select, so stale NaN cannot leak), their v rows
  zeroed. A slot of length 0 returns zeros.

Two things the einsum does badly on TPU stay solved here: an int8
cache (`LMConfig.kv_quant`) is dequantized inline (int8 values and f32
scales stream into VMEM; XLA materializes the whole cache as f32 in
HBM first), and MQA's [T, 64] stream does not under-fill the lanes.

Softmax statistics and accumulators are f32; the MXU operands are in
the cache's own dtype (q·k products of bf16 operands are exact in
f32; p is rounded to the cache's dtype for the p·v dot, as XLA's
default-precision einsum does on TPU). Parity with the oracle holds
to float-associativity noise in f32 and to bf16 rounding of p in bf16.

Measured on one TPU v5e (my chip run, PR 26): see
`generate.uses_decode_kernel`. The reference has no attention
anywhere (SURVEY §0); this serves the net-new LM path.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._util import interpret_default as _interpret_default

NEG_INF = -1e30
LANES = 128  # scratch rows kept [G, 128]: full native tiles


def block_rows(kv: int, d: int, dtype, t: int, block_k: int = 2048) -> int:
    """Cache rows per k-block for a [*, kv, t, d] cache of `dtype`.
    One grid instance holds all KV heads' blocks, so the block is
    clamped to keep each stream's VMEM buffer (cache dtype; bf16
    temporaries for int8) at ~1 MB: 512 rows at KV 8 x D 128 x 2 B.
    Also what a caller needs to reckon the rows a step fetches."""
    itemsize = max(jnp.dtype(dtype).itemsize, 2)
    cap = max(128, (2**20) // (kv * d * itemsize) // 128 * 128)
    return min(block_k, cap, t)


def _decode_kernel(len_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale, quantized, n_kv, bk,
                   n_q=1, mask_block=1, v_width=None):
    ik = pl.program_id(1)
    nk = pl.num_programs(1)
    length = len_ref[pl.program_id(0)]
    rows = q_ref.shape[1]  # n_q * G query rows a KV head, query-major

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(ik * bk < length)
    def _block():
        # rows of this block that are live, along lanes (scores) and
        # along sublanes (v rows); all true except in the last block
        live_t = ik * bk + jax.lax.broadcasted_iota(
            jnp.int32, (1, bk), 1) < length
        live_r = ik * bk + jax.lax.broadcasted_iota(
            jnp.int32, (bk, 1), 0) < length
        if n_q > 1:
            # `length` counts the n_q rows this dispatch wrote; query
            # i of them stops short of the rows of later queries
            # (causal) or of later blocks (`mask_block`): a [rows, bk]
            # mask in place of the [1, bk] one
            qi = jax.lax.broadcasted_iota(
                jnp.int32, (rows, 1), 0) // (rows // n_q)
            back = n_q - (qi // mask_block + 1) * mask_block
            live_t = ik * bk + jax.lax.broadcasted_iota(
                jnp.int32, (rows, bk), 1) < length - back
        # static per-head loop: one grid instance streams ALL kv heads'
        # blocks (a per-(b, head) grid at decode sizes is dominated by
        # instance overhead)
        for h in range(n_kv):
            # MXU dots take the cache's own dtype (int8 -> bf16 is
            # EXACT for |v| <= 127); the per-position scales fold into
            # the [G, bk] score/probability rows AFTER the dot — 16x
            # fewer multiplies than dequantizing the [bk, D] block,
            # and no f32 cache temporary in VMEM
            k = k_ref[h]
            if quantized:
                k = k.astype(jnp.bfloat16)
            s = jax.lax.dot_general(
                q_ref[h].astype(k.dtype), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [G, bk] f32
            if quantized:
                s = s * ks_ref[h]  # [1, bk] f32 scale row, exact in f32
            s = jnp.where(live_t, s, NEG_INF)

            m_prev = m_scr[h, :, :1]  # [G, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_scr[h, :, :1] * alpha + jnp.sum(p, -1, keepdims=True)
            # one shared plane (`v_width`): the values are the first
            # columns of the block the keys came in, fetched once
            v = k[:, :v_width] if v_ref is None else v_ref[h]
            if quantized:
                # fold the v scales into the prob rows (a dead row's
                # scale is stale too: select, don't multiply)
                p = jnp.where(live_t, p * vs_ref[h], 0.0)
                v = v.astype(jnp.bfloat16)
            # p is exactly 0 on dead rows, but 0 x stale NaN is NaN
            v = jnp.where(live_r, v, jnp.zeros_like(v))
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32
            )
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(ik == nk - 1)
    def _finish():
        for h in range(n_kv):
            l = jnp.maximum(l_scr[h, :, :1], 1e-30)
            o_ref[h] = (acc_scr[h] / l).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,  # [B, Q, H, D]; Q = 1 is the decode step
    k: jax.Array,  # [B, KV, T, D] cache (cfg dtype, or int8 with scales)
    v: Optional[jax.Array],  # [B, KV, T, D]; None: k's first `v_width` columns
    lengths: jax.Array,  # [B] int32 — slot b attends cache rows < lengths[b]
    *,
    k_scale: Optional[jax.Array] = None,  # [B, KV, 1, T] f32 (int8 cache)
    v_scale: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    block_k: int = 2048,
    interpret: Optional[bool] = None,
    mask_block: int = 1,
    v_width: Optional[int] = None,
) -> jax.Array:
    """One decode step of cache attention; returns [B, Q, H, D] f32
    ([B, Q, H, v_width] over a shared plane).

    With Q > 1 query rows a slot (speculative decoding's verify, block
    diffusion: `generate.batched_block_step`) `lengths[b]` counts the
    Q rows the dispatch has just written at the slot's end, and query
    i attends rows < lengths[b] - (Q - (i // mask_block + 1) *
    mask_block): its own row and every earlier one (`mask_block` 1,
    causal), or every row up to its own block of `mask_block` (Q a
    multiple of it, the write position too). A KV head's Q * G query
    rows share each fetched block, so the bytes are those of Q = 1.

    `lengths[b]` is the number of cache rows slot b attends: `pos + 1`
    for a slot that has just written row `pos`, 0 for an empty slot
    (which returns zeros and fetches nothing); values are clipped to
    [0, T]. Rows at or past a slot's length are never read into the
    result, whatever they hold.

    The cache is head-major ([B, KV, T, D] — `init_cache`'s layout):
    each head's [T, D] plane is contiguous, so the blocked axes are
    the trailing two, which is the only arrangement Mosaic's block
    constraint admits without a materialized transpose. H = KV * G
    grouped-query with kv-major head order (head h = kv * G + g),
    matching `batched_decode_step`'s reshape. Pass `k_scale`/`v_scale`
    to read an int8 cache with inline dequant.

    `v=None` with `v_width` is latent attention's absorbed form
    (`generate._latent_attention`): keys and values are ONE stream, a
    [B, 1, T, D] plane of cached latent rows whose first `v_width`
    columns are also the values. Each block is fetched once; all H
    query heads' rows score its D columns and accumulate over its
    first `v_width`. `scale` is then the caller's (the model's key
    width is not D)."""
    b, n_q, h, d = q.shape
    if (v is None) != (v_width is not None):
        raise ValueError("v_width comes with v=None, and only with it")
    if v is None and (k_scale is not None or scale is None
                      or not 0 < v_width <= d):
        raise ValueError(
            "a shared plane is read unquantized, under the caller's "
            "scale, its values the first v_width <= D columns")
    dv = d if v_width is None else v_width
    if n_q % mask_block:
        raise ValueError(f"{n_q} query rows under blocks of {mask_block}")
    kv, t = k.shape[1], k.shape[2]
    if h % kv:
        raise ValueError(f"H {h} not divisible by KV {kv}")
    g = h // kv
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("pass both k_scale and v_scale or neither")
    scale = d ** -0.5 if scale is None else scale
    interpret = _interpret_default() if interpret is None else interpret

    bk = block_rows(kv, d, k.dtype, t, block_k)
    # a ragged last block needs no padded copy of the cache: whatever
    # the out-of-range rows read as lies past every slot's length
    nk = pl.cdiv(t, bk)
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, t)
    # the slot whose blocks are on show at grid row b: b itself if it
    # is live, else the last live slot before it (slot 0 if none)
    src = jax.lax.cummax(
        jnp.where(lengths > 0, jnp.arange(b, dtype=jnp.int32), 0)
    )

    def cache_block(b_, j, len_ref, src_ref):
        s = src_ref[b_]
        last = jnp.maximum(pl.cdiv(len_ref[s], bk) - 1, 0)
        return s, jnp.where(len_ref[b_] > 0, jnp.minimum(j, last), last)

    def kv_map(b_, j, len_ref, src_ref):
        s, blk = cache_block(b_, j, len_ref, src_ref)
        return s, 0, blk, 0

    def sc_map(b_, j, len_ref, src_ref):
        s, blk = cache_block(b_, j, len_ref, src_ref)
        return s, 0, 0, blk

    # a KV head's rows are query-major: row i * G + j is query i's
    # head j of the group (for Q = 1 the plain [B, KV, G, D] view)
    rows = n_q * g
    qg = q.reshape(b, n_q, kv, g, d).swapaxes(1, 2).reshape(b, kv, rows, d)
    q_spec = pl.BlockSpec(
        (None, kv, rows, d), lambda b_, j, *_: (b_, 0, 0, 0))
    o_spec = pl.BlockSpec(
        (None, kv, rows, dv), lambda b_, j, *_: (b_, 0, 0, 0))
    kv_spec = pl.BlockSpec((None, kv, bk, d), kv_map)
    sc_spec = pl.BlockSpec((None, kv, 1, bk), sc_map)

    if quantized:
        ins = (qg, k, k_scale, v, v_scale)
        in_specs = [q_spec, kv_spec, sc_spec, kv_spec, sc_spec]
    elif v is None:
        ins = (qg, k)
        in_specs = [q_spec, kv_spec]
    else:
        # the scale streams don't exist: don't DMA dummy buffers
        ins = (qg, k, v)
        in_specs = [q_spec, kv_spec, kv_spec]

    def kernel(len_r, _src_r, q_r, *refs):  # src: the index maps' alone
        if quantized:
            k_r, ks_r, v_r, vs_r, *rest = refs
        elif v is None:
            k_r, *rest = refs
            ks_r = v_r = vs_r = None
        else:
            k_r, v_r, *rest = refs
            ks_r = vs_r = None
        _decode_kernel(len_r, q_r, k_r, ks_r, v_r, vs_r, *rest,
                       scale=scale, quantized=quantized, n_kv=kv, bk=bk,
                       n_q=n_q, mask_block=mask_block, v_width=v_width)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nk),
            in_specs=in_specs,
            out_specs=o_spec,
            scratch_shapes=[
                pltpu.VMEM((kv, rows, LANES), jnp.float32),  # running max
                pltpu.VMEM((kv, rows, LANES), jnp.float32),  # running denom
                pltpu.VMEM((kv, rows, dv), jnp.float32),     # output accum
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, rows, dv), jnp.float32),
        interpret=interpret,
        # the kernel's name in a profiler trace (a part: tracing.PARTS)
        name="decode_attention",
    )(lengths, src, *ins)
    return out.reshape(b, kv, n_q, g, dv).swapaxes(1, 2).reshape(
        b, n_q, h, dv)
