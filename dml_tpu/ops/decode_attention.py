"""Decode-step attention over the KV cache as a Pallas TPU kernel.

One autoregressive step attends a [B, 1, H, D] query against a
[B, KV, T, D] cache of which slot b holds `lengths[b]` live rows —
pure HBM streaming, ~zero FLOPs per byte, so the bytes fetched are the
whole cost. Under continuous batching most of the grid is dead: a slot
at its 350th token of a 4,096-row cache is 8% live, an empty slot 0%.
The XLA einsum (inference/generate.py `batched_decode_step`, the CPU
and test oracle) streams every row of every slot behind a mask; this
kernel fetches only the k-blocks that hold live rows.

What a slot's live rows are goes by the layer's type. A layer that
caches a row a token hands its [B, KV, max_len, D] planes and the slots'
lengths. A WINDOW layer (`generate.LMConfig.attention_layers`) caches a
ring of its window's W rows a slot, position p at row p mod W
(`generate.init_cache`), and hands that [B, KV, W, D] plane with
`min(length, W)`: the ring's first min(length, W) rows ARE the slot's
last min(length, W) positions, in the ring's order (softmax asks no
order of its keys; a key carries its rope), so validity stays `iota <
length` and no block has a dead lower edge: the k-blocks of a slot's
last W positions are the ring's blocks, cdiv(min(length, W), block) of
them, where a window cut out of a max_len plane would start a slot's
work at a first row inside a block. One kernel, both layer types.

The query may hold Q > 1 rows a slot (speculative decoding's verify,
block diffusion's forwards: `generate.batched_block_step`): a KV
head's Q * G rows then share every fetched block, each row masked to
its own limit (causal, or by blocks of `mask_block`).

Structure: the grid is the list of LIVE (slot, k-block) pairs,
`sum_b cdiv(lengths[b], block)` steps and not slots x blocks of T
(`work_list`: slot and block of every item, made from the lengths by a
cumulative sum; the grid's bound is a traced scalar, as megablox.gmm's
`num_active_tiles` is). Online-softmax accumulation across a slot's
items in VMEM scratch (the decode-shaped sibling of
flash_attention.py's forward kernel, G = H/KV query rows instead of a
q-block); one grid step streams ALL kv heads' blocks. Lengths and the
work list are scalar-prefetch operands
(`pltpu.PrefetchScalarGridSpec`), read by both halves:

- the `index_map`s name item w's slot and block, so no step, no DMA
  and no test exists for a block past a slot's length or for an empty
  slot, and the pipeline's look-ahead fetches the next LIVE block, a
  slot boundary or a run of empty slots between them or not;
- the body starts a slot's softmax on its block 0 and writes its
  output on its last; inside the one partly-live block validity is
  `iota < length`: scores of dead rows are replaced (a select, so
  stale NaN cannot leak), their v rows zeroed. No item visits an empty
  slot, whose output the caller's select zeroes: it returns zeros and
  reads nothing.

A dead step of the (slots, blocks of T) grid this replaces cost ~0.5 us
with its DMA skipped and its body under `pl.when` (my chip run, PR 39:
107 of 128 steps a call at the dense jobs cell's lengths, 117 -> 64 us
a call at the same blocks of 512 rows; 42 -> 16 with 3 of 16 slots
live). A grid over slots with an in-body loop and its own
double-buffered copies measured 11-22% behind this form at every
shape, and ahead at no block. The block's arithmetic is ONE chain for
all KV heads (a batch dimension of the two dots): a static loop over
heads ran eight chains end to end, 2.35 us a step of 256 rows whose
copies take 1.28, and held the block at 512 rows; side by side they
hide under the copies of 256 (`block_rows` on the block: 51 us a call).

Two things the einsum does badly on TPU stay solved here: an int8
cache (`LMConfig.kv_quant`) is dequantized inline (int8 values and f32
scales stream into VMEM; XLA materializes the whole cache as f32 in
HBM first), and MQA's [T, 64] stream does not under-fill the lanes.

Softmax statistics and accumulators are f32; the MXU operands are in
the cache's own dtype (q·k products of bf16 operands are exact in
f32; p is rounded to the cache's dtype for the p·v dot, as XLA's
default-precision einsum does on TPU). Parity with the oracle holds
to float-associativity noise in f32 and to bf16 rounding of p in bf16.

Measured on one TPU v5e (my chip runs, PR 26 and PR 39): see
`generate.uses_decode_kernel`. The reference has no attention
anywhere (SURVEY §0); this serves the net-new LM path.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._util import interpret_default as _interpret_default

NEG_INF = -1e30
LANES = 128  # scratch rows kept [G, 128]: full native tiles


def block_rows(kv: int, d: int, dtype, t: int, shared: bool = False) -> int:
    """Cache rows per k-block for a [*, kv, t, d] cache of `dtype`
    (`shared`: one plane that is keys and values, else a K and a V
    stream): the power of two nearest 1 MB of copies a STEP, all KV
    heads of every stream together, between 128 (the int8 scale
    stream's lane tile) and 2,048, never past `t`: 256 rows at KV 8 x
    D 128 x 2 B x 2 streams. Also what a caller needs to reckon the
    rows a step fetches.

    The rule trades a step's chain of arithmetic (scores, max, exp,
    sum, values: ~1.2 us however few rows, the heads' chains side by
    side) against the rounding of a slot's last block: a step whose
    copies take less than the chain is bound by the chain, one whose
    copies take more fetches dead rows for nothing. On one TPU v5e
    (my chip run, PR 39; us a call at the cells' lengths / every slot
    at 4,000 rows; 1 MB a step marked *): KV 8 reads 63 / 440 at 128
    rows, 54 / 364 at 256*, 65 / 364 at 512; KV 4 with 32 query rows
    83 / 448 at 256, 77 / 367 at 512*, 104 / 366 at 1,024; KV 2 with
    64 slots 108 / 446 at 512, 115 / 365 at 1,024*, 189 / 365 at
    2,048; the shared 640-column plane 103 / 141 at 512, 96 / 121 at
    1,024*; an int8 cache at KV 8 (half the bytes a row) 40 / 264 at
    256, 39 / 213 at 512*. The copies alone run at 91% of the chip's
    bandwidth at any block from 128 rows up. At the rule's two ends
    (16 slots): MHA at KV 32 lands on the floor, 2 MB a step, 178 /
    1,425 at 128* against 168 / 1,408 at 64 and 195 / 1,425 at 256
    (PR 26's kernel 391 / 2,284: its 32 chains end to end); MQA at
    KV 1 takes 2,048 rows, 27 / 50 against 20 / 62 at 1,024 and 18 /
    87 at 512 (PR 26's 47 / 50): short slots would like less, full
    ones lose more by it. An int8 cache at KV 32 reads 120 / 839 at
    128* and 116 / 728 at 256: its step is bound by the casts, not
    the copies, and nothing but tests reaches it."""
    row = (1 if shared else 2) * kv * d * jnp.dtype(dtype).itemsize
    nearest = 2 ** round(math.log2(2**20 / row))
    return min(max(128, nearest), 2048, t)


def work_list(lengths: jax.Array, bk: int, n_items: int):
    """The kernel's work: the live (slot, k-block) pairs in slot order,
    `sum_b cdiv(lengths[b], bk)` of them. Returns (slot_of, block_of,
    n): int32 [n_items] each and the traced count, n_items >= n being
    the static room (slots x blocks of T). Item w is block
    `block_of[w]` of slot `slot_of[w]`; a slot's first item has block
    0, its last `cdiv(length, bk) - 1`; an empty slot has none. Items
    past n repeat the last one (the pipeline's look-ahead then names a
    block it holds already). With every slot empty n is 1 and the one
    item is a block of no live rows, so the grid is never empty."""
    b = lengths.shape[0]
    nblk = (lengths + bk - 1) // bk
    ends = jnp.cumsum(nblk)  # items of slots <= b
    n = jnp.maximum(ends[-1], 1)
    w = jnp.minimum(jnp.arange(n_items, dtype=jnp.int32), n - 1)
    # slot s owns item w iff ends[s - 1] <= w < ends[s]: s is the count
    # of slots that end at or before w, its first item their blocks' sum
    before = ends[None, :] <= w[:, None]  # [n_items, B]
    slot_of = jnp.minimum(jnp.sum(before, axis=1, dtype=jnp.int32), b - 1)
    block_of = w - jnp.sum(
        jnp.where(before, nblk[None, :], 0), axis=1, dtype=jnp.int32)
    return slot_of, block_of, n


def _decode_kernel(len_ref, slot_ref, blk_ref, q_ref, k_ref, ks_ref, v_ref,
                   vs_ref, o_ref, m_scr, l_scr, acc_scr, *, scale, quantized,
                   bk, n_q=1, mask_block=1, v_width=None):
    w = pl.program_id(0)
    ik = blk_ref[w]
    length = len_ref[slot_ref[w]]

    @pl.when(ik == 0)  # a slot's first item
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # every item is a live block: no test, and none of a dead one
    rows = q_ref.shape[1]  # n_q * G query rows a KV head, query-major
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
    # all KV heads at once, a batch dimension of the two dots: one
    # grid step streams ALL kv heads' blocks (a per-(b, head) grid at
    # decode sizes is dominated by instance overhead). MXU dots take
    # the cache's own dtype (int8 -> bf16 is EXACT for |v| <= 127);
    # the per-position scales fold into the [G, bk] score/probability
    # rows AFTER the dot: 16x fewer multiplies than dequantizing the
    # [bk, D] block, and no f32 cache temporary in VMEM
    k = k_ref[...]  # [KV, bk, D]
    if quantized:
        k = k.astype(jnp.bfloat16)
    s = jax.lax.dot_general(
        q_ref[...].astype(k.dtype), k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale  # [KV, rows, bk] f32
    if quantized:
        s = s * ks_ref[...]  # [KV, 1, bk] f32 scale rows, exact in f32
    s = jnp.where(live_t, s, NEG_INF)

    m_prev = m_scr[:, :, :1]  # [KV, rows, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_scr[:, :, :1] * alpha + jnp.sum(p, -1, keepdims=True)
    # one shared plane (`v_width`): the values are the first columns
    # of the block the keys came in, fetched once
    v = k[:, :, :v_width] if v_ref is None else v_ref[...]
    if quantized:
        # fold the v scales into the prob rows (a dead row's scale is
        # stale too: select, don't multiply)
        p = jnp.where(live_t, p * vs_ref[...], 0.0)
        v = v.astype(jnp.bfloat16)
    # p is exactly 0 on dead rows, but 0 x stale NaN is NaN
    v = jnp.where(live_r, v, jnp.zeros_like(v))
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when((ik + 1) * bk >= length)  # its last
    def _finish():
        l = jnp.maximum(l_scr[:, :, :1], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,  # [B, Q, H, D]; Q = 1 is the decode step
    k: jax.Array,  # [B, KV, T, D] cache (cfg dtype, or int8 with scales)
    v: Optional[jax.Array],  # [B, KV, T, D]; None: k's first `v_width` columns
    lengths: jax.Array,  # [B] int32 — slot b attends cache rows < lengths[b]
    *,
    k_scale: Optional[jax.Array] = None,  # [B, KV, 1, T] f32 (int8 cache)
    v_scale: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    block_k: Optional[int] = None,
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

    rows = n_q * g  # query rows a KV head
    bk = (block_rows(kv, d, k.dtype, t, v is None) if block_k is None
          else min(block_k, t))
    # a ragged last block needs no padded copy of the cache: whatever
    # the out-of-range rows read as lies past every slot's length
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, t)
    slot_of, block_of, n_live = work_list(lengths, bk, b * pl.cdiv(t, bk))

    def slot_map(w, len_ref, slot_ref, blk_ref):
        return slot_ref[w], 0, 0, 0

    def kv_map(w, len_ref, slot_ref, blk_ref):
        return slot_ref[w], 0, blk_ref[w], 0

    def sc_map(w, len_ref, slot_ref, blk_ref):
        return slot_ref[w], 0, 0, blk_ref[w]

    # a KV head's rows are query-major: row i * G + j is query i's
    # head j of the group (for Q = 1 the plain [B, KV, G, D] view)
    qg = q.reshape(b, n_q, kv, g, d).swapaxes(1, 2).reshape(b, kv, rows, d)
    q_spec = pl.BlockSpec((None, kv, rows, d), slot_map)
    o_spec = pl.BlockSpec((None, kv, rows, dv), slot_map)
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

    def kernel(len_r, slot_r, blk_r, q_r, *refs):
        if quantized:
            k_r, ks_r, v_r, vs_r, *rest = refs
        elif v is None:
            k_r, *rest = refs
            ks_r = v_r = vs_r = None
        else:
            k_r, v_r, *rest = refs
            ks_r = vs_r = None
        _decode_kernel(len_r, slot_r, blk_r, q_r, k_r, ks_r, v_r, vs_r, *rest,
                       scale=scale, quantized=quantized, bk=bk,
                       n_q=n_q, mask_block=mask_block, v_width=v_width)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # one step a live (slot, k-block) pair: the bound is traced
            grid=(n_live,),
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
    )(lengths, slot_of, block_of, *ins)
    # no item visits an empty slot, so nothing was written there
    out = jnp.where(lengths[:, None, None, None] > 0, out, 0.0)
    return out.reshape(b, kv, n_q, g, dv).swapaxes(1, 2).reshape(
        b, n_q, h, dv)
