"""Ring attention: exact attention over sequences sharded across the
`sp` mesh axis.

Long-context support the TPU way (net-new vs the reference, which has
no sequence models at all — SURVEY §0): each device holds a sequence
chunk of Q/K/V; K/V blocks rotate around the ring via `ppermute` over
ICI while every device accumulates its queries' attention with the
flash-attention online-softmax recurrence (running max + running
denominator), so the full T×T score matrix never materializes and the
sequence length scales with the number of devices. Communication
overlaps the per-block compute under XLA's scheduler.

Written with `shard_map` (per-device code, explicit collective) —
this is the one place the framework hand-places a collective, because
the KV rotation order IS the algorithm; everything else in
dml_tpu.parallel stays GSPMD-annotated jit.

Layout convention: [batch, seq, heads, head_dim] ("BTHD"), seq sharded
over `sp`, batch over `dp`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P


NEG_INF = -1e30


def _block_attn(q, k, v, scale, mask):
    """Scores + masked softmax numerator pieces for one KV block.

    q: [B,Tq,H,D], k/v: [B,Tk,H,D], mask: [Tq,Tk] bool (True=keep).
    Returns (m_blk [B,H,Tq], p [B,H,Tq,Tk]) with p = exp(s - m_blk).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, NEG_INF)
    m_blk = jnp.max(s, axis=-1)
    p = jnp.exp(s - m_blk[..., None])
    if mask is not None:
        # a fully-masked row yields exp(NEG_INF - NEG_INF) = 1s; zero it
        any_valid = jnp.any(mask, axis=-1)  # [Tq]
        p = p * any_valid[None, None, :, None]
        m_blk = jnp.where(any_valid[None, None], m_blk, NEG_INF)
    return m_blk, p


def _ring_attention_local(
    q, k, v, *, axis_name: str, batch_axis: str, causal: bool, scale: float
):
    """Per-device body (inside shard_map). q,k,v: [B, T_local, H, D]."""
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, t_local, h, d = q.shape
    q_pos = my_idx * t_local + jnp.arange(t_local)  # global query positions

    # cast-to-varying: the scan carry must be device-varying like
    # q/k/v are, or shard_map's vma type checker rejects the loop
    def varying(x):
        return jax.lax.pcast(x, (batch_axis, axis_name), to="varying")

    o = varying(jnp.zeros((b, h, t_local, d), jnp.float32))
    m = varying(jnp.full((b, h, t_local), NEG_INF, jnp.float32))
    l = varying(jnp.zeros((b, h, t_local), jnp.float32))

    def step(carry, i):
        o, m, l, k_blk, v_blk = carry
        # the block we hold at step i originated on device (my_idx - i)
        src = (my_idx - i) % axis_size
        mask = None
        if causal:
            k_pos = src * t_local + jnp.arange(t_local)
            mask = q_pos[:, None] >= k_pos[None, :]
        m_blk, p = _block_attn(
            q.astype(jnp.float32), k_blk.astype(jnp.float32),
            v_blk.astype(jnp.float32), scale, mask,
        )
        m_new = jnp.maximum(m, m_blk)
        alpha = jnp.exp(m - m_new)
        o = o * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p * jnp.exp(m_blk - m_new)[..., None],
            v_blk.astype(jnp.float32),
        )
        l = l * alpha + jnp.sum(p, axis=-1) * jnp.exp(m_blk - m_new)
        m = m_new
        # rotate KV around the ring (ICI neighbor exchange)
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return (o, m, l, k_blk, v_blk), None

    (o, m, l, _, _), _ = jax.lax.scan(
        step, (o, m, l, k, v), jnp.arange(axis_size)
    )
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def _ring_attention_local_flash(
    q, k, v, *, axis_name: str, batch_axis: str, causal: bool,
    scale: float
):
    """Per-device ring body with the Pallas flash kernel computing each
    KV block (ops/flash_attention.py) instead of materializing the
    [B,H,Tq,Tk] block scores in HBM. The kernel returns (out, lse) per
    block; blocks merge through the standard two-estimate recurrence
    m = max(lse, lse_blk); out = out*(1-w) + out_blk*w with
    w = exp(lse_blk - m) / (exp(lse - m) + exp(lse_blk - m)).

    Causality at block granularity: the diagonal block (src == my_idx)
    runs the kernel's causal mask, strictly-past blocks run full
    attention, strictly-future blocks are skipped via lax.cond (the
    taken branch alone executes on TPU — future blocks cost nothing).
    """
    from ..ops.flash_attention import flash_attention_lse

    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, t_local, h, d = q.shape

    def varying(x):
        return jax.lax.pcast(x, (batch_axis, axis_name), to="varying")

    out0 = varying(jnp.zeros((b, t_local, h, d), jnp.float32))
    lse0 = varying(jnp.full((b, h, t_local), NEG_INF, jnp.float32))

    def blk_causal(q, kb, vb):
        o, l = flash_attention_lse(q, kb, vb, causal=True, scale=scale)
        return o.astype(jnp.float32), l

    def blk_full(q, kb, vb):
        o, l = flash_attention_lse(q, kb, vb, causal=False, scale=scale)
        return o.astype(jnp.float32), l

    def blk_skip(q, kb, vb):
        return (
            jnp.zeros((b, t_local, h, d), jnp.float32),
            jnp.full((b, h, t_local), NEG_INF, jnp.float32),
        )

    def step(carry, i):
        out, lse, k_blk, v_blk = carry
        src = (my_idx - i) % axis_size
        if causal:
            o_blk, lse_blk = jax.lax.cond(
                src == my_idx,
                blk_causal,
                lambda q, kb, vb: jax.lax.cond(
                    src < my_idx, blk_full, blk_skip, q, kb, vb
                ),
                q, k_blk, v_blk,
            )
        else:
            o_blk, lse_blk = blk_full(q, k_blk, v_blk)
        m = jnp.maximum(lse, lse_blk)
        a = jnp.exp(lse - m)
        bb = jnp.exp(lse_blk - m)
        w = (bb / (a + bb))  # [B,H,T]; first block: a=0 -> w=1
        w_bthd = jnp.einsum("bht->bth", w)[..., None]
        out = out * (1.0 - w_bthd) + o_blk * w_bthd
        lse = m + jnp.log(a + bb)
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return (out, lse, k_blk, v_blk), None

    (out, _, _, _), _ = jax.lax.scan(
        step, (out0, lse0, k, v), jnp.arange(axis_size)
    )
    return out.astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    axis_name: str = "sp",
    scale: Optional[float] = None,
    use_flash: Optional[bool] = None,
) -> jax.Array:
    """Exact (flash-equivalent) attention with the sequence sharded
    over `axis_name`. Inputs/outputs [B, T, H, D] with T sharded on
    `axis_name` and B on `dp`. T must divide evenly by the axis size.

    `use_flash=None` auto-selects: the Pallas-kernel block body on TPU
    (each device's KV block streams through VMEM instead of
    materializing [B,H,Tq,Tk] scores in HBM), the dense-jnp body
    elsewhere. Both are differentiable and numerically equivalent.
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"
    spec = P("dp", axis_name, None, None)
    if use_flash:
        body = functools.partial(
            _ring_attention_local_flash, axis_name=axis_name,
            batch_axis="dp", causal=causal, scale=scale,
        )
    else:
        body = functools.partial(
            _ring_attention_local, axis_name=axis_name, batch_axis="dp",
            causal=causal, scale=scale,
        )
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # pallas_call outputs carry no vma info; the body is
        # per-device pure either way
        check_vma=not use_flash,
    )
    return fn(q, k, v)


def reference_attention(q, k, v, *, causal: bool = True, scale=None):
    """Plain full-matrix attention (the correctness oracle)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        t_q, t_k = s.shape[-2], s.shape[-1]
        mask = jnp.arange(t_q)[:, None] >= jnp.arange(t_k)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
