"""Ulysses-style sequence parallelism: all_to_all head<->sequence
resharding instead of the ring's KV rotation.

The second of the two standard long-context strategies (SURVEY's
mandate: "ring attention or all-to-all sequence/context parallelism";
ring_attention.py is the first — the reference itself has no sequence
models at all, SURVEY §0). Both compute EXACT attention over a
sequence sharded on the `sp` mesh axis; they differ in how the
communication is shaped:

- **ring**: sp rounds of neighbor `ppermute`, each moving one KV
  block [B, T/sp, H, D] over ICI; compute and communication overlap,
  and it works for ANY head count (even H=1).
- **ulysses** (this module): TWO `all_to_all` collectives total —
  reshard [B, T/sp, H, D] -> [B, T, H/sp, D], run ordinary
  full-sequence attention per head-group on every device (the Pallas
  flash kernel on TPU), reshard back. Communication volume per device
  is 2 x the activation size regardless of sp (the ring moves
  (sp-1)/sp x K AND V around), and the attention itself is a single
  dense-sequence kernel call — but it requires heads % sp == 0 and
  materializes the full T on every device for its head slice, so
  max T is bounded by per-device memory for ONE head group.

Rule of thumb on a v5e pod: prefer ulysses when n_heads >= sp and T
fits per-device at H/sp heads (fewer, bigger collectives; one kernel
launch); prefer ring when sp exceeds the head count (MQA/GQA-heavy
models) or T must scale past single-device memory even per head
group. Measured backing (tools/ring_vs_ulysses.py, HLO collective
footprint): at T=4096 H=8 sp=8 ring moves 28 MB/device over 7 serialized
ppermute rounds vs ulysses' 8 MB in 4 one-shot all_to_alls; at
T=8192 H=16 sp=4, 96 MB vs 64 MB; at H=4 sp=8 ulysses cannot run
(heads % sp != 0) and ring is the only strategy. Both are
differentiable (all_to_all transposes to all_to_all; the flash
kernel carries a custom VJP).

Layout convention matches ring_attention.py: [batch, seq, heads,
head_dim], seq sharded over `sp`, batch over `dp`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P

from .ring_attention import reference_attention


def _ulysses_local(
    q, k, v, *, axis_name: str, causal: bool, scale: float,
    use_flash: bool,
):
    """Per-device body (inside shard_map). q: [B, T/sp, H, D]; k/v
    arrive either at full heads (MQA-ish cases the wrapper
    pre-broadcast) or at their NATIVE kv head count when it divides
    sp — then the cheap local `rep` broadcast below runs AFTER the
    collective, so grouped caches don't inflate communication.

    all_to_all with tiled=True splits `split_axis` across the axis
    and concatenates the received pieces on `concat_axis`:
    [B, T/sp, H, D] --(split H, concat T)--> [B, T, H/sp, D].
    """
    def to_heads(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    qh = to_heads(q)  # [B, T, H/sp, D]
    kh = to_heads(k)  # [B, T, KV/sp, D] — native kv heads ride the
    vh = to_heads(v)  # collective; GQA broadcast happens locally below
    rep = qh.shape[2] // kh.shape[2]
    if rep > 1:
        kh = jnp.repeat(kh, rep, axis=2)
        vh = jnp.repeat(vh, rep, axis=2)
    if use_flash:
        from ..ops.flash_attention import flash_attention

        oh = flash_attention(qh, kh, vh, causal=causal, scale=scale)
    else:
        oh = reference_attention(qh, kh, vh, causal=causal, scale=scale)
    # inverse reshard: [B, T, H/sp, D] -> [B, T/sp, H, D]
    return jax.lax.all_to_all(
        oh, axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def ulysses_attention(
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
    """Exact attention with the sequence sharded over `axis_name`,
    communicated as two all_to_all reshards (see module docstring).
    Inputs/outputs [B, T, H, D] with T sharded on `axis_name` and B
    on `dp`; requires n_heads % axis_size == 0 and T % axis_size == 0.

    GQA/MQA inputs (k/v with fewer heads than q) are broadcast to full
    heads before the reshard — same convention as the flash prefill
    path (inference/generate.py).
    """
    sp = mesh.shape.get(axis_name, 1)
    b, t, h, d = q.shape
    if h % sp:
        raise ValueError(
            f"ulysses needs n_heads ({h}) divisible by {axis_name} "
            f"axis size ({sp}); use ring_attention for head-poor models"
        )
    if t % sp:
        raise ValueError(f"T {t} not divisible by {axis_name}={sp}")
    kv_h = k.shape[2]
    if kv_h != h:
        if h % kv_h:
            raise ValueError(f"q heads {h} not a multiple of kv heads {kv_h}")
        if sp == 1 or kv_h % sp:
            # broadcast to full heads up front when there is no
            # reshard at all (sp == 1: the local kernel needs matched
            # heads) or when kv heads don't split across sp (e.g. MQA
            # on sp=4 — pays n_heads/kv_heads x KV comm;
            # ring_attention avoids that and is usually the better
            # strategy there)
            k = jnp.repeat(k, h // kv_h, axis=2)
            v = jnp.repeat(v, h // kv_h, axis=2)
        # else: kv rides the all_to_all at its NATIVE head count and
        # broadcasts locally after (no inflated collective)
    scale = scale if scale is not None else d ** -0.5
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"
    if sp == 1:
        # degenerate mesh: no resharding to do — one local kernel
        if use_flash:
            from ..ops.flash_attention import flash_attention

            return flash_attention(q, k, v, causal=causal, scale=scale)
        return reference_attention(q, k, v, causal=causal, scale=scale)
    spec = P("dp", axis_name, None, None)
    body = functools.partial(
        _ulysses_local, axis_name=axis_name, causal=causal,
        scale=scale, use_flash=use_flash,
    )
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=not use_flash,
    )(q, k, v)
