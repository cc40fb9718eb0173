"""Pipeline parallelism (GPipe-style) over a `pp` mesh axis.

Net-new vs the reference, which has no model parallelism of any kind
(SURVEY §2 "Parallelism strategies": TP/PP/SP/EP all absent) — this is
the TPU-native layer-sharding path for models too deep for one chip.

Design (the scaling-book recipe, compiler-friendly throughout):

- Stage parameters are STACKED along a leading axis of size S and
  sharded over `pp`, so each device holds exactly one stage's weights
  in HBM and XLA never gathers them.
- The schedule is a single `lax.scan` of S + M - 1 ticks inside one
  `shard_map`: at every tick each device applies its stage to its
  current activation and hands the result to its pp-neighbor with
  `ppermute` (one hop over ICI per tick — the canonical
  neighbor-exchange pattern, same as ring attention's KV rotation).
- Stage 0 injects microbatch `t` at tick `t`; the last stage's output
  at tick `t` is microbatch `t - (S-1)`. Ticks outside a microbatch's
  window compute garbage that is masked out of the collected output —
  the classic S-1-tick bubble, amortized by M.
- Static shapes everywhere: the scan carries one [mb, ...] activation
  per device; masks are `jnp.where` on traced tick indices; no python
  control flow depends on data.
- The whole thing is differentiable: `ppermute`'s transpose is the
  reverse permute, so `jax.grad` through the scan yields backward
  pipeline communication automatically (reverse schedule, same wire
  pattern). `remat=True` wraps the stage fn in `jax.checkpoint` to
  trade recompute for activation memory, which is what makes M large
  enough to hide the bubble.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# stage_fn(stage_params, x_microbatch) -> y_microbatch (same shape family)
StageFn = Callable[[Any, jax.Array], jax.Array]


def stack_stage_params(per_stage: Sequence[Any]) -> Any:
    """Stack S per-stage param pytrees along a new leading axis
    (shard it over `pp` with `stage_sharding`)."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves, axis=0), *per_stage
    )


def stage_sharding(mesh: Mesh, stacked: Any) -> Any:
    """NamedShardings placing each stage's slice on its pp device row."""
    def spec_for(leaf):
        return NamedSharding(mesh, P("pp", *([None] * (leaf.ndim - 1))))

    return jax.tree_util.tree_map(spec_for, stacked)


def pipeline_apply(
    stage_fn: StageFn,
    stacked_params: Any,
    x: jax.Array,
    *,
    mesh: Mesh,
    num_microbatches: int,
    axis: str = "pp",
    remat: bool = False,
) -> jax.Array:
    """Run `x` [B, ...] through S pipelined stages; returns [B, ...]
    with the last stage's output.

    `stacked_params` leaves have leading dim S = mesh.shape[axis];
    B must divide into `num_microbatches` equal microbatches.
    """
    s = mesh.shape[axis]
    m = num_microbatches
    b = x.shape[0]
    if b % m != 0:
        raise ValueError(f"batch {b} not divisible by microbatches {m}")
    mb = b // m
    fn = jax.checkpoint(stage_fn) if remat else stage_fn
    xm = x.reshape(m, mb, *x.shape[1:])

    fwd = [(i, (i + 1) % s) for i in range(s)]

    def per_device(params, xm_local):
        # shard_map hands each device its stage slice with the leading
        # pp-sharded axis of size 1
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)
        # xm_local is [M, mb_local, ...] — mb_local may be a dp shard
        zero = jnp.zeros(xm_local.shape[1:], xm_local.dtype)

        def tick(carry, t):
            state = carry  # activation arriving from the previous stage
            inject = xm_local[jnp.clip(t, 0, m - 1)]
            cur = jnp.where(stage == 0, inject, state)
            out = fn(params, cur)
            nxt = jax.lax.ppermute(out, axis, fwd)
            # last stage emits microbatch t-(S-1) at tick t; masked
            # ticks contribute zeros and are dropped by the caller
            emit_idx = t - (s - 1)
            valid = (stage == s - 1) & (emit_idx >= 0)
            emit = jnp.where(valid, out, jnp.zeros_like(out))
            return nxt, (emit, emit_idx)

        _, (emits, idxs) = jax.lax.scan(
            tick, zero, jnp.arange(s + m - 1)
        )
        # scatter the valid emissions into microbatch order; psum
        # replicates the last stage's result to every pp row so the
        # caller sees one global [M, mb, ...] array
        out = jnp.zeros_like(xm_local)
        out = out.at[jnp.clip(idxs, 0, m - 1)].add(emits)
        return jax.lax.psum(out, axis)

    # the microbatch's example dim shards over dp when it divides —
    # each dp row then pipelines its own slice of the batch (pp and dp
    # compose); otherwise replicate (identical redundant compute)
    dp = mesh.shape.get("dp", 1)
    x_spec = P(None, "dp") if dp > 1 and mb % dp == 0 else P()
    # check_vma off: the checker rejects the masked psum-collect above
    ym = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(
            jax.tree_util.tree_map(lambda _: P(axis), stacked_params),
            x_spec,  # stage 0 injects its dp-row's microbatch slice
        ),
        out_specs=x_spec,
        check_vma=False,
    )(stacked_params, xm)
    return ym.reshape(b, *x.shape[1:])
