"""Multi-chip batched inference: the engine's forward pass spread over
a device mesh.

The single-chip engine (inference/engine.py) is the reference's
per-VM executor rebuilt for TPU; this wraps the same forward in
mesh shardings so one *pod slice* serves a batch: inputs sharded over
`dp` (each chip takes batch/dp images), params replicated over `dp`
and channel-sharded over `tp` (sharding.py). XLA inserts the ICI
collectives; host code stays identical to the single-chip path.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.params_io import init_variables
from ..ops.preprocess import normalize_sharded
from ..models.registry import get_model
from .sharding import partition_params


class ShardedInference:
    """A model compiled for a mesh. Batch size must be a multiple of
    the dp axis (static shapes: one compilation serves every call).

    Two tensor-parallel execution forms:

    - ``param_gather=False`` (Megatron form): compute stays channel-
      sharded end to end; XLA partitions the contractions, so psum
      reduction order differs from a single chip and outputs agree
      only to float tolerance.
    - ``param_gather=True`` (serving-group form, jobs/groups.py):
      weights STAY tp-sharded in HBM (the memory win that lets a
      group hold models no single chip can) but are all-gathered over
      ICI at forward entry, so every dp shard runs the bit-identical
      single-chip program on its batch slice. Outputs are BITWISE
      EQUAL to the single-chip path — the property the worker-group
      pipeline asserts end-to-end (``__graft_entry__.dryrun_multichip``
      part 5) so a degradation/reformation mid-job can never change
      what a query returns.

    The LM serving stack (inference/lm_sharded.py) keeps weights
    resident tp-sharded with NO per-forward gather — the Megatron
    form, token-exact for greedy decode per ``dryrun_multichip``
    part 4. The CNN path here keeps param_gather as its default
    serving form because image batches are one forward per batch
    (one gather), whereas LM decode would pay the gather EVERY chunk
    dispatch — which is exactly why the LM path does not use it.
    """

    def __init__(
        self,
        model_name: str,
        mesh: Mesh,
        batch_size: int,
        variables: Any = None,
        dtype=jnp.bfloat16,
        seed: int = 0,
        param_gather: bool = False,
    ):
        self.spec = get_model(model_name)
        self.mesh = mesh
        self.param_gather = bool(param_gather)
        dp = mesh.shape.get("dp", 1)
        if batch_size % dp != 0:
            raise ValueError(f"batch_size {batch_size} not divisible by dp={dp}")
        self.batch_size = batch_size
        self.dtype = dtype
        if variables is None:
            variables = init_variables(self.spec, seed=seed, dtype=dtype)
        self.num_classes = int(
            variables["params"]["predictions"]["bias"].shape[-1]
        )
        self._shardings = partition_params(variables, mesh)
        self.variables = jax.device_put(variables, self._shardings)
        model = self.spec.build(dtype=dtype)
        batch_sharding = NamedSharding(mesh, P("dp"))
        out_sharding = NamedSharding(mesh, P("dp"))
        replicated = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), variables
        )

        def fwd(vs, batch_u8):
            if self.param_gather:
                # all-gather the tp-sharded weights, then run the
                # replicated (single-chip-identical) program per dp
                # shard — reduction orders match a single chip exactly
                vs = jax.lax.with_sharding_constraint(vs, replicated)
            x = normalize_sharded(
                batch_u8, self.spec.preprocess, dtype, mesh
            )
            return model.apply(vs, x, train=False)

        self._forward = jax.jit(
            fwd,
            in_shardings=(self._shardings, batch_sharding),
            out_shardings=out_sharding,
        )

    def __call__(self, images_u8: np.ndarray) -> np.ndarray:
        """uint8 (N,H,W,3) -> float32 probs (N,classes); N padded up to
        the compiled batch size."""
        n = images_u8.shape[0]
        bs = self.batch_size
        outs = []
        for start in range(0, n, bs):
            chunk = images_u8[start : start + bs]
            pad = bs - chunk.shape[0]
            if pad:
                chunk = np.concatenate(
                    [chunk, np.zeros((pad, *chunk.shape[1:]), np.uint8)]
                )
            probs = self._forward(self.variables, jnp.asarray(chunk))
            outs.append(np.asarray(probs)[: bs - pad if pad else bs])
        if not outs:
            return np.zeros((0, self.num_classes), np.float32)
        return np.concatenate(outs)[:n]
