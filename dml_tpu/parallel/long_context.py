"""Long-context LM execution: sequences sharded across the mesh.

Ties TransformerLM + ring attention + GSPMD sharding into runnable
forward/train steps: tokens arrive [B, T] with B sharded over `dp` and
T over `sp`, attention runs as the ring (KV blocks rotating over ICI),
and the loss is the standard next-token cross-entropy computed on the
sharded logits (XLA reduces across the mesh).

This is the capability the reference never had — its inputs are single
JPEGs — but which a TPU framework must treat as first-class: context
length scales linearly with `sp` at constant per-chip memory.
"""

from __future__ import annotations

import functools
import logging
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.transformer import TransformerLM
from .ring_attention import ring_attention
from .sharding import partition_params


def make_lm(mesh: Mesh, seq_parallel: str = "ring", **config) -> TransformerLM:
    """A TransformerLM with the right attention for `mesh`: a
    sequence-parallel strategy when the sequence is sharded —
    `seq_parallel="ring"` (KV rotation over ICI, parallel/
    ring_attention.py; works for any head count) or `"ulysses"`
    (two all_to_all head<->seq reshards, parallel/ulysses.py; fewer
    bigger collectives, needs heads % sp == 0 — tradeoff in the
    ulysses module docstring) — and the Pallas flash kernel
    (ops/flash_attention.py) on a single sequence shard, where dp/tp
    sharding of the flash path is GSPMD's job."""
    if seq_parallel not in ("ring", "ulysses"):
        # validate regardless of mesh: a typo must not train silently
        # on an sp=1 dev mesh and only explode on the real pod
        raise ValueError(
            f"seq_parallel must be 'ring' or 'ulysses', "
            f"got {seq_parallel!r}"
        )
    if mesh.shape.get("sp", 1) > 1:
        if seq_parallel == "ulysses":
            from .ulysses import ulysses_attention

            attn = functools.partial(ulysses_attention, mesh=mesh)
        else:
            attn = functools.partial(ring_attention, mesh=mesh)

        def attention(q, k, v, causal=True):
            return attn(q, k, v, causal=causal)
    else:
        from ..ops import flash_attention

        # GSPMD can't partition an opaque pallas_call, so place the
        # kernel per-device explicitly: batch over dp, heads over tp
        # (both embarrassingly parallel in attention)
        spec = P("dp", None, "tp", None)

        def attention(q, k, v, causal=True):
            def local(q, k, v):
                return flash_attention(q, k, v, causal=causal)

            # model.init traces with batch=1; anything not evenly
            # shardable (batch over dp, heads over tp) runs the kernel
            # unplaced — correct, just not partitioned. Warn outside
            # the known init trace: at real batch sizes this replicates
            # full attention on every device, a silent perf cliff.
            if (q.shape[0] % mesh.shape.get("dp", 1) != 0
                    or q.shape[2] % mesh.shape.get("tp", 1) != 0):
                if q.shape[0] > 1:
                    logging.getLogger(__name__).warning(
                        "attention batch=%d heads=%d not divisible by "
                        "mesh dp=%d/tp=%d: running UNPARTITIONED "
                        "(replicated on every device)",
                        q.shape[0], q.shape[2],
                        mesh.shape.get("dp", 1), mesh.shape.get("tp", 1),
                    )
                return flash_attention(q, k, v, causal=causal)
            # checking stays off: pallas_call out_shapes carry no vma
            # info, and the kernel is per-device pure anyway
            return jax.shard_map(
                local, mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=spec, check_vma=False,
            )(q, k, v)

    return TransformerLM(attention=attention, mesh=mesh, **config)


def lm_loss(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Next-token cross entropy; last position predicts nothing."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    tgt = tokens[:, 1:]
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    return nll.mean()


class LongContextLM:
    """A sharded LM with compiled forward + train step.

    >>> mesh = local_mesh(dp=1, sp=8)
    >>> lm = LongContextLM(mesh, vocab_size=1000, d_model=128, seq_len=1024)
    >>> loss = lm.train_step(tokens)          # T=1024 split 8 ways
    >>> logits = lm.forward(tokens)
    """

    def __init__(
        self,
        mesh: Mesh,
        seq_len: int,
        learning_rate: float = 3e-4,
        dtype=jnp.bfloat16,
        seed: int = 0,
        moe_aux_weight: float = 1e-2,
        **config,
    ):
        sp = mesh.shape.get("sp", 1)
        if seq_len % max(sp, 1) != 0:
            raise ValueError(f"seq_len {seq_len} not divisible by sp={sp}")
        self.mesh = mesh
        self.seq_len = seq_len
        self.model = make_lm(mesh, dtype=dtype, **config)
        # init at batch=dp so the ring's shard_map (batch over dp) is
        # satisfiable in the init trace; param shapes are batch-free
        tokens0 = jnp.zeros((max(1, mesh.shape.get("dp", 1)), seq_len), jnp.int32)
        with mesh:
            variables = jax.jit(
                lambda rng: self.model.init(rng, tokens0)
            )(jax.random.PRNGKey(seed))
        self.optimizer = optax.adamw(learning_rate)
        state = {
            "params": variables["params"],
            "opt_state": self.optimizer.init(variables["params"]),
            "step": jnp.zeros((), jnp.int32),
        }
        self._state_sh = partition_params(state, mesh)
        self.state = jax.device_put(state, self._state_sh)
        self._gen_cache: Dict[Any, Any] = {}  # decode-config -> jitted fn
        tok_sh = NamedSharding(mesh, P("dp", "sp"))
        logits_sh = NamedSharding(mesh, P("dp", "sp", None))
        repl = NamedSharding(mesh, P())

        def fwd(params, tokens):
            return self.model.apply({"params": params}, tokens)

        self.forward = jax.jit(
            fwd,
            in_shardings=(self._state_sh["params"], tok_sh),
            out_shardings=logits_sh,
        )
        aux_w = moe_aux_weight

        def train_step(state, tokens):
            def loss_fn(params):
                # collect the MoE load-balance losses sown by MoEMLP —
                # without them in the objective the top-2 router can
                # collapse onto one expert and silently drop tokens
                logits, updated = self.model.apply(
                    {"params": params}, tokens, mutable=["losses"]
                )
                aux_terms = jax.tree_util.tree_leaves(
                    updated.get("losses", {})
                )
                aux = (
                    sum(aux_terms) / len(aux_terms) if aux_terms else 0.0
                )
                return lm_loss(logits, tokens) + aux_w * aux

            loss, grads = jax.value_and_grad(loss_fn)(state["params"])
            updates, opt_state = self.optimizer.update(
                grads, state["opt_state"], state["params"]
            )
            params = optax.apply_updates(state["params"], updates)
            return {
                "params": params,
                "opt_state": opt_state,
                "step": state["step"] + 1,
            }, loss

        self._train_step = jax.jit(
            train_step,
            in_shardings=(self._state_sh, tok_sh),
            out_shardings=(self._state_sh, repl),
            donate_argnums=(0,),
        )

    def train_step(self, tokens: np.ndarray) -> float:
        self.state, loss = self._train_step(self.state, jnp.asarray(tokens))
        return float(jax.device_get(loss))

    def generate(
        self,
        prompt: np.ndarray,
        max_new_tokens: int,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        seed: int = 0,
        quantize_weights: bool = False,
        serve_dtype_cast: bool = True,
        kv_quant: bool = False,
    ) -> np.ndarray:
        """Autoregressive decoding with the trained weights (KV-cache
        path, inference/generate.py); MoE blocks decode with exact
        per-token top-2 routing.

        Decode is HBM-bound, so by default the f32 master weights are
        cast once to the model dtype for serving — that keeps a
        second parameter copy resident;
        pass `serve_dtype_cast=False` to stream the training tree
        directly when HBM is too tight for the copy.
        `quantize_weights=True` serves weight-only int8 instead
        (inference/quantize.py: less HBM, see its docstring);
        `kv_quant=True` stores the KV cache as int8 + per-position
        scales (~1.9x less cache HBM). Serving forms are cached per
        training step."""
        from ..inference.generate import LMConfig, generate as _generate

        m = self.model
        cfg = LMConfig(
            vocab_size=m.vocab_size, d_model=m.d_model, n_heads=m.n_heads,
            n_layers=m.n_layers, d_ff=m.d_ff, dtype=m.dtype,
            n_kv_heads=m.n_kv_heads, kv_quant=kv_quant,
        )
        # one jitted closure per decode config, cached — repeated
        # serving calls must not re-trace the n_layers decode graph
        key = (prompt.shape, max_new_tokens, temperature, top_k,
               quantize_weights, kv_quant)
        fn = self._gen_cache.get(key)
        if fn is None:
            fn = jax.jit(
                lambda p, pr, r: _generate(
                    p, cfg, pr, max_new_tokens,
                    temperature=temperature, top_k=top_k, rng=r,
                )
            )
            self._gen_cache[key] = fn
        # serving weights: decode is HBM-bound, so streaming f32 master
        # weights wastes half the bandwidth — serve a model-dtype
        # (bf16) cast by default, or the int8 tree (capacity always;
        # throughput when the read fuses).
        # All forms carry the training shardings through (XLA gathers
        # what each op needs; force-replicating would defeat tp
        # sharding for models that only fit partitioned).
        params = self._serving_params(
            quantized=quantize_weights, cast=serve_dtype_cast
        )
        return np.asarray(fn(
            params, jnp.asarray(prompt.astype(np.int32)),
            jax.random.PRNGKey(seed),
        ))

    def _serving_params(self, quantized: bool, cast: bool):
        """Serving-form weights (model-dtype cast, weight-only int8,
        or the training tree itself), cached against the training step
        so serving after more training re-derives them. No copy is
        made when the cast would be a no-op (params already in the
        model dtype) or when the caller opted out."""
        if quantized:
            key = "int8"
        elif cast and any(
            leaf.ndim >= 2 and leaf.dtype != self.model.dtype
            for leaf in jax.tree_util.tree_leaves(self.state["params"])
        ):
            key = "cast"
        else:
            return self.state["params"]  # zero-copy serving
        step = int(jax.device_get(self.state["step"]))
        cached = getattr(self, "_serve_params", None)
        if cached is None or cached[0] != step:
            self._serve_params = (step, {})
        forms = self._serve_params[1]
        if key not in forms:
            if key == "int8":
                from ..inference.quantize import quantize_lm_params

                forms[key] = jax.jit(quantize_lm_params)(
                    self.state["params"]
                )
            else:
                dt = self.model.dtype
                forms[key] = jax.jit(lambda p: jax.tree_util.tree_map(
                    lambda x: x.astype(dt) if x.ndim >= 2 else x, p
                ))(self.state["params"])
        return forms[key]

    def save_checkpoint(self, directory: str, keep: int = 3) -> str:
        from .checkpoint import CheckpointManager

        step = int(jax.device_get(self.state["step"]))
        return CheckpointManager(directory, keep=keep).save(step, self.state)

    def restore_checkpoint(self, directory: str, step=None) -> int:
        from .checkpoint import CheckpointManager

        self.state = CheckpointManager(directory).restore(
            jax.device_get(self.state), step=step, shardings=self._state_sh
        )
        return int(jax.device_get(self.state["step"]))
