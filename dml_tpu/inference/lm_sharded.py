"""Weight-resident tp-sharded LM serving + prefill/decode
disaggregation for the cluster pipeline.

PR 5's worker groups served IMAGE jobs sharded (param_gather
ShardedInference) but deliberately forfeited the group's chips for LM
rounds — the pool collapsed back to single-chip slots because the
group engine could not run an LM forward. This module closes that
gap with two serving forms over one group topology, both built on
the SAME deterministic params tree (`lm_backend.lm_spec_parts`) and
the SAME continuous-batching server:

- **weight-resident** (the production form): `shard_lm_params` places
  the tree tp-sharded over the group mesh
  (`parallel.sharding.partition_params` — Megatron channel
  partitioning) and the LMServer's prefill/chunk programs run with
  GSPMD-partitioned contractions. No per-forward gather: the HBM win
  that lets a group hold models no single chip can, with NO ICI
  weight traffic per dispatch. `__graft_entry__.dryrun_multichip`
  part 4 asserts this decode form token-exact vs a single device
  (f32; greedy).
- **disaggregated**: `WorkerGroupSpec.roles` splits the group into
  prefill-role and decode-role members (Gemma-on-TPU serving
  comparison, arxiv 2605.25645: prefill is compute-bound, decode is
  bandwidth-bound — different chips want different work). The decode
  primary ships each batch's prompts to a prefill-role member
  (LM_PREFILL_REQUEST), the prefill worker runs the chunked
  bucket-padded prefill and serializes the KV-cache slab
  (`kv_slab_to_bytes` — bf16 and kv_quant layouts both round-trip
  bit-exact), the decode node pulls the slab over the TCP store data
  plane (`DataPlane.fetch_token_bytes`, TunnelFault applies) and
  adopts it straight into free decode slots
  (`LMServer.submit_prefilled`). A failed handoff (dead peer, tunnel
  fault, oversized prompts) falls back to LOCAL prefill — greedy
  outputs are identical either way, so degradation is a throughput
  event, never a correctness one.

Role assignment lives in `WorkerGroupSpec`/`GroupDirectory` (static
spec + SWIM liveness), so degradation/reform and exactly-once batch
semantics carry over from PR 5 unchanged: a member death mid-decode
raises `GroupDegraded`, the batch rides TASK_FAIL -> requeue onto the
surviving single-chip pool, and completion dedup keeps every batch —
and therefore every emitted token — counted exactly once.

Observability: ``lm_sharded_*`` (batches/tokens by serving mode,
prefill slabs) and ``jobs_kv_handoff_*`` (handoff count by result,
bytes, seconds) metric families; see the observability docstring map.

Speculative decoding rides the same forms (`SPEC_DECODE_SUPPORT`):
``lm_spec["spec_k"] > 0`` arms a derived draft model locally on the
resident primary, while the disagg form puts the draft on the
otherwise-idle prefill-role peers — `LMPrefillBackend` generates
spec_k proposal tokens per request and ships them as an optional
``draft`` field in the slab header (old slabs/readers round-trip
unchanged), and the decode primary verifies them on the adoption
round (`LMServer` shipped-draft verification). The pp>1 form is a
typed exclusion (batch-granular stage schedule, no per-slot verify
seam). Greedy outputs stay bitwise-identical in every placement —
a lost or garbage proposal shortens acceptance, never changes tokens.
"""

from __future__ import annotations

import asyncio
import json
import logging
import struct
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from ..observability import METRICS
from ..tracing import TRACER, TraceContext, current_all_ctxs

log = logging.getLogger(__name__)

_M_SHARDED_BATCHES = METRICS.counter(
    "lm_sharded_batches_total",
    "LM batches served on a group's sharded engine, by serving mode "
    "(resident|disagg|pp)")
_M_SHARDED_TOKENS = METRICS.counter(
    "lm_sharded_tokens_total",
    "generated tokens delivered by group-sharded LM serving")
_M_PREFILL_SLABS = METRICS.counter(
    "lm_sharded_prefill_slabs_total",
    "KV-cache slabs produced by prefill-role workers")
_M_HANDOFF = METRICS.counter(
    "jobs_kv_handoff_total",
    "prefill->decode KV slab handoffs by result (ok|fallback)")
_M_HANDOFF_BYTES = METRICS.counter(
    "jobs_kv_handoff_bytes_total",
    "serialized KV-cache slab bytes pulled over the data plane")
_M_HANDOFF_T = METRICS.histogram(
    "jobs_kv_handoff_seconds",
    "one batch's prefill RPC + slab pull wall (decode side)")


# ----------------------------------------------------------------------
# parameter placement
# ----------------------------------------------------------------------


def shard_lm_params(params: Any, mesh) -> Any:
    """device_put the LM params tree tp-sharded over `mesh` (Megatron
    channel partitioning, parallel/sharding.py). This is the
    weight-RESIDENT placement: each chip holds 1/tp of every sharded
    tensor and GSPMD partitions the serving contractions in place."""
    import jax

    from ..parallel.sharding import partition_params

    return jax.device_put(params, partition_params(params, mesh))


def sharded_lm_backend(
    lm_spec: Dict[str, Any],
    mesh,
    spec_draft_local: bool = True,
) -> "Any":
    """An `LMBackend` whose server runs over `mesh`, its params
    tp-sharded in HBM (`shard_lm_params`), no per-forward gather.

    ``lm_spec["spec_k"] > 0`` arms speculative decoding: a derived
    draft model (config.draft_lm_spec, or lm_spec["spec_draft"]
    overrides) lives next to the target on this mesh and proposes
    spec_k tokens per slot per round. ``spec_draft_local=False``
    (the disaggregated wiring) skips the local draft and arms
    shipped-draft verification only — prefill-role peers run the
    draft and ship proposals in the KV slab header instead, so the
    decode primary spends zero HBM/step-time on drafting. The draft
    tree is small (~1/8 the target's FLOPs at the default halving)
    and stays replicated rather than tp-sharded: per-step draft
    latency is launch-bound at draft sizes, so sharding it would
    trade HBM nobody is short of for extra collective latency on
    the critical decode path.

    Serial (lock) serving mode: a group primary is ONE scheduler
    slot, so batches arrive one at a time and the overlap driver's
    extra thread hop buys nothing."""
    from .lm_backend import LMBackend, lm_spec_parts

    params, cfg = lm_spec_parts(lm_spec)
    sharded = shard_lm_params(params, mesh)
    max_new = int(lm_spec.get("max_new_tokens", 32))
    spec_k = int(lm_spec.get("spec_k", 0) or 0)
    spec_draft = (
        LMBackend._draft_spec_of(lm_spec) if spec_draft_local else None
    )
    be = LMBackend(
        sharded, cfg,
        spec_k=spec_k,
        spec_draft=spec_draft,
        spec_min_accept=lm_spec.get("spec_min_accept"),
        max_new_tokens=max_new,
        max_slots=int(lm_spec.get("max_slots", 4)),
        max_len=int(lm_spec.get("max_len", 1024)),
        chunk=int(lm_spec.get("chunk", max(1, min(max_new, 32)))),
        temperature=float(lm_spec.get("temperature", 0.0)),
        top_k=(
            int(lm_spec["top_k"]) if lm_spec.get("top_k") is not None
            else None
        ),
        seed=int(lm_spec.get("seed", 0)),
        # same knob as LMBackend.from_spec: the sharded decode primary
        # warm-starts from its resident prefix cache too
        kv_cache_bytes=int(
            float(lm_spec.get("kv_cache_mb", 0) or 0) * (1 << 20)
        ),
    )
    # Default serial (lock) serving: a group primary is ONE scheduler
    # slot, so batches arrive one at a time and the overlap driver's
    # extra thread hop buys nothing FOR THROUGHPUT. But the overlap
    # driver is also the continuous-batching join point — concurrent
    # serve() calls merge into one slot grid and a late batch's
    # requests adopt freed slots at the next step boundary instead of
    # waiting for the running batch to drain — so operators chasing
    # TTFT under sustained load flip {"overlap": true} in the spec
    # (same knob LMBackend.from_spec honors).
    be.overlap = bool(lm_spec.get("overlap", False))
    return be


# ----------------------------------------------------------------------
# pipeline-parallel serving (layer-stack sharded over the `pp` axis)
# ----------------------------------------------------------------------


def lm_param_bytes(params: Any) -> int:
    """Total bytes of a params tree (HBM-budget accounting)."""
    import jax

    return int(sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves(params)
    ))


def pp_hbm_report(lm_spec: Dict[str, Any], pp: int) -> Dict[str, Any]:
    """Per-member HBM accounting for a pp group: the block stack
    shards 1/pp per member while embed/ln_out/lm_head replicate. This
    is the number `WorkerGroupSpec.hbm_bytes` is checked against — a
    model whose FULL tree exceeds a member's budget can still serve
    when `per_member_bytes` fits."""
    params, _cfg = lm_spec_parts_cached(lm_spec)
    blocks = {k: v for k, v in params.items() if k.startswith("block_")}
    io = {k: v for k, v in params.items() if not k.startswith("block_")}
    full = lm_param_bytes(params)
    block_b = lm_param_bytes(blocks)
    io_b = lm_param_bytes(io)
    return {
        "full_bytes": full,
        "block_bytes": block_b,
        "io_bytes": io_b,
        "per_member_bytes": io_b + block_b // max(1, int(pp)),
        "pp": int(pp),
    }


_SPEC_CACHE: Dict[str, Tuple[Any, Any]] = {}


def lm_spec_parts_cached(lm_spec: Dict[str, Any]):
    """lm_spec_parts with a process cache keyed on the JSON'd spec —
    the pp wiring consults the tree for byte accounting AND builds the
    engine from it; initializing the weights twice per node is wasted
    startup wall."""
    from .lm_backend import lm_spec_parts

    key = json.dumps(
        {k: v for k, v in lm_spec.items()}, sort_keys=True, default=str
    )
    hit = _SPEC_CACHE.get(key)
    if hit is None:
        hit = lm_spec_parts(lm_spec)
        _SPEC_CACHE[key] = hit
    return hit


class PipelinedLMBackend:
    """GPipe-style pipeline-parallel LM serving over a group mesh's
    ``pp`` axis — the serving graft of `parallel/pipeline.py`'s stage
    logic (same schedule skeleton: stacked stage params sharded over
    `pp`, a single `lax.scan` of ticks inside one `shard_map`, one
    `ppermute` hop per tick, masked bubble ticks), extended with what
    decode needs and prefill doesn't: per-stage KV caches and a RING
    token feedback (the last stage's sampled token rides the same
    wrap-around ppermute edge back to stage 0, where it embeds as the
    next step's input).

    This is the serving form for models DEEPER than one member's HBM:
    each pp device holds only ``n_layers/pp`` transformer blocks (the
    dominant weights) plus the replicated embed/head, so a group of S
    members serves a layer stack no single member could hold
    (`pp_hbm_report` is the accounting the group wiring checks against
    ``WorkerGroupSpec.hbm_bytes``).

    Schedule:

    - **prefill**: microbatch m enters stage 0 at tick m; stage s
      applies its block slice with flash attention and writes its
      layers' KV rows; S + M - 1 ticks total — `pipeline_apply`'s
      exact shape, with the last stage reading per-row true-length
      logits (bucket padding, like the LMServer) and emitting each
      microbatch's first token.
    - **decode**: microbatch m's token k occupies stage s at tick
      (k-1)·S + m + s. With M = S microbatches the ring is FULL: every
      device computes every tick (the S-1-tick bubble only at fill and
      drain). Tokens travel as a separate i32 lane alongside the
      hidden-state buffer, so vocab ids never round-trip through the
      activation dtype.

    Exactness: the stage body is `generate.py`'s `_apply_block` with
    the same flash-prefill / einsum-decode attention closures, applied
    in the same layer order with the same dtypes — greedy outputs are
    token-identical to isolated `generate()` per prompt (asserted by
    the bench and tests/test_lm_sharded.py). Greedy only (sampling
    streams are server-rid-keyed); bf16/f32 cache layouts only
    (kv_quant's scale planes would double the per-tick permute
    traffic for a form the Pallas kernel owns anyway)."""

    def __init__(
        self,
        lm_spec: Dict[str, Any],
        mesh,
        microbatches: Optional[int] = None,
    ):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.mesh = mesh
        self.pp = int(mesh.shape.get("pp", 1))
        if self.pp < 2:
            raise ValueError(
                f"pipeline serving needs a pp axis >= 2, mesh has "
                f"pp={self.pp}"
            )
        for ax in ("dp", "tp", "sp", "ep"):
            if mesh.shape.get(ax, 1) != 1:
                raise ValueError(
                    "the pipeline serving form parallelizes over `pp` "
                    f"only; mesh axis {ax}={mesh.shape[ax]} would "
                    "replicate stage compute and misreport capacity "
                    "(tp x pp composition is the real-ICI remainder, "
                    "ROADMAP item 3)"
                )
        params, cfg = lm_spec_parts_cached(lm_spec)
        if cfg.kv_quant:
            raise ValueError("pipeline serving supports bf16/f32 "
                             "KV cache layouts only (no kv_quant)")
        if cfg.layer_pattern is not None:
            raise ValueError(
                "pipeline serving stacks identical blocks over `pp` and "
                "carries K/V rows a stage; a layer_pattern's layers differ "
                "in kind and a state-space layer's state is no K/V row")
        if cfg.latent is not None:
            raise ValueError(
                "pipeline serving carries K and V rows a stage and attends "
                "them itself; it has no form for latent attention's rows")
        if cfg.n_layers % self.pp:
            raise ValueError(
                f"n_layers {cfg.n_layers} not divisible by pp {self.pp}"
            )
        self.cfg = cfg
        self.model = str(lm_spec.get("name", "LM"))
        self.max_new_tokens = int(lm_spec.get("max_new_tokens", 32))
        self.max_len = int(lm_spec.get("max_len", 1024))
        self.temperature = float(lm_spec.get("temperature", 0.0))
        if self.temperature != 0.0:
            raise ValueError("pipeline serving is greedy-only")
        self.microbatches = int(microbatches or self.pp)
        if not (1 <= self.microbatches <= self.pp):
            raise ValueError(
                f"microbatches {self.microbatches} must be in "
                f"[1, pp={self.pp}] (the ring holds at most one "
                "in-flight token per stage)"
            )
        self._jax = jax
        self._jnp = jnp
        # stage-stacked block params: leaves [n_layers, ...] sharded
        # over pp on the stack axis — each device holds its contiguous
        # n_layers/pp slice and NOTHING else of the stack
        blocks = [params[f"block_{i}"] for i in range(cfg.n_layers)]
        stacked = jax.tree_util.tree_map(
            lambda *ls: jnp.stack(ls, axis=0), *blocks
        )
        self.stacked = jax.device_put(
            stacked,
            jax.tree_util.tree_map(
                lambda l: NamedSharding(
                    mesh, P("pp", *([None] * (l.ndim - 1)))
                ),
                stacked,
            ),
        )
        self.io = jax.device_put(
            {k: v for k, v in params.items()
             if not k.startswith("block_")},
            NamedSharding(mesh, P()),
        )
        self.hbm = pp_hbm_report(lm_spec, self.pp)
        self._per_query = 0.05
        self._fns: Dict[Tuple, Any] = {}
        self.tokens_delivered = 0
        self.batches_served = 0

    # -- compiled stage programs --------------------------------------

    #: bound on retained (slots, bucket, T) program pairs — each is
    #: two GSPMD compiles; a long-lived node must not grow this with
    #: every batch-shape it ever saw
    MAX_COMPILED_SHAPES = 8

    def _stage_fns(self, slots: int, bucket: int, new_tokens: int):
        """(prefill_fn, decode_fn) for one (slots, bucket, T) shape,
        jit-cached with FIFO eviction at `MAX_COMPILED_SHAPES`.
        `slots` must be a multiple of `microbatches`."""
        key = (slots, bucket, new_tokens)
        fns = self._fns.get(key)
        if fns is None:
            while len(self._fns) >= self.MAX_COMPILED_SHAPES:
                self._fns.pop(next(iter(self._fns)))
            fns = (
                self._build_prefill(slots, bucket),
                self._build_decode(slots, new_tokens),
            )
            self._fns[key] = fns
        return fns

    def _build_prefill(self, slots: int, bucket: int):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from ..ops.flash_attention import flash_attention
        from .generate import _apply_block, _head

        cfg = self.cfg
        s = self.pp
        m_count = self.microbatches
        mb = slots // m_count
        l_per = cfg.n_layers // s
        max_len = self.max_len
        grp = cfg.n_heads // cfg.kv_heads
        fwd = [(i, (i + 1) % s) for i in range(s)]
        positions = jnp.arange(bucket)

        def flash_fn(q, k, v):
            if grp > 1:
                k = jnp.repeat(k, grp, axis=2)
                v = jnp.repeat(v, grp, axis=2)
            return flash_attention(q, k, v, causal=True)

        def per_device(blocks, io, prompts, tps):
            # blocks leaves arrive [l_per, ...] (the pp shard); the
            # replicated io/prompts arrive whole
            stage = jax.lax.axis_index("pp")
            prompts_m = prompts.reshape(m_count, mb, bucket)
            tps_m = tps.reshape(m_count, mb)
            cache0 = {
                f"block_{l}": {
                    "k": jnp.zeros(
                        (slots, cfg.kv_heads, max_len, cfg.head_dim),
                        cfg.dtype),
                    "v": jnp.zeros(
                        (slots, cfg.kv_heads, max_len, cfg.head_dim),
                        cfg.dtype),
                }
                for l in range(l_per)
            }
            zero_act = jnp.zeros((mb, bucket, cfg.d_model), cfg.dtype)
            firsts0 = jnp.zeros((m_count, mb), jnp.int32)

            def tick(carry, t):
                act, cache, firsts = carry
                m = t - stage
                valid = (m >= 0) & (m < m_count)
                mc = jnp.clip(m, 0, m_count - 1)
                inj = io["embed"]["embedding"][
                    prompts_m[jnp.clip(t, 0, m_count - 1)]
                ].astype(cfg.dtype)
                x = jnp.where(stage == 0, inj, act)
                off = mc * mb
                for l in range(l_per):
                    blk = jax.tree_util.tree_map(
                        lambda a, l=l: a[l], blocks
                    )
                    x, k, v = _apply_block(
                        blk, cfg, x, positions, flash_fn
                    )
                    pad4 = ((0, 0), (0, 0), (0, max_len - bucket), (0, 0))
                    kh = jnp.pad(
                        jnp.swapaxes(k, 1, 2).astype(cfg.dtype), pad4)
                    vh = jnp.pad(
                        jnp.swapaxes(v, 1, 2).astype(cfg.dtype), pad4)
                    name = f"block_{l}"
                    old_k = jax.lax.dynamic_slice_in_dim(
                        cache[name]["k"], off, mb, axis=0)
                    old_v = jax.lax.dynamic_slice_in_dim(
                        cache[name]["v"], off, mb, axis=0)
                    cache[name] = {
                        "k": jax.lax.dynamic_update_slice_in_dim(
                            cache[name]["k"],
                            jnp.where(valid, kh, old_k), off, axis=0),
                        "v": jax.lax.dynamic_update_slice_in_dim(
                            cache[name]["v"],
                            jnp.where(valid, vh, old_v), off, axis=0),
                    }
                # last stage: per-row true-length logits -> greedy
                # first token (the bucket-padding exactness contract)
                x_last = jax.vmap(
                    lambda row, i: jax.lax.dynamic_slice_in_dim(
                        row, i, 1, axis=0)
                )(x, jnp.clip(tps_m[mc] - 1, 0, bucket - 1))
                tok = jnp.argmax(
                    _head(io, cfg, x_last), axis=-1).astype(jnp.int32)
                write = valid & (stage == s - 1)
                firsts = firsts.at[mc].set(
                    jnp.where(write, tok, firsts[mc])
                )
                nxt = jax.lax.ppermute(x, "pp", fwd)
                return (nxt, cache, firsts), None

            (act, cache, firsts), _ = jax.lax.scan(
                tick, (zero_act, cache0, firsts0),
                jnp.arange(s + m_count - 1),
            )
            # every pp row must agree for the replicated out_spec
            return jax.lax.psum(firsts, "pp"), cache

        cache_spec = {
            f"block_{l}": {"k": P("pp"), "v": P("pp")}
            for l in range(l_per)
        }
        # check_vma off: the checker rejects the masked psum-collect
        mapped = jax.shard_map(
            per_device,
            mesh=self.mesh,
            in_specs=(
                jax.tree_util.tree_map(lambda _: P("pp"), self.stacked),
                jax.tree_util.tree_map(lambda _: P(), self.io),
                P(), P(),
            ),
            out_specs=(P(), cache_spec),
            check_vma=False,
        )
        return jax.jit(mapped)

    def _build_decode(self, slots: int, new_tokens: int):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from .generate import _apply_block, _head

        cfg = self.cfg
        s = self.pp
        m_count = self.microbatches
        mb = slots // m_count
        l_per = cfg.n_layers // s
        max_len = self.max_len
        t_new = int(new_tokens)
        grp = cfg.n_heads // cfg.kv_heads
        hd = cfg.head_dim
        fwd = [(i, (i + 1) % s) for i in range(s)]
        n_ticks = (t_new - 1) * s + m_count - 1 if t_new > 1 else 0

        def per_device(blocks, io, cache, firsts, pos0):
            stage = jax.lax.axis_index("pp")
            firsts_m = firsts.reshape(m_count, mb)
            pos0_m = pos0.reshape(m_count, mb)
            act0 = jnp.zeros((mb, cfg.d_model), cfg.dtype)
            ids0 = jnp.zeros((mb,), jnp.int32)
            out0 = jnp.zeros((t_new, m_count, mb), jnp.int32)

            def tick(carry, t):
                act, ids, cache, out = carry
                v_idx = t - stage
                vc = jnp.clip(v_idx, 0, n_ticks)
                m = vc % s
                k = vc // s + 1
                valid = (v_idx >= 0) & (m < m_count) & (k < t_new)
                mc = jnp.clip(m, 0, m_count - 1)
                off = mc * mb
                # stage 0 input token: the prefill first token at
                # k == 1, else the ring-delivered token from the last
                # stage's previous tick
                ids_in = jnp.where(k == 1, firsts_m[mc], ids)
                x0 = io["embed"]["embedding"][ids_in].astype(cfg.dtype)
                x = jnp.where(stage == 0, x0, act)[:, None, :]
                # input token k-1 writes at its row's position
                # tp + k - 1; invalid ticks park on the reserved last
                # row (never a live position: the last GENERATED token
                # is never written, so real writes stop at max_len-2)
                pos_row = pos0_m[mc] + (k - 1)
                pos_w = jnp.where(valid, pos_row, max_len - 1)
                positions = pos_w[:, None]
                att_valid = (
                    jnp.arange(max_len)[None, :] <= pos_w[:, None]
                )
                for l in range(l_per):
                    blk = jax.tree_util.tree_map(
                        lambda a, l=l: a[l], blocks
                    )
                    name = f"block_{l}"

                    def attn_fn(q, k_new, v_new, name=name):
                        # mirror batched_decode_step's einsum path,
                        # restricted to this microbatch's rows
                        kh = jnp.swapaxes(k_new, 1, 2).astype(cfg.dtype)
                        vh = jnp.swapaxes(v_new, 1, 2).astype(cfg.dtype)
                        ck = cache[name]["k"]
                        cv = cache[name]["v"]
                        for bi in range(mb):
                            start_k = [off + bi, 0, 0, 0]
                            ck = jax.lax.dynamic_update_slice(
                                ck, kh[bi : bi + 1],
                                [start_k[0], jnp.int32(0),
                                 pos_w[bi], jnp.int32(0)],
                            )
                            cv = jax.lax.dynamic_update_slice(
                                cv, vh[bi : bi + 1],
                                [start_k[0], jnp.int32(0),
                                 pos_w[bi], jnp.int32(0)],
                            )
                        cache[name] = {"k": ck, "v": cv}
                        rows_k = jax.lax.dynamic_slice_in_dim(
                            ck, off, mb, axis=0)
                        rows_v = jax.lax.dynamic_slice_in_dim(
                            cv, off, mb, axis=0)
                        qg = q.astype(jnp.float32).reshape(
                            mb, 1, cfg.kv_heads, grp, hd)
                        sc = jnp.einsum(
                            "bqkgd,bktd->bkgqt", qg,
                            rows_k.astype(jnp.float32)
                        ) * (hd ** -0.5)
                        sc = jnp.where(
                            att_valid[:, None, None, None, :],
                            sc, -1e30)
                        p = jax.nn.softmax(sc, axis=-1)
                        attn = jnp.einsum(
                            "bkgqt,bktd->bqkgd", p,
                            rows_v.astype(jnp.float32))
                        return attn.reshape(mb, 1, cfg.n_heads, hd)

                    x, _, _ = _apply_block(
                        blk, cfg, x, positions, attn_fn
                    )
                tok = jnp.argmax(
                    _head(io, cfg, x), axis=-1).astype(jnp.int32)
                kc = jnp.clip(k, 0, t_new - 1)
                write = valid & (stage == s - 1)
                out = out.at[kc, mc].set(
                    jnp.where(write, tok, out[kc, mc])
                )
                nxt_h = jax.lax.ppermute(x[:, 0, :], "pp", fwd)
                nxt_ids = jax.lax.ppermute(
                    jnp.where(stage == s - 1, tok, ids), "pp", fwd
                )
                return (nxt_h, nxt_ids, cache, out), None

            if n_ticks > 0:
                (act, ids, cache, out), _ = jax.lax.scan(
                    tick, (act0, ids0, cache, out0),
                    jnp.arange(n_ticks),
                )
            else:
                out = out0
            return jax.lax.psum(out, "pp")

        l_per_spec = {
            f"block_{l}": {"k": P("pp"), "v": P("pp")}
            for l in range(l_per)
        }
        mapped = jax.shard_map(
            per_device,
            mesh=self.mesh,
            in_specs=(
                jax.tree_util.tree_map(lambda _: P("pp"), self.stacked),
                jax.tree_util.tree_map(lambda _: P(), self.io),
                l_per_spec,
                P(), P(),
            ),
            out_specs=P(),
            check_vma=False,
        )
        return jax.jit(mapped)

    # -- serving ------------------------------------------------------

    def generate_batch(
        self, prompts: Sequence[np.ndarray], budgets: Sequence[int]
    ) -> List[List[int]]:
        """Decode a batch through the pipeline; returns per-prompt
        generated tokens (len = its budget). The whole batch decodes
        to the max budget (static ring schedule) and each row
        truncates to its own — mixed budgets cost the difference, the
        documented pp trade (continuous slot refill is the
        single-chip/tp servers' territory)."""
        import jax.numpy as jnp

        from .lm_server import _bucket

        if not prompts:
            return []
        prompts = [
            np.asarray(p, np.int32).reshape(-1) for p in prompts
        ]
        budgets = [int(b) for b in budgets]
        for p, b in zip(prompts, budgets):
            if p.size == 0:
                raise ValueError("empty prompt")
            if b < 1:
                raise ValueError("budget must be >= 1")
            if p.size + b > self.max_len:
                raise ValueError(
                    f"prompt {p.size} + budget {b} exceeds max_len "
                    f"{self.max_len}"
                )
        n = len(prompts)
        # coarse shape buckets: ingress traffic varies batch size and
        # per-request budget per formed batch, and every distinct
        # (slots, bucket, t_new) triple is TWO multi-second GSPMD
        # compiles — round the decode horizon and microbatch count up
        # to powers of two (prompt lengths already bucket via
        # _bucket). Rows truncate to their OWN budget and overflow
        # cache writes clamp onto the reserved scratch row, so
        # padding costs ticks, never answers.
        t_new = max(budgets)
        if t_new > 1:
            t_new = 1 << (t_new - 1).bit_length()
        t_new = min(t_new, self.max_len - 1)
        bucket = min(_bucket(max(p.size for p in prompts)), self.max_len)
        m_groups = -(-n // self.microbatches)
        m_groups = 1 << (m_groups - 1).bit_length()
        slots = m_groups * self.microbatches
        padded = np.zeros((slots, bucket), np.int32)
        tps = np.ones(slots, np.int32)
        for i in range(slots):
            p = prompts[i if i < n else 0]  # dummy rows repeat row 0
            padded[i, : p.size] = p
            padded[i, p.size:] = p[-1]  # the server's pad policy
            tps[i] = p.size
        prefill_fn, decode_fn = self._stage_fns(slots, bucket, t_new)
        firsts, cache = prefill_fn(
            self.stacked, self.io, jnp.asarray(padded), jnp.asarray(tps)
        )
        toks = decode_fn(
            self.stacked, self.io, cache, firsts.reshape(-1),
            jnp.asarray(tps),
        )  # [t_new, M, mb]
        firsts_host = np.asarray(firsts).reshape(-1)
        rest = np.asarray(toks).reshape(t_new, -1)  # [t_new, slots]
        out: List[List[int]] = []
        for i in range(n):
            seq = [int(firsts_host[i])] + [
                int(rest[k, i]) for k in range(1, budgets[i])
            ]
            out.append(seq)
        return out

    def serve_files(
        self, paths: Sequence[str], on_dispatch=None
    ) -> Tuple[Dict[str, Any], float, Dict[str, float]]:
        """JobService-shaped serve (the LMBackend.serve_files
        contract): parse prompt files, pipeline-decode, key results by
        path."""
        from .lm_backend import parse_prompt_file

        parsed = [
            parse_prompt_file(p, self.cfg.vocab_size) for p in paths
        ]
        prompts = [ids for ids, _ in parsed]
        budgets = [
            b if b is not None else self.max_new_tokens
            for _, b in parsed
        ]
        t0 = time.monotonic()
        toks = self.generate_batch(prompts, budgets)
        infer_time = time.monotonic() - t0
        delivered = sum(len(t) for t in toks)
        self.tokens_delivered += delivered
        self.batches_served += 1
        if paths:
            self._per_query = infer_time / len(paths)
        return (
            {p: {"tokens": list(t)} for p, t in zip(paths, toks)},
            infer_time,
            self.cost_constants(),
        )

    async def backend(
        self, model: str, paths: Sequence[str]
    ) -> Tuple[Dict[str, Any], float, Dict[str, float]]:
        del model
        return await asyncio.to_thread(self.serve_files, paths)

    def decode_tokens_total(self) -> int:
        return int(self.tokens_delivered)

    def cost_constants(self) -> Dict[str, float]:
        return {
            "load_time": 0.0,
            "first_query": self._per_query,
            "per_query": self._per_query,
            "batch_size": max(self.microbatches, 1),
        }

    def close(self) -> None:  # symmetry with LMBackend
        pass


# ----------------------------------------------------------------------
# KV-cache slab serialization (the prefill->decode handoff payload)
# ----------------------------------------------------------------------

_SLAB_MAGIC = b"KVS1"


def kv_slab_to_bytes(entries: Sequence[Dict[str, Any]]) -> bytes:
    """Serialize prefilled-request slabs into one transferable blob.

    Each entry: ``{"prompt_len", "budget", "first_token", "rows"}``
    where `rows` is the per-layer cache for positions < prompt_len
    with the batch axis stripped — bf16 layout ``{block_i: {k, v:
    [KV, Tp, D]}}`` or the kv_quant layout (int8 values + f32 scales
    as ``[KV, 1, Tp]``). Layout-generic: leaves are walked in sorted
    order and each records (shape, dtype), so both layouts — and any
    future one — round-trip BIT-EXACT (bfloat16 rides as ml_dtypes
    raw bytes, not a float32 widening)."""
    header_entries = []
    bufs: List[bytes] = []
    for e in entries:
        leaves = []
        for name in sorted(e["rows"]):
            for key in sorted(e["rows"][name]):
                a = np.ascontiguousarray(e["rows"][name][key])
                leaves.append([name, key, list(a.shape), a.dtype.name])
                bufs.append(a.tobytes())
        he = {
            "prompt_len": int(e["prompt_len"]),
            "budget": int(e.get("budget", 0)),
            "first_token": int(e["first_token"]),
            "leaves": leaves,
        }
        if e.get("draft") is not None:
            # remote-draft shipment (speculative decoding): the
            # prefill peer's k proposed tokens ride the slab header.
            # OPTIONAL field — blobs without it (older peers) round-
            # trip unchanged, and a reader that predates it ignores
            # unknown keys; proposals can never change output values.
            he["draft"] = [int(t) for t in e["draft"]]
        header_entries.append(he)
    header = json.dumps(
        {"entries": header_entries}, separators=(",", ":")
    ).encode()
    return (
        _SLAB_MAGIC + struct.pack("!I", len(header)) + header
        + b"".join(bufs)
    )


def _np_dtype(name: str) -> np.dtype:
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


#: max payload bytes per pushed stream chunk — small enough that the
#: decode side adopts early requests while later ones still transfer,
#: large enough that framing overhead stays noise
SLAB_STREAM_CHUNK = 1 << 18


async def push_slab_entry(feed, idx: int, blob: bytes) -> None:
    """Frame ONE request's serialized slab onto a live StreamFeed:
    a JSON header chunk ``{"i", "size"}`` followed by the blob in
    ``SLAB_STREAM_CHUNK`` pieces. Chunk boundaries survive the wire
    (each push is one length-prefixed frame, data_plane fetch_stream),
    so the reader's framing state machine needs no resync. Pushes via
    the feed's BACKPRESSURED ``put`` — the lossy drop-oldest push()
    is a token-streaming latency trade that would garble the framed
    sequence, and buffering without bound would hold a whole share's
    slabs when the puller lags prefill compute."""
    await feed.put(json.dumps(
        {"i": int(idx), "size": len(blob)}
    ).encode())
    for off in range(0, len(blob), SLAB_STREAM_CHUNK):
        await feed.put(blob[off : off + SLAB_STREAM_CHUNK])


async def push_slab_error(feed, idx: int, error: str) -> None:
    """Frame a per-request prefill failure: the decode side falls
    back to a LOCAL prefill for exactly this request."""
    await feed.put(json.dumps(
        {"i": int(idx), "error": str(error)[:500]}
    ).encode())


async def iter_slab_stream(chunks):
    """Async generator over a framed slab stream: yields
    ``(index, entry_or_None)`` per request as its chunks complete —
    None for a request the peer reported failed. Raises ValueError on
    a garbled frame (the caller treats the REST of that peer's share
    as failed handoffs; requests already yielded stay adopted)."""
    header: Optional[Dict[str, Any]] = None
    buf: List[bytes] = []
    got = 0
    async for chunk in chunks:
        if header is None:
            try:
                header = json.loads(chunk.decode())
                if not isinstance(header, dict) or "i" not in header:
                    raise ValueError
            except (ValueError, UnicodeDecodeError):
                raise ValueError("garbled slab-stream header frame")
            if "error" in header:
                yield int(header["i"]), None
                header = None
                continue
            buf, got = [], 0
            if int(header.get("size", -1)) < 0:
                raise ValueError("slab-stream header without size")
            if header["size"] == 0:
                raise ValueError("zero-size slab entry")
            continue
        buf.append(chunk)
        got += len(chunk)
        if got > int(header["size"]):
            raise ValueError(
                f"slab stream overran its declared size "
                f"({got} > {header['size']})"
            )
        if got == int(header["size"]):
            entries = kv_slab_from_bytes(b"".join(buf))
            if len(entries) != 1:
                raise ValueError(
                    f"slab-stream entry held {len(entries)} slabs"
                )
            yield int(header["i"]), entries[0]
            header = None
    if header is not None:
        raise ValueError("slab stream ended mid-entry")


def kv_slab_from_bytes(data: bytes) -> List[Dict[str, Any]]:
    """Inverse of `kv_slab_to_bytes`; raises ValueError on a
    truncated/foreign blob (the decode side treats that as a failed
    handoff and falls back to local prefill)."""
    if data[:4] != _SLAB_MAGIC:
        raise ValueError("not a KV slab (bad magic)")
    (hlen,) = struct.unpack("!I", data[4:8])
    header = json.loads(data[8 : 8 + hlen].decode())
    off = 8 + hlen
    out: List[Dict[str, Any]] = []
    for e in header["entries"]:
        rows: Dict[str, Dict[str, np.ndarray]] = {}
        for name, key, shape, dtype_name in e["leaves"]:
            dt = _np_dtype(dtype_name)
            count = int(np.prod(shape, dtype=np.int64))
            end = off + count * dt.itemsize
            if end > len(data):
                raise ValueError("truncated KV slab")
            arr = np.frombuffer(
                data, dtype=dt, count=count, offset=off
            ).reshape(shape)
            off = end
            rows.setdefault(name, {})[key] = arr
        entry = {
            "prompt_len": int(e["prompt_len"]),
            "budget": int(e["budget"]),
            "first_token": int(e["first_token"]),
            "rows": rows,
        }
        if e.get("draft") is not None:
            entry["draft"] = [int(t) for t in e["draft"]]
        out.append(entry)
    if off != len(data):
        raise ValueError("KV slab size mismatch")
    return out


# ----------------------------------------------------------------------
# prefill-role worker
# ----------------------------------------------------------------------


class LMPrefillBackend:
    """The prefill half of disaggregated serving: runs the chunked
    (bucket-padded, one forward per prompt) prefill and emits the
    serialized KV slab. Registered on prefill-role nodes via
    ``JobService.register_lm(..., prefill=...)``; the service's
    LM_PREFILL_REQUEST handler calls `slabs_bytes` in a thread and
    exposes the result on the data plane.

    Prompt-length buckets bound compilations exactly like the
    LMServer's placement path, and `logits_index = tp-1` keeps the
    first sampled token identical to an unpadded forward — so the
    decode side's adopted continuation is token-for-token what its
    own local prefill would have produced (greedy)."""

    def __init__(
        self, params: Any, cfg, max_len: int = 1024,
        draft: Optional[Tuple[Any, Any]] = None,
        draft_k: int = 0,
    ):
        import jax

        if cfg.has_state:
            raise ValueError(
                "a prefill worker ships a slab of K/V rows by token; a "
                "state-space layer's scan state and convolution window "
                "are not such rows (LMServer.submit_prefilled refuses "
                "them too)")
        if cfg.attention_layers is not None:
            raise ValueError(
                "a prefill worker ships a slab of K/V rows by token; a "
                "window layer's ring is not such rows, and the slab "
                "names one head count for the stack "
                "(LMServer.submit_prefilled refuses them too)")
        if cfg.latent is not None:
            # `LMServer.submit_prefilled` adopts a slab of latent rows as
            # it adopts any; the chunk-streamed wire format that carries
            # one from here is untried on such a leaf
            raise ValueError(
                "a prefill worker's slab wire format is untried on latent "
                "attention's rows: serve such a model on one server")
        self.params = params
        self.cfg = cfg
        self.max_len = int(max_len)
        self._jax = jax
        self._fns: Dict[int, Any] = {}
        self.slabs_built = 0
        # remote-draft speculation (``draft=(draft_params, draft_cfg)``
        # + draft_k > 0): after each prefill this peer ALSO runs the
        # small draft model on prompt+first_token and ships the k
        # proposed tokens in the slab header — prefill-role members
        # idle during decode-heavy phases, so the draft forward rides
        # otherwise-dead capacity. The decode side seeds the adopted
        # request's first verify round from them; a missing/garbage
        # shipment only costs acceptance, never correctness.
        self.draft = draft
        self.draft_k = int(draft_k)
        self.drafts_shipped = 0

    def _prefill_fn(self, bucket: int):
        fn = self._fns.get(bucket)
        if fn is None:
            from .generate import prefill

            # max_len == bucket: the slab carries only positions
            # < prompt_len, so there is no reason to materialize (or
            # slice back out of) a max_len-padded cache here
            fn = self._jax.jit(
                lambda p, pr, li, b=bucket: prefill(
                    p, self.cfg, pr, b, logits_index=li
                )
            )
            self._fns[bucket] = fn
        return fn

    def prefill_one(
        self, prompt: np.ndarray, budget: int,
        draft_k: Optional[int] = None,
    ) -> Dict[str, Any]:
        import jax.numpy as jnp

        from .lm_server import _bucket

        prompt = np.asarray(prompt, np.int32).reshape(-1)
        tp = int(prompt.size)
        if tp == 0:
            raise ValueError("empty prompt")
        if tp + int(budget) > self.max_len:
            raise ValueError(
                f"prompt {tp} + budget {budget} exceeds max_len "
                f"{self.max_len}"
            )
        bucket = min(_bucket(tp), self.max_len)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :tp] = prompt
        padded[0, tp:] = prompt[-1]  # same pad policy as the server
        logits, pcache = self._prefill_fn(bucket)(
            self.params, jnp.asarray(padded), jnp.int32(tp - 1)
        )
        first = int(np.asarray(jnp.argmax(logits, axis=-1))[0])
        rows: Dict[str, Dict[str, np.ndarray]] = {}
        for name, kv in pcache.items():
            rows[name] = {}
            for key, arr in kv.items():
                a = np.asarray(arr)[0]  # strip the batch axis
                t_axis = 2 if key.endswith("_s") else 1
                sl = [slice(None)] * a.ndim
                sl[t_axis] = slice(0, tp)
                rows[name][key] = np.ascontiguousarray(a[tuple(sl)])
        entry = {
            "prompt_len": tp,
            "budget": int(budget),
            "first_token": first,
            "rows": rows,
        }
        k = self.draft_k if draft_k is None else min(
            int(draft_k), self.draft_k
        )
        if self.draft is not None and k > 0 and int(budget) > 1:
            # draft proposals for the adopted request's first verify
            # round: the draft model's greedy continuation after
            # consuming [prompt, first_token] — exactly what a decode-
            # side device draft would propose from (cur=first, pos=tp).
            # Per-request failure discipline: a broken draft forfeits
            # the shipment, never the slab.
            try:
                from .generate import generate as _generate

                dp, dcfg = self.draft
                ext = np.concatenate(
                    [prompt, np.asarray([first], np.int32)]
                )
                d = np.asarray(_generate(
                    dp, dcfg, jnp.asarray(ext)[None], int(k)
                ))[0]
                entry["draft"] = [int(t) for t in d]
                self.drafts_shipped += 1
            except Exception as e:
                log.warning("draft shipment failed (%r); slab only", e)
        return entry

    def slabs_bytes(
        self, prompts: Sequence[Sequence[int]], budgets: Sequence[int],
        draft_k: Optional[int] = None,
    ) -> bytes:
        entries = [
            self.prefill_one(np.asarray(p, np.int32), b, draft_k=draft_k)
            for p, b in zip(prompts, budgets)
        ]
        self.slabs_built += len(entries)
        _M_PREFILL_SLABS.inc(len(entries))
        return kv_slab_to_bytes(entries)

    async def stream_slabs(
        self,
        prompts: Sequence[Sequence[int]],
        budgets: Sequence[int],
        feed,
        draft_k: Optional[int] = None,
    ) -> None:
        """Chunk-streamed serving form: prefill each prompt IN TURN
        and push its framed slab onto the live feed the moment it is
        built — the decode side adopts request i while request i+1's
        prefill is still computing (transfer overlaps compute; the
        whole-slab form serializes them). A per-request failure frames
        an error entry (decode falls back locally for that request);
        the feed closes at the end either way."""
        try:
            for i, (p, b) in enumerate(zip(prompts, budgets)):
                try:
                    entry = await asyncio.to_thread(
                        self.prefill_one, np.asarray(p, np.int32),
                        int(b), draft_k,
                    )
                    blob = kv_slab_to_bytes([entry])
                except asyncio.CancelledError:
                    raise
                except Exception as e:
                    await push_slab_error(feed, i, repr(e))
                    continue
                await push_slab_entry(feed, i, blob)
                self.slabs_built += 1
                _M_PREFILL_SLABS.inc()
        finally:
            feed.close()


# ----------------------------------------------------------------------
# group backends (decode side)
# ----------------------------------------------------------------------


def _member_check(
    group_name: Optional[str],
    members: Tuple[str, ...],
    alive_fn: Optional[Callable[[], Set[str]]],
) -> None:
    if members and alive_fn is not None:
        from ..jobs.groups import _check_members

        _check_members(group_name or "?", members, alive_fn)


def sharded_lm_group_backend(
    be,  # LMBackend over the group mesh (sharded_lm_backend)
    *,
    model_name: str,
    group_name: str,
    members: Tuple[str, ...] = (),
    alive_fn: Optional[Callable[[], Set[str]]] = None,
    capacity: Optional[float] = None,
    mode: str = "resident",
):
    """JobService LM GROUP backend over a mesh-sharded `LMBackend`:
    the LM analog of `jobs.groups.sharded_backend`. Serves exactly
    one model (``backend.model``); member liveness is checked around
    the decode so a mid-batch group degradation raises
    `GroupDegraded` (-> TASK_FAIL -> requeue onto the single-chip
    pool) instead of acking tokens a broken mesh could not have
    produced."""
    cap = float(capacity if capacity is not None
                else max(len(members), 1))

    async def backend(model: str, paths: List[str]):
        _member_check(group_name, members, alive_fn)
        results, infer_time, cost = await asyncio.to_thread(
            be.serve_files, list(paths)
        )
        _member_check(group_name, members, alive_fn)
        _M_SHARDED_BATCHES.inc(group=group_name, mode=mode)
        _M_SHARDED_TOKENS.inc(
            sum(len(v.get("tokens", ())) for v in results.values()),
            group=group_name,
        )
        return results, infer_time, cost

    backend.model = model_name
    backend.group_name = group_name
    backend.capacity = cap
    backend.lm_backend = be
    return backend


class DisaggLMBackend:
    """Decode-role group backend with the prefill offloaded: scatter
    the batch's prompt token ids across EVERY live prefill-role
    member (multi-prefill fan-out), pull each peer's serialized KV
    slabs back over the data plane, adopt them into the
    (weight-resident sharded) decode server, stream tokens through
    the normal completion path.

    Two handoff forms:

    - ``handoff="stream"`` (default): each peer ACKs a live
      data-plane stream token IMMEDIATELY and pushes per-request slab
      chunks as its prefills complete (`LMPrefillBackend.stream_slabs`
      -> `iter_slab_stream`); the decode primary adopts each request
      into a free slot the moment ITS chunks land — transfer overlaps
      prefill compute and the first decoded token leaves before the
      last prefill chunk is even computed.
    - ``handoff="slab"``: the PR-6 whole-slab pull (one blob per peer
      after its whole share prefilled), kept as the bench's measured
      comparison baseline.

    Fallback discipline is PER REQUEST: a dead/straggling peer, a
    tunnel fault mid-stream, a garbled chunk, a truncated slab, or a
    failed adoption demotes exactly the affected requests to LOCAL
    prefill on the decode engine
    (``jobs_kv_handoff_total{result="fallback"}`` per request; adopted
    requests tick ``result="ok"``). Greedy outputs are identical
    either way, so ANY handoff failure changes throughput
    attribution, never answers."""

    #: shares whose combined token count exceeds this ride the local
    #: path: the UDP control frame caps at ~60 KB and the ids travel
    #: as JSON ints
    MAX_FRAME_TOKENS = 8_000

    def __init__(
        self,
        be,  # LMBackend over the group mesh (decode side)
        *,
        model_name: str,
        group_name: str,
        node,
        store,
        members: Tuple[str, ...] = (),
        alive_fn: Optional[Callable[[], Set[str]]] = None,
        capacity: Optional[float] = None,
        prefill_timeout: float = 30.0,
        handoff: str = "stream",
        fanout: int = 0,
        draft_k: int = 0,
    ):
        if handoff not in ("stream", "slab"):
            raise ValueError(f"unknown handoff form {handoff!r}")
        self.be = be
        self.model = model_name
        self.group_name = group_name
        self.node = node
        self.store = store
        self.members = tuple(members)
        self.alive_fn = alive_fn
        self.capacity = float(
            capacity if capacity is not None else max(len(members), 1)
        )
        self.prefill_timeout = float(prefill_timeout)
        self.handoff = handoff
        #: max prefill peers a batch scatters across; 0 = all alive
        self.fanout = int(fanout)
        self._roles = node.spec.group_roles_unique(group_name)
        self.handoffs = 0  # requests adopted from a peer slab
        self.handoff_bytes = 0
        self.fallbacks = 0  # requests locally prefilled instead
        #: requests kept LOCAL because the decode server's KV prefix
        #: cache already covers their prompt (inference/kv_cache.py) —
        #: a warm start, not a handoff failure
        self.warm_locals = 0
        self.last_ttft_s: Optional[float] = None
        self.lm_backend = be
        #: remote-draft speculation: ask prefill peers to ship this
        #: many draft tokens with each slab (0 = none). Peers without
        #: a draft model simply omit the field; the decode side's
        #: verify round treats an absent shipment as zero acceptance,
        #: so a peer killed mid-verify (chaos) degrades to the plain
        #: per-request local-fallback story with identical outputs.
        self.draft_k = int(draft_k)

    def _prefill_peers(self) -> List[Any]:
        """Alive prefill-role members (not this node), deterministic
        order, capped at `fanout` when set."""
        alive = self.alive_fn() if self.alive_fn is not None else set()
        me = self.node.me.unique_name
        peers = [
            self.node.spec.node_by_unique_name(u)
            for u in sorted(self._roles)
            if self._roles[u] == "prefill" and u != me and u in alive
        ]
        if self.fanout > 0:
            peers = peers[: self.fanout]
        return peers

    async def _prefill_rpc(
        self, peer, model: str, prompts: List[np.ndarray],
        budgets: List[int], stream: bool,
        traces: Optional[List[Dict[str, Any]]] = None,
    ) -> Dict[str, Any]:
        """LM_PREFILL_REQUEST with one retry (at-most-once UDP): a
        single dropped frame costs half the window, not all of it;
        a duplicate just mints another token/stream the TTL reaps.
        ``traces`` ships the share's per-request trace contexts so the
        prefill member's span lands in the stitched cross-node tree."""
        from ..cluster.wire import MsgType

        reply = None
        for _ in range(2):
            try:
                reply = await self.node.request(
                    peer, MsgType.LM_PREFILL_REQUEST,
                    {
                        "model": model,
                        "prompts": [[int(t) for t in p] for p in prompts],
                        "budgets": [int(b) for b in budgets],
                        "stream": bool(stream),
                        **({"draft_k": self.draft_k}
                           if self.draft_k > 0 else {}),
                        **({"traces": traces} if traces else {}),
                    },
                    timeout=self.prefill_timeout / 2,
                )
                break
            except (TimeoutError, asyncio.TimeoutError):
                continue
        if reply is None:
            raise TimeoutError(
                f"prefill peer {peer} never answered "
                f"({self.prefill_timeout:g}s)"
            )
        if not reply.get("ok"):
            raise RuntimeError(f"prefill peer: {reply.get('error')}")
        return reply

    async def _fetch_slabs(
        self, model: str, prompts: List[np.ndarray], budgets: List[int],
        peer=None, traces: Optional[List[Dict[str, Any]]] = None,
    ) -> Optional[List[Dict[str, Any]]]:
        """Whole-slab pull of one peer's share (``handoff="slab"``).
        Returns the share's slab entries, or None when no peer is
        available/eligible."""
        from ..cluster.store_service import data_addr

        if peer is None:
            peers = self._prefill_peers()
            peer = peers[0] if peers else None
        if peer is None:
            return None
        if sum(int(p.size) for p in prompts) > self.MAX_FRAME_TOKENS:
            return None
        t0 = time.monotonic()
        reply = await self._prefill_rpc(
            peer, model, prompts, budgets, stream=False, traces=traces
        )
        data = await self.store.data_plane.fetch_token_bytes(
            data_addr(peer), reply["token"],
            timeout=self.prefill_timeout,
        )
        slabs = kv_slab_from_bytes(data)
        if len(slabs) != len(prompts):
            raise ValueError(
                f"peer returned {len(slabs)} slabs for "
                f"{len(prompts)} prompts"
            )
        _M_HANDOFF_T.observe(time.monotonic() - t0)
        _M_HANDOFF_BYTES.inc(len(data))
        self.handoff_bytes += len(data)
        return slabs

    def _shares(
        self, n: int, n_peers: int
    ) -> List[List[int]]:
        """Contiguous near-equal index shares, one per peer — request
        order within a share is prompt order, so a peer's stream
        adopts in the order the decode grid wants them."""
        if n_peers <= 0:
            return []
        base, extra = divmod(n, n_peers)
        shares: List[List[int]] = []
        start = 0
        for j in range(n_peers):
            size = base + (1 if j < extra else 0)
            shares.append(list(range(start, start + size)))
            start += size
        return shares

    def _share_spans(
        self, ctxs: Optional[List[Optional[TraceContext]]],
        idxs: List[int], delivered: Set[int], peer,
        t0_wall: float, failed: bool,
    ) -> None:
        """One `handoff` span per sampled request of a share; a
        request the share failed to deliver carries the ``fallback``
        event (a tail exemplar, captured regardless of sampling — the
        demotion to local prefill is exactly what explains that
        request's tail latency)."""
        if not ctxs:
            return
        t1_wall = time.time()
        for gi, c in zip(idxs, ctxs):
            if c is None:
                continue
            s = TRACER.start_span(
                "handoff", ctx=c, node=self.node.me.unique_name,
                t0=t0_wall,
                labels={"peer": getattr(peer, "unique_name", str(peer)),
                        "group": self.group_name,
                        "form": self.handoff},
            )
            if failed and gi not in delivered:
                s.event("fallback")
                s.label(result="fallback")
            else:
                s.label(result="ok")
            s.end(t1_wall)

    async def _pull_share_stream(
        self, peer, model: str, idxs: List[int],
        prompts: List[np.ndarray], budgets: List[int], arrivals,
        ctxs: Optional[List[Optional[TraceContext]]] = None,
    ) -> None:
        """One peer's streamed share: RPC for the stream token, then
        reassemble per-request entries as their chunks land, handing
        each to the decode thread's arrival queue. ANY failure demotes
        the share's REMAINING requests to local prefill — requests
        already handed over stay adopted."""
        from ..cluster.store_service import data_addr

        t0 = time.monotonic()
        t0_wall = time.time()
        delivered: Set[int] = set()
        try:
            if sum(int(prompts[i].size) for i in idxs) \
                    > self.MAX_FRAME_TOKENS:
                raise ValueError("share exceeds control-frame budget")
            reply = await self._prefill_rpc(
                peer, model,
                [prompts[i] for i in idxs],
                [budgets[i] for i in idxs],
                stream=True,
                traces=[c.to_wire() for c in (ctxs or []) if c],
            )
            if not reply.get("stream"):
                # old-form peer: its token is a whole-slab file —
                # treat as a one-shot arrival of the whole share
                data = await self.store.data_plane.fetch_token_bytes(
                    data_addr(peer), reply["token"],
                    timeout=self.prefill_timeout,
                )
                slabs = kv_slab_from_bytes(data)
                if len(slabs) != len(idxs):
                    raise ValueError("slab count mismatch")
                _M_HANDOFF_BYTES.inc(len(data))
                self.handoff_bytes += len(data)
                for i, entry in zip(idxs, slabs):
                    arrivals.put_nowait((i, entry))
                    delivered.add(i)
                self._share_spans(ctxs, idxs, delivered, peer,
                                  t0_wall, failed=False)
                return
            chunks = self.store.data_plane.fetch_stream(
                data_addr(peer), reply["token"],
                timeout=self.prefill_timeout,
            )
            async for local_i, entry in iter_slab_stream(
                _counting(chunks, lambda n: _note_bytes(self, n))
            ):
                if not (0 <= local_i < len(idxs)):
                    raise ValueError(
                        f"peer streamed unknown index {local_i}"
                    )
                gi = idxs[local_i]
                arrivals.put_nowait((gi, entry))
                delivered.add(gi)
            if len(delivered) != len(idxs):
                raise ValueError(
                    f"stream ended after {len(delivered)}/{len(idxs)} "
                    "entries"
                )
            _M_HANDOFF_T.observe(time.monotonic() - t0)
            self._share_spans(ctxs, idxs, delivered, peer,
                              t0_wall, failed=False)
        except asyncio.CancelledError:
            raise
        except Exception as e:
            log.warning(
                "%s: streamed KV handoff from %s failed (%r); local "
                "prefill for its %d remaining request(s)",
                self.group_name, peer, e, len(idxs) - len(delivered),
            )
            self._share_spans(ctxs, idxs, delivered, peer,
                              t0_wall, failed=True)
            for i in idxs:
                if i not in delivered:
                    arrivals.put_nowait((i, None))

    async def _pull_share_slab(
        self, peer, model: str, idxs: List[int],
        prompts: List[np.ndarray], budgets: List[int], arrivals,
        ctxs: Optional[List[Optional[TraceContext]]] = None,
    ) -> None:
        """One peer's whole-slab share (the comparison form)."""
        t0_wall = time.time()
        try:
            slabs = await self._fetch_slabs(
                model,
                [prompts[i] for i in idxs],
                [budgets[i] for i in idxs],
                peer=peer,
                traces=[c.to_wire() for c in (ctxs or []) if c],
            )
            if slabs is None:
                raise RuntimeError("no eligible peer/share")
            for i, entry in zip(idxs, slabs):
                arrivals.put_nowait((i, entry))
            self._share_spans(ctxs, idxs, set(idxs), peer,
                              t0_wall, failed=False)
        except asyncio.CancelledError:
            raise
        except Exception as e:
            log.warning(
                "%s: KV handoff from %s failed (%r); local prefill "
                "for its %d request(s)",
                self.group_name, peer, e, len(idxs),
            )
            self._share_spans(ctxs, idxs, set(), peer,
                              t0_wall, failed=True)
            for i in idxs:
                arrivals.put_nowait((i, None))

    async def __call__(
        self, model: str, paths: List[str], on_token=None
    ):
        import queue as _queue

        from .lm_backend import parse_prompt_file

        _member_check(self.group_name, self.members, self.alive_fn)
        parsed = [
            parse_prompt_file(p, self.be.cfg.vocab_size) for p in paths
        ]
        prompts = [ids for ids, _ in parsed]
        budgets = [
            b if b is not None else self.be.max_new_tokens
            for _, b in parsed
        ]
        # validate against decode capacity BEFORE spending a handoff
        for p, prompt, budget in zip(paths, prompts, budgets):
            if prompt.size + budget > self.be.server.max_len:
                raise ValueError(
                    f"{p}: prompt of {prompt.size} tokens + budget "
                    f"{budget} exceeds the server's max_len "
                    f"{self.be.server.max_len}"
                )
        peers = self._prefill_peers()
        arrivals: "_queue.Queue" = _queue.Queue()
        tasks: List[asyncio.Task] = []
        t_batch0 = time.monotonic()
        # per-request trace contexts, routed by local path (the job
        # service re-keyed them before the backend call): request i's
        # prefill/handoff/decode spans land in ITS cross-node trace.
        # UNFILTERED on purpose: a fallback on an unsampled request
        # still pins its tail exemplar (the span records with the
        # context's own sampled flag; the exemplar pin is always-on),
        # while the decode span below gates on .sampled itself.
        by_path = {c.key: c for c in current_all_ctxs()}
        req_ctxs: List[Optional[TraceContext]] = [
            by_path.get(p) for p in paths
        ]
        # KV-prefix warm hits stay LOCAL: a prompt the decode server's
        # prefix cache already covers would have a peer recompute rows
        # the adopter then throws away — route it down the local-
        # prefill arm instead, where placement warm-starts with a
        # suffix-only prefill (inference/kv_cache.py). Peeked without
        # a pin: an entry evicted before placement just cold-prefills
        # locally, so the routing choice can never change answers.
        warm_idx: Set[int] = set()
        kvc = getattr(self.be.server, "kv_cache", None)
        if kvc is not None and self.be.server.temperature == 0.0:
            for i, p in enumerate(prompts):
                if kvc.match_len(p) > 0:
                    warm_idx.add(i)
                    arrivals.put_nowait((i, None))
        remote = [i for i in range(len(prompts)) if i not in warm_idx]
        if not peers:
            # no live prefill peer at all: every request is a typed
            # local fallback
            for i in remote:
                arrivals.put_nowait((i, None))
                TRACER.note_exemplar(
                    req_ctxs[i], "fallback",
                    node=self.node.me.unique_name,
                    labels={"group": self.group_name,
                            "reason": "no_prefill_peer"},
                )
        else:
            shares = self._shares(len(remote), len(peers))
            pull = (
                self._pull_share_stream if self.handoff == "stream"
                else self._pull_share_slab
            )
            for peer, share in zip(peers, shares):
                idxs = [remote[j] for j in share]
                if not idxs:
                    continue
                tasks.append(asyncio.ensure_future(pull(
                    peer, model, idxs, prompts, budgets, arrivals,
                    ctxs=[req_ctxs[i] for i in idxs],
                )))
        _member_check(self.group_name, self.members, self.alive_fn)
        ttft_box: List[float] = []

        def on_first() -> None:
            ttft_box.append(time.monotonic() - t_batch0)

        decode_wall0 = time.time()
        try:
            toks, infer_time, stats = await asyncio.to_thread(
                self.be.serve_prefilled_stream,
                prompts, budgets, arrivals,
                self.be._token_cbs(paths, on_token),
                on_first,
                max(self.prefill_timeout * 2, 30.0),
            )
        finally:
            for t in tasks:
                if not t.done():
                    t.cancel()
        decode_wall1 = time.time()
        for c in req_ctxs:
            if c is not None and c.sampled:
                TRACER.start_span(
                    "decode", ctx=c, node=self.node.me.unique_name,
                    t0=decode_wall0,
                    labels={"group": self.group_name,
                            "mode": "disagg",
                            "shared": len(prompts)},
                ).end(decode_wall1)
        self.last_ttft_s = ttft_box[0] if ttft_box else None
        self.handoffs += stats["adopted"]
        # warm-routed requests ride the "local" arm of the decode
        # stream but are cache HITS, not handoff failures — count them
        # apart so the fallback metric keeps meaning "a peer/handoff
        # let us down" (an entry evicted between the routing peek and
        # placement cold-prefills locally yet still counts warm here;
        # a routing-accuracy approximation, never an answer change)
        n_warm = len(warm_idx)
        fallbacks = max(0, stats["local"] - n_warm)
        self.fallbacks += fallbacks
        self.warm_locals += n_warm
        if stats["adopted"]:
            _M_HANDOFF.inc(stats["adopted"], result="ok")
        if fallbacks:
            _M_HANDOFF.inc(fallbacks, result="fallback")
        if n_warm:
            _M_HANDOFF.inc(n_warm, result="local_warm")
        results = {
            p: {"tokens": [int(t) for t in ts]}
            for p, ts in zip(paths, toks)
        }
        cost = self.be.cost_constants()
        _member_check(self.group_name, self.members, self.alive_fn)
        _M_SHARDED_BATCHES.inc(group=self.group_name, mode="disagg")
        _M_SHARDED_TOKENS.inc(
            sum(len(v.get("tokens", ())) for v in results.values()),
            group=self.group_name,
        )
        return results, infer_time, cost


def _counting(chunks, note):
    """Wrap an async chunk iterator, reporting each chunk's size."""
    async def it():
        async for c in chunks:
            note(len(c))
            yield c

    return it()


def _note_bytes(gb: "DisaggLMBackend", n: int) -> None:
    gb.handoff_bytes += n
    _M_HANDOFF_BYTES.inc(n)


def check_hbm_budget(
    g, lm_spec: Dict[str, Any], pp: Optional[int] = None
) -> Optional[Dict[str, Any]]:
    """Enforce ``WorkerGroupSpec.hbm_bytes`` against the model's
    weight layout: a pp group passes when each member's slice
    (`pp_hbm_report.per_member_bytes`) fits; a non-pp group must fit
    the FULL tree per member (weight-resident tp shards storage too,
    but degradation-to-single-chip materializes the full tree, so the
    budget is the honest bound).
    ``pp`` overrides the spec's declared axis with the RESOLVED mesh
    size — a spec axis of -1 (fill remaining devices) must be checked
    against what it resolved to, not clamped to non-pp. Returns the
    report, or None when no budget is declared. Raising HERE turns
    first-batch OOM into a startup config error."""
    budget = int(getattr(g, "hbm_bytes", 0) or 0)
    if budget <= 0:
        return None
    if pp is None:
        pp = max(int(g.mesh.pp), 1)
    rep = pp_hbm_report(lm_spec, pp)
    need = rep["per_member_bytes"] if pp > 1 else rep["full_bytes"]
    if need > budget:
        hint = (
            "" if pp > 1 else
            " — a model bigger than one member's HBM needs a pp axis "
            "on the group mesh (pipeline-parallel serving)"
        )
        raise RuntimeError(
            f"group {g.name}: model {lm_spec.get('name')!r} needs "
            f"{need} bytes per member, hbm_bytes budget is "
            f"{budget}{hint}"
        )
    return rep


# Which serving forms support speculative decoding, and how the
# draft is placed — consulted by wire_lm_group and documented in the
# README's break-even table. "local" = draft model lives on the
# decode mesh; "shipped" = prefill-role peers run the draft and ship
# proposals in the slab header (decode verifies only); False = typed
# exclusion (the pp engine's batch-granular stage schedule has no
# per-slot verify seam — ROADMAP item 4 remainder).
SPEC_DECODE_SUPPORT: Dict[str, Any] = {
    "resident": "local",
    "disagg": "shipped",
    "pp": False,
}


def wire_lm_group(node, store, lm_spec: Dict[str, Any]):
    """Production wiring for a NodeApp registering `lm_spec`: returns
    ``(group_backend, prefill_backend)`` for this node's role in a
    worker group that declares the model in ``lm_models`` — the LM
    analog of `jobs.groups.wire_group_backend`.

    - group PRIMARY: a sharded decode engine over the group mesh —
      PIPELINE-parallel when the group mesh has a ``pp`` axis > 1
      (each member holds only its layer-stack slice; models deeper
      than one member's HBM), else weight-resident tp-sharded; when
      any OTHER member carries the ``prefill`` role, the
      disaggregated form (multi-peer streamed prefill handoff +
      per-request local fallback; ``lm_spec["kv_handoff"]`` picks
      "stream" (default) or "slab", ``lm_spec["prefill_fanout"]``
      caps the peer fan-out, 0 = all alive);
    - prefill-role members: an `LMPrefillBackend` (serves
      LM_PREFILL_REQUEST, whole-slab and streamed forms);
    - everyone else (lenders without a role, ungrouped nodes):
      ``(None, None)`` — they serve single-chip like before.

    Raises at startup if the group mesh wants more devices than this
    host sees (a group that silently served single-chip while the
    pool weighted it at group capacity would be slower than no
    groups at all — same contract as `group_engine_backend`), or if
    the model's per-member weight bytes exceed a declared
    ``hbm_bytes`` budget (`check_hbm_budget`).

    ``lm_spec["kv_cache_mb"]`` gives the tp/disagg decode primary a
    worker-resident KV prefix cache (inference/kv_cache.py): retired
    requests' slabs warm-start prompts that extend a cached prefix,
    and the disagg form keeps cache-covered prompts local instead of
    shipping them to a prefill peer. The pp>1 engine is excluded —
    its batch-granular stage schedule has no per-request slot
    adoption to warm-start (the tp x pp x cache composition rides
    with ROADMAP item 3's real-ICI remainder)."""
    spec = node.spec
    uname = node.me.unique_name
    g = spec.group_of_unique(uname)
    name = str(lm_spec.get("name", "LM"))
    if g is None or name not in g.lm_models:
        return None, None
    members = spec.group_members_unique(g.name)
    roles = spec.group_roles_unique(g.name)

    def alive() -> Set[str]:
        return {n.unique_name for n in node.membership.alive_nodes()}

    spec_k = int(lm_spec.get("spec_k", 0) or 0)
    prefill = None
    if roles.get(uname) == "prefill":
        if int(g.mesh.pp) == 1:
            # the prefill backend materializes the FULL tree, so the
            # budget gate must hold it to the full-tree bound
            check_hbm_budget(g, lm_spec, pp=1)
            params, cfg = lm_spec_parts_cached(lm_spec)
            draft = None
            if spec_k > 0:
                # prefill-role members idle during decode-heavy
                # phases; spec_k>0 puts the DRAFT model here so they
                # propose tokens for the decode primary to verify
                # (shipped in the slab header over the PR-8 wire
                # path). Same derivation as the local-draft form so
                # both placements propose identical tokens.
                from .lm_backend import LMBackend, lm_spec_parts

                dspec = LMBackend._draft_spec_of(lm_spec)
                if dspec is not None:
                    draft = lm_spec_parts(dspec)
            prefill = LMPrefillBackend(
                params, cfg, max_len=int(lm_spec.get("max_len", 1024)),
                draft=draft, draft_k=spec_k,
            )
        else:
            # a pp group's primary never sends LM_PREFILL_REQUEST (the
            # pipelined engine owns its own prefill schedule), so
            # building the full-tree prefill backend here would hold
            # weights the declared budget says don't fit — and never
            # serve a single slab (tp x pp x disagg composition is the
            # real-ICI remainder, ROADMAP item 3)
            log.warning(
                "%s: prefill role on %s ignored — the pp>1 serving "
                "form does not disaggregate", g.name, uname,
            )
    gb = None
    if members and uname == members[0]:
        import jax

        from ..parallel.mesh import make_mesh

        devices = jax.devices()
        sizes = (g.mesh.dp, g.mesh.tp, g.mesh.sp, g.mesh.pp, g.mesh.ep)
        if -1 not in sizes:
            want = 1
            for s in sizes:
                want *= s
            if len(devices) < want:
                raise RuntimeError(
                    f"group {g.name} mesh needs {want} devices, host "
                    f"sees {len(devices)}"
                )
            devices = devices[:want]
        mesh = make_mesh(g.mesh, devices=devices)
        pp = int(mesh.shape.get("pp", 1))
        # budget-check against the RESOLVED pp: a spec axis of -1
        # (fill remaining) may have resolved to a pipelined layout
        # that fits where the full tree would not
        check_hbm_budget(g, lm_spec, pp=pp)
        disagg = any(
            r == "prefill" for u, r in roles.items() if u != uname
        )
        if pp > 1:
            # pipeline-parallel primary: the layer stack shards over
            # pp; prefill disaggregation composes at the BATCH level
            # only (the pp engine owns its own pipelined prefill), so
            # role-split pp groups serve the pp form directly
            if spec_k > 0:
                # typed exclusion, not a crash: the pp engine's
                # batch-granular stage schedule has no per-slot
                # verify seam (SPEC_DECODE_SUPPORT["pp"] is False);
                # spec decode on pp rides ROADMAP item 4's remainder
                log.warning(
                    "%s: spec_k=%d on %s ignored — the pp>1 serving "
                    "form does not speculative-decode", g.name,
                    spec_k, uname,
                )
            be_pp = PipelinedLMBackend(lm_spec, mesh)
            cap = float(pp * mesh.shape.get("dp", 1))
            gb = sharded_lm_group_backend(
                be_pp, model_name=name, group_name=g.name,
                members=members, alive_fn=alive, capacity=cap,
                mode="pp",
            )
        else:
            # disagg decode primary arms shipped-draft verification
            # only (SPEC_DECODE_SUPPORT["disagg"] = "shipped"): the
            # draft lives on prefill-role peers, so the primary's
            # HBM and step loop carry zero draft cost; the resident
            # form hosts the draft locally ("local")
            be = sharded_lm_backend(
                lm_spec, mesh, spec_draft_local=not disagg,
            )
            cap = float(
                mesh.shape.get("dp", 1) * mesh.shape.get("tp", 1)
            )
            if disagg:
                gb = DisaggLMBackend(
                    be, model_name=name, group_name=g.name, node=node,
                    store=store, members=members, alive_fn=alive,
                    capacity=cap,
                    handoff=str(lm_spec.get("kv_handoff", "stream")),
                    fanout=int(lm_spec.get("prefill_fanout", 0) or 0),
                    draft_k=spec_k,
                )
            else:
                gb = sharded_lm_group_backend(
                    be, model_name=name, group_name=g.name,
                    members=members, alive_fn=alive, capacity=cap,
                )
    return gb, prefill
