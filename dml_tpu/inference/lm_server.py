"""Continuous-batching LM server: requests join and leave a running
decode batch.

The plain `generate` path serves one request shape per call; a real
serving workload has requests of different prompt lengths and budgets
arriving while others are mid-decode. This server keeps `max_slots`
sequences decoding together in ONE compiled program:

- a fixed slot grid: per-layer KV cache [slots, KV, max_len, D]
  (head-major — init_cache's layout, which the Pallas decode kernel
  streams; this module only ever indexes the slot axis 0) plus
  per-slot position/current-token vectors — static shapes, so one
  compilation serves every mix of requests;
- `submit()` prefills the new request's prompt in one flash-attention
  forward (prompt lengths bucketed to powers of two from 512, rows to
  powers of two, to bound distinct compilations; a round's prompts
  share the groups that pad least, `_prefill_groups`) and writes its
  cache rows into a free slot — placement
  is FULLY async: the per-slot next-token/position state is
  device-resident, the first sampled token's value rides the next
  step's packed readback, and nothing blocks on the link;
- `run()`/`step()` advance EVERY active slot one token per
  `batched_decode_step` (per-slot positions), `chunk` tokens per
  dispatch through a `lax.scan` — ONE blocking readback per step is
  the serve loop's only host synchronization, amortized over
  chunk × slots tokens;
- finished slots free immediately and the next queued request takes
  the slot — no drain barrier, which is the whole point of continuous
  batching.

A block-diffusion model (`LMServer(diffusion=BlockDiffusion(...))`, a
`block_causal` `LMConfig`) is served by the same grid, queue, placement
and driver with another dispatch (`_diffuse_impl`, `_diffuse_step`):
every occupied slot advances by whole blocks, each block S denoising
forwards and one commit forward, so a forward yields 0..B tokens a
slot and a request is priced in forwards; requests join at a
dispatch's end and leave after the block that meets their budget
(tests/test_block_diffusion.py pins it against the benchmark's plain
reference).

Correctness contract (pinned by tests/test_lm_server.py): greedy
outputs are IDENTICAL to running `generate` per request in isolation —
batching is a throughput decision, never a semantics change. The
contract is about logic, and holds bit for bit wherever the arithmetic
does not depend on a program's shape: on the CPU mesh, and on the chip
in float32 at HIGHEST matmul precision (chip run, PR 22: 8/8 prompts
identical). At bf16 — and TPU-default float32 — the server's programs
(bucket-padded prefill, max_len-row cache, k+1-token verify) and
`generate`'s round differently, so where the model's own top-2 logit
margin is within rounding (<= 0.006 measured, against a median margin
of 0.108) the argmax can fall either way and the sequences part there.
What holds at every precision: a request's output does not depend on
what shares the batch (alone == batched, same run), and every served
token is the argmax of the plain reference forward up to such a
near-tie (`chip_smoke.py` checks both).

Sampling (temperature > 0) is reproducible PER REQUEST, independent of
batch composition and arrival order: token i of request `rid` is drawn
from `fold_in(fold_in(PRNGKey(seed), rid), position)` — its own
counter-derived stream, not a shared per-step key. Two servers with
the same seed produce identical sampled outputs for a request whether
it decodes alone or packed with others (pinned by
test_sampled_request_independent_of_batch). Note the stream differs
from `generate`'s split-chain, which is shape-coupled by design.

Slots share the weight stream (the per-step HBM bill), and the
per-slot cache writes are an unrolled dynamic_update_slice chain (a
vmap'd update lowers to an XLA scatter that copies the whole cache).
What a step costs on the chip is PERF.md §5. The server makes several
dispatches per request (prefill, insert, chunks); each costs host
time, so the design below keeps them few and never blocks between
them.

Net-new vs the reference (inference over single images, no sequence
serving — SURVEY §0); the slot scheduler is the LM-serving analog of
the job scheduler's one-batch-per-worker fair-share loop
(jobs/scheduler.py).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..observability import METRICS
from ..ops.flash_attention import band_masked, band_visits
from ..tracing import TRACER, TraceContext
from .generate import (
    LMConfig,
    _sample,
    _typed_on_one_device,
    batched_block_step,
    batched_decode_step,
    batched_verify_step,
    decode_block_rows,
    heads_axis,
    init_cache,
    moe_layout,
    part,
    prefill,
    state_bytes,
)
from .quantize import quantized_bytes, resident_params

log = logging.getLogger(__name__)

# Serve-loop instrumentation (see observability.py's C1-C5 map). All
# updates are host-side O(1) dict writes OUTSIDE the jitted chunk /
# prefill programs, at per-DISPATCH granularity (a step covers
# chunk × slots tokens), so the decode path's device rate is
# unaffected: a dispatch makes a fixed handful of CALLS, whatever it
# holds. What a dispatch counts a step a layer (an expert model's
# routing) goes in by one `Histogram.observe_many` a histogram
# (`_note_routing`), never by a loop of `observe`: that loop ran after
# the readback had drained the device and before anything new was
# enqueued, and was most of one cell's device idle (PERF.md, PR 38).
# Handles are bound once at import: no name lookups on the hot path.
# Beside them the serve loop records loop spans
# (tracing.Tracer.loop_span: one per dispatch PHASE, never per token
# or per slot), which say where inside a dispatch the host's time went
# and which the JAX profiler shows as `dml.lm_*` annotations. The
# serving thread's time is tiled by them: `lm_step` (children
# `lm_dispatch`, `lm_pack`, `lm_readback`, `lm_route`, `lm_deliver`,
# `lm_place`), `lm_submit`, `lm_idle` and, between them, `lm_turn`.
# Across them lies `lm_exposed` (= `lm_server_exposed_seconds`): from
# where the thread returns from blocking on the NEWEST program it
# enqueued (the device's queue is then empty) to where the next
# program has been enqueued, or the thread goes idle: the device's
# exposure to the host over a whole run, with no profiler
# (`LMServer._drained`, `_fed`).
_M_REQS = METRICS.counter(
    "lm_server_requests_total", "requests submitted to the slot grid")
_M_REQS_DONE = METRICS.counter(
    "lm_server_requests_completed_total", "requests fully decoded")
_M_TOKENS = METRICS.counter(
    "lm_server_decode_tokens_total",
    "generated tokens delivered to request outputs")
_M_STEPS = METRICS.counter(
    "lm_server_steps_total", "chunked decode dispatches")
_M_QUEUE_WAIT = METRICS.histogram(
    "lm_server_queue_wait_seconds", "submit -> slot placement wait")
_M_PREFILL = METRICS.histogram(
    "lm_server_prefill_dispatch_seconds",
    "host wall of one placement group's ENQUEUE chain (build, prefill, "
    "inserts, first-token sample, merges: the `lm_prefill_group` span). "
    "Dispatch is async: this is not the prefill's device time, which "
    "the next step's readback waits out")
_M_PREFILL_TOKENS = METRICS.counter(
    "lm_server_prefill_tokens_total",
    "tokens sent through group prefills by kind=: prompt (the "
    "requests' own tokens) and padded (group rows x bucket, what the "
    "device computed)")
_M_PREFILL_PROMPT = _M_PREFILL_TOKENS.labels(kind="prompt")
_M_PREFILL_PADDED = _M_PREFILL_TOKENS.labels(kind="padded")
_M_KV_ROWS = METRICS.counter(
    "lm_server_decode_kv_rows_total",
    "cache rows of ONE layer over the chunk dispatches' decode steps by "
    "layers= full (a layer that caches a row a token) | window (a layer "
    "that caches a ring of its window's rows) and kind=: live (rows the "
    "slots' lengths name; in a window layer min(length, window)), read "
    "(rows of the k-blocks cache attention fetches for them; the whole "
    "grid on the einsum route), grid (steps x slots x the layer's rows "
    "a slot, what a length-blind step streams) and blocks (not rows: "
    "the k-blocks the kernel visits, one grid step each; 0 on the "
    "einsum route)")
#: the counter's children a layer type, in `_kv_rows`' order
_KV_KINDS = ("live", "read", "grid", "blocks")
_M_KV = {
    layers: tuple(_M_KV_ROWS.labels(kind=kind, layers=layers)
                  for kind in _KV_KINDS)
    for layers in ("full", "window")}
_M_FIRST_TOKEN = METRICS.histogram(
    "lm_server_first_token_seconds",
    "slot placement -> the request's first token VALUE on the host "
    "(it rides the next dispatch's packed readback)")
_M_STEP = METRICS.histogram(
    "lm_server_step_seconds",
    "one chunked decode step incl. its packed readback")
_M_PACK = METRICS.histogram(
    "lm_server_pack_seconds",
    "issuing a dispatch's packed readback: one concatenate of fixed "
    "shapes (a diffusion dispatch packs on the device: the host copy's "
    "issue)")
_M_READBACK = METRICS.histogram(
    "lm_server_readback_seconds",
    "blocking device->host readbacks (the serve loop's only stalls); "
    "a decode dispatch's excludes issuing the pack")
_M_EXPOSED = METRICS.histogram(
    "lm_server_exposed_seconds",
    "stretches the serving thread left the device with nothing queued: "
    "from its return from a blocking wait on the newest program it "
    "enqueued to the next program enqueued, or to going idle (the "
    "`lm_exposed` loop span, one observation a span)")
_M_DELIVER = METRICS.histogram(
    "lm_server_deliver_seconds",
    "a dispatch's token delivery: first tokens, every request's "
    "on_token callbacks, retirements")
_M_STATE_BYTES = METRICS.gauge(
    "lm_server_state_bytes",
    "the slot grid's bytes by kind= kv (grouped attention's K and V "
    "rows, a row a token) | kv_window (the window layers' rings, which "
    "do not grow with max_len) | latent (latent attention's one row a "
    "token) | conv | scan "
    "(a state-space layer's convolution window and recurrent state, "
    "which every decode step reads and writes whole for every slot)")
_M_WEIGHT_BYTES = METRICS.gauge(
    "lm_server_weight_bytes",
    "the weight tree's bytes by form= handed (as the server was given "
    "it) | resident (as it keeps it: block matrices and expert tensors "
    "stored wider than the compute dtype cast to it); equal where "
    "nothing had to be cast")
_M_SLOTS = METRICS.gauge(
    "lm_server_slots_active", "occupied decode slots")
_M_SLOTS_TOTAL = METRICS.gauge(
    "lm_server_slots_total", "slot grid capacity")
_M_OCCUPANCY = METRICS.histogram(
    "lm_server_slot_occupancy",
    "occupied slots per decode dispatch (grid utilization — the "
    "continuous-batching win/loss ledger)")
_M_FORWARDS = METRICS.counter(
    "lm_server_forwards_total",
    "block-diffusion forwards over the slot grid by kind=: denoise "
    "(a block's tokens against the cache and themselves, with the "
    "head) and commit (the final tokens once more, their rows kept, "
    "no head)")
_M_FWD_DENOISE = _M_FORWARDS.labels(kind="denoise")
_M_FWD_COMMIT = _M_FORWARDS.labels(kind="commit")
_M_FIXED = METRICS.counter(
    "lm_server_tokens_fixed_total",
    "tokens fixed by denoising forwards and delivered to requests "
    "(a prompt's tail in its first block and a last block's rows past "
    "the budget are not counted)")
_M_BLOCKS = METRICS.counter(
    "lm_server_blocks_committed_total",
    "blocks committed for occupied slots (a slot that finished inside "
    "a dispatch still runs the dispatch's later blocks: counted too)")
_M_MOE_ASSIGN = METRICS.counter(
    "moe_assignments_total",
    "(token, expert) assignments of occupied slots' tokens over the "
    "expert layers of decode steps and block-diffusion forwards by "
    "where=: held (an expert this tree holds computes it) | absent "
    "(another chip's part of the sum)")
_M_MOE_HELD = _M_MOE_ASSIGN.labels(where="held")
_M_MOE_ABSENT = _M_MOE_ASSIGN.labels(where="absent")
_M_MOE_TOUCHED = METRICS.histogram(
    "moe_experts_touched",
    "distinct routed experts one forward's tokens reach in one layer, "
    "over ALL the routed experts")
_M_MOE_TOUCHED_HELD = METRICS.histogram(
    "moe_experts_touched_held",
    "distinct HELD experts one forward's tokens reach in one layer "
    "(whose weights that forward has to read here)")
_M_MOE_LOAD = METRICS.histogram(
    "moe_expert_load_max",
    "assignments to the busiest expert over the mean over all routed "
    "experts, one forward one layer")
_M_MOE_WINDOWS = METRICS.counter(
    "moe_windows_total",
    "windows of held assignments the expert layers ran "
    "(`generate.expert_ffn`), a layer a forward a chunk, by kind=: first "
    "(every call's one) | further (past it: the held rows outran "
    "`generate.moe_window`'s size, and nothing was dropped)")
_M_MOE_WINDOWS_FIRST = _M_MOE_WINDOWS.labels(kind="first")
_M_MOE_WINDOWS_FURTHER = _M_MOE_WINDOWS.labels(kind="further")
_M_SPEC_PROPOSED = METRICS.counter(
    "lm_specdec_proposed_total",
    "draft tokens proposed to the verify program")
_M_SPEC_ACCEPTED = METRICS.counter(
    "lm_specdec_accepted_total",
    "proposed draft tokens accepted by target-greedy verification")
_M_SPEC_DISABLED = METRICS.counter(
    "lm_specdec_disabled_total",
    "speculative-decode disable events by reason (acceptance = "
    "measured rate fell below break-even)")


def _mesh_of(params: Any):
    """The multi-device mesh a params tree is sharded over, or None
    for a single-device tree — read from the arrays themselves, so a
    server needs no mesh option to serve a tp-sharded tree."""
    for leaf in jax.tree_util.tree_leaves(params):
        sh = getattr(leaf, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.mesh.size > 1:
            return sh.mesh
    return None


def _bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


#: the shortest prefill bucket (tokens a row) of a server whose max_len
#: allows it. It was chosen (PR 29) when a call under ~512 tokens was
#: bound by reading the weights once: 6.15 GB of float32 on the
#: benchmark's dense decoder, 7.25 GB of experts on its expert model.
#: Since the dense decoder's matrices are resident in bfloat16 (3.6 GB
#: a call) a 512-token row is bound by its matmuls there (a (512, 1)
#: call: 10.2 -> 9.5 ms, PERF.md PR 31), so a shorter bucket would pay
#: for short prompts; it stays because it is one more compiled shape a
#: row count and the set-ups warm these. It is also the charge a group
#: costs in `_prefill_groups`: every call re-reads every weight.
_BUCKET_FLOOR = 512

#: a server whose max_len is at most this has ONE bucket (its max_len),
#: and pads every group's rows to max_slots: one prefill compilation,
#: which a warm-up of single prompts covers. Tier-1's and the
#: rehearsals' tiny servers are the only ones left on this form; a real
#: server's buckets are all longer and take power-of-two rows.
_FULL_ROWS_BUCKET_MAX = 256


def _prefill_bucket(n: int, max_len: int) -> int:
    """The padded length a prompt of n tokens is prefilled at alone."""
    return min(max(_bucket(n), _BUCKET_FLOOR), max_len)


def _group_rows(k: int, bucket: int, max_slots: int) -> int:
    """The padded rows of a prefill group of k prompts."""
    if bucket <= _FULL_ROWS_BUCKET_MAX:
        return max_slots
    return min(_bucket(k, lo=1), max_slots)


#: the most tokens (padded rows x bucket) a prefill group of a model with
#: a state-space layer holds, unless it is one row: the chunked scan's
#: float32 transients (decay masks [tokens x chunk] a head, chunk states)
#: grow with the tokens, and beside 9.3 GB of weights and a 64-slot grid
#: a (512, 64) group does not fit. A bound on the group, so that no slot,
#: width or row has to give way (PERF.md section 4 has the arithmetic).
#: A stack of gated short convolutions has no such transients, and keeps
#: the bound for what its groups hand BACK: a row's K and V padded to
#: max_len in every attention layer (48 MiB a row at 6 layers of 8 x 64 x
#: 4,096), beside a 128-slot grid and the weights (10.8 GiB resident).
#: Ahead of time for a described v5e (PR 44, `tools/aot_memory_window.py`):
#: a (512, 16) group holds 777 MiB of temporaries and 774 MiB of rows, a
#: (512, 32) group 1,457 + 1,549, a (512, 128) group 5.5 + 6.2 GiB, which
#: the chip does not have; and 8,192 tokens are already bound by their
#: matmuls (14 TFLOP against one 5 GB weight read), so more rows a call
#: would buy nothing but a longer stall of the grid's decode.
_STATE_GROUP_TOKENS = 8192

#: ... and of a model with latent attention: no two rows of the shortest
#: bucket, so every prompt is prefilled alone. A row of 512 tokens and more
#: is bound by its matmuls at such a model's depth (a 2,048-token row of
#: 40 layers is 7.4 TFLOP beside one 9.5 GB weight read: 40-60 ms beside
#: 12), so rows that share a call save little; what they cost is a
#: compilation a (bucket, rows) shape, a minute each at that depth, and
#: the bytes of the rows a group hands back (50 KB a token over 40 layers:
#: 0.2 GB a 4,096-token row) beside 9.6 GB of weights and a 3.4 GB grid
#: (ahead-of-time compile, PR 36: a 1 x 4,096 call holds 2.0 GiB of
#: temporaries, a 4 x 4,096 call 1.6 GiB and 0.8 GiB of rows). A stack with
#: window layers takes the same bound for the same two reasons: it is as
#: deep (the one that is served holds 40 layers, and a prefill's attention
#: is 64 query heads wide), and a group hands back its full layers' rows
#: padded to max_len beside every window layer's ring (0.23 GB a row of
#: 4,096) next to 8 GB of weights and a 3.7 GB grid. (The bound is the deep
#: stacks', not latent attention's alone; it keeps the name that
#: `benchmark/tests/test_latent_cell.py` reads it by.)
_LATENT_GROUP_TOKENS = _BUCKET_FLOOR


def _prefill_groups(
    lengths: Sequence[int], max_len: int, max_slots: int,
    max_tokens: Optional[int] = None,
) -> List[Tuple[int, int, List[int]]]:
    """Cut one placement round into prefill groups: `(bucket, rows,
    members)` each, members indexing `lengths`, shortest bucket first.

    Over the prompts sorted by length, the contiguous partition with the
    least padded tokens (rows x bucket summed, a group's bucket being
    its longest member's) plus `_BUCKET_FLOOR` a group, fewer groups on
    a tie: a short prompt rides in a longer group's spare rows, and
    nothing is split to save rows that a call's weight read costs again.
    The (bucket, rows) shapes a round may form are a hard constraint
    (each is a compilation, and a serving window tolerates none): rows
    are `_group_rows` of the members, and never more than `_group_rows`
    of the members whose OWN bucket is the group's, so a rider never
    raises the rows past what equal-length prompts of that bucket form
    alone; and under `max_tokens` no group of more than one row holds
    more padded tokens than that."""
    order = sorted(range(len(lengths)), key=lambda i: lengths[i])
    own = [_prefill_bucket(lengths[i], max_len) for i in order]
    # best[j]: (padded tokens + charges, groups, start of the last
    # group) of the cheapest partition of the j shortest prompts
    best: List[Tuple[int, int, int]] = [(0, 0, 0)]
    for j in range(1, len(order) + 1):
        bucket, native, cands = own[j - 1], 0, []
        for i in range(j - 1, -1, -1):
            native += own[i] == bucket
            rows = _group_rows(j - i, bucket, max_slots)
            if rows > _group_rows(native, bucket, max_slots) or (
                    max_tokens and j - i > 1 and rows * bucket > max_tokens):
                break  # riders only, or too many tokens: so is any more
            cost, groups, _ = best[i]
            cands.append((cost + rows * bucket + _BUCKET_FLOOR,
                          groups + 1, i))
        best.append(min(cands))
    out, j = [], len(order)
    while j:
        i = best[j][2]
        out.append((own[j - 1], _group_rows(j - i, own[j - 1], max_slots),
                    sorted(order[i:j])))
        j = i
    return out[::-1]


def _routing_numbers(counts, lo: int, hi: int) -> list:
    """One expert layer's routing in five numbers, from its assignment
    counts [..., E] (a device or a host array): the assignments, those
    to the held experts `lo .. hi - 1`, the distinct experts reached,
    the distinct held ones, the assignments to the busiest expert."""
    held = counts[..., lo:hi]
    return [counts.sum(-1), held.sum(-1), (counts > 0).sum(-1),
            (held > 0).sum(-1), counts.max(-1)]


@dataclasses.dataclass
class _Request:
    rid: int
    prompt: np.ndarray  # [Tp] int32
    max_new_tokens: int
    # `emitted` counts tokens GENERATED on device; `out` holds the
    # values actually read back. They differ transiently: the first
    # token is sampled at placement but its VALUE rides the next
    # packed readback (deferred-first protocol, see _place_waiting) —
    # retirement/budget logic keys on emitted, results on out.
    out: List[int] = dataclasses.field(default_factory=list)
    emitted: int = 0
    slot: Optional[int] = None
    t_submit: float = 0.0  # monotonic submit time (queue-wait metric)
    # monotonic placement time and the time the first token's VALUE
    # reached the host (0.0 = not yet): the `lm_request` span's events
    t_placed: float = 0.0
    t_first: float = 0.0
    # where the request's `lm_request` span hangs: the worker's `infer`
    # span of a traced ingress request, else None (a trace of its own)
    ctx: Optional[TraceContext] = None
    # per-request delivery callback (ingress token streaming): fired
    # with each token VALUE the moment it is read back to the host —
    # the decode grid's per-token stream source. Never on the device
    # path: deliveries happen at the packed readback, so firing here
    # adds no dispatches and no extra host synchronization.
    on_token: Optional[Callable[[int], None]] = None
    # draft tokens shipped WITH the request (a prefill-role peer's
    # speculative proposals riding the KV slab — inference/
    # lm_sharded.py): consumed by exactly ONE verify round, then the
    # server's own proposer (if any) takes over. Correctness never
    # depends on these — a bad/absent shipment only shortens the
    # acceptance run (greedy verification commits target tokens only).
    shipped_draft: Optional[np.ndarray] = None
    # per-request acceptance-length accounting (spec_rounds verify
    # rounds accepted spec_accepted draft tokens for this request)
    spec_rounds: int = 0
    spec_accepted: int = 0
    # block diffusion only: per delivered token, the denoising step
    # (1..S) of its block that fixed it
    fixed_at: List[int] = dataclasses.field(default_factory=list)
    # ... and what the last block held past the budget: (tokens, steps)
    # of its surplus positions, so that the block can be rebuilt whole
    beyond: Tuple[List[int], List[int]] = ((), ())

    def deliver(self, toks) -> None:
        """Append read-back token values to `out`, firing `on_token`
        per token. The single append point — every readback path
        (step, _flush_firsts, submit_prefilled) must land here so
        streaming sees exactly the tokens the result carries."""
        cb = self.on_token
        for t in toks:
            t = int(t)
            self.out.append(t)
            if cb is not None:
                try:
                    cb(t)
                except Exception as e:
                    # a streaming hint, never a decode error — debug
                    # level: this fires per token and a broken stream
                    # callback would flood anything louder
                    log.debug("on_token callback failed: %r", e)
        # once per call, never per token: the first call stamps the
        # first token, the one that fills the budget closes the span
        if self.t_first == 0.0:
            self.t_first = time.monotonic()
            _M_FIRST_TOKEN.observe(self.t_first - self.t_placed)
        if len(self.out) >= self.max_new_tokens:
            labels = {} if self.slot is None else {"slot": self.slot}
            TRACER.loop_record(
                "lm_request", self.t_submit, time.monotonic(), self.ctx,
                events=(("placed", self.t_placed),
                        ("first_token", self.t_first)),
                prompt_tokens=int(self.prompt.size),
                new_tokens=len(self.out), **labels,
            )

    @property
    def done(self) -> bool:
        return self.emitted >= self.max_new_tokens


@dataclasses.dataclass
class _SpecState:
    """Speculative-decoding state for one LMServer (enable_spec_decode).

    Exactly one proposal source is primary: a device-resident DRAFT
    model (draft_params/draft_cfg/draft_cache — proposals never leave
    the device), a host PROPOSER callable (oracle/heuristic — the
    bench's declared-acceptance harness), or neither (verify rounds
    run only when an adopted request carries a shipped draft). The
    windowed acceptance counters drive automatic disable when the
    measured rate drops below `min_accept` (break-even): a verify
    round costs ~one (k+1)-token forward to emit accepted+1 tokens,
    so low acceptance pays multi-row attention for single-token
    progress."""

    k: int
    draft_params: Any = None
    draft_cfg: Optional[LMConfig] = None
    draft_cache: Any = None
    proposer: Optional[Callable[[Sequence["_Request"], int], Any]] = None
    min_accept: float = 0.0
    min_samples: int = 64
    enabled: bool = True
    disabled_reason: Optional[str] = None
    # lifetime + sliding-window acceptance accounting (window halves
    # once it doubles min_samples, so a long-lived server tracks the
    # CURRENT workload's acceptance, not its launch-hour average)
    proposed_total: int = 0
    accepted_total: int = 0
    win_proposed: int = 0
    win_accepted: int = 0
    rounds: int = 0


#: the remasking rules a block-diffusion server knows
REMASKING = ("low_confidence_static",)


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """How a block-diffusion LM generates (`LMServer(diffusion=...)`;
    the block length and the mask are the model's: `LMConfig`).

    The sequence is tiled in blocks of B from position 0. Whole blocks
    of the prompt are prefilled under the block-causal mask; the
    prompt's tail (0..B-1 tokens) opens the first generated block as
    fixed tokens. A block starts with `mask_token_id` at every unfixed
    position; each of `steps` DENOISING forwards runs the block's B
    tokens against the cache and themselves, takes the argmax and its
    softmax probability at every masked position, and fixes the most
    confident ones (`remasking` "low_confidence_static": the masked
    count split evenly over the steps, the remainder to the first
    steps — a fixed schedule, so the work does not depend on the
    weights); one COMMIT forward then runs the final tokens once more
    and keeps their K/V rows. The logits at a position predict that
    position's own token (no shift). Greedy (temperature 0) only."""

    steps: int
    mask_token_id: int
    remasking: str = REMASKING[0]

    def __post_init__(self):
        if self.steps < 1:
            raise ValueError(f"denoising steps {self.steps}")
        if self.remasking not in REMASKING:
            raise ValueError(f"unknown remasking {self.remasking!r}")


def _hold_resident(params: Any, dtype, tree: str) -> Tuple[Any, int, int]:
    """(`resident_params(params, dtype)`, bytes handed, bytes resident)
    under a one-off `lm_weights_resident` loop span that carries both
    counts (`tree` "target" or "draft"): a trace shows whether the
    cast engaged, and equal counts say the tree was multiplied as
    handed."""
    with TRACER.loop_span("lm_weights_resident", tree=tree) as span:
        handed = quantized_bytes(params)[0]
        params = resident_params(params, dtype)
        resident = quantized_bytes(params)[0]
        span.label(handed_bytes=handed, resident_bytes=resident)
    return params, handed, resident


@functools.lru_cache(maxsize=None)
def _band_labels(bucket: int, window: int, head_dim: int,
                 group: int) -> Dict[str, float]:
    """A window layer's prefill kernel over `bucket` tokens a row, in
    that kernel's own arithmetic at its own blocks
    (`ops.flash_attention`: `band_visits`, `band_masked`): `band_skipped`,
    1 - the k-blocks it visits over the ones the causal rule leaves;
    `band_masked`, the share of the visited ones that an edge crosses,
    so that they build a mask (under 1: the band has an inside);
    `kv_group`, the query heads that share a copy of a K and V block.
    A few buckets a server: reckoned once each."""
    shape = {"head_dim": head_dim, "group": group}
    banded, causal = band_visits(bucket, window, **shape)
    return {
        "band_skipped": round(1.0 - banded / causal, 4),
        "band_masked": round(band_masked(bucket, window, **shape) / banded, 4),
        "kv_group": group,
    }


class LMServer:
    """Slot-based continuous batching over `batched_decode_step`.

    >>> srv = LMServer(params, cfg, max_slots=4, max_len=512)
    >>> a = srv.submit(prompt_a, max_new_tokens=64)
    >>> b = srv.submit(prompt_b, max_new_tokens=32)
    >>> results = srv.run()          # {rid: np.ndarray of new tokens}

    `self.params` is the tree the programs multiply, not the tree the
    caller handed over: block matrices and expert tensors stored wider
    than `cfg.dtype` are held in it (`quantize.resident_params`; the
    embedding, norms, router, head and any int8 leaf are the caller's
    arrays), cast once here and in no dispatch or prefill call. The server keeps no
    reference to what it was handed, so a float32 checkpoint served in
    bfloat16 is freed when its caller lets go of it
    (`lm_server_weight_bytes{form=handed|resident}` has both sizes).
    """

    def __init__(
        self,
        params: Any,
        cfg: LMConfig,
        max_slots: int = 4,
        max_len: int = 1024,
        chunk: int = 16,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        seed: int = 0,
        diffusion: Optional[BlockDiffusion] = None,
    ):
        """`diffusion` makes this a block-diffusion server (see
        `BlockDiffusion` and `_diffuse_impl`): `chunk` is then the
        tokens a slot a dispatch, in whole blocks."""
        if chunk < 1:
            raise ValueError("chunk must be >= 1")
        if max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        self.params, handed, resident = _hold_resident(
            params, cfg.dtype, "target")
        _M_WEIGHT_BYTES.set(handed, form="handed")
        _M_WEIGHT_BYTES.set(resident, form="resident")
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_len = max_len
        self.chunk = chunk
        self.temperature = temperature
        self.top_k = top_k
        self._mesh = _mesh_of(self.params)
        self.diffusion = diffusion
        if (diffusion is None) != (cfg.mask_block == 1):
            raise ValueError(
                "a block-diffusion server needs a block_causal model "
                "and a block_causal model a block-diffusion server")
        if diffusion is not None:
            if temperature != 0.0:
                raise ValueError("block diffusion serves greedy only")
            if not 0 <= diffusion.mask_token_id < cfg.vocab_size:
                raise ValueError("mask_token_id outside the vocabulary")
            if max_len % cfg.block_length:
                raise ValueError(
                    f"max_len {max_len} is no whole number of blocks "
                    f"of {cfg.block_length}")
        if cfg.has_state and (diffusion is not None or self._mesh is not None):
            # a state-space layer's state is whole-sequence and per slot
            # (a gated short convolution's window its last positions'):
            # the denoising forwards rewrite rows it has already taken in,
            # and no sharding rule places it over a mesh yet
            raise ValueError(
                "a model with a state-space layer or a gated short "
                "convolution is served by the plain chunked loop on one "
                "device: block diffusion and the sharded forms cannot "
                "hold its state")
        if cfg.latent is not None and self._mesh is not None:
            # no rule yet says where a latent row lives over a mesh: it
            # has no heads to divide, and every head reads all of it
            raise ValueError(
                "a model with latent attention is served on one device: "
                "the sharded forms have no placement for its shared rows")
        _typed_on_one_device(cfg, self._mesh)
        self.cache = self._new_cache(cfg)
        for kind, n in state_bytes(self.cache).items():
            _M_STATE_BYTES.set(n, kind=kind)
        # whether a placement round enqueues ONE prefill group at a time
        # (`_place_group` says why): the deep models, whose groups hand
        # back tenths of a GB of rows each, and a stack with gated short
        # convolutions, which caches so little a token that it is served
        # over a grid of a hundred slots and more: a round into an empty
        # grid places as many rows, each handed back padded to max_len
        self._one_group = (cfg.latent is not None or cfg.has_ring
                           or cfg.has_conv)
        # the most padded tokens a prefill group of several rows holds
        self._group_tokens = (
            _STATE_GROUP_TOKENS if cfg.has_state
            else _LATENT_GROUP_TOKENS if self._one_group else None)
        # the attention layers of each type (`_kv_rows`): one (layers,
        # a layer of that type) a type the stack holds
        kinds = {}
        for i, kind in enumerate(cfg.kinds):
            if kind in (None, "*") and cfg.attn(i).conv_kernel is None:
                kinds.setdefault(
                    "full" if cfg.attn(i).window is None else "window", i)
        # (a stack without attention counts nothing, under "full")
        self._kv_layers = tuple(kinds.items()) or (("full", 0),)
        # whether a prefill hands back the bucket's own rows, not rows
        # padded to max_len, and an insert writes those alone
        # (`_insert_impl` says why either kind of server may)
        self._bucket_rows = diffusion is not None or cfg.latent is not None
        # Decode state lives ON DEVICE (authoritative): `_cur_dev` the
        # next input token per slot, `_pos_dev` the next write
        # position. Placement writes them with device scatters and the
        # chunk fn returns their advanced forms — the host NEVER reads
        # them back (a slot's position, when needed, is
        # req.prompt.size + req.emitted). Every blocking readback
        # stalls the host until the device drains, and the old
        # host-resident cur/pos forced one per placement round on top
        # of one per chunk.
        self._cur_dev = jnp.zeros(max_slots, jnp.int32)
        self._pos_dev = jnp.zeros(max_slots, jnp.int32)
        self.rid_vec = np.zeros(max_slots, np.int32)  # slot -> request id
        self._slot_req: List[Optional[_Request]] = [None] * max_slots
        # per-instance delivered-token count (the registry's
        # _M_TOKENS is process-global; steady-state measurement wants
        # THIS server's stream without registry key coupling)
        self.tokens_delivered = 0
        # first tokens sampled at placement whose VALUES haven't been
        # read back yet: `_firsts_dev[slot]` holds the token (scattered
        # there by the placement's masked merge, like cur/pos), and
        # `_pending_first` maps the slot to the request that waits for
        # it. One vector of one shape, so the packed readback that
        # carries it has ONE shape too and never compiles in steady
        # state. Read by the next step's packed readback, or by
        # _flush_firsts when a request retires with no step following
        # or its slot is placed again before one.
        self._firsts_dev = jnp.zeros(max_slots, jnp.int32)
        self._pending_first: Dict[int, _Request] = {}
        # (monotonic time, which wait) since when the device has had
        # nothing queued, or None while it has work (`_drained`, `_fed`)
        self._exposed: Optional[Tuple[float, str]] = None
        self._queue: List[_Request] = []
        self._done: Dict[int, _Request] = {}
        self._rid = 0
        # one master key; every sample folds in (rid, position), so a
        # request's stream is a pure function of (seed, rid, position)
        # — no mutable chain to couple slots together
        self._base_rng = jax.random.PRNGKey(seed)
        # params are explicit ARGUMENTS to every jitted piece — closing
        # over them would bake the whole weight tree into the program
        # as constants (rejected outright by remote compile services
        # for real model sizes). jax.jit's own cache handles one
        # compilation per distinct (rows, bucket) prefill group.
        # a block-diffusion prefill reads no logits and hands back the
        # bucket's own rows, not rows padded to max_len: a placement
        # wave of equal budgets prefills a group a bucket at once, and
        # seven groups' max_len-row caches (1.6 GB each at 32 slots x
        # 4,096 rows x 12 KiB) do not fit beside an 8.7 GB model. So
        # does a latent-attention prefill (its logits it reads): a
        # group's rows over 40 layers padded to 4,096 are 0.2 GB a row
        self._prefill = jax.jit(
            lambda p, pr, li: self._prefill_impl(p, pr, li))
        self._insert = jax.jit(self._insert_impl, donate_argnums=(0,))
        self._chunk_fn = jax.jit(
            self._chunk_impl, donate_argnums=(1, 2, 3)
        )
        # fixed-shape masked merge for placement-time cur/pos writes:
        # slot_map[s] = the prefill row whose value slot s takes, or
        # -1 to keep the current value. `vec` and `slot_map` are
        # always [max_slots]; `vals` carries the prefill group's kp
        # rows, so this compiles once per distinct group-row count —
        # the same (few, power-of-two) kp variants the group prefill
        # itself mints, not one per slot assignment
        self._merge_vec = jax.jit(
            lambda vec, vals, slot_map: self._merge_impl(
                vec, vals, slot_map, False),
            donate_argnums=(0,),
        )
        # per-row first-token sampling for a placement group (same
        # (rid, position) streams the chunk sampler continues)
        # prefill's logits are already [rows, vocab] (_head squeezes)
        self._sample_first = jax.jit(self._sample_slots)
        # worker-resident KV prefix cache (inference/kv_cache.py),
        # enable_kv_cache wires both; None (the default) keeps the
        # serve path bit-identical to a cache-less build
        self.kv_cache = None
        self._warm = None
        # speculative decoding (enable_spec_decode wires these; None =
        # the plain chunked-scan path, bit-identical to pre-spec builds)
        self._spec: Optional[_SpecState] = None
        self._verify_fn = None
        self._propose_fn = None
        self._draft_prefill = None
        # rid -> the step that fixed each delivered token (and the
        # last block's rows past the budget), kept from retirement
        # until `take_fixed_at` (block diffusion only)
        self._fixed_at: Dict[int, Dict[str, Any]] = {}
        # block diffusion: the last dispatch's wall over its forwards
        # (what LMBackend prices a request by); None = none measured
        self.forward_seconds: Optional[float] = None
        moes = [
            blk["moe"] for name, blk in self.params.items()
            if name.startswith("block_") and "moe" in blk
        ]
        # (expert layers, routed experts): the routing counts' shape;
        # and the experts this tree holds, [first, first + held)
        self._routed = (
            len(moes),
            moes[0]["router"]["kernel"].shape[-1] if moes else 0)
        self._held = (
            cfg.experts_first,
            cfg.experts_first + jax.tree_util.tree_leaves(
                moes[0]["w_up"])[0].shape[0] if moes else 0)
        # where the tree holds a SHARE of the routed experts, how many
        # windows an expert layer runs is read from the routing
        # (`generate.expert_ffn`): a prefill hands its windows past the
        # first back as a third output, added up here on the device
        # until the next packed readback carries them (as first tokens
        # ride it); None = every call is one window, known from shapes
        self._windows_dev = (
            jnp.zeros(1, jnp.int32)
            if moes and diffusion is None
            and self._held[1] - self._held[0] < self._routed[1] else None)
        self._no_windows = self._windows_dev
        if diffusion is not None:
            # the current block of every slot, device-resident like
            # cur/pos: `_pos_dev` is then the block's first row
            b = cfg.block_length
            self.blocks_per_dispatch = max(1, chunk // b)
            self._blk_dev = jnp.full(
                (max_slots, b), diffusion.mask_token_id, jnp.int32)
            self._diffuse_fn = jax.jit(
                self._diffuse_impl, donate_argnums=(1, 2, 3))
            self._merge_blk = jax.jit(
                lambda blk, vals, slot_map: self._merge_impl(
                    blk, vals, slot_map, True),
                donate_argnums=(0,),
            )
        _M_SLOTS_TOTAL.set(max_slots)

    def enable_spec_decode(
        self,
        k: int,
        *,
        draft_params: Any = None,
        draft_cfg: Optional[LMConfig] = None,
        proposer: Optional[Callable] = None,
        min_accept: float = 0.0,
        min_samples: int = 64,
    ) -> None:
        """Turn on speculative decoding: each decode dispatch becomes
        one PROPOSE (k draft tokens per slot) + one VERIFY (the target
        model consumes all k candidates in a single batched
        `batched_verify_step` forward) committing 1..k target-greedy
        tokens per slot per round. Outputs stay bitwise-identical to
        the plain chunked path — the committed tokens are the TARGET's
        greedy argmaxes, so proposals affect only how many commit per
        round, never their values (tests/test_specdec.py pins both).

        Proposal source (pick one):
        - `draft_params` + `draft_cfg`: a device-resident draft model
          (same vocab, fewer layers/d_model — config.draft_lm_spec).
          Its own KV cache shadows the slot grid; placement runs a
          second bucketed draft prefill; proposals never leave the
          device.
        - `proposer(requests, k) -> [len(requests), k] int32`: a host
          callable (the bench's declared-acceptance oracle). Costs one
          host round-trip of k ints per slot per round.
        - neither: verify rounds run only for shipped drafts riding
          adopted prefill slabs (the disaggregated remote-draft form).

        `min_accept` > 0 arms AUTOMATIC DISABLE: once `min_samples`
        proposals are measured, a windowed acceptance rate below
        min_accept permanently reverts this server to the plain chunk
        path (lm_specdec_disabled_total{reason="acceptance"}) — a
        draft that stopped predicting the target must not keep taxing
        every dispatch with rejected verify rows.

        Greedy-only (temperature == 0): acceptance compares draft
        tokens against target ARGMAXES; a sampled target has no single
        correct token to compare against (lossless sampled
        speculation needs rejection resampling — out of scope, typed
        here). Enable before submitting work: a device draft's cache
        cannot adopt slots that were prefilled before it existed."""
        if self.diffusion is not None:
            raise ValueError(
                "speculative decoding drafts tokens one at a time; a "
                "block-diffusion server has no such step")
        self._refuse_state("speculative decoding (a rejected draft's "
                           "rows are dropped)")
        if self.temperature != 0.0:
            raise ValueError(
                "speculative decoding requires temperature == 0 "
                "(greedy acceptance compares draft tokens against "
                "target argmaxes)"
            )
        if k < 1:
            raise ValueError("spec k must be >= 1")
        if k + 1 >= self.max_len:
            raise ValueError(
                f"spec k {k} leaves no room in max_len {self.max_len}"
            )
        if (draft_params is None) != (draft_cfg is None):
            raise ValueError(
                "draft_params and draft_cfg come together"
            )
        if draft_params is not None and proposer is not None:
            raise ValueError("pick ONE of draft model / proposer")
        if draft_cfg is not None and (
            draft_cfg.vocab_size != self.cfg.vocab_size
        ):
            raise ValueError(
                f"draft vocab {draft_cfg.vocab_size} != target "
                f"vocab {self.cfg.vocab_size}"
            )
        if self.has_work():
            raise RuntimeError(
                "enable_spec_decode on a busy server: active slots "
                "have no draft cache rows to verify against"
            )
        if draft_params is not None:
            draft_params = _hold_resident(
                draft_params, draft_cfg.dtype, "draft")[0]
        self._spec = _SpecState(
            k=int(k), draft_params=draft_params, draft_cfg=draft_cfg,
            proposer=proposer, min_accept=float(min_accept),
            min_samples=int(min_samples),
        )
        if draft_params is not None:
            self._spec.draft_cache = self._new_cache(draft_cfg)
            self._propose_fn = jax.jit(
                self._propose_impl, donate_argnums=(1,)
            )
            self._draft_prefill = jax.jit(
                lambda p, pr, li: prefill(
                    p, draft_cfg, pr, self.max_len, logits_index=li,
                    mesh=self._mesh,
                )
            )
        self._verify_fn = jax.jit(
            self._verify_impl, donate_argnums=(1,)
        )

    def disable_spec_decode(self, reason: str = "manual") -> None:
        """Revert to the plain chunked path (idempotent). The spec
        state object stays for `spec_stats()` post-mortems."""
        sp = self._spec
        if sp is None or not sp.enabled:
            return
        sp.enabled = False
        sp.disabled_reason = reason
        _M_SPEC_DISABLED.inc(reason=reason)
        log.warning(
            "speculative decoding disabled (%s): accepted %d / "
            "proposed %d over %d rounds",
            reason, sp.accepted_total, sp.proposed_total, sp.rounds,
        )

    def spec_stats(self) -> Optional[Dict[str, Any]]:
        """Acceptance accounting (None when spec was never enabled):
        the measured rate, not the configured one."""
        sp = self._spec
        if sp is None:
            return None
        return {
            "enabled": sp.enabled,
            "k": sp.k,
            "rounds": sp.rounds,
            "proposed": sp.proposed_total,
            "accepted": sp.accepted_total,
            "accept_rate": (
                sp.accepted_total / sp.proposed_total
                if sp.proposed_total else None
            ),
            "disabled_reason": sp.disabled_reason,
        }

    def kernel_report(self) -> Dict[str, bool]:
        """Which of this server's device programs hold a Pallas kernel
        (`tpu_custom_call`), asked of the programs as lowered for the
        devices the params live on: `prefill` (the flash kernel) and
        `decode` (the cache-attention kernel, which
        `generate.uses_decode_kernel` picks on a TPU). The switches
        choose by backend without a word; this is the witness."""
        i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
        vec = i32((self.max_slots,))
        lowered = {
            "prefill": self._prefill.lower(
                self.params, i32((self.max_slots, 16)), vec
            ),
            "decode": self._chunk_fn.lower(
                self.params, self.cache, vec, vec, vec
            ),
        }
        return {
            k: "tpu_custom_call" in v.as_text() for k, v in lowered.items()
        }

    def enable_kv_cache(self, cache) -> None:
        """Attach a `KVPrefixCache`: retiring requests donate their KV
        rows + token ids, and queued greedy requests whose prompt
        extends a cached prefix warm-start through `submit_prefilled`
        with only the suffix prefilled. Pass None to detach (the cold
        path, bit-identical to today's behavior)."""
        from .kv_cache import WarmStart

        if cache is not None and self.diffusion is not None:
            raise ValueError(
                "the KV prefix cache warm-starts causal prefills; a "
                "block-diffusion server has none")
        if cache is not None:
            self._refuse_state("the KV prefix cache (a prefix is a cut "
                               "of cached rows)")
        if cache is not None and self.cfg.latent is not None:
            # a latent row CAN be cut by token; what cannot take one is
            # the suffix prefill (`kv_cache.WarmStart`), which attends a
            # prefix's K and V rows by name
            raise ValueError(
                "the KV prefix cache's suffix prefill attends K and V "
                "rows; it has no form for latent attention's rows yet")
        self.kv_cache = cache
        self._warm = (
            WarmStart(cache, self.cfg, self.max_len)
            if cache is not None else None
        )

    def _refuse_state(self, what: str) -> None:
        """Raise where what a slot of this server's model carries is
        not a row a token a layer under ONE attention type, and `what`
        needs it to be: a state-space layer's state holds a whole
        sequence in one array (a gated short convolution's window its
        last positions' products, scan or no scan), and a window
        layer's ring its last
        `window` positions, wrapped; neither can be cut at a token from
        its start nor rolled back past what it overwrote, as K/V rows
        can. (A stack of typed layers without a window could be cut;
        what cuts and re-attends rows here reads one head count and one
        rope for the whole stack, so it is refused with the others.)"""
        if self.cfg.has_state:
            carried = ("gated short convolutions carry a convolution window"
                       if self.cfg.has_conv else
                       "state-space layers carry a scan state and a "
                       "convolution window")
            raise ValueError(
                f"{what} needs state that can be cut by token or rolled "
                f"back; this model's {carried} that allow neither")
        if self.cfg.has_ring:
            raise ValueError(
                f"{what} needs state that can be cut by token or rolled "
                f"back; this model's window layers cache a ring of their "
                f"window's rows, which allows neither")
        if self.cfg.attention_layers is not None:
            raise ValueError(
                f"{what} reads one head count and one rope for the whole "
                f"stack; this model's attention layers go by type")

    def _new_cache(self, cfg: LMConfig):
        """An empty slot-grid cache for `cfg`. Under a mesh every
        device is given its own KV heads (or a full copy where heads
        do not divide) at allocation — the same `heads_axis` rule the
        per-device attention kernels use — instead of one device
        holding the whole grid until GSPMD's choice of an output
        sharding moves it."""
        if self._mesh is None:
            return init_cache(cfg, self.max_slots, self.max_len)
        ax = heads_axis(self._mesh, cfg.n_heads, cfg.kv_heads)
        return jax.jit(
            lambda: init_cache(cfg, self.max_slots, self.max_len),
            out_shardings=NamedSharding(self._mesh, P(None, ax)),
        )()

    def _prefill_impl(self, params, prompts, last):
        """A prefill group's program: `prefill` over the padded rows,
        logits at each row's `last` position. Where the expert layers'
        windows are read from the routing (`_windows_dev`), a third
        output [1]: the windows past each call's first, all layers."""
        experts = (None if self._windows_dev is None
                   else {"live": None, "counts": [], "windows": []})
        logits, pcache = prefill(
            params, self.cfg, prompts,
            prompts.shape[1] if self._bucket_rows else self.max_len,
            logits_index=last, mesh=self._mesh,
            head=self.diffusion is None, experts=experts,
        )
        if experts is None:
            return logits, pcache
        return logits, pcache, sum(
            experts["windows"], jnp.zeros((), jnp.int32)).reshape(1)

    def _insert_impl(self, cache, pcache, slot, row):
        """Copy row `row` of a (possibly group-batched) prefilled
        cache into `slot`. Stale tail positions past the prompt are
        invisible behind the per-slot validity mask, and copying the
        whole row is one contiguous DMA.

        INVARIANT (with `_chunk_impl`): an empty slot's pos is clamped
        to max_len - 1 on the device, so between retire and reuse its
        scan steps only ever rewrite the LAST cache row — and this
        full-row overwrite then erases that too. Any future partial-row
        insert or unclamped scatter would break the pairing; keep both
        sides together. (A block-diffusion server inserts the
        prefilled rows alone — see below — and needs no such pairing:
        its forwards attend rows under each slot's length only, and a
        request's own forwards write every row from its first block on
        before anything attends it. A latent-attention server does the
        same, for the same reason: cache attention reads rows under a
        slot's length only, and a request's own steps write every row
        from its prompt's end on, the last row included, before a
        length reaches it.)

        A state-space layer's leaves (`conv`, `ssm`) are copied like
        any other, and the invariant holds for them in its plainest
        form: an empty slot's state is advanced by every dispatch with
        whatever it holds, read by nobody, and overwritten WHOLE here
        with the state the prefill took at the row's own length."""
        # generic over the cache layout (bf16 {k, v} or kv_quant
        # {k_q, k_s, v_q, v_s}) — every leaf copies the same way
        with part("insert"):
            if self._bucket_rows:
                # the prefilled rows alone ([KV, bucket, D], written from
                # the slot's row 0): what the last occupant left past
                # them lies at or past this request's first block, where
                # nothing attends a row before a forward of this request
                # wrote it
                return {
                    name: {
                        key: jax.lax.dynamic_update_slice(
                            kv[key],
                            jax.lax.dynamic_index_in_dim(
                                pcache[name][key], row, axis=0),
                            (slot,) + (0,) * (kv[key].ndim - 1),
                        )
                        for key in kv
                    }
                    for name, kv in cache.items()
                }
            return {
                name: {
                    key: kv[key].at[slot].set(
                        jax.lax.dynamic_index_in_dim(
                            pcache[name][key], row, axis=0, keepdims=False
                        )
                    )
                    for key in kv
                }
                for name, kv in cache.items()
            }

    @staticmethod
    def _merge_impl(old, vals, slot_map, rows: bool):
        """`old` [max_slots(, B)] with slot s taking `vals[slot_map[s]]`
        where `slot_map[s]` >= 0 (`rows`: whole rows of a matrix): the
        placement-time write of cur / pos / firsts / a first block."""
        with part("insert"):
            take = slot_map >= 0
            return jnp.where(take[:, None] if rows else take,
                             vals[jnp.clip(slot_map, 0, None)], old)

    def _sample_slots(self, logits, rid, write_pos):
        """Per-slot sampling: the token that will occupy position
        write_pos[b] of request rid[b] draws from
        fold_in(fold_in(base, rid), write_pos) — its own
        counter-derived stream, so a request's sampled output does not
        depend on what else is in the batch (advisor finding, r2)."""
        with part("head"):
            if self.temperature == 0.0:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            keys = jax.vmap(
                lambda r, p: jax.random.fold_in(
                    jax.random.fold_in(self._base_rng, r), p
                )
            )(rid, write_pos)
            return jax.vmap(
                lambda k, lg: _sample(
                    lg[None], k, self.temperature, self.top_k
                )[0]
            )(keys, logits)

    def _chunk_impl(self, params, cache, cur, pos, rid):
        """`chunk` batched decode steps in one dispatch. Per-slot pos
        is clamped to the last cache row on the device, making the
        empty-slot write target explicit — see _insert_impl's
        invariant note. The CLAMPED position is what the scan carries
        forward: an active slot's pos never exceeds the last row (its
        prompt + budget fits max_len, enforced at submit), so this is
        an identity for live requests, while a freed slot's pos pins
        at max_len instead of growing by `chunk` every step for the
        life of the server.

        From `pos` alone an empty slot would therefore look like the
        LONGEST sequence on the grid. The host knows better and has
        already said so: `rid` is 0 exactly for an empty slot
        (`_retire` zeroes it; request ids start at 1), so such a slot
        attends 0 rows and cache attention fetches none of its rows.
        Its clamped write stays (the invariant above).

        A model with expert layers returns a fifth array: the routing
        of occupied slots' tokens reduced on the device to five numbers
        a step a layer ([chunk, layers, 5]: assignments, those to held
        experts, distinct experts reached, distinct held experts
        reached, assignments to the busiest expert; a sixth where the
        layers' windows are read from the routing, `expert_ffn`: those
        a layer ran past its first), which ride the dispatch's packed
        readback."""
        last = self.max_len - 1
        lo, hi = self._held

        def body(carry, _):
            cache, cur, pos = carry
            pos_c = jnp.minimum(pos, last)
            experts = ({"live": rid > 0, "counts": [], "windows": []}
                       if self._routed[0] else None)
            logits, cache = batched_decode_step(
                params, self.cfg, cache, cur, pos_c, mesh=self._mesh,
                lengths=jnp.where(rid > 0, pos_c + 1, 0), experts=experts,
            )
            nxt = self._sample_slots(logits, rid, pos_c + 1)
            if experts is None:
                return (cache, nxt, pos_c + 1), (nxt,)
            with part("moe_route"):  # the routing's numbers, a layer
                numbers = _routing_numbers(
                    jnp.stack(experts["counts"]), lo, hi)
                if experts["windows"]:  # a sixth: windows past the first
                    numbers.append(jnp.stack(experts["windows"]))
                routed = jnp.stack(numbers, -1).astype(jnp.int32)
            return (cache, nxt, pos_c + 1), (nxt, routed)

        (cache, cur, pos), out = jax.lax.scan(
            body, (cache, cur, pos), None, length=self.chunk
        )
        return (cache, cur, pos) + out  # toks [chunk, slots] (, routed)

    def _diffuse_impl(self, params, cache, blk, pos, rid):
        """`blocks_per_dispatch` whole blocks for every slot in one
        dispatch: a `lax.scan` over blocks, each `steps` denoising
        forwards and one commit forward (`BlockDiffusion`), all of
        them `batched_block_step` under the block mask. `blk`
        [slots, B] is each slot's current block (fixed tokens, the
        mask id elsewhere), `pos` its first row, `rid` 0 for an empty
        slot, which attends nothing (`_chunk_impl`'s rule); positions
        are clamped as there, so a freed slot rewrites the grid's last
        block until the next insert's full-row overwrite.

        Which positions a step fixes: the block's masked count m at
        its start gives step s (1-based) n_s = m // S + (s <= m % S)
        of them, the most confident first (softmax probability of the
        argmax, float32; ties to the lower position). A step with
        n_s = 0 still runs: the schedule is fixed.

        Returns (cache, blk' = all masks, pos', packed): ONE int32
        vector of one fixed shape — tokens [R, slots, B], the step
        that fixed each (0 = given) [R, slots, B], and the assignments
        of occupied slots' tokens to each routed expert
        [R, S + 1, layers, E] (nothing where the model has no expert
        layer) — so that no readback ever compiles."""
        df, cfg = self.diffusion, self.cfg
        b, s_n = cfg.block_length, df.steps
        last = self.max_len - b
        mask_id = jnp.int32(df.mask_token_id)
        live = rid > 0
        where = jnp.arange(b, dtype=jnp.int32)[None, :]

        def forward(cache, x, pos_c, head):
            experts = {"live": live, "counts": []}
            logits, cache = batched_block_step(
                params, cfg, cache, x, pos_c, mask_block=b, live=live,
                head=head, mesh=self._mesh, experts=experts)
            counts = (jnp.stack(experts["counts"]) if experts["counts"]
                      else jnp.zeros((0, 0), jnp.int32))
            return logits, cache, counts

        def block(carry, _):
            cache, x, pos = carry
            pos_c = jnp.minimum(pos, last)
            with part("diffuse_select"):
                masked0 = (x == mask_id).sum(-1)  # [slots]
                fixed_at = jnp.where(x == mask_id, -1, 0).astype(jnp.int32)
            routed = []
            for s in range(1, s_n + 1):
                logits, cache, counts = forward(cache, x, pos_c, True)
                routed.append(counts)
                with part("diffuse_select"):
                    best = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    conf = jnp.exp(
                        jnp.max(logits, axis=-1)
                        - jax.nn.logsumexp(logits, axis=-1))
                    masked = x == mask_id
                    conf = jnp.where(masked, conf, -1.0)
                    # rank 0 = the most confident masked position
                    ahead = (conf[:, None, :] > conf[:, :, None]) | (
                        (conf[:, None, :] == conf[:, :, None])
                        & (where[:, None, :] < where[:, :, None]))
                    rank = ahead.sum(-1)
                    n_s = masked0 // s_n + (s <= masked0 % s_n)
                    fix = masked & (rank < n_s[:, None])
                    x = jnp.where(fix, best, x)
                    fixed_at = jnp.where(fix, s, fixed_at)
            _, cache, counts = forward(cache, x, pos_c, False)
            routed.append(counts)
            with part("diffuse_select"):
                nxt = jnp.full_like(x, mask_id)
            return (cache, nxt, pos_c + b), (x, fixed_at, jnp.stack(routed))

        (cache, blk, pos), (toks, fixed_at, routed) = jax.lax.scan(
            block, (cache, blk, pos), None,
            length=self.blocks_per_dispatch)
        with part("pack"):
            packed = jnp.concatenate(
                [toks.ravel(), fixed_at.ravel(), routed.ravel()])
        return cache, blk, pos, packed

    def _propose_impl(self, draft_params, draft_cache, cur, pos):
        """k greedy draft steps from every slot's (cur, pos): returns
        (draft cache, proposals [slots, k]). The draft model shares
        the TARGET's committed cur/pos — its cache rows < pos hold the
        K/V of exactly the committed tokens (the verify-round cap in
        `_verify_impl` maintains this invariant), so proposing is a
        plain greedy continuation. Always argmax regardless of how
        good the draft is: proposals only gate how many target tokens
        commit per round, never which (the proposal-independence
        contract)."""
        last = self.max_len - 1
        cfg = self._spec.draft_cfg

        def body(carry, _):
            cache, tok, p = carry
            pc = jnp.minimum(p, last)
            logits, cache = batched_decode_step(
                draft_params, cfg, cache, tok, pc, mesh=self._mesh
            )
            with part("head"):
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (cache, nxt, pc + 1), nxt

        (draft_cache, _, _), d = jax.lax.scan(
            body, (draft_cache, cur, pos), None, length=self._spec.k
        )
        return draft_cache, jnp.swapaxes(d, 0, 1)  # [slots, k]

    def _verify_impl(self, params, cache, cur, pos, d_toks):
        """ONE fused verify + acceptance round: the target consumes
        [cur, d_1..d_k] per slot in a single multi-token forward
        (`batched_verify_step` — one weight stream for k+1 tokens),
        takes its greedy tokens g_1..g_{k+1}, and commits
        c = min(a+1, k) of them, where a = leading draft/target
        matches. Returns (cache, cur', pos', committed-token matrix
        [slots, k] (row b's first c_b entries are live), accept
        lengths a [slots]).

        Why cap at k (not the classic a+1 <= k+1): committing exactly
        <= k keeps BOTH caches consistent by construction — target
        rows pos..pos+c-1 hold the K/V of [cur, g_1..g_{c-1}] =
        [cur, d_1..d_{c-1}] (c-1 <= a, so drafts and targets agree on
        that prefix), and the DRAFT cache rows written at propose time
        hold the same tokens, so neither cache needs a fix-up pass.
        Rows >= pos' written past the commit point are stale but
        UNREAD: the next dispatch (chunk, propose or verify alike)
        writes its own row(s) at pos' before attending, and a freed
        slot's rows die at the next insert's full-row overwrite
        (_insert_impl's invariant — the verify-start clamp below keeps
        a freed slot's garbage writes in-bounds the same way
        _chunk_impl's pos clamp does).

        Exactness: g_i is the argmax after consuming the SAME prefix a
        plain greedy decode would have at that position (prefix
        d_1..d_{i-1} = g_1..g_{i-1} holds for every committed i), so
        delivering g_1..g_c is literally c plain greedy steps —
        bitwise-identical outputs, for ANY d_toks whatsoever."""
        k = self._spec.k
        start = jnp.minimum(pos, self.max_len - (k + 1))
        inputs = jnp.concatenate([cur[:, None], d_toks], axis=1)
        logits, cache = batched_verify_step(
            params, self.cfg, cache, inputs, start, mesh=self._mesh
        )
        # g[:, i] = target-greedy token for position start+i+1 (the
        # argmax after consuming inputs[:, i])
        with part("head"):  # the greedy tokens and what of them commits
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, k+1]
            match = (d_toks == g[:, :k]).astype(jnp.int32)
            a = jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # [B] 0..k
            c = jnp.minimum(a + 1, k)
            cur2 = jnp.take_along_axis(g, (c - 1)[:, None], axis=1)[:, 0]
            return cache, cur2, start + c, g[:, :k], a

    # -- public API ----------------------------------------------------

    def submit(self, prompt: np.ndarray, max_new_tokens: int) -> int:
        """Queue a request; returns its request id. Placement happens
        immediately if a slot is free, else at the next step()."""
        return self.submit_many([prompt], max_new_tokens)[0]

    def _validate(self, prompt: np.ndarray, max_new_tokens: int) -> np.ndarray:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            # generate() returns [B, 0] for a zero budget; a server
            # request always produces tokens, so reject instead of
            # silently emitting one
            raise ValueError("max_new_tokens must be >= 1")
        if prompt.size + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt {prompt.size} + budget {max_new_tokens} "
                f"exceeds max_len {self.max_len}"
            )
        if self.diffusion is not None and (
            prompt == self.diffusion.mask_token_id
        ).any():
            raise ValueError(
                f"the prompt holds the mask token "
                f"{self.diffusion.mask_token_id}, which marks the "
                f"positions still to generate")
        return prompt

    def submit_many(
        self,
        prompts: Sequence[np.ndarray],
        max_new_tokens,
        on_token: Optional[Sequence[Optional[Callable[[int], None]]]] = None,
        trace: Optional[Sequence[Optional[TraceContext]]] = None,
        parent: Any = None,
    ) -> List[int]:
        """Queue a burst of requests and place them in ONE batched
        round. `max_new_tokens` is an int shared by the burst or a
        per-prompt sequence — mixed budgets are continuous batching's
        home turf: each slot refills the moment ITS request retires
        instead of waiting out the burst's slowest. Validates EVERY
        prompt before queueing ANY (atomic), preserving sequential
        submit()'s rid order.

        `on_token` is an optional per-prompt sequence of callbacks;
        request i's callback fires with each of its token values as
        they are read back (the ingress per-token stream source).
        `trace` is an optional per-prompt sequence of trace contexts
        (request i's `lm_request` span hangs under its context) and
        `parent` the loop span that caused the burst (the driver's
        `lm_submit`), under which its placement is recorded."""
        if isinstance(max_new_tokens, (int, np.integer)):
            budgets = [int(max_new_tokens)] * len(prompts)
        else:
            budgets = [int(b) for b in max_new_tokens]
            if len(budgets) != len(prompts):
                raise ValueError(
                    f"{len(budgets)} budgets for {len(prompts)} prompts"
                )
        if on_token is not None and len(on_token) != len(prompts):
            raise ValueError(
                f"{len(on_token)} on_token callbacks for "
                f"{len(prompts)} prompts"
            )
        if trace is not None and len(trace) != len(prompts):
            raise ValueError(
                f"{len(trace)} trace contexts for {len(prompts)} prompts"
            )
        validated = [
            self._validate(p, b) for p, b in zip(prompts, budgets)
        ]
        reqs = []
        now = time.monotonic()
        for i, (prompt, b) in enumerate(zip(validated, budgets)):
            self._rid += 1
            reqs.append(_Request(
                self._rid, prompt, b, t_submit=now,
                on_token=on_token[i] if on_token is not None else None,
                ctx=trace[i] if trace is not None else None,
            ))
        _M_REQS.inc(len(reqs))
        self._queue.extend(reqs)
        self._place_waiting(parent)
        return [r.rid for r in reqs]

    def free_slot_count(self) -> int:
        """Currently-unoccupied decode slots (the disaggregated
        backend paces slab adoption with this)."""
        return sum(1 for r in self._slot_req if r is None)

    def submit_prefilled(
        self,
        prompt: np.ndarray,
        max_new_tokens: int,
        rows: Dict[str, Dict[str, np.ndarray]],
        first_token: int,
        on_token: Optional[Callable[[int], None]] = None,
        draft_tokens: Optional[Sequence[int]] = None,
    ) -> int:
        """Adopt an EXTERNALLY-prefilled request: place a KV-cache
        slab computed elsewhere (a prefill-role worker, transported as
        bytes over the data plane — inference/lm_sharded.py) straight
        into a free slot and decode from it. `rows` is the per-layer
        cache slab for positions < len(prompt), batch axis stripped:
        {block_i: {k/v: [KV, Tp, D]}} (bf16 layout) or the kv_quant
        leaves with scales as [KV, 1, Tp]. `first_token` is the token
        the prefill sampled at the last prompt position; it seeds the
        decode exactly like a local placement's deferred first token,
        except its VALUE is already host-side (it rode the slab), so
        it lands in the output directly with no pending readback.

        Requires a free slot — the caller paces adoption against
        `free_slot_count()` (a queue here would hold the transferred
        slab bytes hostage on the host for unbounded time).

        Exactness: the slab's bits are the prefill node's prefill
        output; padding the T axis back to max_len is the same
        full-row write `_insert_impl` always does, with the stale tail
        behind the per-slot validity mask. With greedy sampling the
        continued decode is token-identical to a local submit() — the
        chunk sampler's argmax has no rid dependence. (Temperature
        sampling streams are keyed by THIS server's rid, which the
        prefill node cannot know; the disaggregated backend therefore
        requires temperature == 0.)

        `draft_tokens` (optional, <= spec k of them) are a REMOTE
        draft's speculative proposals that rode the slab (a
        prefill-role peer that idles during decode-heavy phases ran
        the draft model on prompt+first_token): they seed this
        request's FIRST verify round when speculative decoding is
        enabled without a local device draft, and are silently
        dropped otherwise — a shipped draft can accelerate but never
        affect output values (proposal-independence)."""
        self._refuse_state("submit_prefilled (a slab of K/V rows by token)")
        prompt = self._validate(prompt, max_new_tokens)
        slot = next(
            (s for s in range(self.max_slots)
             if self._slot_req[s] is None), None
        )
        if slot is None:
            raise RuntimeError("no free slot for prefilled request")
        self._rid += 1
        req = _Request(
            self._rid, prompt, int(max_new_tokens),
            t_submit=time.monotonic(), on_token=on_token,
        )
        if (
            draft_tokens is not None and self._spec is not None
            and self._spec.enabled
            and self._spec.draft_params is None
        ):
            # a local device draft re-proposes every round on device;
            # shipped tokens only matter when there is no local draft
            req.shipped_draft = np.asarray(
                draft_tokens, np.int32
            ).reshape(-1)[: self._spec.k]
        _M_REQS.inc()
        self._place_prefilled(slot, req, rows, int(first_token))
        _M_SLOTS.set(sum(1 for r in self._slot_req if r is not None))
        return req.rid

    def _place_prefilled(
        self,
        slot: int,
        req: _Request,
        rows: Dict[str, Dict[str, np.ndarray]],
        first_token: int,
    ) -> None:
        """Place an already-prefilled request into ``slot`` — the
        shared core of `submit_prefilled` (disaggregated slab
        adoption) and the KV-prefix-cache warm placement
        (_place_waiting). ``rows`` covers positions < len(prompt);
        ``first_token`` is host-side, so it lands in the output
        directly with no pending readback."""
        tp = req.prompt.size
        req.t_placed = time.monotonic()
        # rebuild the [1, KV, max_len, ...] insert-shaped tree: values
        # pad the T axis (2), kv_quant scales carry T on lanes (3)
        pcache = {}
        for name, kv in rows.items():
            pcache[name] = {}
            for key, arr in kv.items():
                a = np.asarray(arr)
                t_axis = 2 if key.endswith("_s") else 1
                if a.shape[t_axis] != tp:
                    raise ValueError(
                        f"slab {name}/{key}: T={a.shape[t_axis]} != "
                        f"prompt {tp}"
                    )
                pad = [(0, 0)] * a.ndim
                pad[t_axis] = (0, self.max_len - tp)
                pcache[name][key] = jnp.asarray(np.pad(a, pad))[None]
        self.cache = self._insert(
            self.cache, pcache, jnp.int32(slot), jnp.int32(0)
        )
        self._fed()
        slot_map = np.full(self.max_slots, -1, np.int32)
        slot_map[slot] = 0
        sm = jnp.asarray(slot_map)
        self._cur_dev = self._merge_vec(
            self._cur_dev, jnp.asarray([int(first_token)], jnp.int32), sm
        )
        self._pos_dev = self._merge_vec(
            self._pos_dev, jnp.asarray([tp], jnp.int32), sm
        )
        req.deliver([int(first_token)])
        req.emitted = 1
        req.slot = slot
        self._slot_req[slot] = req
        self.rid_vec[slot] = req.rid
        self.tokens_delivered += 1
        _M_TOKENS.inc()
        if (
            self._spec is not None and self._spec.enabled
            and self._spec.draft_params is not None and not req.done
        ):
            # the slab carried TARGET rows only; the local draft cache
            # needs its own rows for positions < Tp before it can
            # propose for this slot. The prompt is host-known, so this
            # is one single-row bucketed draft prefill — cheap (the
            # draft is the small model) and fully async.
            self._spec_draft_prefill_one(slot, req.prompt)
        if req.done:  # max_new_tokens == 1: the slab's token was all
            self._retire(slot)

    def _spec_draft_prefill_one(self, slot: int, prompt: np.ndarray) -> None:
        """Fill the DRAFT cache's rows for one slot from a host-known
        prompt (adopted-slab / warm-start placements, whose target
        rows arrived as bytes). Same bucket/pad discipline as
        _place_waiting's group prefill."""
        tp = prompt.size
        bucket = min(_bucket(tp), self.max_len)
        padded = np.full((1, bucket), prompt[-1], np.int32)
        padded[0, :tp] = prompt
        _, pcache = self._draft_prefill(
            self._spec.draft_params, jnp.asarray(padded),
            jnp.asarray([tp - 1], np.int32),
        )
        self._spec.draft_cache = self._insert(
            self._spec.draft_cache, pcache, jnp.int32(slot),
            jnp.int32(0),
        )

    def _drained(self, after: str) -> None:
        """The serving thread has just returned from blocking on the
        NEWEST program it enqueued (`after`: `readback` a dispatch's
        packed readback, `firsts` a stray read of the first tokens,
        `insert_wait` a latent prefill group waiting out the last
        group's inserts): whatever it enqueued before is done too, so
        the device's queue is empty from here until `_fed`."""
        if self._exposed is None:
            self._exposed = (time.monotonic(), after)

    def _fed(self) -> None:
        """A program has been enqueued (or the driver goes idle, the
        run ends: no work is no exposure): the stretch since `_drained`
        is one `lm_exposed` span and one observation of
        `lm_server_exposed_seconds`. Recorded from its two readings: it
        runs across spans and is no frame of its own."""
        if self._exposed is None:
            return
        (t0, after), self._exposed = self._exposed, None
        now = time.monotonic()
        TRACER.loop_record("lm_exposed", t0, now, after=after)
        _M_EXPOSED.observe(now - t0)

    def _place_waiting(self, parent: Any = None) -> None:
        """Free slots take queued requests, recorded as one `lm_place`
        span under `parent` (the dispatch's `lm_step`, the driver's
        `lm_submit`, or none) that holds one `lm_prefill_group` span
        per prefill group."""
        with TRACER.loop_span("lm_place", parent) as span:
            span.label(requests=self._place_waiting_in(span))

    def _place_waiting_in(self, span: Any) -> int:
        """`_place_waiting`'s body; returns how many requests it placed."""
        # Placement is FULLY ASYNC and GROUP-BATCHED: free slots take
        # queued requests, the round is cut into the groups that pad
        # least (`_prefill_groups`), each group running ONE batched
        # prefill (rows padded to a power-of-two group size to bound
        # compilations), one row-indexed cache insert per request, one
        # batched first-token sample, and fixed-shape masked merges
        # into the device-resident cur/pos — nothing
        # here blocks on the device, and the first tokens' VALUES ride
        # the next step's packed readback (or _flush_firsts). History:
        # r3 paid two blocking readbacks per prompt, r4 one per
        # placement round plus a [1, bucket] prefill dispatch chain
        # PER PROMPT.
        pairs = []
        for slot in range(self.max_slots):
            if self._slot_req[slot] is None and self._queue:
                pairs.append((slot, self._queue.pop(0)))
        if not pairs:
            return 0
        placed = len(pairs)
        if any(slot in self._pending_first for slot, _ in pairs):
            # a request that retired at placement (a budget of 1) left
            # its first token unread in a slot that is placed again
            # before any step: read it before it is overwritten
            self._flush_firsts()
        if self._warm is not None and self.temperature == 0.0:
            # KV-prefix warm starts intercept placement REQUEST BY
            # REQUEST: a prompt extending a cached prefix adopts the
            # cached rows + a suffix-only prefill through the
            # submit_prefilled placement; everything else falls
            # through to the cold group prefill below. Greedy only —
            # sampled first tokens are rid-keyed (submit_prefilled's
            # documented discipline), and with no cache attached this
            # branch never runs, keeping the cold path bit-identical.
            cold: List[Tuple[int, _Request]] = []
            for slot, req in pairs:
                warm = self._warm.rows_for(self.params, req.prompt)
                if warm is None:
                    cold.append((slot, req))
                    continue
                rows, first, saved = warm
                now = time.monotonic()
                _M_QUEUE_WAIT.observe(now - req.t_submit)
                self._place_prefilled(slot, req, rows, first)
                self.kv_cache.note_adopted(saved)
            pairs = cold
            if not pairs:
                _M_SLOTS.set(
                    sum(1 for r in self._slot_req if r is not None)
                )
                return placed
        for bucket, rows, members in _prefill_groups(
            [req.prompt.size for _, req in pairs],
            self.max_len, self.max_slots, self._group_tokens,
        ):
            self._place_group(
                bucket, rows, [pairs[i] for i in members], span)
        _M_SLOTS.set(sum(1 for r in self._slot_req if r is not None))
        return placed

    def _place_group(
        self, bucket: int, kp: int, grp: List[Tuple[int, _Request]],
        parent: Any,
    ) -> None:
        """One prefill group's placement (`_prefill_groups` chose its
        members, its bucket and its kp padded rows): ONE batched
        prefill, one row-indexed insert per request, one batched
        first-token sample and the masked merges, all enqueued without
        waiting for the device (the `lm_prefill_group` span and the
        `lm_server_prefill_dispatch_seconds` observation are that
        enqueue chain's host wall)."""
        k = len(grp)
        riders = sum(
            _prefill_bucket(req.prompt.size, self.max_len) < bucket
            for _, req in grp
        )
        with TRACER.loop_span(
            "lm_prefill_group", parent, bucket=bucket, rows=k,
            riders=riders,
            # rows whose state-space state the prefill takes at their
            # own length and the inserts copy
            **({"state_rows": k} if self.cfg.has_state else {}),
            # latent attention's form here: keys and values rebuilt
            **({"attn": "expanded"} if self.cfg.latent is not None else {}),
            # of the k-blocks the causal kernel computes over the bucket,
            # the share a window layer's banded kernel never visits
            **self._band_label(bucket),
        ) as span:
            padded = np.zeros((kp, bucket), np.int32)
            tps = np.ones(kp, np.int32)
            rids = np.zeros(kp, np.int32)
            slot_map = np.full(self.max_slots, -1, np.int32)
            for row, (slot, req) in enumerate(grp):
                tp = req.prompt.size
                padded[row, :tp] = req.prompt
                # pad with the last token: garbage positions >= tp are
                # behind the validity mask, but rope/cache write them
                padded[row, tp:] = req.prompt[-1]
                tps[row] = tp
                rids[row] = req.rid
                slot_map[slot] = row
            for row in range(k, kp):  # dummy rows: repeat row 0
                padded[row] = padded[0]
                tps[row] = tps[0]
            # per-row logits_index = tp-1: causal masking makes each
            # row's logits at its true last prompt position identical
            # to an UNPADDED prefill's, so first tokens match
            # generate() exactly despite bucket AND group padding
            if self._one_group:
                # ONE group's rows at a time: a call's outputs are
                # allocated as it is enqueued, and a round of sixteen
                # long prompts enqueued at once would hold 2-3 GB of
                # rows to insert that the chip does not have. The wait
                # is for the last group's inserts; the host's few ms of
                # preparing this one are then the device's idle
                jax.block_until_ready(self.cache)
                self._drained("insert_wait")
            logits, pcache, *further = self._prefill(
                self.params, jnp.asarray(padded),
                jnp.asarray(tps - 1),
            )
            self._fed()
            if further:  # rides the next packed readback
                self._windows_dev = self._windows_dev + further[0]
            for row, (slot, req) in enumerate(grp):
                self.cache = self._insert(
                    self.cache, pcache, jnp.int32(slot), jnp.int32(row)
                )
            if (
                self._spec is not None and self._spec.enabled
                and self._spec.draft_params is not None
            ):
                # second bucketed prefill, DRAFT params: the draft
                # cache shadows the slot grid and needs its own rows
                # for positions < tp before it can propose. Same
                # padded batch, same row->slot inserts; the draft's
                # logits are unused (the first token is the TARGET's).
                _, dpcache = self._draft_prefill(
                    self._spec.draft_params, jnp.asarray(padded),
                    jnp.asarray(tps - 1),
                )
                for row, (slot, req) in enumerate(grp):
                    self._spec.draft_cache = self._insert(
                        self._spec.draft_cache, dpcache,
                        jnp.int32(slot), jnp.int32(row),
                    )
            sm = jnp.asarray(slot_map)
            if self.diffusion is not None:
                # whole blocks of the prompt are in the cache (what the
                # prefill wrote past them is rewritten by the first
                # block's forwards); its tail opens the first block
                b = self.cfg.block_length
                first_blk = np.full(
                    (kp, b), self.diffusion.mask_token_id, np.int32)
                for row, (_, req) in enumerate(grp):
                    tail = req.prompt.size % b
                    if tail:
                        first_blk[row, :tail] = req.prompt[-tail:]
                self._blk_dev = self._merge_blk(
                    self._blk_dev, jnp.asarray(first_blk), sm)
                self._pos_dev = self._merge_vec(
                    self._pos_dev, jnp.asarray(tps // b * b), sm)
            else:
                # first generated tokens occupy position tp — the same
                # (rid, position) streams the chunk sampler continues
                firsts = self._sample_first(
                    logits, jnp.asarray(rids), jnp.asarray(tps)
                )
                self._cur_dev = self._merge_vec(self._cur_dev, firsts, sm)
                self._pos_dev = self._merge_vec(
                    self._pos_dev, jnp.asarray(tps), sm
                )
                self._firsts_dev = self._merge_vec(
                    self._firsts_dev, firsts, sm)
                for slot, req in grp:
                    self._pending_first[slot] = req
            prompt_tokens = int(tps[:k].sum())
            span.label(padded_rows=kp, prompt_tokens=prompt_tokens,
                       padded_tokens=kp * bucket)
            layers, e = self._routed
            if layers:
                # what an expert layer lays out for this group, from
                # shapes: assignments, and rows at one window a chunk
                top = self.cfg.experts_per_token
                chunks, per, window = moe_layout(
                    kp * bucket, top, e, self._held[1] - self._held[0])
                span.label(moe_rows=chunks * per * top,
                           moe_rows_laid=chunks * window)
                _M_MOE_WINDOWS_FIRST.inc(layers * chunks)
        now = span.m1
        _M_PREFILL.observe(now - span.m0)
        _M_PREFILL_PROMPT.inc(prompt_tokens)
        _M_PREFILL_PADDED.inc(kp * bucket)
        for slot, req in grp:
            _M_QUEUE_WAIT.observe(now - req.t_submit)
            req.t_placed = now
            # a first token is sampled at placement, except under block
            # diffusion, whose tokens all come from denoising forwards
            req.emitted = 0 if self.diffusion is not None else 1
            req.slot = slot
            self._slot_req[slot] = req
            self.rid_vec[slot] = req.rid
            if req.done:  # max_new_tokens == 1
                self._retire(slot)

    def _band_label(self, bucket: int) -> Dict[str, float]:
        """`lm_prefill_group`'s labels of a window layer's prefill
        kernel for a group of `bucket` tokens a row (`_band_labels`);
        nothing for a model without a window layer."""
        if not self.cfg.has_ring:
            return {}
        lay = min(
            (t for _, t in self.cfg.attention_layers.types
             if t.window is not None), key=lambda t: t.window)
        return _band_labels(bucket, lay.window, self.cfg.head_dim,
                            lay.n_heads // self.cfg.kv_heads)

    def _retire(self, slot: int) -> None:
        req = self._slot_req[slot]
        assert req is not None
        # greedy-only like the warm/read side: a sampled server can
        # never adopt (first tokens are rid-keyed), so capturing would
        # pay per-retire readbacks into a cache nothing ever reads
        if self.kv_cache is not None and self.temperature == 0.0:
            self._capture_retired(slot, req)
        self._done[req.rid] = req
        if self.diffusion is not None:
            self._fixed_at[req.rid] = {
                "fixed_at": list(req.fixed_at),
                "beyond_budget": {"tokens": list(req.beyond[0]),
                                  "fixed_at": list(req.beyond[1])},
            }
        req.slot = None
        self._slot_req[slot] = None
        # 0 is not only "no request": `_chunk_impl` reads rid 0 as AN
        # EMPTY SLOT, ATTEND NOTHING (request ids start at 1)
        self.rid_vec[slot] = 0
        _M_REQS_DONE.inc()

    def _capture_retired(self, slot: int, req: _Request) -> None:
        """Donate a retiring request's KV rows to the prefix cache.
        Valid cache positions are [0, Tp + emitted - 1): the LAST
        sampled token was never fed back through the model, so its
        row is unwritten — the entry's token list stops one short of
        the full output, which is exactly what a next-turn prompt
        (history + new suffix) re-covers with its own suffix prefill.
        Capture is a device-side slice here; the host materialization
        happens in `KVPrefixCache.offer` (once per retired request,
        never per decode step). Any failure only forfeits the cache
        entry — retirement itself must not break."""
        from .kv_cache import capture_slot_rows

        try:
            n = req.prompt.size + req.emitted - 1
            need = req.emitted - 1  # generated tokens with rows
            if len(req.out) < need:
                # deferred-first placement retire (budget-1 whose
                # token value is still on device): need == 0 there,
                # so this only guards a future delivery-order drift
                return
            tokens = np.concatenate([
                req.prompt, np.asarray(req.out[:need], np.int32),
            ])
            self.kv_cache.offer(
                tokens, capture_slot_rows(self.cache, slot, n)
            )
        except Exception as e:
            log.warning("kv-cache capture failed at retire: %r", e)

    def _take_firsts(self) -> Dict[int, _Request]:
        """The requests whose first token the next readback of
        `_firsts_dev` delivers, by slot; none are pending after."""
        firsts, self._pending_first = self._pending_first, {}
        return firsts

    @staticmethod
    def _distribute_firsts(firsts: Dict[int, _Request], vals, off) -> None:
        """Hand each pending request its first token from the packed
        buffer `vals`, where `_firsts_dev` starts at `off`. Shared by
        step()'s packed readback and _flush_firsts."""
        for slot, req in firsts.items():
            req.deliver([int(vals[off + slot])])

    def _flush_firsts(self) -> None:
        """Read back any placement-time first tokens that haven't
        ridden a step's packed readback (e.g. a budget-1 request that
        retired at placement with no step following). A blocking link
        round-trip — callers gate it (take_done flushes only when a
        pending request is actually done)."""
        if not self._pending_first:
            return
        firsts = self._take_firsts()
        with TRACER.loop_span("lm_readback", arrays=1) as rb:
            vals = np.asarray(self._firsts_dev)
        self._drained("firsts")
        _M_READBACK.observe(rb.m1 - rb.m0)
        self._distribute_firsts(firsts, vals, 0)
        self.tokens_delivered += len(firsts)
        _M_TOKENS.inc(len(firsts))

    def step(self) -> None:
        """One decode dispatch: every active slot advances — a
        chunked scan, or a speculative propose+verify round when
        enabled and this dispatch is eligible (`_use_spec`). Finished
        slots free and waiting requests take their place at this step
        boundary mid-flight (`_place_waiting` at the tail) — the
        continuous-batching join point: a request never waits for the
        batch it joins to drain."""
        if not any(r is not None for r in self._slot_req):
            self._place_waiting()
            if not any(r is not None for r in self._slot_req):
                return
        # `waiting`: requests queued without a slot as the dispatch is
        # issued (the grid has a backlog to refill from)
        with TRACER.loop_span(
            "lm_step", waiting=len(self._queue),
            # latent attention's form in every cached step: the cached
            # rows attended as they are
            **({"attn": "absorbed"} if self.cfg.latent is not None else {}),
        ) as span:
            # the dispatch's own reckoning is the span's self time
            occupancy = sum(1 for r in self._slot_req if r is not None)
            _M_OCCUPANCY.observe(occupancy)
            mode, dispatch = (
                ("diffusion", self._diffuse_step)
                if self.diffusion is not None
                else ("spec", self._spec_step) if self._use_spec()
                else ("chunk", self._chunk_step))
            span.label(occupancy=occupancy, mode=mode)
            dispatch(span)
        _M_STEP.observe(span.m1 - span.m0)

    def _use_spec(self) -> bool:
        """Per-DISPATCH host gate for the speculative round. False
        falls back to the plain chunk scan for this dispatch only:

        - no proposal source this round (no draft model, no proposer,
          and no adopted request carrying a shipped draft) — verifying
          garbage rows to commit ~1 token per round would be SLOWER
          than the chunk scan;
        - any active slot within k+1 positions of max_len: the verify
          forward writes rows pos..pos+k, and a clamped
          dynamic_update_slice start would silently relocate live
          tail rows (the host knows every active slot's pos as
          prompt + emitted — the device never reports back).
        """
        sp = self._spec
        if sp is None or not sp.enabled:
            return False
        if sp.draft_params is None and sp.proposer is None and not any(
            r is not None and r.shipped_draft is not None
            for r in self._slot_req
        ):
            return False
        lim = self.max_len - (sp.k + 1)
        for r in self._slot_req:
            if r is not None and r.prompt.size + r.emitted > lim:
                return False
        return True

    def _spec_step(self, step: Any) -> None:
        """One speculative round: propose k tokens per slot, verify
        all of them in ONE multi-token target forward, commit 1..k
        target-greedy tokens per slot. Same packed-readback
        discipline as `_chunk_step` — committed tokens + accept
        lengths + any deferred placement firsts ride ONE blocking
        readback — and the same phase spans under `step`, with one
        `lm_dispatch` for the proposals and one for the verify."""
        sp = self._spec
        k = sp.k
        b = self.max_slots
        firsts = self._take_firsts()
        real = [False] * b  # slots whose proposals count toward rate
        propose = TRACER.loop_span("lm_dispatch", step, phase="propose")
        if sp.draft_params is not None:
            # device draft: proposals never leave the chip. A shipped
            # draft is redundant here (the local draft re-proposes) —
            # consume it so it can't leak into a later round.
            for r in self._slot_req:
                if r is not None:
                    r.shipped_draft = None
                    real[r.slot] = True
            sp.draft_cache, d_toks = self._propose_fn(
                sp.draft_params, sp.draft_cache,
                self._cur_dev, self._pos_dev,
            )
            self._fed()
        else:
            # host-side proposals: shipped drafts first (consumed
            # once), then the proposer callable for the rest. Slots
            # with neither get zero rows — verification still commits
            # >= 1 correct token for them (proposal-independence), and
            # they are excluded from acceptance accounting.
            d = np.zeros((b, k), np.int32)
            need: List[_Request] = []
            for slot, r in enumerate(self._slot_req):
                if r is None:
                    continue
                if r.shipped_draft is not None:
                    sd = r.shipped_draft[:k]
                    r.shipped_draft = None
                    d[slot, : sd.size] = sd
                    real[slot] = True
                elif sp.proposer is not None:
                    need.append(r)
            if need:
                rows = np.asarray(
                    sp.proposer(need, k), np.int32
                ).reshape(len(need), k)
                for r, row in zip(need, rows):
                    d[r.slot] = row
                    real[r.slot] = True
            d_toks = jnp.asarray(d)
        propose.end()
        with TRACER.loop_span("lm_dispatch", step, phase="verify"):
            (
                self.cache, self._cur_dev, self._pos_dev, toks, acc
            ) = self._verify_fn(
                self.params, self.cache, self._cur_dev, self._pos_dev,
                d_toks,
            )
            self._fed()
        packed = self._read_packed(
            step, [jnp.ravel(toks), acc, self._firsts_dev])
        n = b * k
        first_n = len(firsts)
        with TRACER.loop_span("lm_deliver", step) as deliver:
            tokm = packed[:n].reshape(b, k)
            accs = packed[n : n + b]
            # same pre-callback occupancy snapshot as _chunk_step: an
            # on_token adoption mid-delivery must wait for the next
            # dispatch, not consume this round's stale verify column
            live = list(enumerate(self._slot_req))
            self._distribute_firsts(firsts, packed, n + b)
            delivered = first_n
            retired = 0
            prop_n = acc_n = 0
            for slot, req in live:
                if req is None:
                    continue
                a = int(accs[slot])
                c = min(a + 1, k)
                take = min(c, req.max_new_tokens - req.emitted)
                req.deliver(tokm[slot, :take])
                req.emitted += take
                delivered += take
                if real[slot]:
                    prop_n += k
                    acc_n += a
                    req.spec_rounds += 1
                    req.spec_accepted += a
                # take < c ⇒ retire; device cur/pos overran the budget,
                # erased by the next insert (the _insert_impl invariant
                # — same discipline as the chunk path)
                if req.done:
                    self._retire(slot)
                    retired += 1
            deliver.label(tokens=delivered, retired=retired)
        _M_DELIVER.observe(deliver.m1 - deliver.m0)
        sp.rounds += 1
        if prop_n:
            _M_SPEC_PROPOSED.inc(prop_n)
            _M_SPEC_ACCEPTED.inc(acc_n)
            sp.proposed_total += prop_n
            sp.accepted_total += acc_n
            sp.win_proposed += prop_n
            sp.win_accepted += acc_n
            if (
                sp.min_accept > 0.0
                and (sp.draft_params is not None
                     or sp.proposer is not None)
                and sp.win_proposed >= sp.min_samples
            ):
                rate = sp.win_accepted / sp.win_proposed
                if rate < sp.min_accept:
                    # below break-even: each round's verify forward
                    # costs ~k+1 cache rows of attention + one weight
                    # stream to commit ~rate*k+1 tokens; the chunk
                    # scan beats that once acceptance collapses
                    self.disable_spec_decode(reason="acceptance")
                elif sp.win_proposed >= 2 * sp.min_samples:
                    # slide the window so the gate tracks the CURRENT
                    # workload, not the lifetime average
                    sp.win_proposed //= 2
                    sp.win_accepted //= 2
        self._finish_step(step, delivered, first_n)

    def _read_packed(self, step: Any, arrays: List[jax.Array]) -> np.ndarray:
        """ONE packed readback per step: the dispatch's tokens, the
        first tokens placements deferred since the last one
        (`_firsts_dev`, whole), what else the dispatch counted and,
        last, the prefills' windows (`_windows_dev`, where it is kept).
        cur/pos never come back to the host (device-authoritative). Two
        phases under `step`: `lm_pack` issues the concatenate, whose
        operands have the same shapes in every step of a server's mode,
        so it compiles once, in the first; `lm_readback` is the
        blocking np.asarray, which stalls the host until the device
        drains and is the ONLY such stall in the serve loop."""
        if self._windows_dev is not None:
            arrays = arrays + [self._windows_dev]
        with TRACER.loop_span("lm_pack", step, arrays=len(arrays)) as pack:
            with part("pack"):
                packed = jnp.concatenate(arrays)
        with TRACER.loop_span("lm_readback", step) as readback:
            out = np.asarray(packed)
        self._drained("readback")
        _M_PACK.observe(pack.m1 - pack.m0)
        _M_READBACK.observe(readback.m1 - readback.m0)
        if self._windows_dev is not None:
            # the prefills' windows past their first since the last one
            _M_MOE_WINDOWS_FURTHER.inc(int(out[-1]))
            self._windows_dev = self._no_windows
            out = out[:-1]
        return out

    def _finish_step(self, step: Any, delivered: int, first_n: int) -> None:
        """A dispatch's tail: freed slots take waiting requests (the
        continuous-batching join point), then the per-dispatch counts."""
        self._place_waiting(step)
        self.tokens_delivered += delivered
        _M_TOKENS.inc(delivered)
        _M_STEPS.inc()
        _M_SLOTS.set(sum(1 for r in self._slot_req if r is not None))
        step.label(tokens=delivered, firsts=first_n)

    def _kv_rows(self, layer: int = 0) -> Tuple[int, int, int, int]:
        """(live, read, grid, blocks) of ONE layer, attention layer
        `layer`, over the chunk dispatch about to be issued: cache rows
        live, fetched and in the whole grid, and the k-blocks the
        kernel visits. Host arithmetic on what the device will do, no
        readback. A live slot at step i attends prompt + emitted + i
        rows (its clamped position + 1) of a layer that caches a row a
        token, and min(that, window) rows of a window layer's ring,
        whose plane is the ring (`LMConfig.layer_rows`); cache
        attention walks the live (slot, k-block) pairs alone,
        `cdiv(rows, block)` a slot, and fetches each whole; an empty
        slot has none (a step with every slot empty visits one block of
        no live rows: ops/decode_attention.py)."""
        plane = self.cfg.layer_rows(layer, self.max_len)
        if "*" not in (self.cfg.layer_pattern or "*") or not plane:
            return 0, 0, 0, 0  # no attention layer, no rows
        grid = self.chunk * self.max_slots * plane
        pos0 = np.asarray(
            [r.prompt.size + r.emitted - 1
             for r in self._slot_req if r is not None], np.int64
        )
        lens = np.minimum(np.minimum(
            pos0[:, None] + np.arange(self.chunk), self.max_len - 1
        ) + 1, plane)  # [live slots, chunk]
        live = int(lens.sum())
        bk = decode_block_rows(self.cfg, self.max_len, self._mesh, layer)
        if bk is None:  # the einsum streams every row; it has no blocks
            return live, grid, grid, 0
        nblk = -(-lens // bk)
        rows = np.minimum(nblk * bk, plane)  # the plane's last block is short
        blocks = np.maximum(nblk.sum(axis=0), 1)  # a step
        read = np.maximum(rows.sum(axis=0), min(bk, plane))
        return live, int(read.sum()), grid, int(blocks.sum())

    def _chunk_step(self, step: Any) -> None:
        """The plain chunked-scan dispatch (step()'s pre-spec body),
        as five phase spans under `step`: `lm_dispatch`, `lm_pack`,
        `lm_readback`, `lm_deliver`, `lm_place`."""
        firsts = self._take_firsts()
        with TRACER.loop_span("lm_dispatch", step):
            self.cache, self._cur_dev, self._pos_dev, toks, *routed = (
                self._chunk_fn(
                    self.params, self.cache, self._cur_dev, self._pos_dev,
                    jnp.asarray(self.rid_vec),
                ))
            self._fed()
        # reckoned while the device works, before delivery moves
        # `emitted`: one layer of each type the stack holds. A layer
        # that caches a row a token keeps the labels it had; a window
        # layer's ring has its own
        for layers, i in self._kv_layers:
            rows = self._kv_rows(i)
            for child, n in zip(_M_KV[layers], rows):
                child.inc(n)
            live, read, _, blocks = rows
            if layers == "full":
                step.label(kv_rows_live=live, kv_rows_read=read,
                           kv_blocks=blocks)
            else:
                step.label(kv_window_rows_live=live,
                           kv_window_rows_read=read,
                           kv_window_blocks=blocks)
        if self.cfg.has_state:
            # every occupied slot's state was read and written whole by
            # each of the dispatch's steps
            step.label(state_slots=step.labels["occupancy"])
        packed = self._read_packed(
            step, [jnp.ravel(toks), self._firsts_dev]
            + [jnp.ravel(r) for r in routed])
        n = self.chunk * self.max_slots
        # deferred first tokens ride this readback: they are delivered
        # tokens of this step (the chunk takes below cover budget - 1
        # of each request, the placement-time first covers the rest)
        first_n = len(firsts)
        if routed:
            with TRACER.loop_span("lm_route", step):
                self._note_routing(
                    step, packed[n + self.max_slots:].reshape(
                        self.chunk, self._routed[0], -1))
        with TRACER.loop_span("lm_deliver", step) as deliver:
            toks = packed[:n].reshape(self.chunk, self.max_slots)
            # snapshot occupancy BEFORE any deliver() fires user
            # callbacks: a callback may adopt a prefilled request
            # (submit_prefilled) into a slot this step freed — or never
            # occupied — and a live iteration would then hand the
            # adoptee THIS dispatch's stale column. The adoptee decodes
            # from the NEXT dispatch; its placement already delivered
            # the slab's first token exactly once
            # (tests/test_specdec.py pins the race).
            live = list(enumerate(self._slot_req))
            self._distribute_firsts(firsts, packed, n)
            delivered = first_n
            retired = 0
            for slot, req in live:
                if req is None:
                    continue
                take = min(self.chunk, req.max_new_tokens - req.emitted)
                req.deliver(toks[:take, slot])
                req.emitted += take
                delivered += take
                # take < chunk ⇒ the request retires here; the slot's
                # device cur/pos ran past its budget, which the next
                # insert's full overwrite erases (the _insert_impl
                # invariant) — an ACTIVE continuation always has
                # take == chunk, so device and host never disagree
                if req.done:
                    self._retire(slot)
                    retired += 1
            deliver.label(tokens=delivered, retired=retired)
        _M_DELIVER.observe(deliver.m1 - deliver.m0)
        self._finish_step(step, delivered, first_n)

    def _diffuse_step(self, step: Any) -> None:
        """The block-diffusion dispatch: every occupied slot advances
        by `blocks_per_dispatch` whole blocks (`_diffuse_impl`), under
        the same phase spans as `_chunk_step`. `lm_pack` is the issue
        of the readback's host copy: the packing itself is part of the
        device program and has ONE shape, so nothing here can compile.
        Tokens are delivered a committed block at a time; a request
        joins at a dispatch's end and leaves after the block that meets
        its budget, whose surplus rows are dropped (the dispatch's
        later blocks of that slot are work nobody reads)."""
        df, b = self.diffusion, self.cfg.block_length
        r_n, s_n = self.blocks_per_dispatch, df.steps
        live = list(enumerate(self._slot_req))
        with TRACER.loop_span("lm_dispatch", step):
            (self.cache, self._blk_dev, self._pos_dev,
             packed) = self._diffuse_fn(
                self.params, self.cache, self._blk_dev, self._pos_dev,
                jnp.asarray(self.rid_vec),
            )
            self._fed()
        with TRACER.loop_span("lm_pack", step, arrays=1) as pack:
            packed.copy_to_host_async()
        with TRACER.loop_span("lm_readback", step) as readback:
            out = np.asarray(packed)
        self._drained("readback")
        _M_PACK.observe(pack.m1 - pack.m0)
        _M_READBACK.observe(readback.m1 - readback.m0)
        n = r_n * self.max_slots * b
        toks = out[:n].reshape(r_n, self.max_slots, b)
        fixed = out[n : 2 * n].reshape(r_n, self.max_slots, b)
        with TRACER.loop_span("lm_deliver", step) as deliver:
            delivered = retired = blocks = 0
            for slot, req in live:
                if req is None:
                    continue
                blocks += r_n
                for r in range(r_n):
                    if req.done:
                        break
                    new = fixed[r, slot] > 0  # 0 = the prompt's tail
                    take = min(int(new.sum()),
                               req.max_new_tokens - req.emitted)
                    req.fixed_at.extend(
                        int(f) for f in fixed[r, slot][new][:take])
                    req.deliver(toks[r, slot][new][:take])
                    req.emitted += take
                    delivered += take
                    if req.done:
                        req.beyond = (
                            [int(t) for t in toks[r, slot][new][take:]],
                            [int(f) for f in fixed[r, slot][new][take:]])
                if req.done:
                    self._retire(slot)
                    retired += 1
            deliver.label(tokens=delivered, retired=retired)
        _M_DELIVER.observe(deliver.m1 - deliver.m0)
        _M_FWD_DENOISE.inc(r_n * s_n)
        _M_FWD_COMMIT.inc(r_n)
        _M_FIXED.inc(delivered)
        _M_BLOCKS.inc(blocks)
        layers, e = self._routed
        if layers:
            lo, hi = self._held
            with TRACER.loop_span("lm_route", step):
                self._note_routing(step, np.stack(_routing_numbers(
                    out[2 * n :].reshape(-1, e), lo, hi), -1))
        step.label(forwards=r_n * (s_n + 1), tokens_fixed=delivered,
                   blocks_committed=blocks)
        self._finish_step(step, delivered, 0)
        self.forward_seconds = (
            time.monotonic() - step.m0) / (r_n * (s_n + 1))

    def _note_routing(self, step: Any, routed: np.ndarray) -> None:
        """A dispatch's routing into the counters and onto its `lm_step`
        span. `routed` [..., 5], a forward a layer: the assignments of
        occupied slots' tokens, those to held experts, the distinct
        experts they reach, the distinct held ones, the assignments to
        the busiest expert (`_chunk_impl` reduces its counts to these on
        the device; a diffusion dispatch reads the counts back whole).
        Every row is one call of an expert layer, so one first window;
        a sixth number is the windows that call ran past it."""
        routed = routed.reshape(-1, routed.shape[-1]).astype(np.float64)
        _M_MOE_WINDOWS_FIRST.inc(len(routed))
        if routed.shape[-1] > 5:
            _M_MOE_WINDOWS_FURTHER.inc(float(routed[:, 5].sum()))
        routed = routed[routed[:, 0] > 0]  # forwards that had a token
        if not len(routed):
            return
        total, held, touched, touched_held, busiest = routed.T[:5]
        load = busiest * self._routed[1] / total
        _M_MOE_HELD.inc(float(held.sum()))
        _M_MOE_ABSENT.inc(float((total - held).sum()))
        # one call a histogram a dispatch, whatever the steps x layers
        _M_MOE_TOUCHED.observe_many(touched)
        _M_MOE_TOUCHED_HELD.observe_many(touched_held)
        _M_MOE_LOAD.observe_many(load)
        step.label(
            experts_touched=round(float(touched.mean()), 3),
            experts_touched_held=round(float(touched_held.mean()), 3),
            expert_load_max=round(float(load.mean()), 3))

    def take_fixed_at(
        self, rids: Optional[Sequence[int]] = None
    ) -> Dict[int, Dict[str, Any]]:
        """Block diffusion: for retired requests (`rids`, or all),
        {"fixed_at": per generated token the denoising step (1..S) that
        fixed it, "beyond_budget": {"tokens", "fixed_at"} of the last
        block's positions past the budget (a budget is met by trimming
        the last block; with these the block can be rebuilt whole)};
        removed from the server as `take_done` removes the tokens.
        Empty for an autoregressive server."""
        keys = list(self._fixed_at) if rids is None else [
            r for r in rids if r in self._fixed_at]
        return {r: self._fixed_at.pop(r) for r in keys}

    def has_work(self) -> bool:
        """True while any request is queued or occupying a slot."""
        return bool(self._queue) or any(
            r is not None for r in self._slot_req
        )

    def take_done(self) -> Dict[int, np.ndarray]:
        """Drain finished requests: {rid: generated tokens}. The
        incremental form of run()'s result — LMDriver calls this after
        every step to deliver each batch's results the moment its last
        request retires, without waiting for the whole grid to drain.
        Deferred first tokens are flushed ONLY when a pending request
        has actually retired (a budget-1 request can retire at
        placement with its one token still on device): an
        unconditional flush would re-add the blocking placement-round
        readback the deferred-first protocol exists to remove — the
        driver calls take_done every loop iteration, right after
        step() defers the newly placed round's firsts."""
        if any(r.done for r in self._pending_first.values()):
            self._flush_firsts()
        out = {
            rid: np.asarray(r.out, np.int32)
            for rid, r in self._done.items()
        }
        self._done.clear()
        return out

    def run(
        self, rids: Optional[Sequence[int]] = None
    ) -> Dict[int, np.ndarray]:
        """Drive until every submitted request finishes; returns
        {rid: generated tokens}.

        With `rids`, drives until THOSE requests finish and returns
        (and removes) only them, leaving everything else in the done
        set. A caller sharing the server with an LMDriver (LMBackend's
        serial mode between driver tickets) must use this form: the
        bare drain would consume — and discard — results belonging to
        in-flight driver tickets, hanging their serve() callers."""
        if rids is None:
            while self.has_work():
                self.step()
            done = self.take_done()
            self._fed()  # nothing left to feed the device with
            return done
        want = set(rids)
        while (want - set(self._done)) and self.has_work():
            self.step()
        self._flush_firsts()  # a wanted budget-1 rid may have no step
        if not self.has_work():
            self._fed()
        out = {}
        for rid in want:
            r = self._done.pop(rid, None)
            if r is not None:
                out[rid] = np.asarray(r.out, np.int32)
        return out


@dataclasses.dataclass
class _Ticket:
    """One caller's batch of prompts inside the driver. `event` fires
    when every request in the ticket has finished (or on error)."""

    prompts: List[np.ndarray]
    max_new_tokens: Any  # int, or per-prompt sequence of ints
    event: threading.Event
    on_dispatch: Optional[Callable[[], None]] = None
    # per-prompt token-delivery callbacks (ingress streaming), passed
    # through to LMServer.submit_many
    on_token: Optional[Sequence[Optional[Callable[[int], None]]]] = None
    # per-prompt trace contexts, passed through likewise
    trace: Optional[Sequence[Optional[TraceContext]]] = None
    # monotonic time the caller queued the ticket: a ticket waits while
    # the driver thread is inside a decode dispatch, which no other
    # clock sees (`lm_submit`'s label `ticket_wait_s`)
    t_queued: float = dataclasses.field(default_factory=time.monotonic)
    rids: Optional[List[int]] = None
    remaining: int = 0
    results: Optional[Dict[int, np.ndarray]] = None
    # block diffusion: rid -> `LMServer.take_fixed_at`'s record
    fixed_at: Dict[int, Dict[str, Any]] = dataclasses.field(
        default_factory=dict)
    error: Optional[BaseException] = None


class LMDriver:
    """Thread-safe continuous-batching front door for ONE `LMServer`.

    The server itself is single-threaded mutable state; the round-3/4
    cluster LM path serialized co-located workers on a lock, so batch
    N+1's prompts could not enter the grid until batch N fully drained
    — every per-chunk readback ran serially and distributed LM serving
    sat far below the device's own continuous-batching rate.

    The driver fixes the structure, not the constants: ONE background
    thread owns the server; any number of serving tasks call
    `serve()` concurrently (each from its own `asyncio.to_thread`),
    and their prompts merge into the SAME slot grid. A new batch's
    prefills enter freed slots while earlier batches are still
    decoding (prefill-of-next overlapped with current drain), the
    per-chunk readbacks amortize over every request in flight, and
    each caller gets its results the moment its OWN requests retire —
    no drain barrier between batches.

    Exactness is unchanged: slots decode independently
    (`batched_decode_step` masks per-slot), so outputs remain
    identical to isolated `generate()` calls no matter how tickets
    interleave (the LMServer batching-exactness contract).

    This supersedes per-worker servers for co-located workers on one
    chip — separate grids would split the weight stream across
    programs instead of sharing it. On multi-host deployments each
    host runs its own backend+driver over its own chip(s), which is
    the "per-worker server" layout with the worker = the host.
    """

    def __init__(
        self,
        server: LMServer,
        server_lock: Optional[threading.Lock] = None,
    ):
        self.server = server
        # `server_lock` guards the RAW server against a caller that
        # also drives it directly (LMBackend's serial mode holds this
        # lock for a whole run(); a preempted serial decode keeps
        # running orphaned — the driver must not interleave with it
        # when a mode flip races an orphan)
        self._server_lock = server_lock or threading.Lock()
        self._cv = threading.Condition()
        self._incoming: List[_Ticket] = []
        self._owner: Dict[int, _Ticket] = {}  # rid -> ticket
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        # serving stats (read by bench/observability; driver thread
        # writes under _cv)
        self.steps = 0
        self.tickets_served = 0

    # -- caller side ---------------------------------------------------

    def serve(
        self,
        prompts: Sequence[np.ndarray],
        max_new_tokens,
        on_dispatch: Optional[Callable[[], None]] = None,
        on_token: Optional[
            Sequence[Optional[Callable[[int], None]]]
        ] = None,
        trace: Optional[Sequence[Optional[TraceContext]]] = None,
        fixed_at: Optional[List[Dict[str, Any]]] = None,
    ) -> List[np.ndarray]:
        """Blocking: decode `prompts`, return their completions in
        order. A caller that passes a list as `fixed_at` gets it
        filled, in the same order, with a block-diffusion server's
        record of the denoising step that fixed each token
        (`LMServer.take_fixed_at`). `max_new_tokens` is an int or a per-prompt sequence
        (passed through to submit_many). Safe from any thread.
        `on_dispatch` fires (on the DRIVER thread) the moment the
        ticket's prompts are submitted to the server — the caller's
        pipeline can start preparing its next batch from that point,
        not from completion. `on_token` (per-prompt callbacks, fired
        on the driver thread per delivered token) streams each
        request's tokens as they read back. `trace` (per-prompt trace
        contexts) hangs each request's `lm_request` span under the
        span that caused it."""
        t = _Ticket(
            prompts=[np.asarray(p, np.int32).reshape(-1) for p in prompts],
            max_new_tokens=max_new_tokens,
            event=threading.Event(),
            on_dispatch=on_dispatch,
            on_token=on_token,
            trace=trace,
        )
        with self._cv:
            if self._stop:
                raise RuntimeError("LMDriver is stopped")
            self._ensure_thread()
            self._incoming.append(t)
            self._cv.notify_all()
        t.event.wait()
        if t.error is not None:
            raise t.error
        assert t.results is not None and t.rids is not None
        if fixed_at is not None:
            fixed_at.extend(t.fixed_at.get(rid) for rid in t.rids)
        return [t.results[rid] for rid in t.rids]

    def stop(self) -> None:
        """Stop the driver thread (idempotent). In-flight tickets
        finish first; new serve() calls are rejected.

        If the thread has not drained when the join times out (e.g. a
        wedged device mid-chunk), the handle is KEPT and the
        timeout logged loudly: that thread still owns the server's
        slot grid, and dropping the reference would silently leak a
        live driver (and let a future restart interleave two drivers
        over one grid). A later stop() retries the join."""
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        t = self._thread
        if t is not None:
            t.join(timeout=60.0)
            if t.is_alive():
                log.error(
                    "LMDriver thread %s did not stop within 60s; "
                    "keeping the handle (it still owns the LMServer "
                    "slot grid — likely a wedged device dispatch)",
                    t.name,
                )
                return
            self._thread = None

    # -- driver thread -------------------------------------------------

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._loop, name="lm-driver", daemon=True
            )
            self._thread.start()

    def _loop(self) -> None:
        try:
            self._loop_inner()
        except BaseException as e:
            # a device error mid-step would otherwise kill this
            # thread silently and leave every serve() caller blocked
            # forever on its event — fail ALL in-flight and queued
            # tickets loudly, then stop accepting work
            with self._cv:
                self._stop = True
                pending = list(self._incoming)
                self._incoming = []
            owned = {id(t): t for t in self._owner.values()}
            self._owner.clear()
            for t in list(owned.values()) + pending:
                if t.error is None:
                    t.error = RuntimeError(f"LMDriver thread died: {e!r}")
                t.event.set()
            raise

    def _loop_inner(self) -> None:
        srv = self.server
        # the thread's time is tiled by loop spans: `lm_idle`,
        # `lm_submit`, `lm_step` (the server's) and, from the end of one
        # of them to the start of the next, `lm_turn`: finished requests
        # to their tickets, the tickets' events, the locks
        turn: Any = None

        def turned() -> None:
            if turn is not None:
                turn.end()

        while True:
            with self._cv:
                if not (self._incoming or srv.has_work() or self._stop):
                    turned()
                    srv._fed()  # no work is no exposure of the device
                    with TRACER.loop_span("lm_idle"):
                        while not (
                            self._incoming or srv.has_work() or self._stop
                        ):
                            self._cv.wait()
                    turn = TRACER.loop_span("lm_turn")
                if self._stop and not self._incoming and not srv.has_work():
                    turned()
                    return
                new = self._incoming
                self._incoming = []
            # server access happens only under _server_lock: a
            # lock-mode (serial) decode running orphaned after a
            # preemption must fully drain before the driver touches
            # the grid
            with self._server_lock:
                if new:
                    turned()
                    with TRACER.loop_span(
                        "lm_submit", tickets=len(new),
                        requests=sum(len(t.prompts) for t in new),
                    ) as span:
                        span.label(ticket_wait_s=round(sum(
                            span.m0 - t.t_queued for t in new), 6))
                        self._submit_tickets(new, span)
                    turn = TRACER.loop_span("lm_turn")
                if srv.has_work():
                    turned()
                    srv.step()
                    turn = TRACER.loop_span("lm_turn")
                    with self._cv:
                        self.steps += 1
                done = srv.take_done()
                fixed = srv.take_fixed_at(list(done))
            for rid, toks in done.items():
                t = self._owner.pop(rid, None)
                if t is None:
                    continue  # pre-driver submission via raw server API
                t.results[rid] = toks
                if rid in fixed:
                    t.fixed_at[rid] = fixed[rid]
                t.remaining -= 1
                if t.remaining == 0:
                    self.tickets_served += 1
                    t.event.set()

    def _submit_tickets(self, new: List[_Ticket], span: Any) -> None:
        """Hand the tickets taken this round to the server (under the
        server lock, inside the round's `lm_submit` span)."""
        srv = self.server
        for t in new:
            try:
                # validation failures reject the WHOLE ticket
                # before any of its prompts queue (submit_many
                # is atomic), so a bad prompt file can't leave
                # siblings decoding into a discarded result
                t.rids = srv.submit_many(
                    t.prompts, t.max_new_tokens,
                    on_token=t.on_token, trace=t.trace, parent=span,
                )
                t.remaining = len(t.rids)
                t.results = {}
                for rid in t.rids:
                    self._owner[rid] = t
                if t.remaining == 0:
                    t.event.set()
            except Exception as e:
                t.error = e
                t.event.set()
                continue
            if t.on_dispatch is not None:
                try:
                    t.on_dispatch()
                except Exception as e:
                    # a pipeline hint, never a decode error
                    log.warning("on_dispatch hook failed: %r", e)
