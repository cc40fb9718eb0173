"""End-to-end distributed request tracing: spans, context propagation,
per-node flight recorder, cluster collection, tail attribution.

The metrics registry (observability.py) answers "how is the cluster
doing" in aggregate — exactly the coordinator console the reference
paper ships. What it cannot answer is "where did THIS request's time
go": a p99 outlier or a deadline miss crosses the front door, the
coordinator's batch former, the scheduler, a worker's fetch/infer/put
pipeline, and (for disaggregated LM serving) a prefill peer and a KV
handoff — four or more processes, none of which holds the whole story.
This module is the per-request causality layer:

- **Span** — one named, wall-clocked interval on one node, belonging
  to a trace (``trace_id``) under a parent span. Span NAMES are a
  closed registry (``SPAN_NAMES``): the stage names the attribution
  table reports are the same constants the instrumentation emits, and
  tools/dmllint.py (rule ``drift-span-names``) fails the build when a
  ``start_span("...")`` call site uses a name this registry doesn't
  declare — stage names cannot silently drift.
- **TraceContext** — the (trace_id, parent span, sampled) triple that
  rides the wire next to ``slo_class``: REQUEST_SUBMIT mints it at
  admission (seeded head-sampling decision), the formed batch carries
  one context per request through ``ingress_submit`` → scheduler →
  WORKER_TASK_REQUEST → LM_PREFILL_REQUEST → back out via
  REQUEST_DONE, so one trace stitches the full cross-node span tree.
- **Flight recorder** (``Tracer``) — a bounded ring buffer of finished
  spans per process, plus ALWAYS-ON capture (regardless of the head
  sampling decision) of the slowest-K request roots and of every span
  carrying a tail-exemplar event (``deadline_miss`` / ``shed`` /
  ``requeue`` / ``fallback``): the exemplars that explain the tail are
  never sampled away.
- **Loop spans** (``Tracer.loop_span``) — spans of the SERVE LOOP,
  whose work belongs to no single request: one decode dispatch and its
  phases on the LM serving thread, a worker's batch stages, a store
  operation. Always on, never sampled, kept in a bounded ring of their
  own (a burst of requests cannot evict them, nor they a request's
  spans), timed on ``time.monotonic()`` and placed on the wall clock by
  ONE offset taken when the ``Tracer`` is made (``wall_of``), and
  entered into the JAX profiler as ``TraceAnnotation("dml.<name>")`` so
  that a device trace shows them on the profiler's own clock beside
  the device's operations. ``summary()`` folds the ring per name.
- **Parts and the device account** (``PARTS``, ``read_profile``) — the
  model's programs name their parts with ``jax.named_scope`` (embedding,
  attention projections, cache attention, expert routing, ... one table
  here, applied in inference/generate.py and inference/lm_server.py),
  so a profiler trace can be read in the program's own words:
  ``read_profile`` folds one ``.xplane.pb`` into device-busy seconds by
  (program, part) and device-idle seconds by the serving thread's span
  open in each gap. CLI: ``profile trace read [dir]``.
- **TRACE_PULL** (cluster/node.py) — leader aggregation of every
  node's recorder with the same tier-by-tier datagram degradation as
  METRICS_PULL; ``assemble_traces`` stitches the pulled spans into
  per-trace trees and ``chrome_trace`` exports them for
  ``chrome://tracing`` / Perfetto. CLI: ``trace [dump|pull|chrome]``.
- **Attribution** — ``stage_breakdown`` folds one trace's spans into
  per-stage seconds; ``ingress/loadgen.summarize`` joins completions
  against these to report where the p99 cohort's time went
  (queue-wait vs formation vs dispatch vs prefill vs handoff vs
  decode vs result-return).

Overhead discipline: every recorder update is a host-side O(1) dict /
deque operation outside any jitted device step (same contract as the
metrics registry), sampling is decided ONCE at admission, and an
unsampled request's spans are recorded only if they end up tail
exemplars.

In-process simulations run many nodes in ONE process sharing this
module-global ``TRACER`` (like ``observability.METRICS``); spans carry
the recording node's name and collection dedupes by span id, so the
sim's cluster trace equals the shared recorder instead of multiplying
by the node count.
"""

from __future__ import annotations

import contextvars
import glob
import hashlib
import itertools
import os
import re
import secrets
import struct
import sys
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .observability import METRICS

# ----------------------------------------------------------------------
# span-name registry (lint-enforced: dmllint rule drift-span-names)
# ----------------------------------------------------------------------

#: the root span of a request's trace: admission -> terminal
SPAN_ROOT = "request"

#: Every name ``start_span(...)`` / ``loop_span(...)`` /
#: ``loop_record(...)`` may emit, and therefore every stage the
#: attribution table can report. tools/dmllint.py cross-checks all
#: literal call sites in the tree against
#: this tuple — add the name HERE first, or the build fails. Keep the
#: comment on each line: it is the one place the stage vocabulary is
#: documented.
# plain assignment (no annotation): dmllint's _module_const_strs reads
# top-level Assign nodes, and this tuple IS its machine contract
SPAN_NAMES = (
    "request",     # root: admission -> terminal on the router
    "admission",   # REQUEST_SUBMIT handling (sampling, SLO, shed check)
    "formation",   # admission -> batch dispatch (the queue wait)
    "dispatch",    # ingress_submit -> WORKER_TASK_REQUEST send
    "fetch",       # worker: store replica fetch + host decode
    "infer",       # worker: backend infer call (device forward)
    "prefill",     # prefill-role member: chunked prompt prefill
    "handoff",     # decode primary: prefill RPC + KV slab pull
    "decode",      # decode side of a disaggregated LM batch
    "put",         # worker: output write + replicated store PUT
    "store_put",   # replicated store PUT under a request's trace
    "store_get",   # replicated store GET under a request's trace
    "result",      # job completion -> REQUEST_DONE push
    "marker",      # zero-duration exemplar marker (note_exemplar)
    # -- loop spans (Tracer.loop_span: serve loop, not per request) --
    "worker_fetch",      # worker: a batch's replica fetch + host decode
    "worker_infer",      # worker: a batch's backend call (label joined)
    "worker_put",        # worker: a batch's output write + store PUT
    "store_op_put",      # replicated store PUT, every one (untraced too)
    "store_op_get",      # replicated store GET, every one (untraced too)
    "lm_weights_resident",  # LMServer built: the tree cast to its resident form
    "lm_idle",           # LM driver thread waiting with no work
    "lm_submit",         # LM driver: submit_many of the tickets taken this round
    "lm_step",           # one decode dispatch, entry to exit (label waiting)
    "lm_dispatch",       # enqueue of the chunk (or propose/verify) program
    "lm_pack",           # issuing the packed readback's eager concatenate
    "lm_readback",       # the blocking np.asarray: host waits for device
    "lm_deliver",        # first tokens + req.deliver callbacks + retirements
    "lm_route",          # a dispatch's routing numbers into the moe_* counters
    "lm_place",          # _place_waiting: free slots take queued requests
    "lm_prefill_group",  # one bucket group's build/prefill/insert/sample/merge
    "lm_request",        # LM request: submit -> last token on the host
    "lm_turn",           # LM driver between two dispatches: results, locks
    "lm_exposed",        # device left with nothing queued: a blocking wait's
                         # return -> the next program enqueued (label after)
)

#: loop-span labels `Tracer.summary` (``profile spans``) averages beside
#: the walls: `worker_infer`'s ``joined`` (1 when the batch entered its
#: backend while another batch of the worker was still in inference)
#: and `lm_step`'s ``waiting`` (requests queued without a slot as the
#: dispatch was issued) say whether a worker's second batch keeps the
#: slot grid fed
SUMMARY_LABELS = ("joined", "waiting")

#: The parts of a model's programs: the `jax.named_scope` names that
#: inference/generate.py (`part`) and the programs of
#: inference/lm_server.py put around each part's work, and therefore
#: every part `read_profile` can report device time under. Scopes are
#: metadata: they change no compiled program (tests/test_parts.py holds
#: the lowered text to that). The two Pallas kernels carry their names
#: through `pl.pallas_call(name=)`, inside `attn_core`: the innermost
#: name of this table on an operation's scope path is its part.
PARTS = (
    "embed",             # token ids -> rows of the embedding table
    "attn_proj",         # attention's norms, projections, rope, output proj
    "attn_core",         # attention over the cache / the call's rows: glue
    "decode_attention",  # ... the cache kernel (ops/decode_attention.py)
    "flash_attention",   # ... the prefill kernel (ops/flash_attention.py)
    "cache_write",       # rows into the slot grid / a prefill's rows laid out
    "mlp",               # dense feed-forward, its norm included
    "moe_route",         # expert layer: norm, scores, top-k, sort, counts
    "moe_experts",       # expert layer: gathers, grouped matmuls, scatter-add
    "moe_shared",        # expert layer: the shared expert
    "ssm_proj",          # state-space mixer: norm, projections, conv, gate
    "ssm_scan",          # state-space mixer: the recurrence (chunks, a step)
    "conv_proj",         # gated short convolution: norm, in_proj, gates, out_proj
    "conv_mix",          # ... the depthwise convolution and its window's update
    "head",              # final norm, logits, argmax / sample
    "diffuse_select",    # block diffusion: confidences, ranks, fix, commit
    "insert",            # a prefilled row into its slot; cur/pos/firsts merges
    "pack",              # the packed readback's concatenate
)

#: `read_profile`'s names for what carries no name of the program's:
#: device time of an operation under no part, idle time in no span
UNSCOPED = "unscoped"
UNATTRIBUTED = "unattributed"

#: the loop ring's size: ten minutes at the chat cell's rate (about 10
#: spans a decode dispatch x 3.7 dispatches/s = 22,200; PERF.md §5)
LOOP_SPAN_BUDGET = 32768

#: span events that force always-on exemplar capture: any span ending
#: with one of these pins its whole trace in the recorder regardless
#: of the head sampling decision — these are the requests that explain
#: the tail, and a tail you sampled away cannot be attributed
EXEMPLAR_EVENTS: Tuple[str, ...] = (
    "deadline_miss", "shed", "requeue", "fallback",
)

_M_SPANS = METRICS.counter(
    "tracing_spans_total",
    "finished spans observed by the flight recorder, by sampled=")
_M_DROPPED = METRICS.counter(
    "tracing_spans_dropped_total",
    "spans evicted from a flight-recorder ring: sampled request spans "
    "(no label) and serve-loop spans (ring=loop)")
_M_DROPPED_LOOP = _M_DROPPED.labels(ring="loop")
_M_EXEMPLARS = METRICS.counter(
    "tracing_exemplars_total",
    "tail-exemplar span captures, by kind= (deadline_miss|shed|...)")


# ----------------------------------------------------------------------
# context + span
# ----------------------------------------------------------------------


@dataclass(frozen=True)
class TraceContext:
    """What propagates across a hop: which trace, under which parent
    span, and whether the head decision sampled it. The wire form is
    a three-key dict (``t``/``p``/``s``) small enough to ride every
    batch and prefill frame next to ``slo_class``; ``key`` optionally
    binds the context to its request's input file (``f``) so batch-
    level code can route per-request contexts without a side table."""

    trace_id: str
    span_id: str = ""
    sampled: bool = True
    key: str = ""

    def to_wire(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {"t": self.trace_id, "p": self.span_id,
                             "s": 1 if self.sampled else 0}
        if self.key:
            d["f"] = self.key
        return d

    @staticmethod
    def from_wire(d: Any) -> Optional["TraceContext"]:
        """Tolerant decode: byzantine/garbled context degrades to 'no
        trace', never to a handler exception."""
        if not isinstance(d, dict) or not isinstance(d.get("t"), str):
            return None
        return TraceContext(
            trace_id=d["t"],
            span_id=str(d.get("p", "")),
            sampled=bool(d.get("s", 1)),
            key=str(d.get("f", "")),
        )


class Span:
    """One live span; finished (and recorded) exactly once via
    ``end()`` or the context-manager exit."""

    __slots__ = (
        "trace_id", "span_id", "parent_id", "name", "node", "sampled",
        "t0", "t1", "labels", "events", "_tracer",
    )

    def __init__(
        self, tracer: "Tracer", name: str, trace_id: str,
        parent_id: str, node: str, sampled: bool,
        t0: Optional[float] = None,
        labels: Optional[Dict[str, Any]] = None,
        span_id: Optional[str] = None,
    ):
        self._tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id or tracer._new_span_id()
        self.parent_id = parent_id
        self.node = node
        self.sampled = sampled
        self.t0 = time.time() if t0 is None else float(t0)
        self.t1: Optional[float] = None
        self.labels = dict(labels) if labels else {}
        self.events: List[List[Any]] = []

    def ctx(self) -> TraceContext:
        """Context for children of THIS span."""
        return TraceContext(self.trace_id, self.span_id, self.sampled)

    def event(self, name: str, ts: Optional[float] = None) -> None:
        self.events.append([name, round(time.time() if ts is None
                                        else ts, 6)])

    def label(self, **labels: Any) -> None:
        self.labels.update(labels)

    def end(self, t1: Optional[float] = None) -> None:
        if self.t1 is not None:
            return  # idempotent: error paths may double-close
        self.t1 = time.time() if t1 is None else float(t1)
        self._tracer._record(self)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.end()

    @property
    def duration(self) -> float:
        return (self.t1 or time.time()) - self.t0

    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "tid": self.trace_id, "sid": self.span_id,
            "par": self.parent_id, "name": self.name, "node": self.node,
            "t0": round(self.t0, 6),
            "t1": round(self.t1 if self.t1 is not None else self.t0, 6),
        }
        if self.labels:
            d["lb"] = {k: v for k, v in self.labels.items()}
        if self.events:
            d["ev"] = [list(e) for e in self.events]
        return d


#: ``jax.profiler.TraceAnnotation``, bound the first time a loop span
#: is opened in a process that has JAX loaded (never imported from
#: here: a control-plane node without JAX records the span and skips
#: the annotation)
_TRACE_ANNOTATION: Any = None


def _annotate(name: str) -> Any:
    """An entered profiler annotation ``dml.<name>``, or None where
    JAX is not loaded. While no profiler trace runs this is one flag
    test inside ``TraceMe``; while one runs, the span is in the same
    ``.xplane.pb`` as the device's operations, on the profiler's own
    clock."""
    global _TRACE_ANNOTATION
    cls = _TRACE_ANNOTATION
    if cls is None:
        if "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation as cls

        _TRACE_ANNOTATION = cls
    ann = cls("dml." + name)
    ann.__enter__()
    return ann


class LoopSpan:
    """One live serve-loop span (``Tracer.loop_span``): opened where it
    is made, recorded exactly once by ``end()`` or the context-manager
    exit. ``m0``/``m1`` are ``time.monotonic()`` readings; the recorded
    ``t0``/``t1`` are those plus the tracer's one wall offset. Has the
    ``trace_id``/``span_id`` pair a child span takes as its parent."""

    __slots__ = (
        "trace_id", "span_id", "parent_id", "name", "node", "m0", "m1",
        "labels", "events", "_tracer", "_ann",
    )

    def __init__(
        self, tracer: "Tracer", name: str, parent: Any, node: str,
        labels: Dict[str, Any], annotate: bool = True,
    ):
        self._tracer = tracer
        self.name = name
        # `parent` is whatever caused the span (a LoopSpan, a Span, a
        # TraceContext: each has the pair); without one it roots a trace
        self.trace_id = (tracer.new_trace_id() if parent is None
                         else parent.trace_id)
        self.parent_id = "" if parent is None else parent.span_id
        self.span_id = tracer._new_span_id()
        self.node = node
        self.labels = labels
        self.events: List[List[Any]] = []
        self.m1: Optional[float] = None
        self.m0 = time.monotonic()
        self._ann = _annotate(name) if annotate else None

    def label(self, **labels: Any) -> None:
        self.labels.update(labels)

    def end(self) -> None:
        if self.m1 is not None:
            return  # idempotent, like Span.end
        self.m1 = time.monotonic()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._tracer._record_loop(self)

    def __enter__(self) -> "LoopSpan":
        return self

    def __exit__(self, *exc) -> None:
        self.end()

#: batch-scoped contexts for code that cannot thread them through its
#: call signature (store put/get under a worker's fetch, the LM group
#: backends' prefill/handoff/decode internals): the service sets this
#: around a batch's backend call; asyncio tasks and to_thread hops
#: inherit it via contextvars copy semantics
CURRENT_CTXS: "contextvars.ContextVar[Tuple[TraceContext, ...]]" = (
    contextvars.ContextVar("dml_tpu_trace_ctxs", default=())
)


def current_ctxs() -> Tuple[TraceContext, ...]:
    """The batch's propagated trace contexts, sampled ones only (the
    common gate ordinary span-recording sites want)."""
    return tuple(c for c in CURRENT_CTXS.get() if c.sampled)


def current_all_ctxs() -> Tuple[TraceContext, ...]:
    """Every propagated context, sampled or not — for the ALWAYS-ON
    exemplar paths (a handoff fallback on an unsampled request must
    still be captured; that is the whole point of exemplars)."""
    return tuple(CURRENT_CTXS.get())


# ----------------------------------------------------------------------
# the flight recorder
# ----------------------------------------------------------------------


class Tracer:
    """Process-wide span recorder: seeded head sampling, a bounded
    ring of finished sampled spans, and always-on slowest-K + tail
    exemplar capture. Thread-safe (backends finish spans on decode
    threads)."""

    def __init__(
        self,
        sample_rate: float = 0.1,
        seed: int = 0,
        span_budget: int = 4096,
        slow_k: int = 32,
        exemplar_traces: int = 256,
        loop_budget: int = LOOP_SPAN_BUDGET,
    ):
        self._lock = threading.Lock()
        self._salt = secrets.token_hex(3)
        self._span_counter = itertools.count(1)
        self._trace_counter = itertools.count(1)
        # the ONE mapping from the loop spans' clock to the wall clock
        # the request spans and the profiler's host plane stamp: taken
        # once, so a wall clock that is stepped later can neither shrink
        # nor stretch a span, and every loop span lines up with every
        # other
        self._wall_offset = time.time() - time.monotonic()
        self.configure(
            sample_rate=sample_rate, seed=seed, span_budget=span_budget,
            slow_k=slow_k, exemplar_traces=exemplar_traces,
            loop_budget=loop_budget,
        )

    def configure(
        self,
        sample_rate: Optional[float] = None,
        seed: Optional[int] = None,
        span_budget: Optional[int] = None,
        slow_k: Optional[int] = None,
        exemplar_traces: Optional[int] = None,
        loop_budget: Optional[int] = None,
    ) -> None:
        """(Re)configure knobs; omitted arguments keep their value.
        Changing ``span_budget`` (or ``loop_budget``) re-bounds that
        ring, carrying over the newest spans that still fit."""
        with self._lock:
            if sample_rate is not None:
                self.sample_rate = min(1.0, max(0.0, float(sample_rate)))
            if seed is not None:
                self.seed = int(seed)
            if span_budget is not None:
                self.span_budget = max(16, int(span_budget))
                old = list(getattr(self, "_ring", ()))
                self._ring: "deque[Dict[str, Any]]" = deque(
                    old[-self.span_budget:], maxlen=self.span_budget
                )
            if slow_k is not None:
                self.slow_k = max(1, int(slow_k))
                self._slow: List[Tuple[float, Dict[str, Any]]] = list(
                    getattr(self, "_slow", ())
                )[: self.slow_k]
            if exemplar_traces is not None:
                self.max_exemplar_traces = max(4, int(exemplar_traces))
                self._exemplars: "OrderedDict[str, List[Dict[str, Any]]]" \
                    = OrderedDict(getattr(self, "_exemplars", ()))
            if loop_budget is not None:
                self.loop_budget = max(16, int(loop_budget))
                old = list(getattr(self, "_loop_ring", ()))
                self._loop_ring: "deque[Dict[str, Any]]" = deque(
                    old[-self.loop_budget:], maxlen=self.loop_budget
                )
            if not hasattr(self, "dropped"):
                self.dropped = 0
                self.peak_spans = 0
                self.recorded = 0
                self.loop_dropped = 0
                self.loop_recorded = 0

    # -- identity + sampling ------------------------------------------

    def _new_span_id(self) -> str:
        return f"s{self._salt}{next(self._span_counter):x}"

    def new_trace_id(self) -> str:
        return f"t{self._salt}{next(self._trace_counter):x}"

    def head_sample(self, trace_id: str) -> bool:
        """Deterministic seeded head decision: the same (seed,
        trace_id) pair samples identically on every node and every
        run — the property the bench's replayed traces rely on."""
        if self.sample_rate >= 1.0:
            return True
        if self.sample_rate <= 0.0:
            return False
        h = hashlib.blake2b(
            f"{self.seed}:{trace_id}".encode(), digest_size=8
        ).digest()
        return int.from_bytes(h, "big") < self.sample_rate * 2.0 ** 64

    # -- span lifecycle -----------------------------------------------

    def start_span(
        self,
        name: str,
        ctx: Optional[TraceContext] = None,
        *,
        trace_id: Optional[str] = None,
        parent_id: str = "",
        node: str = "",
        sampled: Optional[bool] = None,
        t0: Optional[float] = None,
        labels: Optional[Dict[str, Any]] = None,
        span_id: Optional[str] = None,
    ) -> Span:
        """Open a span. ``ctx`` supplies trace/parent/sampled in one
        argument (the propagated-hop form); the keyword triple is the
        root-creation form. ``span_id`` pins the id explicitly — the
        promoted router reconstructs an adopted request's ROOT under
        its relayed original id, so spans the dead leader recorded
        against it still resolve their parent (no orphans across a
        failover). Names MUST come from ``SPAN_NAMES`` — dmllint
        cross-checks every literal call site."""
        if ctx is not None:
            trace_id = ctx.trace_id
            parent_id = ctx.span_id
            if sampled is None:
                sampled = ctx.sampled
        if trace_id is None:
            trace_id = self.new_trace_id()
        return Span(
            self, name, trace_id, parent_id, node,
            self.head_sample(trace_id) if sampled is None else sampled,
            t0=t0, labels=labels, span_id=span_id,
        )

    # -- loop spans ---------------------------------------------------

    def wall_of(self, monotonic_s: float) -> float:
        """Where a ``time.monotonic()`` reading lies on the spans' wall
        clock (``t0``/``t1``): the reading plus the one offset taken
        when this tracer was made. A reader with a window in monotonic
        seconds maps it through here and guesses nothing."""
        return monotonic_s + self._wall_offset

    def loop_span(
        self, name: str, parent: Any = None, *, node: str = "",
        **labels: Any,
    ) -> LoopSpan:
        """Open a serve-loop span (use as a context manager, or call
        ``end()``). ``parent`` is whatever caused it — a ``LoopSpan``,
        a ``Span`` or a ``TraceContext`` — and gives the trace id and
        the parent span id; without one the span roots a new trace, so
        all spans of one decode dispatch share the dispatch's trace id.
        Always recorded (no sampling), into the loop ring. Names MUST
        come from ``SPAN_NAMES`` (dmllint cross-checks every literal
        call site). Budget: under 20 us of host with no profiler
        running — nothing per token may call this."""
        return LoopSpan(self, name, parent, node, labels)

    def loop_record(
        self, name: str, m0: float, m1: float, parent: Any = None, *,
        node: str = "",
        events: Sequence[Tuple[str, float]] = (),
        **labels: Any,
    ) -> None:
        """Record a loop span after the fact from two monotonic
        readings (an interval that many dispatches overlap, like an LM
        request's life in the grid: it is no stack frame of the serve
        loop, so it carries no profiler annotation). ``events`` are
        (name, monotonic seconds) instants inside it."""
        span = LoopSpan(self, name, parent, node, labels, annotate=False)
        span.m0, span.m1 = float(m0), float(m1)
        span.events = [[n, float(m)] for n, m in events]
        self._record_loop(span)

    def _record_loop(self, span: LoopSpan) -> None:
        off = self._wall_offset
        d: Dict[str, Any] = {
            "tid": span.trace_id, "sid": span.span_id,
            "par": span.parent_id, "name": span.name, "node": span.node,
            "t0": round(span.m0 + off, 6), "t1": round(span.m1 + off, 6),
            "loop": 1,
        }
        if span.labels:
            d["lb"] = span.labels
        if span.events:
            d["ev"] = [[n, round(m + off, 6)] for n, m in span.events]
        with self._lock:
            self.loop_recorded += 1
            if len(self._loop_ring) == self.loop_budget:
                self.loop_dropped += 1
                _M_DROPPED_LOOP.inc()
            self._loop_ring.append(d)

    def loop_spans(self, name: Optional[str] = None) -> List[Dict[str, Any]]:
        """The loop ring, oldest first (``name`` filters)."""
        with self._lock:
            rows = list(self._loop_ring)
        if name is None:
            return rows
        return [d for d in rows if d["name"] == name]

    def summary(self) -> Dict[str, Dict[str, float]]:
        """count / total / mean / max seconds per loop-span name over
        the ring (the CLI's ``profile spans``), and the mean of each
        ``SUMMARY_LABELS`` label over the spans that carry it."""
        acc: Dict[str, List[float]] = {}
        lab: Dict[str, Dict[str, List[float]]] = {}
        for d in self.loop_spans():
            acc.setdefault(d["name"], []).append(d["t1"] - d["t0"])
            for k in SUMMARY_LABELS:
                if k in d.get("lb", ()):
                    lab.setdefault(d["name"], {}).setdefault(
                        k, []).append(float(d["lb"][k]))
        return {
            name: {
                "count": float(len(xs)), "total_s": sum(xs),
                "mean_s": sum(xs) / len(xs), "max_s": max(xs),
                **{f"{k}_mean": sum(v) / len(v)
                   for k, v in lab.get(name, {}).items()},
            }
            for name, xs in sorted(acc.items())
        }

    def _record(self, span: Span) -> None:
        d = span.to_dict()
        exemplar_kinds = [
            e[0] for e in span.events if e[0] in EXEMPLAR_EVENTS
        ]
        with self._lock:
            self.recorded += 1
            _M_SPANS.inc(sampled="yes" if span.sampled else "no")
            if span.sampled:
                if len(self._ring) == self.span_budget:
                    self.dropped += 1
                    _M_DROPPED.inc()
                self._ring.append(d)
                self.peak_spans = max(self.peak_spans, len(self._ring))
            # always-on slowest-K request roots (head sampling must
            # not be able to hide the slowest requests in the fleet)
            if span.name == SPAN_ROOT:
                dur = d["t1"] - d["t0"]
                self._slow.append((dur, d))
                self._slow.sort(key=lambda x: -x[0])
                del self._slow[self.slow_k:]
            for kind in exemplar_kinds:
                _M_EXEMPLARS.inc(kind=kind)
            if exemplar_kinds:
                self._pin_trace_locked(span.trace_id, d)

    def _pin_trace_locked(self, trace_id: str, d: Dict[str, Any]) -> None:
        spans = self._exemplars.get(trace_id)
        if spans is None:
            spans = self._exemplars[trace_id] = []
            # retroactively pin what the ring already holds for this
            # trace: an exemplar's earlier spans must survive eviction
            spans.extend(
                s for s in self._ring if s["tid"] == trace_id
            )
            while len(self._exemplars) > self.max_exemplar_traces:
                self._exemplars.popitem(last=False)
        if all(s["sid"] != d["sid"] for s in spans):
            spans.append(d)

    def note_exemplar(self, ctx: Optional[TraceContext], kind: str,
                      node: str = "", labels: Optional[Dict[str, Any]]
                      = None) -> None:
        """Record a zero-duration exemplar marker for ``ctx``'s trace
        (kind must be in ``EXEMPLAR_EVENTS``): the requeue/shed call
        sites have no surrounding interval worth a timed span, but the
        trace must still be pinned and the event must still show in
        the tree."""
        if ctx is None:
            return
        t = time.time()
        s = Span(self, "marker", ctx.trace_id, ctx.span_id, node, True,
                 t0=t, labels=labels)
        s.event(kind, t)
        s.end(t)

    # -- collection ----------------------------------------------------

    def dump(
        self,
        trace_ids: Optional[Iterable[str]] = None,
        max_spans: Optional[int] = None,
        strip: bool = False,
    ) -> List[Dict[str, Any]]:
        """Finished spans this node holds: the ring, the slowest-K
        roots, every pinned exemplar trace and the loop ring, deduped
        by span id, newest-last. ``trace_ids`` filters; ``max_spans``
        keeps the NEWEST — except exemplar-trace spans, which survive
        the cut first (the recorder pinned them against ring eviction;
        a collection cap must not un-pin them, or a deadline miss early
        in a long run loses exactly the trace that explains it). Under
        a cap the two rings stay apart as they do in memory: loop spans
        take at most half of it unless the request spans leave more.
        ``strip`` drops labels/events (the datagram-degraded form)."""
        want = set(trace_ids) if trace_ids is not None else None
        with self._lock:
            rows = list(self._ring)
            rows.extend(d for _, d in self._slow)
            for spans in self._exemplars.values():
                rows.extend(spans)
            rows.extend(self._loop_ring)
            pinned_tids = set(self._exemplars)
        seen: set = set()
        out: List[Dict[str, Any]] = []
        for d in rows:
            if d["sid"] in seen:
                continue
            if want is not None and d["tid"] not in want:
                continue
            seen.add(d["sid"])
            out.append(d)
        out.sort(key=lambda d: (d["t0"], d["sid"]))
        if max_spans is not None and len(out) > max_spans:
            loops = [d for d in out if "loop" in d]
            reqs = [d for d in out if "loop" not in d]
            n_loop = min(len(loops),
                         max(max_spans // 2, max_spans - len(reqs)))
            room = max_spans - n_loop
            ex = [d for d in reqs if d["tid"] in pinned_tids]
            if len(ex) >= room:
                reqs = ex[len(ex) - room:]
            else:
                rest = [d for d in reqs if d["tid"] not in pinned_tids]
                reqs = rest[len(rest) - (room - len(ex)):] + ex
            out = reqs + loops[len(loops) - n_loop:]
            out.sort(key=lambda d: (d["t0"], d["sid"]))
        if strip:
            out = [
                {k: v for k, v in d.items() if k not in ("lb", "ev")}
                for d in out
            ]
        return out

    def exemplar_trace_ids(self, kind: Optional[str] = None) -> List[str]:
        """Pinned exemplar traces, oldest first. ``kind`` filters to
        traces holding at least one span with that event (the signal
        plane attaches the freshest ``deadline_miss`` exemplar to a
        deadline-burn alert, not merely a recent shed)."""
        with self._lock:
            if kind is None:
                return list(self._exemplars)
            return [
                tid for tid, spans in self._exemplars.items()
                if any(
                    e[0] == kind
                    for s in spans for e in s.get("ev", ())
                )
            ]

    def stats(self) -> Dict[str, Any]:
        """Flight-recorder accounting (the bench's budget verdict):
        the ring NEVER exceeds ``span_budget`` by construction;
        ``peak_spans`` records the high-water mark so the artifact can
        prove it."""
        with self._lock:
            return {
                "span_budget": self.span_budget,
                "spans": len(self._ring),
                "peak_spans": self.peak_spans,
                "dropped": self.dropped,
                "recorded": self.recorded,
                "slow_k": self.slow_k,
                "slow_held": len(self._slow),
                "exemplar_traces": len(self._exemplars),
                "sample_rate": self.sample_rate,
                "within_budget": self.peak_spans <= self.span_budget,
                "loop_budget": self.loop_budget,
                "loop_spans": len(self._loop_ring),
                "loop_dropped": self.loop_dropped,
                "loop_recorded": self.loop_recorded,
            }

    def reset(self) -> None:
        """Drop every recorded span + counters (tests/bench phases);
        configuration survives."""
        with self._lock:
            self._ring.clear()
            self._loop_ring.clear()
            self._slow = []
            self._exemplars = OrderedDict()
            self.dropped = 0
            self.peak_spans = 0
            self.recorded = 0
            self.loop_dropped = 0
            self.loop_recorded = 0


#: the process-wide recorder every subsystem writes into
TRACER = Tracer()


# ----------------------------------------------------------------------
# assembly + attribution + export
# ----------------------------------------------------------------------


def merge_span_dumps(
    dumps: Sequence[Sequence[Dict[str, Any]]],
) -> List[Dict[str, Any]]:
    """Fold per-node dumps into one deduped span list (in-process sims
    share one recorder, so every node returns the same spans — span
    ids make the dedupe exact; real deployments dedupe nothing)."""
    seen: set = set()
    out: List[Dict[str, Any]] = []
    for dump in dumps:
        for d in dump:
            sid = d.get("sid")
            if not isinstance(sid, str) or sid in seen:
                continue
            seen.add(sid)
            out.append(d)
    out.sort(key=lambda d: (d.get("t0", 0.0), d.get("sid", "")))
    return out


def assemble_traces(
    spans: Sequence[Dict[str, Any]],
) -> Dict[str, List[Dict[str, Any]]]:
    """Group a span list by trace id, each trace's spans in start
    order (the stitched cross-node tree; parents sort before their
    children because a child starts after its parent)."""
    out: Dict[str, List[Dict[str, Any]]] = {}
    for d in spans:
        tid = d.get("tid")
        if isinstance(tid, str):
            out.setdefault(tid, []).append(d)
    for rows in out.values():
        rows.sort(key=lambda d: (d.get("t0", 0.0), d.get("sid", "")))
    return out


def trace_covers(spans: Sequence[Dict[str, Any]],
                 stages: Sequence[str]) -> bool:
    """Whether one trace's spans include every named stage (the
    acceptance contract for the stitched disaggregated-path trace)."""
    have = {d.get("name") for d in spans}
    return all(s in have for s in stages)


def stage_breakdown(spans: Sequence[Dict[str, Any]]) -> Dict[str, float]:
    """Per-stage seconds for ONE trace: wall duration summed by span
    name, root span excluded (it IS the e2e). Batch-shared spans (a
    worker's fetch covers every request in the batch) count their full
    duration — the request waited that long regardless of who shared
    the ride — and nested detail spans (store_put under fetch) are
    reported under their own name, so stages are not disjoint by
    construction; the attribution table reads the top-level stage
    names."""
    out: Dict[str, float] = {}
    for d in spans:
        name = d.get("name")
        if name == SPAN_ROOT or not isinstance(name, str):
            continue
        dur = max(0.0, float(d.get("t1", 0.0)) - float(d.get("t0", 0.0)))
        out[name] = out.get(name, 0.0) + dur
    return out


def trace_e2e(spans: Sequence[Dict[str, Any]]) -> Optional[float]:
    """Root-span duration of one trace, if the root was recorded."""
    for d in spans:
        if d.get("name") == SPAN_ROOT:
            return max(0.0, float(d.get("t1", 0.0)) - float(d.get("t0", 0.0)))
    return None


def chrome_trace(spans: Sequence[Dict[str, Any]]) -> Dict[str, Any]:
    """Chrome ``chrome://tracing`` / Perfetto JSON: one complete
    ('X') event per span — pid = recording node, tid = trace — plus an
    instant ('i') event per span event. Serve-loop spans that root a
    trace of their own (a decode dispatch and its phases) share ONE
    row per node, below the request rows, so a sampled request shows
    above the dispatches that served it; a loop span under a request's
    trace (``lm_request``) stays in that request's row. Times in
    microseconds as the format demands."""
    nodes = sorted({str(d.get("node", "")) for d in spans})
    pid_of = {n: i + 1 for i, n in enumerate(nodes)}
    req_tids = {str(d.get("tid", "")) for d in spans if "loop" not in d}

    def row(d: Dict[str, Any]) -> str:
        tid = str(d.get("tid", ""))
        return tid if "loop" not in d or tid in req_tids else "~serve loop"

    tid_of = {t: i + 1 for i, t in enumerate(sorted({row(d) for d in spans}))}
    events: List[Dict[str, Any]] = []
    for n, pid in pid_of.items():
        events.append({
            "ph": "M", "pid": pid, "name": "process_name",
            "args": {"name": n or "?"},
        })
    for d in spans:
        pid = pid_of[str(d.get("node", ""))]
        tid = tid_of[row(d)]
        t0 = float(d.get("t0", 0.0))
        t1 = float(d.get("t1", t0))
        args: Dict[str, Any] = {
            "trace_id": d.get("tid"), "span_id": d.get("sid"),
            "parent": d.get("par"),
        }
        args.update(d.get("lb") or {})
        events.append({
            "ph": "X", "name": str(d.get("name", "?")), "cat": "dml",
            "pid": pid, "tid": tid,
            "ts": round(t0 * 1e6, 1),
            "dur": round(max(0.0, t1 - t0) * 1e6, 1),
            "args": args,
        })
        for ev in d.get("ev") or ():
            try:
                ev_name, ev_ts = str(ev[0]), float(ev[1])
            except (TypeError, ValueError, IndexError):
                continue
            events.append({
                "ph": "i", "name": ev_name, "cat": "dml", "s": "t",
                "pid": pid, "tid": tid, "ts": round(ev_ts * 1e6, 1),
            })
    events.sort(key=lambda e: e.get("ts", 0.0))
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def cohort_attribution(
    breakdowns: Sequence[Dict[str, float]],
    e2es: Sequence[float],
) -> Dict[str, Any]:
    """Mean per-stage seconds over a cohort of traces (the p99 cohort
    in the bench), plus how much of the cohort's mean e2e the named
    stages explain (``attributed_fraction`` — the >= 0.9 claim gate).
    Overlapping stages (store detail under fetch; pipelined decode
    under handoff) are EXCLUDED from the coverage sum via their known
    parents, so the fraction cannot exceed honesty by double
    counting."""
    if not breakdowns or not e2es:
        return {"n": 0}
    stages: Dict[str, float] = {}
    for b in breakdowns:
        for k, v in b.items():
            stages[k] = stages.get(k, 0.0) + v
    n = len(breakdowns)
    mean_stages = {k: v / n for k, v in sorted(stages.items())}
    mean_e2e = sum(e2es) / len(e2es)
    # top-level stages only: detail spans nest under (or run
    # concurrently with) these and would double-count the same wall
    # time — admission sits inside formation, store_* inside
    # fetch/put, and the disagg prefill/handoff/decode trio runs
    # INSIDE the primary's infer span (that is the point of the
    # disaggregation: it all overlaps the batch's device window)
    detail = {"store_put", "store_get", "admission", "decode",
              "prefill", "handoff", "marker", "lm_request"}
    covered = sum(v for k, v in mean_stages.items() if k not in detail)
    return {
        "n": n,
        "mean_e2e_ms": round(mean_e2e * 1e3, 2),
        "stage_ms": {k: round(v * 1e3, 2) for k, v in mean_stages.items()},
        "attributed_ms": round(covered * 1e3, 2),
        "attributed_fraction": (
            round(covered / mean_e2e, 4) if mean_e2e > 0 else None
        ),
    }


# ----------------------------------------------------------------------
# the device account: one profiler trace in the program's own names
# ----------------------------------------------------------------------


def _pb_varint(buf: bytes, i: int) -> Tuple[int, int]:
    r = shift = 0
    while True:
        c = buf[i]
        i += 1
        r |= (c & 0x7F) << shift
        if c < 0x80:
            return r, i
        shift += 7


def _pb_fields(buf: bytes) -> Iterable[Tuple[int, Any]]:
    """(field number, value) of one protobuf message: an int for a
    varint, bytes for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _pb_varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _pb_varint(buf, i)
        elif wire == 2:
            ln, i = _pb_varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire in (1, 5):
            ln = 8 if wire == 1 else 4
            v, i = buf[i:i + ln], i + ln
        else:
            raise ValueError(f"protobuf wire type {wire} in a profiler trace")
        yield key >> 3, v


def _xplane(buf: bytes) -> Dict[str, Any]:
    """One `XPlane` of a profiler trace: its name, its own stats by
    name, its lines as (name, [(metadata id, start ns, end ns)]) and
    its event metadata as id -> (name, {stat name: value}).

    `jax.profiler.ProfileData` reads the same file, but hands out an
    event's OWN stats only; an operation's scope path (`tf_op`) and
    program (`program_id`) are stats of its event METADATA, so this
    reads the wire format itself (xplane.proto's field numbers)."""
    name, lines, stats, stat_names, metas = "", [], [], {}, {}
    for f, v in _pb_fields(buf):
        if f == 2:
            name = v.decode()
        elif f == 3:
            lines.append(v)
        elif f == 6:
            stats.append(v)
        elif f in (4, 5):  # map entries: key = 1, value = 2
            entry = dict(_pb_fields(v))
            into = stat_names if f == 5 else metas
            into[entry.get(1, 0)] = entry.get(2, b"")
    stat_names = {
        k: dict(_pb_fields(v)).get(2, b"").decode()
        for k, v in stat_names.items()}

    def stat(buf: bytes) -> Tuple[str, Any]:
        d = dict(_pb_fields(buf))
        key = stat_names.get(d.get(1), "")
        if 5 in d:
            return key, d[5].decode(errors="replace")
        if 7 in d:  # a reference to an interned string
            return key, stat_names.get(d[7], "")
        if 2 in d:
            return key, struct.unpack("<d", d[2])[0]
        return key, d.get(3, d.get(4, d.get(6)))

    meta: Dict[int, Tuple[str, Dict[str, Any]]] = {}
    for mid, v in metas.items():
        mname, mstats = "", {}
        for f, x in _pb_fields(v):
            if f == 2:
                mname = x.decode(errors="replace")
            elif f == 5:
                k, val = stat(x)
                mstats[k] = val
        meta[mid] = (mname, mstats)
    out_lines = []
    for buf_line in lines:
        lname, t0, events = "", 0, []
        for f, v in _pb_fields(buf_line):
            if f == 2:
                lname = v.decode()
            elif f == 3:
                t0 = v
            elif f == 4:
                mid = off = dur = 0
                for g, x in _pb_fields(v):
                    if g == 1:
                        mid = x
                    elif g == 2:
                        off = x
                    elif g == 3:
                        dur = x
                events.append((mid, off, off + dur))
        # a line's `timestamp_ns` plus an event's offset in ps
        out_lines.append((lname, [
            (mid, t0 + a * 1e-3, t0 + b * 1e-3) for mid, a, b in events]))
    return {"name": name, "stats": dict(stat(b) for b in stats),
            "lines": out_lines, "meta": meta}


def part_of(scope_path: str) -> str:
    """The part an operation belongs to: the innermost name of `PARTS`
    on its scope path (`jit(f)/jit(main)/while/body/attn_proj/dot_general`),
    `UNSCOPED` where there is none."""
    for seg in reversed(scope_path.rstrip(":").split("/")):
        if seg in PARTS:
            return seg
    return UNSCOPED


def find_profile(trace_dir: str) -> Optional[str]:
    """The newest `.xplane.pb` under a `jax.profiler` log directory."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_profile(xplane_path: str, top: int = 10) -> Dict[str, Any]:
    """One profiler trace (`.xplane.pb`) as an account of its window in
    the program's own names.

    BUSY: the device's `XLA Ops` line, each operation's SELF time (its
    interval less its children's: a `while` spans its body, a call its
    callee), summed by program (the XLA module the operation's
    `program_id` names, e.g. `jit__chunk_impl`) and part (`part_of` its
    `tf_op` stat, the scope path `jax.named_scope` wrote; `UNSCOPED`
    for the rest, whose longest operations are listed). Self times add
    up to the union of the intervals, which is the busy time.

    IDLE: the window less that union, gap by gap, each gap under the
    innermost (shortest) `dml.*` annotation open at its middle on any
    host thread (the loop spans `Tracer.loop_span` enters into the
    profiler), `UNATTRIBUTED` where none is open.

    The window is the span of the device's operations, first start to
    last end (of the `dml.*` annotations in a file with no device
    plane): what the device was WATCHED over. The session's own
    `profile_start_time` .. `profile_stop_time` is longer by the
    profiler's start and stop (0.3 s of a 5 s trace on the chip, my
    chip run, PR 38), in which nothing is recorded and nothing can be
    said. busy_s + idle_s = window_s by construction. Of several chips,
    the first that ran anything."""
    with open(xplane_path, "rb") as fh:
        planes = [_xplane(v) for f, v in _pb_fields(fh.read()) if f == 1]
    device = next(
        (p for p in planes if re.match(r"^/device:(TPU|GPU):\d+$", p["name"])
         and any(n == "XLA Ops" and ev for n, ev in p["lines"])), None)
    spans: List[Tuple[float, float, str]] = []
    for p in planes:
        if p["name"].startswith("/host:"):
            for _, events in p["lines"]:
                spans += [(a, b, p["meta"][mid][0][4:])
                          for mid, a, b in events
                          if p["meta"].get(mid, ("",))[0].startswith("dml.")]
    ops = sorted(
        (ev for n, evs in (device["lines"] if device else ())
         if n == "XLA Ops" for ev in evs),
        key=lambda e: (e[1], -e[2]))
    edges = ([t for _, a, b in ops for t in (a, b)]
             or [t for a, b, _ in spans for t in (a, b)] or [0.0])
    lo, hi = min(edges), max(edges)

    programs = {}
    for n, evs in (device["lines"] if device else ()):
        if n == "XLA Modules":
            for mid, _, _ in evs:
                m = re.match(r"^(.*)\((\d+)\)$", device["meta"][mid][0])
                if m:
                    programs[m.group(2)] = m.group(1)
    # self time: a stack of the operations open at each start
    self_ns = [b - a for _, a, b in ops]
    stack: List[int] = []
    busy_iv: List[List[float]] = []
    for i, (_, a, b) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= min(b, ops[stack[-1]][2]) - a
        elif busy_iv and a <= busy_iv[-1][1]:
            busy_iv[-1][1] = max(busy_iv[-1][1], b)
        else:
            busy_iv.append([a, b])
        if stack and b > ops[stack[-1]][2]:
            continue  # overlaps its parent's end: no frame of its own
        stack.append(i)
    busy: Dict[str, Dict[str, float]] = {}
    unscoped: Dict[str, float] = {}
    for (mid, _, _), ns in zip(ops, self_ns):
        name, stats = device["meta"].get(mid, ("", {}))
        kind = programs.get(str(stats.get("program_id")), "other")
        part = part_of(str(stats.get("tf_op", "")))
        row = busy.setdefault(kind, {})
        row[part] = row.get(part, 0.0) + ns * 1e-9
        if part == UNSCOPED:
            op = name.split(" = ", 1)[0].lstrip("%")[:80]
            key = (f"{kind}:{op}", str(stats.get("tf_op", "")))
            unscoped[key] = unscoped.get(key, 0.0) + ns * 1e-9

    # idle: the window less the busy intervals, named gap by gap
    cuts = [lo] + [t for iv in busy_iv for t in iv] + [hi]
    iv = np.asarray([(a, b) for a, b, _ in spans], np.float64).reshape(-1, 2)
    idle: Dict[str, float] = {}
    for a, b in zip(cuts[0::2], cuts[1::2]):
        if b <= a:
            continue
        mid_t = 0.5 * (a + b)
        hit = np.flatnonzero((iv[:, 0] <= mid_t) & (iv[:, 1] > mid_t))
        who = UNATTRIBUTED
        if len(hit):
            who = spans[hit[np.argmin(iv[hit, 1] - iv[hit, 0])]][2]
        idle[who] = idle.get(who, 0.0) + (b - a) * 1e-9
    busy_s = sum(b - a for a, b in busy_iv) * 1e-9
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_s,
        "idle_s": (hi - lo) * 1e-9 - busy_s,
        "operations": len(ops),
        "annotations": len(spans),
        "busy": {k: dict(sorted(v.items(), key=lambda kv: -kv[1]))
                 for k, v in sorted(
                     busy.items(), key=lambda kv: -sum(kv[1].values()))},
        "idle": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
        # [program:operation, seconds, its scope path (often none: an
        # operation the compiler made carries no name of the program's)]
        "unscoped_ops": [[k, v, path] for (k, path), v in sorted(
            unscoped.items(), key=lambda kv: -kv[1])[:top]],
    }
