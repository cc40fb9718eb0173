"""Distributed request tracing (dml_tpu/tracing.py): span/context
units, seeded head sampling, the bounded flight recorder with
always-on tail exemplars, cluster collection over TRACE_PULL, chrome
export, tail attribution — and the cross-node continuity contracts
(one stitched trace through the disaggregated LM path; trace ids that
survive a leader failover with no orphan spans)."""

import asyncio
import contextlib
import json
import os
import shutil

import pytest

from dml_tpu import tracing as trc
from dml_tpu.tracing import (
    EXEMPLAR_EVENTS,
    SPAN_NAMES,
    TRACER,
    TraceContext,
    Tracer,
    assemble_traces,
    chrome_trace,
    cohort_attribution,
    merge_span_dumps,
    stage_breakdown,
    trace_covers,
    trace_e2e,
)


@pytest.fixture()
def tracer():
    """Reset the process-global recorder around a test and restore its
    configuration after (other suites share it)."""
    saved = (TRACER.sample_rate, TRACER.seed, TRACER.span_budget)
    TRACER.configure(sample_rate=1.0, seed=0, span_budget=4096)
    TRACER.reset()
    yield TRACER
    TRACER.configure(sample_rate=saved[0], seed=saved[1],
                     span_budget=saved[2])
    TRACER.reset()


# ----------------------------------------------------------------------
# context + sampling units
# ----------------------------------------------------------------------


@pytest.mark.tracing
def test_ctx_wire_roundtrip():
    c = TraceContext("t1", "s9", False, key="img.jpeg")
    back = TraceContext.from_wire(c.to_wire())
    assert back == c
    # sampled default-on, key optional
    assert TraceContext.from_wire({"t": "tX"}) == TraceContext("tX")
    # garbled/byzantine input degrades to None, never raises
    for junk in (None, 42, [], {"p": "x"}, {"t": 7}):
        assert TraceContext.from_wire(junk) is None


@pytest.mark.tracing
def test_head_sample_seeded_deterministic():
    a = Tracer(sample_rate=0.5, seed=11)
    b = Tracer(sample_rate=0.5, seed=11)
    ids = [f"t{i}" for i in range(400)]
    da = [a.head_sample(t) for t in ids]
    assert da == [b.head_sample(t) for t in ids]  # same seed: identical
    c = Tracer(sample_rate=0.5, seed=12)
    assert da != [c.head_sample(t) for t in ids]  # seed matters
    frac = sum(da) / len(da)
    assert 0.35 < frac < 0.65  # roughly the configured rate
    a.configure(sample_rate=0.0)
    assert not any(a.head_sample(t) for t in ids)
    a.configure(sample_rate=1.0)
    assert all(a.head_sample(t) for t in ids)


# ----------------------------------------------------------------------
# flight recorder: ring bound, slowest-K, exemplars
# ----------------------------------------------------------------------


@pytest.mark.tracing
def test_recorder_ring_bounded_and_peak():
    t = Tracer(sample_rate=1.0, span_budget=64)
    for i in range(300):
        t.start_span("fetch", trace_id=f"t{i}", node="n1").end()
    st = t.stats()
    assert st["spans"] == 64 and st["peak_spans"] == 64
    assert st["dropped"] == 300 - 64
    assert st["within_budget"] is True
    assert len(t.dump()) <= 64 + st["slow_k"]


@pytest.mark.tracing
def test_exemplars_and_slow_k_survive_sampling_off():
    t = Tracer(sample_rate=0.0, span_budget=64, slow_k=4)
    # unsampled spans never enter the ring...
    for i in range(20):
        s = t.start_span("request", trace_id=f"t{i}", node="n1",
                         t0=100.0 + i)
        s.end(100.0 + i + 0.001 * (i + 1))
    assert t.stats()["spans"] == 0
    # ...but the slowest-K request roots are captured anyway
    slow = [d["tid"] for _, d in t._slow]
    assert slow == ["t19", "t18", "t17", "t16"]
    # and a deadline_miss/shed/requeue/fallback event pins its trace
    assert set(EXEMPLAR_EVENTS) == {
        "deadline_miss", "shed", "requeue", "fallback",
    }
    s = t.start_span("handoff", trace_id="tmiss", node="n2")
    s.event("fallback")
    s.end()
    t.note_exemplar(TraceContext("tmiss", "p", False), "requeue",
                    node="n3")
    assert "tmiss" in t.exemplar_trace_ids()
    got = t.dump(trace_ids=["tmiss"])
    kinds = {e[0] for d in got for e in d.get("ev", ())}
    assert {"fallback", "requeue"} <= kinds


@pytest.mark.tracing
def test_dump_truncation_keeps_exemplar_spans():
    """A collection cap (max_spans) keeps pinned exemplar-trace spans
    in preference to newest-ordinary spans: a deadline miss early in
    a long run must survive into the pulled cluster view, or the
    bench's 100%-miss-coverage gate could fail spuriously."""
    t = Tracer(sample_rate=1.0, span_budget=4096)
    s = t.start_span("request", trace_id="tearly", node="n1", t0=1.0)
    s.event("deadline_miss")
    s.end(2.0)
    for i in range(500):
        t.start_span("infer", trace_id=f"z{i}", node="n1",
                     t0=10.0 + i).end(10.5 + i)
    got = t.dump(max_spans=50)
    assert len(got) == 50
    assert any(d["tid"] == "tearly" for d in got), \
        "the pinned exemplar was cut by the newest-first cap"


@pytest.mark.tracing
def test_exemplar_pins_earlier_ring_spans():
    """A trace's spans already in the ring are retroactively pinned
    the moment it becomes an exemplar — later eviction can't lose
    them."""
    t = Tracer(sample_rate=1.0, span_budget=32)
    t.start_span("fetch", trace_id="tA", node="n1").end()
    s = t.start_span("request", trace_id="tA", node="n1")
    s.event("deadline_miss")
    s.end()
    for i in range(100):  # flood the ring
        t.start_span("infer", trace_id=f"z{i}", node="n1").end()
    names = {d["name"] for d in t.dump(trace_ids=["tA"])}
    assert {"fetch", "request"} <= names


# ----------------------------------------------------------------------
# assembly, attribution, export
# ----------------------------------------------------------------------


def _mk(tid, sid, par, name, node, t0, t1, ev=None):
    d = {"tid": tid, "sid": sid, "par": par, "name": name,
         "node": node, "t0": t0, "t1": t1}
    if ev:
        d["ev"] = ev
    return d


@pytest.mark.tracing
def test_stage_breakdown_and_cohort_attribution():
    spans = [
        _mk("T", "r", "", "request", "H1", 0.0, 1.0),
        _mk("T", "a", "r", "admission", "H1", 0.0, 0.01),
        _mk("T", "f", "r", "formation", "H1", 0.0, 0.4),
        _mk("T", "d", "r", "dispatch", "H1", 0.4, 0.45),
        _mk("T", "w", "r", "fetch", "H3", 0.45, 0.5),
        _mk("T", "i", "r", "infer", "H3", 0.5, 0.9),
        _mk("T", "p", "r", "put", "H3", 0.9, 0.92),
        _mk("T", "x", "r", "result", "H1", 0.92, 0.95),
    ]
    bd = stage_breakdown(spans)
    assert "request" not in bd  # the root IS the e2e, not a stage
    assert abs(bd["formation"] - 0.4) < 1e-9
    assert abs(trace_e2e(spans) - 1.0) < 1e-9
    att = cohort_attribution([bd], [trace_e2e(spans)])
    # admission nests inside formation: excluded from the coverage sum
    assert att["attributed_fraction"] == pytest.approx(
        (0.4 + 0.05 + 0.05 + 0.4 + 0.02 + 0.03) / 1.0, abs=1e-6)
    assert att["attributed_fraction"] >= 0.9
    assert trace_covers(spans, ("request", "formation", "infer"))
    assert not trace_covers(spans, ("prefill",))


@pytest.mark.tracing
def test_assemble_merge_dedupe_and_chrome_export():
    a = [_mk("T", "s1", "", "request", "H1", 0.0, 1.0)]
    b = [_mk("T", "s1", "", "request", "H1", 0.0, 1.0),
         _mk("T", "s2", "s1", "infer", "H2", 0.2, 0.8,
             ev=[["fallback", 0.5]])]
    merged = merge_span_dumps([a, b])
    assert [d["sid"] for d in merged] == ["s1", "s2"]  # deduped
    traces = assemble_traces(merged)
    assert list(traces) == ["T"] and len(traces["T"]) == 2
    doc = chrome_trace(merged)
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == 2
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    # two nodes -> two process metadata rows + one instant event
    assert sum(1 for e in evs if e["ph"] == "M") == 2
    assert sum(1 for e in evs if e["ph"] == "i") == 1
    json.dumps(doc)  # must be serializable as-is


@pytest.mark.tracing
def test_summarize_joins_traces_for_p99_attribution():
    from dml_tpu.ingress.loadgen import Outcome, summarize

    outs = []
    stages_by_tid = {}
    for i in range(50):
        tid = f"t{i}"
        e2e = 0.1 + 0.01 * i
        outs.append(Outcome(
            slo="interactive", terminal="completed", e2e_s=e2e,
            deadline_met=True, trace_id=tid,
        ))
        stages_by_tid[tid] = {"formation": 0.6 * e2e, "infer": 0.38 * e2e}
    s = summarize(outs, wall_s=10.0, trace_stages=stages_by_tid)
    att = s["p99_attribution"]
    assert att["join_fraction"] == 1.0
    assert att["attributed_fraction"] == pytest.approx(0.98, abs=0.01)
    assert att["p99_ms"] > 0
    # terminal-carried stages are the fallback when no trace joined
    outs2 = [Outcome(slo="i", terminal="completed", e2e_s=0.2,
                     deadline_met=True, trace_id="zz",
                     stages={"formation": 0.19})]
    s2 = summarize(outs2, wall_s=1.0)
    assert s2["p99_attribution"]["attributed_fraction"] \
        == pytest.approx(0.95, abs=0.01)
    # no stages anywhere -> no attribution block, not a crash
    s3 = summarize([Outcome(slo="i", terminal="completed", e2e_s=0.2,
                            deadline_met=True)], wall_s=1.0)
    assert "p99_attribution" not in s3


@pytest.mark.tracing
def test_handoff_fallback_produces_fallback_span_event(tracer):
    """Per-request handoff-fallback discipline: a failed share records
    one `handoff` span per request with the `fallback` event (a tail
    exemplar) for exactly the undelivered requests."""
    from types import SimpleNamespace

    from dml_tpu.inference.lm_sharded import DisaggLMBackend

    fake = SimpleNamespace(
        node=SimpleNamespace(me=SimpleNamespace(unique_name="H4:1")),
        group_name="tp0", handoff="stream",
    )
    ctxs = [TraceContext("tf", "root", True, key=f"p{i}")
            for i in range(3)]
    DisaggLMBackend._share_spans(
        fake, ctxs, [0, 1, 2], {0}, "H5:2", 100.0, failed=True,
    )
    spans = tracer.dump(trace_ids=["tf"])
    hand = [d for d in spans if d["name"] == "handoff"]
    assert len(hand) == 3
    fb = [d for d in hand
          if any(e[0] == "fallback" for e in d.get("ev", ()))]
    assert len(fb) == 2  # delivered request 0 carries no fallback
    assert all(d["lb"]["result"] == "fallback" for d in fb)
    assert "tf" in tracer.exemplar_trace_ids()


@pytest.mark.tracing
def test_scheduler_requeue_notes_exemplar(tracer):
    """A requeued batch marks every riding request's trace as a tail
    exemplar (requeues are what explain later deadline misses)."""
    from dml_tpu.jobs.scheduler import Scheduler

    s = Scheduler()
    ctx = TraceContext("trq", "root", False, key="img.jpeg")
    s.submit_job(1, "M", ["img.jpeg"], 1, "client", batch_size=1,
                 traces=[ctx.to_wire()])
    out = s.schedule(["W1"])
    assert len(out) == 1
    assert out[0].batch.trace_ctxs() == []  # unsampled ctx filtered
    s.on_worker_failed("W1")
    assert "trq" in tracer.exemplar_trace_ids()
    got = tracer.dump(trace_ids=["trq"])
    assert any(
        e[0] == "requeue" for d in got for e in d.get("ev", ())
    )


# ----------------------------------------------------------------------
# loop spans: the serve loop's ring, clock and profiler annotation
# ----------------------------------------------------------------------


class _StubAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: counts enters/exits."""

    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _StubAnnotation.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        _StubAnnotation.log.append(("exit", self.name))


def _loop_ring_bounded(t, monkeypatch):
    for i in range(300):
        t.loop_span("lm_step", i=i).end()
    st = t.stats()
    assert st["loop_spans"] == 64 and st["loop_budget"] == 64
    assert st["loop_dropped"] == 300 - 64 and st["loop_recorded"] == 300
    kept = [d["lb"]["i"] for d in t.loop_spans("lm_step")]
    assert kept == list(range(300 - 64, 300))  # the newest survive


def _loop_flood_evicts_no_request_span(t, monkeypatch):
    for i in range(10):
        t.start_span("fetch", trace_id=f"t{i}", node="n1").end()
    for _ in range(500):
        t.loop_span("lm_step").end()
    st = t.stats()
    assert st["spans"] == 10 and st["dropped"] == 0
    assert {d["tid"] for d in t.dump() if "loop" not in d} == {
        f"t{i}" for i in range(10)}


def _request_flood_evicts_no_loop_span(t, monkeypatch):
    for i in range(10):
        t.loop_span("lm_step", i=i).end()
    for i in range(500):
        t.start_span("fetch", trace_id=f"t{i}", node="n1").end()
    st = t.stats()
    assert st["loop_spans"] == 10 and st["loop_dropped"] == 0
    assert st["spans"] == 64 and st["dropped"] == 500 - 64
    # a capped dump keeps both kinds apart too: neither starves
    cut = t.dump(max_spans=16)
    assert len(cut) == 16
    assert sum("loop" in d for d in cut) == 8


def _loop_parent_and_trace_ids(t, monkeypatch):
    with t.loop_span("lm_step", occupancy=3) as step:
        with t.loop_span("lm_dispatch", step) as child:
            pass
        t.loop_record("lm_request", step.m0, child.m1, step,
                      events=(("placed", step.m0),), slot=2)
    with t.loop_span("lm_step") as other:
        pass
    ctx = TraceContext("tREQ", "sINFER")
    t.loop_record("lm_request", 1.0, 2.0, ctx)
    rows = {d["sid"]: d for d in t.loop_spans()}
    assert len(rows) == 5 and all(d["loop"] == 1 for d in rows.values())
    assert rows[child.span_id]["par"] == step.span_id
    assert rows[child.span_id]["tid"] == step.trace_id  # one dispatch,
    assert rows[step.span_id]["par"] == ""              # one trace id
    assert other.trace_id != step.trace_id
    assert rows[step.span_id]["lb"] == {"occupancy": 3}
    under = [d for d in rows.values() if d["tid"] == "tREQ"]
    assert [d["par"] for d in under] == ["sINFER"]
    rec = [d for d in rows.values()
           if d["name"] == "lm_request" and d["tid"] == step.trace_id]
    assert rec[0]["ev"] == [["placed", rows[step.span_id]["t0"]]]
    assert rec[0]["lb"] == {"slot": 2}
    # carried by dump / chrome_trace like any other span
    doc = chrome_trace(t.dump())
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
    assert sorted(names) == ["lm_dispatch", "lm_request", "lm_request",
                             "lm_step", "lm_step"]
    assert t.dump(trace_ids=["tREQ"]) == under


def _wall_of_maps_the_monotonic_clock(t, monkeypatch):
    import time

    m, w = time.monotonic(), time.time()
    assert abs(t.wall_of(m) - w) < 0.05
    assert t.wall_of(m + 2.5) - t.wall_of(m) == pytest.approx(2.5)
    with t.loop_span("lm_step") as s:
        pass
    d = t.loop_spans()[0]
    assert d["t0"] == pytest.approx(t.wall_of(s.m0), abs=1e-6)
    assert d["t1"] == pytest.approx(t.wall_of(s.m1), abs=1e-6)


def _summary_folds_the_ring_per_name(t, monkeypatch):
    t.loop_record("lm_step", 10.0, 10.5)
    t.loop_record("lm_step", 11.0, 11.25)
    t.loop_record("lm_place", 11.0, 11.125)
    s = t.summary()
    assert list(s) == ["lm_place", "lm_step"]
    assert s["lm_step"]["count"] == 2
    assert s["lm_step"]["total_s"] == pytest.approx(0.75, abs=1e-5)
    assert s["lm_step"]["mean_s"] == pytest.approx(0.375, abs=1e-5)
    assert s["lm_step"]["max_s"] == pytest.approx(0.5, abs=1e-5)
    t.reset()
    assert t.summary() == {} and t.stats()["loop_recorded"] == 0


def _summary_averages_the_labels_that_say_the_grid_is_fed(t, monkeypatch):
    """`profile spans` lists `worker_infer`'s `joined` and `lm_step`'s
    `waiting` as means over the spans that carry them, and no other
    label."""
    t.loop_record("worker_infer", 1.0, 2.0, joined=0, batch=0)
    t.loop_record("worker_infer", 1.5, 2.5, joined=1, batch=1)
    t.loop_record("lm_step", 3.0, 3.5, waiting=6, occupancy=8)
    t.loop_record("lm_step", 3.5, 4.0, waiting=2, occupancy=8)
    t.loop_record("lm_step", 4.0, 4.5, occupancy=8)  # an older writer
    t.loop_record("lm_place", 4.0, 4.25, requests=3)
    s = t.summary()
    assert s["worker_infer"]["joined_mean"] == 0.5
    assert s["lm_step"]["waiting_mean"] == 4.0
    assert s["lm_step"]["count"] == 3
    assert set(s["lm_place"]) == {"count", "total_s", "mean_s", "max_s"}


def _stepped_wall_clock_leaves_durations_right(t, monkeypatch):
    """time.time() jumps back an hour inside the span: the duration
    stays the monotonic one and the span still ends after it starts."""
    import time

    real = time.time
    with t.loop_span("lm_step") as s:
        monkeypatch.setattr(time, "time", lambda: real() - 3600.0)
        t.start_span("fetch", trace_id="tX", node="n").end()
    monkeypatch.setattr(time, "time", real)
    d = t.loop_spans()[0]
    assert 0.0 <= d["t1"] - d["t0"] < 1.0
    assert d["t1"] - d["t0"] == pytest.approx(s.m1 - s.m0, abs=2e-6)
    assert abs(d["t0"] - real()) < 5.0  # still on the unstepped wall


def _annotation_entered_once_per_span(t, monkeypatch):
    monkeypatch.setattr(trc, "_TRACE_ANNOTATION", _StubAnnotation)
    _StubAnnotation.log = []
    with t.loop_span("lm_step") as step:
        with t.loop_span("lm_pack", step):
            pass
    t.loop_record("lm_request", 1.0, 2.0)  # no stack frame: none
    assert _StubAnnotation.log == [
        ("enter", "dml.lm_step"), ("enter", "dml.lm_pack"),
        ("exit", "dml.lm_pack"), ("exit", "dml.lm_step")]
    step.end()  # idempotent: no second exit, no second record
    assert len(_StubAnnotation.log) == 4 and len(t.loop_spans()) == 3


def _annotation_skipped_where_jax_is_not_loaded(t, monkeypatch):
    import sys

    monkeypatch.setattr(trc, "_TRACE_ANNOTATION", None)
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    with t.loop_span("lm_step"):
        pass
    assert trc._TRACE_ANNOTATION is None and "jax" not in sys.modules
    assert len(t.loop_spans("lm_step")) == 1


@pytest.mark.tracing
@pytest.mark.parametrize("case", [
    _loop_ring_bounded,
    _loop_flood_evicts_no_request_span,
    _request_flood_evicts_no_loop_span,
    _loop_parent_and_trace_ids,
    _wall_of_maps_the_monotonic_clock,
    _summary_folds_the_ring_per_name,
    _summary_averages_the_labels_that_say_the_grid_is_fed,
    _stepped_wall_clock_leaves_durations_right,
    _annotation_entered_once_per_span,
    _annotation_skipped_where_jax_is_not_loaded,
], ids=lambda f: f.__name__.lstrip("_"))
def test_loop_spans(case, monkeypatch):
    case(Tracer(sample_rate=1.0, span_budget=64, loop_budget=64),
         monkeypatch)


@pytest.mark.tracing
def test_every_loop_span_name_is_registered():
    """The names the serve loop opens are in the closed registry (the
    lint rule checks the call sites; this pins the vocabulary)."""
    assert {"lm_idle", "lm_submit", "lm_step", "lm_dispatch", "lm_pack",
            "lm_readback", "lm_deliver", "lm_place", "lm_prefill_group",
            "lm_request", "lm_route", "lm_turn", "lm_exposed", "worker_fetch", "worker_infer", "worker_put",
            "store_op_put", "store_op_get"} <= set(SPAN_NAMES)


# ----------------------------------------------------------------------
# cluster end-to-end: stitched traces over TRACE_PULL
# ----------------------------------------------------------------------


@contextlib.asynccontextmanager
async def _cluster(n, base_port, tmp_path, **kw):
    from dml_tpu.cluster.chaos import LocalCluster

    root = str(tmp_path / f"trc_{base_port}")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    c = LocalCluster(n, root, base_port, with_ingress=True, **kw)
    try:
        await c.start()
        await c.wait_for(c.converged, 20.0, "initial convergence")
        yield c
    finally:
        await c.stop()


def _no_orphans(spans):
    sids = {d["sid"] for d in spans}
    return all((d.get("par") or "") in sids or not d.get("par")
               for d in spans)


@pytest.mark.tracing
@pytest.mark.ingress
def test_cluster_trace_stitched_end_to_end(tmp_path, tracer):
    """One sampled request through the stub serving path yields ONE
    trace whose tree covers admission -> formation -> dispatch ->
    fetch -> infer -> put -> result, collected cluster-wide via
    TRACE_PULL and exportable as Chrome trace JSON."""
    from dml_tpu.cluster import chaos

    async def run():
        async with _cluster(3, 24951, tmp_path) as c:
            client = c.client()
            await client.store.put_bytes("img.jpeg", b"stub-bytes",
                                         timeout=20.0)
            terms = [
                await client.ingress.request(chaos.STUB_MODEL,
                                             timeout=30.0)
                for _ in range(3)
            ]
            for t in terms:
                assert t["ok"] and t["trace_id"]
                assert isinstance(t["stages"], dict)
                assert t["stages"].get("formation") is not None
            leader = next(
                sn for sn in c.nodes.values() if sn.node.is_leader
            )
            view = await leader.node.pull_cluster_traces(max_spans=2048)
            for t in terms:
                spans = view["traces"].get(t["trace_id"])
                assert spans, "completed request's trace not collected"
                assert trace_covers(spans, (
                    "request", "admission", "formation", "dispatch",
                    "fetch", "infer", "put", "result",
                ))
                assert _no_orphans(spans)
                # cross-node: the router's spans and the worker's
                # spans carry different recording nodes
                assert len({d["node"] for d in spans}) >= 2
                bd = stage_breakdown(spans)
                e2e = trace_e2e(spans)
                att = cohort_attribution([bd], [e2e])
                assert att["attributed_fraction"] >= 0.8
            doc = chrome_trace(view["spans"])
            assert len(doc["traceEvents"]) >= len(view["spans"])
            # a non-leader node answers TRACE_PULL too (any node can
            # assemble the cluster view)
            other = next(
                sn for sn in c.nodes.values() if not sn.node.is_leader
            )
            view2 = await other.node.pull_cluster_traces()
            assert terms[0]["trace_id"] in view2["traces"]

    asyncio.run(run())


@pytest.mark.tracing
@pytest.mark.ingress
def test_sampling_zero_records_only_exemplars(tmp_path, tracer):
    """sampling=0: served requests record no ring spans (the overhead
    knob), but a SHED request still pins its tail exemplar."""
    from dml_tpu.cluster import chaos
    from dml_tpu.ingress.slo import SLOClass

    tracer.configure(sample_rate=0.0)
    tiny = {"interactive": SLOClass("interactive", deadline_s=2.0,
                                    queue_limit=1, linger_s=0.02)}

    async def run():
        async with _cluster(
            3, 24971, tmp_path, ingress_classes=tiny
        ) as c:
            client = c.client()
            await client.store.put_bytes("img.jpeg", b"stub-bytes",
                                         timeout=20.0)
            from dml_tpu.ingress.router import RequestRejected

            async def one():
                try:
                    rid = await client.ingress.submit(
                        chaos.STUB_MODEL, timeout=8.0
                    )
                    await client.ingress.wait(rid, timeout=20.0)
                    return "completed"
                except RequestRejected as e:
                    return "shed" if e.shed else "rejected"

            results = await asyncio.gather(*(one() for _ in range(8)))
            assert "shed" in results
            assert tracer.stats()["spans"] == 0
            ex = tracer.exemplar_trace_ids()
            assert ex, "shed exemplars must be captured at sampling=0"
            kinds = {
                e[0]
                for tid in ex
                for d in tracer.dump(trace_ids=[tid])
                for e in d.get("ev", ())
            }
            assert "shed" in kinds

    asyncio.run(run())


@pytest.mark.tracing
@pytest.mark.ingress
def test_failover_trace_continuity(tmp_path, tracer):
    """Leader killed with dispatched requests in flight: completions
    fanned out by the PROMOTED router carry the ORIGINAL trace_id
    (relayed with the ingress table) and the assembled traces have no
    orphan spans — the re-rooted adopted request reuses the original
    root span id, so spans the dead leader recorded keep a resolvable
    parent. Deterministic: a slow (2 s) LM backend guarantees the
    batch is still executing when the leader dies."""
    from dml_tpu.cluster.chaos import stub_backend
    from dml_tpu.jobs.cost_model import ModelCost
    from dml_tpu.jobs.service import JobService

    async def slow_lm(model, paths, **kw):
        await asyncio.sleep(2.0)
        return ({p: {"text": "slow"} for p in paths}, 2.0, None)

    def make_jobs(node, store):
        js = JobService(node, store, infer_backend=stub_backend())
        js.register_lm(
            "SlowLM", backend=slow_lm,
            cost=ModelCost(load_time=0.0, first_query=0.01,
                           per_query=0.01, batch_size=4),
        )
        return js

    async def run():
        async with _cluster(
            4, 24991, tmp_path, make_jobs=make_jobs,
        ) as c:
            client = c.client()
            await client.store.put_bytes("p0.prompt.txt", b"1 2 3\n",
                                         timeout=20.0)
            leader0 = c.leader_uname()
            assert leader0 is not None
            leader_sn = c.nodes[leader0]
            rids = [
                await client.ingress.submit("SlowLM", timeout=10.0)
                for _ in range(4)
            ]

            def dispatched():
                act = leader_sn.ingress._active
                return len(act) == 4 and all(
                    st.state == "dispatched" for st in act.values()
                )

            await c.wait_for(dispatched, 10.0, "requests dispatched")
            await c.crash_node(leader0)
            terms = await asyncio.gather(*(
                client.ingress.wait(r, timeout=60.0) for r in rids
            ))
            completed = [t for t in terms if t.get("ok")]
            assert completed, "traffic must complete across the kill"
            assert all(t.get("trace_id") for t in completed), \
                "every completion carries its (original) trace id"
            new_leader = c.leader_uname()
            assert new_leader is not None and new_leader != leader0
            view = await c.nodes[new_leader].node.pull_cluster_traces(
                max_spans=2048
            )
            # adopted requests: re-rooted under the ORIGINAL trace +
            # root id on the promoted router
            adopted = [
                d for d in view["spans"]
                if d["name"] == "request"
                and (d.get("lb") or {}).get("adopted")
            ]
            assert adopted, \
                "no request crossed the failover via the ingress relay"
            completed_tids = {t["trace_id"] for t in completed}
            assert completed_tids & {d["tid"] for d in adopted}, \
                "a promoted-router completion must keep its trace id"
            for d in adopted:
                spans = view["traces"][d["tid"]]
                assert _no_orphans(spans)
                # the trace stitches spans from the DEAD leader (its
                # admission/formation) and the promoted router
                assert leader0 in {s["node"] for s in spans}
            # every completed request's collected trace is orphan-free
            for t in completed:
                spans = view["traces"].get(t["trace_id"])
                if spans:
                    assert _no_orphans(spans)

    asyncio.run(run())


# ----------------------------------------------------------------------
# disaggregated LM path: the full stitched tree (acceptance contract)
# ----------------------------------------------------------------------


@pytest.mark.tracing
@pytest.mark.disagg
def test_disagg_ingress_request_yields_full_stitched_trace(
    tmp_path, tracer
):
    """A sampled per-request submit served through the DISAGGREGATED
    LM path yields ONE cross-node trace covering admission ->
    formation -> dispatch -> prefill -> handoff -> decode -> result,
    exported in Chrome trace format."""
    import jax
    import numpy as np

    from dml_tpu.cluster.chaos import LocalCluster
    from dml_tpu.config import MeshSpec, Timing, WorkerGroupSpec
    from dml_tpu.inference.lm_backend import (
        LMBackend, lm_spec_parts, write_prompt_file,
    )
    from dml_tpu.inference.lm_sharded import (
        DisaggLMBackend, LMPrefillBackend, sharded_lm_backend,
    )
    from dml_tpu.jobs.service import JobService
    from dml_tpu.parallel.mesh import make_mesh

    SPEC = {
        "name": "ShardLM", "vocab_size": 64, "d_model": 32,
        "n_heads": 4, "n_kv_heads": 2, "n_layers": 2, "d_ff": 64,
        "dtype": "float32", "max_new_tokens": 8, "max_slots": 2,
        "max_len": 64, "chunk": 4, "seed": 0,
    }
    params, cfg = lm_spec_parts(SPEC)
    mesh = make_mesh(MeshSpec(dp=1, tp=2), devices=jax.devices()[:2])
    be_dis = sharded_lm_backend(SPEC, mesh)
    be_single = LMBackend(params, cfg, max_new_tokens=8, max_slots=2,
                          max_len=64, chunk=4)
    prefill_be = LMPrefillBackend(params, cfg, max_len=64)
    # H1 is the rank leader and H2 the standby, so the schedulable
    # pool is exactly the collapsed group {H3 (decode primary)} — the
    # ingress batch MUST serve on the disaggregated engine
    group = WorkerGroupSpec(
        "tp0", ("H3", "H4"), MeshSpec(dp=1, tp=2),
        lm_models=("ShardLM",),
        roles={"H3": "decode", "H4": "prefill"},
    )

    def make_jobs(node, store):
        js = JobService(node, store)
        uname = node.me.unique_name
        members = node.spec.group_members_unique(group.name)
        gb = None
        if members and uname == members[0]:
            gb = DisaggLMBackend(
                be_dis, model_name="ShardLM", group_name=group.name,
                node=node, store=store, members=members,
                alive_fn=lambda: {
                    n.unique_name for n in node.membership.alive_nodes()
                },
                capacity=2.0,
            )
        js.register_lm(
            "ShardLM", backend=be_single.backend,
            cost=be_single.cost(), prefill=prefill_be,
            group_backend=gb,
        )
        return js

    root = str(tmp_path / "disagg_trc")
    os.makedirs(root, exist_ok=True)
    cluster = LocalCluster(
        4, root, 25011, with_ingress=True,
        timing=Timing(ping_interval=0.2, ack_timeout=0.3,
                      cleanup_time=1.0, leader_rpc_timeout=10.0),
        worker_groups=[group],
        make_jobs=make_jobs,
    )

    async def run():
        try:
            await cluster.start()
            await cluster.wait_for(
                cluster.converged, 30.0, "disagg trace convergence"
            )
            client = cluster.client()
            rng = np.random.RandomState(1)
            prompt = rng.randint(0, SPEC["vocab_size"], 9)
            p = os.path.join(root, "p0.tokens.txt")
            write_prompt_file(p, prompt)
            await client.store.put(p, "p0.tokens.txt")
            term = await client.ingress.request(
                "ShardLM", store_name="p0.tokens.txt", timeout=60.0
            )
            assert term["ok"] and term["trace_id"]
            leader = cluster.nodes[cluster.leader_uname()]
            view = await leader.node.pull_cluster_traces(max_spans=2048)
            spans = view["traces"].get(term["trace_id"])
            assert spans, "disagg request's trace not collected"
            assert trace_covers(spans, (
                "request", "admission", "formation", "dispatch",
                "fetch", "prefill", "handoff", "decode", "infer",
                "put", "result",
            )), sorted({d["name"] for d in spans})
            assert _no_orphans(spans)
            # genuinely cross-node: router (H1), decode primary (H3),
            # prefill member (H4) all recorded spans in ONE trace
            assert len({d["node"] for d in spans}) >= 3
            doc = chrome_trace(spans)
            assert any(e["ph"] == "X" and e["name"] == "handoff"
                       for e in doc["traceEvents"])
        finally:
            await cluster.stop()
            be_single.close()

    asyncio.run(run())


@pytest.mark.tracing
def test_span_name_registry_is_closed():
    """Every stage name the attribution tooling can report is in the
    registry, and the registry is what dmllint enforces at call
    sites."""
    for name in ("request", "admission", "formation", "dispatch",
                 "fetch", "infer", "prefill", "handoff", "decode",
                 "put", "result", "store_put", "store_get", "marker"):
        assert name in SPAN_NAMES


@pytest.mark.tracing
def test_trace_reply_degradation_detection():
    """drift-wire-payloads fix (ISSUE 13): every degraded TRACE_PULL
    reply tier is detected — the explicit count-only `truncated`
    marker, the label-stripped tier, AND the halved-newest-half tiers
    (which only betray themselves as got < held)."""
    from dml_tpu.cluster.node import Node

    detect = Node._trace_reply_degradation
    # full reply: nothing to report
    assert detect({"ok": True, "held": 4}, 4) is None
    assert detect({"ok": True}, 7) is None
    # count-only tier
    deg = detect({"ok": True, "held": 9, "truncated": "spans"}, 0)
    assert deg == {"held": 9, "got": 0, "truncated": "spans"}
    # halved tier: no marker at all, only the count gap
    deg = detect({"ok": True, "held": 100}, 25)
    assert deg == {"held": 100, "got": 25}
    # stripped tier
    deg = detect({"ok": True, "held": 4, "stripped": True}, 4)
    assert deg == {"held": 4, "got": 4, "stripped": True}
