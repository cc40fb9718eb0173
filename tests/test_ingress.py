"""Request front door (dml_tpu/ingress/): SLO admission + shedding,
continuous batch formation, seeded open-loop load generation,
percentile accounting, session affinity, token streaming, and the
failover-mid-traffic exactly-once contract — unit coverage on the
pure pieces (injected clocks), end-to-end on chaos.LocalCluster (the
same chassis the soaks validate)."""

import asyncio
import contextlib
import math
import os
import shutil

import pytest

from dml_tpu.ingress import loadgen
from dml_tpu.ingress.loadgen import Outcome, open_loop_trace, percentile
from dml_tpu.ingress.router import BatchFormer, PendingRequest, RequestRejected
from dml_tpu.ingress.slo import DEFAULT_CLASSES, SLOClass, resolve_class, shed_reason

# ----------------------------------------------------------------------
# open-loop trace: determinism + JSON round-trip (ISSUE 7 satellite)
# ----------------------------------------------------------------------


@pytest.mark.ingress
def test_trace_same_seed_identical_and_json_roundtrip():
    a = open_loop_trace(7, duration_s=5.0, rate_qps=20.0,
                        slo_mix={"interactive": 0.8, "batch": 0.2},
                        session_pct=25.0, stream_pct=10.0)
    b = open_loop_trace(7, duration_s=5.0, rate_qps=20.0,
                        slo_mix={"interactive": 0.8, "batch": 0.2},
                        session_pct=25.0, stream_pct=10.0)
    assert a.arrivals == b.arrivals  # same seed => identical trace
    assert len(a.arrivals) > 50
    # JSON round-trip is exact
    c = loadgen.ArrivalTrace.from_json(a.to_json())
    assert c.arrivals == a.arrivals
    assert (c.seed, c.duration_s, c.rate_qps) == (7, 5.0, 20.0)
    # a different seed draws a different trace
    d = open_loop_trace(8, duration_s=5.0, rate_qps=20.0,
                        slo_mix={"interactive": 0.8, "batch": 0.2})
    assert d.arrivals != a.arrivals
    # arrivals are ordered and inside the window, classes from the mix
    ts = [x.t for x in a.arrivals]
    assert ts == sorted(ts) and all(0 <= t < 5.0 for t in ts)
    assert {x.slo for x in a.arrivals} <= {"interactive", "batch"}


# ----------------------------------------------------------------------
# percentile accounting vs a hand-computed fixture (ISSUE 7 satellite)
# ----------------------------------------------------------------------


@pytest.mark.ingress
def test_percentile_hand_computed_fixture():
    vals = [10.0, 20.0, 30.0, 40.0, 50.0, 60.0, 70.0, 80.0, 90.0, 100.0]
    # linear interpolation at rank p/100*(n-1): n=10
    assert percentile(vals, 50) == pytest.approx(55.0)   # rank 4.5
    assert percentile(vals, 95) == pytest.approx(95.5)   # rank 8.55
    assert percentile(vals, 99) == pytest.approx(99.1)   # rank 8.91
    assert percentile(vals, 0) == 10.0
    assert percentile(vals, 100) == 100.0
    assert percentile([42.0], 99) == 42.0
    assert math.isnan(percentile([], 50))


@pytest.mark.ingress
def test_summarize_sheds_are_rejections_excluded_from_latency():
    outcomes = [
        Outcome(slo="interactive", terminal="completed", e2e_s=0.1,
                deadline_met=True),
        Outcome(slo="interactive", terminal="completed", e2e_s=0.2,
                deadline_met=True),
        Outcome(slo="interactive", terminal="completed", e2e_s=0.3,
                deadline_met=False),
        Outcome(slo="interactive", terminal="shed", reason="queue_full"),
        Outcome(slo="interactive", terminal="shed",
                reason="deadline_unmeetable"),
        Outcome(slo="interactive", terminal="lost", reason="failover"),
    ]
    s = loadgen.summarize(outcomes, wall_s=10.0)
    assert s["n"] == 6
    assert s["completed"] == 3
    assert s["shed"] == 2
    assert s["rejected"] == 1  # a LOST is a typed rejection
    assert s["shed_ratio"] == pytest.approx(0.5)
    # shed/lost excluded from the latency distribution: p50 over the
    # three completions only (0.1/0.2/0.3 s)
    assert s["latency_ms"]["p50"] == pytest.approx(200.0)
    # goodput counts only in-deadline completions: 2 / 10 s
    assert s["goodput_qps"] == pytest.approx(0.2)
    assert s["by_class"]["interactive"]["n"] == 6


@pytest.mark.ingress
def test_summarize_degenerate_inputs():
    # zero outcomes at all: every count 0, every percentile None (not
    # NaN — NaN would poison downstream JSON and burn-rate math)
    s = loadgen.summarize([], wall_s=5.0)
    assert (s["n"], s["completed"], s["shed"], s["rejected"]) == (0, 0, 0, 0)
    assert s["goodput_qps"] == 0.0 and s["shed_ratio"] == 0.0
    assert s["latency_ms"] == {"p50": None, "p95": None, "p99": None}
    assert s["by_class"] == {}

    # all-shed trace: zero completions but nonzero rows — shed_ratio
    # is 1.0 and the latency distribution stays empty/None
    shed_only = [
        Outcome(slo="batch", terminal="shed", reason="queue_full")
        for _ in range(4)
    ]
    s = loadgen.summarize(shed_only, wall_s=10.0)
    assert s["completed"] == 0 and s["shed"] == 4
    assert s["shed_ratio"] == pytest.approx(1.0)
    assert s["goodput_qps"] == 0.0
    assert s["latency_ms"]["p99"] is None
    assert s["by_class"]["batch"]["shed_ratio"] == pytest.approx(1.0)

    # single completed sample: every percentile collapses to it
    one = [Outcome(slo="interactive", terminal="completed", e2e_s=0.25,
                   deadline_met=True)]
    s = loadgen.summarize(one, wall_s=10.0)
    assert s["latency_ms"]["p50"] == pytest.approx(250.0)
    assert s["latency_ms"]["p95"] == pytest.approx(250.0)
    assert s["latency_ms"]["p99"] == pytest.approx(250.0)

    # zero wall: goodput guarded to 0.0, never a division error
    assert loadgen.summarize(one, wall_s=0.0)["goodput_qps"] == 0.0


# ----------------------------------------------------------------------
# admission math (pure, deterministic)
# ----------------------------------------------------------------------


@pytest.mark.ingress
def test_shed_reason_unit():
    # queue_full: per-class backpressure bound
    assert shed_reason(
        now=0.0, deadline=2.0, pending_in_class=256, queue_limit=256,
        backlog_batches=0, slots=2, est_batch_exec_s=0.05,
    ) == "queue_full"
    # deadline_unmeetable: projected wait + exec exceeds deadline
    assert shed_reason(
        now=0.0, deadline=2.0, pending_in_class=0, queue_limit=256,
        backlog_batches=100, slots=2, est_batch_exec_s=0.1,
    ) == "deadline_unmeetable"  # 100/2*0.1 + 0.1 = 5.1 > 2
    # admit: slack is positive
    assert shed_reason(
        now=0.0, deadline=2.0, pending_in_class=10, queue_limit=256,
        backlog_batches=4, slots=2, est_batch_exec_s=0.1,
    ) is None
    # no measured exec yet (cold coordinator / fresh promotion): the
    # slack check is SKIPPED — err permissive, never shed on a prior
    assert shed_reason(
        now=0.0, deadline=2.0, pending_in_class=0, queue_limit=256,
        backlog_batches=10_000, slots=1, est_batch_exec_s=None,
    ) is None


@pytest.mark.ingress
def test_resolve_class_unknown_lists_known():
    assert resolve_class("interactive") is DEFAULT_CLASSES["interactive"]
    with pytest.raises(KeyError) as ei:
        resolve_class("platinum")
    assert "interactive" in str(ei.value)


# ----------------------------------------------------------------------
# continuous batch formation (injected clock)
# ----------------------------------------------------------------------


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def step(self, dt):
        self.t += dt


def _req(clock, i, slo=None, model="m"):
    slo = slo or SLOClass("interactive", deadline_s=2.0, linger_s=0.02)
    return PendingRequest(
        id=f"r{i}", client="c", model=model, slo=slo, file="f.jpeg",
        payload=None, session=None, stream=False,
        arrival=clock.t, deadline=clock.t + slo.deadline_s,
    )


@pytest.mark.ingress
def test_former_full_batch_dispatches_immediately():
    clock = Clock()
    f = BatchFormer(lambda m: 4, lambda m, n: 0.01 * n, now=clock)
    for i in range(4):
        f.add(_req(clock, i), None)
    due = f.due(hungry_models=set())
    assert len(due) == 1 and len(due[0].reqs) == 4
    assert f.pending() == 0


@pytest.mark.ingress
def test_former_hungry_pipeline_dispatches_partial_after_linger():
    clock = Clock()
    f = BatchFormer(lambda m: 8, lambda m, n: 0.01 * n, now=clock)
    f.add(_req(clock, 0), None)
    # not hungry, plenty of slack, not full: keeps forming
    assert f.due(hungry_models=set()) == []
    # hungry but inside the linger window: still coalescing
    assert f.due(hungry_models={"m"}) == []
    clock.step(0.05)  # past linger_s=0.02
    due = f.due(hungry_models={"m"})
    assert len(due) == 1 and len(due[0].reqs) == 1
    # light load + free pipeline = single-request latency, by design


@pytest.mark.ingress
def test_former_slack_expiry_dispatches_partial():
    clock = Clock()
    f = BatchFormer(lambda m: 8, lambda m, n: 0.1, now=clock)
    f.add(_req(clock, 0), None)
    # never hungry (pipeline busy): holds until the deadline-derived
    # slack expires — dispatch_by = deadline - 1.5*est - 0.05
    assert f.due(hungry_models=set()) == []
    clock.step(1.70)
    assert f.due(hungry_models=set()) == []
    clock.step(0.15)  # past 100 + 2.0 - 0.15 - 0.05 = 101.8
    due = f.due(hungry_models=set())
    assert len(due) == 1
    assert not f.forming


def _held_then_fed(f, clock):
    """Linger runs out at 100.02; the ticks at 100.03 and 100.5 find no
    worker free; the one at 100.8 does. The first request waited for a
    worker from the first tick that held it, the second from its own
    (later) arrival."""
    f.add(_req(clock, 0), None)
    clock.step(0.03)
    assert f.due(hungry_models=set()) == []       # held: no worker free
    clock.step(0.37)
    f.add(_req(clock, 1), None)                   # arrives at 100.4
    clock.step(0.10)
    assert f.due(hungry_models=set()) == []
    clock.step(0.30)
    (fb,) = f.due(hungry_models={"m"})
    return fb, [0.77, 0.40]


def _fed_at_once(f, clock):
    """A worker is free at the first tick past the linger: no wait for
    one, whatever the linger and the tick took."""
    f.add(_req(clock, 0), None)
    assert f.due(hungry_models={"m"}) == []       # inside the linger
    clock.step(0.05)
    (fb,) = f.due(hungry_models={"m"})
    return fb, [0.0]


def _held_until_slack_ran_out(f, clock):
    """Never hungry: the batch leaves when its slack expires, and all
    of the time past the first held tick was a wait for a worker."""
    f.add(_req(clock, 0), None)
    clock.step(0.05)
    assert f.due(hungry_models=set()) == []
    clock.step(1.90)                              # dispatch_by = 101.935
    (fb,) = f.due(hungry_models=set())
    return fb, [1.90]


def _full_slices_carry_the_wait(f, clock):
    """A held batch that fills leaves in device-batch slices; each
    request keeps the wait it had."""
    f.add(_req(clock, 0), None)
    clock.step(0.05)
    assert f.due(hungry_models=set()) == []
    clock.step(0.25)
    for i in range(1, 8):
        f.add(_req(clock, i), None)
    clock.step(0.10)
    (fb,) = f.due(hungry_models=set())
    return fb, [0.35] + [0.10] * 7


@pytest.mark.ingress
@pytest.mark.parametrize("case", [
    _held_then_fed, _fed_at_once, _held_until_slack_ran_out,
    _full_slices_carry_the_wait,
], ids=lambda f: f.__name__.lstrip("_"))
def test_former_worker_wait(case):
    """`worker_wait`: of a request's formation, the part it sat in a
    batch whose linger had run out and that only the want of a free
    worker still held. Never negative, never more than its own wait."""
    clock = Clock()
    f = BatchFormer(lambda m: 8, lambda m, n: 0.01 * n, now=clock)
    fb, want = case(f, clock)
    assert [r.worker_wait for r in fb.reqs] == pytest.approx(want)
    for r in fb.reqs:
        assert 0.0 <= r.worker_wait <= clock.t - r.arrival


@pytest.mark.ingress
def test_former_fixed_mode_never_waits_for_a_worker():
    """The fixed baseline holds a batch for fullness, not for a worker."""
    clock = Clock()
    f = BatchFormer(lambda m: 4, lambda m, n: 0.01, mode="fixed", now=clock)
    f.add(_req(clock, 0), None)
    clock.step(1.0)
    assert f.due(hungry_models=set()) == []
    clock.step(1.1)
    (fb,) = f.due(hungry_models=set())
    assert fb.reqs[0].worker_wait == 0.0


@pytest.mark.ingress
def test_former_fixed_mode_waits_for_full():
    clock = Clock()
    f = BatchFormer(lambda m: 4, lambda m, n: 0.01, mode="fixed", now=clock)
    f.add(_req(clock, 0), None)
    clock.step(1.9)  # hungry or not, fixed mode ignores both signals
    assert f.due(hungry_models={"m"}) == []
    clock.step(0.2)  # past the ABSOLUTE deadline: late, but bounded
    assert len(f.due(hungry_models=set())) == 1
    # a second batch fills: dispatches at once even in fixed mode
    for i in range(4):
        f.add(_req(clock, 10 + i), None)
    assert len(f.due(hungry_models=set())) == 1


@pytest.mark.ingress
def test_scheduler_affinity_same_target_never_double_assigns():
    """Two queued batches sharing one affinity target: exactly one
    lands on it, the other pours onto a different free worker — a
    double assignment would overwrite in_progress and orphan the
    first batch forever (review-caught)."""
    from dml_tpu.jobs.cost_model import ModelCost
    from dml_tpu.jobs.scheduler import Scheduler

    s = Scheduler()
    s.costs["m"] = ModelCost(0.0, 0.0, 0.01, batch_size=2)
    s.submit_job(1, "m", ["a"], 2, "c", batch_size=2, affinity="W1")
    s.submit_job(2, "m", ["a"], 2, "c", batch_size=2, affinity="W1")
    out = s.schedule(["W1", "W2"])
    workers = [x.worker for x in out]
    assert sorted(workers) == ["W1", "W2"]
    assert s.in_progress["W1"].job_id == 1  # first in queue wins W1
    assert s.in_progress["W2"].job_id == 2
    # every queued batch is tracked somewhere — nothing orphaned
    assert not s.all_queued_batches()


@pytest.mark.ingress
def test_former_affinity_keys_separate_batches():
    clock = Clock()
    f = BatchFormer(lambda m: 8, lambda m, n: 0.01, now=clock)
    f.add(_req(clock, 0), "nodeA")
    f.add(_req(clock, 1), "nodeB")
    f.add(_req(clock, 2), None)
    assert len(f.forming) == 3  # (model, class, affinity) buckets


# ----------------------------------------------------------------------
# end-to-end on chaos.LocalCluster
# ----------------------------------------------------------------------


@contextlib.asynccontextmanager
async def _cluster(n, base_port, tmp_path, **kw):
    from dml_tpu.cluster.chaos import LocalCluster

    root = str(tmp_path / f"ingr_{base_port}")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    c = LocalCluster(n, root, base_port, with_ingress=True, **kw)
    try:
        await c.start()
        await c.wait_for(c.converged, 15.0, "initial convergence")
        yield c
    finally:
        await c.stop()


@pytest.mark.ingress
def test_request_end_to_end_inline_results(tmp_path):
    """Per-request serving through the real pipeline: admitted ->
    formed -> scheduled -> completed, with the result riding the batch
    ACK (no replicated-store output object per ingress batch) and the
    request_* metrics moving."""
    from dml_tpu.cluster import chaos
    from dml_tpu.observability import METRICS

    async def run():
        async with _cluster(3, 24651, tmp_path) as c:
            client = c.client()
            await client.store.put_bytes("img.jpeg", b"stub-bytes",
                                         timeout=20.0)
            terms = await asyncio.gather(*(
                client.ingress.request(chaos.STUB_MODEL, timeout=30.0)
                for _ in range(6)
            ))
            for t in terms:
                assert t["ok"] and t["terminal"] == "completed"
                assert t["result"] == [
                    {"label": chaos.STUB_MODEL, "score": 1.0}
                ]
                assert t["deadline_met"] in (True, False)
                # of the formation wait, the part spent waiting for a
                # free worker: never negative, never more than it
                st = t["stages"]
                assert 0.0 <= st["worker_wait"] <= st["formation"]
            # inline results: NO output_* store objects were created
            leader = next(
                sn for sn in c.nodes.values() if sn.node.is_leader
            )
            outs = [
                f for f in leader.store.metadata.all_files()
                if f.startswith("output_")
            ]
            assert outs == []
            snap = METRICS.snapshot()
            cs = snap["counters"]
            admitted = sum(
                v for k, v in cs.items()
                if k.startswith("request_admitted_total")
            )
            completed = sum(
                v for k, v in cs.items()
                if k.startswith("request_completed_total")
            )
            assert admitted >= 6 and completed >= 6
            assert any(
                k.startswith("request_e2e_latency_seconds")
                for k in snap["histograms"]
            )
            assert any(
                k.startswith("request_worker_wait_seconds")
                for k in snap["histograms"]
            )
            # operator surface
            stats = client.ingress.stats()
            assert stats["mode"] == "continuous"
            assert "interactive" in stats["classes"]

    asyncio.run(run())


@pytest.mark.ingress
def test_shed_is_immediate_typed_rejection(tmp_path):
    """A request the door refuses gets a TYPED rejection right away —
    reason string, shed flag — never a timeout."""
    import time

    from dml_tpu.cluster import chaos
    from dml_tpu.ingress.slo import SLOClass

    tiny = {
        "interactive": SLOClass("interactive", deadline_s=2.0,
                                queue_limit=2, linger_s=0.02),
    }

    async def run():
        async with _cluster(
            3, 24671, tmp_path, ingress_classes=tiny
        ) as c:
            client = c.client()
            await client.store.put_bytes("img.jpeg", b"stub-bytes",
                                         timeout=20.0)

            async def one():
                t0 = time.monotonic()
                try:
                    rid = await client.ingress.submit(
                        chaos.STUB_MODEL, timeout=8.0
                    )
                    await client.ingress.wait(rid, timeout=20.0)
                    return ("completed", time.monotonic() - t0, None)
                except RequestRejected as e:
                    return ("shed" if e.shed else "rejected",
                            time.monotonic() - t0, e.reason)

            results = await asyncio.gather(*(one() for _ in range(12)))
            sheds = [r for r in results if r[0] == "shed"]
            dones = [r for r in results if r[0] == "completed"]
            assert sheds, "queue_limit=2 under a 12-wide burst must shed"
            assert dones, "admitted requests must still complete"
            for kind, dt, reason in sheds:
                assert reason == "queue_full"
                assert dt < 2.0, "a shed must be immediate, not a timeout"

    asyncio.run(run())


@pytest.mark.ingress
def test_session_affinity_follow_up_lands_on_same_worker(tmp_path):
    """Multi-turn: the second turn of a session is served by the node
    that served the first (the one holding its KV state)."""
    from dml_tpu.ingress.streaming import STUB_LM_MODEL

    async def run():
        async with _cluster(4, 24691, tmp_path) as c:
            client = c.client()
            await client.store.put_bytes("p1.prompt.txt", b"1 2 3\n",
                                         timeout=20.0)
            t1 = await client.ingress.request(
                STUB_LM_MODEL, session="sess-A", timeout=30.0
            )
            assert t1["ok"] and t1["worker"]
            # quiet cluster: the affinity preference is deterministic
            for _ in range(3):
                t2 = await client.ingress.request(
                    STUB_LM_MODEL, session="sess-A", timeout=30.0
                )
                assert t2["ok"]
                assert t2["worker"] == t1["worker"]

    asyncio.run(run())


@pytest.mark.ingress
def test_streaming_tokens_arrive_over_data_plane(tmp_path):
    """A streaming LM request's tokens arrive over the worker's TCP
    data plane while the batch decodes, and concatenate to exactly
    the completed result."""
    from dml_tpu.ingress.streaming import STUB_LM_MODEL

    async def run():
        async with _cluster(3, 24711, tmp_path) as c:
            client = c.client()
            rid = await client.ingress.submit(
                STUB_LM_MODEL, payload="1 2 3", stream=True, timeout=10.0
            )
            toks = await client.ingress.stream_text(rid, timeout=20.0)
            term = await client.ingress.wait(rid, timeout=20.0)
            assert term["ok"]
            assert toks, "tokens must stream, not just the terminal"
            assert "".join(toks).strip() == term["result"]["text"]

    asyncio.run(run())


@pytest.mark.ingress
def test_streaming_shared_store_input_both_clients_get_tokens(tmp_path):
    """Two streaming requests naming the SAME store input in one
    formation window must EACH get a live token stream — per-request
    feeds, not per-input (a file-keyed map would drop one READY)."""
    from dml_tpu.ingress.streaming import STUB_LM_MODEL

    async def run():
        async with _cluster(3, 24771, tmp_path) as c:
            client = c.client()
            await client.store.put_bytes("shared.prompt.txt", b"1 2 3\n",
                                         timeout=20.0)
            rids = await asyncio.gather(*(
                client.ingress.submit(
                    STUB_LM_MODEL, store_name="shared.prompt.txt",
                    stream=True, timeout=10.0,
                )
                for _ in range(2)
            ))
            tok_lists = await asyncio.gather(*(
                client.ingress.stream_text(rid, timeout=20.0)
                for rid in rids
            ))
            terms = await asyncio.gather(*(
                client.ingress.wait(rid, timeout=20.0) for rid in rids
            ))
            for toks, term in zip(tok_lists, terms):
                assert term["ok"]
                assert toks, "every streaming request gets tokens"
                assert "".join(toks).strip() == term["result"]["text"]

    asyncio.run(run())


@pytest.mark.ingress
def test_demoted_router_drops_dispatched_ledger(tmp_path):
    """A router that is NOT leader must not hold dispatched-request
    residue: stale _active / _pending_by_class from a lost leadership
    would make a later re-promotion shed live traffic as queue_full
    against phantom in-flight counts. The formation loop's demotion
    sweep clears it (the new leader owns those requests via the
    standby relay)."""
    import time

    from dml_tpu.ingress.router import _RequestState

    async def run():
        async with _cluster(3, 24791, tmp_path) as c:
            follower = next(
                sn for sn in c.nodes.values()
                if not sn.node.is_leader and sn.ingress is not None
            )
            ing = follower.ingress
            now = time.monotonic()
            r = PendingRequest(
                id="stale-1", client=follower.node.me.unique_name,
                model="StubModel", slo=DEFAULT_CLASSES["interactive"],
                file="img.jpeg", payload=None, session=None,
                stream=False, arrival=now, deadline=now + 2.0,
            )
            ing._active["stale-1"] = _RequestState(
                req=r, state="dispatched", job_id=99
            )
            ing._by_job[99] = ["stale-1"]
            ing._pending_by_class["interactive"] = 7
            await asyncio.sleep(ing.tick_s * 5)
            assert ing._active == {}
            assert ing._by_job == {}
            assert ing._pending_by_class.get("interactive", 0) == 0

    asyncio.run(run())


@pytest.mark.ingress
@pytest.mark.chaos
def test_leader_failover_mid_traffic_exactly_once(tmp_path):
    """Kill the leader while open-loop traffic is in flight: every
    submitted request reaches EXACTLY ONE terminal — completed, shed,
    typed-rejected, or client-side LOST conversion — never a silent
    hang, and the cluster resumes completing after the new leader
    takes over."""
    from dml_tpu.cluster import chaos

    async def run():
        async with _cluster(4, 24731, tmp_path) as c:
            client = c.client()
            await client.store.put_bytes("img.jpeg", b"stub-bytes",
                                         timeout=20.0)
            # warm one request through so costs are measured
            await client.ingress.request(chaos.STUB_MODEL, timeout=30.0)
            leader0 = c.leader_uname()
            assert leader0 is not None
            trace = open_loop_trace(3, duration_s=6.0, rate_qps=8.0,
                                    model=chaos.STUB_MODEL)

            async def submit(a):
                # the same shared driver bench + CLI use
                return await loadgen.drive_one(
                    client.ingress, a,
                    submit_timeout=8.0, wait_timeout=30.0,
                )

            async def killer():
                await asyncio.sleep(1.5)
                await c.crash_node(leader0)

            kill = asyncio.ensure_future(killer())
            outcomes, wall = await loadgen.run_open_loop(submit, trace)
            await kill
            # exactly one terminal per submitted request
            assert len(outcomes) == len(trace.arrivals)
            assert all(
                o.terminal in ("completed", "shed", "rejected", "lost")
                for o in outcomes
            )
            completed = [o for o in outcomes if o.terminal == "completed"]
            assert completed, "traffic must complete across the failover"
            # observational exactly-once: no router saw a late terminal
            # disagree with the settled one, and every completion
            # carried its result (never a hollow ok=True)
            assert all(o.has_result for o in completed)
            assert sum(
                sn.ingress.terminal_conflicts
                for sn in c.nodes.values() if sn.ingress is not None
            ) == 0
            # the cluster converged on a new leader and still serves
            leaders = {sn.node.leader_unique for sn in c.nodes.values()}
            assert len(leaders) == 1 and None not in leaders
            post = await client.ingress.request(
                chaos.STUB_MODEL, timeout=30.0
            )
            assert post["ok"]

    asyncio.run(run())


# ----------------------------------------------------------------------
# wait_job dropped-push regression (ISSUE 7 satellite)
# ----------------------------------------------------------------------


@pytest.mark.ingress
def test_wait_job_survives_dropped_success_push(tmp_path):
    """The SUBMIT_JOB_REQUEST_SUCCESS completion push is a single
    unacked datagram; if it is lost the client-side status re-poll
    fallback must complete wait_job anyway (service.py wait_job) —
    the push is dropped deterministically at the client's dispatch
    layer here."""
    from dml_tpu.cluster import chaos
    from dml_tpu.cluster.wire import MsgType

    async def run():
        async with _cluster(3, 24751, tmp_path) as c:
            client = c.client()
            await client.store.put_bytes("img.jpeg", b"stub-bytes",
                                         timeout=20.0)

            async def drop_push(msg, addr):
                return  # the lost-datagram case, made deterministic

            # replace (not register: Node refuses duplicates) the
            # client's success-push handler with a black hole
            client.node._handlers[
                MsgType.SUBMIT_JOB_REQUEST_SUCCESS
            ] = drop_push
            job_id = await client.jobs.submit_job(
                chaos.STUB_MODEL, 16, timeout=15.0, retries=5
            )
            done = await asyncio.wait_for(
                client.jobs.wait_job(job_id, timeout=30.0), 30.0
            )
            assert done["total_queries"] == 16

    asyncio.run(run())


@pytest.mark.ingress
def test_submit_cancellation_does_not_leak_futures(monkeypatch):
    """race-yield-hazard fix (ISSUE 13): a CANCELLED submit — a
    wait_for timeout around it, client teardown — must pop the future
    and stream queue it registered before awaiting admission.
    CancelledError flies past `except Exception`, so only the
    try/finally form cleans up on that path."""
    from types import SimpleNamespace

    from dml_tpu.ingress import router as router_mod

    async def run():
        node = SimpleNamespace(
            register=lambda *a, **k: None,
            on_became_leader_cbs=[],
            on_node_failed_cbs=[],
            new_rid=lambda: "n#1",
            me=SimpleNamespace(unique_name="n:1"),
        )
        jobs = SimpleNamespace(node=node, store=None, on_job_done_cbs=[])
        r = router_mod.RequestRouter(jobs)

        hang = asyncio.Event()

        async def never(*a, **k):
            await hang.wait()

        monkeypatch.setattr(router_mod, "leader_retry", never)
        t = asyncio.create_task(r.submit("m", stream=True))
        await asyncio.sleep(0.05)
        assert len(r._futs) == 1 and len(r._streams) == 1
        t.cancel()
        with pytest.raises(asyncio.CancelledError):
            await t
        assert len(r._futs) == 0
        assert len(r._streams) == 0
        # the submit may have been ADMITTED with only its ACK lost:
        # the cancelled client records the lost classification, so a
        # late completed push counts as a terminal conflict instead of
        # silently evading the exactly-once verdict
        assert list(r._client_terminal.values()) == ["lost"]

    asyncio.run(run())
