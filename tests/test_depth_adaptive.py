"""The probe-adaptive pipeline-depth controller (ISSUE 4 tentpole):
pure-logic determinism under an injected clock, the commit/fallback/
drift/abort state machine, the service wiring on the in-process
chaos.LocalCluster (tier-1-speed smoke: one full probe cycle through
the real coordinator ACK path) and a leader kill mid-probe."""

import asyncio
import contextlib
import os
import shutil

import pytest

from dml_tpu.jobs.scheduler import DepthController


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def step(self, dt):
        self.t += dt
        return self.t


def drive(ctl, clock, acks):
    """Feed (dt, n_images, fetch, infer, put) acks; returns depths."""
    out = []
    for dt, n, f, i, p in acks:
        clock.step(dt)
        out.append(ctl.on_ack(n, fetch=f, infer=i, put=p))
    return out


PHASE = lambda dt, n=8: [(dt, n, 0.01, 0.05, 0.001)]  # noqa: E731


def make_probed(d1_dt, d2_dt, probe_batches=3):
    """A controller driven through one full probe cycle with the given
    per-ack spacing per phase; returns (ctl, clock)."""
    clock = Clock()
    ctl = DepthController(probe_batches=probe_batches, now=clock)
    assert ctl.tick(4 * probe_batches) in (1, 2)
    assert ctl.state == "probing"
    drive(ctl, clock, PHASE(d1_dt) * (probe_batches + 1))  # depth-1 phase
    drive(ctl, clock, PHASE(d2_dt) * (probe_batches + 1))  # depth-2 phase
    return ctl, clock


def test_probe_is_deterministic():
    """Identical ack streams commit identical verdicts — the probe is
    a pure function of the stream + clock (seeded-stub property the
    cluster smoke below relies on)."""
    a, _ = make_probed(0.10, 0.05)
    b, _ = make_probed(0.10, 0.05)
    assert a.state == b.state == "settled"
    assert a.depth == b.depth == 2
    assert a.explain() == b.explain()
    assert a.last_probe["qps_depth1"] == b.last_probe["qps_depth1"]


def test_depth_falls_back_to_1_when_overlap_loses():
    """The r5 regime: depth-2 measures SLOWER -> commit depth 1 (the
    cheap sync path), with the reason recorded."""
    ctl, _ = make_probed(0.05, 0.10)
    assert ctl.state == "settled" and ctl.depth == 1
    assert ctl.last_probe["winner"] == 1
    assert "overlap did not pay" in ctl.last_probe["reason"]


def test_noise_margin_prefers_depth_1():
    """A depth-2 'win' inside the noise margin is not a win: the
    overlap state machine must pay for itself."""
    ctl, _ = make_probed(0.100, 0.098)  # 1.02x < 1.05 margin
    assert ctl.depth == 1
    ctl2, _ = make_probed(0.100, 0.080)  # 1.25x: a real win
    assert ctl2.depth == 2


def test_commit_then_drift_reprobes():
    """Stage walls drifting past drift_ratio re-arm the probe; the
    next sufficient backlog starts a fresh cycle tagged 'drift'."""
    ctl, clock = make_probed(0.10, 0.05)
    assert ctl.state == "settled" and ctl.signature["fetch"] > 0
    # trailing window full of 5x-fetch acks -> drift
    for _ in range(2 * ctl.probe_batches):
        clock.step(0.05)
        ctl.on_ack(8, fetch=0.05, infer=0.05, put=0.001)
    assert ctl.state == "warmup" and ctl.reprobes == 1
    assert ctl.tick(4 * ctl.probe_batches) == 1  # probing restarts at d1
    assert ctl.state == "probing"
    drive(ctl, clock, PHASE(0.05) * (ctl.probe_batches + 1))
    drive(ctl, clock, PHASE(0.10) * (ctl.probe_batches + 1))
    assert ctl.state == "settled" and ctl.probes == 2
    assert ctl.last_probe["trigger"] == "drift"


def test_steady_walls_do_not_reprobe():
    """Acks matching the committed signature keep the commitment."""
    ctl, clock = make_probed(0.10, 0.05)
    for _ in range(6 * ctl.probe_batches):
        clock.step(0.05)
        ctl.on_ack(8, fetch=0.01, infer=0.05, put=0.001)
    assert ctl.state == "settled" and ctl.reprobes == 0


def test_ttl_reprobe_and_phase_abort():
    clock = Clock()
    ctl = DepthController(probe_batches=2, reprobe_ttl_s=100.0,
                          probe_phase_timeout_s=10.0, now=clock)
    ctl.tick(12)
    drive(ctl, clock, PHASE(0.1) * 3 + PHASE(0.2) * 3)
    assert ctl.state == "settled" and ctl.depth == 1
    clock.step(101.0)
    ctl.tick(0)  # TTL re-arms even with no backlog to probe yet
    assert ctl.state == "warmup"
    ctl.tick(12)
    assert ctl.state == "probing"
    clock.step(0.1)
    ctl.on_ack(8)  # transition ack starts the phase clock
    clock.step(11.0)  # ...then the work drains away
    ctl.tick(12)  # timeout -> abort, fall back to the last verdict
    assert ctl.aborted_probes == 1
    assert ctl.depth == 1  # last commit's winner


def test_zero_ack_probe_phase_times_out():
    """A probe whose phase never receives ANY ACK (workers died right
    after it started) must still abort on the phase timeout — TTL
    only covers 'settled', so without the phase-start wall the
    controller would wedge in 'probing' forever."""
    clock = Clock()
    ctl = DepthController(probe_batches=2, probe_phase_timeout_s=10.0,
                          now=clock)
    ctl.tick(12)
    assert ctl.state == "probing"
    clock.step(11.0)  # no on_ack at all
    ctl.tick(12)
    assert ctl.aborted_probes == 1
    assert ctl.depth == 1  # nothing ever committed: cheap sync path
    # abort imposes a cooldown: the SAME standing backlog must not
    # re-begin the probe immediately (a stalled pool would otherwise
    # cycle probe/abort forever, flapping the depth)
    assert ctl.state == "warmup"
    ctl.tick(12)
    assert ctl.state == "warmup"
    clock.step(10.5)  # past the cooldown
    ctl.tick(12)
    assert ctl.state == "probing"


def test_slow_but_flowing_phase_does_not_abort():
    """The phase timeout measures from the LAST ACK, not the first —
    a congested link delivering an ACK every 8 s (exactly where
    depth-2 overlap wins) is a measurement in progress, not a stall."""
    clock = Clock()
    ctl = DepthController(probe_batches=5, probe_phase_timeout_s=10.0,
                          now=clock)
    ctl.tick(24)
    for _ in range(6):  # 48 s of phase wall at 8 s/ACK: no abort
        clock.step(8.0)
        ctl.on_ack(8, fetch=0.01, infer=0.05, put=0.001)
        ctl.tick(24)
    assert ctl.aborted_probes == 0
    assert ctl._phase_rates.get(1)  # the d1 phase completed


def test_per_worker_transition_discard():
    """Each phase discards the FIRST ACK from EVERY worker — on a
    multi-worker pool up to W in-flight batches predate the depth
    switch, and one global discard would count wrong-depth batches
    into the phase rate."""
    clock = Clock()
    ctl = DepthController(probe_batches=2, now=clock)
    ctl.tick(12)
    # depth-1 phase: w1's and w2's first ACKs (stragglers, absurdly
    # fast) are BOTH discarded; the counted acks set the honest rate
    for worker, dt in (("w1", 0.001), ("w2", 0.001),
                       ("w1", 0.1), ("w2", 0.1)):
        clock.step(dt)
        ctl.on_ack(8, fetch=0.01, infer=0.05, put=0.001, worker=worker)
    assert ctl._phase_rates[1] == pytest.approx(16 / 0.2)
    # depth-2 phase: same shape
    for worker, dt in (("w1", 0.001), ("w2", 0.001),
                       ("w1", 0.05), ("w2", 0.05)):
        clock.step(dt)
        ctl.on_ack(8, fetch=0.01, infer=0.05, put=0.001, worker=worker)
    assert ctl.state == "settled" and ctl.depth == 2
    assert ctl.last_probe["qps_depth2"] == pytest.approx(16 / 0.1)


def test_unprobed_default_is_depth_1():
    """Un-probed (short jobs, not enough backlog), the controller
    serves the reference-faithful cheap sync path — never the mode
    both r5 captures measured as a pessimization."""
    ctl = DepthController(now=Clock())
    assert ctl.depth == 1 and ctl.state == "warmup"


def test_insufficient_backlog_never_probes():
    clock = Clock()
    ctl = DepthController(probe_batches=3, now=clock)
    for _ in range(20):
        assert ctl.tick(3) == ctl.depth  # < min_probe_backlog (8)
        clock.step(0.1)
        ctl.on_ack(8)
    assert ctl.state == "warmup" and ctl.probes == 0


# ----------------------------------------------------------------------
# service wiring on the in-process cluster (chaos.LocalCluster — the
# same chassis the soaks validate)
# ----------------------------------------------------------------------


@contextlib.asynccontextmanager
async def _cluster(n, base_port, tmp_path):
    from dml_tpu.cluster.chaos import LocalCluster

    root = str(tmp_path / f"adapt_{base_port}")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    c = LocalCluster(n, root, base_port)
    try:
        await c.start()
        await c.wait_for(c.converged, 15.0, "initial convergence")
        for sn in c.nodes.values():
            ctl = sn.jobs.depth_ctl
            assert ctl is not None  # adaptive is the product default
            ctl.probe_batches = 2
            ctl.min_probe_backlog = 4
        yield c
    finally:
        await c.stop()


@pytest.mark.adaptive
def test_probe_cycle_smoke_on_local_cluster(tmp_path):
    """Tier-1-speed smoke: one full probe cycle through the REAL
    coordinator ACK path on the stub-backend cluster — the controller
    path can never silently rot to untested (ISSUE 4 CI satellite)."""
    from dml_tpu.cluster import chaos
    from dml_tpu.observability import METRICS

    async def run():
        async with _cluster(3, 23400, tmp_path) as c:
            client = c.client()
            await client.store.put_bytes("img.jpeg", b"stub-bytes",
                                         timeout=20.0)
            leader = next(
                sn for sn in c.nodes.values() if sn.node.is_leader
            )
            # 64 queries / batch 8 = 8 batches >= the 4-batch backlog
            job_id = await client.jobs.submit_job(
                chaos.STUB_MODEL, 64, timeout=15.0, retries=5
            )
            await client.jobs.wait_job(job_id, timeout=30.0)
            ctl = leader.jobs.depth_ctl
            assert ctl.state == "settled", ctl.explain()
            assert ctl.probes == 1 and ctl.depth in (1, 2)
            assert ctl.last_probe["qps_depth1"] > 0
            assert ctl.last_probe["qps_depth2"] > 0
            # the scheduler runs what the controller committed
            assert leader.jobs.scheduler.pipeline_depth == ctl.depth
            # operator surface: the breakdown verdict carries the why
            stats = leader.jobs.depth_controller_stats()
            assert stats["mode"] == "adaptive"
            assert "reason" in stats["last_probe"]
            assert "overlap_headroom_bound" in stats
            # observability: the gauge shows the committed depth and
            # the probe histogram saw both phases
            snap = METRICS.snapshot()
            assert snap["gauges"].get("jobs_pipeline_depth") == ctl.depth
            hist = {
                k: v for k, v in snap["histograms"].items()
                if k.startswith("jobs_depth_probe_qps")
            }
            assert any("depth=1" in k for k in hist)
            assert any("depth=2" in k for k in hist)

    asyncio.run(run())


@pytest.mark.adaptive
@pytest.mark.parametrize("joins,port", [(True, 23440), (False, 23460)],
                         ids=["joins_a_grid", "batch_after_batch"])
def test_the_probe_is_fed_only_by_models_it_governs(tmp_path, joins, port):
    """The same job (8 batches, over the 4-batch probe backlog) through a
    backend that declares `on_dispatch` and one that does not. The first
    kind is staged from the first round with the controller at its
    unprobed depth 1, and neither its backlog nor its ACKs reach the
    controller (no "depth 1" phase would be in force for it): it stays
    in warmup, no probe, no abort. The second kind is the controller's:
    one full probe cycle."""
    from _gridstub import MODEL, StubGrid, drain, grid_cluster, prompt_name

    grid = StubGrid(8)

    async def run():
        async with grid_cluster(3, port, tmp_path, grid, joins) as c:
            for sn in c.nodes.values():
                sn.jobs.depth_ctl.probe_batches = 2
                sn.jobs.depth_ctl.min_probe_backlog = 4
            client = c.client()
            for i in range(8):
                await client.store.put_bytes(
                    prompt_name(i, 3), b"1 2 3\n", timeout=20.0)
            leader = next(
                sn for sn in c.nodes.values() if sn.node.is_leader)
            ctl = leader.jobs.depth_ctl
            job_id = await client.jobs.submit_job(
                MODEL, 64, timeout=15.0, retries=5)
            staged_at_depth_1 = []
            done = await drain(
                grid, asyncio.ensure_future(
                    client.jobs.wait_job(job_id, timeout=30.0)),
                lambda: staged_at_depth_1.append(bool(
                    ctl.depth == 1 and ctl.state == "warmup"
                    and leader.jobs.scheduler.prefetch)))
            assert done["total_queries"] == 64
            if joins:
                assert any(staged_at_depth_1)
                assert ctl.state == "warmup" and ctl.depth == 1, (
                    ctl.explain())
                assert ctl.probes == 0 and ctl.aborted_probes == 0
                assert leader.jobs.scheduler.pipeline_depth == 1
            else:
                assert not any(staged_at_depth_1)
                assert ctl.state == "settled" and ctl.probes == 1, (
                    ctl.explain())

    asyncio.run(run())


@pytest.mark.adaptive
def test_leader_kill_mid_probe_recovers(tmp_path):
    """Chaos: the coordinator dies WHILE its controller is probing.
    Failover must complete the job exactly once (shadow relays), end
    with exactly one leader, and the new coordinator's own controller
    must still be operable — the invariant set the chaos sweeps
    enforce, scoped to the probe window."""
    from dml_tpu.cluster import chaos

    async def run():
        async with _cluster(4, 23420, tmp_path) as c:
            client = c.client()
            await client.store.put_bytes("img.jpeg", b"stub-bytes",
                                         timeout=20.0)
            leader = next(
                sn for sn in c.nodes.values() if sn.node.is_leader
            )
            leader_u = leader.node.me.unique_name
            n = 400  # 50 batches: the probe window is easy to hit
            job_id = await client.jobs.submit_job(
                chaos.STUB_MODEL, n, timeout=15.0, retries=5
            )
            for _ in range(600):
                if leader.jobs.depth_ctl.state == "probing":
                    break
                await asyncio.sleep(0.01)
            assert leader.jobs.depth_ctl.state == "probing"
            await c.crash_node(leader_u)  # abrupt: no goodbye
            done = await client.jobs.wait_job(job_id, timeout=60.0)
            assert done["total_queries"] == n
            # invariant sweep, scoped: exactly one converged leader...
            leaders = {
                sn.node.leader_unique for sn in c.nodes.values()
            }
            assert len(leaders) == 1 and None not in leaders
            new_leader = next(
                sn for sn in c.nodes.values() if sn.node.is_leader
            )
            # ...every query counted exactly once on the new leader...
            sched = new_leader.jobs.scheduler
            assert sched.query_counts.get(chaos.STUB_MODEL, 0) >= n
            assert sched.job_state(job_id).done
            # ...and the promoted coordinator's controller is live
            # (fresh state; it probes its own future jobs)
            assert new_leader.jobs.depth_ctl is not None
            assert new_leader.jobs.depth_controller_stats()["mode"] == (
                "adaptive"
            )

    asyncio.run(run())


@pytest.mark.adaptive
@pytest.mark.sharded
def test_group_ack_duplicates_freshness_gated(tmp_path):
    """ISSUE 5 satellite: duplicate/stale WORKER_TASK_REQUEST_ACKs
    from a worker-GROUP primary are freshness-gated out of both the
    scheduler counts and the DepthController exactly like single
    workers — a re-delivered group ACK (LinkShaper dup, resent task)
    must not inflate query totals, feed the drift trail, or re-arm a
    probe."""
    from dml_tpu.cluster import chaos
    from dml_tpu.cluster.wire import Message, MsgType
    from dml_tpu.config import MeshSpec, WorkerGroupSpec

    async def run():
        from dml_tpu.cluster.chaos import LocalCluster

        root = str(tmp_path / "grp_ack")
        os.makedirs(root)
        c = LocalCluster(
            5, root, 23440,
            worker_groups=[
                WorkerGroupSpec("tp0", ("H4", "H5"), MeshSpec(dp=1, tp=2))
            ],
        )
        try:
            await c.start()
            await c.wait_for(c.converged, 15.0, "initial convergence")
            for sn in c.nodes.values():
                sn.jobs.depth_ctl.probe_batches = 2
                sn.jobs.depth_ctl.min_probe_backlog = 4
            spec = c.spec
            h4 = spec.node_by_name("H4").unique_name
            client = c.nodes[spec.node_by_name("H3").unique_name]
            await client.store.put_bytes("img.jpeg", b"stub-bytes",
                                         timeout=20.0)
            job_id = await client.jobs.submit_job(
                chaos.STUB_MODEL, 64, timeout=15.0, retries=5
            )
            await client.jobs.wait_job(job_id, timeout=30.0)
            leader = c.nodes[c.leader_uname()]
            jobs = leader.jobs
            ctl = jobs.depth_ctl
            assert ctl.state == "settled", ctl.explain()
            before_counts = dict(jobs.scheduler.query_counts)
            before_trail = len(ctl._trail)
            before_probes = (ctl.probes, ctl.reprobes)
            before_cap = jobs.groups.capacity("tp0")
            # replay a completed batch's ACK from the group primary —
            # a duplicate delivery in every field that matters,
            # including a BOGUS capacity the directory must not ingest
            dup = Message(
                sender=h4, type=MsgType.WORKER_TASK_REQUEST_ACK,
                data={
                    "job": job_id, "batch": 0,
                    "model": chaos.STUB_MODEL, "n_images": 8,
                    "exec_time": 0.01, "fetch_time": 5.0,
                    "infer_time": 5.0, "put_time": 5.0,
                    "group": "tp0", "group_size": 2,
                    "group_capacity": 99.0,
                },
            )
            for _ in range(3):
                await jobs._h_task_ack(dup, None)
            # scheduler: no double-counted queries
            assert jobs.scheduler.query_counts == before_counts
            # directory: the stale advert did not revert the capacity
            assert jobs.groups.capacity("tp0") == before_cap
            # controller: the dup never reached the drift trail or
            # re-armed a probe
            assert len(ctl._trail) == before_trail
            assert (ctl.probes, ctl.reprobes) == before_probes
            assert ctl.state == "settled"
            # a STALE ack for a long-retired job is equally inert
            stale = Message(
                sender=h4, type=MsgType.WORKER_TASK_REQUEST_ACK,
                data={"job": 999, "batch": 0,
                      "model": chaos.STUB_MODEL, "n_images": 8,
                      "exec_time": 0.01, "fetch_time": 5.0,
                      "infer_time": 5.0, "put_time": 5.0},
            )
            await jobs._h_task_ack(stale, None)
            assert jobs.scheduler.query_counts == before_counts
            assert len(ctl._trail) == before_trail
        finally:
            await c.stop()

    asyncio.run(run())


def test_overlap_headroom_bound():
    from dml_tpu.jobs.cost_model import overlap_headroom

    # prep ≈ infer: overlap can near-halve the wall
    assert overlap_headroom(0.05, 0.05, 0.1, 0.0) == 2.0
    # infer-dominated (the r5 fast-link regime): nothing to hide
    assert overlap_headroom(0.001, 0.0, 0.1, 0.0) < 1.02
    assert overlap_headroom(0.0, 0.0, 0.0, 0.0) == 1.0
