"""End-to-end job-pipeline simulation (reference call stack §3.4:
submit-job -> schedule -> worker execute -> collect; §3.5 failover).

Same in-process localhost-cluster pattern as test_cluster_sim, with a
controllable fake inference backend so the pipeline is exercised
deterministically and without JAX compiles. The real engine path is
covered by test_engine/test_models; the seam between them
(JobService._engine_backend) is a thin adapter.
"""

import asyncio
import contextlib
import json
import os

import pytest

from dml_tpu.config import ClusterSpec, StoreConfig, Timing
from dml_tpu.cluster.introducer import IntroducerService
from dml_tpu.cluster.node import Node
from dml_tpu.cluster.store_service import StoreService
from dml_tpu.jobs.service import JobService

FAST = Timing(
    ping_interval=0.05,
    ack_timeout=0.15,
    cleanup_time=0.3,
    missed_acks_to_suspect=2,
    leader_rpc_timeout=5.0,
)


class FakeBackend:
    """Deterministic stand-in for the TPU engine: records calls, can
    be paused to hold a batch in flight (for preemption/failure tests)."""

    def __init__(self):
        self.calls = []
        self.gate = None  # asyncio.Event to block on, if set
        self.per_model_delay = {}
        self.fail_times = 0  # raise on the first N calls

    async def __call__(self, model, paths):
        self.calls.append((model, list(paths)))
        if self.fail_times > 0:
            self.fail_times -= 1
            raise RuntimeError("injected backend failure")
        if self.gate is not None:
            await self.gate.wait()
        delay = self.per_model_delay.get(model, 0.0)
        if delay:
            await asyncio.sleep(delay)
        # key by the FULL local path, mirroring the real engine
        # (InferenceResult.files carries str(path)) so the service's
        # sdfs re-keying is exercised production-shaped
        results = {
            p: [{"wnid": "n000", "label": model, "score": 1.0}]
            for p in paths
        }
        cost = {"load_time": 0.0, "first_query": 0.0, "per_query": 0.001}
        return results, 0.001 * len(paths), cost


class JobSim:
    def __init__(self, spec: ClusterSpec, tmp_path):
        self.spec = spec
        self.tmp_path = tmp_path
        self.dns = IntroducerService(spec)
        self.nodes = {}
        self.stores = {}
        self.jobs = {}
        self.backends = {}

    async def start_node(self, node_id):
        node = Node(self.spec, node_id)
        store = StoreService(node, root=str(self.tmp_path / f"store_{node_id.port}"))
        backend = FakeBackend()
        jobs = JobService(node, store, infer_backend=backend)
        await node.start()
        await store.start()
        await jobs.start()
        u = node_id.unique_name
        self.nodes[u], self.stores[u], self.jobs[u], self.backends[u] = (
            node, store, jobs, backend,
        )
        return node

    async def start_all(self):
        await self.dns.start()
        for n in self.spec.nodes:
            await self.start_node(n)

    async def stop_node(self, unique_name):
        await self.jobs.pop(unique_name).stop()
        await self.stores.pop(unique_name).stop()
        await self.nodes.pop(unique_name).stop()
        self.backends.pop(unique_name)

    async def stop_all(self):
        for u in list(self.nodes):
            await self.stop_node(u)
        await self.dns.stop()

    async def wait_for(self, cond, timeout=10.0, what="condition"):
        deadline = asyncio.get_running_loop().time() + timeout
        while asyncio.get_running_loop().time() < deadline:
            if cond():
                return
            await asyncio.sleep(0.02)
        raise AssertionError(f"timed out waiting for {what}")

    async def wait_converged(self, timeout=10.0):
        n = len(self.nodes)

        def ok():
            return all(
                node.joined
                and node.leader_unique is not None
                and len(node.membership.alive_nodes()) == n
                for node in self.nodes.values()
            )

        await self.wait_for(ok, timeout, f"convergence of {n} nodes")

    def by_name(self, name):
        return self.spec.node_by_name(name).unique_name

    async def seed_images(self, client_uname, count=4):
        """PUT `count` tiny fake .jpeg files into the store."""
        names = []
        for i in range(count):
            p = self.tmp_path / f"img_{i}.jpeg"
            p.write_bytes(b"\xff\xd8fakejpeg" + bytes([i]))
            await self.stores[client_uname].put(str(p), f"img_{i}.jpeg")
            names.append(f"img_{i}.jpeg")
        return names

    def coordinator_jobs(self) -> JobService:
        any_node = next(iter(self.nodes.values()))
        return self.jobs[any_node.leader_unique]


@contextlib.asynccontextmanager
async def cluster(n, tmp_path, base_port, **spec_kw):
    spec_kw.setdefault("timing", FAST)
    spec = ClusterSpec.localhost(
        n,
        base_port=base_port,
        introducer_port=base_port - 1,
        store=StoreConfig(root=str(tmp_path / "roots"),
                          download_dir=str(tmp_path / "dl")),
        **spec_kw,
    )
    sim = JobSim(spec, tmp_path)
    try:
        await sim.start_all()
        yield sim
    finally:
        await sim.stop_all()


async def test_submit_job_end_to_end(tmp_path):
    async with cluster(4, tmp_path, 22100) as sim:
        await sim.wait_converged()
        client_u = sim.by_name("H4")
        await sim.seed_images(client_u, 3)
        client = sim.jobs[client_u]

        job_id = await client.submit_job("ResNet50", 10)
        done = await client.wait_job(job_id, timeout=15.0)
        assert done["total_queries"] == 10

        # outputs merged from the store (reference get-output)
        out = tmp_path / "final.json"
        merged = await client.get_output(job_id, str(out))
        assert merged, "merged output must not be empty"
        assert json.loads(out.read_text()) == merged
        # every result row is a top-k list from the fake backend
        for rows in merged.values():
            assert rows[0]["label"] == "ResNet50"

        # C1 on the coordinator counted all 10 queries
        coord = sim.coordinator_jobs()
        assert coord.c1_stats()["ResNet50"]["total_queries"] == 10.0


@pytest.mark.parametrize("depth,jobs,port,slow", [
    (1, (("ResNet50", 40),), 22110, 0.0),
    (2, (("ResNet50", 96), ("InceptionV3", 64)), 22130, 0.0),
    (1, (("ResNet50", 64),), 22170, 0.4),
], ids=["one_job", "two_jobs_depth_2", "batches_outlast_the_resend"])
async def test_batch_timing_carries_the_leaders_dispatch_to_ack(
        tmp_path, depth, jobs, port, slow):
    """Every `batch_timing` entry holds `dispatch_to_ack`, the leader's
    own wall from the FIRST send of WORKER_TASK_REQUEST to the ACK (what
    the signal plane's liar check is handed), and it is never less than
    the exec wall the worker reports inside it: staged batches included
    (their clock starts at the stage's send), and batches that the
    resend loop sent again while they ran (a wall from the last re-send
    could never exceed the resend interval)."""
    async with cluster(4, tmp_path, port) as sim:
        await sim.wait_converged()
        for j in sim.jobs.values():
            j.set_pipeline_depth(depth)
            if slow:
                j.task_resend_after = 0.1
        for be in sim.backends.values():
            be.per_model_delay = {m: slow for m, _ in jobs}
        client_u = sim.by_name("H4")
        await sim.seed_images(client_u, 3)
        client = sim.jobs[client_u]
        ids = [await client.submit_job(m, n) for m, n in jobs]
        for job_id, (_, n) in zip(ids, jobs):
            done = await client.wait_job(job_id, timeout=20.0)
            assert done["total_queries"] == n
        rows = list(sim.coordinator_jobs().batch_timing)
        assert len(rows) >= sum(-(-n // 32) for _, n in jobs)
        for b in rows:
            assert b["dispatch_to_ack"] >= b["exec"] >= slow, b


async def test_submit_unknown_to_leader_fails_fast(tmp_path):
    """register_lm is per-node; if the leader never saw it, a submit
    for that model must be rejected at intake — not silently fed
    *.jpeg files until max_batch_failures burns the job."""
    async with cluster(3, tmp_path, 22150) as sim:
        await sim.wait_converged()
        coord_u = sim.coordinator_jobs().node.me.unique_name
        client_u = next(u for u in sim.jobs if u != coord_u)
        await sim.seed_images(client_u, 2)
        client = sim.jobs[client_u]
        # registered on the client only — the leader has no backend,
        # no patterns, and no registry entry for it
        client.register_lm("GhostLM", patterns=("*.tokens.txt",))
        with pytest.raises(RuntimeError, match="neither a registry CNN"):
            await client.submit_job("GhostLM", 4)


async def test_dual_model_jobs_complete(tmp_path):
    async with cluster(5, tmp_path, 22200) as sim:
        await sim.wait_converged()
        client_u = sim.by_name("H5")
        await sim.seed_images(client_u, 2)
        client = sim.jobs[client_u]

        j1 = await client.submit_job("ResNet50", 12)
        j2 = await client.submit_job("InceptionV3", 12)
        r1 = await client.wait_job(j1, timeout=20.0)
        r2 = await client.wait_job(j2, timeout=20.0)
        assert r1["total_queries"] == 12 and r2["total_queries"] == 12
        c1 = sim.coordinator_jobs().c1_stats()
        assert c1["ResNet50"]["total_queries"] == 12.0
        assert c1["InceptionV3"]["total_queries"] == 12.0


async def test_c2_and_c3_verbs(tmp_path):
    async with cluster(3, tmp_path, 22300) as sim:
        await sim.wait_converged()
        client_u = sim.by_name("H3")
        await sim.seed_images(client_u, 2)
        client = sim.jobs[client_u]

        # C3: shrink the batch size cluster-wide before submitting
        await client.set_batch_size("ResNet50", 4)
        job = await client.submit_job("ResNet50", 8)
        await client.wait_job(job, timeout=15.0)

        coord = sim.coordinator_jobs()
        # 8 queries at batch 4 -> 2 batches
        assert coord.scheduler.job_state(job).total_queries == 8
        samples = coord.scheduler.latency_samples["ResNet50"]
        assert sum(n for (_, _, n) in samples) == 8
        assert {n for (_, _, n) in samples} == {4}

        # C2 fetched remotely from a non-coordinator
        stats = await client.c2_stats("ResNet50")
        assert stats["count"] == 2.0
        assert stats["mean"] > 0


async def test_worker_failure_requeues_and_completes(tmp_path):
    async with cluster(4, tmp_path, 22400) as sim:
        await sim.wait_converged()
        client_u = sim.by_name("H4")
        await sim.seed_images(client_u, 2)
        coord = sim.coordinator_jobs()
        coord_u = coord.node.me.unique_name

        # block every worker's backend so batches stay in flight
        gates = {}
        for u, be in sim.backends.items():
            gates[u] = be.gate = asyncio.Event()

        client = sim.jobs[client_u]
        job_id = await client.submit_job("ResNet50", 32)  # 1 batch of 32

        # wait until some worker holds the batch
        await sim.wait_for(
            lambda: len(coord.scheduler.in_progress) == 1,
            what="batch assigned",
        )
        victim = next(iter(coord.scheduler.in_progress))
        assert victim != coord_u

        await sim.stop_node(victim)
        # release the remaining gates so the requeued batch can run
        for u, ev in gates.items():
            if u != victim:
                ev.set()

        done = await client.wait_job(job_id, timeout=20.0)
        assert done["total_queries"] == 32


async def test_backend_failure_sends_fail_ack_and_requeues(tmp_path):
    async with cluster(3, tmp_path, 22600) as sim:
        await sim.wait_converged()
        client_u = sim.by_name("H3")
        await sim.seed_images(client_u, 2)
        # every backend fails its first call; the WORKER_TASK_FAIL path
        # must requeue and the retry completes the job
        for be in sim.backends.values():
            be.fail_times = 1
        client = sim.jobs[client_u]
        job_id = await client.submit_job("ResNet50", 32)
        done = await client.wait_job(job_id, timeout=20.0)
        assert done["total_queries"] == 32
        assert sum(len(be.calls) for be in sim.backends.values()) >= 2


LOSSY = Timing(
    # 3% drop with suspicion after >5 consecutive misses: per-round
    # miss ~6% (ping AND ack must survive), 5-in-a-row ~1e-7 — the
    # detector stays quiet, matching the reference's deployed regime
    # (3% drop, >3 misses at 12s ticks). Tighter settings make false
    # suspicion a statistical certainty at test ping rates.
    ping_interval=0.05,
    ack_timeout=0.25,
    cleanup_time=1.0,
    missed_acks_to_suspect=5,
    leader_rpc_timeout=3.0,
)


async def test_job_completes_under_packet_loss(tmp_path):
    # the reference's test-mode drops 3% of datagrams (protocol.py:10):
    # exercise task resend, ACK-loss recovery, and submit retry
    async with cluster(4, tmp_path, 22700, testing=True,
                       packet_drop_pct=3.0, timing=LOSSY) as sim:
        # everything runs lossy, including store seeding: PUT carries
        # an idempotency token and the leader re-sends un-ACKed
        # fan-outs, so the whole stack must converge under drops
        await sim.wait_converged(timeout=20.0)
        client_u = sim.by_name("H4")
        await sim.seed_images(client_u, 2)
        client = sim.jobs[client_u]
        job_id = await client.submit_job("ResNet50", 64)  # 2 batches
        done = await client.wait_job(job_id, timeout=40.0)
        assert done["total_queries"] == 64
        dropped = sum(n.transport.packets_dropped for n in sim.nodes.values())
        assert dropped > 0, "loss injection must actually have dropped packets"


async def test_coordinator_failover_resumes_from_shadow(tmp_path):
    async with cluster(5, tmp_path, 22500) as sim:
        await sim.wait_converged()
        client_u = sim.by_name("H5")
        await sim.seed_images(client_u, 2)
        client = sim.jobs[client_u]
        coord = sim.coordinator_jobs()
        coord_u = coord.node.me.unique_name
        standby = coord.store.standby_node().unique_name

        # slow the backends so the job outlives the coordinator kill
        for be in sim.backends.values():
            be.per_model_delay["ResNet50"] = 0.3

        job_id = await client.submit_job("ResNet50", 96)  # 3 batches

        # the standby must have mirrored the job before we kill
        await sim.wait_for(
            lambda: job_id in sim.jobs[standby].scheduler.jobs,
            what="standby shadow of the job",
        )
        await sim.stop_node(coord_u)

        # standby wins the election and finishes the job
        done = await client.wait_job(job_id, timeout=30.0)
        assert done["total_queries"] == 96
        new_coord = sim.jobs[standby]
        assert new_coord.node.is_leader
        assert new_coord.scheduler.job_state(job_id).done


async def test_jobs_checkpoint_restore_through_store(tmp_path):
    """checkpoint-jobs -> (simulated scheduler wipe) -> restore-jobs:
    the snapshot in the replicated store carries everything needed to
    finish the job — net-new vs the reference, whose scheduler state
    survives only via the live standby relay (SURVEY §5)."""
    async with cluster(4, tmp_path, 22700) as sim:
        await sim.wait_converged()
        client_u = sim.by_name("H4")
        await sim.seed_images(client_u, 3)
        client = sim.jobs[client_u]

        # hold every backend so no batch can complete yet
        gate = asyncio.Event()
        for be in sim.backends.values():
            be.gate = gate

        job_id = await client.submit_job("ResNet50", 96)  # 3 batches
        coord = sim.coordinator_jobs()
        await sim.wait_for(
            lambda: job_id in coord.scheduler.jobs, what="job intake"
        )
        ck = await coord.checkpoint_jobs()
        assert ck["replicas"]

        # restore refuses while the job is live (it would drop it)
        try:
            await coord.restore_jobs()
            assert False, "expected RuntimeError without force"
        except RuntimeError:
            pass

        # simulate a coordinator restart losing all scheduler state
        coord.scheduler.queues.clear()
        coord.scheduler.in_progress.clear()
        coord.scheduler.jobs.clear()

        r = await coord.restore_jobs()
        assert r["jobs"] == 1
        assert r["queued_batches"] == 3  # in-flight folded back to queue

        gate.set()
        done = await client.wait_job(job_id, timeout=30.0)
        assert done["total_queries"] == 96
        # non-coordinator refuses the verbs
        other = sim.jobs[client_u]
        if other is not coord:
            try:
                await other.checkpoint_jobs()
                assert False, "expected RuntimeError"
            except RuntimeError:
                pass


async def test_restore_relays_to_standby_failover(tmp_path):
    """After restore-jobs, the standby's shadow matches the restored
    snapshot, so a coordinator death right after a restore still
    finishes the job (review finding: restore used to leave the shadow
    empty and failover dropped every restored job)."""
    async with cluster(4, tmp_path, 22800) as sim:
        await sim.wait_converged()
        client_u = sim.by_name("H4")
        await sim.seed_images(client_u, 3)
        client = sim.jobs[client_u]

        gate = asyncio.Event()
        for be in sim.backends.values():
            be.gate = gate

        job_id = await client.submit_job("ResNet50", 96)  # 3 batches
        coord = sim.coordinator_jobs()
        coord_u = next(iter(sim.nodes.values())).leader_unique
        standby_u = sim.stores[coord_u].standby_node().unique_name
        await sim.wait_for(
            lambda: job_id in coord.scheduler.jobs, what="job intake"
        )
        await coord.checkpoint_jobs()

        coord.scheduler.queues.clear()
        coord.scheduler.in_progress.clear()
        coord.scheduler.jobs.clear()
        # also wipe the standby's relay-built shadow: the restore relay
        # must rebuild it from the store snapshot
        sb_jobs = sim.jobs[standby_u]
        sb_jobs.scheduler.queues.clear()
        sb_jobs.scheduler.jobs.clear()

        await coord.restore_jobs()
        await sim.wait_for(
            lambda: job_id in sb_jobs.scheduler.jobs,
            what="standby shadow rebuilt from snapshot",
        )

        await sim.stop_node(coord_u)
        gate.set()
        done = await client.wait_job(job_id, timeout=30.0)
        assert done["total_queries"] == 96
        assert sb_jobs.node.is_leader


async def test_relays_buffered_during_shadow_restore(tmp_path):
    """A job submitted while the standby's snapshot fetch is in flight
    must survive the restore (review finding: restore() used to replace
    the shadow wholesale, erasing relays that raced the fetch)."""
    async with cluster(4, tmp_path, 22900) as sim:
        await sim.wait_converged()
        client_u = sim.by_name("H4")
        await sim.seed_images(client_u, 3)
        client = sim.jobs[client_u]
        gate = asyncio.Event()
        for be in sim.backends.values():
            be.gate = gate

        j1 = await client.submit_job("ResNet50", 96)
        coord = sim.coordinator_jobs()
        coord_u = next(iter(sim.nodes.values())).leader_unique
        standby_u = sim.stores[coord_u].standby_node().unique_name
        sb = sim.jobs[standby_u]
        await sim.wait_for(lambda: j1 in coord.scheduler.jobs, what="intake")
        await coord.checkpoint_jobs()
        coord.scheduler.queues.clear()
        coord.scheduler.in_progress.clear()
        coord.scheduler.jobs.clear()
        sb.scheduler.queues.clear()
        sb.scheduler.jobs.clear()

        # slow the standby's snapshot fetch so relays can race it
        orig_get = sb.store.get_bytes

        async def slow_get(*a, **k):
            await asyncio.sleep(0.6)
            return await orig_get(*a, **k)

        sb.store.get_bytes = slow_get
        await coord.restore_jobs()
        await sim.wait_for(lambda: sb._shadow_restoring,
                           what="standby fetch in flight")
        j2 = await client.submit_job("InceptionV3", 32)  # races the fetch
        await sim.wait_for(
            lambda: j1 in sb.scheduler.jobs and j2 in sb.scheduler.jobs,
            what="shadow holds restored AND raced job",
        )
        assert sb._shadow_gen is not None
        gate.set()
        r1 = await client.wait_job(j1, timeout=30.0)
        r2 = await client.wait_job(j2, timeout=30.0)
        assert r1["total_queries"] == 96 and r2["total_queries"] == 32


async def test_relay_flood_overflowing_log_survives_restore(tmp_path):
    """>500 relays landing while the snapshot fetch is in flight used
    to evict earlier post-generation relays from the bounded relay log
    before the replay ran (advisor finding); the unbounded in-flight
    side buffer must keep them replayable."""
    from dml_tpu.cluster.wire import Message, MsgType

    async with cluster(3, tmp_path, 23100) as sim:
        await sim.wait_converged()
        client_u = sim.by_name("H3")
        names = await sim.seed_images(client_u, 2)
        coord = sim.coordinator_jobs()
        coord_u = next(iter(sim.nodes.values())).leader_unique
        standby_u = sim.stores[coord_u].standby_node().unique_name
        sb = sim.jobs[standby_u]

        await coord.checkpoint_jobs()  # snapshot: no jobs

        # slow the standby's snapshot fetch so the flood races it
        orig_get = sb.store.get_bytes

        async def slow_get(*a, **k):
            await asyncio.sleep(0.5)
            return await orig_get(*a, **k)

        fail_first_fetch = {"left": 3}  # one whole _restore_shadow run

        async def flaky_slow_get(*a, **k):
            if fail_first_fetch["left"] > 0:
                fail_first_fetch["left"] -= 1
                raise OSError("store briefly down")
            await asyncio.sleep(0.5)
            return await orig_get(*a, **k)

        sb.store.get_bytes = flaky_slow_get
        # first restore relay: every fetch attempt fails, no ack —
        # but the side buffer must OPEN here and stay open
        await sb._h_restore_relay(Message(
            sender=coord_u, type=MsgType.JOBS_RESTORE_RELAY,
            data={"version": 1, "gen": 1, "rid": "r1"},
        ), None)
        await sim.wait_for(lambda: not sb._shadow_restoring,
                           what="first (failing) fetch settles")
        # post-restore submit relay lands BETWEEN fetch attempts
        await sb._h_submit_relay(Message(
            sender=coord_u, type=MsgType.SUBMIT_JOB_RELAY,
            data={"job": 7, "model": "ResNet50", "n": 4, "files": names,
                  "batch_size": 4, "requester": client_u, "gen": 1},
        ), None)
        # the coordinator's resend re-triggers the restore (same gen):
        # the buffer must NOT be wiped
        await sb._h_restore_relay(Message(
            sender=coord_u, type=MsgType.JOBS_RESTORE_RELAY,
            data={"version": 1, "gen": 1, "rid": "r1b"},
        ), None)
        assert sb._shadow_restoring
        # ...followed by a flood that evicts the submit from the
        # bounded log (acks for an unknown job are valid no-op relays)
        for i in range(600):
            await sb._h_ack_relay(Message(
                sender=coord_u, type=MsgType.WORKER_TASK_ACK_RELAY,
                data={"job": 999, "batch": i, "n_images": 0, "gen": 1},
            ), None)
        assert not any(
            m.data.get("job") == 7 for _, _, _, m in sb._relay_log
        ), "flood should have evicted the submit from the bounded log"
        await sim.wait_for(lambda: not sb._shadow_restoring,
                           what="shadow restore settles")
        # the side buffer replayed the evicted submit over the snapshot
        assert 7 in sb.scheduler.jobs
        assert sb._shadow_gen == 1
        assert sb._restore_buffer_gen is None  # buffer retired


async def test_newer_generation_restore_mid_fetch_keeps_buffering(tmp_path):
    """A gen-2 restore relay arriving while gen-1's fetch is in flight
    must advance the side buffer to gen 2 immediately (review finding:
    the in-flight latch used to drop it before the buffer bookkeeping,
    so gen-2 relays lost eviction protection until the ~10s resend)."""
    from dml_tpu.cluster.wire import Message, MsgType

    async with cluster(3, tmp_path, 23200) as sim:
        await sim.wait_converged()
        client_u = sim.by_name("H3")
        names = await sim.seed_images(client_u, 2)
        coord = sim.coordinator_jobs()
        coord_u = next(iter(sim.nodes.values())).leader_unique
        standby_u = sim.stores[coord_u].standby_node().unique_name
        sb = sim.jobs[standby_u]

        await coord.checkpoint_jobs()  # snapshot: no jobs
        orig_get = sb.store.get_bytes

        async def slow_get(*a, **k):
            await asyncio.sleep(0.4)
            return await orig_get(*a, **k)

        sb.store.get_bytes = slow_get
        # gen-1 restore: fetch in flight
        await sb._h_restore_relay(Message(
            sender=coord_u, type=MsgType.JOBS_RESTORE_RELAY,
            data={"version": 1, "gen": 1, "rid": "r1"},
        ), None)
        assert sb._shadow_restoring
        # gen-2 restore arrives mid-fetch: dropped by the latch, but
        # the buffer must advance to gen 2 NOW
        await sb._h_restore_relay(Message(
            sender=coord_u, type=MsgType.JOBS_RESTORE_RELAY,
            data={"version": 1, "gen": 2, "rid": "r2"},
        ), None)
        assert sb._restore_buffer_gen == 2
        # a gen-2 submit relay + a flood that evicts it from the log
        await sb._h_submit_relay(Message(
            sender=coord_u, type=MsgType.SUBMIT_JOB_RELAY,
            data={"job": 9, "model": "ResNet50", "n": 4, "files": names,
                  "batch_size": 4, "requester": client_u, "gen": 2},
        ), None)
        for i in range(600):
            await sb._h_ack_relay(Message(
                sender=coord_u, type=MsgType.WORKER_TASK_ACK_RELAY,
                data={"job": 999, "batch": i, "n_images": 0, "gen": 2},
            ), None)
        assert not any(
            m.data.get("job") == 9 for _, _, _, m in sb._relay_log
        )
        # gen-1 fetch completes: its replay must NOT retire the gen-2
        # buffer
        await sim.wait_for(lambda: not sb._shadow_restoring,
                           what="gen-1 restore settles")
        assert sb._shadow_gen == 1
        assert sb._restore_buffer_gen == 2
        # the coordinator's gen-2 resend: restore wipes the shadow and
        # replays — job 9 must come back from the side buffer
        await sb._h_restore_relay(Message(
            sender=coord_u, type=MsgType.JOBS_RESTORE_RELAY,
            data={"version": 1, "gen": 2, "rid": "r2b"},
        ), None)
        await sim.wait_for(lambda: not sb._shadow_restoring,
                           what="gen-2 restore settles")
        assert sb._shadow_gen == 2
        assert 9 in sb.scheduler.jobs
        assert sb._restore_buffer_gen is None  # retired


async def test_post_restore_relay_arriving_before_restore_relay(tmp_path):
    """UDP gives no ordering: a relay SENT after the restore (higher
    generation) can ARRIVE before the restore relay. The gen-stamped
    relay log must re-apply it on top of the restored snapshot."""
    from dml_tpu.cluster.wire import Message, MsgType

    async with cluster(3, tmp_path, 23000) as sim:
        await sim.wait_converged()
        client_u = sim.by_name("H3")
        names = await sim.seed_images(client_u, 2)
        coord = sim.coordinator_jobs()
        coord_u = next(iter(sim.nodes.values())).leader_unique
        standby_u = sim.stores[coord_u].standby_node().unique_name
        sb = sim.jobs[standby_u]

        await coord.checkpoint_jobs()  # snapshot: no jobs

        # post-restore submit relay (gen 1) arrives FIRST
        await sb._h_submit_relay(Message(
            sender=coord_u, type=MsgType.SUBMIT_JOB_RELAY,
            data={"job": 7, "model": "ResNet50", "n": 4, "files": names,
                  "batch_size": 4, "requester": client_u, "gen": 1},
        ), None)
        assert 7 in sb.scheduler.jobs
        # then the restore relay (same generation) arrives
        await sb._h_restore_relay(Message(
            sender=coord_u, type=MsgType.JOBS_RESTORE_RELAY,
            data={"version": 1, "gen": 1, "rid": "r1"},
        ), None)
        await sim.wait_for(lambda: not sb._shadow_restoring,
                           what="shadow restore settles")
        # snapshot had no jobs, but the gen-1 relay was replayed on top
        assert 7 in sb.scheduler.jobs
        assert sb._shadow_gen == 1

        # a PRE-restore relay (gen 0) arriving late is stale: dropped
        await sb._h_submit_relay(Message(
            sender=coord_u, type=MsgType.SUBMIT_JOB_RELAY,
            data={"job": 3, "model": "ResNet50", "n": 4, "files": names,
                  "batch_size": 4, "requester": client_u, "gen": 0},
        ), None)
        assert 3 not in sb.scheduler.jobs

        # a delayed restore relay from an OLDER restore (gen 0) must
        # not roll the shadow back: acked but not applied
        await sb._h_restore_relay(Message(
            sender=coord_u, type=MsgType.JOBS_RESTORE_RELAY,
            data={"version": 1, "gen": 0, "rid": "r0"},
        ), None)
        await asyncio.sleep(0.1)
        assert 7 in sb.scheduler.jobs  # survived, no rollback
        assert sb._shadow_gen == 1


async def test_node_joining_midjob_takes_work(tmp_path):
    """Elasticity: a node that (re)joins while a job is running gets
    scheduled batches (the reference's worker pool is a hardcoded
    H3..H10 slice, worker.py:52 — ours is the live membership)."""
    async with cluster(4, tmp_path, 23100) as sim:
        await sim.wait_converged()
        # staging machinery under test: pin static depth 2 (the
        # adaptive default commits depth on measurement and, un-
        # probed, runs the reference-faithful depth 1 — no stages)
        for j in sim.jobs.values():
            j.set_pipeline_depth(2)
        client_u = sim.by_name("H3")
        late_u = sim.by_name("H4")
        await sim.seed_images(client_u, 3)
        client = sim.jobs[client_u]

        # take H4 down before the job starts
        late_id = sim.spec.node_by_name("H4")
        await sim.stop_node(late_u)
        await sim.wait_for(
            lambda: all(
                len(n.membership.alive_nodes()) == 3
                for n in sim.nodes.values()
            ),
            what="cluster settles at 3 nodes",
        )

        # slow batches so the job outlives the rejoin
        for be in sim.backends.values():
            be.per_model_delay["ResNet50"] = 0.25

        job_id = await client.submit_job("ResNet50", 320)  # 10 batches

        # H4 comes back mid-job
        await sim.start_node(late_id)
        sim.backends[late_u].per_model_delay["ResNet50"] = 0.25
        await sim.wait_for(
            lambda: sim.nodes[late_u].joined, what="late node joined"
        )

        done = await client.wait_job(job_id, timeout=40.0)
        assert done["total_queries"] == 320
        # the late joiner actually executed batches
        assert sim.backends[late_u].calls, "late node never got work"


async def test_auto_checkpoint_loop(tmp_path):
    """With jobs_checkpoint_interval set, the coordinator snapshots
    in-flight work into the store without operator action."""
    async with cluster(3, tmp_path, 23200,
                       jobs_checkpoint_interval=0.2) as sim:
        await sim.wait_converged()
        client_u = sim.by_name("H3")
        await sim.seed_images(client_u, 2)
        client = sim.jobs[client_u]
        gate = asyncio.Event()
        for be in sim.backends.values():
            be.gate = gate
        job_id = await client.submit_job("ResNet50", 64)
        coord = sim.coordinator_jobs()
        # within a few intervals the snapshot appears in the store
        from dml_tpu.jobs.service import JobService

        async def snapshot_exists():
            files = await client.store.ls_all(JobService.JOBS_CKPT_NAME)
            return bool(files)

        deadline = asyncio.get_running_loop().time() + 5
        found = False
        while asyncio.get_running_loop().time() < deadline:
            if await snapshot_exists():
                found = True
                break
            await asyncio.sleep(0.1)
        assert found, "auto checkpoint never landed in the store"
        gate.set()
        done = await client.wait_job(job_id, timeout=20.0)
        assert done["total_queries"] == 64


async def test_double_failure_coordinator_and_standby(tmp_path):
    """Losing the coordinator AND the hot standby together exceeds
    what the relay shadow can cover — the store-backed scheduler
    snapshot is the designed recovery path: the third-in-line wins the
    election, restores the snapshot from the replicated store, and
    the job still completes on the surviving workers."""
    async with cluster(6, tmp_path, 24300) as sim:
        await sim.wait_converged()
        client_u = sim.by_name("H6")
        await sim.seed_images(client_u, 4)
        client = sim.jobs[client_u]
        gate = asyncio.Event()
        for be in sim.backends.values():
            be.gate = gate

        job_id = await client.submit_job("ResNet50", 96)  # 3 batches
        coord = sim.coordinator_jobs()
        coord_u = next(iter(sim.nodes.values())).leader_unique
        standby_u = sim.stores[coord_u].standby_node().unique_name
        await sim.wait_for(
            lambda: job_id in coord.scheduler.jobs, what="job intake"
        )
        await coord.checkpoint_jobs()  # snapshot into the store

        # M=2 simultaneous failures: primary AND its hot standby
        await sim.stop_node(coord_u)
        await sim.stop_node(standby_u)

        def third_leader():
            leaders = {n.leader_unique for n in sim.nodes.values()}
            return (
                len(leaders) == 1
                and None not in leaders
                and next(iter(leaders)) in sim.nodes
            )

        await sim.wait_for(third_leader, timeout=15.0,
                           what="third-in-line elected")
        new_coord = sim.coordinator_jobs()
        assert new_coord.scheduler.job_state(job_id) is None  # shadow died too
        r = await new_coord.restore_jobs()
        assert r["jobs"] >= 1
        gate.set()
        done = await client.wait_job(job_id, timeout=30.0)
        assert done["total_queries"] == 96


async def test_ten_node_ring_full_stack(tmp_path):
    """BASELINE config 4 at the reference's deployed scale: a 10-node
    ring (the reference's H1-H10 universe, config.py:54-63) running the
    full stack — join, replicated-store bulk load, a batch=32 ResNet50
    job fanned across the 8 non-coordinator workers, C1/C5 metrics,
    and output collection."""
    async with cluster(10, tmp_path, 24100) as sim:
        await sim.wait_converged(timeout=20.0)
        client_u = sim.by_name("H10")
        names = await sim.seed_images(client_u, 6)
        client = sim.jobs[client_u]

        await client.set_batch_size("ResNet50", 32)  # C3, cluster-wide
        job_id = await client.submit_job("ResNet50", 256)
        done = await client.wait_job(job_id, timeout=30.0)
        assert done["total_queries"] == 256

        coord = sim.coordinator_jobs()
        # all 8 batches ran, spread across multiple workers (not
        # serialized onto one)
        used_workers = {
            u for u, be in sim.backends.items()
            if any(m == "ResNet50" for m, _ in be.calls)
        }
        assert len(used_workers) >= 4, used_workers
        c1 = coord.c1_stats()
        assert c1["ResNet50"]["total_queries"] == 256
        out = await client.get_output(job_id, str(tmp_path / "final.json"))
        assert len(out) == len(names)  # every distinct image classified


async def test_efficientnet_dynamic_batching_with_failure(tmp_path):
    """BASELINE config 5: the plug-in model (EfficientNet-B4) served
    with a mid-run C3 batch-size change (dynamic batching) and a
    worker killed mid-job (1-node failure injection); the job must
    still complete every query."""
    async with cluster(5, tmp_path, 24200) as sim:
        await sim.wait_converged()
        client_u = sim.by_name("H5")
        await sim.seed_images(client_u, 4)
        client = sim.jobs[client_u]
        coord = sim.coordinator_jobs()
        coord_u = next(iter(sim.nodes.values())).leader_unique

        # dynamic batching: C3 re-sizes EfficientNetB4 batches
        # cluster-wide before the job (reference SET_BATCH_SIZE,
        # worker.py:1028-1037)
        await client.set_batch_size("EfficientNetB4", 8)
        gate = asyncio.Event()
        for be in sim.backends.values():
            be.gate = gate

        job_id = await client.submit_job("EfficientNetB4", 64)  # 8 batches
        await sim.wait_for(
            lambda: len(coord.scheduler.in_progress) > 0,
            what="batches in flight",
        )
        # failure injection: kill a worker that holds a batch
        victim = next(
            w for w in coord.scheduler.in_progress
            if w not in (coord_u, client_u)
        )
        await sim.stop_node(victim)
        gate.set()
        done = await client.wait_job(job_id, timeout=30.0)
        assert done["total_queries"] == 64
        # the batch size actually took effect (8 per call, not default)
        sizes = {
            len(paths)
            for be in sim.backends.values()
            for m, paths in be.calls
            if m == "EfficientNetB4"
        }
        assert sizes == {8}, sizes


async def test_deterministic_batch_failure_fails_job_loudly(tmp_path):
    """A batch failing max_batch_failures times on live workers fails
    the JOB with an error surfaced to the client — not an infinite
    front-requeue loop (reference has no such cap)."""
    async with cluster(3, tmp_path, 23300) as sim:
        await sim.wait_converged()
        client_u = sim.by_name("H3")
        await sim.seed_images(client_u, 2)
        client = sim.jobs[client_u]
        for be in sim.backends.values():
            be.fail_times = 1000  # deterministic failure everywhere

        job_id = await client.submit_job("ResNet50", 8)
        try:
            await client.wait_job(job_id, timeout=20.0)
            assert False, "expected job failure"
        except RuntimeError as e:
            assert "failed" in str(e)
        coord = sim.coordinator_jobs()
        st = coord.scheduler.job_state(job_id)
        assert st.done and st.error
        # workers are all free again (no pinned batch)
        assert not coord.scheduler.in_progress


async def test_job_failure_relayed_to_standby(tmp_path):
    """A capped-out job is dropped from the standby's shadow too — a
    failover must not resurrect work the client was told failed."""
    async with cluster(4, tmp_path, 23400) as sim:
        await sim.wait_converged()
        client_u = sim.by_name("H4")
        await sim.seed_images(client_u, 2)
        client = sim.jobs[client_u]
        coord_u = next(iter(sim.nodes.values())).leader_unique
        standby_u = sim.stores[coord_u].standby_node().unique_name
        for be in sim.backends.values():
            be.fail_times = 1000

        job_id = await client.submit_job("ResNet50", 8)
        try:
            await client.wait_job(job_id, timeout=20.0)
            assert False, "expected failure"
        except RuntimeError:
            pass
        sb = sim.jobs[standby_u]
        await sim.wait_for(
            lambda: job_id not in sb.scheduler.jobs
            and not any(
                b.job_id == job_id
                for q in sb.scheduler.queues.values() for b in q
            ),
            what="standby shadow dropped the failed job",
        )
        st = sb.scheduler.job_state(job_id)
        assert st is not None and st.error


# ------------------------------------------------------- worker pipelining


async def test_pipeline_stage_prepares_while_primary_infers(tmp_path):
    """Depth-2 pipelining: while a worker's PRIMARY batch is held in
    the backend, its STAGED batch must be assigned and its prepare
    (store fetch) must complete — the overlap that makes the serving
    path wall ~ max(stage), not sum."""
    async with cluster(4, tmp_path, 23100) as sim:
        await sim.wait_converged()
        # staging machinery under test: pin static depth 2 (the
        # adaptive default commits depth on measurement and, un-
        # probed, runs the reference-faithful depth 1 — no stages)
        for j in sim.jobs.values():
            j.set_pipeline_depth(2)
        client_u = sim.by_name("H4")
        await sim.seed_images(client_u, 2)
        coord = sim.coordinator_jobs()

        gates = {}
        for u, be in sim.backends.items():
            gates[u] = be.gate = asyncio.Event()

        client = sim.jobs[client_u]
        job_id = await client.submit_job("ResNet50", 96)  # 3 batches of 32

        # a worker holds a primary batch (gated) AND a staged one
        await sim.wait_for(
            lambda: len(coord.scheduler.prefetch) >= 1,
            what="a staged assignment",
        )
        worker_u = next(iter(coord.scheduler.prefetch))
        wsvc = sim.jobs[worker_u]
        # the stage's prepare (fetch) finishes while the primary is
        # still gated in the backend
        await sim.wait_for(
            lambda: wsvc._staged is not None and wsvc._staged[3].done(),
            what="staged prepare completed during primary inference",
        )
        assert not wsvc._staged[3].cancelled()

        for ev in gates.values():
            ev.set()
        done = await client.wait_job(job_id, timeout=20.0)
        assert done["total_queries"] == 96


async def test_pipeline_stage_cancel_on_second_model(tmp_path):
    """A second model's job arriving while stages are out must pull
    the staged batches back (fair split sees them) and cancel the
    workers' stages; both jobs then complete."""
    async with cluster(4, tmp_path, 26200) as sim:
        await sim.wait_converged()
        # staging machinery under test: pin static depth 2 (the
        # adaptive default commits depth on measurement and, un-
        # probed, runs the reference-faithful depth 1 — no stages)
        for j in sim.jobs.values():
            j.set_pipeline_depth(2)
        client_u = sim.by_name("H4")
        await sim.seed_images(client_u, 2)
        coord = sim.coordinator_jobs()

        gates = {}
        for u, be in sim.backends.items():
            gates[u] = be.gate = asyncio.Event()

        client = sim.jobs[client_u]
        job_a = await client.submit_job("ResNet50", 128)  # 4 batches
        await sim.wait_for(
            lambda: len(coord.scheduler.prefetch) >= 1,
            what="staged assignments",
        )
        staged_workers = list(coord.scheduler.prefetch)

        job_b = await client.submit_job("InceptionV3", 64)
        await sim.wait_for(
            lambda: not coord.scheduler.prefetch,
            what="stages revoked on dual-model activation",
        )
        # workers received the cancel (stage cleared or promoted; a
        # promoted stage is allowed to finish — completion dedup)
        await sim.wait_for(
            lambda: all(
                sim.jobs[u]._staged is None for u in staged_workers
                if u in sim.jobs
            ),
            what="worker stages cancelled",
        )

        for ev in gates.values():
            ev.set()
        done_a = await client.wait_job(job_a, timeout=30.0)
        done_b = await client.wait_job(job_b, timeout=30.0)
        assert done_a["total_queries"] == 128
        assert done_b["total_queries"] == 64


async def test_pipeline_worker_death_with_stage_completes(tmp_path):
    """Killing a worker that holds a primary AND a staged batch must
    requeue both; the job still completes 100%."""
    async with cluster(4, tmp_path, 23300) as sim:
        await sim.wait_converged()
        # staging machinery under test: pin static depth 2 (the
        # adaptive default commits depth on measurement and, un-
        # probed, runs the reference-faithful depth 1 — no stages)
        for j in sim.jobs.values():
            j.set_pipeline_depth(2)
        client_u = sim.by_name("H4")
        await sim.seed_images(client_u, 2)
        coord = sim.coordinator_jobs()

        gates = {}
        for u, be in sim.backends.items():
            gates[u] = be.gate = asyncio.Event()

        client = sim.jobs[client_u]
        job_id = await client.submit_job("ResNet50", 96)
        await sim.wait_for(
            lambda: len(coord.scheduler.prefetch) >= 1,
            what="a staged assignment",
        )
        victim = next(iter(coord.scheduler.prefetch))
        assert victim in coord.scheduler.in_progress
        before = coord.scheduler.requeue_count
        await sim.stop_node(victim)
        for u, ev in gates.items():
            if u != victim:
                ev.set()
        done = await client.wait_job(job_id, timeout=20.0)
        assert done["total_queries"] == 96
        assert coord.scheduler.requeue_count >= before + 2


def test_decode_cache_unit(tmp_path):
    """_decode_cached: hits on identical (path, mtime, size), misses
    after overwrite, byte-budget eviction."""
    import numpy as np
    from PIL import Image

    class Dummy:
        pass

    svc = Dummy()
    svc.decode_cache_bytes = 10 * 224 * 224 * 3  # ~10 images
    svc._decode_cache = __import__("collections").OrderedDict()
    svc._decode_cache_lock = __import__("threading").Lock()
    svc._decode_cache_used = 0
    svc.decode_cache_hits = 0
    svc.decode_cache_misses = 0
    decode = JobService._decode_cached

    rng = np.random.RandomState(0)
    files = []
    for i in range(4):
        p = tmp_path / f"c_{i}.jpeg"
        Image.fromarray(rng.randint(0, 255, (64, 64, 3), np.uint8)).save(p)
        files.append(str(p))

    a = decode(svc, files, (224, 224))
    assert svc.decode_cache_misses == 4 and svc.decode_cache_hits == 0
    b = decode(svc, files, (224, 224))
    assert svc.decode_cache_hits == 4
    np.testing.assert_array_equal(a, b)

    # overwrite one file -> its entry must not serve stale pixels
    import time as _t
    _t.sleep(0.01)
    Image.fromarray(rng.randint(0, 255, (64, 64, 3), np.uint8)).save(files[0])
    c = decode(svc, files, (224, 224))
    assert not np.array_equal(c[0], a[0])
    np.testing.assert_array_equal(c[1], a[1])

    # disabled cache bypasses entirely
    svc.decode_cache_bytes = 0
    h, m = svc.decode_cache_hits, svc.decode_cache_misses
    decode(svc, files, (224, 224))
    assert (svc.decode_cache_hits, svc.decode_cache_misses) == (h, m)

    # eviction respects the byte budget
    svc.decode_cache_bytes = 2 * 224 * 224 * 3
    for i in range(4):
        decode(svc, [files[i]], (224, 224))
    assert svc._decode_cache_used <= svc.decode_cache_bytes
    assert len(svc._decode_cache) <= 2


async def test_pipeline_reordered_stage_before_primary(tmp_path):
    """UDP reorder: the STAGE datagram outruns its same-round primary.
    The worker must park the stage (not execute it — that would get it
    cancelled as a 'preemption' when the primary lands) and the
    stale-seq primary (a DIFFERENT batch of the same round) must still
    run; the parked stage then promotes through the normal path. (The
    same-key prepare-reuse branch is exercised separately below.)"""
    from dml_tpu.cluster.wire import Message, MsgType

    async with cluster(3, tmp_path, 23400) as sim:
        await sim.wait_converged()
        client_u = sim.by_name("H3")
        files = await sim.seed_images(client_u, 2)
        coord = sim.coordinator_jobs()
        worker_u = next(
            u for u in sim.jobs
            if u != coord.node.me.unique_name
        )
        w = sim.jobs[worker_u]
        leader_u = coord.node.me.unique_name
        base = {"model": "ResNet50", "files": files,
                "replicas": {}, "versions": {}, "inc": 7}

        # stage arrives FIRST with the HIGHER seq
        await w._h_task_request(Message(
            sender=leader_u, type=MsgType.WORKER_TASK_REQUEST,
            data={**base, "job": 99, "batch": 1, "staged": True, "seq": 6},
        ), None)
        assert w._staged is not None and w._staged[0] == (99, 1)
        assert not w._running, "reordered stage must NOT execute eagerly"

        # primary arrives second with the LOWER (stale) seq
        await w._h_task_request(Message(
            sender=leader_u, type=MsgType.WORKER_TASK_REQUEST,
            data={**base, "job": 99, "batch": 0, "staged": False, "seq": 5},
        ), None)
        assert (99, 0) in w._running, "stale-seq primary must run when idle"
        # the stage stays parked; promotion happens via the normal path
        await sim.wait_for(
            lambda: not w._running and w._staged is None,
            timeout=15.0, what="both batches drained",
        )


async def test_pipeline_orphaned_stage_self_promotes(tmp_path):
    """A stage whose primary was LOST entirely must self-promote after
    a beat instead of stranding until the coordinator's resend."""
    from dml_tpu.cluster.wire import Message, MsgType

    async with cluster(3, tmp_path, 26500) as sim:
        await sim.wait_converged()
        client_u = sim.by_name("H3")
        files = await sim.seed_images(client_u, 2)
        coord = sim.coordinator_jobs()
        worker_u = next(
            u for u in sim.jobs if u != coord.node.me.unique_name
        )
        w = sim.jobs[worker_u]
        await w._h_task_request(Message(
            sender=coord.node.me.unique_name,
            type=MsgType.WORKER_TASK_REQUEST,
            data={"job": 98, "batch": 3, "model": "ResNet50",
                  "files": files, "replicas": {}, "versions": {},
                  "staged": True, "seq": 2, "inc": 3},
        ), None)
        assert w._staged is not None and not w._running
        await sim.wait_for(
            lambda: w._staged is None,
            timeout=5.0, what="orphaned stage promoted",
        )


async def test_pipeline_promotion_resend_reuses_prepare(tmp_path):
    """A primary assignment for the SAME key as the parked stage (the
    coordinator's promotion resend) must reuse the stage's in-flight
    prepare task rather than starting a second fetch+decode."""
    from dml_tpu.cluster.wire import Message, MsgType

    async with cluster(3, tmp_path, 23600) as sim:
        await sim.wait_converged()
        client_u = sim.by_name("H3")
        files = await sim.seed_images(client_u, 2)
        coord = sim.coordinator_jobs()
        worker_u = next(
            u for u in sim.jobs if u != coord.node.me.unique_name
        )
        w = sim.jobs[worker_u]
        base = {"model": "ResNet50", "files": files,
                "replicas": {}, "versions": {}, "inc": 9}
        await w._h_task_request(Message(
            sender=coord.node.me.unique_name,
            type=MsgType.WORKER_TASK_REQUEST,
            data={**base, "job": 97, "batch": 2, "staged": True, "seq": 3},
        ), None)
        assert w._staged is not None
        prep_task = w._staged[3]
        await w._h_task_request(Message(
            sender=coord.node.me.unique_name,
            type=MsgType.WORKER_TASK_REQUEST,
            data={**base, "job": 97, "batch": 2, "staged": False, "seq": 4},
        ), None)
        assert w._staged is None and (97, 2) in w._running
        # the execute must consume the ORIGINAL prepare, not re-fetch
        await sim.wait_for(lambda: prep_task.done(), what="prepare consumed")
        assert not prep_task.cancelled()
        await sim.wait_for(lambda: not w._running, what="batch drained")


@pytest.mark.sharded
def test_group_sharded_serving_outputs_equal_single_chip(tmp_path):
    """ISSUE 5 acceptance case: one image job served by a tp-sharded
    worker GROUP through the full cluster pipeline (store fetch ->
    group primary's param_gather ShardedInference -> output PUT ->
    get_output merge), with every served result asserted EQUAL to the
    single-chip path on the same bytes. TinyNet keeps the XLA compiles
    tier-1-cheap; the ResNet50 form of the same assertion runs in
    __graft_entry__.dryrun_multichip part 5."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from _tinynet import ensure_tinynet
    from dml_tpu.cluster.chaos import LocalCluster
    from dml_tpu.config import MeshSpec, WorkerGroupSpec
    from dml_tpu.jobs.groups import _make_sharded_jobs, sharded_backend
    from dml_tpu.models.params_io import init_variables
    from dml_tpu.parallel.inference import ShardedInference
    from dml_tpu.parallel.mesh import make_mesh

    spec_model = ensure_tinynet()
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 virtual devices for tp=2")
    img_size = spec_model.input_size
    variables = init_variables(spec_model, seed=0, dtype=jnp.float32)
    mesh_g = make_mesh(MeshSpec(dp=1, tp=2), devices=devs[:2])
    mesh_1 = make_mesh(MeshSpec(), devices=devs[:1])
    si_g = ShardedInference(
        "TinyNet", mesh_g, batch_size=4, variables=variables,
        dtype=jnp.float32, param_gather=True,
    )
    si_1 = ShardedInference(
        "TinyNet", mesh_1, batch_size=4, variables=variables,
        dtype=jnp.float32,
    )
    group = WorkerGroupSpec("tp0", ("H4", "H5"), MeshSpec(dp=1, tp=2))

    async def run():
        from PIL import Image
        from dml_tpu.jobs.service import JobService

        root = str(tmp_path / "sharded_sim")
        os.makedirs(root)
        c = LocalCluster(
            5, root, 23650, timing=FAST, worker_groups=[group],
            make_jobs=lambda node, store: _make_sharded_jobs(
                node, store, JobService, si_g, si_1, group,
                img_size, "TinyNet", 4,
            ),
        )
        try:
            await c.start()
            await c.wait_for(c.converged, 15.0, "initial convergence")
            client = c.nodes[c.spec.node_by_name("H3").unique_name]
            rng = np.random.RandomState(0)
            files = []
            for i in range(3):
                p = str(tmp_path / f"real_{i}.jpeg")
                Image.fromarray(
                    rng.randint(0, 255, (40, 40, 3)).astype(np.uint8)
                ).save(p)
                await client.store.put(p, f"real_{i}.jpeg")
                files.append((f"real_{i}.jpeg", p))
            job_id = await client.jobs.submit_job("TinyNet", 6)
            done = await client.jobs.wait_job(job_id, timeout=60.0)
            assert done["total_queries"] == 6
            merged = await client.jobs.get_output(
                job_id, str(tmp_path / "final_sharded.json")
            )
            leader = c.nodes[c.leader_uname()]
            gstats = leader.jobs.group_stats()["tp0"]
            assert gstats["formed"], gstats
            # every merged result row equals the single-chip backend's
            # on the same bytes: == on the served JSON (the bitwise
            # param_gather contract carried through the pipeline)
            single = sharded_backend(si_1, input_size=img_size)
            for sdfs, local in files:
                exp, _, _ = await single("TinyNet", [local])
                assert merged[sdfs] == exp[local], sdfs
        finally:
            await c.stop()

    asyncio.run(run())
