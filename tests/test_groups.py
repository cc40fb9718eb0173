"""Worker groups: tensor-parallel multi-chip serving in the cluster
pipeline (jobs/groups.py; ISSUE 5 tentpole).

Coverage layers:
- spec topology (config.WorkerGroupSpec): resolution, validation,
  JSON round-trip;
- GroupDirectory: pool collapse + weights, degrade/reform edges,
  ACK-advertised capacity;
- weighted fair share (cost_model.fair_split_weighted): uniform
  reduction to the reference split, heavy-slot behavior;
- the stub-backend cluster: group serving end to end, lender
  exclusion, member death mid-job (exactly-once on the reformed
  pool), member restart -> re-formation, leader failover;
- the real sharded path: ShardedInference param_gather bitwise
  equality (TinyNet, cheap) — the full-cluster ResNet50 equality case
  lives in tests/test_jobs_sim.py and __graft_entry__ part 5.
"""

import asyncio
import contextlib
import os
import shutil

import pytest

from dml_tpu.config import ClusterSpec, MeshSpec, Timing, WorkerGroupSpec
from dml_tpu.jobs.cost_model import ModelCost, fair_split, fair_split_weighted
from dml_tpu.jobs.groups import GroupDegraded, GroupDirectory, stub_group_backend

FAST = Timing(
    ping_interval=0.05,
    ack_timeout=0.15,
    cleanup_time=0.3,
    missed_acks_to_suspect=2,
    leader_rpc_timeout=5.0,
)


def _spec(n=5, groups=(("tp0", ("H4", "H5")),), base_port=8001):
    return ClusterSpec.localhost(
        n, base_port=base_port,
        worker_groups=[
            WorkerGroupSpec(name, tuple(members), MeshSpec(dp=1, tp=2))
            for name, members in groups
        ],
    )


# ----------------------------------------------------------------------
# spec topology
# ----------------------------------------------------------------------


def test_group_spec_resolution_and_roundtrip():
    spec = _spec()
    members = spec.group_members_unique("tp0")
    assert len(members) == 2 and members == tuple(sorted(members))
    assert spec.group_of_unique(members[0]).name == "tp0"
    assert spec.group_of_unique("127.0.0.1:8001") is None
    spec2 = ClusterSpec.from_json(spec.to_json())
    assert spec2.group_members_unique("tp0") == members
    assert spec2.worker_groups[0].mesh.tp == 2


def test_group_spec_validation():
    with pytest.raises(ValueError, match="unknown member"):
        _spec(groups=(("g", ("H4", "H99")),))
    with pytest.raises(ValueError, match="duplicate"):
        _spec(groups=(("g", ("H4", "H4")),))
    with pytest.raises(ValueError, match="two worker groups"):
        _spec(groups=(("g1", ("H3", "H4")), ("g2", ("H4", "H5"))))


# ----------------------------------------------------------------------
# directory: collapse, edges, capacity
# ----------------------------------------------------------------------


def _unames(spec, *names):
    return [spec.node_by_name(n).unique_name for n in names]


def test_directory_collapse_and_edges():
    spec = _spec()
    d = GroupDirectory(spec)
    h3, h4, h5 = _unames(spec, "H3", "H4", "H5")
    pool, weights = d.collapse([h3, h4, h5])
    # formed: lenders pooled under the primary, capacity as weight
    assert pool == [h3, h4]
    assert weights == {h4: 2.0}
    # a member missing from the pool degrades the group to singles
    pool, weights = d.collapse([h3, h4])
    assert pool == [h3, h4] and weights == {}
    assert d.degradations["tp0"] == 1
    # every member back -> re-formed
    pool, weights = d.collapse([h3, h4, h5])
    assert weights == {h4: 2.0}
    assert d.reforms["tp0"] == 1
    st = d.stats()["tp0"]
    assert st["formed"] and st["primary"] == h4
    assert st["degradations"] == 1 and st["reforms"] == 1


def test_directory_ack_capacity_and_fast_path():
    spec = _spec()
    d = GroupDirectory(spec)
    h3, h4, h5 = _unames(spec, "H3", "H4", "H5")
    d.collapse([h3, h4, h5])
    d.observe_ack(h4, {"group": "tp0", "group_capacity": 3.5,
                       "group_size": 2})
    _, weights = d.collapse([h3, h4, h5])
    assert weights == {h4: 3.5}
    assert d.stats()["tp0"]["capacity_source"] == "ack"
    # SWIM fast path: a member death degrades NOW and names the
    # primary whose in-flight work must requeue
    assert d.on_node_failed(h5) == ("tp0", h4)
    assert d.on_node_failed(h5) is None  # already degraded: no edge
    assert d.degradations["tp0"] == 1
    # disabled directory = the reference single-chip shape
    d.enabled = False
    pool, weights = d.collapse([h3, h4, h5])
    assert pool == [h3, h4, h5] and weights == {}
    assert d.role_in([h3, h4, h5], h4) is None


def test_directory_degrades_with_no_member_in_pool():
    """A formed group whose members are all still ALIVE but no longer
    schedulable (e.g. promoted to leader + standby after a failover)
    must degrade — the old pool-only walk never revisited a group with
    zero members in the pool, reporting it formed forever."""
    spec = _spec()
    d = GroupDirectory(spec)
    h3, h4, h5 = _unames(spec, "H3", "H4", "H5")
    d.collapse([h3, h4, h5])
    assert d.stats()["tp0"]["formed"]
    pool, weights = d.collapse([h3])  # both members ineligible
    assert pool == [h3] and weights == {}
    assert d.degradations["tp0"] == 1
    assert d.stats()["tp0"]["formed"] is False


def test_directory_roles():
    spec = _spec()
    d = GroupDirectory(spec)
    h3, h4, h5 = _unames(spec, "H3", "H4", "H5")
    assert d.role_in([h3, h4, h5], h4) == "primary"
    assert d.role_in([h3, h4, h5], h5) == "lender"
    assert d.role_in([h3, h4], h4) == "degraded"
    assert d.role_in([h3, h4, h5], h3) is None


# ----------------------------------------------------------------------
# weighted fair share
# ----------------------------------------------------------------------


def test_fair_split_weighted_uniform_reduces_to_reference():
    a, b = ModelCost(1, 1, 0.001), ModelCost(1, 1, 0.004)
    for n in range(1, 9):
        assert fair_split(n, a, b) == fair_split_weighted([1.0] * n, a, b)


def test_fair_split_weighted_heavy_slot():
    # equal costs, pool = one capacity-3 group + three singles: the
    # balanced split is group-vs-three-singles (3.0 vs 3.0), which no
    # count-based split could find
    c = ModelCost(1, 1, 0.002)
    i, j = fair_split_weighted([3.0, 1.0, 1.0, 1.0], c, c)
    assert sorted((i, j)) == [1, 3]
    # single heavy slot goes to the slower model
    slow, fast = ModelCost(1, 1, 0.01), ModelCost(1, 1, 0.001)
    assert fair_split_weighted([4.0], slow, fast) == (1, 0)


def test_scheduler_places_heavy_slot_per_split_direction():
    """The split's placement direction must be HONORED by assignment:
    with equal costs over [group(w=3), s1, s2, s3] the balanced split
    is group-vs-three-singles, so the group slot must end up running a
    different model than all three singles — counts poured onto
    arbitrary free workers would realize 1-vs-5 instead of 3-vs-3."""
    from dml_tpu.jobs.scheduler import Scheduler

    c = ModelCost(load_time=1, first_query=1, per_query=0.002,
                  download_time=0.0)
    sched = Scheduler()
    sched.set_cost("A", c)
    sched.set_cost("B", c)
    files = [f"f{i}" for i in range(8)]
    sched.submit_job(1, "A", files, 320, "t")
    sched.submit_job(2, "B", files, 320, "t")
    workers = ["w1", "w2", "w3", "w4"]
    assigns = sched.schedule(workers, weights={"w2": 3.0})
    by_worker = {a.worker: a.batch.model for a in assigns}
    assert len(by_worker) == 4
    group_model = by_worker["w2"]
    singles = [by_worker[w] for w in ("w1", "w3", "w4")]
    assert all(m != group_model for m in singles), by_worker


# ----------------------------------------------------------------------
# stub group backend
# ----------------------------------------------------------------------


def test_stub_group_backend_degrades_when_member_dies():
    alive = {"a:1", "a:2"}
    be = stub_group_backend("g", ("a:1", "a:2"), lambda: alive,
                            per_file_s=0.001)
    assert be.capacity == 2.0

    async def run():
        results, exec_time, _ = await be("M", ["p1", "p2"])
        assert set(results) == {"p1", "p2"}
        alive.discard("a:2")
        with pytest.raises(GroupDegraded, match="lost member"):
            await be("M", ["p1"])

    asyncio.run(run())


# ----------------------------------------------------------------------
# stub-backend cluster: the control-plane story end to end
# ----------------------------------------------------------------------


@contextlib.asynccontextmanager
async def _cluster(n, base_port, tmp_path, groups=(("tp0", ("H4", "H5")),)):
    from dml_tpu.cluster.chaos import LocalCluster

    root = str(tmp_path / f"grp_{base_port}")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    c = LocalCluster(
        n, root, base_port, timing=FAST,
        worker_groups=[
            WorkerGroupSpec(name, tuple(members), MeshSpec(dp=1, tp=2))
            for name, members in groups
        ],
    )
    try:
        await c.start()
        await c.wait_for(c.converged, 15.0, "initial convergence")
        yield c
    finally:
        await c.stop()


async def _seed(client, tmp_path, count=4):
    for i in range(count):
        p = tmp_path / f"img_{i}.jpeg"
        p.write_bytes(b"\xff\xd8fakejpeg" + bytes([i]))
        await client.store.put(str(p), f"img_{i}.jpeg")


def test_group_serving_end_to_end(tmp_path):
    """Formed group: the job completes, the lender takes no direct
    assignments, the group ACKs advertise capacity, the scheduler's
    weights carry it, and the pool shows one slot for the group."""
    from dml_tpu.cluster import chaos

    async def run():
        async with _cluster(5, 23500, tmp_path) as c:
            spec = c.spec
            h4 = spec.node_by_name("H4").unique_name
            h5 = spec.node_by_name("H5").unique_name
            client = c.nodes[spec.node_by_name("H3").unique_name]
            await _seed(client, tmp_path)
            job_id = await client.jobs.submit_job(
                chaos.STUB_MODEL, 80, timeout=15.0, retries=5
            )
            done = await client.jobs.wait_job(job_id, timeout=30.0)
            assert done["total_queries"] == 80
            leader = c.nodes[c.leader_uname()]
            pool = leader.jobs.worker_pool()
            assert h4 in pool and h5 not in pool
            assert leader.jobs._pool_weights.get(h4) == 2.0
            assert leader.jobs.scheduler.worker_weights.get(h4) == 2.0
            # the lender never executed a batch; the primary did, on
            # the group engine
            st = leader.jobs.group_stats()["tp0"]
            assert st["formed"] and st["capacity_source"] == "ack"
            assert h5 not in leader.jobs.scheduler.in_progress
            # group metrics moved
            from dml_tpu.observability import METRICS

            snap = METRICS.snapshot()
            assert any(
                k.startswith("jobs_group_batches_total") and v > 0
                for k, v in snap["counters"].items()
            )
            assert snap["gauges"].get(
                'jobs_group_formed{group=tp0}'
            ) == 1.0

    asyncio.run(run())


def test_group_member_death_mid_job_exactly_once(tmp_path):
    """The acceptance chaos case: kill a group member (the lender)
    mid-job. The group degrades, the primary's in-flight batch
    requeues onto the reformed single-chip pool, and the job completes
    with every acked batch counted exactly once."""
    from dml_tpu.cluster import chaos

    async def run():
        async with _cluster(5, 23530, tmp_path) as c:
            spec = c.spec
            h5 = spec.node_by_name("H5").unique_name
            client = c.nodes[spec.node_by_name("H3").unique_name]
            await _seed(client, tmp_path)
            leader = c.nodes[c.leader_uname()]
            n = 400  # 50 batches of 8: plenty in flight at the kill
            job_id = await client.jobs.submit_job(
                chaos.STUB_MODEL, n, timeout=15.0, retries=5
            )
            # kill the lender once the group primary is actually busy
            h4 = spec.node_by_name("H4").unique_name
            for _ in range(500):
                if h4 in leader.jobs.scheduler.in_progress:
                    break
                await asyncio.sleep(0.01)
            await c.crash_node(h5)  # abrupt: no goodbye
            done = await client.jobs.wait_job(job_id, timeout=60.0)
            assert done["total_queries"] == n
            sched = leader.jobs.scheduler
            st = sched.job_state(job_id)
            assert st.done and st.error is None
            # exactly-once: completed batches and counted queries both
            # match the job size despite the requeue/re-execution races
            assert len(st.completed_batches) == (n + 7) // 8
            assert sched.query_counts.get(chaos.STUB_MODEL, 0) == n
            gs = leader.jobs.group_stats()["tp0"]
            assert not gs["formed"] and gs["degradations"] >= 1
            # the degraded pool serves single-chip: the primary is a
            # weight-1 slot now
            pool = leader.jobs.worker_pool()
            assert h4 in pool and leader.jobs._pool_weights == {}

    asyncio.run(run())


def test_group_member_restart_reforms(tmp_path):
    """A crashed member coming back with the same identity re-forms
    the group automatically — the view is derived from spec + SWIM
    liveness, no repair protocol."""
    from dml_tpu.cluster import chaos

    async def run():
        async with _cluster(5, 23560, tmp_path) as c:
            spec = c.spec
            h5 = spec.node_by_name("H5").unique_name
            client = c.nodes[spec.node_by_name("H3").unique_name]
            await _seed(client, tmp_path)
            leader = c.nodes[c.leader_uname()]
            await c.crash_node(h5)
            await c.wait_for(
                lambda: not leader.jobs.group_stats()["tp0"]["formed"],
                10.0, "group degradation",
            )
            await c.restart_node(h5)
            await c.wait_for(
                lambda: leader.jobs.group_stats()["tp0"]["formed"],
                15.0, "group re-formation",
            )
            assert leader.jobs.group_stats()["tp0"]["reforms"] >= 1
            # the reformed group still serves
            job_id = await client.jobs.submit_job(
                chaos.STUB_MODEL, 40, timeout=15.0, retries=5
            )
            done = await client.jobs.wait_job(job_id, timeout=30.0)
            assert done["total_queries"] == 40

    asyncio.run(run())


def test_group_survives_leader_failover(tmp_path):
    """Kill the coordinator mid-job: the promoted standby's directory
    — derived from the same spec + its own liveness view — keeps the
    group collapsed as one weighted slot and the job completes exactly
    once (shadow relays)."""
    from dml_tpu.cluster import chaos

    async def run():
        async with _cluster(5, 23590, tmp_path) as c:
            spec = c.spec
            h4 = spec.node_by_name("H4").unique_name
            h5 = spec.node_by_name("H5").unique_name
            client = c.nodes[spec.node_by_name("H3").unique_name]
            await _seed(client, tmp_path)
            leader_u = c.leader_uname()
            n = 400
            job_id = await client.jobs.submit_job(
                chaos.STUB_MODEL, n, timeout=15.0, retries=5
            )
            await asyncio.sleep(0.2)  # let scheduling start
            await c.crash_node(leader_u)
            done = await client.jobs.wait_job(job_id, timeout=60.0)
            assert done["total_queries"] == n
            # wait_job only needs the promoted leader; the other nodes
            # may still be mid-gossip about who that is
            await c.wait_for(
                lambda: c.leader_uname() is not None, 15.0, "leader agreement"
            )
            new_leader = c.nodes[c.leader_uname()]
            sched = new_leader.jobs.scheduler
            assert sched.query_counts.get(chaos.STUB_MODEL, 0) >= n
            # the promoted coordinator's pool still collapses the group
            pool = new_leader.jobs.worker_pool()
            assert h5 not in pool
            if h4 in pool:  # h4 may BE the new standby on tiny rings
                assert new_leader.jobs._pool_weights.get(h4, 1.0) >= 1.0

    asyncio.run(run())


def test_lm_rounds_keep_the_full_individual_pool(tmp_path):
    """Pool collapse is round-aware: a round with LM work (models the
    group engine cannot serve) must keep every chip as an individual
    slot — withdrawing the lender while weighting the primary at group
    capacity would model throughput that never arrives, making a
    grouped cluster SLOWER at LM serving than an ungrouped one."""
    from dml_tpu.cluster import chaos

    async def lm_backend(model, paths):
        await asyncio.sleep(0.002 * max(1, len(paths)))
        return {p: {"tokens": [1, 2]} for p in paths}, 0.002, None

    async def run():
        async with _cluster(5, 23680, tmp_path) as c:
            spec = c.spec
            h4 = spec.node_by_name("H4").unique_name
            h5 = spec.node_by_name("H5").unique_name
            for sn in c.nodes.values():
                sn.jobs.register_lm("StubLM", backend=lm_backend,
                                    patterns=("*.prompt.txt",))
            client = c.nodes[spec.node_by_name("H3").unique_name]
            p = tmp_path / "a.prompt.txt"
            p.write_bytes(b"1 2 3")
            await client.store.put(str(p), "a.prompt.txt")
            leader = c.nodes[c.leader_uname()]
            jobs = leader.jobs
            # idle baseline: the CNN view collapses the group
            pool = jobs.worker_pool()
            assert h4 in pool and h5 not in pool
            assert jobs._pool_weights.get(h4) == 2.0
            # LM work queued (deterministic: drive the scheduler
            # directly, the pool decision reads active_models) ->
            # the pool must be UNCOLLAPSED with no group weights
            jobs.scheduler.submit_job(
                991, "StubLM", ["a.prompt.txt"], 8, "t"
            )
            assert jobs.scheduler.active_models() == ["StubLM"]
            pool = jobs.worker_pool()
            assert h4 in pool and h5 in pool
            assert jobs._pool_weights == {}
            # drained again -> re-collapsed
            jobs.scheduler.fail_job(991, "test teardown")
            jobs.scheduler.pop_failed_jobs()
            pool = jobs.worker_pool()
            assert h5 not in pool
            assert jobs._pool_weights.get(h4) == 2.0
            # and a real LM job completes through the full pipeline
            job_id = await client.jobs.submit_job(
                "StubLM", 64, timeout=15.0, retries=5
            )
            done = await client.jobs.wait_job(job_id, timeout=30.0)
            assert done["total_queries"] == 64

    asyncio.run(run())


def test_group_backend_serves_only_its_model(tmp_path):
    """A sharded group engine is compiled for ONE model; a job for any
    other model must fall through to the primary's single-chip backend
    — routing it to the group engine would run the wrong forward and
    ack wrong predictions silently."""
    from dml_tpu.cluster import chaos
    from dml_tpu.cluster.chaos import LocalCluster, stub_backend
    from dml_tpu.jobs.service import JobService
    from dml_tpu.observability import METRICS

    def make_jobs(node, store):
        uname = node.me.unique_name
        gb = None
        g = node.spec.group_of_unique(uname)
        if g is not None:
            members = node.spec.group_members_unique(g.name)
            if members and uname == members[0]:
                gb = stub_group_backend(
                    g.name, members,
                    lambda: {n.unique_name
                             for n in node.membership.alive_nodes()},
                )
                gb.model = "SomeOtherModel"  # pinned engine mismatch
        js = JobService(node, store, infer_backend=stub_backend(),
                        group_backend=gb)
        js.scheduler.set_batch_size(chaos.STUB_MODEL, 8)
        return js

    async def run():
        root = str(tmp_path / "grp_model")
        os.makedirs(root)
        c = LocalCluster(
            5, root, 23620, timing=FAST,
            worker_groups=[WorkerGroupSpec(
                "tp0", ("H4", "H5"), MeshSpec(dp=1, tp=2))],
            make_jobs=make_jobs,
        )
        try:
            await c.start()
            await c.wait_for(c.converged, 15.0, "initial convergence")
            client = c.nodes[c.spec.node_by_name("H3").unique_name]
            await _seed(client, tmp_path)
            key = "jobs_group_batches_total{group=tp0}"
            before = METRICS.snapshot()["counters"].get(key, 0.0)
            job_id = await client.jobs.submit_job(
                chaos.STUB_MODEL, 40, timeout=15.0, retries=5
            )
            done = await client.jobs.wait_job(job_id, timeout=30.0)
            assert done["total_queries"] == 40
            # every batch ran single-chip: the mismatched group engine
            # never executed one
            after = METRICS.snapshot()["counters"].get(key, 0.0)
            assert after == before
        finally:
            await c.stop()

    asyncio.run(run())


# ----------------------------------------------------------------------
# real sharded path: param_gather bitwise equality (cheap TinyNet)
# ----------------------------------------------------------------------


def test_wire_group_backend_primary_only():
    """Production (CLI/NodeApp) wiring: the group PRIMARY gets the
    lazy multi-model group engine; lenders and ungrouped nodes get
    None — a spec-configured group must never collapse the pool while
    its primary serves single-chip."""
    from dml_tpu.cluster.node import Node
    from dml_tpu.jobs.groups import wire_group_backend

    spec = _spec()
    h4 = spec.node_by_name("H4")
    h5 = spec.node_by_name("H5")
    h1 = spec.node_by_name("H1")
    gb = wire_group_backend(Node(spec, h4))
    assert gb is not None
    assert gb.model is None  # lazy per-model engines: serves any CNN
    assert gb.capacity == 2.0  # chip-count prior until first build
    assert wire_group_backend(Node(spec, h5)) is None  # lender
    assert wire_group_backend(Node(spec, h1)) is None  # ungrouped


@pytest.mark.sharded
def test_group_engine_backend_lazy_models_and_equality(tmp_path):
    """The lazy production group engine builds a param_gather
    ShardedInference per model on first use, serves bitwise the
    single-device outputs, and self-corrects its advertised capacity
    to the resolved mesh size."""
    import asyncio as _a

    import jax
    import jax.numpy as jnp
    import numpy as np
    from PIL import Image

    from _tinynet import ensure_tinynet
    from dml_tpu.jobs.groups import group_engine_backend, sharded_backend
    from dml_tpu.parallel.inference import ShardedInference
    from dml_tpu.parallel.mesh import make_mesh

    ensure_tinynet()
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 virtual devices for tp=2")
    members = ("a:1", "a:2")
    be = group_engine_backend(
        "g", members, lambda: set(members), MeshSpec(dp=1, tp=2),
        batch_size=4,
    )
    rng = np.random.RandomState(0)
    paths = []
    for i in range(3):
        p = str(tmp_path / f"ge_{i}.jpeg")
        Image.fromarray(
            rng.randint(0, 255, (40, 40, 3)).astype(np.uint8)
        ).save(p)
        paths.append(p)
    results, infer_time, _ = _a.run(be("TinyNet", paths))
    assert set(results) == set(paths) and infer_time > 0
    assert be.capacity == 2.0  # resolved dp=1 × tp=2
    # bitwise the single-device path (same seed, dtype, decode)
    one = make_mesh(MeshSpec(), devices=devs[:1])
    single = sharded_backend(
        ShardedInference("TinyNet", one, batch_size=4, seed=0)
    )
    expected, _, _ = _a.run(single("TinyNet", paths))
    assert results == expected
    # load-model contract: set_variables rebuilds the group engine on
    # the operator-loaded tree — group answers must track the same
    # weights the single-chip engine serves, not the init seed
    from dml_tpu.models.params_io import init_variables
    from dml_tpu.models.registry import get_model

    other = init_variables(get_model("TinyNet"), seed=7,
                           dtype=jnp.bfloat16)
    be.set_variables("TinyNet", other)
    reloaded, _, _ = _a.run(be("TinyNet", paths))
    single7 = sharded_backend(ShardedInference(
        "TinyNet", one, batch_size=4, variables=other
    ))
    expected7, _, _ = _a.run(single7("TinyNet", paths))
    assert reloaded == expected7
    assert reloaded != expected  # the weights actually changed


@pytest.mark.sharded
def test_param_gather_bitwise_equality():
    """The property the whole group-serving equality story rests on:
    a param_gather ShardedInference over dp×tp produces BITWISE the
    single-device outputs (weights sharded in HBM, gathered at forward
    entry, replicated compute per dp shard)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dml_tpu.models.params_io import init_variables
    from dml_tpu.parallel.inference import ShardedInference
    from dml_tpu.parallel.mesh import make_mesh

    from _tinynet import ensure_tinynet

    spec = ensure_tinynet()
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 virtual devices")
    variables = init_variables(spec, seed=0, dtype=jnp.float32)
    mesh22 = make_mesh(MeshSpec(dp=2, tp=2), devices=devs[:4])
    mesh1 = make_mesh(MeshSpec(), devices=devs[:1])
    sh = ShardedInference(
        "TinyNet", mesh22, batch_size=4, variables=variables,
        dtype=jnp.float32, param_gather=True,
    )
    one = ShardedInference(
        "TinyNet", mesh1, batch_size=4, variables=variables,
        dtype=jnp.float32,
    )
    imgs = np.random.RandomState(0).randint(
        0, 255, (6, 32, 32, 3), np.uint8
    )
    np.testing.assert_array_equal(sh(imgs), one(imgs))
