"""TrainJob: elastic data-parallel training as a first-class cluster
workload (jobs/train.py).

Layers covered:

- the deterministic training math: spec round-trip, per-step shard
  draw (same-step prefix property across world sizes), linear LR
  scaling with the effective global batch, name-derived gradients,
  replay_reference as the exactly-once oracle
- the step ledger: monotone exactly-once accounting, duplicate /
  out-of-order refusal, snapshot/restore validation
- the worker fetch-cache name inversion (BOTH local-naming schemes:
  replica pre-fetch `name_versionN` and data-plane `name.vN`)
- cluster e2e on the product LocalCluster: a run completes step-exact
  and replay-equal; capacity joining mid-run lands as a checkpoint-
  restore re-shard at a step boundary with the LR rescaled; a leader
  killed mid-run is adopted from the store checkpoint by the promoted
  coordinator with no step lost or double-applied (slow)
"""

import asyncio
import json
import os
import shutil

import pytest

from dml_tpu.config import Timing
from dml_tpu.jobs.train import (
    TRAIN_CKPT_PREFIX,
    StepLedger,
    TrainJobSpec,
    apply_step,
    grad_for,
    lr_for,
    recover_sdfs_name,
    replay_reference,
    shard_files,
)

pytestmark = pytest.mark.train

FAST = Timing(
    ping_interval=0.05,
    ack_timeout=0.15,
    cleanup_time=0.3,
    missed_acks_to_suspect=2,
    leader_rpc_timeout=5.0,
)

SECRET = "test-train-secret"

DATASET = [f"train_shard_{i:02d}.bin" for i in range(6)]


def _spec(**kw):
    kw.setdefault("name", "t")
    kw.setdefault("dataset", list(DATASET))
    return TrainJobSpec(**kw)


# ----------------------------------------------------------------------
# (a) spec + deterministic math
# ----------------------------------------------------------------------

def test_spec_round_trips_through_checkpoint_form():
    spec = _spec(steps=9, shard_batch=3, base_lr=0.25, base_world=2,
                 seed=7, checkpoint_every=4, min_step_s=0.05)
    again = TrainJobSpec.from_dict(
        json.loads(json.dumps(spec.to_dict())))
    assert again == spec


def test_lr_scales_linearly_with_world():
    spec = _spec(base_lr=0.1, base_world=1)
    assert lr_for(spec, 1) == pytest.approx(0.1)
    assert lr_for(spec, 3) == pytest.approx(0.3)
    # base_world anchors the rule: at base_world the LR is base_lr
    spec2 = _spec(base_lr=0.2, base_world=2)
    assert lr_for(spec2, 2) == pytest.approx(0.2)
    assert lr_for(spec2, 1) == pytest.approx(0.1)


def test_shard_files_deterministic_and_sized():
    spec = _spec(shard_batch=2, seed=3)
    for step in range(4):
        for world in (1, 2, 3):
            files = shard_files(spec, step, world)
            assert len(files) == 2 * world
            assert files == shard_files(spec, step, world)
            assert set(files) <= set(DATASET)
    # different steps draw different permutations (not a fixed slice)
    draws = {tuple(shard_files(spec, s, 2)) for s in range(8)}
    assert len(draws) > 1


def test_shard_files_same_step_prefix_property():
    """For one step, a smaller world's global batch is a prefix of a
    larger world's — the draw comes from one per-step permutation
    cycle, so re-dispatching a step at a different world keeps the
    overlap deterministic."""
    spec = _spec(shard_batch=2, seed=11)
    for step in (0, 1, 5):
        small = shard_files(spec, step, 1)
        big = shard_files(spec, step, 3)
        assert big[: len(small)] == small


def test_empty_dataset_refused():
    with pytest.raises(ValueError, match="empty dataset"):
        shard_files(_spec(dataset=[]), 0, 1)


def test_grad_for_is_name_derived_and_bounded():
    g = grad_for("train_shard_00.bin")
    assert g == grad_for("train_shard_00.bin")
    assert g != grad_for("train_shard_01.bin")
    assert len(g) == 4 and all(-1.0 <= x < 1.0 for x in g)
    assert len(grad_for("x", dim=7)) == 7


def test_replay_reference_matches_stepwise_apply():
    spec = _spec(shard_batch=2, seed=5)
    state = [0.0] * spec.grad_dim
    history = []
    for step, world in enumerate((1, 1, 2, 3, 2)):
        lr = lr_for(spec, world)
        state = apply_step(
            state, shard_files(spec, step, world), lr, spec.grad_dim)
        history.append(
            {"step": step, "world": world, "lr": lr, "reason": "x"})
    assert replay_reference(spec, history) == state  # bitwise
    # a dropped step is visible to the oracle
    assert replay_reference(spec, history[:-1]) != state


def test_recover_sdfs_name_inverts_both_cache_schemes():
    # data-plane download naming: name.vN
    assert recover_sdfs_name("/tmp/w1/train_shard_03.bin.v2") == \
        "train_shard_03.bin"
    assert recover_sdfs_name("a.bin.vlatest") == "a.bin"
    # replica pre-fetch naming: name_versionN
    assert recover_sdfs_name("/tmp/w2/train_shard_03.bin_version1") == \
        "train_shard_03.bin"
    assert recover_sdfs_name("b.bin_versionlatest") == "b.bin"
    # an unversioned name passes through
    assert recover_sdfs_name("/x/train_shard_03.bin") == \
        "train_shard_03.bin"


# ----------------------------------------------------------------------
# (b) the step ledger
# ----------------------------------------------------------------------

def test_ledger_applies_in_order_exactly_once():
    led = StepLedger()
    assert led.next_step() == 0
    led.record(0, 1, 0.1, "start")
    led.record(1, 2, 0.2, "steady")
    assert led.applied == 2
    assert [e["step"] for e in led.history] == [0, 1]
    with pytest.raises(ValueError, match="not next"):
        led.record(3, 2, 0.2, "steady")


def test_ledger_refusal_classification():
    led = StepLedger()
    led.record(0, 1, 0.1, "start")
    assert led.refuse(0) == "duplicate"  # replayed ACK
    assert led.refuse(5) == "out_of_order"  # stale-adoption race
    assert led.duplicates_refused == 1
    assert led.out_of_order_refused == 1
    assert led.applied == 1  # refusals never advance the ledger


def test_ledger_snapshot_restore_round_trip_and_validation():
    led = StepLedger()
    led.record(0, 1, 0.1, "start")
    led.record(1, 1, 0.1, "steady")
    led.refuse(0)
    again = StepLedger.restore(
        json.loads(json.dumps(led.snapshot())))
    assert again.snapshot() == led.snapshot()
    assert again.next_step() == 2
    # a torn blob (applied disagreeing with history) is refused
    bad = led.snapshot()
    bad["applied"] = 5
    with pytest.raises(ValueError, match="history"):
        StepLedger.restore(bad)


# ----------------------------------------------------------------------
# (c) cluster e2e
# ----------------------------------------------------------------------

async def _arm(cluster, tmp_path, n_files=6):
    client = cluster.client()
    names = []
    for i in range(n_files):
        p = str(tmp_path / f"shard_{i}.bin")
        with open(p, "wb") as f:
            f.write(bytes([i]) * 64)
        name = f"train_shard_{i:02d}.bin"
        await client.store.put(p, name)
        cluster.expect_files.add(name)
        names.append(name)
    return names


def _leader(cluster):
    return next(sn for sn in cluster.nodes.values()
                if sn.node.is_leader)


def test_train_run_completes_step_exact(tmp_path):
    """Tier-1 smoke on the product LocalCluster: a run drives every
    global step through the scheduler exactly once, the final state is
    bitwise replay-equal, and the store holds a done checkpoint an
    adopting coordinator could read."""
    from dml_tpu.cluster.chaos import LocalCluster, invariant_sweep

    async def run():
        root = str(tmp_path / "c")
        shutil.rmtree(root, ignore_errors=True)
        os.makedirs(root)
        cluster = LocalCluster(3, root, 47310, timing=FAST,
                               join_secret=SECRET)
        try:
            await cluster.start()
            await cluster.wait_for(cluster.converged, 15.0, "converge")
            names = await _arm(cluster, tmp_path)
            coord = _leader(cluster).jobs.train
            spec = TrainJobSpec(name="t1", dataset=names, steps=6,
                                shard_batch=2, base_lr=0.1,
                                checkpoint_every=2)
            run_ = await coord.start_run(spec)
            st = await coord.wait("t1", timeout=45.0)
            assert st["done"] and st["applied"] == 6
            assert st["grad_mismatches"] == 0
            assert [e["step"] for e in run_.ledger.history] == \
                list(range(6))
            assert run_.state == replay_reference(
                spec, run_.ledger.history)
            blob = await cluster.client().store.get_bytes(
                TRAIN_CKPT_PREFIX + "t1")
            d = json.loads(blob.decode())
            assert d["done"] is True and d["state"] == run_.state
            # the sweep's train section replays the same oracle
            cluster.train_runs.append("t1")
            report = await invariant_sweep(cluster, {}, {})
            assert report.ok, report.failures
            assert report.checks["train"]["t1"]["applied"] == 6
        finally:
            await cluster.stop()

    asyncio.run(run())


def test_join_reshards_at_step_boundary(tmp_path):
    """The elasticity claim end to end: capacity joining mid-run lands
    as a checkpoint-restore re-shard at the next step boundary — the
    world grows, the LR rescales linearly, no process restarts, and
    the ledger history stays step-exact across the transition."""
    from dml_tpu.cluster.chaos import LocalCluster

    async def run():
        root = str(tmp_path / "c")
        shutil.rmtree(root, ignore_errors=True)
        os.makedirs(root)
        cluster = LocalCluster(3, root, 47340, timing=FAST,
                               join_secret=SECRET)
        try:
            await cluster.start()
            await cluster.wait_for(cluster.converged, 15.0, "converge")
            names = await _arm(cluster, tmp_path)
            coord = _leader(cluster).jobs.train
            spec = TrainJobSpec(name="t2", dataset=names, steps=24,
                                shard_batch=2, base_lr=0.1,
                                checkpoint_every=3, min_step_s=0.05)
            run_ = await coord.start_run(spec)
            assert run_.world == 1  # 3 nodes: leader + standby + 1
            await cluster.wait_for(
                lambda: run_.ledger.applied >= 2, 20.0,
                "a few steps before the join")
            await cluster.scale_out()
            await cluster.wait_for(
                lambda: run_.world >= 2 or run_.done, 20.0,
                "join landing as a re-shard")
            st = await coord.wait("t2", timeout=60.0)
            assert st["done"] and st["applied"] == 24
            assert st["resharding"].get("join", 0) >= 1
            worlds = {e["world"] for e in run_.ledger.history}
            assert {1, 2} <= worlds
            # LR followed the world linearly, step ids stayed exact
            for e in run_.ledger.history:
                assert e["lr"] == pytest.approx(
                    lr_for(spec, e["world"]))
            assert [e["step"] for e in run_.ledger.history] == \
                list(range(24))
            assert run_.state == replay_reference(
                spec, run_.ledger.history)
        finally:
            await cluster.stop()

    asyncio.run(run())


@pytest.mark.slow
@pytest.mark.chaos
def test_leader_kill_adoption_no_step_lost(tmp_path):
    """Coordinator failover: the leader dies mid-run; the promoted
    coordinator adopts the run from the store checkpoint and finishes
    it. The restored monotone ledger makes the handoff step-exact —
    the adopted history is a contiguous step range and replay-equal,
    whatever the previous incarnation had in flight."""
    from dml_tpu.cluster.chaos import LocalCluster

    async def run():
        root = str(tmp_path / "c")
        shutil.rmtree(root, ignore_errors=True)
        os.makedirs(root)
        cluster = LocalCluster(5, root, 47370, timing=FAST,
                               join_secret=SECRET)
        try:
            await cluster.start()
            await cluster.wait_for(cluster.converged, 15.0, "converge")
            names = await _arm(cluster, tmp_path)
            old_leader = cluster.leader_uname()
            coord = _leader(cluster).jobs.train
            spec = TrainJobSpec(name="t3", dataset=names, steps=20,
                                shard_batch=2, base_lr=0.1,
                                checkpoint_every=1, min_step_s=0.05)
            run_ = await coord.start_run(spec)
            await cluster.wait_for(
                lambda: run_.ledger.applied >= 3, 20.0,
                "progress before the kill")
            await cluster.crash_node(old_leader)
            await cluster.wait_for(
                lambda: cluster.leader_uname() not in (None, old_leader),
                20.0, "promotion")

            def adopted():
                sn = cluster.nodes.get(cluster.leader_uname())
                if sn is None:
                    return None
                return sn.jobs.train.runs.get("t3")

            await cluster.wait_for(
                lambda: adopted() is not None, 20.0, "adoption")
            await cluster.wait_for(
                lambda: adopted().done, 60.0, "adopted run finishing")
            r2 = adopted()
            assert r2.resharding.get("adopt", 0) >= 1
            assert [e["step"] for e in r2.ledger.history] == \
                list(range(20))
            assert r2.state == replay_reference(
                r2.spec, r2.ledger.history)
        finally:
            await cluster.stop()

    asyncio.run(run())
