"""Unit tests for the pure-logic scheduler + cost model (reference
schedule_job worker.py:255-495; cost model models.py:128-139).

These are the tests the reference never had (SURVEY §4): the
preempt/requeue/failover state machine exercised deterministically.
"""

import json

import pytest

from dml_tpu.jobs.cost_model import ModelCost, batch_exec_time, fair_split, query_rate
from dml_tpu.jobs.scheduler import Scheduler


FAST = ModelCost(load_time=0, first_query=0, per_query=0.01, download_time=0.0, batch_size=10)
SLOW = ModelCost(load_time=0, first_query=0, per_query=0.04, download_time=0.0, batch_size=10)


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def make(costs=None):
    clock = Clock()
    s = Scheduler(costs or {"a": FAST, "b": SLOW}, now=clock)
    return s, clock


# ---------------------------------------------------------------- cost model


def test_batch_exec_time_reference_formula():
    # non-resident (reference CPU regime): dl*B + load + first + per*(B-1)
    c = ModelCost(load_time=3.5, first_query=1.0, per_query=0.25,
                  download_time=1.0, batch_size=10, resident=False)
    assert batch_exec_time(c) == 10 * 1.0 + 3.5 + 1.0 + 0.25 * 9


def test_batch_exec_time_resident_tpu_regime():
    c = ModelCost(load_time=3.5, first_query=1.0, per_query=0.01,
                  download_time=0.05, batch_size=32, resident=True)
    assert batch_exec_time(c) == 32 * 0.05 + 0.01 * 32


def test_fair_split_balances_rates():
    # SLOW is 4x slower per query -> it needs ~4x the workers
    i, j = fair_split(10, SLOW, FAST)
    assert i + j == 10
    assert i == 8  # rates: 8/.04=200 vs 2*... -> check relative diff minimal
    ra, rb = query_rate(SLOW, i), query_rate(FAST, j)
    # every other split must be no better
    for k in range(1, 10):
        alt = abs(query_rate(SLOW, k) - query_rate(FAST, 10 - k))
        alt /= max(query_rate(SLOW, k), query_rate(FAST, 10 - k))
        assert abs(ra - rb) / max(ra, rb) <= alt + 1e-12


def test_fair_split_single_worker_prefers_slow_model():
    assert fair_split(1, SLOW, FAST) == (1, 0)
    assert fair_split(1, FAST, SLOW) == (0, 1)


# ---------------------------------------------------------------- intake


def test_submit_wraps_around_and_batches():
    s, _ = make()
    st = s.submit_job(1, "a", ["x.jpg", "y.jpg", "z.jpg"], 25, "client")
    assert st.pending_batches == 3  # 10+10+5
    batches = list(s.queues["a"])
    assert [len(b.files) for b in batches] == [10, 10, 5]
    # wrap-around sampling (reference preprocess_job_request)
    assert batches[0].files[:6] == ["x.jpg", "y.jpg", "z.jpg", "x.jpg", "y.jpg", "z.jpg"]


def test_job_ids_monotonic_and_observable():
    s, _ = make()
    assert s.next_job_id() == 1
    s.observe_job_id(7)
    assert s.next_job_id() == 8


# ---------------------------------------------------------------- scheduling


def test_single_model_fills_free_workers():
    s, _ = make()
    s.submit_job(1, "a", ["x"], 50, "c")  # 5 batches
    out = s.schedule(["w1", "w2", "w3"])
    assert {a.worker for a in out} == {"w1", "w2", "w3"}
    assert all(a.preempted is None for a in out)
    assert len(s.queues["a"]) == 2
    # second round: all workers busy, nothing scheduled
    assert s.schedule(["w1", "w2", "w3"]) == []


def test_dual_model_fair_split_with_preemption():
    s, _ = make()
    workers = [f"w{i}" for i in range(10)]
    # model a (fast) hogs the whole pool first
    s.submit_job(1, "a", ["x"], 200, "c")  # 20 batches
    out = s.schedule(workers)
    assert len(out) == 10
    # now the slow model arrives: fair share says it deserves 8 workers
    s.submit_job(2, "b", ["y"], 200, "c")
    out = s.schedule(workers)
    preempted = [a for a in out if a.preempted is not None]
    assert preempted, "slow model must preempt the fast model's workers"
    got_b = sum(1 for b in s.in_progress.values() if b.model == "b")
    assert got_b == 8
    # preempted batches returned to the FRONT of a's queue
    assert all(a.preempted.model == "a" for a in preempted)


def test_preempted_batch_requeued_at_front():
    s, _ = make()
    s.submit_job(1, "a", ["x"], 30, "c")  # 3 batches
    s.schedule(["w1"])
    first = s.in_progress["w1"]
    s.submit_job(2, "b", ["y"], 10, "c")
    out = s.schedule(["w1"])
    # single worker -> slow model (b) wins it, a's batch requeued front
    assert s.in_progress["w1"].model == "b"
    assert s.queues["a"][0] is first
    assert out[0].preempted is first


# ---------------------------------------------------------------- completion


def test_batch_done_frees_worker_and_completes_job():
    s, clock = make()
    s.submit_job(1, "a", ["x"], 15, "c")  # 2 batches
    s.schedule(["w1", "w2"])
    assert s.on_batch_done("w1", 1, 0, exec_time=0.5, n_images=10) is None
    done = s.on_batch_done("w2", 1, 1, exec_time=0.3, n_images=5)
    assert done is not None and done.job_id == 1 and done.done
    assert s.in_progress == {}
    assert s.query_counts["a"] == 15


def test_worker_failure_requeues_front():
    s, _ = make()
    s.submit_job(1, "a", ["x"], 30, "c")
    s.schedule(["w1", "w2"])
    lost = s.in_progress["w1"]
    back = s.on_worker_failed("w1")
    assert back is lost
    assert s.queues["a"][0] is lost
    # rescheduling hands it to a free worker again
    out = s.schedule(["w1", "w2", "w3"])
    assert any(a.batch is lost for a in out)


def test_duplicate_ack_does_not_complete_job_early():
    # false suspicion: worker requeued+reassigned, then BOTH copies ACK
    s, _ = make()
    s.submit_job(1, "a", ["x"], 30, "c")  # 3 batches
    s.schedule(["w1"])
    lost = s.on_worker_failed("w1")  # falsely suspected; requeued front
    s.schedule(["w2"])  # reassigned to w2
    assert s.in_progress["w2"] is lost
    # the "dead" worker's ACK arrives first
    assert s.on_batch_done("w1", 1, lost.batch_id, 0.1, 10) is None
    # duplicate from w2 must not double-count or double-decrement
    assert s.on_batch_done("w2", 1, lost.batch_id, 0.1, 10) is None
    assert s.query_counts["a"] == 10
    assert s.jobs[1].pending_batches == 2
    assert not s.jobs[1].done


def test_ack_for_requeued_batch_removes_queued_copy():
    s, _ = make()
    s.submit_job(1, "a", ["x"], 20, "c")  # 2 batches
    s.schedule(["w1"])
    lost = s.on_worker_failed("w1")  # requeued at front
    # the falsely-suspected worker finishes it anyway
    s.on_batch_done("w1", 1, lost.batch_id, 0.1, 10)
    # the queued duplicate is gone; only batch 1 remains
    assert [b.batch_id for b in s.queues["a"]] == [1]


def test_stale_ack_ignored():
    s, _ = make()
    s.submit_job(1, "a", ["x"], 10, "c")
    s.schedule(["w1"])
    # ack for a batch w1 is not running (stale/duplicate) must not free it
    s.on_batch_done("w1", 99, 0, 0.1, 10)
    assert "w1" in s.in_progress


# ---------------------------------------------------------------- standby


def test_shadow_prune_mirrors_primary_progress():
    s, _ = make()
    # standby receives the relay: same submit, but never schedules
    s.submit_job(5, "a", ["x"], 25, "c")
    s.shadow_prune(5, 0, 10)
    s.shadow_prune(5, 1, 10)
    assert s.jobs[5].pending_batches == 1
    assert len(s.queues["a"]) == 1
    assert s.queues["a"][0].batch_id == 2
    s.shadow_prune(5, 2, 5)
    assert s.job_state(5).done
    assert 5 not in s.jobs  # retired to done_jobs


# ---------------------------------------------------------------- metrics


def test_c1_counts_and_windowed_rate():
    s, clock = make()
    s.submit_job(1, "a", ["x"], 20, "c")
    s.schedule(["w1", "w2"])
    clock.t = 100.0
    s.on_batch_done("w1", 1, 0, 0.5, 10)
    clock.t = 105.0
    s.on_batch_done("w2", 1, 1, 0.5, 10)
    clock.t = 106.0
    c1 = s.c1_stats(window=10.0)
    assert c1["a"]["total_queries"] == 20
    assert c1["a"]["rate_per_sec"] == 2.0  # 20 images in the window


def test_c2_percentiles():
    s, clock = make()
    s.submit_job(1, "a", ["x"], 40, "c")
    for i, (w, et) in enumerate([("w1", 1.0), ("w2", 2.0), ("w3", 3.0), ("w4", 4.0)]):
        s.schedule([w])
        s.on_batch_done(w, 1, i, et, 10)
    c2 = s.c2_stats("a")
    assert c2["count"] == 4
    assert abs(c2["mean"] - 0.25) < 1e-9
    assert c2["p50"] in (0.2, 0.3)


def test_c3_set_batch_size_affects_future_jobs():
    s, _ = make()
    s.set_batch_size("a", 5)
    st = s.submit_job(1, "a", ["x"], 20, "c")
    assert st.pending_batches == 4


def test_c5_assignment_dump():
    s, _ = make()
    s.submit_job(1, "a", ["x"], 10, "c")
    s.schedule(["w1"])
    c5 = s.c5_assignments()
    assert c5["w1"]["model"] == "a" and c5["w1"]["images"] == 10


# ------------------------------------------------------- worker pipelining


def make_pipelined():
    s, clock = make()
    s.pipeline_depth = 2
    return s, clock


def test_pipeline_stages_one_extra_batch_per_busy_worker():
    s, _ = make_pipelined()
    s.submit_job(1, "a", ["x"], 50, "c")  # 5 batches of 10
    out = s.schedule(["w1", "w2"])
    # 2 primaries + 2 staged
    assert len(out) == 4
    assert [a.staged for a in out] == [False, False, True, True]
    assert set(s.in_progress) == {"w1", "w2"}
    assert set(s.prefetch) == {"w1", "w2"}
    # no double-staging on the next round
    assert s.schedule(["w1", "w2"]) == []


def test_pipeline_ack_promotes_staged_batch():
    s, _ = make_pipelined()
    s.submit_job(1, "a", ["x"], 30, "c")  # 3 batches
    s.schedule(["w1"])
    staged = s.prefetch["w1"]
    s.on_batch_done("w1", 1, 0, 0.1, 10)
    assert s.in_progress["w1"] is staged
    assert "w1" not in s.prefetch
    # next round stages the third batch
    out = s.schedule(["w1"])
    assert len(out) == 1 and out[0].staged


def test_pipeline_out_of_order_ack_clears_stage_only():
    s, _ = make_pipelined()
    s.submit_job(1, "a", ["x"], 20, "c")
    s.schedule(["w1"])
    primary = s.in_progress["w1"]
    staged_key = s.prefetch["w1"].key
    s.on_batch_done("w1", *staged_key, 0.1, 10)
    assert s.in_progress["w1"] is primary
    assert "w1" not in s.prefetch


def test_pipeline_worker_death_requeues_both_in_order():
    s, _ = make_pipelined()
    s.submit_job(1, "a", ["x"], 20, "c")
    s.schedule(["w1"])
    primary_key = s.in_progress["w1"].key
    staged_key = s.prefetch["w1"].key
    before = s.requeue_count
    s.on_worker_failed("w1")
    q = list(s.queues["a"])
    assert [b.key for b in q[:2]] == [primary_key, staged_key]
    assert s.requeue_count == before + 2
    assert "w1" not in s.prefetch and "w1" not in s.in_progress


def test_pipeline_staged_batch_failure_keeps_primary_running():
    s, _ = make_pipelined()
    s.submit_job(1, "a", ["x"], 20, "c")
    s.schedule(["w1"])
    primary = s.in_progress["w1"]
    staged_key = s.prefetch["w1"].key
    requeued = s.on_batch_failed("w1", *staged_key)
    assert requeued is not None and requeued.key == staged_key
    assert s.in_progress["w1"] is primary
    assert "w1" not in s.prefetch
    assert s.queues["a"][0].key == staged_key


def test_pipeline_primary_failure_promotes_stage():
    s, _ = make_pipelined()
    s.submit_job(1, "a", ["x"], 20, "c")
    s.schedule(["w1"])
    primary_key = s.in_progress["w1"].key
    staged = s.prefetch["w1"]
    requeued = s.on_batch_failed("w1", *primary_key)
    assert requeued is not None and requeued.key == primary_key
    assert s.in_progress["w1"] is staged
    assert "w1" not in s.prefetch


def test_pipeline_preemption_requeues_stage_behind_primary():
    s, clock = make_pipelined()
    # model a starts alone and gets staged work; then model b arrives
    # and the fair split preempts a's workers: both the displaced
    # primary and its stage must requeue, primary in front
    s.submit_job(1, "a", ["x"], 40, "c")
    s.schedule(["w1", "w2"])
    assert set(s.prefetch) == {"w1", "w2"}
    s.submit_job(2, "b", ["y"], 40, "c")
    out = s.schedule(["w1", "w2"])
    preempting = [a for a in out if a.preempted is not None]
    assert preempting, "b should preempt at least one of a's workers"
    w = preempting[0].worker
    assert w not in s.prefetch  # stage requeued with its primary
    qa = list(s.queues["a"])
    assert qa[0].key == preempting[0].preempted.key


def test_pipeline_never_stages_in_dual_model_rounds():
    s, _ = make_pipelined()
    s.submit_job(1, "a", ["x"], 40, "c")
    s.submit_job(2, "b", ["y"], 40, "c")
    out = s.schedule(["w1", "w2", "w3"])
    assert all(not a.staged for a in out)
    assert not s.prefetch


def test_pipeline_snapshot_folds_stage_behind_primary():
    s, _ = make_pipelined()
    s.submit_job(1, "a", ["x"], 30, "c")
    s.schedule(["w1"])
    primary_key = s.in_progress["w1"].key
    staged_key = s.prefetch["w1"].key
    snap = s.snapshot()
    s2 = Scheduler({"a": FAST})
    s2.restore(snap)
    keys = [b.key for b in s2.queues["a"]]
    assert keys[0] == primary_key and keys[1] == staged_key
    assert not s2.prefetch and not s2.in_progress


def test_pipeline_c5_shows_staged_assignments():
    s, _ = make_pipelined()
    s.submit_job(1, "a", ["x"], 20, "c")
    s.schedule(["w1"])
    c5 = s.c5_assignments()
    assert c5["w1"]["model"] == "a"
    assert c5["w1 (staged)"]["staged"] is True


# ------------------------------------------------- per-class fair share


def _drain_classes(s, workers, rounds=64):
    """Drive schedule rounds, completing every assignment each round;
    returns the grant order as a list of slo_class values."""
    grants = []
    for _ in range(rounds):
        out = s.schedule(workers)
        if not out:
            break
        for a in out:
            grants.append(a.batch.slo_class)
        for a in list(out):
            s.on_batch_done(a.worker, a.batch.job_id, a.batch.batch_id,
                            0.01, len(a.batch.files))
    return grants


def test_class_weighted_fair_share_deterministic():
    """Interactive/batch classes sharing one model queue split its
    free workers 3:1 by weight (class_split over the fair_split
    machinery) with FIFO preserved WITHIN each class — deterministic
    grant sequence, no starvation even at one slot per round."""
    s, _ = make()
    # 12 interactive + 12 batch single-file jobs, interleaved arrival
    job = 0
    for i in range(12):
        for cls in ("batch", "interactive"):
            job += 1
            s.submit_job(job, "a", [f"f{job}"], 1, "c",
                         batch_size=1, slo_class=cls)
    grants = _drain_classes(s, ["w1"])  # ONE slot per round
    assert len(grants) == 24
    # 3:1 weighted share: every window of 4 grants holds 3
    # interactive + 1 batch until interactive runs dry
    for i in range(0, 16, 4):
        win = grants[i : i + 4]
        assert win.count("interactive") == 3 and win.count("batch") == 1
    # leftovers (batch only) still drain
    assert grants[16:].count("batch") == 8
    # determinism: identical setup => identical sequence
    s2, _ = make()
    job = 100
    for i in range(12):
        for cls in ("batch", "interactive"):
            job += 1
            s2.submit_job(job, "a", [f"g{job}"], 1, "c",
                          batch_size=1, slo_class=cls)
    assert _drain_classes(s2, ["w1"]) == grants


def test_class_fifo_within_class_and_disable():
    s, _ = make()
    for j, cls in enumerate(
        ["interactive", "interactive", "batch", "interactive", "batch"],
        start=1,
    ):
        s.submit_job(j, "a", [f"f{j}"], 1, "c",
                     batch_size=1, slo_class=cls)
    out = s.schedule(["w1", "w2", "w3", "w4"])
    # 4 slots over {3 interactive, 2 batch}: 3:1 by weight
    got = [(a.batch.job_id, a.batch.slo_class) for a in out]
    assert [j for j, c in got if c == "interactive"] == [1, 2, 4]
    assert [j for j, c in got if c == "batch"] == [3]
    # class_weights = {} restores strict FIFO
    s2, _ = make()
    s2.class_weights = {}
    for j, cls in enumerate(
        ["batch", "batch", "batch", "interactive"], start=1
    ):
        s2.submit_job(j, "a", [f"f{j}"], 1, "c",
                      batch_size=1, slo_class=cls)
    out2 = s2.schedule(["w1", "w2"])
    assert [a.batch.job_id for a in out2] == [1, 2]


def test_class_unclassed_batches_keep_reference_fifo():
    """Operator jobs (slo_class None) are untouched by the class
    machinery: a single-class queue pops in reference FIFO order."""
    s, _ = make()
    for j in range(1, 5):
        s.submit_job(j, "a", [f"f{j}"], 1, "c", batch_size=1)
    out = s.schedule(["w1", "w2"])
    assert [a.batch.job_id for a in out] == [1, 2]


def test_class_weighted_share_applies_in_dual_model_rounds():
    """The weighted class split must hold when TWO models are active
    (the normal mixed deployment: an image model plus the ingress LM
    model) — `_grow_to` draws through `_take_batches`, so a sustained
    batch-class backlog on one model's queue cannot starve that
    model's interactive requests just because another model shares
    the round."""
    s, _ = make()
    # model b keeps the dual-model path engaged; model a's queue is
    # mixed-class with batch submitted first
    for j in range(1, 9):
        s.submit_job(j, "a", [f"f{j}"], 1, "c", batch_size=1,
                     slo_class="batch")
    for j in range(9, 13):
        s.submit_job(j, "a", [f"f{j}"], 1, "c", batch_size=1,
                     slo_class="interactive")
    s.submit_job(20, "b", [f"g{n}" for n in range(40)], 40, "c")
    out = s.schedule(["w1", "w2", "w3", "w4"])
    a_grants = [a.batch.slo_class for a in out if a.batch.model == "a"]
    assert a_grants, "model a got no workers in the dual-model round"
    # strict FIFO would hand model a's slots to the batch backlog
    # exclusively; the weighted split (3:1) must seat interactive
    # work first despite its later arrival
    assert a_grants.count("interactive") >= a_grants.count("batch")
    assert "interactive" in a_grants


def test_class_weights_cap_by_availability():
    """A class granted more slots than it has queued work hands the
    spares to the other class — slots never idle while work waits."""
    s, _ = make()
    s.submit_job(1, "a", ["x"], 1, "c", batch_size=1,
                 slo_class="interactive")
    for j in range(2, 8):
        s.submit_job(j, "a", [f"f{j}"], 1, "c", batch_size=1,
                     slo_class="batch")
    out = s.schedule(["w1", "w2", "w3", "w4"])
    assert len(out) == 4  # 1 interactive + 3 batch (redistributed)
    classes = [a.batch.slo_class for a in out]
    assert classes.count("interactive") == 1
    assert classes.count("batch") == 3


# ------------------------------------- models whose batches join a slot grid
# (a backend that declares `on_dispatch`: `Scheduler.set_joins_grid`); each
# case over both kinds of model, at the depth controller's unprobed depth 1

KINDS = pytest.mark.parametrize(
    "joins", [True, False], ids=["joins_a_grid", "batch_after_batch"])


def make_kind(joins):
    s, clock = make()
    s.set_joins_grid("a", joins)
    assert s.pipeline_depth == 1
    return s, clock


@KINDS
def test_depth_1_stages_only_a_model_that_joins_a_grid(joins):
    s, _ = make_kind(joins)
    s.submit_job(1, "a", ["x"], 50, "c")  # 5 batches of 10
    out = s.schedule(["w1", "w2"])
    assert [a.staged for a in out] == (
        [False, False, True, True] if joins else [False, False])
    assert set(s.in_progress) == {"w1", "w2"}
    assert set(s.prefetch) == ({"w1", "w2"} if joins else set())
    # two batches a worker, not N: nothing more goes out
    assert s.schedule(["w1", "w2"]) == []
    # an ACK promotes the stage and the next round stages again
    s.on_batch_done("w1", 1, 0, 0.1, 10)
    out = s.schedule(["w1", "w2"])
    assert [(a.worker, a.staged) for a in out] == [("w1", joins)]


@KINDS
def test_the_probes_backlog_leaves_joining_models_out(joins):
    s, _ = make_kind(joins)
    s.submit_job(1, "a", ["x"], 50, "c")
    s.submit_job(2, "b", ["x"], 30, "c")
    assert s.probe_backlog() == (3 if joins else 8)


@KINDS
def test_snapshot_restore_keeps_what_the_backend_is(joins):
    s, _ = make_kind(joins)
    s.submit_job(1, "a", ["x"], 30, "c")
    s.schedule(["w1"])
    snap = json.loads(json.dumps(s.snapshot()))
    r = Scheduler({}, now=Clock())
    r.restore(snap)
    assert ("a" in r.joins_grid) == joins
    # in-flight batches folded back in order; staged the same way
    out = r.schedule(["w1"])
    assert [(a.batch.batch_id, a.staged) for a in out] == (
        [(0, False), (1, True)] if joins else [(0, False)])


@KINDS
def test_a_standby_that_takes_over_stages_the_same_way(joins):
    """The standby's own `register_lm` recorded the fact; its shadow
    holds what the relays gave it, and it never assigned anything."""
    primary, _ = make_kind(joins)
    standby, _ = make_kind(joins)
    primary.submit_job(1, "a", ["x"], 40, "c")
    standby.submit_job(1, "a", ["x"], 40, "c", batch_size=10)
    primary.schedule(["w1", "w2"])
    primary.on_batch_done("w1", 1, 0, 0.1, 10)
    standby.shadow_prune(1, 0, 10)
    out = standby.schedule(["w1", "w2"])
    assert [(a.batch.batch_id, a.staged) for a in out] == (
        [(1, False), (2, False), (3, True)] if joins
        else [(1, False), (2, False)])


@KINDS
def test_a_second_model_unstages_whatever_the_first_is(joins):
    s, _ = make_kind(joins)
    s.pipeline_depth = 2  # the other kind stages at depth 2 only
    s.submit_job(1, "a", ["x"], 40, "c")
    s.schedule(["w1", "w2"])
    assert len(s.prefetch) == 2
    s.submit_job(2, "b", ["x"], 20, "c")
    s.schedule(["w1", "w2"])
    assert not s.prefetch and len(s.pop_revoked_stages()) == 2
