"""A worker holds two batches of a continuous-batching model.

Over the in-process cluster (`chaos.LocalCluster`: real UDP control
plane, real store, real `JobService`) with `tests/_gridstub.StubGrid`
behind every node where one `LMServer` stands behind the benchmark's:
`S` slots, a FIFO queue, requests that hold a slot for the number of
steps their file's name gives. The TEST steps the grid, and only when
the job path has nothing in flight, so the control plane takes no grid
time at all and what the grid records follows from the rules alone:

- the scheduler stages a next batch on a busy worker of a model whose
  backend declares `on_dispatch`, with the depth controller at 1;
- the worker lets a stage that lands mid-drain enter the backend at
  once, behind the batch before it;
- a stage cancel after promotion, a failed primary and a dead worker
  each leave every batch answered exactly once;
- two closed-loop jobs of two batches of unequal lengths fill the
  grid, where one batch a worker (the rule before) leaves a fifth of
  it empty.
"""

import asyncio
import random

import pytest

from dml_tpu.cluster import chaos
from dml_tpu.observability import METRICS
from dml_tpu.tracing import TRACER

from _gridstub import MODEL, StubGrid, drain, grid_cluster, prompt_name

SLOTS = 8


def leader_of(c):
    return next(sn for sn in c.nodes.values() if sn.node.is_leader)


async def seed_prompts(c, steps):
    """One prompt file a request; request i holds a slot `steps[i]`
    steps. Sorted names = the order a job samples them in."""
    client = c.client()
    for i, n in enumerate(steps):
        await client.store.put_bytes(prompt_name(i, n), b"1 2 3\n",
                                     timeout=20.0)
    return client


def assigned(c):
    s = leader_of(c).jobs.scheduler
    return list(s.in_progress.values()) + list(s.prefetch.values())


def joined_count():
    return METRICS.snapshot()["counters"].get(
        f"jobs_batches_joined_total{{model={MODEL}}}", 0)


async def settle(c, grid, n_live, what):
    """Until the grid holds `n_live` live calls and the coordinator has
    as many batches assigned: nothing of the job path is in flight."""
    await c.wait_for(
        lambda: len(grid.live_calls()) == n_live
        and len(assigned(c)) == n_live, 15.0, what)


async def step_until(c, grid, cond, what, limit=2000):
    for _ in range(limit):
        if cond():
            return
        grid.step()
        # let what the step finished reach whoever waits for it
        await asyncio.sleep(0)
    raise AssertionError(f"grid ran {limit} steps without {what}")


# ----------------------------------------------------------------------
# the worker's half: when a staged batch enters the backend
# ----------------------------------------------------------------------


async def test_stage_landing_mid_drain_joins_the_running_grid(tmp_path):
    """One worker, one job of three batches, a grid that holds two.
    b0 (long) and b1 (short) go out in one round and enter the grid in
    that order; b1 ends first, so b2 is staged while b0 is still
    draining: it must enter the grid at once, not when b0 ends, and
    third."""
    grid = StubGrid(2 * SLOTS)
    steps = [40] * SLOTS + [4] * SLOTS + [6] * SLOTS
    async with grid_cluster(3, 24100, tmp_path, grid, batch=SLOTS) as c:
        client = await seed_prompts(c, steps)
        leader = leader_of(c)
        # the depth controller is at its unprobed 1 and stays out of it
        assert leader.jobs.depth_ctl.depth == 1
        TRACER.reset()
        before = joined_count()
        job_id = await client.jobs.submit_job(MODEL, 3 * SLOTS)
        await settle(c, grid, 2, "b0 and b1 in the grid")
        assert [call.items[0] for call in grid.calls] == [0, SLOTS]
        assert leader.jobs.scheduler.pipeline_depth == 1

        b0, b1 = grid.calls
        await step_until(c, grid, lambda: b1.fut.done(), "b1 finishing")
        assert b0.live, "b0 must still be draining"
        # b1's ACK stages b2 onto the busy worker; it joins mid-drain
        await settle(c, grid, 2, "b2 joining b0's drain")
        assert [call.items[0] for call in grid.calls] == [
            0, SLOTS, 2 * SLOTS], "a worker's batches enter in order"
        assert b0.live and grid.calls[2].live

        await step_until(c, grid, lambda: not grid.has_work(), "drain")
        done = await client.jobs.wait_job(job_id, timeout=20.0)
        assert done["total_queries"] == 3 * SLOTS
        assert leader.jobs.depth_ctl.state == "warmup"

        # tracing: both later batches joined a batch still in its
        # inference; the first did not
        rows = [d["lb"] for d in TRACER.loop_spans("worker_infer")
                if d["lb"].get("model") == MODEL]
        assert sorted((lb["batch"], lb["joined"]) for lb in rows) == [
            (0, 0), (1, 1), (2, 1)]
        assert joined_count() - before == 2
        assert TRACER.summary()["worker_infer"]["joined_mean"] == (
            pytest.approx(2 / 3))
        # a stage that joins does not wait out its predecessor
        waits = [b["stage_wait"] for b in leader.jobs.batch_timing]
        assert max(waits) < 1.0, waits


async def test_batch_after_batch_backend_is_not_staged(tmp_path):
    """The same job over a backend WITHOUT `on_dispatch`: with the
    controller unprobed at depth 1 nothing is staged, the worker runs
    one batch at a time, and no batch joins another."""
    grid = StubGrid(SLOTS)
    steps = [5] * (3 * SLOTS)
    async with grid_cluster(3, 24120, tmp_path, grid, joins=False) as c:
        client = await seed_prompts(c, steps)
        leader = leader_of(c)
        before = joined_count()
        job_id = await client.jobs.submit_job(MODEL, 3 * SLOTS)
        for k in range(3):
            await settle(c, grid, 1, f"batch {k} alone in the grid")
            assert not leader.jobs.scheduler.prefetch
            call = grid.calls[-1]
            await step_until(c, grid, lambda: call.fut.done(),
                             f"batch {k} finishing")
        done = await client.jobs.wait_job(job_id, timeout=20.0)
        assert done["total_queries"] == 3 * SLOTS
        assert max(grid.occupancy) == SLOTS and len(grid.calls) == 3
        assert joined_count() == before


# ----------------------------------------------------------------------
# exactly once, with two running batches a worker
# ----------------------------------------------------------------------


def answered_once(leader, n):
    """The coordinator counted every query of the job once."""
    return leader.jobs.scheduler.query_counts.get(MODEL, 0) == n


async def test_stage_cancel_after_promotion_answers_once(tmp_path):
    """A second model's job arrives while a worker's stage has already
    been promoted into the grid: the scheduler pulls the stage back to
    its queue (`WORKER_STAGE_CANCEL` finds nothing parked, the promoted
    batch is left to finish) and every batch is still counted once."""
    grid = StubGrid(SLOTS)
    steps = [30] * SLOTS + [3] * SLOTS + [3] * SLOTS
    async with grid_cluster(3, 24140, tmp_path, grid) as c:
        client = await seed_prompts(c, steps)
        await client.store.put_bytes("img.jpeg", b"stub", timeout=20.0)
        leader = leader_of(c)
        job_id = await client.jobs.submit_job(MODEL, 3 * SLOTS)
        await settle(c, grid, 2, "b0 and b1 in the grid")
        assert len(leader.jobs.scheduler.prefetch) == 1
        other = await client.jobs.submit_job(chaos.STUB_MODEL, 8)
        await c.wait_for(lambda: not leader.jobs.scheduler.prefetch,
                         10.0, "the stage revoked")
        # both jobs finish; the grid keeps draining whatever is re-sent
        done, done_other = await drain(grid, asyncio.ensure_future(
            asyncio.gather(client.jobs.wait_job(job_id, timeout=30.0),
                           client.jobs.wait_job(other, timeout=30.0))))
        assert done["total_queries"] == 3 * SLOTS
        assert done_other["total_queries"] == 8
        assert answered_once(leader, 3 * SLOTS)


async def test_failed_primary_with_a_promoted_stage_answers_once(tmp_path):
    """The primary fails in the backend after its stage was promoted:
    the coordinator requeues it, the stage goes on, the job completes
    with every query counted once."""
    grid = StubGrid(SLOTS)
    steps = [6] * (3 * SLOTS)
    failed = []

    def fail_first_b0(call):
        if call.items[0] == 0 and not failed:
            failed.append(call)
            return True
        return False

    grid.fail = fail_first_b0
    async with grid_cluster(3, 24160, tmp_path, grid) as c:
        client = await seed_prompts(c, steps)
        leader = leader_of(c)
        job_id = await client.jobs.submit_job(MODEL, 3 * SLOTS)
        done = await drain(grid, asyncio.ensure_future(
            client.jobs.wait_job(job_id, timeout=30.0)))
        assert done["total_queries"] == 3 * SLOTS
        assert failed and leader.jobs.scheduler.requeue_count >= 1
        assert answered_once(leader, 3 * SLOTS)
        # b0 ran again, after the failure
        assert sum(call.items[0] == 0 for call in grid.calls) == 2


async def test_dead_worker_with_two_running_batches_answers_once(tmp_path):
    """A worker dies holding TWO running batches; both are requeued in
    order and the other worker answers them, each counted once."""
    grid = StubGrid(SLOTS)
    steps = [50] * (4 * SLOTS)
    async with grid_cluster(4, 24180, tmp_path, grid) as c:
        client = await seed_prompts(c, steps)
        leader = leader_of(c)
        job_id = await client.jobs.submit_job(MODEL, 4 * SLOTS)
        await settle(c, grid, 4, "two workers, two batches each")
        sched = leader.jobs.scheduler
        victim = next(
            w for w in sched.prefetch if w != c.client().node.me.unique_name)
        assert victim in sched.in_progress
        before = sched.requeue_count
        await c.crash_node(victim)
        await c.wait_for(lambda: sched.requeue_count >= before + 2, 15.0,
                         "both of the dead worker's batches requeued")
        done = await drain(grid, asyncio.ensure_future(
            c.client().jobs.wait_job(job_id, timeout=60.0)))
        assert done["total_queries"] == 4 * SLOTS
        assert answered_once(leader, 4 * SLOTS)


async def test_standby_that_takes_over_stages_the_same_way(tmp_path):
    """The leader dies mid-job: the promoted standby's scheduler knows
    what the model's backend is (its own `register_lm` said so) and
    stages onto busy workers with ITS controller at depth 1."""
    grid = StubGrid(SLOTS)
    steps = [20] * (8 * SLOTS)
    async with grid_cluster(5, 24200, tmp_path, grid) as c:
        client = await seed_prompts(c, steps)
        old = leader_of(c)
        old_u = old.node.me.unique_name
        job_id = await client.jobs.submit_job(MODEL, 8 * SLOTS)
        await settle(c, grid, 6, "three workers, two batches each")
        await c.crash_node(old_u)
        await c.wait_for(
            lambda: c.converged() and leader_of(c).node.me.unique_name
            != old_u, 20.0, "the standby leading")
        new = leader_of(c)
        assert MODEL in new.jobs.scheduler.joins_grid
        assert new.jobs.depth_ctl.depth == 1
        staged = []
        done = await drain(
            grid, asyncio.ensure_future(
                c.client().jobs.wait_job(job_id, timeout=60.0)),
            lambda: staged.append(len(new.jobs.scheduler.prefetch)))
        assert done["total_queries"] == 8 * SLOTS
        assert max(staged) >= 1, "the new coordinator never staged"
        assert new.jobs.scheduler.pipeline_depth == 1


# ----------------------------------------------------------------------
# the issue's simulation, as a test over the real job path
# ----------------------------------------------------------------------


def answer_steps(n, seed=4):
    """Unequal answer lengths: lognormal, median 48, sigma 0.5, clipped
    to 16..128 (the shape of `lm_jobs_closed_long_answers`, an eighth
    of its size)."""
    rng = random.Random(seed)
    return [max(16, min(128, int(rng.lognormvariate(3.871, 0.5))))
            for _ in range(n)]


@pytest.mark.parametrize("joins,port", [(True, 24220), (False, 24240)],
                         ids=["two_batches_a_worker", "one_batch_a_worker"])
async def test_closed_loop_occupancy(tmp_path, joins, port):
    """Two closed-loop clients, each one job of two batches in flight,
    two workers, answers of unequal lengths. The grid steps only when
    the job path is quiet (every assigned batch is in the grid, nothing
    assignable is queued, every client waits on a job the coordinator
    holds), so occupancy is the rule's, not the control plane's speed.
    Two batches a worker: >= 0.95 S. One batch a worker (the rule
    before this one: the scheduler's fact cleared, the same backend):
    <= 0.85 S."""
    slots = 2 * SLOTS
    grid = StubGrid(slots)
    n = 2 * slots  # a job: two batches
    rounds = 4
    async with grid_cluster(4, port, tmp_path, grid) as c:
        client = await seed_prompts(c, answer_steps(n))
        if not joins:
            for sn in c.nodes.values():
                sn.jobs.scheduler.set_joins_grid(MODEL, False)
        leader = leader_of(c)
        sched = leader.jobs.scheduler
        pool = 2
        state = {}  # client -> ("turning" | "waiting" | "done", job id)

        async def loop(k):
            for _ in range(rounds):
                state[k] = ("turning", None)
                job_id = await client.jobs.submit_job(MODEL, n)
                state[k] = ("waiting", job_id)
                await client.jobs.wait_job(job_id, timeout=120.0)
            state[k] = ("done", None)

        def quiet():
            held = assigned(c)
            if len(grid.live_calls()) != len(held):
                return False
            room = pool * (2 if joins else 1) - len(held)
            if room > 0 and sched.queues.get(MODEL):
                return False
            return len(state) == 2 and all(
                phase == "done" or (phase == "waiting" and job in sched.jobs)
                for phase, job in state.values())

        tasks = [asyncio.ensure_future(loop(k)) for k in range(2)]
        steady = []
        while not all(t.done() for t in tasks):
            await c.wait_for(
                lambda: quiet() or all(t.done() for t in tasks), 30.0,
                "the job path to fall quiet")
            grid.step()
            # let what the step finished reach whoever waits for it
            await asyncio.sleep(0)
            if not any(phase == "done" for phase, _ in state.values()):
                # (once a client has stopped the loop is no longer closed)
                steady.append(grid.occupancy[-1])
        for t in tasks:
            t.result()
        assert answered_once(leader, 2 * rounds * n)
        # steady state: the first quarter (the first jobs, which start
        # together into an empty grid) left out
        window = steady[len(steady) // 4:]
        assert len(window) > 100, len(window)
        mean = sum(window) / len(window)
        if joins:
            assert mean >= 0.95 * slots, mean
            assert joined_count() > 0
        else:
            assert mean <= 0.85 * slots, mean
