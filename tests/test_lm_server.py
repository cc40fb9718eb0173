"""Continuous-batching LM server (inference/lm_server.py).

The load-bearing contract: batching requests together NEVER changes
any request's greedy output vs running `generate` on it in isolation
— slots, per-slot positions, prompt bucketing, mid-flight joins, and
slot reuse are all throughput mechanics, not semantics."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dml_tpu.inference.generate import LMConfig, generate
from dml_tpu.inference.lm_server import (
    _BUCKET_FLOOR, LMServer, _bucket, _prefill_bucket, _prefill_groups)
from dml_tpu.models.transformer import TransformerLM

CFG = LMConfig(vocab_size=61, d_model=32, n_heads=4, n_layers=2, d_ff=64,
               dtype=jnp.float32, n_kv_heads=2)


@pytest.fixture(scope="module")
def params():
    model = TransformerLM(
        vocab_size=CFG.vocab_size, d_model=CFG.d_model,
        n_heads=CFG.n_heads, n_layers=CFG.n_layers, d_ff=CFG.d_ff,
        dtype=jnp.float32, n_kv_heads=CFG.n_kv_heads,
    )
    return model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]


def _isolated(params, prompt, n):
    return np.asarray(generate(
        params, CFG, jnp.asarray(np.asarray(prompt, np.int32)[None]), n
    ))[0]


def test_bucket():
    assert _bucket(1) == 16 and _bucket(16) == 16
    assert _bucket(17) == 32 and _bucket(100) == 128


def test_single_request_matches_generate(params):
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, CFG.vocab_size, 16)  # exact bucket
    srv = LMServer(params, CFG, max_slots=2, max_len=64, chunk=4)
    rid = srv.submit(prompt, 10)
    out = srv.run()
    np.testing.assert_array_equal(out[rid], _isolated(params, prompt, 10))


def test_bucketed_prompt_matches_generate(params):
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, CFG.vocab_size, 11)  # 11 -> bucket 16
    srv = LMServer(params, CFG, max_slots=2, max_len=64, chunk=4)
    rid = srv.submit(prompt, 9)
    out = srv.run()
    np.testing.assert_array_equal(out[rid], _isolated(params, prompt, 9))


def test_mixed_requests_batch_without_interference(params):
    """Different prompt lengths and budgets decoding TOGETHER must
    each match their isolated generation exactly."""
    rng = np.random.RandomState(2)
    reqs = [
        (rng.randint(0, CFG.vocab_size, 7), 12),
        (rng.randint(0, CFG.vocab_size, 16), 5),
        (rng.randint(0, CFG.vocab_size, 23), 9),
    ]
    srv = LMServer(params, CFG, max_slots=3, max_len=64, chunk=4)
    rids = [srv.submit(p, n) for p, n in reqs]
    out = srv.run()
    for rid, (p, n) in zip(rids, reqs):
        np.testing.assert_array_equal(
            out[rid], _isolated(params, p, n), err_msg=f"req {rid}"
        )


def test_slot_reuse_and_queueing(params):
    """More requests than slots: finished slots are reused and the
    queued request's output is still exact (stale cache from the
    previous occupant must be invisible)."""
    rng = np.random.RandomState(3)
    reqs = [(rng.randint(0, CFG.vocab_size, 5 + 3 * i), 4 + 2 * i)
            for i in range(5)]
    srv = LMServer(params, CFG, max_slots=2, max_len=64, chunk=3)
    rids = [srv.submit(p, n) for p, n in reqs]
    out = srv.run()
    assert len(out) == 5
    for rid, (p, n) in zip(rids, reqs):
        np.testing.assert_array_equal(
            out[rid], _isolated(params, p, n), err_msg=f"req {rid}"
        )


def test_mid_flight_join(params):
    """A request submitted while others are mid-decode joins a live
    batch and still matches isolation."""
    rng = np.random.RandomState(4)
    p1 = rng.randint(0, CFG.vocab_size, 9)
    p2 = rng.randint(0, CFG.vocab_size, 6)
    srv = LMServer(params, CFG, max_slots=2, max_len=64, chunk=2)
    r1 = srv.submit(p1, 12)
    srv.step()  # p1 is now mid-decode
    r2 = srv.submit(p2, 8)  # joins the running batch
    out = srv.run()
    np.testing.assert_array_equal(out[r1], _isolated(params, p1, 12))
    np.testing.assert_array_equal(out[r2], _isolated(params, p2, 8))


def test_single_token_budget_and_validation(params):
    srv = LMServer(params, CFG, max_slots=1, max_len=32, chunk=4)
    rid = srv.submit(np.array([3, 1, 4]), 1)
    out = srv.run()
    np.testing.assert_array_equal(
        out[rid], _isolated(params, np.array([3, 1, 4]), 1)
    )
    with pytest.raises(ValueError):
        srv.submit(np.array([], np.int32), 4)
    with pytest.raises(ValueError):
        srv.submit(np.arange(30), 10)  # 30 + 10 > max_len 32
    with pytest.raises(ValueError):
        srv.submit(np.array([1, 2]), 0)  # zero budget: rejected, not
        # silently one token (generate() returns [] for it)


def test_sampled_request_independent_of_batch(params):
    """temperature > 0: a request's sampled output is a pure function
    of (seed, rid, positions) — fold_in streams, not a shared per-step
    key — so it cannot depend on what else is decoding alongside it
    (advisor finding, r2)."""
    rng = np.random.RandomState(5)
    pa = rng.randint(0, CFG.vocab_size, 9)
    pb = rng.randint(0, CFG.vocab_size, 14)

    def serve(prompts_budgets):
        srv = LMServer(params, CFG, max_slots=2, max_len=64, chunk=3,
                       temperature=0.8, top_k=20, seed=7)
        rids = [srv.submit(p, n) for p, n in prompts_budgets]
        return srv.run(), rids

    out_alone, (ra,) = serve([(pa, 10)])
    out_packed, (ra2, rb) = serve([(pa, 10), (pb, 6)])
    # rid of A is 1 in both servers -> identical stream
    np.testing.assert_array_equal(out_alone[ra], out_packed[ra2])
    # and the second request actually produced tokens under sampling
    assert len(out_packed[rb]) == 6


def test_submit_many_matches_sequential_submit(params):
    """submit_many (one batched placement round) must produce the
    same rids and the same outputs as sequential submit() calls."""
    prompts = [
        np.array([1, 2, 3], np.int32),
        np.array([4, 5], np.int32),
        np.array([6], np.int32),
    ]
    a = LMServer(params, CFG, max_slots=2, max_len=32, chunk=4)
    rids_a = [a.submit(p, 6) for p in prompts]
    out_a = a.run()
    b = LMServer(params, CFG, max_slots=2, max_len=32, chunk=4)
    rids_b = b.submit_many(prompts, 6)
    out_b = b.run()
    assert rids_a == rids_b
    for ra, rb in zip(rids_a, rids_b):
        np.testing.assert_array_equal(out_a[ra], out_b[rb])


def test_submit_many_validates_before_queueing(params):
    srv = LMServer(params, CFG, max_slots=2, max_len=8, chunk=2)
    with pytest.raises(ValueError, match="exceeds max_len"):
        srv.submit_many(
            [np.array([1, 2], np.int32), np.arange(7, dtype=np.int32)], 4
        )
    # the valid first prompt must not have been queued by the failed call
    assert not srv._queue


# -- LMDriver: thread-safe cross-batch continuous batching ------------


def test_driver_single_ticket_matches_generate(params):
    from dml_tpu.inference.lm_server import LMDriver

    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, CFG.vocab_size, 5 + 4 * i) for i in range(3)]
    srv = LMServer(params, CFG, max_slots=2, max_len=64, chunk=4)
    drv = LMDriver(srv)
    try:
        outs = drv.serve(prompts, 8)
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(o, _isolated(params, p, 8))
    finally:
        drv.stop()


def test_driver_concurrent_tickets_are_exact(params):
    """The load-bearing property of the cluster LM path (VERDICT r4
    item 2): many callers submitting concurrently — their prompts
    interleaved arbitrarily into one slot grid — each get outputs
    identical to isolated generate()."""
    import threading as th

    from dml_tpu.inference.lm_server import LMDriver

    rng = np.random.RandomState(7)
    batches = [
        [rng.randint(0, CFG.vocab_size, int(rng.randint(3, 20)))
         for _ in range(3)]
        for _ in range(4)
    ]
    srv = LMServer(params, CFG, max_slots=3, max_len=64, chunk=3)
    drv = LMDriver(srv)
    results = [None] * len(batches)
    errors = []

    def worker(i):
        try:
            results[i] = drv.serve(batches[i], 7)
        except BaseException as e:  # surfaced in the main thread
            errors.append(e)

    threads = [th.Thread(target=worker, args=(i,)) for i in range(len(batches))]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not errors, errors
        for batch, outs in zip(batches, results):
            assert outs is not None
            for p, o in zip(batch, outs):
                np.testing.assert_array_equal(o, _isolated(params, p, 7))
    finally:
        drv.stop()


def test_driver_on_dispatch_fires_before_completion(params):
    """on_dispatch must fire once the ticket's prompts are submitted
    — the hook the job pipeline uses to promote its staged next batch
    while this one is still decoding."""
    import threading as th

    from dml_tpu.inference.lm_server import LMDriver

    srv = LMServer(params, CFG, max_slots=1, max_len=32, chunk=2)
    drv = LMDriver(srv)
    fired = th.Event()
    try:
        out = drv.serve(
            [np.array([1, 2, 3], np.int32)], 6,
            on_dispatch=fired.set,
        )
        assert fired.is_set()
        assert len(out[0]) == 6
    finally:
        drv.stop()


def test_lm_step_says_how_many_requests_waited_without_a_slot(params):
    """`lm_step`'s label `waiting`: requests queued in the server
    without a slot as the dispatch is issued. Three requests over one
    slot: two wait behind the first, one behind the second, none
    behind the last; `profile spans` averages it."""
    from dml_tpu.tracing import TRACER

    srv = LMServer(params, CFG, max_slots=1, max_len=32, chunk=2)
    TRACER.reset()
    rids = srv.submit_many([np.array([1, 2, 3], np.int32)] * 3, 4)
    assert len(srv.run(rids)) == 3
    steps = TRACER.loop_spans("lm_step")
    waited = [d["lb"]["waiting"] for d in steps]
    assert waited == sorted(waited, reverse=True), waited
    assert waited[0] == 2 and waited[-1] == 0 and 1 in waited
    assert TRACER.summary()["lm_step"]["waiting_mean"] == pytest.approx(
        sum(waited) / len(waited))


def test_driver_validation_error_propagates_to_caller(params):
    from dml_tpu.inference.lm_server import LMDriver

    srv = LMServer(params, CFG, max_slots=1, max_len=8, chunk=2)
    drv = LMDriver(srv)
    try:
        with pytest.raises(ValueError, match="exceeds max_len"):
            drv.serve([np.arange(7, dtype=np.int32)], 4)
        # and the driver still serves valid work afterwards
        out = drv.serve([np.array([1, 2], np.int32)], 3)
        np.testing.assert_array_equal(
            out[0], _isolated(params, np.array([1, 2]), 3)
        )
    finally:
        drv.stop()


def test_driver_rejects_after_stop(params):
    from dml_tpu.inference.lm_server import LMDriver

    srv = LMServer(params, CFG, max_slots=1, max_len=32, chunk=2)
    drv = LMDriver(srv)
    out = drv.serve([np.array([4, 2], np.int32)], 2)
    assert len(out[0]) == 2
    drv.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        drv.serve([np.array([1], np.int32)], 2)


def test_backend_overlap_and_serial_modes_agree(params, tmp_path):
    """LMBackend.overlap=True (driver) and =False (the r3/r4 lock
    path) must produce identical results for the same prompt files."""
    from dml_tpu.inference.lm_backend import LMBackend, write_prompt_file

    rng = np.random.RandomState(8)
    paths = []
    for i in range(4):
        p = str(tmp_path / f"p{i}.tokens.txt")
        write_prompt_file(p, rng.randint(0, CFG.vocab_size, 4 + 3 * i))
        paths.append(p)

    def results_for(overlap):
        be = LMBackend(params, CFG, max_new_tokens=6, max_slots=2,
                       max_len=64, chunk=3)
        be.overlap = overlap
        try:
            res, infer_t, cost = be.serve_files(paths)
        finally:
            be.close()
        assert infer_t > 0 and cost["batch_size"] == 2
        return res

    assert results_for(True) == results_for(False)


def test_driver_thread_death_fails_tickets_not_hangs(params):
    """A device error mid-step must FAIL every in-flight serve() call
    (review finding): silence would block callers forever on
    event.wait() — the exact hang the driver exists to prevent."""
    from dml_tpu.inference.lm_server import LMDriver

    srv = LMServer(params, CFG, max_slots=1, max_len=32, chunk=2)

    def exploding_step():
        raise RuntimeError("device fell over")

    srv.step = exploding_step
    drv = LMDriver(srv)
    with pytest.raises(RuntimeError, match="LMDriver thread died"):
        drv.serve([np.array([1, 2], np.int32)], 4)
    # the driver is stopped; new work is rejected, not hung
    with pytest.raises(RuntimeError):
        drv.serve([np.array([3], np.int32)], 2)


def test_run_with_rids_leaves_other_results(params):
    """run(rids) must return exactly the requested rids and leave
    other finished requests for their owner (the serial-mode /
    LMDriver coexistence contract — review finding)."""
    srv = LMServer(params, CFG, max_slots=2, max_len=32, chunk=2)
    pa, pb = np.array([1, 2], np.int32), np.array([3, 4, 5], np.int32)
    ra = srv.submit(pa, 4)
    rb = srv.submit(pb, 3)
    out = srv.run([rb])
    assert set(out) == {rb}
    np.testing.assert_array_equal(out[rb], _isolated(params, pb, 3))
    # ra was NOT consumed: it is either still decoding (run([rb])
    # stops stepping the moment rb retires) or parked in the done set
    # — its owner can still collect the exact result
    left = srv.run([ra])
    assert set(left) == {ra}
    np.testing.assert_array_equal(left[ra], _isolated(params, pa, 4))


def test_mixed_budgets_exact_and_slots_refill(params):
    """Per-request budgets in one burst: every output matches its own
    isolated generate(), and short requests retire early so queued
    work enters freed slots (the continuous-batching property the
    mixed-budget bench row measures)."""
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, CFG.vocab_size, 4 + 2 * i) for i in range(5)]
    budgets = [2, 9, 4, 7, 3]
    srv = LMServer(params, CFG, max_slots=2, max_len=64, chunk=3)
    rids = srv.submit_many(prompts, budgets)
    out = srv.run()
    for rid, p, b in zip(rids, prompts, budgets):
        np.testing.assert_array_equal(
            out[rid], _isolated(params, p, b), err_msg=f"req {rid}"
        )
    with pytest.raises(ValueError, match="budgets for"):
        srv.submit_many(prompts, [1, 2])


def test_metrics_counters_after_mixed_budget_serve(params):
    """The serve loop's registry instrumentation (observability.py):
    a mixed-budget continuous-batching run must account every request,
    every delivered token, and its dispatch/queue/readback timings.
    The registry is process-global, so assertions are deltas."""
    from dml_tpu.observability import METRICS

    c_req = METRICS.counter("lm_server_requests_total")
    c_done = METRICS.counter("lm_server_requests_completed_total")
    c_tok = METRICS.counter("lm_server_decode_tokens_total")
    c_steps = METRICS.counter("lm_server_steps_total")
    h_wait = METRICS.histogram("lm_server_queue_wait_seconds")
    h_step = METRICS.histogram("lm_server_step_seconds")
    g_slots = METRICS.gauge("lm_server_slots_active")
    g_total = METRICS.gauge("lm_server_slots_total")

    def hist_count(h):
        return sum(st[0] for _, st in h.items())

    before = (c_req.value(), c_done.value(), c_tok.value(),
              c_steps.value(), hist_count(h_wait), hist_count(h_step))

    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, CFG.vocab_size, 4 + 2 * i) for i in range(5)]
    budgets = [2, 9, 4, 7, 3]
    srv = LMServer(params, CFG, max_slots=2, max_len=64, chunk=3)
    srv.submit_many(prompts, budgets)
    srv.run()

    assert c_req.value() - before[0] == len(prompts)
    assert c_done.value() - before[1] == len(prompts)
    # every generated token is delivered exactly once: the placement
    # firsts plus the chunked takes sum to each request's own budget
    assert c_tok.value() - before[2] == sum(budgets)
    assert c_steps.value() - before[3] >= math.ceil((max(budgets) - 1) / 3)
    # one queue-wait sample per placed request; >=1 step timing
    assert hist_count(h_wait) - before[4] == len(prompts)
    assert hist_count(h_step) - before[5] >= 1
    assert g_slots.value() == 0.0  # drained
    assert g_total.value() == 2.0


def test_backend_mixed_budget_files(params, tmp_path):
    """serve_files honors per-file `# max_new_tokens` directives in
    both serving modes; outputs equal isolated generate() at each
    file's own budget."""
    from dml_tpu.inference.lm_backend import LMBackend, write_prompt_file

    rng = np.random.RandomState(10)
    paths, prompts, budgets = [], [], [3, 8, None, 5]
    for i, b in enumerate(budgets):
        p = str(tmp_path / f"p{i}.tokens.txt")
        prompt = rng.randint(0, CFG.vocab_size, 4 + 3 * i)
        write_prompt_file(p, prompt, max_new_tokens=b)
        paths.append(p)
        prompts.append(prompt)

    for overlap in (True, False):
        be = LMBackend(params, CFG, max_new_tokens=6, max_slots=2,
                       max_len=64, chunk=3)
        be.overlap = overlap
        try:
            res, _, _ = be.serve_files(paths)
        finally:
            be.close()
        for p, prompt, b in zip(paths, prompts, budgets):
            np.testing.assert_array_equal(
                res[p]["tokens"],
                _isolated(params, prompt, b if b is not None else 6),
                err_msg=f"{p} overlap={overlap}",
            )


def test_on_token_streams_equal_final_result(params, tmp_path):
    """Real-engine token streaming (the ingress on_token contract):
    every delivered token fires on_token(path, text) from the decode
    grid's packed readbacks, and the streamed text concatenates to
    EXACTLY the final result — both driver (overlap) and serial
    modes. This is what makes `request-load` streaming real-backend,
    not stub-only."""
    import numpy as np

    from dml_tpu.inference.lm_backend import LMBackend, write_prompt_file

    rng = np.random.RandomState(5)
    paths, prompts = [], []
    for i in range(3):
        p = str(tmp_path / f"s{i}.tokens.txt")
        prompt = rng.randint(0, CFG.vocab_size, 5 + 2 * i)
        write_prompt_file(p, prompt)
        paths.append(p)
        prompts.append(prompt)
    for overlap in (True, False):
        be = LMBackend(params, CFG, max_new_tokens=6, max_slots=2,
                       max_len=64, chunk=3)
        be.overlap = overlap
        streamed = {}
        try:
            res, _, _ = be.serve_files(
                paths,
                on_token=lambda path, text: streamed.setdefault(
                    path, []).append(text),
            )
        finally:
            be.close()
        for p in paths:
            toks = [int(t) for t in "".join(streamed[p]).split()]
            assert toks == res[p]["tokens"], (overlap, p)
    # the service's reflection sees the opt-in on the real backend
    from dml_tpu.jobs.service import _accepts_on_token

    be = LMBackend(params, CFG, max_new_tokens=4, max_slots=2,
                   max_len=64, chunk=2)
    try:
        assert _accepts_on_token(be.backend)
    finally:
        be.close()


def test_on_token_streams_prefilled_adoption(params):
    """The disaggregated decode path (submit_prefilled adoption)
    fires on_token too, first token included — streamed == final."""
    import numpy as np

    from dml_tpu.inference.lm_backend import LMBackend
    from dml_tpu.inference.lm_sharded import LMPrefillBackend

    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, CFG.vocab_size, n) for n in (5, 9)]
    pf = LMPrefillBackend(params, CFG, max_len=64)
    slabs = [pf.prefill_one(p, 5) for p in prompts]
    be = LMBackend(params, CFG, max_new_tokens=5, max_slots=2,
                   max_len=64, chunk=2)
    got = {0: [], 1: []}
    try:
        toks, _ = be.serve_prefilled(
            prompts, [5, 5], slabs,
            on_token=[
                (lambda t, i=i: got[i].append(int(t)))
                for i in range(2)
            ],
        )
    finally:
        be.close()
    for i in range(2):
        assert got[i] == [int(t) for t in toks[i]]


# ----------------------------------------------------------------------
# serve-loop spans (tracing.Tracer.loop_span) and the counters beside them
# ----------------------------------------------------------------------

_PHASES = ("lm_dispatch", "lm_pack", "lm_readback", "lm_deliver", "lm_place")


def _served_mixed_budgets(params):
    """Mixed budgets through a 3-slot grid (queueing, slot reuse, a
    budget-1 request that retires at placement), with the recorder and
    the registry read before and after."""
    from dml_tpu.observability import METRICS
    from dml_tpu.tracing import TRACER

    def metrics():
        hist = METRICS.histogram("lm_server_first_token_seconds").items()
        tok = METRICS.counter("lm_server_prefill_tokens_total")
        rows = METRICS.counter("lm_server_decode_kv_rows_total")
        return {"first_token_count": sum(v[0] for _, v in hist),
                "prompt": tok.value(kind="prompt"),
                "padded": tok.value(kind="padded"),
                **{"kv_" + k: rows.value(kind=k, layers="full")
                   for k in ("live", "read", "grid", "blocks")}}

    rng = np.random.RandomState(11)
    reqs = [(rng.randint(0, CFG.vocab_size, n), b) for n, b in
            ((7, 12), (16, 5), (23, 9), (40, 1), (5, 6), (30, 14), (9, 3))]
    TRACER.reset()
    before = metrics()
    srv = LMServer(params, CFG, max_slots=3, max_len=64, chunk=4)
    rids = srv.submit_many([p for p, _ in reqs], [b for _, b in reqs])
    out = srv.run()
    after = metrics()
    spans = TRACER.loop_spans()
    TRACER.reset()
    return srv, reqs, rids, out, spans, {
        k: after[k] - before[k] for k in after}


@pytest.fixture(scope="module")
def served(params):
    return _served_mixed_budgets(params)


def test_spans_leave_greedy_tokens_what_they_were(params, served):
    _, reqs, rids, out, _, _ = served
    for rid, (p, n) in zip(rids, reqs):
        np.testing.assert_array_equal(
            out[rid], _isolated(params, p, n), err_msg=f"req {rid}")


def test_every_chunk_step_holds_its_five_phases(served):
    """Each `lm_step` of the plain chunk path has exactly one child of
    each phase, in order, disjoint, inside the step, sharing its trace
    id; the phases cover all but a sliver of the step."""
    spans = served[4]
    steps = [d for d in spans if d["name"] == "lm_step"]
    assert len(steps) >= 4
    for st in steps:
        kids = sorted((d for d in spans if d["par"] == st["sid"]),
                      key=lambda d: d["t0"])
        assert [d["name"] for d in kids] == list(_PHASES)
        assert {d["tid"] for d in kids} == {st["tid"]}
        edges = [st["t0"]] + [x for d in kids for x in (d["t0"], d["t1"])] \
            + [st["t1"]]
        # one microsecond of slack: t0/t1 are rounded to it
        assert all(b - a >= -2e-6 for a, b in zip(edges, edges[1:])), edges
        assert st["lb"]["occupancy"] >= 1
        assert st["lb"]["tokens"] == [d for d in kids
                                      if d["name"] == "lm_deliver"][0]["lb"]["tokens"]
    # what no step delivered, `_flush_firsts` did: a readback with no
    # parent (the budget-1 request retired at placement), and only a
    # readback, a placement at submit or the constructor's one-off
    # cast of the tree may stand outside a step
    total = sum(b for _, b in served[1])
    assert served[0].tokens_delivered == total
    assert 0 < sum(st["lb"]["tokens"] for st in steps) <= total
    assert {d["name"] for d in spans if not d["par"]} <= {
        "lm_step", "lm_readback", "lm_place", "lm_request",
        "lm_weights_resident", "lm_exposed"}


def test_prefill_groups_count_prompt_and_padded_tokens(served):
    srv, reqs, _, _, spans, delta = served
    groups = [d for d in spans if d["name"] == "lm_prefill_group"]
    places = [d for d in spans if d["name"] == "lm_place"]
    assert groups and all(
        g["par"] in {d["sid"] for d in places} for g in groups)
    for g in groups:
        lb = g["lb"]
        # max_len 64 is under the bucket floor: ONE bucket, its rows
        # padded to max_slots, so a round is one group and nobody rides
        assert lb["bucket"] == srv.max_len and lb["riders"] == 0
        assert lb["padded_rows"] == srv.max_slots >= lb["rows"] >= 1
        assert lb["padded_tokens"] == srv.max_slots * srv.max_len
        assert 0 < lb["prompt_tokens"] <= lb["rows"] * lb["bucket"]
    assert len(groups) == sum(d["lb"]["requests"] > 0 for d in places)
    assert sum(g["lb"]["rows"] for g in groups) == len(reqs)
    assert sum(g["lb"]["prompt_tokens"] for g in groups) == sum(
        p.size for p, _ in reqs)
    # the counter's two kinds are the label sums
    assert delta["prompt"] == sum(g["lb"]["prompt_tokens"] for g in groups)
    assert delta["padded"] == sum(g["lb"]["padded_tokens"] for g in groups)
    assert sum(d["lb"]["requests"] for d in places) == len(reqs)


@pytest.mark.parametrize("lengths,shapes", [
    # the short three share one group of the floor's bucket
    ([5, 40, 70, 600], [(512, 4, 3, 0), (1024, 1, 1, 0)]),
    # the short one takes the spare row of three of bucket 1024
    ([5, 600, 801, 702], [(1024, 4, 4, 1)]),
])
def test_short_prompt_in_a_longer_group_matches_generate(
        params, lengths, shapes):
    """max_len 1024, so that groups take power-of-two rows and a bucket
    over the floor exists: whatever bucket and rows a prompt shares, it
    is served `generate`'s tokens."""
    from dml_tpu.tracing import TRACER

    rng = np.random.RandomState(29)
    prompts = [rng.randint(0, CFG.vocab_size, n) for n in lengths]
    budgets = [9, 1, 6, 12]
    srv = LMServer(params, CFG, max_slots=4, max_len=1024, chunk=4)
    TRACER.reset()
    rids = srv.submit_many(prompts, budgets)
    out = srv.run()
    groups = TRACER.loop_spans("lm_prefill_group")
    TRACER.reset()
    assert [(g["lb"]["bucket"], g["lb"]["padded_rows"], g["lb"]["rows"],
             g["lb"]["riders"]) for g in groups] == shapes
    for rid, p, n in zip(rids, prompts, budgets):
        np.testing.assert_array_equal(
            out[rid], _isolated(params, p, n), err_msg=f"len {p.size}")


def _traffic_lengths(rng, k):
    """k prompt lengths from the benchmark's traffic files: lognormal,
    median 256, sigma 0.9, clipped to 32..2048."""
    return np.clip(np.exp(rng.normal(np.log(256), 0.9, k)),
                   32, 2048).astype(int).tolist()


def _padded_before_pr29(lengths, max_len, max_slots):
    """What the rule this one replaced padded a round to: a group a
    power-of-two bucket from 16, max_slots rows up to bucket 256."""
    groups = {}
    for n in lengths:
        b = min(_bucket(n), max_len)
        groups[b] = groups.get(b, 0) + 1
    return sum(
        (max_slots if b <= 256 else min(_bucket(k, lo=1), max_slots)) * b
        for b, k in groups.items())


@pytest.mark.parametrize("case,a,b", [
    *[("shapes", seed, slots) for seed in range(4) for slots in (16, 32)],
    *[("equal", k, n) for k in (1, 2, 4, 8, 16) for n in (40, 300, 700, 2048)],
    *[("no_worse", seed, slots) for seed in range(3) for slots in (16, 32)],
    ("by_hand", 0, 0), ("tiny", 0, 0),
])
def test_prefill_groups_partition(case, a, b):
    """`_prefill_groups` at a real server's size (max_len 4096), where
    no CPU test can run the prefill: the shapes it may form, the
    warm-up's contract, and what it saves."""
    max_len = 4096
    if case == "shapes":
        rng, slots = np.random.RandomState(100 + a), b
        for _ in range(200):
            lens = _traffic_lengths(rng, rng.randint(1, slots + 1))
            groups = _prefill_groups(lens, max_len, slots)
            assert sorted(i for _, _, m in groups for i in m) == list(
                range(len(lens)))
            for bucket, rows, members in groups:
                own = [_prefill_bucket(lens[i], max_len) for i in members]
                assert bucket == max(own) >= _BUCKET_FLOOR
                assert rows == _bucket(len(members), lo=1) <= slots
                # a rider never raises the rows: equal-length prompts
                # of this bucket alone (the warm-up) form this shape
                assert rows <= _bucket(own.count(bucket), lo=1)
    elif case == "equal":
        # the warm-up's contract: 2^j equal-length prompts submitted
        # alone are ONE group of 2^j rows
        k, n = a, b
        assert _prefill_groups([n] * k, max_len, 16) == [
            (max(_bucket(n), _BUCKET_FLOOR), k, list(range(k)))]
    elif case == "no_worse":
        rng, slots, new, old = np.random.RandomState(200 + a), b, 0, 0
        worse = 0
        for _ in range(500):
            lens = _traffic_lengths(rng, rng.randint(1, 9))
            n = sum(r * bk for bk, r, _ in
                    _prefill_groups(lens, max_len, slots))
            o = _padded_before_pr29(lens, max_len, slots)
            # never above the old rule's on 32 slots. On 16, a round
            # that holds several prompts of ONE bucket <= 128 can be:
            # that group was 16 x 128 = 2048 tokens for all of them
            assert n <= o + (2 * _BUCKET_FLOOR if slots == 16 else 0), lens
            worse, new, old = worse + (n > o), new + n, old + o
        assert worse <= 5 and new < 0.6 * old
    elif case == "by_hand":
        g = _prefill_groups
        # the issue's rounds: one short prompt alone; three short ones
        assert g([200], max_len, 16) == [(512, 1, [0])]
        assert g([40, 100, 200], max_len, 32) == [(512, 4, [0, 1, 2])]
        # three of bucket 512 tie with 2 + 1 and stay whole; three of
        # bucket 1024 do not
        assert g([300] * 3, max_len, 16) == [(512, 4, [0, 1, 2])]
        assert [x[:2] for x in g([600] * 3, max_len, 16)] == [
            (1024, 1), (1024, 2)]
        # a short prompt takes the spare row of three long ones, and
        # is refused where it would raise the rows
        assert g([700, 5, 900, 600], max_len, 16) == [
            (1024, 4, [0, 1, 2, 3])]
        assert g([700, 5, 900, 600, 800], max_len, 16) == [
            (512, 1, [1]), (1024, 4, [0, 2, 3, 4])]
        assert g([5, 40, 70, 600], max_len, 16) == [
            (512, 4, [0, 1, 2]), (1024, 1, [3])]
        # max_slots that is no power of two caps the rows
        assert g([300] * 5, max_len, 6) == [(512, 6, [0, 1, 2, 3, 4])]
    else:
        # a server under the floor: one bucket, max_slots rows, one group
        assert _prefill_groups([5, 40, 70, 128], 128, 4) == [
            (128, 4, [0, 1, 2, 3])]
        assert _prefill_groups([9], 64, 3) == [(64, 3, [0])]


def test_every_request_span_is_ordered_and_counted(served):
    _, reqs, _, _, spans, delta = served
    rs = [d for d in spans if d["name"] == "lm_request"]
    assert len(rs) == len(reqs) and delta["first_token_count"] == len(reqs)
    assert sorted((d["lb"]["prompt_tokens"], d["lb"]["new_tokens"])
                  for d in rs) == sorted((p.size, b) for p, b in reqs)
    for d in rs:
        ev = dict(d["ev"])
        assert d["t0"] <= ev["placed"] <= ev["first_token"] <= d["t1"]
        assert d["par"] == ""  # no worker's infer span here


def test_spec_step_names_its_two_enqueues_lm_dispatch(params):
    from dml_tpu.tracing import TRACER

    rng = np.random.RandomState(12)
    prompt = rng.randint(0, CFG.vocab_size, 9)
    srv = LMServer(params, CFG, max_slots=2, max_len=64, chunk=4)
    srv.enable_spec_decode(3, draft_params=params, draft_cfg=CFG)
    TRACER.reset()
    rid = srv.submit(prompt, 10)
    out = srv.run()
    spans = TRACER.loop_spans()
    TRACER.reset()
    np.testing.assert_array_equal(out[rid], _isolated(params, prompt, 10))
    steps = [d for d in spans if d["name"] == "lm_step"]
    assert steps
    for st in steps:
        kids = sorted((d for d in spans if d["par"] == st["sid"]),
                      key=lambda d: d["t0"])
        assert [d["name"] for d in kids] == [
            "lm_dispatch", "lm_dispatch", "lm_pack", "lm_readback",
            "lm_deliver", "lm_place"]
        assert [d["lb"]["phase"] for d in kids[:2]] == ["propose", "verify"]


def test_driver_records_idle_submit_and_request_parent(params):
    """Through the LMDriver: an `lm_idle` span while it waits, an
    `lm_submit` span that parents the burst's `lm_place`, and an
    `lm_request` under the trace context handed in with the prompt."""
    from dml_tpu.inference.lm_server import LMDriver
    from dml_tpu.tracing import TRACER, TraceContext

    rng = np.random.RandomState(13)
    prompts = [rng.randint(0, CFG.vocab_size, n) for n in (6, 12)]
    srv = LMServer(params, CFG, max_slots=2, max_len=64, chunk=4)
    drv = LMDriver(srv)
    TRACER.reset()
    try:
        ctx = TraceContext("tREQ", "sINFER", True, key="a.txt")
        outs = drv.serve(prompts, [5, 7], trace=[ctx, None])
    finally:
        drv.stop()
    spans = TRACER.loop_spans()
    TRACER.reset()
    for p, n, o in zip(prompts, (5, 7), outs):
        np.testing.assert_array_equal(o, _isolated(params, p, n))
    sub = [d for d in spans if d["name"] == "lm_submit"]
    assert len(sub) == 1 and sub[0]["lb"]["requests"] == 2
    assert sub[0]["lb"]["tickets"] == 1
    # the ticket's wait for the thread: it was idle, so next to nothing
    assert 0.0 <= sub[0]["lb"]["ticket_wait_s"] < 1.0
    assert [d for d in spans if d["name"] == "lm_place"
            and d["par"] == sub[0]["sid"]]
    assert any(d["name"] == "lm_idle" for d in spans)
    reqs = {d["lb"]["prompt_tokens"]: d for d in spans
            if d["name"] == "lm_request"}
    assert (reqs[6]["tid"], reqs[6]["par"]) == ("tREQ", "sINFER")
    assert reqs[12]["par"] == "" and reqs[12]["tid"] != "tREQ"


# ----------------------------------------------------------------------
# live lengths: the kernel route, empty slots, the kv_rows accounting
# ----------------------------------------------------------------------


@pytest.fixture
def kernel_route(monkeypatch):
    """Cache attention through the Pallas kernel, as on a TPU; on the
    CPU it interprets. Function-scoped: every program traced under it
    belongs to a server (or an eager call) made inside the test."""
    from dml_tpu.inference import generate as g

    monkeypatch.setattr(g, "uses_decode_kernel", lambda: True)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-4),
                                        (jnp.bfloat16, 6e-2)])
def test_decode_step_kernel_route_matches_einsum(
        params, monkeypatch, dtype, atol):
    """A grouped cache (4 heads / 2 KV), ragged positions, two EMPTY
    slots whose pos is pinned at the last row and whose length is 0,
    stale garbage in every row: the kernel route gives the einsum
    route's logits for the live slots and its whole cache."""
    import dataclasses

    from dml_tpu.inference import generate as g

    cfg = dataclasses.replace(CFG, dtype=dtype)
    b, max_len = 5, 48
    rng = np.random.RandomState(20)
    cache = jax.tree_util.tree_map(
        lambda x: jnp.asarray(rng.standard_normal(x.shape), x.dtype),
        g.init_cache(cfg, b, max_len))
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, b), jnp.int32)
    pos = jnp.asarray([3, max_len - 1, 17, max_len - 1, 31], jnp.int32)
    lengths = jnp.asarray([4, 0, 18, 0, 32], jnp.int32)

    def step():
        logits, new = g.batched_decode_step(
            params, cfg, cache, tokens, pos, lengths=lengths)
        return np.asarray(logits), jax.tree_util.tree_map(
            lambda x: np.asarray(x.astype(jnp.float32)), new)

    want_logits, want_cache = step()
    monkeypatch.setattr(g, "uses_decode_kernel", lambda: True)
    got_logits, got_cache = step()
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(
        got_logits[live], want_logits[live], atol=atol)
    assert np.isfinite(got_logits).all()
    jax.tree_util.tree_map(
        lambda a, b_: np.testing.assert_allclose(a, b_, atol=atol),
        got_cache, want_cache)
    # and `pos + 1` is the default: the live slots read the same
    np.testing.assert_allclose(
        np.asarray(g.batched_decode_step(
            params, cfg, cache, tokens, pos)[0])[live],
        got_logits[live], atol=atol)


def test_kernel_route_serves_the_same_tokens_through_reused_slots(
        params, served, kernel_route):
    """Seven requests through three slots on the kernel route: slots
    free and refill, a freed slot's device pos stays pinned high while
    its length is 0 — and every request gets the tokens the einsum
    route served (the module's `served`, itself held to `generate`)."""
    _, reqs, rids, out, _, _ = served
    srv, _, k_rids, k_out, spans, delta = _served_mixed_budgets(params)
    for rid, k_rid, (_, n) in zip(rids, k_rids, reqs):
        np.testing.assert_array_equal(k_out[k_rid], out[rid])
        assert k_out[k_rid].size == n
    _check_kv_rows(srv, spans, delta)
    # one block is the whole 64-row cache here, so what was skipped
    # is the empty slots
    assert delta["kv_read"] < delta["kv_grid"]


def _check_kv_rows(srv, spans, delta):
    steps = [d["lb"] for d in spans if d["name"] == "lm_step"]
    grid = srv.chunk * srv.max_slots * srv.max_len
    for lb in steps:
        assert 0 < lb["kv_rows_live"] <= lb["kv_rows_read"] <= grid
        # a live slot attends at least its prompt and at most max_len
        assert lb["kv_rows_live"] <= lb["occupancy"] * srv.chunk * srv.max_len
    assert delta["kv_live"] == sum(lb["kv_rows_live"] for lb in steps)
    assert delta["kv_read"] == sum(lb["kv_rows_read"] for lb in steps)
    assert delta["kv_blocks"] == sum(lb["kv_blocks"] for lb in steps)
    assert delta["kv_grid"] == len(steps) * grid


def test_kv_rows_labels_and_counter_add_up(served):
    """On the einsum route every step streams the whole grid, and the
    counter says so: read == grid."""
    srv, _, _, _, spans, delta = served
    _check_kv_rows(srv, spans, delta)
    assert delta["kv_read"] == delta["kv_grid"]
    assert 0 < delta["kv_live"] < delta["kv_grid"]


def test_kv_rows_arithmetic_by_hand(params, monkeypatch, kernel_route):
    """`_kv_rows` against hand arithmetic, at a block of 16 rows."""
    from dml_tpu.inference import lm_server

    monkeypatch.setattr(lm_server, "decode_block_rows", lambda *a: 16)
    rng = np.random.RandomState(21)
    srv = LMServer(params, CFG, max_slots=3, max_len=64, chunk=4)
    # a budget of 1 retires at placement: slot 0 is empty again
    srv.submit_many([rng.randint(0, CFG.vocab_size, n) for n in (5, 7, 23)],
                    [1, 9, 9])
    assert [r is not None for r in srv._slot_req] == [False, True, True]
    assert list(srv.rid_vec) == [0, 2, 3]
    # slot 1 attends 8..11 rows (one block of 16 each step), slot 2
    # 24..27 (two blocks); the empty slot at the head has no item in
    # the kernel's work list: nothing fetched, no block visited
    assert srv._kv_rows() == (38 + 102, 4 * 16 + 4 * 32, 4 * 3 * 64,
                              4 * 1 + 4 * 2)
    srv.run()
    # near max_len: lengths clamp at the last row, blocks at the cache
    srv2 = LMServer(params, CFG, max_slots=1, max_len=64, chunk=4)
    srv2.submit(rng.randint(0, CFG.vocab_size, 61), 3)
    assert srv2._kv_rows() == (62 + 63 + 64 + 64, 4 * 64, 4 * 64, 4 * 4)
    # a block that does not divide the cache: the last one is short
    monkeypatch.setattr(lm_server, "decode_block_rows", lambda *a: 48)
    assert srv2._kv_rows() == (62 + 63 + 64 + 64, 4 * 64, 4 * 64, 4 * 2)


def test_kv_rows_are_what_the_kernel_walks(params, monkeypatch, kernel_route):
    """`read` and `blocks` against the kernel's own work list: at every
    step of the dispatch, `blocks` items and `read` rows of whole
    blocks, whatever slots stand empty; a dispatch with no live slot
    still visits one block a step (the grid is never empty)."""
    from dml_tpu.inference import lm_server
    from dml_tpu.ops.decode_attention import work_list

    bk = 16
    monkeypatch.setattr(lm_server, "decode_block_rows", lambda *a: bk)
    rng = np.random.RandomState(22)
    srv = LMServer(params, CFG, max_slots=4, max_len=64, chunk=4)
    srv.submit_many(
        [rng.randint(0, CFG.vocab_size, n) for n in (33, 3, 16, 47)],
        [9, 1, 9, 9])
    assert [r is not None for r in srv._slot_req] == [True, False, True, True]
    live, read, _, blocks = srv._kv_rows()
    pos0 = np.asarray([33, -1, 16, 47])  # the row each slot writes next
    items = rows = 0
    for i in range(srv.chunk):
        lens = np.where(pos0 >= 0, pos0 + i + 1, 0)
        slot_of, block_of, n = work_list(jnp.asarray(lens, jnp.int32), bk, 16)
        n = int(n)
        assert n == sum(-(-x // bk) for x in lens)
        items += n
        rows += n * bk
        assert not (np.asarray(slot_of)[:n] == 1).any()  # the empty slot
    assert (read, blocks) == (rows, items)
    assert live == sum(int(np.where(pos0 >= 0, pos0 + i + 1, 0).sum())
                       for i in range(srv.chunk))
    srv.run()
    empty = LMServer(params, CFG, max_slots=2, max_len=64, chunk=4)
    assert empty._kv_rows() == (0, 4 * bk, 4 * 2 * 64, 4)
