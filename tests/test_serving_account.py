"""The LM serving thread's account of itself, over a small serve loop on
the CPU (a chunk server, a block-diffusion expert server and a
latent-attention expert server, each the benchmark's configuration at its
rehearsal size): `lm_exposed` opens where the thread returns from a
blocking wait and closes where it has fed the device again or goes idle,
never across a readback; a dispatch's routing is one `lm_route` span and
three histogram calls; and the thread's spans tile its time."""

import os
import sys
import threading

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import manifest as mf  # noqa: E402
from dml_tpu.inference import lm_server  # noqa: E402
from dml_tpu.tracing import TRACER  # noqa: E402

#: the spans that tile the driver thread (`lm_place` where a step finds
#: the grid empty and places before it dispatches)
TILES = ("lm_step", "lm_submit", "lm_idle", "lm_turn", "lm_place")
EPS = 5e-6  # t0 / t1 are rounded to a microsecond


@pytest.fixture(scope="module", params=[
    "mistral7b_widths_l8", "sdar30b_a3b_l6", "joyai_llm_flash_ep16"])
def served(request):
    """(server, the loop spans of two callers' four batches through its
    driver, the routing histograms' observe_many calls)."""
    config = mf.load_json("configs", request.param)
    small = {**config, **config["rehearsal"]}
    system = mf.load_module("backends", small["system"]).System(
        small, mf.load_module("references", small["reference"]), seed=5)
    calls = []
    real = lm_server._M_MOE_TOUCHED.observe_many
    mp = pytest.MonkeyPatch()
    mp.setattr(lm_server._M_MOE_TOUCHED, "observe_many",
               lambda v, **kw: (calls.append(len(v)), real(v, **kw))[1])
    try:
        srv, driver = system.be.server, system.be.driver
        top = srv.cfg.vocab_size if srv.diffusion is None else min(
            srv.cfg.vocab_size, srv.diffusion.mask_token_id)
        rng = np.random.default_rng(3)

        def batch(n):
            sizes = rng.integers(4, srv.max_len // 3, size=n)
            return ([rng.integers(1, top, size=s).astype(np.int32)
                     for s in sizes],
                    [int(b) for b in rng.integers(1, srv.max_len // 3, n)])

        driver.serve(*batch(2))  # compiles outside the account
        TRACER.reset()
        calls.clear()
        work = [[batch(srv.max_slots + 1), batch(2)] for _ in range(2)]

        def client(batches):
            for prompts, budgets in batches:
                driver.serve(prompts, budgets)

        threads = [threading.Thread(target=client, args=(w,)) for w in work]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
        yield srv, TRACER.loop_spans(), calls
    finally:
        mp.undo()
        system.free()
        TRACER.reset()


def _inside(t, spans):
    return any(d["t0"] - EPS <= t <= d["t1"] + EPS for d in spans)


def test_exposure_runs_from_a_wait_to_the_next_feed(served):
    srv, spans, _ = served
    by = {}
    for d in spans:
        by.setdefault(d["name"], []).append(d)
    exposed = by["lm_exposed"]
    assert len(exposed) >= len(by["lm_step"]) >= 4
    for d in exposed:
        after = d["lb"]["after"]
        assert after in ("readback", "firsts", "insert_wait")
        if after == "insert_wait":  # inside a latent prefill group
            assert srv.cfg.latent is not None
            assert _inside(d["t0"], by["lm_prefill_group"])
        else:  # at a blocking readback's return
            assert any(0 <= d["t0"] - r["t1"] <= 1e-3
                       for r in by["lm_readback"]), d
        # closed by an enqueue (a dispatch's, a prefill group's) or at
        # the thread's going idle (its last `lm_idle` is still open, so
        # not in the ring: the last stretch ends after the last step)
        assert (_inside(d["t1"], by["lm_dispatch"])
                or _inside(d["t1"], by.get("lm_prefill_group", ()))
                or any(abs(d["t1"] - i["t0"]) <= 1e-3
                       for i in by.get("lm_idle", ()))
                or (d is exposed[-1]
                    and d["t1"] >= by["lm_step"][-1]["t1"] - EPS)), d
        # the device is busy, or about to be read, in none of it
        for r in by["lm_readback"]:
            assert min(d["t1"], r["t1"]) - max(d["t0"], r["t0"]) <= EPS
    for a, b in zip(exposed, exposed[1:]):
        assert b["t0"] >= a["t1"] - EPS  # one stretch at a time


def test_the_driver_threads_spans_tile_its_time(served):
    _, spans, _ = served
    steps = [d for d in spans if d["name"] == "lm_step"]
    lo, hi = steps[0]["t0"], steps[-1]["t1"]
    tiles = sorted(
        (max(lo, d["t0"]), min(hi, d["t1"])) for d in spans
        if d["name"] in TILES and not d["par"]
        and d["t1"] > lo and d["t0"] < hi)
    covered, edge = 0.0, lo
    for a, b in tiles:
        assert a >= edge - EPS, "two of the thread's spans overlap"
        covered += b - a
        edge = max(edge, b)
    assert covered >= 0.95 * (hi - lo), (covered, hi - lo)
    assert sum(d["name"] == "lm_turn" for d in spans) >= len(steps) - 1


def test_a_dispatchs_routing_is_one_span_and_one_call_a_histogram(served):
    srv, spans, calls = served
    steps = [d for d in spans if d["name"] == "lm_step"]
    routes = [d for d in spans if d["name"] == "lm_route"]
    if not srv._routed[0]:
        assert not routes and not calls
        return
    sids = {d["sid"] for d in steps}
    assert len(routes) == len(steps) and {d["par"] for d in routes} == sids
    # one call a dispatch, holding a forward a layer each (a dispatch
    # whose slots were all empty in every forward makes none)
    assert 0 < len(calls) <= len(steps) and max(calls) > 1
    for st in steps:
        assert "experts_touched" in st["lb"]
