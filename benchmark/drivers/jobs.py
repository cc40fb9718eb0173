"""Closed-loop driver: whole jobs through `client.jobs.submit_job`.

`jobs_in_flight` clients each submit a job of `queries_per_job` queries
over the pool of items in the store, wait for it, fetch its merged
output and submit the next, until the window closes; the jobs then in
flight are left to finish and are checked, but only what was delivered
inside the window counts toward the rate. The pool holds exactly
`queries_per_job` items, so every job of every seed is the same work.
"""

from __future__ import annotations

import asyncio
import os
import random
import time
from typing import Any, Dict, List

from benchmark.harness.loadgen import ORDER_SEED, Request


def plan(traffic: Dict[str, Any], seconds: float, seed: int,
         config: Dict[str, Any], items_mod, rate_rps: float = None,
         ) -> List[Request]:
    # the pool's sizes are the same, in the same order, for every seed (a
    # closed loop has no window that could cut the sequence elsewhere:
    # rotating the pool moved throughput by 4-5% from seed to seed where
    # two runs of one seed agreed to 0.001-0.7%; PR 24); the seed gives the
    # prompts' tokens and the weights
    order = random.Random(ORDER_SEED)
    return items_mod.make(traffic["items"], int(traffic["queries_per_job"]),
                          order, 0, seed, config)


def store_items(traffic, reqs, seed, config, items_mod) -> List[Request]:
    return list(reqs)


async def _job(client, model: str, n: int, root: str, tag: str,
               timeout: float) -> Dict[str, Any]:
    job_id = await client.jobs.submit_job(model, n)
    done = await client.jobs.wait_job(job_id, timeout=timeout)
    merged = await client.jobs.get_output(
        job_id, os.path.join(root, f"out_{tag}_{job_id}.json"))
    return {"job": job_id, "queries": int(done.get("total_queries", 0)),
            "output": merged}


async def warm(cluster, system, traffic, stored, items_mod) -> None:
    """One whole round of the closed loop before the window: every client's
    job, full size. It runs the job path end to end, brings the loop to
    its steady state, and runs the placement rounds of a job's start (a
    whole batch into an empty grid), whose packed readbacks the window's
    jobs then repeat (see `backends/lm.py`, `_warm_packed_readbacks`)."""
    n = int(traffic["queries_per_job"])
    outs = await asyncio.gather(*[
        _job(cluster.client(), system.name, n, cluster.root, f"warm{k}", 600.0)
        for k in range(int(traffic["jobs_in_flight"]))])
    for out in outs:
        if out["queries"] != n:
            raise RuntimeError(f"warm-up job answered {out['queries']} of {n}")


async def run(cluster, system, traffic, reqs: List[Request], items_mod,
              seconds: float, root: str, at_window_end) -> Dict[str, Any]:
    client = cluster.client()
    n = int(traffic["queries_per_job"])
    drain_s = float(traffic["drain_limit_s"])
    by_name = {r.name: r for r in reqs}
    jobs: List[Dict[str, Any]] = []
    t0 = time.monotonic()

    async def loop(k: int) -> None:
        i = 0
        while time.monotonic() - t0 < seconds:
            rec = {"client": k, "sent": time.monotonic() - t0, "ok": False,
                   "queries": n, "items": 0, "bad": 0}
            jobs.append(rec)
            try:
                out = await _job(client, system.name, n, root, f"{k}_{i}",
                                 seconds + drain_s)
            except Exception as e:
                rec["reason"] = repr(e)
                i += 1
                continue
            rec["done"] = time.monotonic() - t0
            for name, r in by_name.items():
                got = out["output"].get(name)
                if got is None:
                    rec["bad"] += 1
                    continue
                toks = items_mod.result_items(got)
                rec["items"] += len(toks)
                rec["bad"] += len(toks) != r.size["output_tokens"]
                # the newest answer of each item is what `correct` samples
                r.result, r.items, r.ok, r.done = toks, len(toks), True, rec["done"]
            rec["ok"] = rec["bad"] == 0 and out["queries"] == n
            i += 1

    tasks = [asyncio.ensure_future(loop(k))
             for k in range(int(traffic["jobs_in_flight"]))]
    await asyncio.sleep(max(0.0, seconds - (time.monotonic() - t0)))
    window_s = time.monotonic() - t0
    at_window_end()
    await asyncio.gather(*tasks)
    return {"t0": t0, "window_s": window_s, "jobs": jobs,
            "drain_s": time.monotonic() - t0 - window_s}
