"""Open-loop driver: single requests through the cluster's front door.

Each request goes through `client.ingress.submit(model, slo=...,
store_name=<its own item>, stream=...)` at its DUE time, whatever became
of the ones before it; a streamed request's chunks are stamped as the
client receives them (`stream_text(on_chunk=...)`), and `wait` gives the
terminal. The rate is the traffic file's `rate_rps`: fixed, never
searched for.
"""

from __future__ import annotations

import asyncio
import random
import time
from typing import Any, Dict, List

from benchmark.harness.loadgen import ORDER_SEED, Request, arrival_times

#: seconds of the cell's own arrivals that warm-up replays before the window
WARM_SECONDS = 6.0


def plan(traffic: Dict[str, Any], seconds: float, seed: int,
         config: Dict[str, Any], items_mod, rate_rps: float = None,
         ) -> List[Request]:
    order = random.Random(ORDER_SEED)
    rate = float(rate_rps or traffic["arrivals"]["rate_rps"])
    due = arrival_times(rate, seconds, order, seed)
    reqs = items_mod.make(traffic["items"], len(due), order, seed, seed,
                          config)
    for r, t in zip(reqs, due):
        r.due = t
    return reqs


def _warm_plan(traffic, seed, config, items_mod) -> List[Request]:
    warm = plan(traffic, WARM_SECONDS, seed + 1, config, items_mod)
    for i, w in enumerate(warm):
        w.name = f"w{i:05d}.tokens.txt"
    return warm


def store_items(traffic, reqs, seed, config, items_mod) -> List[Request]:
    """What set-up puts into the store: every request's own item, and
    those of the few seconds of arrivals that only the warm-up sends."""
    return list(reqs) + _warm_plan(traffic, seed, config, items_mod)


async def _one(ingress, model: str, r: Request, traffic, items_mod,
               t0: float, limit_s: float) -> None:
    from dml_tpu.ingress.router import RequestRejected

    now = time.monotonic
    stream = bool(traffic.get("stream"))
    r.sent = now() - t0
    try:
        rid = await ingress.submit(
            model, slo=traffic["slo"], store_name=r.name, stream=stream,
            timeout=float(traffic.get("submit_timeout_s", 8.0)))
    except RequestRejected as e:
        r.reason = ("shed:" if e.shed else "rejected:") + str(e.reason)
        return
    chunks: List[str] = []
    if stream:
        def stamp(c: str) -> None:
            t = now() - t0
            n = items_mod.count_items(c)
            if r.first is None:
                r.first = t
            # reads that arrive within 2 ms are one delivery: a decode
            # dispatch hands its tokens over one by one, microseconds apart
            if r.last is None or t - r.last > 0.002:
                r.chunks += 1
            if r.chunks == 1:
                r.first_chunk_items += n
            r.last = t
            r.items += n

        chunks = await ingress.stream_text(rid, timeout=limit_s,
                                           on_chunk=stamp)
    term = await ingress.wait(rid, timeout=limit_s)
    r.done = now() - t0
    if not term.get("ok"):
        r.reason = str(term.get("reason") or term.get("terminal"))
        return
    r.ok = True
    r.result = items_mod.result_items(term["result"])
    r.stages = dict(term.get("stages") or {})
    if stream:
        r.streamed = items_mod.parse_streamed(chunks)
    else:
        r.first = r.last = r.done
        r.items = len(r.result)


async def _timed(ingress, model, r, traffic, items_mod, t0, drain_s):
    delay = r.due - (time.monotonic() - t0)
    if delay > 0:
        await asyncio.sleep(delay)
    try:
        await asyncio.wait_for(
            _one(ingress, model, r, traffic, items_mod, t0, drain_s),
            timeout=drain_s)
    except asyncio.TimeoutError:
        r.ok, r.reason = False, "undrained"
    except Exception as e:  # lost submit, no leader: a miss, not a crash
        r.ok, r.reason = False, f"lost:{e!r}"


async def warm(cluster, system, traffic, stored: List[Request],
               items_mod) -> None:
    """A few seconds of the cell's own arrivals before the window, on items
    of their own: the front door's path end to end, the serving loop in
    its steady state, and the packed readbacks that such traffic makes
    (see `backends/lm.py`, `_warm_packed_readbacks`)."""
    client = cluster.client()
    warm = [r for r in stored if r.name.startswith("w")]
    t0 = time.monotonic()
    await asyncio.gather(*[
        _timed(client.ingress, system.name, r, traffic, items_mod, t0, 120.0)
        for r in warm])
    bad = [r.reason for r in warm if not r.ok]
    if bad:
        raise RuntimeError(f"warm-up requests failed: {bad[:3]}")


async def run(cluster, system, traffic, reqs: List[Request], items_mod,
              seconds: float, root: str, at_window_end) -> Dict[str, Any]:
    client = cluster.client()
    drain_s = float(traffic["drain_limit_s"])
    t0 = time.monotonic()
    tasks = [asyncio.ensure_future(
        _timed(client.ingress, system.name, r, traffic, items_mod, t0,
               drain_s)) for r in reqs]

    def in_flight() -> int:
        return sum(1 for r, t in zip(reqs, tasks)
                   if r.sent is not None and not t.done())

    samples: List[int] = []
    while time.monotonic() - t0 < seconds:
        await asyncio.sleep(min(0.25, max(0.0, seconds - (time.monotonic() - t0))))
        samples.append(in_flight())
    window_s = time.monotonic() - t0
    at_window_end()
    await asyncio.gather(*tasks)
    q = max(1, len(samples) // 4)

    def mean(xs: List[int]) -> float:
        return sum(xs) / max(1, len(xs))

    return {"t0": t0, "window_s": window_s,
            "in_flight_second_quarter": mean(samples[q:2 * q]),
            "in_flight_last_quarter": mean(samples[-q:]),
            "in_flight_at_close": samples[-1] if samples else 0,
            "drain_s": time.monotonic() - t0 - window_s}
