"""Traffic from a data file and a seed, and the arithmetic that turns what
clients saw into latencies.

One general generator reads every traffic mix (`benchmark/traffic/*.json`):
arrivals, the sizes of the items sent, and how they are driven. A seed
never changes the amount of work or its shape: every seed gets the SAME
sequence of inter-arrival gaps and item sizes (quantiles of the stated
distributions, not random draws, shuffled once by `ORDER_SEED`), started
at another point (rotated by the seed), and other item contents. A free shuffle for every seed was tried first (PR
24): it moved the chat cell's median time to first token by 5-8% from
seed to seed where two runs of one seed differed by 1-5%, because the
order decides which long prompts meet which bursts. A rotation keeps the
bursts and only moves where the window cuts them.

The open-loop rules (copied and corrected from `dml_tpu/ingress/loadgen.py`,
whose `drive_one` timed from the actual send and whose `summarize` left
shed requests out of the percentiles):

- a request's clock starts when it was DUE, not when it was sent, so a
  stalled generator or server charges the wait to the requests behind;
- a request that was shed, rejected, lost or not finished by the drain
  limit stays in the sample at the drain limit; it is never dropped;
- how late the generator sent each request is reported beside them.
"""

from __future__ import annotations

import math
import random
import statistics
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence


#: the one fixed order of every traffic mix's sizes and gaps
ORDER_SEED = 0


def percentile(values: Sequence[float], p: float) -> float:
    """Linear-interpolation percentile (the NIST / numpy 'linear' rule):
    rank p/100 * (n - 1) between its floor and ceiling neighbours."""
    vals = sorted(values)
    if not vals:
        return math.nan
    if len(vals) == 1:
        return float(vals[0])
    rank = (p / 100.0) * (len(vals) - 1)
    lo, hi = math.floor(rank), math.ceil(rank)
    frac = rank - lo
    return float(vals[lo] * (1.0 - frac) + vals[hi] * frac)


def quantile_draws(dist: Dict[str, Any], n: int) -> List[float]:
    """`n` values at the mid-quantiles (i + 0.5) / n of `dist`, in
    ascending order: the same multiset for every seed."""
    kind = dist["dist"]
    lo, hi = dist.get("min", -math.inf), dist.get("max", math.inf)
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if kind == "lognormal":
            z = statistics.NormalDist().inv_cdf(u)
            v = float(dist["median"]) * math.exp(float(dist["sigma"]) * z)
        elif kind == "exponential":
            v = -math.log(1.0 - u) * float(dist["mean"])
        elif kind == "fixed":
            v = float(dist["value"])
        else:
            raise ValueError(f"unknown distribution {kind!r}")
        out.append(min(max(v, lo), hi))
    return out


def ordered(values: List[Any], order: random.Random, seed: int) -> List[Any]:
    """`values` in the traffic mix's one fixed order (a shuffle by
    `order`), started at the seed's point: a rotation by seed mod n."""
    vals = list(values)
    order.shuffle(vals)
    k = seed % len(vals) if vals else 0
    return vals[k:] + vals[:k]


def int_draws(dist: Dict[str, Any], n: int, order: random.Random,
              seed: int) -> List[int]:
    return ordered([int(round(v)) for v in quantile_draws(dist, n)],
                   order, seed)


def arrival_times(rate_rps: float, seconds: float, order: random.Random,
                  seed: int) -> List[float]:
    """Due times of a Poisson-like open loop: round(rate * seconds)
    arrivals whose gaps are the mid-quantiles of the exponential with
    mean 1 / rate, in the mix's fixed order from the seed's point, scaled
    so that the last one is due half a mean gap before the window closes."""
    n = max(1, int(round(rate_rps * seconds)))
    gaps = ordered(quantile_draws(
        {"dist": "exponential", "mean": 1.0 / rate_rps}, n), order, seed)
    scale = (seconds - 0.5 / rate_rps) / sum(gaps)
    t, out = 0.0, []
    for g in gaps:
        t += g * scale
        out.append(t)
    return out


@dataclass
class Request:
    """One item of work and what its client saw. Times are seconds on
    the host's monotonic clock, relative to the window's start."""

    index: int
    name: str  # the item's name in the store
    size: Dict[str, int]  # e.g. {"prompt_tokens": 256, "output_tokens": 96}
    payload: Any = None  # what the reference needs (the prompt's tokens)
    due: Optional[float] = None  # open loop only
    sent: Optional[float] = None
    first: Optional[float] = None  # first streamed chunk
    last: Optional[float] = None  # last streamed chunk, or the terminal
    done: Optional[float] = None  # terminal at the client
    chunks: int = 0
    first_chunk_items: int = 0
    items: int = 0  # output items delivered (tokens, answers)
    ok: bool = False
    reason: Optional[str] = None
    streamed: Optional[List[int]] = None
    result: Any = None
    stages: Dict[str, float] = field(default_factory=dict)


def open_loop_summary(reqs: Sequence[Request],
                      drain_limit_s: float) -> Dict[str, Any]:
    """Latencies of an open-loop window over EVERY request due in it."""
    lim = float(drain_limit_s)

    def held(v: Optional[float], r: Request) -> float:
        # missing, or later than the limit: at the limit
        if v is None or not r.ok:
            return lim
        return min(max(v - r.due, 0.0), lim)

    e2e = [held(r.done, r) for r in reqs]
    ttft = [held(r.first, r) for r in reqs]
    tpot = [
        (r.last - r.first) / (r.items - r.first_chunk_items)
        for r in reqs
        if r.ok and r.chunks > 1 and r.items > r.first_chunk_items
    ]
    late = [max(0.0, r.sent - r.due) for r in reqs if r.sent is not None]
    after_first = sum(r.items - r.first_chunk_items for r in reqs
                      if r.ok and r.chunks > 1)
    failed = sum(1 for r in reqs if not r.ok)
    ms = 1000.0
    return {
        "attempted": len(reqs),
        "failed": failed,
        "latency_p50_ms": percentile(e2e, 50) * ms,
        "latency_p95_ms": percentile(e2e, 95) * ms,
        "ttft_mean_ms": sum(ttft) / len(ttft) * ms,
        "tpot_mean_ms": (sum(r.last - r.first for r in reqs
                             if r.ok and r.chunks > 1) / after_first * ms
                         if after_first else None),
        "ttft_p50_ms": percentile(ttft, 50) * ms,
        "ttft_p95_ms": percentile(ttft, 95) * ms,
        "tpot_p50_ms": percentile(tpot, 50) * ms if tpot else None,
        "tpot_p95_ms": percentile(tpot, 95) * ms if tpot else None,
        "tpot_samples": len(tpot),
        "gen_late_p50_ms": percentile(late, 50) * ms if late else None,
        "gen_late_p95_ms": percentile(late, 95) * ms if late else None,
        "gen_late_max_ms": max(late) * ms if late else None,
    }
