"""`correct` for a served LM: what the timed path delivered, against the
plain reference.

After the window has closed and the program's state is freed, a sample
of the requests it finished is taken, twice as many as the grid has
slots: those that were live together at the moment the most were, which
hold distinct slots of the grid, so that a fault confined to one slot is
met; the longest; and picks drawn from the seed in turn from each prompt
bucket, so that every prefill program is met. For each, the reference runs once over the prompt and its served
tokens (float32, HIGHEST precision, full attention), and at each served
token the gap by which its logit lies below the reference's best is read.
Greedy decoding is exact in logic; at bfloat16 programs of different
shapes round differently, so a served token may sit a rounding below
the best where the reference itself hardly prefers one. Two numbers:

- `served_gap_mean`, the mean gap over the sample's tokens. A rounding
  error e flips a token about as often as e and by about e, so the mean
  goes as e squared: it is steady from seed to seed (thousands of tokens)
  and sets bfloat16 and int8 well apart, where the widest gap and the
  share of flipped tokens, which go as e, do not (PERF.md section 2).
- `served_gap_max`, the widest gap, held against the fault a mean would
  dilute: one wrong token (a broken cache row, a shifted position) sits
  about 1 below, on logits of unit spread.

Limits and the readings they were set from: PERF.md section 2.

Exact numbers, over EVERY finished request, limit 0: output tokens
missing against each request's budget, and streamed sequences that are
not the terminal's result.
"""

from __future__ import annotations

import os
import random
from typing import Any, Dict, List


def _bucket(n: int) -> int:
    return max(16, 1 << (int(n) - 1).bit_length())


def _live_together(done: List[Any], tap: List[Dict[str, Any]]) -> List[Any]:
    """The finished requests that were being served at the moment the most
    were, by the token tap's newest record of each (first to last token
    at the host): requests served together hold distinct slots. Where an
    item was served more than once (a closed loop repeats its jobs) the
    newest serve is as a rule, not always, the one whose answer is kept."""
    span: Dict[int, Any] = {}
    for rec in tap:
        base = os.path.basename(rec["path"])
        for r in done:
            if r.name in base and rec["first"] is not None:
                span[id(r)] = (rec["first"], rec["last"], r)
    if not span:
        return []
    at = max((a for a, _, _ in span.values()), key=lambda t: sum(
        1 for a, b, _ in span.values() if a <= t <= b))
    return [r for a, b, r in span.values() if a <= at <= b]


def _sample(done: List[Any], tap: List[Dict[str, Any]], k: int,
            seed: int) -> List[Any]:
    longest = max(done, key=lambda r: (len(r.payload) + len(r.result), r.index))
    picked = {id(r): r for r in [longest] + _live_together(done, tap)[:k - 1]}
    by_bucket: Dict[int, List[Any]] = {}
    rest = [r for r in done if id(r) not in picked]
    random.Random(seed).shuffle(rest)
    for r in rest:
        by_bucket.setdefault(_bucket(len(r.payload)), []).append(r)
    queues = [by_bucket[b] for b in sorted(by_bucket)]
    while len(picked) < k and any(queues):
        for q in queues:
            if q and len(picked) < k:
                r = q.pop()
                picked[id(r)] = r
    return list(picked.values())


def check(run: Dict[str, Any], reference, seed: int, *,
          control: bool = False) -> List[Dict[str, Any]]:
    cfg = run["config"]
    spec = run["system"]["spec"]
    limits = cfg["correct"]["limits"]
    reqs = run["requests"]
    done = [r for r in reqs if r.ok and r.result is not None]
    missing = sum(abs(r.size["output_tokens"] - len(r.result)) for r in done)
    mismatch = sum(1 for r in done
                   if r.streamed is not None and r.streamed != r.result)
    numbers: List[Dict[str, Any]] = [
        {"name": "tokens_missing", "value": missing,
         "limit": limits["tokens_missing"],
         "ok": missing <= limits["tokens_missing"], "over": len(done)},
        {"name": "stream_mismatch", "value": mismatch,
         "limit": limits["stream_mismatch"],
         "ok": mismatch <= limits["stream_mismatch"], "over": len(done)},
    ]
    if not done:
        numbers.append({"name": "served_gap_mean", "value": None,
                        "limit": limits["served_gap_mean"], "ok": False,
                        "over": 0})
        return numbers
    items = run["traffic"]["items"]
    rows_pad = int(items["output_tokens"]["max"])
    pad_to = int(items["prompt_tokens"]["max"]) + rows_pad
    sample = _sample(done, run["tap"], 2 * int(spec["max_slots"]), seed)
    params = reference.make_params(spec, seed)
    gap, total, ctl, ctl_total, tokens, exact, margins = 0., 0., 0., 0., 0, 0, []
    for r in sample:
        g = reference.served_gaps(
            params, spec, r.payload, r.result, pad_to=pad_to,
            rows_pad=rows_pad, control=control,
            rope_theta=float(cfg.get("rope_theta", 10000.0)))
        gap = max(gap, g["gap_max"])
        total += g["gap_sum"]
        ctl = max(ctl, g.get("control_gap_max", 0.0))
        ctl_total += g.get("control_gap_sum", 0.0)
        tokens += g["tokens"]
        exact += g["exact"]
        margins.append(g["top2_margin_median"])
    del params
    numbers.append({
        "name": "served_gap_mean", "value": total / tokens,
        "limit": limits["served_gap_mean"],
        "ok": total / tokens <= limits["served_gap_mean"],
        "over": len(sample), "tokens": tokens, "tokens_exact": exact,
        "reference_top2_margin_median": sorted(margins)[len(margins) // 2],
    })
    numbers.append({
        "name": "served_gap_max", "value": gap,
        "limit": limits["served_gap_max"],
        "ok": gap <= limits["served_gap_max"], "over": len(sample),
    })
    if control:
        # readings, not comparisons: what the int8 reference would put
        # first on the same sequences; the mean has to be over its limit
        numbers.append({"name": "control_int8_gap_mean",
                        "value": ctl_total / tokens,
                        "would_fail": ctl_total / tokens
                        > limits["served_gap_mean"]})
        numbers.append({"name": "control_int8_gap_max", "value": ctl})
    return numbers
