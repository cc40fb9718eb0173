"""`correct` for a served block-diffusion LM: what the timed path
delivered, against the plain reference.

A request's answer is tokens, the denoising step that fixed each
(`fixed_at`) and what its last block held past the budget; from these
every generated block is rebuilt as each denoising forward saw it. After
the window has closed and the program's state is freed, a sample of the
finished requests is taken as `checks/lm_served_tokens.py` takes it (those
live together when the most were, which hold distinct slots; the longest;
seeded picks from each prompt bucket). For each, the reference runs ONE
forward over the final sequence and the noisy copies of its blocks
(float32, HIGHEST precision, full attention under an explicit mask), and
reads at every masked position of every copy the best logit, its
log-probability and the logit of the token the position ended up with.

- `served_gap_mean` / `served_gap_max`: at the step that fixed a token,
  how far its reference logit lies below the reference's best at that
  position, given the block's state at that step. A rounding error e
  flips a token about as often as e and by about e, so the mean goes as
  e squared: steady from seed to seed, and it sets bfloat16 and int8
  apart. The widest gap is held against ONE wrong token (a commit that
  kept a mask row, a shifted position, a wrong mask: the blocks after it
  sit about 1 below, on logits of unit spread), which a mean dilutes.
- `choice_gap_max`: which positions a step fixed. The reference's
  confidence (log-probability of its best token) of a position fixed at
  step s may not lie below that of a position the step left masked by
  more than a rounding: the widest such shortfall.

Exact, over EVERY finished request, limit 0: `schedule_faults` (blocks
whose steps are not the configured static schedule, answers whose
`fixed_at` does not match their tokens or that do not end a block),
`tokens_missing`, `stream_mismatch`.

Limits and the readings they were set from: PERF.md section 2.
"""

from __future__ import annotations

import os
import random
from typing import Any, Dict, List


def _bucket(n: int) -> int:
    return max(16, 1 << (int(n) - 1).bit_length())


def _live_together(done: List[Any], tap: List[Dict[str, Any]]) -> List[Any]:
    """The finished requests that were being served at the moment the most
    were, by the token tap's newest record of each: requests served
    together hold distinct slots."""
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


def _whole(r: Any):
    """A request's generated tokens and steps with the last block's
    surplus: whole blocks."""
    res = r.result
    return (list(res) + list(res.beyond_tokens),
            list(res.fixed_at) + list(res.beyond_fixed_at))


def schedule_faults(reference, spec: Dict[str, Any], r: Any) -> int:
    """Blocks of one answer whose steps are not the configured schedule
    (host arithmetic; 1 for an answer that cannot be cut into blocks)."""
    b, s_n = int(spec["block_length"]), int(spec["denoising_steps"])
    tokens, steps = _whole(r)
    tail = len(r.payload) % b
    if (len(tokens) != len(steps) or (tail + len(tokens)) % b
            or len(r.result.fixed_at) != len(r.result)
            or len(r.result.beyond_tokens) >= b):
        return 1
    steps = [0] * tail + steps
    faults = 0
    for i in range(0, len(steps), b):
        blk = steps[i:i + b]
        masked = sum(1 for s in blk if s > 0)
        ok = (masked == b or i == 0) and masked > 0 and [
            blk.count(s) for s in range(1, s_n + 1)
        ] == reference.schedule(masked, s_n)
        faults += not ok
    return faults


def _number(name: str, value, limits: Dict[str, Any], **more) -> Dict[str, Any]:
    return {"name": name, "value": value, "limit": limits[name],
            "ok": value is not None and value <= limits[name], **more}


def check(run: Dict[str, Any], reference, seed: int, *,
          control: bool = False) -> List[Dict[str, Any]]:
    cfg = run["config"]
    spec = run["system"]["spec"]
    limits = cfg["correct"]["limits"]
    done = [r for r in run["requests"] if r.ok and r.result is not None]
    faults = [schedule_faults(reference, spec, r) for r in done]
    numbers: List[Dict[str, Any]] = [
        _number("tokens_missing", sum(
            abs(r.size["output_tokens"] - len(r.result)) for r in done),
            limits, over=len(done)),
        _number("stream_mismatch", sum(
            1 for r in done
            if r.streamed is not None and r.streamed != list(r.result)),
            limits, over=len(done)),
        _number("schedule_faults", sum(faults), limits, over=len(done)),
    ]
    sound = [r for r, f in zip(done, faults) if not f]
    if not sound:
        numbers.append(_number("served_gap_mean", None, limits, over=0))
        return numbers
    b, s_n = int(spec["block_length"]), int(spec["denoising_steps"])
    items = run["traffic"]["items"]
    out_max = int(items["output_tokens"]["max"])
    pad_final = -(-(int(items["prompt_tokens"]["max"]) + out_max) // b) * b
    pad_copies = (-(-out_max // b) + 1) * s_n
    sample = _sample(sound, run["tap"], int(cfg["correct"]["sample"]), seed)
    params = reference.make_params(spec, seed)
    total = widest = choice = 0.0
    ctl_total = ctl_widest = ctl_choice = 0.0
    tokens = exact = 0
    confs: List[float] = []  # the reference's mean log-confidence a request
    for r in sample:
        generated, steps = _whole(r)
        rows = reference.request_rows(spec, r.payload, generated, steps)
        n = len(rows["tokens"]) - rows["final_rows"]
        hidden, final = reference.copy_rows(
            params, spec, rows, pad_final=pad_final, pad_copies=pad_copies)
        st = reference.row_stats(params, hidden, final, n)
        if control:
            low_hidden, _ = reference.copy_rows(
                params, spec, rows, pad_final=pad_final,
                pad_copies=pad_copies, precision="int8")
            low = reference.row_stats(params, low_hidden, final, n,
                                      precision="int8")
            # what the int8 forward would have put first, scored by the
            # float32 reference
            scored = final.copy()
            scored[:n] = low["argmax"]
            ctl = reference.row_stats(params, hidden, scored, n)
        for c in rows["copies"]:
            at = c["row0"] - rows["final_rows"]
            left = [j for j in c["masked"] if j not in c["fixed_now"]]
            for j in c["fixed_now"]:
                gap = float(st["best"][at + j] - st["scored"][at + j])
                total += gap
                widest = max(widest, gap)
                tokens += 1
                exact += gap == 0.0
                if control:
                    gap = float(ctl["best"][at + j] - ctl["scored"][at + j])
                    ctl_total += gap
                    ctl_widest = max(ctl_widest, gap)
            if c["fixed_now"] and left:
                conf = st["log_conf"]
                choice = max(choice, float(
                    max(conf[at + j] for j in left)
                    - min(conf[at + j] for j in c["fixed_now"])))
                if control:
                    # the positions the int8 forward's confidence would
                    # have fixed, by the float32 reference's confidence
                    order = sorted(c["masked"],
                                   key=lambda j: (-low["log_conf"][at + j], j))
                    now = order[:len(c["fixed_now"])]
                    rest = order[len(c["fixed_now"]):]
                    ctl_choice = max(ctl_choice, float(
                        max(conf[at + j] for j in rest)
                        - min(conf[at + j] for j in now)))
        confs.append(float(st["log_conf"].mean()))
        del hidden
    del params
    numbers += [
        _number("served_gap_mean", total / tokens, limits, over=len(sample),
                tokens=tokens, tokens_exact=exact,
                reference_log_conf_median=sorted(confs)[len(confs) // 2]),
        _number("served_gap_max", widest, limits, over=len(sample)),
        _number("choice_gap_max", choice, limits, over=len(sample)),
    ]
    if control:
        # readings, not comparisons: what the int8 reference would have
        # served on the same blocks; the mean has to be over its limit
        numbers += [
            {"name": "control_int8_gap_mean", "value": ctl_total / tokens,
             "would_fail": ctl_total / tokens > limits["served_gap_mean"]},
            {"name": "control_int8_gap_max", "value": ctl_widest,
             "would_fail": ctl_widest > limits["served_gap_max"]},
            {"name": "control_int8_choice_gap_max", "value": ctl_choice,
             "would_fail": ctl_choice > limits["choice_gap_max"]},
        ]
    return numbers
