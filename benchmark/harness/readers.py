"""Arithmetic the per-layer metric readers share. A reader
(`benchmark/metrics/<metric>.py`) is a few lines that pick one of these
and return a number, or None where its cell has nothing to read."""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Tuple

from . import trace as tr
from .peaks import peaks_of


def window_delta(run: Dict[str, Any], key: str) -> Optional[float]:
    c = run["counters"]
    if "end" not in c or key not in c["end"]:
        return None
    return c["end"][key] - c["start"][key]


def mean_of_hist(run: Dict[str, Any], name: str) -> Optional[float]:
    """Mean observation of a program histogram over the window."""
    n = window_delta(run, name + "_count")
    s = window_delta(run, name + "_sum")
    return s / n if n else None


def stage_mean_ms(run: Dict[str, Any], stage: str) -> Optional[float]:
    """Mean of a router terminal stage over the requests that finished."""
    vals = [r.stages[stage] for r in run["requests"]
            if r.ok and stage in r.stages]
    return 1000.0 * sum(vals) / len(vals) if vals else None


def batch_mean_ms(run: Dict[str, Any], key: str) -> Optional[float]:
    """Mean of an ACK-carried stage wall over the window's batches."""
    vals = [b[key] for b in run["batches"] if key in b]
    return 1000.0 * sum(vals) / len(vals) if vals else None


def _prompt_tokens_by_path(run: Dict[str, Any]) -> List[Tuple[Dict, int]]:
    sizes = {r.name: r.size["prompt_tokens"] for r in run["requests"]}
    out = []
    for rec in run["tap"]:
        base = os.path.basename(rec["path"])
        hit = next((n for n in sizes if n in base), None)
        if hit is not None and rec["first"] is not None:
            out.append((rec, sizes[hit]))
    return out


def live_tokens_mean(run: Dict[str, Any], a: float, b: float) -> float:
    """Mean number of tokens live in the slot grid over [a, b] (host
    monotonic seconds), from the token tap: a served prompt is live from
    its first delivered token to its last, and grows from its prompt's
    length by the tokens delivered. Leaves out the time a slot is held
    before its first token reaches the host: a floor."""
    total = 0.0
    for rec, prompt in _prompt_tokens_by_path(run):
        s, e = max(a, rec["first"]), min(b, rec["last"])
        if e <= s:
            continue
        span = rec["last"] - rec["first"]
        at_mid = rec["n"] * ((s + e) / 2 - rec["first"]) / span
        total += (e - s) * (prompt + at_mid)
    return total / (b - a)


def _whole(durations: List[float]) -> List[float]:
    """Executions the trace holds whole: one cut by the trace's edge is
    far shorter than its kind's median."""
    if not durations:
        return []
    med = sorted(durations)[len(durations) // 2]
    return [d for d in durations if d >= 0.5 * med]


def decode_roofline(run: Dict[str, Any]) -> Optional[float]:
    """Least time of the decode steps in the traced window (the weights
    once a step and the live K/V rows, at HBM bandwidth: memory bound at
    these batch sizes) over the device time of the decode-chunk program."""
    t = run.get("trace")
    if not t:
        return None
    pat = run["config"]["trace_modules"]["decode"]
    durs = _whole(tr.module_durations(t, pat["module"]))
    if not durs:
        return None
    a, b = run["trace_window"]
    live = live_tokens_mean(run, a, b)
    spec = run["system"]["spec"]
    step_bytes = run["costs"].decode_step_bytes(spec, live)
    least = (len(durs) * run["system"]["chunk"] * step_bytes
             / peaks_of(run["device_kind"])["hbm_bytes_per_s"])
    return 100.0 * least / sum(durs)


def prefill_roofline(run: Dict[str, Any]) -> Optional[float]:
    """Least time to prefill the prompts that were surely prefilled inside
    the traced window, over the device time of the prefill programs in it.

    Least time is the prompts' FLOPs at the bf16 peak (a floor: a short
    prompt alone is bound by reading the weights). A prompt counts when
    its first token reached the host in [a + 2 steps, b]: the token's
    value rides the packed readback of the decode dispatch after its
    placement, so its prefill ran at most a dispatch or two before. The
    prompts prefilled just before `b` are left out, so the share reads a
    little low, never high."""
    t = run.get("trace")
    if not t:
        return None
    mods = run["config"]["trace_modules"]
    _, secs = tr.module_seconds(t, mods["prefill"]["module"],
                                mods["prefill"].get("min_us", 0.0))
    n_dec, dec_s = tr.module_seconds(t, mods["decode"]["module"])
    if not secs or not n_dec:
        return None
    a, b = run["trace_window"]
    a += 2.0 * dec_s / n_dec
    spec = run["system"]["spec"]
    flops = sum(run["costs"].prefill_flops(spec, prompt)
                for rec, prompt in _prompt_tokens_by_path(run)
                if a <= rec["first"] <= b)
    if not flops:
        return None
    return 100.0 * flops / peaks_of(run["device_kind"])["bf16_flops"] / secs
