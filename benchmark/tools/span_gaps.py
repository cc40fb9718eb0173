#!/usr/bin/env python3
"""One traced run of one cell, read through the program's own spans.

    python3 benchmark/tools/span_gaps.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as `run.py --trace 1` does and, while the profiler's file is
still there, reads it once more for what `harness/trace.py` does not take
from it: the `dml.*` annotations the program's loop spans enter
(`dml_tpu.tracing.Tracer.loop_span`). Prints, one JSON line each,

- `span_gaps`: the device's idle gaps named by the innermost `dml.*`
  annotation open at each gap's middle on any host thread (`unattributed`
  where there is none), with the share of idle time that got a name;
- `span_offset`: how far each `dml.lm_step` annotation's start in the
  trace lies from its span's `t0` in the recorder (the profiler's clock
  against `TRACER.wall_of`): median and extremes, in microseconds;
- `step_account`: per decode dispatch, `lm_step` against the sum of its
  five phases, the self time left over, mean `lm_step` / chunk against
  `lm_step_ms.*` from the counters, and mean `lm_step` inside the
  profiler's window against before it (what live annotations cost);
- `ttft_account` (cells with requests): the window's `ttft_mean_ms`
  against formation + dispatch + fetch + the ticket's wait for the
  serving thread + LM queue wait + first token + the stream's way to
  the client, and what is left over;

then the run's result line, as `run.py` prints it. Not a cell and not a
metric: the tables go into PERF.md section 5.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

PREFIX = "dml."
PHASES = ("lm_dispatch", "lm_pack", "lm_readback", "lm_deliver", "lm_place")


def read_trace(path):
    """(idle gaps of the first chip that ran anything, its busy seconds,
    the [start_ns, end_ns) rows and the names, less the prefix, of every
    `dml.*` host event, the profile's start on the wall clock in ns or
    None)."""
    import jax
    import numpy as np

    from benchmark.harness import trace as tr

    data = jax.profiler.ProfileData.from_file(path)
    gaps, busy, host, start_ns = [], 0.0, [], None
    for plane in data.planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                start_ns = int(value)
        if tr._DEVICE_PLANE.match(plane.name):
            if gaps:
                continue
            for line in plane.lines:
                if line.name != tr.OPS_LINE:
                    continue
                iv, _ = tr._events(line)
                if len(iv):
                    total, merged = tr._union(iv)
                    busy = total * 1e-9
                    gaps = list(zip(merged[:-1, 1], merged[1:, 0]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                iv, names = tr._events(line)
                host += [(iv[i, 0], iv[i, 1], n[len(PREFIX):])
                         for i, n in enumerate(names) if n.startswith(PREFIX)]
    host_iv = np.asarray([(a, b) for a, b, _ in host],
                         np.float64).reshape(-1, 2)
    return gaps, busy, host_iv, [n for _, _, n in host], start_ns


def span_gaps(facts, top=12):
    """Idle seconds by the innermost `dml.*` annotation open at each
    gap's middle."""
    import numpy as np

    gaps, busy, iv, names, _ = facts
    named, idle = {}, 0.0
    for a, b in gaps:
        mid, length = 0.5 * (a + b), (b - a) * 1e-9
        idle += length
        hit = np.flatnonzero((iv[:, 0] <= mid) & (iv[:, 1] > mid)) \
            if len(iv) else []
        best = "unattributed"
        if len(hit):
            best = names[hit[np.argmin(iv[hit, 1] - iv[hit, 0])]]
        named[best] = named.get(best, 0.0) + length
    rows = sorted(named.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": busy, "idle_in_gaps_s": idle, "gaps": len(gaps),
        "annotations": len(names),
        "attributed_share": (1.0 - named.get("unattributed", 0.0) / idle
                             if idle else None),
        "by_span": [[n, s, s / idle] for n, s in rows[:top]],
    }


def span_offset(facts):
    """Start of each `dml.lm_step` annotation in the trace less its
    span's `t0` in the recorder, in microseconds."""
    import bisect
    import statistics

    from dml_tpu.tracing import TRACER

    _, _, iv, names, start_ns = facts
    if start_ns is None or not hasattr(TRACER, "loop_spans"):
        return {"matched": 0}
    ann = sorted((start_ns + iv[i, 0]) * 1e-9
                 for i, n in enumerate(names) if n == "lm_step")
    t0s = sorted(d["t0"] for d in TRACER.loop_spans("lm_step"))
    offs = []
    for t in ann:  # the span that started nearest to the annotation
        i = bisect.bisect_left(t0s, t)
        near = min(t0s[max(0, i - 1):i + 1], key=lambda s: abs(t - s),
                   default=None)
        if near is not None and abs(t - near) < 0.05:
            offs.append((t - near) * 1e6)
    if not offs:
        return {"matched": 0, "annotations": len(ann), "spans": len(t0s)}
    return {"matched": len(offs), "annotations": len(ann),
            "median_us": statistics.median(offs), "min_us": min(offs),
            "max_us": max(offs)}


def step_account(run):
    """`lm_step` against its phases, over the dispatches of the window."""
    from benchmark.harness.program_spans import program_spans
    from benchmark.harness.readers import window_delta

    steps = program_spans(run, "lm_step")
    if not steps:
        return None
    kids = {}
    for name in PHASES:
        for d in program_spans(run, name, under="lm_step"):
            kids.setdefault(d["par"], {})[name] = d["t1"] - d["t0"]
    whole = [s for s in steps if len(kids.get(s["sid"], ())) == len(PHASES)]
    n = len(whole)
    step_s = sum(s["t1"] - s["t0"] for s in whole)
    out = {"dispatches": len(steps), "with_five_phases": n,
           "lm_step_ms": 1000.0 * step_s / n}
    covered = 0.0
    for name in PHASES:
        total = sum(kids[s["sid"]][name] for s in whole)
        out[name + "_ms"] = 1000.0 * total / n
        covered += total
    out["self_ms"] = 1000.0 * (step_s - covered) / n
    out["phases_over_step"] = covered / step_s
    chunk = run["system"]["chunk"]
    s, k = window_delta(run, "step_sum"), window_delta(run, "steps_total")
    all_ms = 1000.0 * sum(d["t1"] - d["t0"] for d in steps) / len(steps)
    out["span_step_ms_per_token_step"] = all_ms / chunk
    out["counter_lm_step_ms"] = 1000.0 * s / (k * chunk) if k else None
    # what live annotations cost: the dispatches inside the profiler's
    # window against those before it (the window's last seconds)
    a, b = run.get("trace_window") or (None, None)
    if a is not None:
        from dml_tpu.tracing import TRACER

        a, b = TRACER.wall_of(a), TRACER.wall_of(b)
        for key, rows in (
                ("traced", [d for d in steps if a <= d["t0"] and d["t1"] <= b]),
                ("untraced", [d for d in steps if d["t1"] < a])):
            if rows:
                out[f"lm_step_ms_{key}"] = 1000.0 * sum(
                    d["t1"] - d["t0"] for d in rows) / len(rows)
                out[f"dispatches_{key}"] = len(rows)
    out["tokens"] = sum(d["lb"]["tokens"] for d in steps)
    out["firsts"] = sum(d["lb"]["firsts"] for d in steps)
    # placement outside any dispatch: a submit places at once into
    # free slots, so its prefill groups are enqueued under `lm_submit`
    submits = program_spans(run, "lm_submit")
    if submits:
        out["submits"] = len(submits)
        out["lm_submit_ms"] = 1000.0 * sum(
            d["t1"] - d["t0"] for d in submits) / len(submits)
        out["placed_at_submit"] = sum(
            d["lb"]["requests"]
            for d in program_spans(run, "lm_place", under="lm_submit"))
        out["placed_in_steps"] = sum(
            d["lb"]["requests"]
            for d in program_spans(run, "lm_place", under="lm_step"))
    return out


def _ticket_wait_ms(run):
    """Mean wait of a backend call's ticket for the serving thread (it
    is taken between two decode dispatches), over the window's
    `lm_submit` spans."""
    from benchmark.harness.program_spans import program_spans

    spans = program_spans(run, "lm_submit") or ()
    tickets = sum(d["lb"]["tickets"] for d in spans)
    if not tickets:
        return None
    return 1000.0 * sum(d["lb"]["ticket_wait_s"] for d in spans) / tickets


def _stream_ms(run):
    """Mean time from a request's first token on the serving thread (the
    token tap inside `on_token`) to its first chunk at the client."""
    from benchmark.harness.readers import _prompt_tokens_by_path

    t0 = run["driver"].get("t0")
    first = {r.name: r.first for r in run["requests"]
             if r.ok and r.first is not None}
    gaps = []
    for rec, _ in _prompt_tokens_by_path(run):
        base = os.path.basename(rec["path"])
        hit = next((n for n in first if n in base), None)
        if hit is not None and t0 is not None:
            gaps.append(t0 + first[hit] - rec["first"])
    return 1000.0 * sum(gaps) / len(gaps) if gaps else None


def ttft_account(run):
    """`ttft_mean_ms` of the window's requests against the program's own
    terms, each a mean over what finished in the window, in the order a
    request meets them."""
    from benchmark.harness.program_spans import event_gap_mean_ms
    from benchmark.harness.readers import (batch_mean_ms, mean_of_hist,
                                           stage_mean_ms)

    if not run.get("summary"):
        return None
    terms = {
        "formation_ms": stage_mean_ms(run, "formation"),
        "of_it_worker_wait_ms": stage_mean_ms(run, "worker_wait"),
        "dispatch_ms": stage_mean_ms(run, "dispatch"),
        "fetch_ms": batch_mean_ms(run, "fetch"),
        "ticket_wait_ms": _ticket_wait_ms(run),
        "lm_queue_wait_ms": 1000.0 * (mean_of_hist(run, "queue_wait") or 0.0),
        "first_token_ms": event_gap_mean_ms(
            run, "lm_request", "placed", "first_token"),
        "stream_ms": _stream_ms(run),
    }
    ttft = run["summary"].get("ttft_mean_ms")
    named = sum(v or 0.0 for k, v in terms.items()
                if not k.startswith("of_it_"))
    return {"ttft_mean_ms": ttft, **terms, "named_ms": named,
            "left_over_ms": None if ttft is None else ttft - named}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from benchmark.harness import cell
    from benchmark.harness import trace as tr

    kept = {}
    reduce_trace, read_metrics = tr.reduce_trace, cell.read_metrics

    def reduce_and_read_spans(path, **kw):
        facts = read_trace(path)
        kept["span_gaps"] = span_gaps(facts)
        kept["span_offset"] = span_offset(facts)
        return reduce_trace(path, **kw)

    def read_and_keep_run(c, run, group):
        kept["run"] = run
        return read_metrics(c, run, group)

    # the harness deletes the profiler's file when the run ends: read it
    # where the harness reads it, and take the run's facts where the
    # metric readers are handed them
    tr.reduce_trace, cell.read_metrics = reduce_and_read_spans, read_and_keep_run
    try:
        result = cell.run_cell(args.workload, args.seed, args.seconds, True,
                               t_start=T_START)
    except (cell.NoAccelerator, cell.CompiledInWindow) as e:
        print(f"span_gaps: {e}", file=sys.stderr)
        return 1 if isinstance(e, cell.NoAccelerator) else 2
    finally:
        tr.reduce_trace, cell.read_metrics = reduce_trace, read_metrics
    for what in ("span_gaps", "span_offset"):
        cell.say(what, workload=args.workload, **(kept.get(what) or {}))
    run = kept.get("run")
    if run is not None:
        for what, fn in (("step_account", step_account),
                         ("ttft_account", ttft_account)):
            table = fn(run)
            if table is not None:
                cell.say(what, workload=args.workload, **table)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)  # parked service threads must not hold the exit
