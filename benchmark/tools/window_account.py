#!/usr/bin/env python3
"""One traced run of one cell, its profiler trace read in the program's own
names.

    python3 benchmark/tools/window_account.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as `run.py --trace 1` does and, while the profiler's file is
still there, hands it to the program's own reader
(`dml_tpu.tracing.read_profile`): device-busy seconds by program and model
part (the `jax.named_scope` names of `dml_tpu.tracing.PARTS`, by self time),
device-idle seconds by the serving thread's span open in each gap. Prints,
one JSON line each,

- `window_account`: that account (`busy`, `idle`, `unscoped_ops`, the
  totals), the share of busy time that carries a part's name (`named_busy`)
  and of idle time that carries a span's (`named_idle`), and the harness's
  own `busy_s` / `window_s` of the same file beside them;
- `exposed_account`: what the program knows of the device's idle time
  with no profiler: `lm_exposed` + `lm_idle` spans clipped to the profiler's
  window, as a share of it, against the trace's idle share there; and the
  same two over the whole measured window;
- `step_account`: `tools/span_gaps.py`'s table (a dispatch against its
  phases; `lm_step` inside the profiler's window against before it);

then the run's result line, as `run.py` prints it. A program without
`read_profile` (a parent commit) prints the accounts it can. Not a cell and
not a metric: the tables go into PERF.md section 5.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def account(path):
    """`read_profile`'s account of the file and the shares that carry a
    name; None where the program has no such reader."""
    import dml_tpu.tracing as trc

    if not hasattr(trc, "read_profile"):
        return None
    acc = trc.read_profile(path)
    parts = sum(s for row in acc["busy"].values() for s in row.values())
    unscoped = sum(row.get(trc.UNSCOPED, 0.0) for row in acc["busy"].values())
    idle = sum(acc["idle"].values())
    acc["named_busy"] = 1.0 - unscoped / parts if parts else None
    acc["named_idle"] = (1.0 - acc["idle"].get(trc.UNATTRIBUTED, 0.0) / idle
                         if idle else None)
    return acc


def exposed_account(run):
    """`lm_exposed` + `lm_idle` against the trace's idle share, inside the
    profiler's window and over the whole measured window."""
    from dml_tpu.tracing import TRACER

    from benchmark.harness.program_spans import share_pct

    if not hasattr(TRACER, "loop_spans"):
        return None
    out = {}
    t0, t1 = run.get("trace_window") or (None, None)
    windows = {"window": run["counters"]}
    if t0 is not None:  # the readers' window is a pair of counter readings
        windows["traced"] = {"start": {"t": t0}, "end": {"t": t1}}
    for key, counters in windows.items():
        for name in ("lm_exposed", "lm_idle"):
            out[f"{name}_share_{key}"] = (share_pct(
                {"counters": counters}, name) or 0.0) / 100.0
        out[f"exposed_plus_idle_share_{key}"] = (
            out[f"lm_exposed_share_{key}"] + out[f"lm_idle_share_{key}"])
    by_after = {}
    for d in TRACER.loop_spans("lm_exposed"):
        row = by_after.setdefault(d.get("lb", {}).get("after", "?"), [0, 0.0])
        row[0] += 1
        row[1] += d["t1"] - d["t0"]
    out["lm_exposed_by_after"] = by_after
    if run.get("trace"):
        tr = run["trace"]
        out["trace_idle_share"] = 1.0 - tr["busy_s"] / tr["window_s"]
        out["points_apart"] = 100.0 * (
            out["exposed_plus_idle_share_traced"] - out["trace_idle_share"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from benchmark.harness import cell
    from benchmark.harness import trace as tr
    from benchmark.tools import span_gaps as sg

    kept = {}
    reduce_trace, read_metrics = tr.reduce_trace, cell.read_metrics

    def reduce_and_account(path, **kw):
        kept["account"] = account(path)
        reduced = reduce_trace(path, **kw)
        if kept["account"] is not None:
            kept["account"]["harness"] = {
                k: reduced[k] for k in ("window_s", "span_s", "busy_s")}
        return reduced

    def read_and_keep_run(c, run, group):
        kept["run"] = run
        return read_metrics(c, run, group)

    # the harness deletes the profiler's file when the run ends: read it
    # where the harness reads it (`tools/span_gaps.py`'s way)
    tr.reduce_trace, cell.read_metrics = reduce_and_account, read_and_keep_run
    try:
        result = cell.run_cell(args.workload, args.seed, args.seconds, True,
                               t_start=T_START)
    except (cell.NoAccelerator, cell.CompiledInWindow) as e:
        print(f"window_account: {e}", file=sys.stderr)
        return 1 if isinstance(e, cell.NoAccelerator) else 2
    finally:
        tr.reduce_trace, cell.read_metrics = reduce_trace, read_metrics
    if kept.get("account") is not None:
        cell.say("window_account", workload=args.workload, **kept["account"])
    run = kept.get("run")
    if run is not None:
        for what, fn in (("exposed_account", exposed_account),
                         ("step_account", sg.step_account)):
            table = fn(run)
            if table is not None:
                cell.say(what, workload=args.workload, **table)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)  # parked service threads must not hold the exit
