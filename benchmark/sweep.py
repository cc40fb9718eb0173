#!/usr/bin/env python3
"""Several windows of one open-loop cell at different rates after ONE
set-up: finds the knee, the highest rate the system sustains. Not a cell;
the driver never runs it. Run once when a cell is defined:

    python3 benchmark/sweep.py --workload <name> --seed <n> --seconds <s> \
        --rates 2,4,6,9,12

A rate is sustained when nothing failed and the queue did not grow: the
requests in flight, sampled every quarter second, average no more over the
window's last quarter than 1.25 times their average over its second
quarter plus one (a single reading at the close and at the midpoint swings
too much at a few requests in flight). The cell's `rate_rps` (in its
traffic file) is then fixed at about four fifths of the knee.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    from benchmark.harness import cell as hc
    from benchmark.harness import manifest as mf

    cell = hc.Cell(mf.load(), args.workload)
    try:
        hc.require_device(int(cell.entry["chips"]), False)
    except hc.NoAccelerator as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 1
    hc.configure_compile_cache()
    meter = hc.CompileMeter()
    faulthandler.dump_traceback_later(3000, exit=True)
    rates = [float(r) for r in args.rates.split(",")]
    served = hc.Served(cell, args.seed)
    rows = []

    async def go() -> None:
        plans = [cell.driver.plan(cell.traffic, args.seconds, args.seed + i,
                                  cell.config, cell.items, rate_rps=rate)
                 for i, rate in enumerate(rates)]
        for i, reqs in enumerate(plans):  # names unique across windows
            for r in reqs:
                r.name = f"s{i}_{r.name}"
        warm = cell.driver.store_items(cell.traffic, [], args.seed,
                                       cell.config, cell.items)  # warm-up's
        await served.start([r.size for p in plans for r in p])
        try:
            await served.put(warm)
            await served.warm_path(warm)
            hc.say("setup", setup_s=time.monotonic() - T_START,
                   **served.split, **meter.snapshot())
            for rate, reqs in zip(rates, plans):
                await served.put(reqs)
                run = await hc.window(served, reqs, args.seconds, meter)
                s, d = run["summary"], run["driver"]
                row = {
                    "rate_rps": rate, "attempted": s["attempted"],
                    "failed": s["failed"],
                    "in_flight_second_quarter": d["in_flight_second_quarter"],
                    "in_flight_last_quarter": d["in_flight_last_quarter"],
                    "sustained": (
                        s["failed"] == 0 and d["in_flight_last_quarter"]
                        <= 1.25 * d["in_flight_second_quarter"] + 1.0),
                    "ttft_p50_ms": s["ttft_p50_ms"],
                    "ttft_p95_ms": s["ttft_p95_ms"],
                    "tpot_p50_ms": s["tpot_p50_ms"],
                    "tpot_p95_ms": s["tpot_p95_ms"],
                    "latency_p95_ms": s["latency_p95_ms"],
                    "gen_late_p95_ms": s["gen_late_p95_ms"],
                    "drain_s": d["drain_s"],
                    "tokens_per_s": (run["counters"]["end"]["tap_tokens"]
                                     - run["counters"]["start"]["tap_tokens"])
                    / d["window_s"],
                    "compiled_in_window": run["compiled_in_window"],
                }
                rows.append(row)
                hc.say("rate", **row)
        finally:
            await served.stop()

    try:
        asyncio.run(go())
    finally:
        served.close()
    knee = max((r["rate_rps"] for r in rows if r["sustained"]), default=None)
    print(json.dumps({"workload": args.workload, "seconds": args.seconds,
                      "rates": rates, "knee_rps": knee, "rows": rows}),
          flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
