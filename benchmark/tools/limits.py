#!/usr/bin/env python3
"""Readings that a limit of `correct` is set from, many seeds in ONE
process (only `correct`'s numbers are read, no timing):

    python3 benchmark/tools/limits.py --workload <cell> --seeds 301-312 \
        --control-seeds 401-403 --seconds 15

For each seed: a short window at the cell's own load, then the widest gap
of the program's served tokens below the reference's best (`sound`), and
what the int8 reference would put first on the same sequences
(`control_ref`). For each control seed: the same with the program's own
int8 weight path serving (`--variant int8w`), whose `correct` has to come
out false. Prints the largest sound reading and the smallest control, of
the mean gap (the number the lower precision has to fail).
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def span(text):
    out = []
    for part in filter(None, text.split(",")):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b or a) + 1))
    return out


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args()
    from benchmark.harness import cell

    rows = []
    for variant, seeds in ((None, span(args.seeds)),
                           ("int8w", span(args.control_seeds))):
        for seed in seeds:
            r = cell.run_cell(args.workload, seed, args.seconds, False,
                              t_start=time.monotonic(), variant=variant,
                              control=True)
            n = {x["name"]: x for x in r["numbers"]}
            row = {"seed": seed, "variant": variant or "program",
                   "correct": r["correct"], "failed": r["failed"],
                   "attempted": r["attempted"],
                   "gap_mean": n["served_gap_mean"]["value"],
                   "gap_max": n["served_gap_max"]["value"],
                   "tokens": n["served_gap_mean"].get("tokens"),
                   "tokens_exact": n["served_gap_mean"].get("tokens_exact"),
                   "control_ref_mean": n["control_int8_gap_mean"]["value"],
                   "control_ref_max": n["control_int8_gap_max"]["value"]}
            rows.append(row)
            print(json.dumps({"limits": row}), flush=True)
    sound = [r["gap_mean"] for r in rows if r["variant"] == "program"]
    ctl_ref = [r["control_ref_mean"] for r in rows]
    ctl_prog = [r["gap_mean"] for r in rows if r["variant"] == "int8w"]
    print(json.dumps({
        "workload": args.workload,
        "sound_largest": max(sound, default=None), "sound_seeds": len(sound),
        "control_reference_int8_smallest": min(ctl_ref, default=None),
        "control_program_int8w_smallest": min(ctl_prog, default=None),
        "control_program_int8w_correct": [r["correct"] for r in rows
                                          if r["variant"] == "int8w"],
        "every_sound_run_correct": all(r["correct"] for r in rows
                                       if r["variant"] == "program"),
    }), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
