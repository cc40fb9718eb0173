#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Exits 1 (and prints no result) where JAX finds no TPU or fewer chips than
the cell asks for, 2 where a program compiled inside the measured window;
else prints the result as the last line of its output and exits 0.
`--control 1` also reads what the int8 reference would put first, and
`--variant int8w` serves the program's own int8 weight path: both are for
setting and proving the limits of `correct` (PERF.md section 2), and the
driver passes neither.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: a hung run (a wedged chip, a lost datagram loop) ends non-zero inside
#: the 1200 s a compiling first run may take, whatever thread hangs
RUN_LIMIT_S = 1150


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--variant", default=None)
    args = ap.parse_args(argv)

    from benchmark.harness import cell

    faulthandler.dump_traceback_later(RUN_LIMIT_S, exit=True)
    try:
        result = cell.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            t_start=T_START, variant=args.variant,
            control=bool(args.control))
    except cell.NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    except cell.CompiledInWindow as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    # threads the cluster's services leave parked must not hold the exit
    os._exit(code)
