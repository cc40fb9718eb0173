#!/usr/bin/env python3
"""Record the small trace that `benchmark/tests/test_trace.py` reduces:
a few executions of two named programs on the chip with known idle gaps
between them. Run on the chip once; the `.xplane.pb` it writes under
`chiprun_out/trace_fixture/` is kept in `benchmark/tests/data/`.

    python3 benchmark/tools/record_trace.py
"""

import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1

    @jax.jit
    def big_step(x):
        return jnp.tanh(x @ x)

    @jax.jit
    def small_step(x):
        return x + 1.0

    x = jnp.ones((2048, 2048), jnp.bfloat16)
    y = jnp.ones((128,), jnp.float32)
    big_step(x).block_until_ready()
    small_step(y).block_until_ready()
    out = os.path.join(ROOT, "chiprun_out", "trace_fixture")
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # keep the fixture small
    jax.profiler.start_trace(out, profiler_options=opts)
    t0 = time.monotonic()
    for _ in range(4):
        big_step(x).block_until_ready()
        time.sleep(0.01)
        small_step(y).block_until_ready()
        time.sleep(0.005)
    window = time.monotonic() - t0
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out, "fixture.xplane.pb"))
    print({"window_s": window, "bytes": os.path.getsize(path)})
    from benchmark.harness import trace as tr

    r = tr.reduce_trace(path, window_s=window)
    print({k: (v if k != "modules" else
               {m: (x["count"], x["seconds"]) for m, x in v.items()})
           for k, v in r.items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
