#!/usr/bin/env python3
"""Record the small trace that `tests/test_profile_account.py` reads through
`dml_tpu.tracing.read_profile`: a few executions on the chip of one program
with two of the model's parts (`jax.named_scope`s of `tracing.PARTS`) inside
a `while` and one operation under no part, a known idle gap under a `dml.*`
annotation (a loop span of the program's recorder) and a known one under
none. Run on the chip once; the `.xplane.pb` it writes under
`chiprun_out/account_fixture/` is kept in `benchmark/tests/data/`.

    python3 benchmark/tools/record_account_trace.py
"""

import glob
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

#: seconds slept under the `dml.lm_turn` annotation, and under none
SPAN_GAP_S, BARE_GAP_S = 0.010, 0.005
ROUNDS = 4


def main() -> int:
    import jax
    import jax.numpy as jnp

    from dml_tpu.inference.generate import part
    from dml_tpu.tracing import TRACER, read_profile

    if jax.devices()[0].platform != "tpu":
        print("record_account_trace: needs a TPU", file=sys.stderr)
        return 1

    @jax.jit
    def parts_in_a_while(x, n):
        def body(_, x):
            with part("attn_proj"):
                y = jnp.tanh(x @ x)
            with part("mlp"):
                return jax.nn.silu(y @ x) * 0.01

        # a trip count the compiler cannot see: the loop stays a `while`
        return jax.lax.fori_loop(0, n, body, x) + 1.0  # under no part

    x = jnp.full((2048, 2048), 0.01, jnp.bfloat16)
    n = jnp.int32(6)
    parts_in_a_while(x, n).block_until_ready()
    out = os.path.join(ROOT, "chiprun_out", "account_fixture")
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # keep the fixture small
    jax.profiler.start_trace(out, profiler_options=opts)
    for _ in range(ROUNDS):
        parts_in_a_while(x, n).block_until_ready()
        with TRACER.loop_span("lm_turn"):
            time.sleep(SPAN_GAP_S)
        parts_in_a_while(x, n).block_until_ready()
        time.sleep(BARE_GAP_S)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    shutil.copy(path, os.path.join(out, "fixture.xplane.pb"))
    print(json.dumps({"bytes": os.path.getsize(path),
                      "account": read_profile(path)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
