"""Expert layer: a dispatch's routing numbers into the `moe_*` counters
and onto its `lm_step` span (span `lm_route`, on the serving thread after
the readback has drained the device), mean a dispatch over the window."""


def read(run):
    from benchmark.harness.program_spans import mean_ms
    return mean_ms(run, "lm_route", under="lm_step")
