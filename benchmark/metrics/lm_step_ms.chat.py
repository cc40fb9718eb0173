"""LM server: host wall of one decode step, from the window's delta of
`lm_server_step_seconds` (a step dispatch ends in its packed readback, so
the host clock sees the device) over steps x chunk."""


def read(run):
    from benchmark.harness.readers import window_delta
    s, n = window_delta(run, "step_sum"), window_delta(run, "steps_total")
    return 1000.0 * s / (n * run["system"]["chunk"]) if n else None
