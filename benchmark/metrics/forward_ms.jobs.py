"""LM server: host wall of one forward over the slot grid, from the window's
delta of `lm_server_step_seconds` (a dispatch ends in its readback, so the
host clock sees the device) over the delta of `lm_server_forwards_total`
(denoising and commit forwards alike)."""


def read(run):
    from benchmark.harness.readers import window_delta
    s, n = window_delta(run, "step_sum"), window_delta(run, "forwards_total")
    return 1000.0 * s / n if n else None
