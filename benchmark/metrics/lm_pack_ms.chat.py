"""LM server: issuing a decode dispatch's packed readback (span `lm_pack`): the
eager concatenate's trace, cache load or compile, and enqueue, which
`window_compile_ms.*` counts only in part, mean a dispatch over the window."""


def read(run):
    from benchmark.harness.program_spans import mean_ms
    return mean_ms(run, "lm_pack", under="lm_step")
