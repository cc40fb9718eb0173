"""LM server: the blocking readback of a decode dispatch (span `lm_readback`
under an `lm_step`): the host's wait for the device, mean a dispatch over the
window."""


def read(run):
    from benchmark.harness.program_spans import mean_ms
    return mean_ms(run, "lm_readback", under="lm_step")
