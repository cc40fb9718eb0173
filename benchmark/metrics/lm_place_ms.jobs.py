"""LM server: a decode dispatch's placement of waiting requests (span `lm_place`
under an `lm_step`): its prefill groups' enqueue chains, mean a dispatch over
the window."""


def read(run):
    from benchmark.harness.program_spans import mean_ms
    return mean_ms(run, "lm_place", under="lm_step")
