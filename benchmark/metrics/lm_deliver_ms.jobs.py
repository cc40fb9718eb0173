"""LM server: a decode dispatch's token delivery (span `lm_deliver`): first
tokens, every request's `on_token` callbacks, retirements, mean a dispatch
over the window."""


def read(run):
    from benchmark.harness.program_spans import mean_ms
    return mean_ms(run, "lm_deliver", under="lm_step")
