"""LM server: slot placement to the request's first token VALUE on the host
(events `placed` -> `first_token` of the `lm_request` spans that ended in
the window), mean: the prefill and up to one decode dispatch."""


def read(run):
    from benchmark.harness.program_spans import event_gap_mean_ms
    return event_gap_mean_ms(run, "lm_request", "placed", "first_token")
