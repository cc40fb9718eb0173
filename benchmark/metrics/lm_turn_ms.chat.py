"""LM server: what the serving thread does between two dispatches (span
`lm_turn`: finished requests to their tickets, the tickets' events, the
locks, new tickets taken), mean a turn over the window."""


def read(run):
    from benchmark.harness.program_spans import mean_ms
    return mean_ms(run, "lm_turn")
