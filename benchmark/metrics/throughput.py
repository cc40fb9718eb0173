"""Output items (LM cells: tokens) delivered to the host inside the window,
per second of window, counted by the benchmark's own tap on the backend's
`on_token` contract and timed on the host's clock."""


def read(run):
    from benchmark.harness.readers import window_delta
    n = window_delta(run, "tap_tokens")
    return n / run["driver"]["window_s"] if n else None
