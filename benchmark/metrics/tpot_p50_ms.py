"""Median of the per-request time per output token that `tpot_p95_ms` takes
the tail of; `tpot_mean_ms.chat` is the same quantity over all tokens."""


def read(run):
    s = run["summary"]
    return s and s["tpot_p50_ms"]
