"""Due time to first streamed chunk at the client, median over every request
due in the window; a request that failed stays in at the drain limit.
Recorded, not judged: the median of 200 such times spreads by 5-9% between
runs of the same code (PERF.md section 2)."""


def read(run):
    s = run["summary"]
    return s and s["ttft_p50_ms"]
