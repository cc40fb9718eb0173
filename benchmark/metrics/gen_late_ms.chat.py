"""How late the load generator sent a request after it was due, 95th
percentile: a starved generator must not be read as a fast server."""


def read(run):
    s = run["summary"]
    return s and s["gen_late_p95_ms"]
