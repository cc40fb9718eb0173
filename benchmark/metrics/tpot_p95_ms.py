"""(last delivery - first delivery) / (tokens after the first delivery) per
request, 95th percentile over the requests that got more than one delivery:
recorded, not judged (it spreads by 6-18% between runs of the same code)."""


def read(run):
    s = run["summary"]
    return s and s["tpot_p95_ms"]
