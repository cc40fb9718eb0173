"""Mean gap between streamed output tokens over ALL of the window's tokens:
sum of (last delivery - first delivery) over sum of (tokens after the first
delivery), over the requests that got more than one delivery. Recorded, not
judged: between runs of the same code it spreads by 3-8%, over half of the
widest bound the contract allows (PERF.md section 2)."""


def read(run):
    s = run["summary"]
    return s and s["tpot_mean_ms"]
