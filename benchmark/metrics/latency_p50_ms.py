"""Due time to terminal at the client (the whole answer), median over every
request due in the window; a request that failed stays in at the drain limit."""


def read(run):
    s = run["summary"]
    return s and s["latency_p50_ms"]
