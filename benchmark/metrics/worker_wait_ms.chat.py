"""Router: of a request's formation, the wait in a batch whose linger had
run out and that only the want of a free worker still held (terminal
`stages["worker_wait"]`), mean over the requests that finished."""


def read(run):
    from benchmark.harness.readers import stage_mean_ms
    return stage_mean_ms(run, "worker_wait")
