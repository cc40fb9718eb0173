"""LM server: submit to slot placement (`lm_server_queue_wait_seconds`), mean
over the window."""


def read(run):
    from benchmark.harness.readers import mean_of_hist
    v = mean_of_hist(run, "queue_wait")
    return None if v is None else 1000.0 * v
