"""Process start to window start: imports, weights, the cluster, store puts,
warm-up and, in a run that compiles, compilation. Host clock."""


def read(run):
    return run["setup_s"]
