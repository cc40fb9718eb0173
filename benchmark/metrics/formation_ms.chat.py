"""Router: admission to batch dispatch (terminal `stages["formation"]`), mean."""


def read(run):
    from benchmark.harness.readers import stage_mean_ms
    return stage_mean_ms(run, "formation")
