"""Scheduler: dispatch to completion not explained by the worker's own walls
(queue + wire + ACK residual; terminal `stages["dispatch"]`), mean."""


def read(run):
    from benchmark.harness.readers import stage_mean_ms
    return stage_mean_ms(run, "dispatch")
