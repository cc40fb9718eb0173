"""Scheduler: the leader's own wall from the send of WORKER_TASK_REQUEST to
the batch's ACK (`batch_timing` `dispatch_to_ack`), mean over the window's
batches."""


def read(run):
    from benchmark.harness.readers import batch_mean_ms
    return batch_mean_ms(run, "dispatch_to_ack")
