"""Worker: replica fetch of a batch's prompt files (ACK-carried `fetch`), mean
per batch over the window's batches."""


def read(run):
    from benchmark.harness.readers import batch_mean_ms
    return batch_mean_ms(run, "fetch")
