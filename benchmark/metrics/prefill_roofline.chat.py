"""Kernels: the prefill programs' share of their roofline in the traced window
(see `readers.prefill_roofline`; costs from `benchmark/costs/`)."""


def read(run):
    from benchmark.harness.readers import prefill_roofline
    return prefill_roofline(run)
