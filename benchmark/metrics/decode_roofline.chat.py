"""Kernels: the decode-chunk program's share of its roofline in the traced
window (see `readers.decode_roofline`; costs from `benchmark/costs/`)."""


def read(run):
    from benchmark.harness.readers import decode_roofline
    return decode_roofline(run)
