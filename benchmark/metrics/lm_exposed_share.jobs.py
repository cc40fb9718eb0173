"""LM server: share of the window in which the serving thread had left
the device with nothing queued (spans `lm_exposed`, clipped to the window):
from its return from a blocking wait on the newest program it enqueued to
the next program enqueued. With `lm_idle_share` (no work at all) it is the
device's idle time over the WHOLE window as the program itself can know
it, where the device trace sees the window's last seconds."""


def read(run):
    from dml_tpu.tracing import SPAN_NAMES

    from benchmark.harness.program_spans import share_pct
    if "lm_exposed" not in SPAN_NAMES:  # a program that records none
        return None
    return share_pct(run, "lm_exposed")
