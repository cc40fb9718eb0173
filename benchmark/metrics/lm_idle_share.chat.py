"""LM server: share of the window the serving thread waited with no work
(spans `lm_idle`, clipped to the window)."""


def read(run):
    from benchmark.harness.program_spans import share_pct
    return share_pct(run, "lm_idle")
