"""LM server: of the tokens the window's prefill groups sent to the device
(rows x bucket, label `padded_tokens` of the `lm_prefill_group` spans), the
share that were the requests' own prompt tokens (label `prompt_tokens`)."""


def read(run):
    from benchmark.harness.program_spans import label_ratio_pct
    return label_ratio_pct(run, "lm_prefill_group", "prompt_tokens",
                           "padded_tokens")
