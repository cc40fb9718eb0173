"""LM server: tokens a forward yields an occupied slot: over the window's
`lm_step` spans, tokens fixed and delivered (label `tokens_fixed`) over
forwards x occupied slots (labels `forwards`, `occupancy`). A block of B
at S denoising steps and one commit gives B / (S + 1); blocks a slot runs
after its request's last one, and a last block's rows past the budget,
pull it below."""


def read(run):
    from benchmark.harness.program_spans import program_spans
    spans = [d for d in program_spans(run, "lm_step") or ()
             if "forwards" in d["lb"]]
    work = sum(d["lb"]["forwards"] * d["lb"]["occupancy"] for d in spans)
    return sum(d["lb"]["tokens_fixed"] for d in spans) / work if work else None
