"""Slot state: of the bytes a decode step over the grid needs
(`costs/nemotron_h_latent_moe.py`: matrices once, the held experts touched,
the live K/V rows, every occupied slot's state read and written), the share
that is the state-space layers' convolution windows and scan states, in per
cent, at the window's mean occupancy, mean held experts touched (the
program's counters) and mean live tokens (the token tap). What a narrower
state, or fewer idle slots advanced, would move."""


def read(run):
    from benchmark.harness.readers import live_tokens_mean, mean_of_hist

    touched = mean_of_hist(run, "experts_held_touched")
    slots = mean_of_hist(run, "occupancy")
    if touched is None or slots is None:
        return None
    if not hasattr(run["costs"], "decode_step_parts"):
        return None
    c = run["counters"]
    parts = run["costs"].decode_step_parts(
        run["system"]["spec"],
        live_tokens_mean(run, c["start"]["t"], c["end"]["t"]), slots, touched)
    return 100.0 * parts["state"] / sum(parts.values())
