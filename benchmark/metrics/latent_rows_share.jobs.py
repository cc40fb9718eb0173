"""Latent cache: of the bytes a decode step over the grid needs
(`costs/joyai_mla_moe.py`: matrices once, the held experts touched, the live
latent rows once), the share that is the live latent rows, in per cent, at
the window's mean held experts touched (the program's counters) and mean live
tokens (the token tap). Whether the cell is still attention's: longer
contexts or a narrower weight stream raise it, more experts touched lower
it."""


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
    if "latent_rows" not in parts:
        return None
    return 100.0 * parts["latent_rows"] / sum(parts.values())
