"""Slot state: of the bytes a decode step over the grid needs (the
configuration's `costs`: matrices once, the held experts touched, the live
K/V rows, every occupied slot's state read and written), the share that is
the live K/V rows, in per cent, at the window's mean occupancy, mean held
experts touched (the program's counters) and mean live tokens (the token
tap). Whether a wider grid still pays: while the share is small a step is
the weights', its cost nearly flat in the slots, and more slots are more
tokens a step; as it grows a step's time goes with the rows, and a slot
more costs what it brings. Nothing where the costs name no `kv` part or the
program has no such counters."""


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
    if "kv" not in parts:
        return None
    return 100.0 * parts["kv"] / sum(parts.values())
