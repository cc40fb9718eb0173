"""LM server: occupied slots per decode dispatch (`lm_server_slot_occupancy`),
mean over the window."""


def read(run):
    from benchmark.harness.readers import mean_of_hist
    return mean_of_hist(run, "occupancy")
