"""Expert layer: assignments to the busiest expert over the mean over all
routed experts, one forward one layer (histogram `moe_expert_load_max`), mean
over the window: how uneven the grouped matmul's groups are."""


def read(run):
    from benchmark.harness.readers import mean_of_hist
    return mean_of_hist(run, "expert_load_max")
