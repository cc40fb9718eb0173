"""Expert layer: distinct routed experts the tokens of one forward reach in
one layer (histogram `moe_experts_touched`, from the dispatch's routing
counts), mean over the window: the expert weights a forward has to read."""


def read(run):
    from benchmark.harness.readers import mean_of_hist
    return mean_of_hist(run, "experts_touched")
