"""Expert layer: distinct HELD experts the tokens of one decode step reach in
one layer (histogram `moe_experts_touched_held`, from the dispatch's routing
counts reduced on the device), mean over the window: the expert weights a
step has to read on this chip, of the `experts_held` it holds."""


def read(run):
    from benchmark.harness.readers import mean_of_hist
    return mean_of_hist(run, "experts_held_touched")
