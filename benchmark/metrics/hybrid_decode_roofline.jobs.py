"""Kernels: the decode-chunk program's share of its roofline in the traced
window, for a model whose slots carry a state-space layer's state. Least
time: each whole `jit__chunk_impl` execution in the trace is `chunk` decode
steps, and a step must move every mixer's matrices, the shared experts, the
latent projections, the routers and the head's slice once, the weights of the
HELD experts its tokens TOUCHED (the program's routing counter's mean over the
window, a layer a step), the live K/V rows, and the convolution window and
scan state of every OCCUPIED slot read once and written once (the mean
occupancy of the window's dispatches) (`costs/nemotron_h_latent_moe.py`), at
HBM bandwidth: memory bound (a step over 64 slots is ~0.3 TFLOP against ~11
GB). Over the device time of that program. Never clipped at 100."""


def read(run):
    from benchmark.harness import trace as tr
    from benchmark.harness.peaks import peaks_of
    from benchmark.harness.readers import (_whole, live_tokens_mean,
                                           mean_of_hist)

    t = run.get("trace")
    touched = mean_of_hist(run, "experts_held_touched")
    slots = mean_of_hist(run, "occupancy")
    if not t or touched is None or slots is None:
        return None
    if not hasattr(run["costs"], "decode_step_parts"):
        return None
    pat = run["config"]["trace_modules"]["decode"]
    durs = _whole(tr.module_durations(t, pat["module"]))
    if not durs:
        return None
    a, b = run["trace_window"]
    step_bytes = run["costs"].decode_step_bytes(
        run["system"]["spec"], live_tokens_mean(run, a, b), slots, touched)
    least = (len(durs) * run["system"]["chunk"] * step_bytes
             / peaks_of(run["device_kind"])["hbm_bytes_per_s"])
    return 100.0 * least / sum(durs)
