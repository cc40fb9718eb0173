"""Kernels: the diffusion dispatch's share of its roofline in the traced
window. Least time: each whole dispatch in the trace is `chunk / B` blocks
of S denoising forwards and one commit forward, and a forward must move the
attention and router matrices once, the weights of the experts its tokens
TOUCHED (the program's routing counter's mean over the window, a layer a
forward), the head on denoising forwards only, and the live K/V rows
(`costs/sdar_moe_block_diffusion.py`), at HBM bandwidth: memory bound (a
forward is ~0.17 TFLOP against ~8 GB). Over the device time of the
`jit__diffuse_impl` program. Never clipped at 100."""


def read(run):
    from benchmark.harness import trace as tr
    from benchmark.harness.peaks import peaks_of
    from benchmark.harness.readers import (_whole, live_tokens_mean,
                                           mean_of_hist)

    t = run.get("trace")
    touched = mean_of_hist(run, "experts_touched")
    if not t or touched is None:
        return None
    pat = run["config"]["trace_modules"]["decode"]
    durs = _whole(tr.module_durations(t, pat["module"]))
    if not durs:
        return None
    spec = run["system"]["spec"]
    if "block_length" not in spec or not hasattr(run["costs"], "dispatch_bytes"):
        return None
    a, b = run["trace_window"]
    blocks = max(1, run["system"]["chunk"] // int(spec["block_length"]))
    least = (len(durs) * run["costs"].dispatch_bytes(
        spec, blocks, live_tokens_mean(run, a, b), touched)
        / peaks_of(run["device_kind"])["hbm_bytes_per_s"])
    return 100.0 * least / sum(durs)
