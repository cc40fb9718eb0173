"""Kernels: the decode-chunk program's share of its roofline in the traced
window, for a model with latent attention. Least time: each whole
`jit__chunk_impl` execution in the trace is `chunk` decode steps, and a step
must move every layer's attention matrices, the routers, the shared experts,
the dense layer and the head once, the weights of the HELD experts its tokens
TOUCHED (the program's routing counter's mean over the window, a layer a
step) and the latent row of every live token ONCE at 576 values a layer
(`costs/joyai_mla_moe.py`), at HBM bandwidth: memory bound. Over the device
time of that program. Never clipped at 100. A program that streams the rows
twice, expands them to per-head keys and values, or pads them reads low.
`hybrid_decode_roofline.jobs`'s arithmetic, on this configuration's costs."""

from benchmark.harness import manifest as mf

read = mf.load_module("metrics", "hybrid_decode_roofline.jobs").read
