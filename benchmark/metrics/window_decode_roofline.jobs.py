"""Kernels: the decode-chunk program's share of its roofline in the traced
window, for a model whose attention layers are full or a window. Least
time: each whole `jit__chunk_impl` execution in the trace is `chunk` decode
steps, and a step must move every layer's attention matrices (at its own
head count, with its gate), the routers, the shared experts, the dense
layer and the head once, the weights of the HELD experts its tokens TOUCHED
(the program's routing counter's mean over the window, a layer a step), and
the K and V rows of the live tokens ONCE: every live token's in a full
layer, min(length, 512) a slot in a window layer, at the window's mean
occupancy (`costs/laguna_window_moe.py`), at HBM bandwidth: memory bound.
Over the device time of that program. Never clipped at 100. A program that
streams a window layer's dead rows, keeps more rows than the window, or
reads every held expert reads low. `hybrid_decode_roofline.jobs`'s
arithmetic, on this configuration's costs."""

from benchmark.harness import manifest as mf

read = mf.load_module("metrics", "hybrid_decode_roofline.jobs").read
