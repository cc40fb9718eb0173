#!/usr/bin/env python3
"""`aot_memory.py` for a configuration whose attention layers go by type
(full, or a window whose rows a slot caches in a ring): compile, ahead of
time and for a DESCRIBED TPU v5e (no chip), the chunk dispatch
(`LMServer._chunk_impl` itself, on a bare instance that holds what the
method reads: a server cannot be built without a device for its cache)
and the prefill groups the program may form (one row a group), at the
configuration's real size, and print each one's `memory_analysis()`:

    JAX_PLATFORMS=cpu python benchmark/tools/aot_memory_window.py \
        --config laguna_xs2_ep16 [--slots 16] [--groups 512x1,4096x1] \
        [--layers 8]

`--layers N` compiles the first N layers alone (whole periods of the
pattern: a quick look at what the chip's compiler says of the kernels
before the minutes the whole depth takes).

A compile that passes is not a chip run; this only says whether the
chip's compiler takes the program and how many bytes it reckons.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")  # compile only
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="laguna_xs2_ep16")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--groups", default="512x1,1024x1,2048x1,4096x1",
                    help="comma list of <bucket>x<rows>")
    ap.add_argument("--only", default="", help="comma list: chunk,prefill")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dml_tpu.inference.generate import init_cache, prefill
    from dml_tpu.inference.lm_backend import lm_spec_parts
    from dml_tpu.inference.lm_server import LMServer

    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           args.config + ".json")) as f:
        spec = json.load(f)["lm_spec"]
    if args.layers:
        al = spec["attention_layers"]
        spec = {**spec, "n_layers": args.layers, "attention_layers": {
            **al, "layers": al["layers"][:args.layers]}}
    slots = args.slots or int(spec["max_slots"])
    max_len = int(spec["max_len"])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    # the kernel switches ask the backend; answer for the chip
    jax.default_backend = lambda: "tpu"

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    made = {}

    def declared():
        params, made["cfg"] = lm_spec_parts(spec)
        return params

    params = on_chip(jax.eval_shape(declared))
    cfg = made["cfg"]
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, slots, max_len)))
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    size = lambda tree: sum(s.size * s.dtype.itemsize
                            for s in jax.tree_util.tree_leaves(tree))
    print(json.dumps({"weights_gb": round(size(params) / 1e9, 3),
                      "cache_gb": round(size(cache) / 1e9, 3),
                      "layers": cfg.n_layers, "slots": slots}), flush=True)

    def report(name, compiled, secs):
        m = compiled.memory_analysis()
        mib = lambda b: round(b / 2 ** 20, 1)
        print(json.dumps({
            "program": name, "compile_s": round(secs, 1),
            "args_mib": mib(m.argument_size_in_bytes),
            "out_mib": mib(m.output_size_in_bytes),
            "temp_mib": mib(m.temp_size_in_bytes),
            "alias_mib": mib(m.alias_size_in_bytes),
            "live_mib": mib(m.argument_size_in_bytes + m.output_size_in_bytes
                            + m.temp_size_in_bytes - m.alias_size_in_bytes),
            "kernels": compiled.as_text().count("tpu_custom_call"),
        }), flush=True)

    only = set(filter(None, args.only.split(",")))
    if not only or "chunk" in only:
        srv = object.__new__(LMServer)
        srv.cfg, srv.max_len, srv.max_slots = cfg, max_len, slots
        srv._mesh = None
        srv.temperature, srv.chunk = 0.0, int(spec.get("chunk", 32))
        held = spec.get("experts_held") or (0, int(spec["num_experts"]))
        srv._routed = (cfg.n_layers - int(spec.get("dense_layers", 0)),
                       int(spec["num_experts"]))
        srv._held = (int(held[0]), int(held[0]) + int(held[1]))
        t0 = time.monotonic()
        compiled = jax.jit(srv._chunk_impl, donate_argnums=(1, 2, 3)).lower(
            params, cache, vec, vec, vec).compile()
        report(f"decode chunk of {srv.chunk}, {slots} slots", compiled,
               time.monotonic() - t0)

    if not only or "prefill" in only:
        for group in args.groups.split(","):
            bucket, rows = (int(n) for n in group.split("x"))
            prompt = jax.ShapeDtypeStruct((rows, bucket), jnp.int32,
                                          sharding=one)
            li = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one)
            t0 = time.monotonic()
            compiled = jax.jit(
                lambda p, x, i: prefill(p, cfg, x, max_len, logits_index=i)
            ).lower(params, prompt, li).compile()
            report(f"prefill {rows}x{bucket}", compiled,
                   time.monotonic() - t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
