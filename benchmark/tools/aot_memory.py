#!/usr/bin/env python3
"""Compile, ahead of time and for a DESCRIBED TPU v5e (no chip), the
programs a configuration's cells run at their real size, and print each
one's `memory_analysis()`. Run before any chip call:

    JAX_PLATFORMS=cpu python benchmark/tools/aot_memory.py \
        --config mistral7b_widths_l8 [--slots 16] [--weights float32]

A compile that passes is not a chip run; this only says whether the
chip's compiler takes the program and how many bytes it reckons.

The decode chunk below restates `LMServer._chunk_impl` (a scan of
`chunk` greedy `batched_decode_step`s, positions clamped to the last
row) because the method cannot be lowered without a server, which
allocates its cache on a device. The prefill is the program's own
`generate.prefill`, called as the server calls it.
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
    ap.add_argument("--config", default="mistral7b_widths_l8")
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--weights", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--buckets", default="32,64,128,256,512,1024,2048")
    ap.add_argument("--only", default="", help="comma list: chunk,prefill")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dml_tpu.inference.generate import (
        LMConfig, batched_decode_step, init_cache, prefill,
    )

    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           args.config + ".json")) as f:
        spec = json.load(f)["lm_spec"]
    slots = args.slots or int(spec["max_slots"])
    max_len = int(spec["max_len"])
    chunk = max(1, min(int(spec["max_new_tokens"]), 32))
    cfg = LMConfig(
        vocab_size=spec["vocab_size"], d_model=spec["d_model"],
        n_heads=spec["n_heads"], n_layers=spec["n_layers"],
        d_ff=spec["d_ff"], dtype=jnp.bfloat16, n_kv_heads=spec["n_kv_heads"],
    )
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    # the kernel switches ask the backend; answer for the chip
    jax.default_backend = lambda: "tpu"

    from benchmark.references import dense_gqa_lm as ref

    wdt = jnp.dtype(args.weights)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    params = on_chip(jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, wdt),
        jax.eval_shape(lambda: ref.make_params(spec, 0))))
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, slots, max_len)))
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)

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
        }), flush=True)

    only = set(filter(None, args.only.split(",")))
    if not only or "chunk" in only:
        def chunk_fn(p, c, cur, pos):
            def body(carry, _):
                c, cur, pos = carry
                pc = jnp.minimum(pos, max_len - 1)
                logits, c = batched_decode_step(p, cfg, c, cur, pc)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (c, nxt, pc + 1), nxt
            (c, cur, pos), toks = jax.lax.scan(
                body, (c, cur, pos), None, length=chunk)
            return c, cur, pos, toks

        t0 = time.monotonic()
        compiled = jax.jit(chunk_fn, donate_argnums=(1, 2, 3)).lower(
            params, cache, vec, vec).compile()
        report(f"decode chunk of {chunk}, {slots} slots", compiled,
               time.monotonic() - t0)

    if not only or "prefill" in only:
        for bucket in [int(b) for b in args.buckets.split(",")]:
            rows_list = [slots] if bucket <= 256 else [
                r for r in (1, 2, 4, 8, 16, 32) if r <= slots]
            for rows in rows_list:
                prompt = jax.ShapeDtypeStruct((rows, bucket), jnp.int32,
                                              sharding=one)
                li = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one)
                t0 = time.monotonic()
                compiled = jax.jit(
                    lambda p, x, i: prefill(p, cfg, x, max_len,
                                            logits_index=i)
                ).lower(params, prompt, li).compile()
                report(f"prefill {rows}x{bucket}", compiled,
                       time.monotonic() - t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
