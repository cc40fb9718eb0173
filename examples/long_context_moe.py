"""Long-context MoE LM: sequence-parallel training + KV-cache decoding.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/long_context_moe.py --dp 2 --sp 2 --ep 2 --seq-len 512

Trains a small MoE transformer on a synthetic copy task with the
sequence dimension sharded over `sp` (ring attention rotating KV over
ICI) and experts over `ep`, then decodes greedily through the KV cache.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> None:
    from dml_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--dp", type=int, default=-1)
    p.add_argument("--sp", type=int, default=2)
    p.add_argument("--ep", type=int, default=2)
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--experts", type=int, default=4)
    p.add_argument("--steps", type=int, default=50)
    args = p.parse_args()

    from dml_tpu.parallel.long_context import LongContextLM
    from dml_tpu.parallel.mesh import local_mesh

    mesh = local_mesh(dp=args.dp, sp=args.sp, ep=args.ep)
    print(f"mesh: {dict(mesh.shape)}")
    lm = LongContextLM(
        mesh, seq_len=args.seq_len, vocab_size=args.vocab,
        d_model=args.d_model, n_heads=args.d_model // 32,
        n_layers=args.layers, d_ff=4 * args.d_model,
        num_experts=args.experts, moe_every=2, learning_rate=3e-3,
    )
    dp = mesh.shape["dp"]
    # learnable pattern: token[i+1] = (token[i] + 1) % 16
    start = np.random.RandomState(0).randint(0, 16, size=(2 * dp, 1))
    toks = ((start + np.arange(args.seq_len)[None, :]) % 16).astype(np.int32)
    for step in range(args.steps):
        loss = lm.train_step(toks)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step}: loss={loss:.4f}")

    out = lm.generate(np.array([[0, 1, 2, 3]], np.int32), 16)
    print(f"prompt [0,1,2,3] ->: {out[0].tolist()}")


if __name__ == "__main__":
    main()
