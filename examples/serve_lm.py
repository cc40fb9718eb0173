"""Serve a transformer LM: flash prefill + continuous batching.

Runs on whatever JAX sees (one TPU chip, or CPU for a smoke run):

    python examples/serve_lm.py

Shows the three serving layers working together:
1. `generate`: one-shot decoding — flash-attention prefill fills the
   KV cache in a single forward, then one lax.scan emits new tokens.
2. `LMServer`: continuous batching — mixed prompt lengths decode
   together; requests join/leave the running batch.
3. weight forms: bf16-cast serving weights (the HBM roofline) and the
   weight-only int8 tree for memory-constrained chips.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from dml_tpu.inference.generate import LMConfig, generate
from dml_tpu.inference.lm_server import LMServer
from dml_tpu.inference.quantize import quantize_lm_params, quantized_bytes
from dml_tpu.models.transformer import TransformerLM

CFG = LMConfig(vocab_size=512, d_model=128, n_heads=8, n_layers=4,
               d_ff=512, dtype=jnp.bfloat16, n_kv_heads=2)  # GQA-2


def main() -> None:
    from dml_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    model = TransformerLM(
        vocab_size=CFG.vocab_size, d_model=CFG.d_model,
        n_heads=CFG.n_heads, n_layers=CFG.n_layers, d_ff=CFG.d_ff,
        dtype=CFG.dtype, n_kv_heads=CFG.n_kv_heads,
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    params = jax.tree_util.tree_map(  # serve bf16, not f32 masters
        lambda x: x.astype(jnp.bfloat16) if x.ndim >= 2 else x, params
    )
    rng = np.random.RandomState(0)

    # 1. one-shot generation (prefill + scan)
    prompt = rng.randint(0, CFG.vocab_size, (1, 48)).astype(np.int32)
    t0 = time.monotonic()
    out = np.asarray(generate(params, CFG, jnp.asarray(prompt), 32))
    print(f"generate: {out.shape[1]} tokens in "
          f"{time.monotonic() - t0:.1f}s (incl. compile): {out[0, :8]}...")

    # 2. continuous batching: three different requests, one batch
    srv = LMServer(params, CFG, max_slots=4, max_len=256, chunk=8)
    rids = [
        srv.submit(rng.randint(0, CFG.vocab_size, n), budget)
        for n, budget in ((12, 24), (40, 16), (25, 32))
    ]
    t0 = time.monotonic()
    results = srv.run()
    print(f"server: {sum(len(v) for v in results.values())} tokens "
          f"across {len(rids)} concurrent requests in "
          f"{time.monotonic() - t0:.1f}s")

    # 3. weight-only int8: same API, 1.57x less weight HBM
    qparams = jax.jit(quantize_lm_params)(params)
    nb, _ = quantized_bytes(qparams)
    fb, _ = quantized_bytes(params)
    qout = np.asarray(generate(qparams, CFG, jnp.asarray(prompt), 8))
    print(f"int8 weights: {fb / 1e6:.1f} MB -> {nb / 1e6:.1f} MB, "
          f"decodes fine: {qout[0]}")


if __name__ == "__main__":
    main()
