"""What a dense GQA decoder NEEDS per call, counted from shapes at the
configuration's stated precision (bfloat16: 2 bytes a weight, 2 bytes a
cache element). Never what the compiler emitted (`cost_analysis`) and
never what the program happens to store or read today: a program that
keeps float32 weights or reads all `max_len` cache rows shows that as a
low roofline share, and a change that stops doing so moves the share
toward 100%, never past it.

`spec` is the configuration's `lm_spec` block.
"""

from __future__ import annotations

from typing import Any, Dict

BYTES = 2  # bfloat16, weights and cache


def _dims(spec: Dict[str, Any]):
    d, ff, v = int(spec["d_model"]), int(spec["d_ff"]), int(spec["vocab_size"])
    h = int(spec["n_heads"])
    kv = int(spec.get("n_kv_heads") or h)
    return d, ff, v, h, kv, d // h, int(spec["n_layers"])


def layer_matmul_params(spec: Dict[str, Any]) -> int:
    d, ff, _, _, kv, hd, _ = _dims(spec)
    return d * (d + 2 * kv * hd) + d * d + 2 * d * ff


def matmul_params(spec: Dict[str, Any]) -> int:
    """Parameters every token multiplies: the layers' matrices and the
    head. The embedding is a row lookup and the norms are vectors."""
    d, _, v, *_ , layers = _dims(spec)
    return layers * layer_matmul_params(spec) + d * v


def kv_bytes_per_token(spec: Dict[str, Any]) -> int:
    """K and V rows one cached token holds over all layers."""
    *_, kv, hd, layers = _dims(spec)
    return 2 * kv * hd * BYTES * layers


def decode_step_bytes(spec: Dict[str, Any], live_tokens: float) -> float:
    """Least bytes one decode step over the whole slot grid must move:
    every matmul weight once (shared by all slots), and the K/V rows of
    the tokens that are live in the grid. Activations and the rows a
    step writes are left out (small, and leaving them out keeps the
    count a floor)."""
    return matmul_params(spec) * BYTES + live_tokens * kv_bytes_per_token(spec)


def decode_step_flops(spec: Dict[str, Any], slots: float,
                      live_tokens: float) -> float:
    d, *_ = _dims(spec)
    layers = _dims(spec)[-1]
    return 2.0 * matmul_params(spec) * slots + 4.0 * d * live_tokens * layers


def prefill_flops(spec: Dict[str, Any], prompt_tokens: int) -> float:
    """Least FLOPs to prefill one prompt: 2 per parameter per token
    through the layers, causal attention (QK^T and PV over the lower
    triangle: 2 * 2 * d * T^2 / 2 a layer), and the head at the one
    position whose logits are needed."""
    d, _, v, *_ , layers = _dims(spec)
    t = float(prompt_tokens)
    return (2.0 * layers * layer_matmul_params(spec) * t
            + 2.0 * d * t * t * layers + 2.0 * d * v)


def prefill_bytes(spec: Dict[str, Any], prompt_tokens: int) -> float:
    """Least bytes to prefill one prompt alone: the weights once and the
    K/V rows it writes."""
    return (matmul_params(spec) * BYTES
            + prompt_tokens * kv_bytes_per_token(spec))
