"""What a block-diffusion decoder with gated top-k experts NEEDS per
call, counted from shapes at the configuration's stated precision
(bfloat16: 2 bytes a weight, 2 bytes a cache element). Never what the
compiler emitted and never what the program happens to read: a program
that reads every expert, or the whole cache grid, shows that as a low
roofline share.

A forward over the slot grid (B = `block_length` tokens a slot) needs:
the attention matrices and the router of every layer once, the weights
of the experts its tokens TOUCH (counted by the program's routing
counters, not assumed), the head (and its norm) on a denoising forward
only, and the K/V rows of the tokens live in the grid. A commit forward
keeps K/V rows and reads no logits: it needs neither the head nor the
LAST layer's experts (a layer's K/V rows depend on its input alone). Activations, the embedding rows
looked up and the rows a forward writes are left out (small, and leaving
them out keeps the count a floor).

`spec` is the configuration's `lm_spec` block.
"""

from __future__ import annotations

from typing import Any, Dict

BYTES = 2  # bfloat16, weights and cache


def _dims(spec: Dict[str, Any]) -> Dict[str, int]:
    h = int(spec["n_heads"])
    e = int(spec["num_experts"])
    return {
        "d": int(spec["d_model"]), "v": int(spec["vocab_size"]), "h": h,
        "kv": int(spec.get("n_kv_heads") or h),
        "hd": int(spec.get("head_dim") or int(spec["d_model"]) // h),
        "layers": int(spec["n_layers"]), "e": e,
        "held": int((spec.get("experts_held") or (0, e))[1]),
        "f": int(spec["expert_d_ff"]),
        "k": int(spec["experts_per_token"]),
        "mats": 3 if spec.get("gated") else 2,
    }


def attention_params(spec: Dict[str, Any]) -> int:
    """q, k, v and o of one layer, and its router."""
    m = _dims(spec)
    qw, kvw = m["h"] * m["hd"], m["kv"] * m["hd"]
    return m["d"] * (qw + 2 * kvw) + qw * m["d"] + m["d"] * m["e"]


def expert_params(spec: Dict[str, Any]) -> int:
    """One expert's matrices."""
    m = _dims(spec)
    return m["mats"] * m["d"] * m["f"]


def head_params(spec: Dict[str, Any]) -> int:
    m = _dims(spec)
    return m["d"] * m["v"]


def param_count(spec: Dict[str, Any]) -> int:
    """Every parameter the tree holds (norms left out: vectors)."""
    m = _dims(spec)
    return (m["layers"] * (attention_params(spec)
                           + m["held"] * expert_params(spec))
            + 2 * head_params(spec))


def kv_bytes_per_token(spec: Dict[str, Any]) -> int:
    """K and V rows one cached token holds over all layers."""
    m = _dims(spec)
    return 2 * m["kv"] * m["hd"] * BYTES * m["layers"]


def forward_bytes(spec: Dict[str, Any], live_tokens: float,
                  experts_touched: float, head: bool) -> float:
    """Least bytes one forward over the slot grid must move.
    `experts_touched` is the mean number of distinct experts a layer's
    tokens reach in one forward (at most the experts held)."""
    m = _dims(spec)
    touched = min(float(experts_touched), float(m["held"]))
    expert_layers = m["layers"] if head else m["layers"] - 1
    weights = (m["layers"] * attention_params(spec)
               + expert_layers * touched * expert_params(spec))
    if head:
        weights += head_params(spec)
    return weights * BYTES + live_tokens * kv_bytes_per_token(spec)


def dispatch_bytes(spec: Dict[str, Any], blocks: int, live_tokens: float,
                   experts_touched: float) -> float:
    """A dispatch of `blocks` blocks a slot: `denoising_steps` denoising
    forwards and one commit forward a block."""
    s = int(spec["denoising_steps"])
    return blocks * (
        s * forward_bytes(spec, live_tokens, experts_touched, True)
        + forward_bytes(spec, live_tokens, experts_touched, False))


def forward_flops(spec: Dict[str, Any], slots: float, live_tokens: float,
                  head: bool) -> float:
    """Least FLOPs of one forward: 2 per active parameter per token
    (`experts_per_token` experts a token), attention against the live
    rows, the head where it runs."""
    m = _dims(spec)
    b = int(spec["block_length"])
    per_token = m["layers"] * (
        attention_params(spec) + m["k"] * expert_params(spec))
    if head:
        per_token += head_params(spec)
    return (2.0 * per_token * slots * b
            + 4.0 * m["h"] * m["hd"] * live_tokens * b * m["layers"])


def prefill_flops(spec: Dict[str, Any], prompt_tokens: int) -> float:
    """Least FLOPs to prefill one prompt: 2 per ACTIVE parameter per
    token through the layers (the k experts a token is routed to), and
    block-causal attention (QK^T and PV over the lower triangle, the
    diagonal blocks whole: 2 * 2 * H * D * T * (T + B) / 2 a layer). No
    head: a block-diffusion prefill reads no logits."""
    m = _dims(spec)
    t, b = float(prompt_tokens), float(spec["block_length"])
    per_token = attention_params(spec) + m["k"] * expert_params(spec)
    return m["layers"] * (2.0 * per_token * t
                          + 2.0 * m["h"] * m["hd"] * t * (t + b))


def prefill_bytes(spec: Dict[str, Any], prompt_tokens: int) -> float:
    """Least bytes to prefill one prompt alone: every held weight once
    (a prompt of hundreds of tokens touches every expert) and the K/V
    rows it writes."""
    m = _dims(spec)
    return (m["layers"] * (attention_params(spec)
                           + m["held"] * expert_params(spec)) * BYTES
            + prompt_tokens * kv_bytes_per_token(spec))
