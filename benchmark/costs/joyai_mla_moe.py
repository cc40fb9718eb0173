"""What a `joyai_llm_flash` decoder (latent attention under gated experts
with a gated shared expert, leading dense layers) NEEDS per call, counted
from shapes at the configuration's stated precision: bfloat16 (2 bytes)
for every matrix and for the cached latent rows. Never what the compiler
emitted and never what the program happens to read: a program that
streams the latent rows twice (once as keys, once as values), expands
them to per-head keys and values, pads a row's 576 values or a head's
values to the keys' width, or reads every held expert shows that as a low
roofline share.

A decode step over the slot grid needs: every layer's attention matrices
(q_a, q_b, kv_a, kv_b, o), the routers, the shared experts, the dense
layers and the head once; the weights of the HELD experts its tokens
TOUCH (counted by the program's routing counter, not assumed); and the
latent row of every token live in the grid ONCE, at `kv_lora_rank +
qk_rope_head_dim` values a layer: attention in the absorbed form reads a
row as key and as value in one pass. Activations, the embedding rows
looked up and the row a step writes are left out (small, and leaving them
out keeps the count a floor).

`spec` is the configuration's `lm_spec` block.
"""

from __future__ import annotations

from typing import Any, Dict

BYTES = 2  # bfloat16: matrices and latent rows


def _dims(spec: Dict[str, Any]) -> Dict[str, Any]:
    lat = spec["latent_attention"]
    e = int(spec["num_experts"])
    layers, dense = int(spec["n_layers"]), int(spec.get("dense_layers", 0))
    return {
        "d": int(spec["d_model"]), "v": int(spec["vocab_size"]),
        "h": int(spec["n_heads"]), "layers": layers, "n_dense": dense,
        "n_moe": layers - dense, "ff": int(spec["d_ff"]),
        "qr": int(lat["q_lora_rank"]), "c": int(lat["kv_lora_rank"]),
        "nope": int(lat["qk_nope_head_dim"]),
        "rope": int(lat["qk_rope_head_dim"]), "vd": int(lat["v_head_dim"]),
        "e": e, "held": int((spec.get("experts_held") or (0, e))[1]),
        "f": int(spec["expert_d_ff"]), "k": int(spec["experts_per_token"]),
        "shared": int(spec["shared_expert_d_ff"]),
    }


def attention_params(spec: Dict[str, Any]) -> int:
    """q_a, q_b, kv_a, kv_b (as w_uk and w_uv) and o of one layer."""
    m = _dims(spec)
    return (m["d"] * m["qr"] + m["qr"] * m["h"] * (m["nope"] + m["rope"])
            + m["d"] * (m["c"] + m["rope"])
            + m["c"] * m["h"] * (m["nope"] + m["vd"])
            + m["h"] * m["vd"] * m["d"])


def expert_params(spec: Dict[str, Any]) -> int:
    """One routed expert's three matrices."""
    m = _dims(spec)
    return 3 * m["d"] * m["f"]


def expert_layer_fixed_params(spec: Dict[str, Any]) -> int:
    """What every token of an expert layer multiplies whatever it is
    routed to: the router (and its bias) and the gated shared expert."""
    m = _dims(spec)
    return m["d"] * m["e"] + m["e"] + 3 * m["d"] * m["shared"]


def dense_params(spec: Dict[str, Any]) -> int:
    """A leading layer's gated dense MLP."""
    m = _dims(spec)
    return 3 * m["d"] * m["ff"]


def head_params(spec: Dict[str, Any]) -> int:
    m = _dims(spec)
    return m["d"] * m["v"]


def param_count(spec: Dict[str, Any]) -> int:
    """Every parameter the tree holds (the norms left out: vectors)."""
    m = _dims(spec)
    return (m["layers"] * attention_params(spec)
            + m["n_dense"] * dense_params(spec)
            + m["n_moe"] * (expert_layer_fixed_params(spec)
                            + m["held"] * expert_params(spec))
            + 2 * head_params(spec))


def kv_bytes_per_token(spec: Dict[str, Any]) -> int:
    """The latent rows one cached token holds over the layers: the
    latent and the rope key, once (not as keys and again as values)."""
    m = _dims(spec)
    return (m["c"] + m["rope"]) * BYTES * m["layers"]


def decode_step_parts(spec: Dict[str, Any], live_tokens: float,
                      slots: float, held_touched: float) -> Dict[str, float]:
    """Least bytes one decode step over the slot grid must move, by
    part. `held_touched` is the mean number of distinct held experts a
    layer's tokens reach in one step (at most those held); `slots` (the
    occupied slots) moves nothing here: a slot carries no state beside
    its rows."""
    del slots
    m = _dims(spec)
    touched = min(float(held_touched), float(m["held"]))
    return {
        "latent_rows": live_tokens * kv_bytes_per_token(spec),
        "attention_matrices": m["layers"] * attention_params(spec) * BYTES,
        "experts": m["n_moe"] * touched * expert_params(spec) * BYTES,
        "expert_layer_fixed":
            m["n_moe"] * expert_layer_fixed_params(spec) * BYTES,
        "dense": m["n_dense"] * dense_params(spec) * BYTES,
        "head": head_params(spec) * BYTES,
    }


def decode_step_bytes(spec: Dict[str, Any], live_tokens: float,
                      slots: float, held_touched: float) -> float:
    return sum(decode_step_parts(
        spec, live_tokens, slots, held_touched).values())


def prefill_flops(spec: Dict[str, Any], prompt_tokens: int) -> float:
    """Least FLOPs to prefill one prompt in the expanded form: 2 per
    ACTIVE parameter per token through the layers (of a token's k
    experts, the share held here: k x held / E on average under a router
    that does not know the cut); the causal triangle of every layer's
    attention at a head's OWN widths (keys nope + rope, values v: 2 x H x
    (nope + rope + v) x T^2 / 2, no width padded to the other); the head
    at the one position whose logits are needed."""
    m = _dims(spec)
    t = float(prompt_tokens)
    active = m["k"] * m["held"] / m["e"]
    per_token = 2.0 * (
        m["layers"] * attention_params(spec)
        + m["n_dense"] * dense_params(spec)
        + m["n_moe"] * (expert_layer_fixed_params(spec)
                        + active * expert_params(spec)))
    triangle = m["h"] * (m["nope"] + m["rope"] + m["vd"]) * t * t
    return per_token * t + m["layers"] * triangle + 2.0 * head_params(spec)


def prefill_bytes(spec: Dict[str, Any], prompt_tokens: int) -> float:
    """Least bytes to prefill one prompt alone: every held weight once
    and the latent rows it writes."""
    return ((param_count(spec) - head_params(spec)) * BYTES
            + prompt_tokens * kv_bytes_per_token(spec))
