"""What a `laguna` decoder (grouped attention whose layers are full or a
window of W positions, with head counts by the type and a per-head gate,
under gated experts with a shared expert, leading dense layers) NEEDS per
call, counted from shapes at the configuration's stated precision:
bfloat16 (2 bytes) for every matrix and for the cached K and V rows. Never
what the compiler emitted and never what the program happens to read: a
program that streams a window layer's rows past its last W, pads a ring or
a block, holds R > W rows, expands the KV heads to the query heads or reads
every held expert shows that as a low roofline share.

A decode step over the slot grid needs: every layer's attention matrices
(q, k, v, o and the gate, at ITS type's head count), the routers, the
shared experts, the dense layers and the head once; the weights of the
HELD experts its tokens TOUCH (counted by the program's routing counter,
not assumed); and of every token live in the grid its K and V rows, once:
in a full layer every live token's, in a window layer min(length, W) a
slot. Activations, the embedding rows looked up and the row a step writes
are left out (small, and leaving them out keeps the count a floor).

`spec` is the configuration's `lm_spec` block.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

BYTES = 2  # bfloat16: matrices and cached rows


def _dims(spec: Dict[str, Any]) -> Dict[str, Any]:
    e = int(spec["num_experts"])
    layers, dense = int(spec["n_layers"]), int(spec.get("dense_layers", 0))
    return {
        "d": int(spec["d_model"]), "v": int(spec["vocab_size"]),
        "kv": int(spec["n_kv_heads"]), "hd": int(spec["head_dim"]),
        "layers": layers, "n_dense": dense, "n_moe": layers - dense,
        "ff": int(spec["d_ff"]), "e": e,
        "held": int((spec.get("experts_held") or (0, e))[1]),
        "f": int(spec["expert_d_ff"]), "k": int(spec["experts_per_token"]),
        "shared": int(spec["shared_expert_d_ff"]),
    }


def layer_types(spec: Dict[str, Any]) -> List[Tuple[int, int, bool]]:
    """(query heads, window or 0, gated) of every layer, in order."""
    al = spec["attention_layers"]
    return [(int(al["types"][n]["n_heads"]),
             int(al["types"][n].get("window") or 0),
             al["types"][n].get("gate") is not None) for n in al["layers"]]


def attention_params(spec: Dict[str, Any], heads: int, gated: bool) -> int:
    """q, k, v, o and the gate of one layer of `heads` query heads."""
    m = _dims(spec)
    return (m["d"] * heads * m["hd"] + 2 * m["d"] * m["kv"] * m["hd"]
            + heads * m["hd"] * m["d"] + (m["d"] * heads if gated else 0))


def attention_params_all(spec: Dict[str, Any]) -> int:
    return sum(attention_params(spec, h, g) for h, _, g in layer_types(spec))


def expert_params(spec: Dict[str, Any]) -> int:
    """One routed expert's three matrices."""
    m = _dims(spec)
    return 3 * m["d"] * m["f"]


def expert_layer_fixed_params(spec: Dict[str, Any]) -> int:
    """What every token of an expert layer multiplies whatever it is
    routed to: the router and the shared expert."""
    m = _dims(spec)
    return m["d"] * m["e"] + 3 * m["d"] * m["shared"]


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
    return (attention_params_all(spec)
            + m["n_dense"] * dense_params(spec)
            + m["n_moe"] * (expert_layer_fixed_params(spec)
                            + m["held"] * expert_params(spec))
            + 2 * head_params(spec))


def kv_bytes_per_token(spec: Dict[str, Any]) -> Dict[str, int]:
    """The K and V rows one cached token holds, by layer type: over the
    full layers (every token of a sequence) and over the window layers
    (a sequence's last W tokens alone), with the window."""
    m = _dims(spec)
    row = 2 * m["kv"] * m["hd"] * BYTES
    types = layer_types(spec)
    windows = {w for _, w, _ in types if w}
    return {"full": row * sum(1 for _, w, _ in types if not w),
            "window": row * sum(1 for _, w, _ in types if w),
            "window_rows": min(windows) if windows else 0}


def cached_bytes(spec: Dict[str, Any], length: float) -> float:
    """What a sequence of `length` tokens needs cached: a window layer's
    rows capped at its window."""
    kv = kv_bytes_per_token(spec)
    return (length * kv["full"]
            + min(length, kv["window_rows"]) * kv["window"])


def decode_step_parts(spec: Dict[str, Any], live_tokens: float,
                      slots: float, held_touched: float) -> Dict[str, float]:
    """Least bytes one decode step over the slot grid must move, by
    part. `live_tokens` is the tokens live in the grid and `slots` the
    occupied slots: a full layer needs every live token's rows, a window
    layer min(length, W) a slot, reckoned at the mean length (`live_tokens
    / slots`; exact where every occupied slot holds W tokens or more, as
    under traffic whose prompts are no shorter than the window).
    `held_touched` is the mean number of distinct held experts a layer's
    tokens reach in one step (at most those held)."""
    m = _dims(spec)
    kv = kv_bytes_per_token(spec)
    touched = min(float(held_touched), float(m["held"]))
    return {
        "full_rows": live_tokens * kv["full"],
        "window_rows":
            min(live_tokens, slots * kv["window_rows"]) * kv["window"],
        "attention_matrices": attention_params_all(spec) * BYTES,
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


def attended_pairs(t: float, window: int) -> float:
    """(query, key) pairs of a sequence of t positions: the causal
    triangle, or under a window the band (position i sees min(i + 1, W)
    keys)."""
    if not window or t <= window:
        return t * (t + 1) / 2
    return window * t - window * (window - 1) / 2


def prefill_flops(spec: Dict[str, Any], prompt_tokens: int) -> float:
    """Least FLOPs to prefill one prompt: 2 per ACTIVE parameter per token
    through the layers (of a token's k experts, the share held here: k x
    held / E on average under a router that does not know the cut); every
    layer's attention over the pairs its mask keeps (the triangle of a
    full layer, the BAND of a window layer) at its own head count: 2 x H
    x 2 D a pair; the head at the one position whose logits are needed."""
    m = _dims(spec)
    t = float(prompt_tokens)
    active = m["k"] * m["held"] / m["e"]
    per_token = 2.0 * (
        attention_params_all(spec)
        + m["n_dense"] * dense_params(spec)
        + m["n_moe"] * (expert_layer_fixed_params(spec)
                        + active * expert_params(spec)))
    attention = sum(4.0 * h * m["hd"] * attended_pairs(t, w)
                    for h, w, _ in layer_types(spec))
    return per_token * t + attention + 2.0 * head_params(spec)


def prefill_bytes(spec: Dict[str, Any], prompt_tokens: int) -> float:
    """Least bytes to prefill one prompt alone: every held weight once
    and the rows it leaves cached."""
    return ((param_count(spec) - head_params(spec)) * BYTES
            + cached_bytes(spec, prompt_tokens))
