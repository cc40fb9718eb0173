"""What an `lfm2_moe` decoder (classic blocks whose operator is a gated short
convolution or grouped attention with q/k norms, by the layer's type, under
leading dense layers and sigmoid-routed gated experts without a shared
expert, a head tied to the embedding) NEEDS per call, counted from shapes at
the configuration's stated precision: bfloat16 (2 bytes) for every matrix,
for the cached K and V rows and for the convolution windows. Never what the
compiler emitted and never what the program happens to read: a program that
reads every held expert, the whole K/V grid, the windows of empty slots or
the tied table twice shows that as a low roofline share.

A decode step over the slot grid needs: every convolution layer's two
matrices and taps, every attention layer's q, k, v, o (and the two head
norms), the dense layers, the routers and the head (the embedding table,
once) once; the weights of the HELD experts its tokens TOUCH (counted by
the program's routing counter, not assumed); the K and V rows of the tokens
live in the grid, in the attention layers alone; and the convolution window
of every OCCUPIED slot, read once and written once (K - 1 rows of d values
a convolution layer: it does not grow with the rows cached). Activations,
the embedding rows looked up and the K/V row a step writes are left out
(small, and leaving them out keeps the count a floor).

`spec` is the configuration's `lm_spec` block.
"""

from __future__ import annotations

from typing import Any, Dict, List

BYTES = 2  # bfloat16: matrices, K/V rows, convolution windows


def conv_kernels(spec: Dict[str, Any]) -> List[int]:
    """Every layer's convolution kernel, 0 for an attention layer."""
    al = spec["attention_layers"]
    return [int(al["types"][n].get("conv_kernel") or 0) for n in al["layers"]]


def _dims(spec: Dict[str, Any]) -> Dict[str, Any]:
    e = int(spec["num_experts"])
    layers, dense = int(spec["n_layers"]), int(spec.get("dense_layers", 0))
    al = spec["attention_layers"]
    heads = {int(t["n_heads"]) for t in al["types"].values()
             if t.get("conv_kernel") is None}
    if len(heads) > 1:
        raise ValueError("attention types of several head counts")
    kernels = conv_kernels(spec)
    return {
        "d": int(spec["d_model"]), "v": int(spec["vocab_size"]),
        "h": heads.pop() if heads else 0,
        "kv": int(spec["n_kv_heads"]), "hd": int(spec["head_dim"]),
        "n_conv": sum(1 for k in kernels if k),
        "n_attn": sum(1 for k in kernels if not k),
        "window_rows": sum(k - 1 for k in kernels if k),
        "taps": sum(kernels),
        "n_dense": dense, "n_moe": layers - dense,
        "ff": int(spec["d_ff"]), "e": e,
        "held": int((spec.get("experts_held") or (0, e))[1]),
        "f": int(spec["expert_d_ff"]), "k": int(spec["experts_per_token"]),
        "bias": bool((spec.get("router") or {}).get("bias")),
        "tied": bool(spec.get("tied_head")),
    }


def conv_params(spec: Dict[str, Any]) -> int:
    """One convolution layer's two matrices (in_proj d x 3d, out_proj d x
    d); the taps are counted over all layers (`conv_taps`)."""
    m = _dims(spec)
    return 4 * m["d"] * m["d"]


def conv_taps(spec: Dict[str, Any]) -> int:
    """The [K, d] taps of every convolution layer."""
    m = _dims(spec)
    return m["taps"] * m["d"]


def attention_params(spec: Dict[str, Any]) -> int:
    """q, k, v, o and the two head norms of one attention layer."""
    m = _dims(spec)
    qw, kvw = m["h"] * m["hd"], m["kv"] * m["hd"]
    return m["d"] * (qw + 2 * kvw) + qw * m["d"] + 2 * m["hd"]


def dense_params(spec: Dict[str, Any]) -> int:
    """One dense layer's gated MLP."""
    m = _dims(spec)
    return 3 * m["d"] * m["ff"]


def expert_params(spec: Dict[str, Any]) -> int:
    """One routed expert's three matrices."""
    m = _dims(spec)
    return 3 * m["d"] * m["f"]


def expert_layer_fixed_params(spec: Dict[str, Any]) -> int:
    """What every token of an expert layer multiplies whatever it is
    routed to: the router (and its selection bias). No shared expert."""
    m = _dims(spec)
    return m["d"] * m["e"] + (m["e"] if m["bias"] else 0)


def head_params(spec: Dict[str, Any]) -> int:
    m = _dims(spec)
    return m["d"] * m["v"]


def param_count(spec: Dict[str, Any]) -> int:
    """Every parameter the tree holds (the layers' norms left out: vectors
    of `d`); a tied head is the embedding, counted once."""
    m = _dims(spec)
    return (m["n_conv"] * conv_params(spec) + conv_taps(spec)
            + m["n_attn"] * attention_params(spec)
            + m["n_dense"] * dense_params(spec)
            + m["n_moe"] * (expert_layer_fixed_params(spec)
                            + m["held"] * expert_params(spec))
            + (1 if m["tied"] else 2) * head_params(spec))


def kv_bytes_per_token(spec: Dict[str, Any]) -> int:
    """K and V rows one cached token holds over the attention layers."""
    m = _dims(spec)
    return 2 * m["kv"] * m["hd"] * BYTES * m["n_attn"]


def state_bytes_per_slot(spec: Dict[str, Any]) -> int:
    """The convolution windows a sequence carries: K - 1 rows of d values
    a convolution layer, whatever its length."""
    m = _dims(spec)
    return m["window_rows"] * m["d"] * BYTES


def decode_step_parts(spec: Dict[str, Any], live_tokens: float,
                      slots: float, held_touched: float) -> Dict[str, float]:
    """Least bytes one decode step over the slot grid must move, by part.
    `slots` are the OCCUPIED slots (each one's windows are read once and
    written once), `held_touched` the mean number of distinct held experts
    a layer's tokens reach in one step (at most those held)."""
    m = _dims(spec)
    touched = min(float(held_touched), float(m["held"]))
    return {
        "experts": m["n_moe"] * touched * expert_params(spec) * BYTES,
        "conv_matrices":
            (m["n_conv"] * conv_params(spec) + conv_taps(spec)) * BYTES,
        "attention_matrices": m["n_attn"] * attention_params(spec) * BYTES,
        "dense": m["n_dense"] * dense_params(spec) * BYTES,
        "expert_layer_fixed":
            m["n_moe"] * expert_layer_fixed_params(spec) * BYTES,
        "head": head_params(spec) * BYTES,
        "kv": live_tokens * kv_bytes_per_token(spec),
        "state": 2.0 * slots * state_bytes_per_slot(spec),
    }


def decode_step_bytes(spec: Dict[str, Any], live_tokens: float,
                      slots: float, held_touched: float) -> float:
    return sum(decode_step_parts(
        spec, live_tokens, slots, held_touched).values())


def _active_params(spec: Dict[str, Any]) -> float:
    """Parameters a token multiplies through the layers on THIS chip: of
    its k experts the share held here (k x held / E on average under a
    router that does not know the cut)."""
    m = _dims(spec)
    return (m["n_conv"] * conv_params(spec)
            + m["n_attn"] * attention_params(spec)
            + m["n_dense"] * dense_params(spec)
            + m["n_moe"] * (expert_layer_fixed_params(spec)
                            + m["k"] * m["held"] / m["e"]
                            * expert_params(spec)))


def decode_step_flops(spec: Dict[str, Any], slots: float,
                      live_tokens: float) -> float:
    """Least FLOPs of one decode step: 2 per ACTIVE parameter per occupied
    slot, the convolutions' taps and two gates (2 K + 2 a channel a
    layer), the head, attention against the live rows."""
    m = _dims(spec)
    per_token = (_active_params(spec) + head_params(spec)
                 + conv_taps(spec) + m["n_conv"] * m["d"])
    return (2.0 * per_token * slots
            + 4.0 * m["h"] * m["hd"] * live_tokens * m["n_attn"])


def prefill_flops(spec: Dict[str, Any], prompt_tokens: int) -> float:
    """Least FLOPs to prefill one prompt: 2 per ACTIVE parameter per token
    through the layers; the convolutions' taps and gates (2 K + 2 a channel
    a token a layer); the attention layers' causal triangle (2 x 2 x H D x
    T^2 / 2); the head at the one position whose logits are needed."""
    m = _dims(spec)
    t = float(prompt_tokens)
    per_token = 2.0 * (_active_params(spec) + conv_taps(spec)
                       + m["n_conv"] * m["d"])
    return (per_token * t + 2.0 * m["h"] * m["hd"] * t * t * m["n_attn"]
            + 2.0 * head_params(spec))


def prefill_bytes(spec: Dict[str, Any], prompt_tokens: int) -> float:
    """Least bytes to prefill one prompt alone: every held weight once (a
    prompt of tens of tokens at top-4 of 32 touches every held expert; the
    tied table is the head's read, its looked-up rows left out), the K/V
    rows it writes and the windows it leaves."""
    return (param_count(spec) * BYTES
            + prompt_tokens * kv_bytes_per_token(spec)
            + state_bytes_per_slot(spec))
