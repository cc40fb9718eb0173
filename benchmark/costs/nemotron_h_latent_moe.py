"""What a `nemotron_h` hybrid decoder (Mamba-2 state-space layers, GQA
attention layers, latent expert layers with a shared expert; one mixer a
layer by `layer_pattern`) NEEDS per call, counted from shapes at the
configuration's stated precision: bfloat16 (2 bytes) for every matrix
and for K/V rows and convolution windows, float32 (4 bytes) for the
scan state, which the configuration states (`assumed.scan_state_dtype`).
Never what the compiler emitted and never what the program happens to
read: a program that reads every held expert, the whole K/V grid or the
state of empty slots shows that as a low roofline share.

A decode step over the slot grid needs: every mixer's matrices, the
shared experts, the latent projections, the routers and the head's slice
once; the weights of the HELD experts its tokens TOUCH (counted by the
program's routing counter, not assumed); the K/V rows of the tokens live
in the grid; and the convolution window and the scan state of every
OCCUPIED slot, read once and written once. That last part does not grow
with the rows cached, and is what sets such a model's step apart from an
attention model's. Activations, the embedding rows looked up and the K/V
row a step writes are left out (small, and leaving them out keeps the
count a floor).

`spec` is the configuration's `lm_spec` block.
"""

from __future__ import annotations

from typing import Any, Dict

BYTES = 2  # bfloat16: matrices, K/V rows, convolution windows
STATE_BYTES = 4  # float32: the scan state


def _dims(spec: Dict[str, Any]) -> Dict[str, Any]:
    h = int(spec["n_heads"])
    e = int(spec["num_experts"])
    s = spec["ssm"]
    pat = str(spec["layer_pattern"])
    m = {
        "d": int(spec["d_model"]), "v": int(spec["vocab_size"]), "h": h,
        "kv": int(spec.get("n_kv_heads") or h),
        "hd": int(spec.get("head_dim") or int(spec["d_model"]) // h),
        "e": e, "held": int((spec.get("experts_held") or (0, e))[1]),
        "f": int(spec["expert_d_ff"]), "k": int(spec["experts_per_token"]),
        "latent": int(spec["expert_latent"]),
        "shared": int(spec["shared_expert_d_ff"]),
        "sh": int(s["heads"]), "sp": int(s["head_dim"]),
        "sn": int(s["state"]), "sg": int(s.get("groups", 1)),
        "sk": int(s.get("conv_kernel", 4)), "sc": int(s.get("chunk", 128)),
        "n_ssm": pat.count("M"), "n_attn": pat.count("*"),
        "n_moe": pat.count("E"),
    }
    m["di"] = m["sh"] * m["sp"]
    m["cw"] = m["di"] + 2 * m["sg"] * m["sn"]
    return m


def ssm_params(spec: Dict[str, Any]) -> int:
    """One state-space layer: in_proj (z | xBC | dt), out_proj, the
    convolution and its bias, A_log, D, dt_bias, the gated norm."""
    m = _dims(spec)
    return (m["d"] * (m["di"] + m["cw"] + m["sh"]) + m["di"] * m["d"]
            + (m["sk"] + 1) * m["cw"] + 3 * m["sh"] + m["di"])


def attention_params(spec: Dict[str, Any]) -> int:
    """q, k, v and o of one attention layer."""
    m = _dims(spec)
    qw, kvw = m["h"] * m["hd"], m["kv"] * m["hd"]
    return m["d"] * (qw + 2 * kvw) + qw * m["d"]


def expert_params(spec: Dict[str, Any]) -> int:
    """One routed expert's two matrices, in the latent width."""
    m = _dims(spec)
    return 2 * m["latent"] * m["f"]


def expert_layer_fixed_params(spec: Dict[str, Any]) -> int:
    """What every token of an expert layer multiplies whatever it is
    routed to: the router (and its bias), the two latent projections,
    the shared expert."""
    m = _dims(spec)
    return (m["d"] * m["e"] + m["e"] + 2 * m["d"] * m["latent"]
            + 2 * m["d"] * m["shared"])


def head_params(spec: Dict[str, Any]) -> int:
    m = _dims(spec)
    return m["d"] * m["v"]


def param_count(spec: Dict[str, Any]) -> int:
    """Every parameter the tree holds (the layers' norms left out:
    vectors of `d`)."""
    m = _dims(spec)
    return (m["n_ssm"] * ssm_params(spec)
            + m["n_attn"] * attention_params(spec)
            + m["n_moe"] * (expert_layer_fixed_params(spec)
                            + m["held"] * expert_params(spec))
            + 2 * head_params(spec))


def kv_bytes_per_token(spec: Dict[str, Any]) -> int:
    """K and V rows one cached token holds over the attention layers."""
    m = _dims(spec)
    return 2 * m["kv"] * m["hd"] * BYTES * m["n_attn"]


def state_bytes_per_slot(spec: Dict[str, Any]) -> int:
    """The scan state (float32) and the convolution window a sequence
    carries over the state-space layers."""
    m = _dims(spec)
    return m["n_ssm"] * (m["sh"] * m["sp"] * m["sn"] * STATE_BYTES
                         + (m["sk"] - 1) * m["cw"] * BYTES)


def decode_step_parts(spec: Dict[str, Any], live_tokens: float,
                      slots: float, held_touched: float) -> Dict[str, float]:
    """Least bytes one decode step over the slot grid must move, by
    part. `slots` are the OCCUPIED slots (each one's state is read once
    and written once), `held_touched` the mean number of distinct held
    experts a layer's tokens reach in one step (at most those held)."""
    m = _dims(spec)
    touched = min(float(held_touched), float(m["held"]))
    return {
        "experts": m["n_moe"] * touched * expert_params(spec) * BYTES,
        "state": 2.0 * slots * state_bytes_per_slot(spec),
        "state_space_matrices": m["n_ssm"] * ssm_params(spec) * BYTES,
        "expert_layer_fixed":
            m["n_moe"] * expert_layer_fixed_params(spec) * BYTES,
        "head": head_params(spec) * BYTES,
        "attention_matrices": m["n_attn"] * attention_params(spec) * BYTES,
        "kv": live_tokens * kv_bytes_per_token(spec),
    }


def decode_step_bytes(spec: Dict[str, Any], live_tokens: float,
                      slots: float, held_touched: float) -> float:
    return sum(decode_step_parts(
        spec, live_tokens, slots, held_touched).values())


def decode_step_flops(spec: Dict[str, Any], slots: float,
                      live_tokens: float) -> float:
    """Least FLOPs of one decode step: 2 per ACTIVE parameter per
    occupied slot, the recurrence's update and readout (2 x 2 x H x P x
    N a state-space layer), attention against the live rows."""
    m = _dims(spec)
    active = m["k"] * m["held"] / m["e"]
    per_token = (
        m["n_ssm"] * (ssm_params(spec) + 2 * m["di"] * m["sn"])
        + m["n_attn"] * attention_params(spec)
        + m["n_moe"] * (expert_layer_fixed_params(spec)
                        + active * expert_params(spec))
        + head_params(spec))
    return (2.0 * per_token * slots
            + 4.0 * m["h"] * m["hd"] * live_tokens * m["n_attn"])


def prefill_flops(spec: Dict[str, Any], prompt_tokens: int) -> float:
    """Least FLOPs to prefill one prompt: 2 per ACTIVE parameter per
    token through the layers (of a token's k experts, the share held
    here: k x held / E on average under a router that does not know
    the cut); the chunked scan's products a state-space layer (inside a
    chunk of L the lower triangle of C B^T and of its product with x, 2
    x (G N + H P) x (L + 1) / 2 a token, and the chunk's state built
    and read, 2 x 2 x H P N a token); the attention layers' causal
    triangle (2 x 2 x H D x T^2 / 2); the head at the one position whose
    logits are needed."""
    m = _dims(spec)
    t = float(prompt_tokens)
    active = m["k"] * m["held"] / m["e"]
    scan = ((m["sg"] * m["sn"] + m["di"]) * (m["sc"] + 1)
            + 4.0 * m["di"] * m["sn"])
    per_token = (
        m["n_ssm"] * (2.0 * ssm_params(spec) + scan)
        + m["n_attn"] * 2.0 * attention_params(spec)
        + m["n_moe"] * 2.0 * (expert_layer_fixed_params(spec)
                              + active * expert_params(spec)))
    return (per_token * t + 2.0 * m["h"] * m["hd"] * t * t * m["n_attn"]
            + 2.0 * head_params(spec))


def prefill_bytes(spec: Dict[str, Any], prompt_tokens: int) -> float:
    """Least bytes to prefill one prompt alone: every held weight once
    (a prompt of hundreds of tokens at top-22 touches every held
    expert), the K/V rows it writes and the state it leaves."""
    return ((param_count(spec) - head_params(spec)) * BYTES
            + prompt_tokens * kv_bytes_per_token(spec)
            + state_bytes_per_slot(spec))
