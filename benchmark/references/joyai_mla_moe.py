"""Plain reference for a `joyai_llm_flash` decoder (the DeepSeek-V3 block
at its own widths): latent attention under sigmoid-routed gated experts
with a gated shared expert, after leading dense layers. Every layer is
x <- x + Attn(RMSNorm_1(x)), then x <- x + FF(RMSNorm_2(x)); RMSNorm has
a learned scale and eps `norm_eps`; no bias anywhere but the router's
selection bias; a final RMSNorm and an untied head.

- Attention, H heads: c_q = RMSNorm(W_qa x'); [q_nope_h | q_rope_h] =
  W_qb c_q (nope | rope a head); [c | k_r] = W_kva x' (kv_rank | rope);
  c <- RMSNorm(c); q_rope_h <- RoPE(q_rope_h), k_r <- RoPE(k_r), ONE rope
  key a token shared by every head; [k_nope_h | v_h] = W_kvb,h c; k_h =
  [k_nope_h | k_r]; scores q_h . k_h / sqrt(nope + rope), causal, a full
  masked softmax; o_h = sum_j p_j v_h,j; W_o [o_1 .. o_H]. RoPE rotates
  the pairs (2i, 2i + 1) of the rope columns by position x theta^(-2i /
  rope) (`rope_interleave` true), with no scaling (`rope_scaling` null).
  This is the EXPANDED form at every position: per-head keys and values
  are rebuilt from the latent for the whole sequence; nothing is cached
  and nothing absorbed.
- Expert feed-forward (layers `dense_layers` ..): s = sigmoid(W_r x') over
  all E routed experts, float32; the k with the largest s + b (b: a
  per-expert bias for the choice only; one group, no group limit); gates
  g_e = scale x s_e / sum over the chosen; E_e(x') = W_down,e (SiLU(W_gate,e
  x') * W_up,e x'); one shared expert S of the same gated form that every
  token takes; FF = sum over the chosen e of g_e E_e(x') + S(x'). Where the
  tree holds a share of the experts (`experts_held`: first, count) the
  others' terms are left out, as the program leaves them out, by a plain
  loop over the experts held.
- Dense feed-forward (the first `dense_layers` layers): W_down (SiLU(W_gate
  x') * W_up x') of width `d_ff`.

Departures from the published description, all of layout and none of
mathematics: W_kvb is handed as its two halves a head, `w_uk` [H, kv_rank,
nope] and `w_uv` [H, kv_rank, v] (the tree the program declares; the
forward puts them side by side again); the multi-token-prediction module
(`num_nextn_predict_layers`) is no part of the model's own forward pass
and is left out.

Straightforward `jax.numpy` in float32 at HIGHEST matmul precision, the
softmax over a block of query rows at a time (a lax.map: the same
numbers, so that [H, T, T] need not fit): no cache, no kernel, no
batching. It imports nothing of the program and takes nothing the program
made: the weights come from `make_params(spec, seed)`, which the harness
also hands to the program in the tree `lm_backend.init_lm_params`
declares. Matrices are made in float32 and ROUNDED to
`spec["param_dtype"]`; the forward widens them back at use, which is
exact. The routers' selection bias is the one weight not drawn: it is
balanced as training leaves it, by `references/nemotron_h_latent_moe.py`'s
rule (`balanced`), whose three plain helpers (`_mm`, `_rms`,
`_balancing_bias`) this file shares.

The control (`precision="int8"`) is the same forward with every matrix
multiplication by a weight on int8 operands: the nearest precision below
the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import manifest as mf

_plain = mf.load_module("references", "nemotron_h_latent_moe")
_mm, _rms, _balancing_bias = _plain._mm, _plain._rms, _plain._balancing_bias

HI = jax.lax.Precision.HIGHEST
#: tokens the router's selection bias is balanced over (`balanced`)
BALANCE_TOKENS = 2048
#: query rows the softmax takes at a time
Q_BLOCK = 128


def _dims(spec: Dict[str, Any]) -> Dict[str, Any]:
    e = int(spec["num_experts"])
    first, held = spec.get("experts_held") or (0, e)
    router = spec.get("router") or {}
    lat = spec.get("latent_attention") or {}
    if (spec.get("attention") != "latent" or not spec.get("gated")
            or spec.get("rope_pairing") != "interleaved"
            or spec.get("activation", "silu") != "silu"
            or router.get("scoring") != "sigmoid"
            or spec.get("expert_latent")
            or not spec.get("shared_expert_d_ff")):
        raise ValueError(
            "this reference is the joyai_llm_flash decoder: latent "
            "attention with interleaved rope pairs, SiLU, a sigmoid "
            "router, gated experts in the hidden width and a gated "
            "shared expert")
    return {
        "d": int(spec["d_model"]), "v": int(spec["vocab_size"]),
        "h": int(spec["n_heads"]), "layers": int(spec["n_layers"]),
        "dense": int(spec.get("dense_layers", 0)), "ff": int(spec["d_ff"]),
        "qr": int(lat["q_lora_rank"]), "c": int(lat["kv_lora_rank"]),
        "nope": int(lat["qk_nope_head_dim"]),
        "rope": int(lat["qk_rope_head_dim"]), "vd": int(lat["v_head_dim"]),
        "theta": float(spec.get("rope_theta", 10000.0)),
        "e": e, "first": int(first), "held": int(held),
        "f": int(spec["expert_d_ff"]), "k": int(spec["experts_per_token"]),
        "shared": int(spec["shared_expert_d_ff"]),
        "bias": bool(router.get("bias")),
        "scale": float(router.get("scale", 1.0)),
        "eps": float(spec.get("norm_eps", 1e-6)),
    }


def _shapes(spec: Dict[str, Any]) -> Dict[str, Any]:
    m = _dims(spec)
    d, h = m["d"], m["h"]
    attention = {
        "q_a": {"kernel": (d, m["qr"])}, "q_a_norm": {"scale": (m["qr"],)},
        "q_b": {"kernel": (m["qr"], h * (m["nope"] + m["rope"]))},
        "kv_a": {"kernel": (d, m["c"] + m["rope"])},
        "kv_a_norm": {"scale": (m["c"],)},
        "w_uk": (h, m["c"], m["nope"]), "w_uv": (h, m["c"], m["vd"]),
        "proj": {"kernel": (h * m["vd"], d)},
    }
    router: Dict[str, Any] = {"kernel": (d, m["e"])}
    if m["bias"]:
        router["bias"] = (m["e"],)
    experts = {"moe": {
        "router": router,
        "w_up": (m["held"], d, m["f"]), "w_gate": (m["held"], d, m["f"]),
        "w_down": (m["held"], m["f"], d),
        "shared_up": {"kernel": (d, m["shared"])},
        "shared_gate": {"kernel": (d, m["shared"])},
        "shared_down": {"kernel": (m["shared"], d)}}}
    dense = {"up": {"kernel": (d, m["ff"])}, "gate": {"kernel": (d, m["ff"])},
             "down": {"kernel": (m["ff"], d)}}
    tree: Dict[str, Any] = {"embed": {"embedding": (m["v"], d)}}
    for i in range(m["layers"]):
        tree[f"block_{i}"] = {
            "ln_attn": {"scale": (d,)}, "ln_mlp": {"scale": (d,)},
            **attention, **(dense if i < m["dense"] else experts)}
    tree["ln_out"] = {"scale": (d,)}
    tree["lm_head"] = {"kernel": (d, m["v"])}
    return tree


def _is_shape(x: Any) -> bool:
    return isinstance(x, tuple)


@functools.lru_cache(maxsize=None)
def _maker(spec_json: str):
    spec = json.loads(spec_json)
    pdt = jnp.dtype(spec.get("param_dtype") or "float32")
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        _shapes(spec), is_leaf=_is_shape)

    def make(seed):
        key = jax.random.fold_in(jax.random.PRNGKey(0), seed)
        out = []
        for i, (path, shape) in enumerate(flat):
            names = [getattr(p, "key", "") for p in path]
            k = jax.random.fold_in(key, i)
            if names[-1] == "scale":
                out.append(jnp.ones(shape, jnp.float32))
            elif names[-1] == "bias":  # the router's: set by `balanced`
                out.append(jnp.zeros(shape, jnp.float32))
            else:
                # fan_in is the contracted axis: the second to last of a
                # (stacked) kernel, the last of the embedding table
                fan_in = shape[-1] if names[-1] == "embedding" else shape[-2]
                w = jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
                # the router stays float32, as the program keeps it
                out.append(w if "router" in names else w.astype(pdt))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)


_KEYS = ("vocab_size", "d_model", "n_heads", "n_layers", "d_ff", "attention",
         "latent_attention", "rope_theta", "rope_pairing", "norm_eps",
         "num_experts", "experts_per_token", "expert_d_ff", "gated",
         "experts_held", "router", "shared_expert_d_ff", "dense_layers",
         "activation", "param_dtype")


def make_params(spec: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The weight tree for `spec` from `seed`, made on the default device
    in one jitted call; matrices in `spec["param_dtype"]`; the routers'
    selection bias then `balanced`."""
    only = json.dumps({k: spec.get(k) for k in _KEYS}, sort_keys=True)
    seed = np.uint32(int(seed) % (2 ** 32))
    return balanced(_maker(only)(seed), spec, seed)


def param_shapes(spec: Dict[str, Any]) -> Dict[str, Any]:
    """The tree's shapes (tuples at the leaves), for a caller that has
    to know the layout before any weight is made."""
    return _shapes(spec)


def param_count(spec: Dict[str, Any]) -> int:
    return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        _shapes(spec), is_leaf=_is_shape))


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------


def rope_pairs(x, theta: float):
    """The published pairing: x [T, heads, R]; columns (2i, 2i + 1) of
    the row at position t turn by t x theta^(-2i / R)."""
    t, _, r = x.shape
    freqs = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs  # [T, R/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _mm_heads(x, w, precision: str):
    """x [T, C] by a head-stacked w [H, C, n] -> [T, H, n]: the
    published [C, H * n] matrix, put side by side again."""
    h, c, n = w.shape
    return _mm(x, jnp.moveaxis(w, 0, 1).reshape(c, h * n),
               precision).reshape(-1, h, n)


def attention(y, blk, m: Dict[str, Any], precision: str):
    """Causal latent attention over [T, d], expanded at every position."""
    t = y.shape[0]
    h, nope, rope, vd = m["h"], m["nope"], m["rope"], m["vd"]
    c_q = _rms(_mm(y, blk["q_a"]["kernel"], precision),
               blk["q_a_norm"]["scale"], m["eps"])
    q = _mm(c_q, blk["q_b"]["kernel"], precision).reshape(t, h, nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], rope_pairs(q[..., nope:], m["theta"])], axis=-1)
    kva = _mm(y, blk["kv_a"]["kernel"], precision)
    c = _rms(kva[:, :m["c"]], blk["kv_a_norm"]["scale"], m["eps"])
    k_r = rope_pairs(kva[:, None, m["c"]:], m["theta"])  # [T, 1, rope]
    k = jnp.concatenate([
        _mm_heads(c, blk["w_uk"], precision),
        jnp.broadcast_to(k_r, (t, h, rope))], axis=-1)
    v = _mm_heads(c, blk["w_uv"], precision)
    rows = Q_BLOCK if t % Q_BLOCK == 0 else t

    def block(args):  # a block of query rows against every key
        qb, first = args
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * (
            nope + rope) ** -0.5
        seen = (first + jnp.arange(rows))[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    a = jax.lax.map(block, (q.reshape(t // rows, rows, h, nope + rope),
                            jnp.arange(0, t, rows)))
    return _mm(a.reshape(t, h * vd), blk["proj"]["kernel"], precision)


def _gated(y, up, gate, down, precision: str):
    return _mm(jax.nn.silu(_mm(y, gate, precision)) * _mm(y, up, precision),
               down, precision)


def route(y, moe, m: Dict[str, Any]):
    """(chosen experts [T, k], their gates [T, k]) over ALL the routed
    experts, float32."""
    s = jax.nn.sigmoid(_mm(y, moe["router"]["kernel"], "f32"))
    _, top_i = jax.lax.top_k(s + moe["router"]["bias"] if m["bias"] else s,
                             m["k"])
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    return top_i, m["scale"] * top_s / top_s.sum(-1, keepdims=True)


def experts(y, moe, m: Dict[str, Any], precision: str, *, shared: bool = True):
    """The expert layer by a plain loop over the experts held; `shared`
    False leaves the shared expert out (for adding up shares)."""
    top_i, top_g = route(y, moe, m)

    def one(out, e):
        # this expert's gate for every token (0 where it was not chosen)
        g = jnp.where(top_i == m["first"] + e, top_g, 0.0).sum(
            -1, keepdims=True)
        return out + g * _gated(y, moe["w_up"][e], moe["w_gate"][e],
                                moe["w_down"][e], precision), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y),
                          jnp.arange(moe["w_up"].shape[0]))
    if shared:
        out = out + _gated(
            y, moe["shared_up"]["kernel"], moe["shared_gate"]["kernel"],
            moe["shared_down"]["kernel"], precision)
    return out


def dense(y, blk, precision: str):
    return _gated(y, blk["up"]["kernel"], blk["gate"]["kernel"],
                  blk["down"]["kernel"], precision)


@functools.partial(jax.jit, static_argnames=("dims", "precision"))
def _attn_half(x, blk, *, dims: tuple, precision: str):
    m = dict(dims)
    return x + attention(_rms(x, blk["ln_attn"]["scale"], m["eps"]), blk, m,
                         precision)


@functools.partial(jax.jit, static_argnames=("dims", "precision"))
def _ff_half(x, blk, *, dims: tuple, precision: str):
    m = dict(dims)
    y = _rms(x, blk["ln_mlp"]["scale"], m["eps"])
    if "moe" in blk:
        return x + experts(y, blk["moe"], m, precision)
    return x + dense(y, blk, precision)


def _layer(x, blk, *, dims: tuple, precision: str):
    return _ff_half(_attn_half(x, blk, dims=dims, precision=precision), blk,
                    dims=dims, precision=precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, scale, kernel, *, eps: float, precision: str):
    return _mm(_rms(x, scale, eps), kernel, precision)


def balanced(params, spec, seed):
    """`params` with each expert layer's selection bias set as training
    sets it (`nemotron_h_latent_moe.balanced` has the why): per expert,
    minus the 1 - k / E quantile of its sigmoid score over
    `BALANCE_TOKENS` seeded random tokens pushed through THIS reference
    layer by layer (as one sequence), so that every expert clears a
    common bar for k / E of the tokens. Used for the choice only."""
    m = _dims(spec)
    if not m["bias"]:
        return params
    dims = tuple(sorted(m.items()))
    toks = jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(1), seed), (BALANCE_TOKENS,),
        0, m["v"])
    x = params["embed"]["embedding"][toks].astype(jnp.float32)
    out = dict(params)
    for i in range(m["layers"]):
        blk = params[f"block_{i}"]
        x = _attn_half(x, blk, dims=dims, precision="f32")
        if "moe" in blk:
            router = blk["moe"]["router"]
            bias = _balancing_bias(
                x, blk["ln_mlp"]["scale"], router["kernel"], eps=m["eps"],
                share=m["k"] / m["e"])
            blk = out[f"block_{i}"] = {**blk, "moe": {
                **blk["moe"], "router": {**router, "bias": bias}}}
        x = _ff_half(x, blk, dims=dims, precision="f32")
    return out


def hidden(params, spec, tokens, *, precision: str = "f32"):
    """Hidden states [T, d] after the last layer of ONE sequence."""
    m = _dims(spec)
    dims = tuple(sorted(m.items()))
    x = params["embed"]["embedding"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(m["layers"]):
        x = _layer(x, params[f"block_{i}"], dims=dims, precision=precision)
    return x


def logits_rows(
    params: Dict[str, Any], spec: Dict[str, Any], tokens: Sequence[int],
    first_row: int, n_rows: int, *, pad_to: int, precision: str = "f32",
) -> np.ndarray:
    """Logits [n_rows, vocab] of positions first_row .. first_row+n_rows-1
    for one sequence. `tokens` is padded to `pad_to` on the right (the
    model is causal, so the pad reaches no earlier row), so that one
    compiled program serves every sequence length."""
    toks = np.zeros(pad_to, np.int32)
    toks[:len(tokens)] = np.asarray(tokens, np.int32)
    x = hidden(params, spec, toks, precision=precision)
    rows = jax.lax.dynamic_slice_in_dim(x, first_row, n_rows, axis=0)
    return np.asarray(_head(
        rows, params["ln_out"]["scale"], params["lm_head"]["kernel"],
        eps=float(spec.get("norm_eps", 1e-6)), precision=precision))


def served_gaps(
    params: Dict[str, Any], spec: Dict[str, Any], prompt: Sequence[int],
    served: Sequence[int], *, pad_to: int, rows_pad: int,
    control: bool = False, **_unused: Any,
) -> Dict[str, float]:
    """How far below the reference's best logit each served token sits,
    at its own position, given the prompt and the served tokens before
    it: ONE plain expanded pass over prompt + answer, where the program
    prefilled in the expanded form and then decoded in the absorbed form
    against its cached rows. `gap_max` is the widest such gap (0.0 when
    every served token is the reference's own argmax) and `gap_sum` their
    sum. With `control`, also those of the token that the int8 forward
    puts first at each position."""
    prompt = [int(t) for t in prompt]
    served = [int(t) for t in served]
    n = len(served)
    full = prompt + served
    first = len(prompt) - 1  # row t scores token t + 1
    ref = logits_rows(params, spec, full, first, rows_pad, pad_to=pad_to)[:n]
    best = ref.max(axis=-1)
    gaps = best - ref[np.arange(n), np.asarray(served)]
    srt = np.sort(ref, axis=-1)
    out = {
        "gap_max": float(gaps.max()),
        "gap_sum": float(gaps.sum()),
        "exact": int((gaps == 0.0).sum()),
        "tokens": n,
        "top2_margin_median": float(np.median(srt[:, -1] - srt[:, -2])),
    }
    if control:
        low = logits_rows(params, spec, full, first, rows_pad, pad_to=pad_to,
                          precision="int8")[:n]
        low_gaps = best - ref[np.arange(n), low.argmax(axis=-1)]
        out["control_gap_max"] = float(low_gaps.max())
        out["control_gap_sum"] = float(low_gaps.sum())
    return out
