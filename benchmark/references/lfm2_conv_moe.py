"""Plain reference for an `lfm2_moe` decoder (LFM2-8B-A1B's block at its own
widths): a stack of CLASSIC two-part blocks, x <- x + Op(RMSNorm_1(x)), then
x <- x + FF(RMSNorm_2(x)), whose operator goes by the layer's TYPE: a gated
short convolution, or grouped-query attention with a norm on every head's
query and key. RMSNorm has a learned scale and eps `norm_eps`; no bias
anywhere; one RMSNorm after the last layer and a head TIED to the embedding.

- `conv` (kernel K = `conv_L_cache`, no bias): [B | C | X] = W_in x', thirds
  of d columns in that order; z = B * X; c_t = sum_i w_i z_{t-(K-1)+i}, i <
  K, a column at a time (depthwise), z before the sequence's start zero, NO
  activation; out W_out (C * c). Here: K shifted copies of z over the whole
  sequence, each times its tap. No scan, no step size, nothing carried.
- `full_attention`: H query heads over KV key/value heads of D columns: q_h
  = W_q,h x', k_g = W_k,g x', v_g = W_v,g x'; q_h <- RMSNorm(q_h), k_g <-
  RMSNorm(k_g) over the head's D values (learned scales, eps `norm_eps`)
  BEFORE rope; rope over the whole head, column i with column i + D/2, f_i =
  theta^(-2i/D); head h reads group h // (H / KV); scores q_h . k_g(h) /
  sqrt(D), causal, a full masked softmax; out W_o [o_1 .. o_H]. Every layer
  holds every position's k and v: nothing is cached.
- Dense feed-forward (the first `dense_layers` layers): W_2 (SiLU(W_1 x') *
  W_3 x') of width `d_ff`.
- Expert feed-forward (the others): s = sigmoid(W_g x') over all E routed
  experts, float32; the k with the largest s + b (b: a per-expert bias for
  the CHOICE only); gates g_e = scale x s_e / (sum over the chosen + 1e-6);
  E_e(x') = W_2,e (SiLU(W_1,e x') * W_3,e x'); FF = sum over the chosen e of
  g_e E_e(x'); NO shared expert. Where the tree holds a share of the experts
  (`experts_held`: first, count) the others' terms are left out, as the
  program leaves them out, by a plain loop over the experts held.

ASSUMED, where the published `config.json` says nothing (the configuration's
file has each with its reason): the head is tied; `in_proj`'s thirds are B, C,
X in that order; the gates' sum carries + 1e-6; the router computes in
float32.

Departures from the published description, all of layout and none of
mathematics: W_q, W_k, W_v are handed as ONE matrix [d, (H + 2 KV) D] (the
tree the program declares), cut apart again here; the convolution's weight
is [K, d] (tap, channel), tap K - 1 on the current position, where the
source's Conv1d holds [d, 1, K].

Straightforward `jax.numpy` in float32 at HIGHEST matmul precision, the
softmax over a block of query rows at a time (a lax.map: the same numbers, so
that [H, T, T] need not fit): no cache, no window carried, no kernel, no
batching. It imports nothing of the program and takes nothing the program
made: the weights come from `make_params(spec, seed)`, which the harness also
hands to the program in the tree `lm_backend.init_lm_params` declares.
Matrices are made in float32 and ROUNDED to `spec["param_dtype"]`; the
forward widens them back at use, which is exact. The routers' selection bias
is the one weight not drawn: it is balanced, as training leaves it
(`balanced`). Two plain helpers (`_mm`, `_rms`) are
`references/nemotron_h_latent_moe.py`'s.

The control (`precision="int8"`) is the same forward with every matrix
multiplication by a weight on int8 operands: the nearest precision below the
configuration's bfloat16. The convolution's taps stay float32.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import manifest as mf

_plain = mf.load_module("references", "nemotron_h_latent_moe")
_mm, _rms = _plain._mm, _plain._rms

HI = jax.lax.Precision.HIGHEST
#: query rows the softmax takes at a time
Q_BLOCK = 128
#: tokens the routers' selection bias is balanced over (`balanced`)
BALANCE_TOKENS = 2048
#: the gates' sum carries this (`norm_topk_prob` as the family writes it)
GATE_EPS = 1e-6


def _types(spec: Dict[str, Any]) -> Tuple[Tuple[int, int, float], ...]:
    """Each layer's type as (convolution kernel or 0, query heads or 0,
    rope base), from `attention_layers`."""
    al = spec["attention_layers"]
    out = []
    for name in al["layers"]:
        t = al["types"][name]
        if t.get("conv_kernel") is not None:
            out.append((int(t["conv_kernel"]), 0, 0.0))
        else:
            rope = t.get("rope") or {}
            if set(t) - {"n_heads", "rope"} or set(rope) - {"theta"}:
                raise ValueError(
                    f"layer type {name!r} {t!r}: this reference's attention "
                    f"has heads and a rope base, no window, gate or scaling")
            out.append((0, int(t["n_heads"]),
                        float(rope.get("theta", 10000.0))))
    return tuple(out)


def _dims(spec: Dict[str, Any]) -> Dict[str, Any]:
    e = int(spec["num_experts"])
    first, held = spec.get("experts_held") or (0, e)
    router = spec.get("router") or {}
    if ((spec.get("attention") or "grouped") != "grouped"
            or not spec.get("attention_layers") or not spec.get("gated")
            or not spec.get("qk_norm") or not spec.get("tied_head")
            or (spec.get("rope_pairing") or "half") != "half"
            or (spec.get("activation") or "silu") != "silu"
            or router.get("scoring") != "sigmoid"
            or spec.get("expert_latent") or spec.get("shared_expert_d_ff")):
        raise ValueError(
            "this reference is the lfm2_moe decoder: layers by type (a "
            "gated short convolution, or grouped attention with q/k norms "
            "and half-split rope), SiLU, a sigmoid router, gated experts in "
            "the hidden width, no shared expert, a tied head")
    types = _types(spec)
    if len(types) != int(spec["n_layers"]):
        raise ValueError("attention_layers names another number of layers")
    return {
        "d": int(spec["d_model"]), "v": int(spec["vocab_size"]),
        "kv": int(spec["n_kv_heads"]), "hd": int(spec["head_dim"]),
        "layers": int(spec["n_layers"]), "types": types,
        "dense": int(spec.get("dense_layers", 0)), "ff": int(spec["d_ff"]),
        "e": e, "first": int(first), "held": int(held),
        "f": int(spec["expert_d_ff"]), "k": int(spec["experts_per_token"]),
        "bias": bool(router.get("bias", False)),
        "scale": float(router.get("scale", 1.0)),
        "eps": float(spec.get("norm_eps") or 1e-6),
    }


def _shapes(spec: Dict[str, Any]) -> Dict[str, Any]:
    m = _dims(spec)
    d, hd, kvw = m["d"], m["hd"], m["kv"] * m["hd"]
    router: Dict[str, Any] = {"kernel": (d, m["e"])}
    if m["bias"]:
        router["bias"] = (m["e"],)
    experts = {"moe": {
        "router": router,
        "w_up": (m["held"], d, m["f"]), "w_gate": (m["held"], d, m["f"]),
        "w_down": (m["held"], m["f"], d)}}
    dense = {"up": {"kernel": (d, m["ff"])}, "gate": {"kernel": (d, m["ff"])},
             "down": {"kernel": (m["ff"], d)}}
    tree: Dict[str, Any] = {"embed": {"embedding": (m["v"], d)}}
    for i, (kernel, h, _) in enumerate(m["types"]):
        operator = {"short_conv": {
            "in_proj": {"kernel": (d, 3 * d)},
            "conv": {"kernel": (kernel, d)},
            "out_proj": {"kernel": (d, d)}}} if kernel else {
            "qkv": {"kernel": (d, h * hd + 2 * kvw)},
            "proj": {"kernel": (h * hd, d)},
            "q_norm": {"scale": (hd,)}, "k_norm": {"scale": (hd,)}}
        tree[f"block_{i}"] = {
            "ln_attn": {"scale": (d,)}, "ln_mlp": {"scale": (d,)},
            **operator, **(dense if i < m["dense"] else experts)}
    tree["ln_out"] = {"scale": (d,)}
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
            elif "conv" in names:
                # the [K, d] taps, float32, uniform within 1 / sqrt(K)
                lim = shape[0] ** -0.5
                out.append(jax.random.uniform(
                    k, shape, jnp.float32, -lim, lim))
            else:
                # fan_in is the contracted axis: the second to last of a
                # (stacked) kernel, the last of the embedding table
                fan_in = shape[-1] if names[-1] == "embedding" else shape[-2]
                w = jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
                # the router stays float32, as the program keeps it
                out.append(w if "router" in names else w.astype(pdt))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)


_KEYS = ("vocab_size", "d_model", "n_heads", "n_kv_heads", "head_dim",
         "n_layers", "d_ff", "attention", "attention_layers", "qk_norm",
         "tied_head", "rope_pairing", "norm_eps", "num_experts",
         "experts_per_token", "expert_d_ff", "gated", "experts_held",
         "router", "shared_expert_d_ff", "expert_latent", "dense_layers",
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


def short_conv(y, p, m: Dict[str, Any], precision: str):
    """The gated short convolution over [T, d]: K shifted copies of z = B
    * X, each times its tap (tap K - 1 - j reads the position j back)."""
    d, t = m["d"], y.shape[0]
    bcx = _mm(y, p["in_proj"]["kernel"], precision)
    z = bcx[:, :d] * bcx[:, 2 * d:]
    w = p["conv"]["kernel"].astype(jnp.float32)
    kk = w.shape[0]
    c = sum(jnp.pad(z, ((j, 0), (0, 0)))[:t] * w[kk - 1 - j]
            for j in range(kk))
    return _mm(bcx[:, d:2 * d] * c, p["out_proj"]["kernel"], precision)


def rope_half(x, theta: float):
    """x [T, heads, D]: column i of the row at position t turns with
    column i + D/2 by t x theta^(-2i/D)."""
    t, half = x.shape[0], x.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
        freqs.astype(np.float32))
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def attention(y, blk, m: Dict[str, Any], h: int, theta: float,
              precision: str):
    """Causal grouped attention of one layer over [T, d], a norm on every
    head's query and key before rope."""
    t, kv, hd = y.shape[0], m["kv"], m["hd"]
    qkv = _mm(y, blk["qkv"]["kernel"], precision)
    q = qkv[:, :h * hd].reshape(t, h, hd)
    k = qkv[:, h * hd:(h + kv) * hd].reshape(t, kv, hd)
    v = qkv[:, (h + kv) * hd:].reshape(t, kv, hd)
    q = rope_half(_rms(q, blk["q_norm"]["scale"], m["eps"]), theta)
    k = rope_half(_rms(k, blk["k_norm"]["scale"], m["eps"]), theta)
    # head h reads group h // (H / KV)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    # the largest block of query rows, Q_BLOCK at most, that divides T
    rows = next((r for r in range(min(Q_BLOCK, t), 7, -1) if t % r == 0), t)

    def block(args):  # a block of query rows against every key
        qb, first = args
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * hd ** -0.5
        seen = (first + jnp.arange(rows))[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    a = jax.lax.map(block, (q.reshape(t // rows, rows, h, hd),
                            jnp.arange(0, t, rows)))
    return _mm(a.reshape(t, h * hd), blk["proj"]["kernel"], precision)


def _gated(y, up, gate, down, precision: str):
    return _mm(jax.nn.silu(_mm(y, gate, precision)) * _mm(y, up, precision),
               down, precision)


def route(y, moe, m: Dict[str, Any]):
    """(chosen experts [T, k], their gates [T, k]) over ALL the routed
    experts, float32: the bias chooses, it does not weigh."""
    s = jax.nn.sigmoid(_mm(y, moe["router"]["kernel"], "f32"))
    _, top_i = jax.lax.top_k(
        s + moe["router"]["bias"] if m["bias"] else s, m["k"])
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    return top_i, m["scale"] * top_s / (
        top_s.sum(-1, keepdims=True) + GATE_EPS)


def experts(y, moe, m: Dict[str, Any], precision: str):
    """The expert layer by a plain loop over the experts held."""
    top_i, top_g = route(y, moe, m)

    def one(out, e):
        # this expert's gate for every token (0 where it was not chosen)
        g = jnp.where(top_i == m["first"] + e, top_g, 0.0).sum(
            -1, keepdims=True)
        return out + g * _gated(y, moe["w_up"][e], moe["w_gate"][e],
                                moe["w_down"][e], precision), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y),
                          jnp.arange(moe["w_up"].shape[0]))
    return out


def dense(y, blk, precision: str):
    return _gated(y, blk["up"]["kernel"], blk["gate"]["kernel"],
                  blk["down"]["kernel"], precision)


def operator(x, blk, m: Dict[str, Any], typ: tuple, precision: str):
    """x + Op(RMSNorm_1(x)): the block's first part, by the layer's type."""
    kernel, h, theta = typ
    y = _rms(x, blk["ln_attn"]["scale"], m["eps"])
    if kernel:
        return x + short_conv(y, blk["short_conv"], m, precision)
    return x + attention(y, blk, m, h, theta, precision)


@functools.partial(jax.jit, static_argnames=("dims", "typ", "precision"))
def _layer(x, blk, *, dims: tuple, typ: tuple, precision: str):
    m = dict(dims)
    x = operator(x, blk, m, typ, precision)
    y = _rms(x, blk["ln_mlp"]["scale"], m["eps"])
    if "moe" in blk:
        return x + experts(y, blk["moe"], m, precision)
    return x + dense(y, blk, precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, scale, table, *, eps: float, precision: str):
    """The tied head: the normed rows against the embedding table."""
    return _mm(_rms(x, scale, eps), table.T, precision)


@functools.partial(jax.jit, static_argnames=("dims", "typ", "share"))
def _balancing_bias(x, blk, *, dims: tuple, typ: tuple, share: float):
    """Per expert, minus the score that the `share` of the tokens `x`
    [T, d] that score it highest exceed (centred): with it added, every
    expert clears one common bar for about that share of the tokens."""
    m = dict(dims)
    x = operator(x, blk, m, typ, "f32")
    s = jax.nn.sigmoid(_mm(_rms(x, blk["ln_mlp"]["scale"], m["eps"]),
                           blk["moe"]["router"]["kernel"], "f32"))
    bar = jnp.quantile(s, 1.0 - share, axis=0)
    return jnp.median(bar) - bar


def balanced(params, spec, seed):
    """`params` with each expert layer's selection bias set as training
    sets it (`use_expert_bias`): the bias is no weight of the loss, it is
    nudged after every step toward even loads, so a deployed router sends
    each expert about k / E of the tokens. Random matrices leave every
    token a common preference, under which the busiest expert takes
    several times the mean, which no deployment shows. Here each layer's
    bias is what that rule converges to on `BALANCE_TOKENS` seeded random
    tokens (ONE sequence) pushed through THIS reference layer by layer: per
    expert, minus its score's 1 - k / E quantile. It changes choices and
    is used for the choice only
    (`references/nemotron_h_latent_moe.balanced`'s rule)."""
    m = _dims(spec)
    if not m["bias"]:
        return params
    types = m.pop("types")
    dims = tuple(sorted(m.items()))
    toks = jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(1), seed), (BALANCE_TOKENS,),
        0, m["v"])
    x = params["embed"]["embedding"][toks].astype(jnp.float32)
    out = dict(params)
    for i in range(m["layers"]):
        blk = params[f"block_{i}"]
        if "moe" in blk:
            bias = _balancing_bias(x, blk, dims=dims, typ=types[i],
                                   share=m["k"] / m["e"])
            blk = out[f"block_{i}"] = {**blk, "moe": {
                **blk["moe"], "router": {
                    **blk["moe"]["router"], "bias": bias}}}
        x = _layer(x, blk, dims=dims, typ=types[i], precision="f32")
    return out


def hidden(params, spec, tokens, *, precision: str = "f32"):
    """Hidden states [T, d] after the last layer of ONE sequence."""
    m = _dims(spec)
    types = m.pop("types")
    dims = tuple(sorted(m.items()))
    x = params["embed"]["embedding"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(m["layers"]):
        x = _layer(x, params[f"block_{i}"], dims=dims, typ=types[i],
                   precision=precision)
    return x


def logits_rows(
    params: Dict[str, Any], spec: Dict[str, Any], tokens: Sequence[int],
    first_row: int, n_rows: int, *, pad_to: int, precision: str = "f32",
) -> np.ndarray:
    """Logits [n_rows, vocab] of positions first_row .. first_row+n_rows-1
    for one sequence. `tokens` is padded to `pad_to` on the right (every
    operator is causal, so the pad reaches no earlier row), so that one
    compiled program serves every sequence length."""
    toks = np.zeros(pad_to, np.int32)
    toks[:len(tokens)] = np.asarray(tokens, np.int32)
    x = hidden(params, spec, toks, precision=precision)
    rows = jax.lax.dynamic_slice_in_dim(x, first_row, n_rows, axis=0)
    return np.asarray(_head(
        rows, params["ln_out"]["scale"], params["embed"]["embedding"],
        eps=float(spec.get("norm_eps", 1e-6)), precision=precision))


def served_gaps(
    params: Dict[str, Any], spec: Dict[str, Any], prompt: Sequence[int],
    served: Sequence[int], *, pad_to: int, rows_pad: int,
    control: bool = False, **_unused: Any,
) -> Dict[str, float]:
    """How far below the reference's best logit each served token sits,
    at its own position, given the prompt and the served tokens before
    it: ONE plain pass over prompt + answer, the convolutions over the
    whole sequence and every position's keys and values held, where the
    program prefilled padded rows and then decoded through its cache and
    its convolution windows. `gap_max` is the widest such gap (0.0 when
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
