"""Plain reference for a `laguna` decoder (Laguna-XS.2's block at its own
widths): grouped-query attention whose layers go by TYPE (full attention,
or a sliding window of W positions), with head counts and rope by the
type and a per-head gate on the heads' outputs, under sigmoid-routed
gated experts with a shared expert, after leading dense layers. Every
layer is x <- x + Attn(RMSNorm_1(x)), then x <- x + FF(RMSNorm_2(x));
RMSNorm has a learned scale and eps `norm_eps`; no bias anywhere; a
final RMSNorm and an untied head.

- Attention, layer l of type t(l), H_t query heads over KV key/value
  heads of D columns: q_h = W_q,h x', k_g = W_k,g x', v_g = W_v,g x';
  head h reads group g(h) = h // (H_t / KV). Rope by the type
  (`rope_frequencies`): the first R_t columns of q and k rotate, column i
  with column i + R_t / 2 (half-split), the others pass; frequencies f_i
  = theta_t^(-2i / R_t), under YaRN blended as its ramp says, with cos
  and sin times its attention factor. Scores q_h . k_g(h) / sqrt(D);
  position i sees j iff j <= i (full) or 0 <= i - j < W (window: W keys,
  its own among them), a MASK on the whole score matrix; softmax; o_h =
  sum_j p_j v_g(h),j. Gate: a = sigmoid(W_a x') in R^{H_t}, from the same
  normed input; o_h <- a_h o_h; out W_o [o_1 .. o_{H_t}]. Every layer
  holds every position's k and v: nothing is cached, no ring.
- Expert feed-forward (layers `dense_layers` ..): s = sigmoid(W_r x') over
  all E routed experts, float32; the k largest; gates g_e = scale x s_e /
  sum over the chosen; E_e(x') = W_down,e (SiLU(W_gate,e x') * W_up,e x');
  one shared expert S of the same form that every token takes, ungated;
  FF = sum over the chosen e of g_e E_e(x') + S(x'). Where the tree holds
  a share of the experts (`experts_held`: first, count) the others' terms
  are left out, as the program leaves them out, by a plain loop over the
  experts held.
- Dense feed-forward (the first `dense_layers` layers): W_down (SiLU(W_gate
  x') * W_up x') of width `d_ff`.

ASSUMED, where the published `config.json` says nothing (the
configuration's file has each with its convention; the program states the
same, `dml_tpu/inference/generate.py`): the gate is per head and a sigmoid
of a linear map of the layer's normed input; the router scores by sigmoid,
takes the k largest with no selection bias and no soft cap, renormalises
the chosen and scales them; the shared expert is added ungated; SiLU; rope
pairs columns (i, i + R / 2); YaRN's factor scales the rotated columns'
cos and sin only; no q/k norm.

Departures from the published description, all of layout and none of
mathematics: W_q, W_k, W_v are handed as ONE matrix [d, (H_t + 2 KV) D]
(the tree the program declares), cut apart again here.

Straightforward `jax.numpy` in float32 at HIGHEST matmul precision, the
softmax over a block of query rows at a time (a lax.map: the same numbers,
so that [H, T, T] need not fit): no cache, no ring, no kernel, no batching.
It imports nothing of the program and takes nothing the program made: the
weights come from `make_params(spec, seed)`, which the harness also hands
to the program in the tree `lm_backend.init_lm_params` declares. Matrices
are made in float32 and ROUNDED to `spec["param_dtype"]`; the forward
widens them back at use, which is exact. Two plain helpers (`_mm`, `_rms`)
are `references/nemotron_h_latent_moe.py`'s.

The control (`precision="int8"`) is the same forward with every matrix
multiplication by a weight on int8 operands: the nearest precision below
the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import json
import math
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


def _types(spec: Dict[str, Any]) -> Tuple[Tuple[Any, ...], ...]:
    """Each layer's type as (heads, window or 0, rotated columns, theta,
    yarn numbers or None, gated), from `attention_layers`."""
    al = spec["attention_layers"]
    out = []
    for name in al["layers"]:
        t = al["types"][name]
        rope = t.get("rope") or {}
        y = rope.get("yarn")
        out.append((
            int(t["n_heads"]), int(t.get("window") or 0),
            int(rope.get("rotary_dim") or spec["head_dim"]),
            float(rope.get("theta", 10000.0)),
            None if y is None else (
                float(y["factor"]), int(y["original_max_position"]),
                float(y.get("beta_fast", 32.0)), float(y.get("beta_slow", 1.0)),
                (0.1 * math.log(float(y["factor"])) + 1.0
                 if y.get("attention_factor") is None
                 else float(y["attention_factor"]))),
            t.get("gate") == "per_head"))
    return tuple(out)


def _dims(spec: Dict[str, Any]) -> Dict[str, Any]:
    e = int(spec["num_experts"])
    first, held = spec.get("experts_held") or (0, e)
    router = spec.get("router") or {}
    if ((spec.get("attention") or "grouped") != "grouped"
            or not spec.get("attention_layers") or not spec.get("gated")
            or (spec.get("rope_pairing") or "half") != "half"
            or (spec.get("activation") or "silu") != "silu"
            or router.get("scoring") != "sigmoid" or router.get("bias")
            or spec.get("expert_latent") or spec.get("qk_norm")
            or not spec.get("shared_expert_d_ff")):
        raise ValueError(
            "this reference is the laguna decoder: grouped attention by "
            "layer type with half-split rope, SiLU, a sigmoid router "
            "without a selection bias, gated experts in the hidden width "
            "and a gated-form shared expert, no q/k norm")
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
        "shared": int(spec["shared_expert_d_ff"]),
        "scale": float(router.get("scale", 1.0)),
        "eps": float(spec.get("norm_eps") or 1e-6),
    }


def _shapes(spec: Dict[str, Any]) -> Dict[str, Any]:
    m = _dims(spec)
    d, kvw = m["d"], m["kv"] * m["hd"]
    experts = {"moe": {
        "router": {"kernel": (d, m["e"])},
        "w_up": (m["held"], d, m["f"]), "w_gate": (m["held"], d, m["f"]),
        "w_down": (m["held"], m["f"], d),
        "shared_up": {"kernel": (d, m["shared"])},
        "shared_gate": {"kernel": (d, m["shared"])},
        "shared_down": {"kernel": (m["shared"], d)}}}
    dense = {"up": {"kernel": (d, m["ff"])}, "gate": {"kernel": (d, m["ff"])},
             "down": {"kernel": (m["ff"], d)}}
    tree: Dict[str, Any] = {"embed": {"embedding": (m["v"], d)}}
    for i, (h, _, _, _, _, gated) in enumerate(m["types"]):
        tree[f"block_{i}"] = {
            "ln_attn": {"scale": (d,)}, "ln_mlp": {"scale": (d,)},
            "qkv": {"kernel": (d, h * m["hd"] + 2 * kvw)},
            "proj": {"kernel": (h * m["hd"], d)},
            **({"head_gate": {"kernel": (d, h)}} if gated else {}),
            **(dense if i < m["dense"] else experts)}
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
         "n_layers", "d_ff", "attention", "attention_layers", "rope_pairing",
         "norm_eps", "num_experts", "experts_per_token", "expert_d_ff",
         "gated", "experts_held", "router", "shared_expert_d_ff",
         "dense_layers", "activation", "param_dtype")


def make_params(spec: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The weight tree for `spec` from `seed`, made on the default device
    in one jitted call; matrices in `spec["param_dtype"]`."""
    only = json.dumps({k: spec.get(k) for k in _KEYS}, sort_keys=True)
    return _maker(only)(np.uint32(int(seed) % (2 ** 32)))


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


def rope_frequencies(rot: int, theta: float, yarn) -> Tuple[np.ndarray, float]:
    """(the rot / 2 frequencies, the factor on cos and sin) of a rope over
    `rot` rotated columns. Plain: f_i = theta^(-2i / rot), factor 1. YaRN
    (factor s, L original positions, beta_fast, beta_slow, attention
    factor): c(r) = rot ln(L / (2 pi r)) / (2 ln theta); low =
    floor(c(beta_fast)), high = ceil(c(beta_slow)), clipped to [0, rot / 2
    - 1]; ramp_i = clip((i - low) / (high - low), 0, 1); freq_i = (f_i / s)
    ramp_i + f_i (1 - ramp_i)."""
    i = np.arange(rot // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / rot)
    if yarn is None:
        return f.astype(np.float32), 1.0
    s, length, fast, slow, factor = yarn

    def c(r):
        return rot * math.log(length / (2 * math.pi * r)) / (
            2 * math.log(theta))

    low = min(max(math.floor(c(fast)), 0), rot // 2 - 1)
    high = min(max(math.ceil(c(slow)), 0), rot // 2 - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f / s * ramp + f * (1.0 - ramp)).astype(np.float32), factor


def rope_half(x, freqs: np.ndarray, factor: float):
    """x [T, heads, D]: the first 2 len(freqs) columns of the row at
    position t turn, column i with column i + len(freqs), by t x freqs[i],
    cos and sin times `factor`; the columns past them pass."""
    t, half = x.shape[0], len(freqs)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(freqs)
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate(
        [a * cos - b * sin, a * sin + b * cos, x[..., 2 * half:]], axis=-1)


def attention(y, blk, m: Dict[str, Any], typ: tuple, precision: str):
    """Attention of one layer over [T, d]: causal, under the type's
    window as a mask on the whole score matrix."""
    h, window, rot, theta, yarn, gated = typ
    t, kv, hd = y.shape[0], m["kv"], m["hd"]
    qkv = _mm(y, blk["qkv"]["kernel"], precision)
    q = qkv[:, :h * hd].reshape(t, h, hd)
    k = qkv[:, h * hd:(h + kv) * hd].reshape(t, kv, hd)
    v = qkv[:, (h + kv) * hd:].reshape(t, kv, hd)
    freqs, factor = rope_frequencies(rot, theta, yarn)
    q, k = rope_half(q, freqs, factor), rope_half(k, freqs, factor)
    # head h reads group h // (H / KV)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    # the largest block of query rows, Q_BLOCK at most, that divides T
    rows = next((r for r in range(min(Q_BLOCK, t), 7, -1) if t % r == 0), t)

    def block(args):  # a block of query rows against every key
        qb, first = args
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * hd ** -0.5
        back = (first + jnp.arange(rows))[:, None] - jnp.arange(t)[None, :]
        seen = back >= 0
        if window:
            seen = seen & (back < window)
        p = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    a = jax.lax.map(block, (q.reshape(t // rows, rows, h, hd),
                            jnp.arange(0, t, rows)))
    a = a.reshape(t, h, hd)
    if gated:
        a = a * jax.nn.sigmoid(
            _mm(y, blk["head_gate"]["kernel"], precision))[:, :, None]
    return _mm(a.reshape(t, h * hd), blk["proj"]["kernel"], precision)


def _gated(y, up, gate, down, precision: str):
    return _mm(jax.nn.silu(_mm(y, gate, precision)) * _mm(y, up, precision),
               down, precision)


def route(y, moe, m: Dict[str, Any]):
    """(chosen experts [T, k], their gates [T, k]) over ALL the routed
    experts, float32."""
    s = jax.nn.sigmoid(_mm(y, moe["router"]["kernel"], "f32"))
    top_s, top_i = jax.lax.top_k(s, m["k"])
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


@functools.partial(jax.jit, static_argnames=("dims", "typ", "precision"))
def _layer(x, blk, *, dims: tuple, typ: tuple, precision: str):
    m = dict(dims)
    x = x + attention(_rms(x, blk["ln_attn"]["scale"], m["eps"]), blk, m,
                      typ, precision)
    y = _rms(x, blk["ln_mlp"]["scale"], m["eps"])
    if "moe" in blk:
        return x + experts(y, blk["moe"], m, precision)
    return x + dense(y, blk, precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, scale, kernel, *, eps: float, precision: str):
    return _mm(_rms(x, scale, eps), kernel, precision)


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
    it: ONE plain pass over prompt + answer with every position's keys and
    values held, where the program prefilled under a banded kernel and
    then decoded against full planes and rings. `gap_max` is the widest
    such gap (0.0 when every served token is the reference's own argmax)
    and `gap_sum` their sum. With `control`, also those of the token that
    the int8 forward puts first at each position."""
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
