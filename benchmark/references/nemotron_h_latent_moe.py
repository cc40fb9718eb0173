"""Plain reference for a `nemotron_h` hybrid decoder: Mamba-2 state-space
layers, grouped-query attention layers without rotary embedding, and
latent expert layers with a sigmoid router and a shared expert, ONE
mixer a layer by the pattern (`M` state-space, `*` attention, `E`
expert): every layer is x <- x + mixer(RMSNorm(x)), eps `norm_eps`, a
learned scale; a final RMSNorm and an untied head.

- `M` (Mamba-2; d_inner = H x P, G groups, state N, conv kernel K):
  [z | xBC | dt] = W_in x' (d_inner | d_inner + 2 G N | H); xBC <-
  SiLU(conv_K(xBC) + b), a causal depthwise convolution over the last K
  positions; xBC = [x (H, P) | B (G, N) | C (G, N)], head h reading group
  h // (H / G); dt <- softplus(dt + dt_bias) and A = -exp(A_log) a head;
  h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t, y_t = h_t C_t + D x_t, by a
  plain `lax.scan` over the positions; y <- RMSNorm_group(y * SiLU(z)),
  the norm over each group's d_inner / G channels; W_out y.
- `*`: q (H heads of D), k and v (KV heads), scores / sqrt(D), causal,
  a full masked softmax, W_o. No rotary embedding: the published
  `nemotron_h` code applies none (the state-space layers carry position).
- `E`: s = sigmoid(W_r x') over all E routed experts, float32; the k with
  the largest s + b (b: a per-expert bias for the choice only); gates
  g_e = scale x s_e / sum over the chosen; u = W_lat_down x'; r = sum over
  the chosen e of g_e W_down,e relu(W_up,e u)^2 in the latent width; out =
  W_lat_up r + W_sdown relu(W_sup x')^2 (the shared expert, in the hidden
  width). Where the tree holds a share of the experts (`experts_held`:
  first, count) the others' terms are left out, as the program leaves them
  out, by a plain loop over the experts held.

Straightforward `jax.numpy` in float32 at HIGHEST matmul precision: no
chunked scan, no cache, no kernel, no batching. It imports nothing of the
program and takes nothing the program made: the weights come from
`make_params(spec, seed)`, which the harness also hands to the program in
the tree `dml_tpu.inference.lm_backend.init_lm_params` declares. Matrices
are made in float32 and ROUNDED to `spec["param_dtype"]`; the forward
widens them back at use, which is exact. The routers' selection bias is
the one weight not drawn: it is balanced, as training leaves it
(`balanced`).

The control (`precision="int8"`) is the same forward with every matrix
multiplication on int8 operands (weights rounded per output channel,
activations per row, products accumulated exactly): the nearest precision
below the configuration's bfloat16. The recurrence itself stays float32.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
#: a state-space head's initial step: log-uniform in this range, floored
#: (the source's time_step_min, time_step_max, time_step_floor)
DT_INIT = (0.001, 0.1, 1e-4)
#: tokens the router's selection bias is balanced over (`balanced`)
BALANCE_TOKENS = 2048


def _dims(spec: Dict[str, Any]) -> Dict[str, Any]:
    h = int(spec["n_heads"])
    e = int(spec["num_experts"])
    first, held = spec.get("experts_held") or (0, e)
    router = spec.get("router") or {}
    if (spec.get("rope") != "none" or spec.get("activation") != "relu2"
            or router.get("scoring") != "sigmoid" or spec.get("gated")
            or not spec.get("expert_latent")
            or not spec.get("shared_expert_d_ff")):
        raise ValueError(
            "this reference is the nemotron_h latent-expert decoder: rope "
            "none, relu2, a sigmoid router, ungated latent experts and a "
            "shared expert")
    s = spec["ssm"]
    m = {
        "d": int(spec["d_model"]), "v": int(spec["vocab_size"]), "h": h,
        "kv": int(spec.get("n_kv_heads") or h),
        "hd": int(spec.get("head_dim") or int(spec["d_model"]) // h),
        "pattern": str(spec["layer_pattern"]), "e": e, "first": int(first),
        "held": int(held), "f": int(spec["expert_d_ff"]),
        "k": int(spec["experts_per_token"]),
        "latent": int(spec["expert_latent"]),
        "shared": int(spec["shared_expert_d_ff"]),
        "bias": bool(router.get("bias")),
        "scale": float(router.get("scale", 1.0)),
        "eps": float(spec.get("norm_eps", 1e-6)),
        "sh": int(s["heads"]), "sp": int(s["head_dim"]),
        "sn": int(s["state"]), "sg": int(s.get("groups", 1)),
        "sk": int(s.get("conv_kernel", 4)),
    }
    m["di"] = m["sh"] * m["sp"]
    m["cw"] = m["di"] + 2 * m["sg"] * m["sn"]
    return m


def _shapes(spec: Dict[str, Any]) -> Dict[str, Any]:
    m = _dims(spec)
    d, qw, kvw = m["d"], m["h"] * m["hd"], m["kv"] * m["hd"]
    router: Dict[str, Any] = {"kernel": (d, m["e"])}
    if m["bias"]:
        router["bias"] = (m["e"],)
    kinds = {
        "M": {"ssm": {
            "in_proj": {"kernel": (d, m["di"] + m["cw"] + m["sh"])},
            "conv": {"kernel": (m["sk"], m["cw"]), "bias": (m["cw"],)},
            "A_log": (m["sh"],), "D": (m["sh"],), "dt_bias": (m["sh"],),
            "norm": {"scale": (m["di"],)},
            "out_proj": {"kernel": (m["di"], d)}}},
        "*": {"qkv": {"kernel": (d, qw + 2 * kvw)},
              "proj": {"kernel": (qw, d)}},
        "E": {"moe": {
            "router": router,
            "w_up": (m["held"], m["latent"], m["f"]),
            "w_down": (m["held"], m["f"], m["latent"]),
            "latent_down": {"kernel": (d, m["latent"])},
            "latent_up": {"kernel": (m["latent"], d)},
            "shared_up": {"kernel": (d, m["shared"])},
            "shared_down": {"kernel": (m["shared"], d)}}},
    }
    tree: Dict[str, Any] = {"embed": {"embedding": (m["v"], d)}}
    for i, kind in enumerate(m["pattern"]):
        tree[f"block_{i}"] = {"ln": {"scale": (d,)}, **kinds[kind]}
    tree["ln_out"] = {"scale": (d,)}
    tree["lm_head"] = {"kernel": (d, m["v"])}
    return tree


def _is_shape(x: Any) -> bool:
    return isinstance(x, tuple)


@functools.lru_cache(maxsize=None)
def _maker(spec_json: str):
    spec = json.loads(spec_json)
    pdt = jnp.dtype(spec.get("param_dtype") or "float32")
    kernel = int(spec["ssm"].get("conv_kernel", 4))
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        _shapes(spec), is_leaf=_is_shape)

    def make(seed):
        key = jax.random.fold_in(jax.random.PRNGKey(0), seed)
        out = []
        for i, (path, shape) in enumerate(flat):
            names = [getattr(p, "key", "") for p in path]
            k = jax.random.fold_in(key, i)
            if names[-1] in ("scale", "D"):
                out.append(jnp.ones(shape, jnp.float32))
            elif names[-1] == "A_log":
                out.append(jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1.0, 16.0)))
            elif names[-1] == "dt_bias":
                lo, hi, floor = DT_INIT
                dt = jnp.maximum(floor, jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, np.log(lo), np.log(hi))))
                out.append(dt + jnp.log(-jnp.expm1(-dt)))  # softplus^-1
            elif names[-1] == "bias" and "conv" in names:
                out.append(jax.random.uniform(
                    k, shape, jnp.float32, -kernel ** -0.5, kernel ** -0.5))
            elif names[-1] == "bias":  # the router's: set by `balanced`
                out.append(jnp.zeros(shape, jnp.float32))
            else:
                # fan_in is the contracted axis: the second to last of a
                # (stacked) kernel, the last of the embedding table; the
                # convolution's is its K taps
                fan_in = shape[-1] if names[-1] == "embedding" else shape[-2]
                w = jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
                # the router and the convolution stay float32, as the
                # program keeps them
                out.append(w if {"router", "conv"} & set(names)
                           else w.astype(pdt))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)


_KEYS = ("vocab_size", "d_model", "n_heads", "n_kv_heads", "head_dim",
         "layer_pattern", "ssm", "rope", "norm_eps", "num_experts",
         "experts_per_token", "expert_d_ff", "gated", "experts_held",
         "router", "expert_latent", "shared_expert_d_ff", "activation",
         "param_dtype")


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


def _mm(x, w, precision: str):
    """x [T, k] @ w [k, n] in the reference's or the control's precision."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if precision == "f32":
        return jnp.matmul(x, w, precision=HI)
    # int8 operands, exact accumulation (products of two int8 values fit
    # float32 exactly; HIGHEST keeps the sum in float32)
    ws = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-12) / 127.0
    xs = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True), 1e-12) / 127.0
    wq = jnp.clip(jnp.round(w / ws), -127, 127)
    xq = jnp.clip(jnp.round(x / xs), -127, 127)
    return jnp.matmul(xq, wq, precision=HI) * xs * ws


def _rms(x, scale, eps: float):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def state_space(y, p, m: Dict[str, Any], precision: str):
    """The Mamba-2 mixer over [T, d], from a zero state."""
    t = y.shape[0]
    di, gn, sh, sp, sg, sn = (m["di"], m["sg"] * m["sn"], m["sh"], m["sp"],
                              m["sg"], m["sn"])
    zxbcdt = _mm(y, p["in_proj"]["kernel"], precision)
    z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:di + m["cw"]],
                  zxbcdt[:, di + m["cw"]:])
    padded = jnp.pad(xbc, ((m["sk"] - 1, 0), (0, 0)))
    w = p["conv"]["kernel"].astype(jnp.float32)
    conv = p["conv"]["bias"] + sum(
        padded[i:i + t] * w[i] for i in range(m["sk"]))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :di].reshape(t, sh, sp)
    heads_of = sh // sg  # heads a group
    b = jnp.repeat(xbc[:, di:di + gn].reshape(t, sg, sn), heads_of, axis=1)
    c = jnp.repeat(xbc[:, di + gn:].reshape(t, sg, sn), heads_of, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])  # [T, H]
    a = -jnp.exp(p["A_log"])  # [H]

    def step(h, args):  # h [H, P, N]
        x_t, b_t, c_t, dt_t = args
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    _, ys = jax.lax.scan(step, jnp.zeros((sh, sp, sn), jnp.float32),
                         (x, b, c, dt))
    out = (ys + p["D"][:, None] * x).reshape(t, di) * jax.nn.silu(z)
    grp = out.reshape(t, sg, di // sg)
    grp = grp * jax.lax.rsqrt(
        jnp.mean(grp * grp, axis=-1, keepdims=True) + m["eps"])
    return _mm(grp.reshape(t, di) * p["norm"]["scale"],
               p["out_proj"]["kernel"], precision)


def attention(y, blk, m: Dict[str, Any], precision: str):
    """Causal grouped-query attention over [T, d], no rotary embedding."""
    t = y.shape[0]
    h, kv, hd = m["h"], m["kv"], m["hd"]
    qw, kvw = h * hd, kv * hd
    qkv = _mm(y, blk["qkv"]["kernel"], precision)
    q = qkv[:, :qw].reshape(t, h, hd)
    k = jnp.repeat(qkv[:, qw:qw + kvw].reshape(t, kv, hd), h // kv, axis=1)
    v = jnp.repeat(qkv[:, qw + kvw:].reshape(t, kv, hd), h // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(t, qw)
    return _mm(a, blk["proj"]["kernel"], precision)


def route(y, moe, m: Dict[str, Any]):
    """(chosen experts [T, k], their gates [T, k]) over ALL the routed
    experts, float32."""
    s = jax.nn.sigmoid(_mm(y, moe["router"]["kernel"], "f32"))
    _, top_i = jax.lax.top_k(s + moe["router"]["bias"] if m["bias"] else s,
                             m["k"])
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    return top_i, m["scale"] * top_s / top_s.sum(-1, keepdims=True)


def experts(y, moe, m: Dict[str, Any], precision: str, *, shared: bool = True):
    """The latent expert layer by a plain loop over the experts held;
    `shared` False leaves the shared expert out (for adding up shares)."""
    top_i, top_g = route(y, moe, m)
    u = _mm(y, moe["latent_down"]["kernel"], precision)

    def one(out, e):
        # this expert's gate for every token (0 where it was not chosen)
        g = jnp.where(top_i == m["first"] + e, top_g, 0.0).sum(
            -1, keepdims=True)
        h = _relu2(_mm(u, moe["w_up"][e], precision))
        return out + g * _mm(h, moe["w_down"][e], precision), None

    r, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        jnp.arange(moe["w_up"].shape[0]))
    out = _mm(r, moe["latent_up"]["kernel"], precision)
    if shared:
        out = out + _mm(
            _relu2(_mm(y, moe["shared_up"]["kernel"], precision)),
            moe["shared_down"]["kernel"], precision)
    return out


@functools.partial(jax.jit, static_argnames=("kind", "dims", "precision"))
def _layer(x, blk, *, kind: str, dims: tuple, precision: str):
    m = dict(dims)
    y = _rms(x, blk["ln"]["scale"], m["eps"])
    if kind == "M":
        return x + state_space(y, blk["ssm"], m, precision)
    if kind == "*":
        return x + attention(y, blk, m, precision)
    return x + experts(y, blk["moe"], m, precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, scale, kernel, *, eps: float, precision: str):
    return _mm(_rms(x, scale, eps), kernel, precision)


@functools.partial(jax.jit, static_argnames=("eps", "share"))
def _balancing_bias(x, scale, kernel, *, eps: float, share: float):
    """Per expert, minus the score that the `share` of the tokens `x`
    [T, d] that score it highest exceed (centred): with it added, every
    expert clears one common bar for about that share of the tokens."""
    s = jax.nn.sigmoid(_mm(_rms(x, scale, eps), kernel, "f32"))
    bar = jnp.quantile(s, 1.0 - share, axis=0)
    return jnp.median(bar) - bar


def balanced(params, spec, seed):
    """`params` with each expert layer's selection bias set as training
    sets it. The published router's bias is no weight of the loss: it is
    nudged after every step toward even loads (an expert chosen too often
    has its bias lowered), so a deployed router sends each expert about
    k / E of the tokens. Random matrices leave every token a common
    preference (each mixer's output has a mean that no token changes),
    and a bias drawn at random adds to it: the busiest expert then takes
    nine times the mean, which no deployment shows. Here each layer's
    bias is what that rule converges to on `BALANCE_TOKENS` seeded random
    tokens pushed through THIS reference layer by layer: per expert,
    minus its score's 1 - k / E quantile, so that every expert clears a
    common bar for k / E of the tokens. It changes choices (the scores of
    the 22nd and 23rd of 512 lie ~0.01 apart) and is used for the choice
    only."""
    m = _dims(spec)
    if not m["bias"]:
        return params
    dims = tuple(sorted(m.items()))
    toks = jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(1), seed), (BALANCE_TOKENS,),
        0, m["v"])
    x = params["embed"]["embedding"][toks].astype(jnp.float32)
    out = dict(params)
    for i, kind in enumerate(m["pattern"]):
        blk = params[f"block_{i}"]
        if kind == "E":
            router = blk["moe"]["router"]
            bias = _balancing_bias(
                x, blk["ln"]["scale"], router["kernel"], eps=m["eps"],
                share=m["k"] / m["e"])
            blk = out[f"block_{i}"] = {**blk, "moe": {
                **blk["moe"], "router": {**router, "bias": bias}}}
        x = _layer(x, blk, kind=kind, dims=dims, precision="f32")
    return out


def hidden(params, spec, tokens, *, precision: str = "f32"):
    """Hidden states [T, d] after the last layer of ONE sequence."""
    m = _dims(spec)
    dims = tuple(sorted(m.items()))
    x = params["embed"]["embedding"][jnp.asarray(tokens)].astype(jnp.float32)
    for i, kind in enumerate(m["pattern"]):
        x = _layer(x, params[f"block_{i}"], kind=kind, dims=dims,
                   precision=precision)
    return x


def logits_rows(
    params: Dict[str, Any], spec: Dict[str, Any], tokens: Sequence[int],
    first_row: int, n_rows: int, *, pad_to: int, precision: str = "f32",
) -> np.ndarray:
    """Logits [n_rows, vocab] of positions first_row .. first_row+n_rows-1
    for one sequence. `tokens` is padded to `pad_to` on the right (every
    mixer is causal, so the pad reaches no earlier row), so that one
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
    it: ONE plain pass over prompt + answer, where the program prefilled
    by the chunked scan and then decoded through its state. `gap_max` is
    the widest such gap (0.0 when every served token is the reference's
    own argmax) and `gap_sum` their sum. With `control`, also those of
    the token that the int8 forward puts first at each position."""
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
