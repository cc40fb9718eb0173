"""Plain reference for a dense GQA decoder: RMSNorm, RoPE (half-split,
base `rope_theta`), grouped-query causal attention, a bias-free
`up -> SiLU -> down` MLP, an untied float32 head.

Straightforward `jax.numpy` in float32 at HIGHEST matmul precision:
full-matrix attention, no cache, no kernel, no batching. It imports
nothing of the program and takes nothing the program made: the weights
come from `make_params(spec, seed)` below, which the harness also hands
to the program, so both sides hold the same numbers from the same seed.

`spec` is the configuration file's `lm_spec` block. The tree's layout is
the one a flax `TransformerLM` of these sizes has (`embed/embedding`,
`block_i/{ln_attn,qkv,proj,ln_mlp,up,down}`, `ln_out`, `lm_head`), which
is what the program's serving code indexes.

The control (`precision="int8"`) is the same forward with every matrix
multiplication done on int8 operands: weights rounded per output
channel, activations per row, products accumulated exactly. It is the
nearest precision below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

RMS_EPS = 1e-6
HI = jax.lax.Precision.HIGHEST


def _shapes(spec: Dict[str, Any]) -> Dict[str, Any]:
    d, ff, v = int(spec["d_model"]), int(spec["d_ff"]), int(spec["vocab_size"])
    h = int(spec["n_heads"])
    kv = int(spec.get("n_kv_heads") or h)
    hd = d // h
    block = {
        "ln_attn": {"scale": (d,)},
        "qkv": {"kernel": (d, d + 2 * kv * hd)},
        "proj": {"kernel": (d, d)},
        "ln_mlp": {"scale": (d,)},
        "up": {"kernel": (d, ff)},
        "down": {"kernel": (ff, d)},
    }
    tree: Dict[str, Any] = {"embed": {"embedding": (v, d)}}
    for i in range(int(spec["n_layers"])):
        tree[f"block_{i}"] = block
    tree["ln_out"] = {"scale": (d,)}
    tree["lm_head"] = {"kernel": (d, v)}
    return tree


@functools.lru_cache(maxsize=None)
def _maker(spec_items: tuple):
    spec = dict(spec_items)
    shapes = _shapes(spec)
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))[0]]

    def make(seed):
        key = jax.random.fold_in(jax.random.PRNGKey(0), seed)
        out = []
        for i, (path, shape) in enumerate(zip(paths, leaves)):
            name = getattr(path[-1], "key", "")
            if name == "scale":
                out.append(jnp.ones(shape, jnp.float32))
                continue
            # fan_in is the contracted axis: rows of a kernel, columns
            # of the embedding table (flax's default for both)
            fan_in = shape[1] if name == "embedding" else shape[0]
            out.append(
                jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * (fan_in ** -0.5))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)


def make_params(spec: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The float32 weight tree for `spec` from `seed`, made on the
    default device in one jitted call."""
    keys = ("vocab_size", "d_model", "n_heads", "n_kv_heads", "n_layers",
            "d_ff")
    items = tuple((k, spec.get(k)) for k in keys)
    return _maker(items)(np.uint32(int(seed) % (2 ** 32)))


def param_count(spec: Dict[str, Any]) -> int:
    return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        _shapes(spec), is_leaf=lambda x: isinstance(x, tuple)))


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------


def _mm(x, w, precision: str):
    """x [T, k] @ w [k, n] in the reference's or the control's precision."""
    if precision == "f32":
        return jnp.matmul(x, w, precision=HI)
    # int8 operands, exact accumulation (products of two int8 values fit
    # float32 exactly; HIGHEST keeps the sum in float32)
    ws = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-12) / 127.0
    xs = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True), 1e-12) / 127.0
    wq = jnp.clip(jnp.round(w / ws), -127, 127)
    xq = jnp.clip(jnp.round(x / xs), -127, 127)
    return jnp.matmul(xq, wq, precision=HI) * xs * ws


def _rms(x, scale):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + RMS_EPS) * scale


def _rope(x, base: float):
    """x [T, H, D], positions 0..T-1, half-split rotation."""
    t, _, d = x.shape
    half = d // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("h", "kv", "base", "precision"))
def _block(x, blk, *, h: int, kv: int, base: float, precision: str):
    t, d = x.shape
    hd = d // h
    y = _rms(x, blk["ln_attn"]["scale"])
    qkv = _mm(y, blk["qkv"]["kernel"], precision)
    q = _rope(qkv[:, :d].reshape(t, h, hd), base)
    k = _rope(qkv[:, d:d + kv * hd].reshape(t, kv, hd), base)
    v = qkv[:, d + kv * hd:].reshape(t, kv, hd)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * hd ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(t, d)
    x = x + _mm(a, blk["proj"]["kernel"], precision)
    y = _rms(x, blk["ln_mlp"]["scale"])
    y = jax.nn.silu(_mm(y, blk["up"]["kernel"], precision))
    return x + _mm(y, blk["down"]["kernel"], precision)


@functools.partial(jax.jit, static_argnames=("precision",))
def _head(x, scale, kernel, *, precision: str):
    return _mm(_rms(x, scale), kernel, precision)


def logits_rows(
    params: Dict[str, Any], spec: Dict[str, Any], tokens: Sequence[int],
    first_row: int, n_rows: int, *, pad_to: int, precision: str = "f32",
    rope_theta: float = 10000.0,
) -> np.ndarray:
    """Logits [n_rows, vocab] of positions first_row .. first_row+n_rows-1
    for one sequence. `tokens` is padded to `pad_to` (causal attention
    keeps the pad out of every earlier row), so that one compiled
    program serves every sequence length."""
    toks = np.zeros(pad_to, np.int32)
    toks[:len(tokens)] = np.asarray(tokens, np.int32)
    h = int(spec["n_heads"])
    kv = int(spec.get("n_kv_heads") or h)
    x = params["embed"]["embedding"][jnp.asarray(toks)]
    for i in range(int(spec["n_layers"])):
        x = _block(x, params[f"block_{i}"], h=h, kv=kv,
                   base=float(rope_theta), precision=precision)
    rows = jax.lax.dynamic_slice_in_dim(x, first_row, n_rows, axis=0)
    return np.asarray(_head(rows, params["ln_out"]["scale"],
                            params["lm_head"]["kernel"], precision=precision))


def served_gaps(
    params: Dict[str, Any], spec: Dict[str, Any], prompt: Sequence[int],
    served: Sequence[int], *, pad_to: int, rows_pad: int,
    control: bool = False, rope_theta: float = 10000.0,
) -> Dict[str, float]:
    """How far below the reference's best logit each served token sits,
    at its own position, given the prompt and the served tokens before
    it. `gap_max` is the widest such gap (0.0 when every served token is
    the reference's own argmax) and `gap_sum` their sum. With `control`,
    also those of the token that the int8 forward puts first at each
    position."""
    prompt = list(int(t) for t in prompt)
    served = list(int(t) for t in served)
    n = len(served)
    full = prompt + served
    first = len(prompt) - 1  # row t scores token t + 1
    ref = logits_rows(params, spec, full, first, rows_pad, pad_to=pad_to,
                      rope_theta=rope_theta)[:n]
    best = ref.max(axis=-1)
    chosen = ref[np.arange(n), np.asarray(served)]
    gaps = best - chosen
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
                          precision="int8", rope_theta=rope_theta)[:n]
        low_gaps = best - ref[np.arange(n), low.argmax(axis=-1)]
        out["control_gap_max"] = float(low_gaps.max())
        out["control_gap_sum"] = float(low_gaps.sum())
    return out
