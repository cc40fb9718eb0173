"""Plain reference for a block-diffusion decoder with gated experts
(`sdar_moe`: the Qwen3-MoE block under a block-causal mask).

Per layer, x' = RMSNorm(x) (eps 1e-6):

- attention: q = RoPE(RMSNorm_D(W_q x')) per head, k = RoPE(RMSNorm_D(W_k
  x')) per KV head, v = W_v x'; no biases; rope half-split, base
  `rope_theta`; scores scaled by 1 / sqrt(D); grouped queries;
  h = x + W_o softmax(q k^T + M) v under an EXPLICIT mask M;
- experts: p = softmax(W_r h') over all routed experts; S = the k
  largest; y = h + sum_{e in S} (p_e / sum_S p) W_down,e (SiLU(W_gate,e
  h') * W_up,e h'). No capacity, nothing dropped. Where the tree holds a
  share of the experts (`experts_held`: first, count), the others' terms
  are left out, as the program leaves them out;
- head: logits = W_head RMSNorm(x_L), untied. The logits at a position
  predict that position's OWN token (no shift).

Straightforward `jax.numpy` in float32 at HIGHEST matmul precision:
full-matrix attention, experts by a plain loop over the experts held, no
cache, no kernel, no batching. It imports nothing of the program and
takes nothing the program made: the weights come from `make_params(spec,
seed)`, which the harness also hands to the program (in the tree that
`dml_tpu.inference.lm_backend.init_lm_params` declares: `qkv` fused,
`proj`, `q_norm`/`k_norm`, `moe` {router, w_gate, w_up, w_down}). Matrices
are made in float32 and ROUNDED to `spec["param_dtype"]`, so that the
tree fits beside nothing else on one chip at the published widths; the
forward widens each layer back to float32 at use, which is exact.

What a served request is compared with (`served_rows`): ONE forward over
the request's final sequence followed by the S noisy copies of each of
its generated blocks (rebuilt from `fixed_at`: copy s of a block holds
the tokens fixed before step s and the mask id elsewhere), under the
family's training mask: a final row attends final rows block-causally; a
noisy copy attends the final rows of EARLIER blocks and itself. The rows
of copy s are then exactly what denoising forward s of that block saw.

The control (`precision="int8"`) is the same forward with every matrix
multiplication on int8 operands (weights rounded per output channel,
activations per row, products accumulated exactly): the nearest precision
below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

RMS_EPS = 1e-6
HI = jax.lax.Precision.HIGHEST
_KEYS = ("vocab_size", "d_model", "n_heads", "n_kv_heads", "head_dim",
         "n_layers", "num_experts", "experts_per_token", "expert_d_ff",
         "gated", "qk_norm", "param_dtype")


def _dims(spec: Dict[str, Any]) -> Dict[str, int]:
    h = int(spec["n_heads"])
    e = int(spec["num_experts"])
    first, held = spec.get("experts_held") or (0, e)
    return {
        "d": int(spec["d_model"]), "v": int(spec["vocab_size"]), "h": h,
        "kv": int(spec.get("n_kv_heads") or h),
        "hd": int(spec.get("head_dim") or int(spec["d_model"]) // h),
        "layers": int(spec["n_layers"]), "e": e, "first": int(first),
        "held": int(held), "f": int(spec["expert_d_ff"]),
        "k": int(spec["experts_per_token"]),
    }


def _shapes(spec: Dict[str, Any]) -> Dict[str, Any]:
    m = _dims(spec)
    d, hd, qw, kvw = m["d"], m["hd"], m["h"] * m["hd"], m["kv"] * m["hd"]
    block: Dict[str, Any] = {
        "ln_attn": {"scale": (d,)}, "ln_mlp": {"scale": (d,)},
        "qkv": {"kernel": (d, qw + 2 * kvw)},
        "proj": {"kernel": (qw, d)},
        "moe": {"router": {"kernel": (d, m["e"])},
                "w_up": (m["held"], d, m["f"]),
                "w_down": (m["held"], m["f"], d)},
    }
    if spec.get("qk_norm"):
        block["q_norm"] = {"scale": (hd,)}
        block["k_norm"] = {"scale": (hd,)}
    if spec.get("gated"):
        block["moe"]["w_gate"] = (m["held"], d, m["f"])
    tree: Dict[str, Any] = {"embed": {"embedding": (m["v"], d)}}
    for i in range(m["layers"]):
        tree[f"block_{i}"] = block
    tree["ln_out"] = {"scale": (d,)}
    tree["lm_head"] = {"kernel": (d, m["v"])}
    return tree


def _is_shape(x: Any) -> bool:
    return isinstance(x, tuple)


@functools.lru_cache(maxsize=None)
def _maker(spec_items: tuple):
    spec = dict(spec_items)
    spec["experts_held"] = spec.pop("_held")
    pdt = jnp.dtype(spec.get("param_dtype") or "float32")
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        _shapes(spec), is_leaf=_is_shape)

    def make(seed):
        key = jax.random.fold_in(jax.random.PRNGKey(0), seed)
        out = []
        for i, (path, shape) in enumerate(flat):
            names = [getattr(p, "key", "") for p in path]
            if names[-1] == "scale":
                out.append(jnp.ones(shape, jnp.float32))
                continue
            # fan_in is the contracted axis: the second to last of a
            # (stacked) kernel, the last of the embedding table
            fan_in = shape[-1] if names[-1] == "embedding" else shape[-2]
            w = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * (fan_in ** -0.5)
            # the router stays float32, as the program keeps it
            out.append(w if "router" in names else w.astype(pdt))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)


def make_params(spec: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The weight tree for `spec` from `seed`, made on the default device
    in one jitted call; matrices in `spec["param_dtype"]`."""
    items = tuple((k, spec.get(k)) for k in _KEYS) + (
        ("_held", tuple(spec.get("experts_held") or ()) or None),)
    return _maker(items)(np.uint32(int(seed) % (2 ** 32)))


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


def _rms(x, scale):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + RMS_EPS) * scale


def _rope(x, positions, base: float):
    """x [T, H, D] at `positions` [T], half-split rotation."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _experts(y, moe, *, k: int, first: int, precision: str):
    """The expert layer by a plain loop over the experts held."""
    gates = jax.nn.softmax(_mm(y, moe["router"]["kernel"], "f32"), axis=-1)
    top_g, top_i = jax.lax.top_k(gates, k)
    top_g = top_g / top_g.sum(-1, keepdims=True)
    gated = "w_gate" in moe

    def one(out, e):
        # this expert's gate for every token (0 where it was not chosen)
        g = jnp.where(top_i == first + e, top_g, 0.0).sum(-1, keepdims=True)
        up = _mm(y, moe["w_up"][e], precision)
        h = (jax.nn.silu(_mm(y, moe["w_gate"][e], precision)) * up
             if gated else jax.nn.silu(up))
        return out + g * _mm(h, moe["w_down"][e], precision), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(y),
                          jnp.arange(moe["w_up"].shape[0]))
    return out


@functools.partial(jax.jit, static_argnames=(
    "h", "kv", "hd", "base", "k", "first", "precision"))
def _block(x, blk, positions, allowed, *, h: int, kv: int, hd: int,
           base: float, k: int, first: int, precision: str):
    """One layer over [T, d] under the explicit mask `allowed` [T, T];
    returns (x', k [T, KV, D] after rope, v [T, KV, D])."""
    t = x.shape[0]
    qw, kvw = h * hd, kv * hd
    y = _rms(x, blk["ln_attn"]["scale"])
    qkv = _mm(y, blk["qkv"]["kernel"], precision)
    q = qkv[:, :qw].reshape(t, h, hd)
    kk = qkv[:, qw:qw + kvw].reshape(t, kv, hd)
    v = qkv[:, qw + kvw:].reshape(t, kv, hd)
    if "q_norm" in blk:
        q = _rms(q, blk["q_norm"]["scale"])
        kk = _rms(kk, blk["k_norm"]["scale"])
    q, kk = _rope(q, positions, base), _rope(kk, positions, base)
    kr = jnp.repeat(kk, h // kv, axis=1)
    vr = jnp.repeat(v, h // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, kr, precision=HI) * hd ** -0.5
    s = jnp.where(allowed[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, vr, precision=HI).reshape(t, qw)
    x = x + _mm(a, blk["proj"]["kernel"], precision)
    y = _rms(x, blk["ln_mlp"]["scale"])
    return (x + _experts(y, blk["moe"], k=k, first=first,
                         precision=precision), kk, v)


@functools.partial(jax.jit, static_argnames=("precision",))
def _head(x, scale, kernel, *, precision: str):
    return _mm(_rms(x, scale), kernel, precision)


@jax.jit
def _allowed(block_of, copy_of):
    """The explicit mask. Row i has block index `block_of[i]` (of its
    POSITION) and `copy_of[i]`: -1 = a row of the final sequence, c >= 0
    = a row of noisy copy c. A final row attends final rows of blocks <=
    its own; a noisy row attends final rows of blocks < its own, and the
    rows of its own copy."""
    qi, kj = block_of[:, None], block_of[None, :]
    ci, cj = copy_of[:, None], copy_of[None, :]
    final_k = cj < 0
    return (final_k & (ci < 0) & (kj <= qi)) | (
        final_k & (ci >= 0) & (kj < qi)) | ((ci >= 0) & (ci == cj))


def forward(params, spec, tokens, positions, block_of, copy_of, *,
            precision: str = "f32", keep_kv: bool = False):
    """Hidden states [T, d] after the last layer (and, with `keep_kv`,
    every layer's (k, v) [T, KV, D]) of one sequence of rows."""
    m = _dims(spec)
    allowed = _allowed(jnp.asarray(block_of, jnp.int32),
                       jnp.asarray(copy_of, jnp.int32))
    positions = jnp.asarray(positions, jnp.int32)
    x = params["embed"]["embedding"][jnp.asarray(tokens)].astype(jnp.float32)
    kvs = []
    for i in range(m["layers"]):
        x, k, v = _block(
            x, params[f"block_{i}"], positions, allowed, h=m["h"],
            kv=m["kv"], hd=m["hd"], base=float(spec["rope_theta"]),
            k=m["k"], first=m["first"], precision=precision)
        if keep_kv:
            kvs.append((k, v))
    return (x, kvs) if keep_kv else x


def logits_of(params, hidden, *, precision: str = "f32"):
    return _head(hidden, params["ln_out"]["scale"],
                 params["lm_head"]["kernel"], precision=precision)


# ----------------------------------------------------------------------
# a served request as rows
# ----------------------------------------------------------------------


def schedule(masked: int, steps: int) -> List[int]:
    """`low_confidence_static`: how many of a block's `masked` positions
    each of `steps` denoising steps fixes."""
    return [masked // steps + (s < masked % steps) for s in range(steps)]


def request_rows(spec: Dict[str, Any], prompt: Sequence[int],
                 generated: Sequence[int], fixed_at: Sequence[int],
                 ) -> Dict[str, Any]:
    """The rows `served_rows` runs for one request: its final sequence
    (whole blocks: `generated` ends on a block's last position) and, for
    each generated block and each step s = 1..S, the block as denoising
    forward s saw it. Host arithmetic only."""
    b, s_n = int(spec["block_length"]), int(spec["denoising_steps"])
    mask_id = int(spec["mask_token_id"])
    final = [int(t) for t in prompt] + [int(t) for t in generated]
    if len(final) % b:
        raise ValueError("the generated tokens do not end a block")
    start = len(prompt) // b * b  # the first generated block's first row
    # the step that fixed each position from `start` on (0 = given)
    step_of = [0] * (len(prompt) - start) + [int(f) for f in fixed_at]
    tokens, positions, block_of, copy_of = list(final), list(
        range(len(final))), [i // b for i in range(len(final))], [-1] * len(final)
    blocks = []
    for g in range((len(final) - start) // b):
        lo = start + g * b
        steps = step_of[lo - start:lo - start + b]
        for s in range(1, s_n + 1):
            row0 = len(tokens)
            for j in range(b):
                known = steps[j] < s  # given, or fixed by an earlier step
                tokens.append(final[lo + j] if known else mask_id)
                positions.append(lo + j)
                block_of.append(lo // b)
                copy_of.append(g * s_n + s - 1)
            blocks.append({"block": g, "step": s, "row0": row0,
                           "fixed_now": [j for j in range(b) if steps[j] == s],
                           "masked": [j for j in range(b) if steps[j] >= s],
                           "final": final[lo:lo + b]})
    return {"tokens": tokens, "positions": positions, "block_of": block_of,
            "copy_of": copy_of, "final_rows": len(final), "copies": blocks,
            "steps_of_blocks": [step_of[i:i + b]
                                for i in range(0, len(step_of), b)]}


@functools.partial(jax.jit, static_argnames=("precision",))
def _row_stats(hidden, scale, kernel, scored, *, precision: str):
    logits = _head(hidden, scale, kernel, precision=precision)
    best = logits.max(-1)
    return {"best": best, "argmax": logits.argmax(-1),
            "log_conf": best - jax.nn.logsumexp(logits, axis=-1),
            "scored": jnp.take_along_axis(
                logits, scored[:, None], axis=-1)[:, 0]}


def copy_rows(params, spec, rows: Dict[str, Any], *, pad_final: int,
              pad_copies: int, precision: str = "f32"):
    """Run `rows` (padded to `pad_final` final rows and `pad_copies`
    noisy copies, so that one compiled program serves every request) and
    return the hidden states of the noisy copies' rows [pad_copies * B, d]
    (on the device) and the token each of those positions ended up with."""
    b = int(spec["block_length"])
    n_final, n = rows["final_rows"], len(rows["tokens"])
    if n_final > pad_final or (n - n_final) // b > pad_copies:
        raise ValueError("a request larger than the padding")
    total = pad_final + pad_copies * b
    tokens = np.zeros(total, np.int32)
    positions = np.zeros(total, np.int32)
    # padding rows see only themselves: blocks and copies of their own
    block_of = np.arange(total, dtype=np.int32) + total
    copy_of = np.arange(total, dtype=np.int32) + total
    for name, arr in (("tokens", tokens), ("positions", positions),
                      ("block_of", block_of), ("copy_of", copy_of)):
        vals = np.asarray(rows[name], np.int32)
        arr[:n_final] = vals[:n_final]
        arr[pad_final:pad_final + n - n_final] = vals[n_final:]
    hidden = forward(params, spec, tokens, positions, block_of, copy_of,
                     precision=precision)
    final = np.zeros(pad_copies * b, np.int32)
    for c in rows["copies"]:
        at = c["row0"] - n_final
        final[at:at + b] = c["final"]
    return hidden[pad_final:], final


def row_stats(params, hidden, scored: np.ndarray, n: int, *,
              precision: str = "f32") -> Dict[str, np.ndarray]:
    """For each of the first `n` rows of `hidden`: the best logit, its
    index, its log-probability (the denoiser's confidence), and the logit
    of the token `scored` names for that row."""
    stats = _row_stats(hidden, params["ln_out"]["scale"],
                       params["lm_head"]["kernel"],
                       jnp.asarray(scored, jnp.int32), precision=precision)
    return {k: np.asarray(v)[:n] for k, v in stats.items()}


def served_rows(params, spec, rows: Dict[str, Any], *, pad_final: int,
                pad_copies: int, precision: str = "f32") -> Dict[str, np.ndarray]:
    """`row_stats` of `copy_rows`, scoring the token the request ended up
    with at each position (`scored`)."""
    hidden, final = copy_rows(params, spec, rows, pad_final=pad_final,
                              pad_copies=pad_copies, precision=precision)
    return row_stats(params, hidden, final,
                     len(rows["tokens"]) - rows["final_rows"],
                     precision=precision)
