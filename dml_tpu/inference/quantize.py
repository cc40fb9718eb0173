"""Weight-only int8 quantization for LM serving.

Autoregressive decode is HBM-bandwidth-bound: every token reads every
weight once and does almost no math per byte (inference/generate.py's
step is a chain of [B,1,d] matvecs). Storing the big matmul weights as
int8 with a per-output-channel float scale cuts the weight bytes
1.57x vs bf16 (2.9x vs f32) with no activation-calibration step;
accuracy loss is bounded by per-channel rounding (~0.4%).

What this buys: fewer weight bytes per decode step, which turns into
tokens a second only where XLA fuses the int8 read and the dequant
into the matvec (it has flipped across toolchains, and no cell of the
benchmark measures it: the benchmark uses this path as the control of
its `correct` check, not for speed). The capacity side is
deterministic: 1.33x less HBM than the bf16 tree end-to-end (the f32
embed dominates the remainder). `LongContextLM.generate` serves
bf16-cast weights by default and offers `quantize_weights=True`.

Scope: the 2-D matmul kernels of TransformerLM blocks (qkv, proj,
up, down, lm_head) and the stacked MoE expert tensors (w_up, w_down,
per-expert-and-channel scales). Embeddings, norms, and the router stay
float (tiny, or precision-sensitive). The quantized tree is a drop-in
params pytree for `generate`/`decode_step`/`prefill`: `kernel_of`
dequantizes at use.

A float tree stored wider than it is computed in is not cast at use by
a server: `resident_params` holds the same block matrices and expert
tensors in the compute dtype once, when an `LMServer` is built, so a
float32 checkpoint served in bfloat16 is rounded one time and not in
every program that multiplies it (`kernel_of`'s `astype` is then a
no-op). Quantized leaves stay as they are: an int8 tree is still
dequantized at use.

Net-new vs the reference (it serves f32 Keras CNNs on CPU,
models.py:23-71).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

# params keys quantized at each block level: 2-D kernels, the stacked
# tensors of an expert layer (w_gate: gated experts only), the head
_BLOCK_MATMULS = ("qkv", "proj", "up", "down", "gate", "q_a", "q_b", "kv_a",
                  "head_gate")
_EXPERT_MATMULS = ("w_up", "w_down", "w_gate")
# ... latent attention's up-projections, stacked over heads as the expert
# tensors are over experts ([H, in, out]: one scale a head and channel)
_HEAD_MATMULS = ("w_uk", "w_uv")
# ... the 2-D kernels an expert layer holds beside its experts (the latent
# projections, the shared expert) and a state-space mixer's two, which
# are a gated short convolution's two as well (`short_conv`)
_MOE_MATMULS = ("latent_down", "latent_up", "shared_up", "shared_down",
                "shared_gate")
_SSM_MATMULS = ("in_proj", "out_proj")
_TOP_MATMULS = ("lm_head",)


def _quant_tensor(w: jax.Array, keep_axes: Tuple[int, ...]) -> Dict[str, jax.Array]:
    """Symmetric int8 with one scale per index of `keep_axes` (the
    axes NOT reduced by abs-max). 2-D kernels keep the output axis;
    stacked MoE tensors keep (expert, output) so one outlier expert
    can't inflate every other expert's scale."""
    wf = w.astype(jnp.float32)
    keep = tuple(a % w.ndim for a in keep_axes)
    reduce_axes = tuple(i for i in range(w.ndim) if i not in keep)
    amax = jnp.max(jnp.abs(wf), axis=reduce_axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "scale": scale.astype(jnp.float32)}


def _dequant(t: Dict[str, jax.Array], dtype) -> jax.Array:
    return (t["q"].astype(jnp.float32) * t["scale"]).astype(dtype)


def _map_block_matmuls(params: Dict[str, Any], kernel_fn, expert_fn):
    """The same tree with `kernel_fn` applied to every block's 2-D
    matmul kernel (`_BLOCK_MATMULS`, an expert layer's `_MOE_MATMULS`,
    a state-space mixer's or a short convolution's `_SSM_MATMULS`) and
    `expert_fn` to every
    stacked tensor (`_EXPERT_MATMULS` over experts, `_HEAD_MATMULS`
    over heads): the leaves
    `_apply_block`, `expert_ffn` and `ssm_mixer` read through
    `kernel_of`. Everything else (embedding, norms, router, head, a
    state-space mixer's convolution and per-head vectors) is the
    caller's own object."""
    out: Dict[str, Any] = {}
    for name, sub in params.items():
        if not name.startswith("block_"):
            out[name] = sub
            continue
        blk: Dict[str, Any] = {}
        for k, v in sub.items():
            if k in _BLOCK_MATMULS:
                blk[k] = {**v, "kernel": kernel_fn(v["kernel"])}
            elif k in _HEAD_MATMULS:
                blk[k] = expert_fn(v)
            elif k == "moe":
                blk[k] = {**v, **{
                    w: expert_fn(v[w]) for w in _EXPERT_MATMULS if w in v
                }, **{
                    w: {**v[w], "kernel": kernel_fn(v[w]["kernel"])}
                    for w in _MOE_MATMULS if w in v}}
            elif k in ("ssm", "short_conv"):
                blk[k] = {**v, **{
                    w: {**v[w], "kernel": kernel_fn(v[w]["kernel"])}
                    for w in _SSM_MATMULS}}
            else:
                blk[k] = v
        out[name] = blk
    return out


def quantize_lm_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """TransformerLM params -> same-structure tree with the big matmul
    kernels replaced by {"q": int8, "scale": f32} pairs. Consumable by
    inference/generate.py (which dequantizes at use); training keeps
    the float tree."""
    out = _map_block_matmuls(
        params,
        lambda w: _quant_tensor(w, (-1,)),
        # per-(expert, out-channel) scales: [E, d, d_ff] keeps axes 0, 2
        lambda w: _quant_tensor(w, (0, 2)),
    )
    for name in _TOP_MATMULS:
        if name in out:
            out[name] = {**out[name], "kernel": _quant_tensor(
                out[name]["kernel"], (-1,))}
    return out


def resident_params(params: Dict[str, Any], dtype) -> Dict[str, Any]:
    """The tree a server keeps: every leaf that `kernel_of(node, dtype)`
    would cast on its way into a block's matmul (`_BLOCK_MATMULS`, the
    stacked expert tensors) held in `dtype` already, so the programs
    that take the tree multiply what they are handed and no dispatch
    makes a copy. The values are the roundings `astype` makes at use,
    made once.

    Left exactly as handed (the same arrays): the embedding, every
    norm, the router, `lm_head` (a float32 head multiplies in float32:
    `generate._lm_head`), every quantized leaf, and every leaf no wider
    than `dtype` (in it already, or stored narrower than the compute
    dtype: that one is widened at use as before, so the resident tree
    never takes more bytes than the handed one). A tree stored in the
    compute dtype, a float32 tree under float32 compute and an int8
    tree come back leaf for leaf. A cast leaf keeps the sharding it was
    placed with."""
    dtype = jnp.dtype(dtype)

    def hold(w):
        if is_quantized(w) or w.dtype.itemsize <= dtype.itemsize:
            return w
        return w.astype(dtype)

    return _map_block_matmuls(params, hold, hold)


def is_quantized(leaf: Any) -> bool:
    return (
        isinstance(leaf, dict) and "q" in leaf and "scale" in leaf
    )


def kernel_of(node: Any, dtype) -> jax.Array:
    """`node` is params["block_i"]["qkv"] (a {"kernel": ...} dict), a
    bare tensor (MoE w_up/w_down), or the quantized forms of either;
    returns the kernel in `dtype` regardless — the generate path's one
    weight-access point, so quantized and float trees serve
    identically. A quantized kernel is dequantized here, at use; a
    float one in another dtype is cast here (`generate` on a float32
    checkpoint). A server's tree is not narrowed here: `resident_params`
    has made that cast once and this `astype` returns its operand."""
    kern = (
        node["kernel"]
        if isinstance(node, dict) and "kernel" in node
        else node
    )
    if is_quantized(kern):
        return _dequant(kern, dtype)
    return kern.astype(dtype)


def quantized_bytes(params: Dict[str, Any]) -> Tuple[int, int]:
    """(bytes_now, bytes_float32_equivalent) across the whole tree —
    the serving-memory report for CLI/bench."""
    now = 0
    f32 = 0
    for leaf in jax.tree_util.tree_leaves(params):
        now += leaf.nbytes
        f32 += leaf.size * 4
    return now, f32
