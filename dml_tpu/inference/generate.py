"""Autoregressive decoding with a KV cache for TransformerLM.

Net-new vs the reference (SURVEY §0: no sequence models at all) — the
serving half of the framework's LM path. Written TPU-first:

- The prompt runs through `prefill`: ONE batched forward over
  [B, Tp] with the Pallas flash kernel doing causal attention (bf16
  MXU), filling the KV cache in a single pass — a 2k-token prompt
  costs one forward instead of 2k scanned steps.
- New tokens then run under ONE `lax.scan` of `decode_step` inside
  one jit; the chip never returns to the host between tokens.
  Per-step attention is one [B,H,1,T] f32 matvec against the cached
  keys — bandwidth-bound, exactly what HBM is for.
- The KV cache is a plain pytree argument (functional — no mutable
  module state), pre-allocated at `max_len` so every step has static
  shapes; decode masks positions beyond the current index instead of
  slicing dynamically.
- Prefill and decode share the same `_apply_block` layer body, so the
  two paths cannot drift; they differ only in the attention closure
  (flash kernel vs cache matvec) and therefore in attention precision
  (bf16 MXU vs f32 VPU).

The math mirrors `models/transformer.py` layer-for-layer and consumes
the SAME params tree (`TransformerLM.init(...)["params"]`), so
trained/published weights serve directly — including MoE blocks
(per-token top-2 routing, exact at serve time, chunked over tokens at
prefill so the dense-dispatch intermediate stays bounded).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..models.transformer import rope
from ..tracing import PARTS
from .quantize import kernel_of

RMS_EPS = 1e-6  # flax nn.RMSNorm default, as used by TransformerLM

#: what one layer of a `layer_pattern` can be (the letters of the
#: `nemotron_h` configs' `hybrid_override_pattern`): a Mamba-2
#: state-space mixer, an attention mixer, an expert feed-forward mixer
LAYER_KINDS = {"M": "state-space", "*": "attention", "E": "expert"}
ROUTER_SCORING = ("softmax", "sigmoid")
ACTIVATIONS = ("silu", "relu2")
#: what an attention layer caches a token: K and V rows of `KV x D`
#: ("grouped": multi-head, grouped-query, multi-query), or ONE row of a
#: compressed latent and a shared rope key ("latent": `LatentConfig`)
ATTENTION_KINDS = ("grouped", "latent")
#: which columns rope rotates together: the two halves of a head
#: (i, i + D/2), or neighbours (2i, 2i + 1)
ROPE_PAIRINGS = ("half", "interleaved")


def part(name: str):
    """`jax.named_scope(name)` for a part of the model (`tracing.PARTS`,
    the one table of them): what is written under it carries the name on
    its scope path, in the lowered text's debug info and in a profiler
    trace (`tracing.read_profile`). Metadata alone: no compiled program
    changes by it."""
    if name not in PARTS:
        raise ValueError(f"{name!r} is not in tracing.PARTS")
    return jax.named_scope(name)


@dataclass(frozen=True)
class SSMConfig:
    """A Mamba-2 mixer's sizes: `heads` x `head_dim` inner channels,
    `groups` groups of heads sharing one B and one C of `state`
    numbers, a causal depthwise convolution over the last
    `conv_kernel` positions, the prefill scan's `chunk`."""

    heads: int
    head_dim: int
    state: int
    groups: int = 1
    conv_kernel: int = 4
    chunk: int = 128

    def __post_init__(self):
        if min(self.heads, self.head_dim, self.state, self.groups,
               self.chunk) < 1 or self.conv_kernel < 2:
            raise ValueError(f"state-space sizes {self}")
        if self.heads % self.groups:
            raise ValueError(
                f"{self.groups} groups do not divide {self.heads} heads")

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: x, B and C."""
        return self.d_inner + 2 * self.groups * self.state

    @property
    def in_width(self) -> int:
        """Columns of `in_proj`: z | x B C | dt."""
        return self.d_inner + self.conv_width + self.heads


@dataclass(frozen=True)
class LatentConfig:
    """Latent attention's widths (multi-head latent attention, as the
    DeepSeek-V3 family publishes it): the query's low rank `q_rank`,
    the cached latent's `kv_rank`, and a head's three parts: `nope_dim`
    key columns that carry no position, `rope_dim` that rope rotates
    (ONE such key a token, shared by every head), `v_dim` values."""

    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int

    def __post_init__(self):
        if min(self.q_rank, self.kv_rank, self.nope_dim, self.v_dim) < 1 or (
                self.rope_dim < 2 or self.rope_dim % 2):
            raise ValueError(f"latent attention widths {self}")
        if self.v_dim > self.kv_rank:
            raise ValueError(
                f"v_dim {self.v_dim} over kv_rank {self.kv_rank}: the "
                f"absorbed form reads a head's values out of the latent")

    @property
    def key_width(self) -> int:
        """A head's query and key width in the expanded form."""
        return self.nope_dim + self.rope_dim

    @property
    def row_width(self) -> int:
        """Values a token caches a layer: the latent and the rope key."""
        return self.kv_rank + self.rope_dim

    @property
    def row_stride(self) -> int:
        """Columns a cached row takes: `row_width` filled up with zeros
        to whole tiles of 128 lanes. The chip lays a row out so whatever
        the program says (576 values take 640), and a leaf that says 576
        is given ANOTHER layout at a program's edge (rows along the
        lanes), which every dispatch then copies the whole grid into the
        kernel's and back (ahead-of-time compile, PR 36: two copies of
        3 GB a dispatch and 4.1 GB of temporaries). The leaf states the
        stride, so programs hand it on as it is."""
        return -(-self.row_width // 128) * 128


@dataclass(frozen=True)
class YarnConfig:
    """YaRN's numbers (arXiv:2309.00071, as the published configs'
    `rope_parameters` spell them): frequencies that turn fewer than
    `beta_slow` times over `original_max_position` positions are
    divided by `factor`, those that turn more than `beta_fast` times
    are kept, the ones between are blended by a linear ramp; cos and
    sin are multiplied by `attention_factor` (None: 0.1 ln(factor) +
    1)."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None

    def __post_init__(self):
        if self.factor < 1 or self.original_max_position < 1 or not (
                0 < self.beta_slow < self.beta_fast):
            raise ValueError(f"YaRN numbers {self}")


@dataclass(frozen=True)
class RopeConfig:
    """One rope: the base `theta`, the columns it rotates (the FIRST
    `rotary_dim` of a head, paired (i, i + rotary_dim / 2); None = the
    whole head; the others pass as projected) and YaRN's scaling of the
    frequencies, if any. `rope_table` has the numbers."""

    theta: float = 10000.0
    rotary_dim: Optional[int] = None
    yarn: Optional[YarnConfig] = None

    def __post_init__(self):
        r = self.rotary_dim
        if self.theta <= 0 or (r is not None and (r < 2 or r % 2)):
            raise ValueError(f"rope {self}")

    @property
    def plain(self) -> bool:
        """Whether this is `theta ** (-2i / D)` over the whole head:
        what `rope` and `rope_interleaved` compute from the base."""
        return self.rotary_dim is None and self.yarn is None


@functools.lru_cache(maxsize=None)
def rope_table(rope: RopeConfig, head_dim: int) -> Tuple[np.ndarray, float]:
    """(`rotary_dim / 2` frequencies in float32, the factor on cos and
    sin) of `rope` in a head of `head_dim` columns, computed once a
    (rope, head size) in float64. Plain: `f_i = theta ** (-2i / d)`
    over the d rotated columns, factor 1. YaRN: `c(r) = d ln(L / (2 pi
    r)) / (2 ln theta)` is the index of the frequency that turns r
    times over the L original positions; `low = floor(c(beta_fast))`,
    `high = ceil(c(beta_slow))`, clipped to [0, d / 2 - 1]; `ramp_i =
    clip((i - low) / (high - low), 0, 1)`; `freq_i = (f_i / factor)
    ramp_i + f_i (1 - ramp_i)`."""
    d = head_dim if rope.rotary_dim is None else rope.rotary_dim
    if d > head_dim:
        raise ValueError(f"{d} rotated columns in a head of {head_dim}")
    half = d // 2
    f = rope.theta ** (-np.arange(half, dtype=np.float64) / half)
    y = rope.yarn
    if y is None:
        return f.astype(np.float32), 1.0

    def turns(r):
        return d * math.log(y.original_max_position / (2 * math.pi * r)) / (
            2 * math.log(rope.theta))

    low = min(max(math.floor(turns(y.beta_fast)), 0), half - 1)
    high = min(max(math.ceil(turns(y.beta_slow)), 0), half - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    factor = (0.1 * math.log(y.factor) + 1.0 if y.attention_factor is None
              else y.attention_factor)
    return (f / y.factor * ramp + f * (1 - ramp)).astype(np.float32), factor


def rope_by_table(x: jax.Array, positions: jax.Array, freqs: np.ndarray,
                  factor: float = 1.0) -> jax.Array:
    """`rope` from a frequency table (`rope_table`): the first `2
    len(freqs)` columns of x [B, T, H, D] rotate, column i with column
    i + len(freqs), by `positions * freqs[i]`, cos and sin times
    `factor`; the columns past them pass. positions [T] or [B, T]."""
    half = len(freqs)
    angles = positions[..., None].astype(jnp.float32) * jnp.asarray(freqs)
    if positions.ndim == 1:
        angles = angles[None]
    cos = (jnp.cos(angles) * factor)[:, :, None, :]
    sin = (jnp.sin(angles) * factor)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, x[..., 2 * half:]],
        axis=-1).astype(x.dtype)


@dataclass(frozen=True)
class AttentionType:
    """What the layers of one type share. An attention type: the query
    heads (over the config's `kv_heads` of `head_dim`), the rope, the
    window (None: position i attends every j <= i, and a slot caches a
    row a token; W: i attends j iff 0 <= i - j < W, and a slot caches a
    RING of `W` rows, row p mod W holding position p: `init_cache`), and
    whether a sigmoid gate a head, a linear map of the layer's normed
    input (`head_gate`), scales the heads' outputs before `proj`.

    A type whose operator is no attention: `conv_kernel` K, the gated
    short convolution (`conv_mixer`) over the last K positions, in the
    attention's place in the classic block. It has no heads, rope,
    window or gate; a slot caches the K - 1 rows before its next
    position and no row a token."""

    n_heads: int = 0
    rope: RopeConfig = RopeConfig()
    window: Optional[int] = None
    gate: bool = False
    conv_kernel: Optional[int] = None

    def __post_init__(self):
        if self.conv_kernel is not None:
            if (self.conv_kernel < 2 or self.n_heads or self.gate
                    or self.window is not None):
                raise ValueError(f"convolution layer type {self}")
        elif self.n_heads < 1 or (
                self.window is not None and self.window < 1):
            raise ValueError(f"attention layer type {self}")


@dataclass(frozen=True)
class AttentionLayers:
    """A stack whose layers' operators differ by TYPE: the types by name
    and each layer's type, ONE description that `LMConfig.attn`
    answers "layer i's heads, rope, window, gate" (or "a convolution
    of K positions") from."""

    types: Tuple[Tuple[str, AttentionType], ...]
    layers: Tuple[str, ...]

    def __post_init__(self):
        names = [n for n, _ in self.types]
        if len(set(names)) != len(names) or set(self.layers) - set(names):
            raise ValueError(
                f"attention layers {self.layers} of types {names}")

    def of(self, i: int) -> AttentionType:
        return dict(self.types)[self.layers[i]]


@dataclass(frozen=True)
class LMConfig:
    """Shape config mirroring TransformerLM's fields.

    What a layer caches a token goes by its kind of attention
    (`ATTENTION_KINDS`): grouped (`latent` None), K and V rows of
    `kv_heads x head_dim` each (int8 with scales under `kv_quant`);
    latent (`latent` holds its widths), one row of `latent.row_width`
    values, the normalised latent and the roped shared key
    (`_latent_attention`), which both forms of that attention read. A
    state-space layer caches no rows (`init_cache`). Under
    `attention_layers` the grouped layers go by TYPE (`AttentionType`):
    a full layer caches a K and a V row a token as above, `max_len`
    rows a slot; a window layer the last `window` tokens' alone, a ring
    of that many rows a slot whatever `max_len` is; heads, rope and
    the output gate are the type's too (`attn`, `layer_rows`); a layer
    whose type is a gated short convolution caches its window's K - 1
    rows a slot (`conv`) and no row a token.

    `kv_quant=True` stores the KV cache as int8 with one f32 scale per
    (position, kv-head) — ~1.9x less cache HBM than bf16, i.e. ~2x the
    contexts/slots per chip: the Pallas decode kernel
    (ops/decode_attention.py) dequantizes inline while streaming the
    int8 cache through VMEM (on the XLA einsum path the dequant
    materializes in HBM, which is why the kernel owns this config on
    a TPU; no cell of the benchmark holds an int8 cache yet).
    Numerics: symmetric per-vector rounding on K and V (~0.4% each);
    greedy outputs can differ from the bf16-cache path on near-ties,
    so the serving stack treats kv_quant as a MODEL CONFIG, not a
    transparent switch (the batching-exactness contract holds within
    a config)."""

    vocab_size: int
    d_model: int
    n_heads: int
    n_layers: int
    d_ff: int
    dtype: Any = jnp.bfloat16
    n_kv_heads: Optional[int] = None  # GQA; None = MHA
    kv_quant: bool = False
    # Architecture as data (LMBackend.from_spec's `lm_spec` keys). The
    # defaults are the TransformerLM block every older spec describes.
    d_head: Optional[int] = None  # head size where H * D != d_model
    rope_theta: float = 10000.0
    qk_norm: bool = False  # per-head RMSNorm on q and k before rope
    # expert layers (a block whose tree holds "moe"): experts a token
    # is routed to, and the first routed expert this tree holds (its
    # count is the tree's own; guide section 4's "chip's share")
    experts_per_token: int = 2
    experts_first: int = 0
    # "causal", or "block_causal": position i attends j iff
    # j // block_length <= i // block_length
    attention_mask: str = "causal"
    block_length: int = 1
    # One mixer a layer, by kind (`LAYER_KINDS`), where a model is no
    # stack of "attention, then feed-forward" blocks; None = such a
    # stack. A pattern's block holds ONE norm (`ln`) and its mixer.
    layer_pattern: Optional[str] = None
    ssm: Optional[SSMConfig] = None
    rope: bool = True  # False: q and k are attended as projected
    norm_eps: float = RMS_EPS
    # the expert layers' router: "softmax" (top-k of the softmax,
    # renormalised) or "sigmoid" (top-k of sigmoid + the tree's
    # selection bias, gates the chosen sigmoids renormalised), gates
    # times `router_scale`; the feed-forward activation
    router_scoring: str = "softmax"
    router_scale: float = 1.0
    activation: str = "silu"
    latent: Optional[LatentConfig] = None  # latent attention's widths
    rope_pairing: str = "half"  # of `ROPE_PAIRINGS`
    # attention layers that differ by type (heads, rope, window, gate);
    # None = every layer `n_heads` heads, `rope_theta`, no window, no gate
    attention_layers: Optional[AttentionLayers] = None

    def __post_init__(self):
        if self.rope_pairing not in ROPE_PAIRINGS:
            raise ValueError(f"unknown rope pairing {self.rope_pairing!r}")
        al = self.attention_layers
        if al is not None:
            if len(al.layers) != self.n_layers:
                raise ValueError(
                    f"attention_layers names {len(al.layers)} layers, "
                    f"n_layers is {self.n_layers}")
            if (self.latent is not None or self.layer_pattern is not None
                    or self.attention_mask != "causal" or not self.rope
                    or self.rope_pairing != "half"):
                raise ValueError(
                    "attention layers by type are grouped attention under "
                    "the causal mask in a stack of classic blocks, rope in "
                    "halves: no latent attention, layer_pattern, "
                    "block_causal mask, interleaved pairing, and rope on")
            if self.has_ring and self.kv_quant:
                raise ValueError(
                    "a window layer's ring of rows is cached unquantized: "
                    "no kv_quant")
            for _, t in al.types:
                if t.conv_kernel is not None:
                    continue
                if t.n_heads % self.kv_heads:
                    raise ValueError(
                        f"{self.kv_heads} KV heads do not divide a layer "
                        f"type's {t.n_heads} query heads")
                rope_table(t.rope, self.head_dim)  # raises on its widths
        if self.latent is not None and (
                self.kv_quant or self.qk_norm or self.n_kv_heads is not None
                or self.d_head is not None or self.layer_pattern is not None
                or self.attention_mask != "causal" or not self.rope):
            raise ValueError(
                "latent attention caches one bf16/f32 row a token under "
                "the causal mask in a stack of classic blocks: no "
                "kv_quant, qk_norm, n_kv_heads, d_head, layer_pattern, "
                "block_causal mask, and rope on")
        kv = self.n_kv_heads
        if kv is not None and (kv <= 0 or self.n_heads % kv):
            raise ValueError(
                f"n_kv_heads {kv} must be positive and divide "
                f"n_heads {self.n_heads}"
            )
        if self.attention_mask not in ("causal", "block_causal"):
            raise ValueError(
                f"unknown attention_mask {self.attention_mask!r}")
        if self.block_length < 1 or (
            self.attention_mask == "causal" and self.block_length != 1
        ):
            raise ValueError(
                f"block_length {self.block_length} under "
                f"{self.attention_mask!r} attention")
        if self.router_scoring not in ROUTER_SCORING:
            raise ValueError(f"unknown router scoring {self.router_scoring!r}")
        if self.activation not in ACTIVATIONS:
            raise ValueError(f"unknown activation {self.activation!r}")
        pat = self.layer_pattern
        if pat is not None:
            if not pat or set(pat) - set(LAYER_KINDS):
                raise ValueError(
                    f"layer_pattern {pat!r}: one of {sorted(LAYER_KINDS)} "
                    f"a layer")
            if len(pat) != self.n_layers:
                raise ValueError(
                    f"layer_pattern {pat!r} has {len(pat)} layers, "
                    f"n_layers is {self.n_layers}")
        if ("M" in (pat or "")) != (self.ssm is not None):
            raise ValueError(
                "state-space sizes (`ssm`) come with a layer_pattern "
                "that holds a state-space layer, and only with one")

    @property
    def kinds(self) -> Tuple[Optional[str], ...]:
        """Each layer's kind (`LAYER_KINDS`); None = a classic block."""
        return tuple(self.layer_pattern or (None,) * self.n_layers)

    @property
    def has_state(self) -> bool:
        """Whether some layer carries state that is no row a token:
        state that cannot be cut by token or rolled back (a state-space
        layer's scan state and convolution window; a gated short
        convolution's window, scan or no scan)."""
        return "M" in (self.layer_pattern or "") or self.has_conv

    @property
    def has_conv(self) -> bool:
        """Whether some layer's type is the gated short convolution."""
        al = self.attention_layers
        return al is not None and any(
            al.of(i).conv_kernel is not None for i in range(self.n_layers))

    @property
    def has_ring(self) -> bool:
        """Whether some layer caches a ring of its window's rows: state
        that is no row a token either (a ring cannot be cut by token
        from its start, nor rolled back past what it overwrote)."""
        al = self.attention_layers
        return al is not None and any(
            al.of(i).window is not None for i in range(self.n_layers))

    def attn(self, i: int) -> AttentionType:
        """Layer i's type: its heads, rope, window and gate (or its
        convolution, where that is its operator)."""
        if self.attention_layers is None:
            return AttentionType(self.n_heads, RopeConfig(self.rope_theta))
        return self.attention_layers.of(i)

    def layer_rows(self, i: int, max_len: int) -> int:
        """Cache rows a slot holds in attention layer i: the ring of a
        window layer (`window` rows: position p lives in row p mod
        window, so the window's 512 are there and no other; a window of
        `max_len` and more is a full layer's plane), else `max_len`;
        none in a layer whose operator is a convolution."""
        t = self.attn(i)
        if t.conv_kernel is not None:
            return 0
        return max_len if t.window is None else min(t.window, max_len)

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def q_width(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def mask_block(self) -> int:
        """The attention mask as one number: 1 = causal, B = causal
        across blocks of B and full inside one."""
        return self.block_length if self.attention_mask == "block_causal" else 1

    @property
    def kv_heads(self) -> int:
        return self.n_heads if self.n_kv_heads is None else self.n_kv_heads

    @property
    def kv_pack(self) -> int:
        """KV heads whose rows one cached row holds side by side: heads
        narrower than the chip's 128 lanes that fill them exactly (two of
        64) share a row of a [B, KV / r, T, r D] plane. The chip lays a
        64-column plane out in 128 whatever the program says (a grid
        twice its bytes, or a copy of it a dispatch: ahead-of-time
        compile, PR 44; `LatentConfig.row_stride` met the same), so the
        leaf states what fills a tile (`pack_rows`, `packed_attention`).
        Only in a stack whose layers go by type, whose rows nothing but
        `prefill`, `batched_decode_step` and a server's inserts touch
        (what cuts, ships or re-attends rows by head refuses such a
        stack), and not over a ring or an int8 cache; 1 everywhere
        else."""
        d = self.head_dim
        if (d >= 128 or 128 % d or self.kv_heads % (128 // d)
                or self.attention_layers is None or self.has_ring
                or self.kv_quant):
            return 1
        return 128 // d


#: every leaf a layer of a slot-grid cache can hold: its kind
#: (`state_bytes`) and the axis its rows run along (None: state that
#: has no rows). The one place that knows; an unknown leaf is an error.
CACHE_LEAVES = {
    "k": ("kv", 2), "v": ("kv", 2), "k_q": ("kv", 2), "v_q": ("kv", 2),
    "k_s": ("kv", 3), "v_s": ("kv", 3), "latent": ("latent", 2),
    # a window layer's ring: its rows are no row a token (row r holds
    # the newest position p with p mod rows == r), so it has leaves of
    # its own and nothing that cuts rows by token takes it for a plane
    "k_ring": ("kv_window", 2), "v_ring": ("kv_window", 2),
    "conv": ("conv", None), "ssm": ("scan", None),
}


def init_cache(cfg: LMConfig, batch: int, max_len: int) -> Dict[str, Any]:
    """Pre-allocated cache, by the layer's kind of attention. Grouped
    attention: one [B, KV, max_len, D] pair per layer
    — KV = n_kv_heads under GQA, so the cache (and each decode step's
    HBM reads of it) shrinks n_heads/n_kv_heads-fold. Under
    `cfg.kv_quant` each tensor is int8 plus a [B, KV, max_len, 1] f32
    scale (symmetric per-(position, head) quantization). Latent
    attention: ONE leaf a layer, `latent` [B, 1, max_len, row_stride]
    (the normalised latent | the roped shared key | zeros to whole
    lane tiles: `LatentConfig`), in the layout of a single-KV-head
    plane so that everything that cuts, copies or streams rows treats
    it as one. Heads narrower than a lane tile, in a stack of typed
    layers: [B, KV / r, max_len, r D], r heads' rows side by side
    (`LMConfig.kv_pack`). A WINDOW layer (`LMConfig.attention_layers`): `k_ring`
    and `v_ring` [B, KV, W, D], a ring of the window's W rows a slot
    whatever `max_len` is (`LMConfig.layer_rows`): position p is
    written at row p mod W, over position p - W, which no later query
    attends; the rows a slot at length n holds are its last min(n, W)
    positions, in ring order (softmax asks no order of its keys, and a
    key carries its rope).

    Layout is head-major ([B, KV, T, D], not [B, T, KV, D]): each
    head's rows are a contiguous [T, D] plane, which is what the
    Pallas decode kernel streams block-by-block (ops/
    decode_attention.py — Mosaic wants the blocked axes last) and
    makes every per-step cache write one contiguous D-row per head.
    Scales live time-on-lanes ([B, KV, 1, max_len]) because the
    kernel folds them into [G, T-block] score rows — storing them
    that way saves a per-step transpose of every scale plane.

    Under a `layer_pattern` a slot's state goes by the layer's kind:
    K/V rows in an attention layer; in a state-space layer the last
    `conv_kernel - 1` rows of the convolution's input ([B, K-1, C], the
    model's dtype) and the scan state ([B, H, P, N], float32: the
    recurrence runs for as many steps as a sequence has tokens); nothing
    in an expert layer, which has no entry. A layer whose TYPE is the
    gated short convolution (`AttentionType.conv_kernel` K) holds the
    `conv` leaf alone, the last K - 1 rows of the convolution's input
    [B, K-1, d], beside other layers' rows."""
    shape = (batch, cfg.kv_heads // cfg.kv_pack, max_len,
             cfg.kv_pack * cfg.head_dim)
    sshape = (batch, cfg.kv_heads, 1, max_len)

    def layer(i, kind):
        typ = cfg.attn(i)
        if typ.conv_kernel is not None and kind is None:
            return {"conv": jnp.zeros(
                (batch, typ.conv_kernel - 1, cfg.d_model), cfg.dtype)}
        if typ.window is not None and kind is None:
            ring = (batch, cfg.kv_heads, cfg.layer_rows(i, max_len),
                    cfg.head_dim)
            return {"k_ring": jnp.zeros(ring, cfg.dtype),
                    "v_ring": jnp.zeros(ring, cfg.dtype)}
        if cfg.latent is not None:
            return {"latent": jnp.zeros(
                (batch, 1, max_len, cfg.latent.row_stride), cfg.dtype)}
        if kind == "E":  # an expert layer carries nothing
            return None
        if kind == "M":
            s = cfg.ssm
            return {
                "conv": jnp.zeros(
                    (batch, s.conv_kernel - 1, s.conv_width), cfg.dtype),
                "ssm": jnp.zeros(
                    (batch, s.heads, s.head_dim, s.state), jnp.float32),
            }
        if cfg.kv_quant:
            return {
                "k_q": jnp.zeros(shape, jnp.int8),
                "k_s": jnp.zeros(sshape, jnp.float32),
                "v_q": jnp.zeros(shape, jnp.int8),
                "v_s": jnp.zeros(sshape, jnp.float32),
            }
        return {
            "k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype),
        }

    layers = {f"block_{i}": layer(i, k) for i, k in enumerate(cfg.kinds)}
    return {name: lay for name, lay in layers.items() if lay is not None}


def cache_rows(cache: Dict[str, Any], name: Optional[str] = None) -> int:
    """Rows a slot holds in layer `name` of a cache (`max_len`, or a
    window layer's ring), read off its first leaf that has rows
    (`CACHE_LEAVES`). Without a name: the most any layer holds, which
    is `max_len` wherever a layer caches a row a token (a stack of
    window layers alone holds no more than its rings); 0 for a cache
    of state alone."""
    def rows(lay):
        for key, leaf in lay.items():
            axis = CACHE_LEAVES[key][1]
            if axis is not None:
                return leaf.shape[axis]
        return 0

    if name is not None:
        return rows(cache[name])
    return max((rows(lay) for lay in cache.values()), default=0)


def state_bytes(cache: Dict[str, Any]) -> Dict[str, int]:
    """Bytes of a slot-grid cache by kind of leaf: `kv` (grouped
    attention's rows and scales, a row a token), `kv_window` (the
    window layers' rings, which do not grow with `max_len`), `latent`
    (latent attention's rows), `conv` and `scan` (a state-space layer's
    convolution window and recurrent state; a gated short convolution's
    window is `conv` too)."""
    out = {"kv": 0, "kv_window": 0, "latent": 0, "conv": 0, "scan": 0}
    for lay in cache.values():
        for key, leaf in lay.items():
            out[CACHE_LEAVES[key][0]] += int(leaf.size) * leaf.dtype.itemsize
    return out


def _kv_quantize(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """[..., D] -> (int8 values, f32 scale over the last axis)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _kv_dequant(q: jax.Array, scale: jax.Array) -> jax.Array:
    """int8 + scale -> f32 — the EINSUM-path read side only (the CPU /
    test mesh). XLA materializes this dequant in HBM before the
    attention contraction, which the Pallas kernel the TPU path uses
    does not (inline dequant in VMEM)."""
    return q.astype(jnp.float32) * scale


def _rms_norm(x: jax.Array, scale: jax.Array, dtype,
              eps: float = RMS_EPS) -> jax.Array:
    # flax RMSNorm: reduce in f32, scale, cast back to module dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype)


def _activation(name: str):
    """A feed-forward activation by its `lm_spec` name."""
    if name == "relu2":
        return lambda x: jnp.square(jax.nn.relu(x))
    return jax.nn.silu


_MOE_CHUNK = 4096  # tokens per expert-layer chunk at prefill, at most
#: ... and (token, expert) assignments a chunk. Over all tokens x k are
#: laid out the routing's INDICES alone (`top_k`'s choices and gates, the
#: sort by expert, the counts: 4-byte elements); the sorted copies, the
#: grouped matmuls' float32 outputs, activations and gate products are
#: [window, width], over `moe_window`'s rows of the HELD assignments. Where
#: every expert is held the one window is the whole chunk (32,768 rows at
#: 4,096 tokens and top-8; 1,408 tokens at top-22), which this bounds
_MOE_ROWS = 32768


def moe_window(n: int, k: int, e: int, held: int) -> int:
    """The rows `expert_ffn` lays its per-assignment work out over, for
    `n` tokens top-`k` of `e` routed experts with `held` of them here:
    the held assignments a uniform routing gives, `n k held / e`, and a
    quarter more, in whole tiles of 128 rows. Windows of that many rows
    are run until the held assignments are through, so the number is a
    size and never a capacity. Where it reaches `n k` (every expert is
    held, or all the assignments are one tile) the one window is all
    the rows, in no loop."""
    rows = n * k
    if held >= e:
        return rows
    want = -(-rows * held * 5 // (e * 4))
    return min(-(-want // 128) * 128, rows)


def moe_layout(n: int, k: int, e: int, held: int) -> Tuple[int, int, int]:
    """(chunks, tokens a chunk, `moe_window` of a chunk) of an
    `expert_ffn` call over `n` tokens: what the call lays out, from
    shapes alone (a server's span labels are this arithmetic)."""
    chunk = min(_MOE_CHUNK, max(128, _MOE_ROWS // k // 128 * 128))
    chunks = 1 if n <= chunk else -(-n // chunk)
    per = n if chunks == 1 else chunk
    return chunks, per, moe_window(per, k, e, held)


def uses_grouped_kernel(mesh: Optional[Mesh] = None) -> bool:
    """Whether `expert_ffn`'s grouped matmuls go to the Pallas kernel
    (`_grouped_matmul`): on one TPU. Elsewhere, and under a mesh (GSPMD
    cannot partition a Mosaic call), they are `jax.lax.ragged_dot`."""
    return jax.default_backend() == "tpu" and mesh is None


def _tile(dim: int, cap: int = 2048) -> int:
    """The largest multiple of 128 that divides `dim`, up to `cap`;
    the whole of a dimension that has none."""
    best = [t for t in range(128, min(dim, cap) + 1, 128) if dim % t == 0]
    return best[-1] if best else dim


def _grouped_matmul(x: jax.Array, w: jax.Array, sizes: jax.Array,
                    mesh: Optional[Mesh] = None) -> jax.Array:
    """x [m, k] (rows grouped by expert) @ w [E, k, n] -> [m, n] f32:
    rows of group e against w[e]; rows past the last group come back as
    whatever they were (the caller selects them away).

    On one TPU this is jax's own Pallas grouped matmul (`megablox.gmm`)
    with tiles of 128 rows and the whole of k and n (up to 2,048 each):
    a tile visit loads one expert's [k, n] panel once, so a decode-sized
    call is bound by the weights of the experts touched. Its grid runs
    over the ACTIVE row tiles alone (`num_active_tiles`, a traced scalar
    it reckons from `sizes`): tiles past the last group are never
    visited, so a call costs what its groups' rows cost whatever `m` is;
    what the `m` rows cost is the caller's arrays around it. `ragged_dot`
    computes the same thing everywhere else (the tests' oracle) and was
    the first form on the TPU too, where the compiler lowers it to a
    grouped-matmul call of its own. Measured on one TPU v5e (my chip
    run, PR 28), 1,024 assignment rows over 125 of 128 experts of
    2,048 x 768 in bfloat16, ms a matmul: `ragged_dot` 1.44 (up) / 1.40
    (down); `gmm` 0.57 / 0.57 at tiles of 128 rows (0.58 at 32, 0.61 at
    16 and 64; 0.63-0.87 with k cut to 512), against 0.48 for the
    touched weights at 819 GB/s. A layer's three matmuls were 4.3 of
    the expert layer's 4.44 ms (routing, sort and gathers: 0.05 ms), and
    the six layers 27 of a forward's 31 ms; with `gmm` the layer is 1.77
    ms. At a prefill chunk's 32,768 rows: `ragged_dot` 3.15 ms, `gmm`
    1.62 at tiles of 128 rows (1.58 at 256, 2.05 at 512)."""
    if not uses_grouped_kernel(mesh):
        return jax.lax.ragged_dot(
            x, w, sizes, preferred_element_type=jnp.float32)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    m, k = x.shape
    n = w.shape[-1]
    pad = (-m) % 128
    out = gmm(jnp.pad(x, ((0, pad), (0, 0))) if pad else x, w, sizes,
              jnp.float32, (128, _tile(k), _tile(n)))
    return out[:m] if pad else out


def expert_ffn(
    moe: Dict[str, Any],
    y: jax.Array,  # [B, T, d]
    dtype,
    k: int = 2,
    first: int = 0,
    live: Optional[jax.Array] = None,  # [B] bool: rows that count
    mesh: Optional[Mesh] = None,
    scoring: str = "softmax",
    scale: float = 1.0,
    activation: str = "silu",
    windows: Optional[List[jax.Array]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """The serve-time expert layer: dropless top-`k` routing over ALL
    the routed experts, computed for the experts this tree HOLDS.

    Route, in float32 over the router's E outputs: `scoring` "softmax"
    takes the `k` largest of the softmax and renormalises them to sum
    to 1; "sigmoid" takes the `k` experts with the largest sigmoid
    plus the tree's selection bias (`router.bias`, for the choice
    only) and renormalises the chosen sigmoids; either way times
    `scale`. No capacity, so no dropped token (training-time capacity
    drops are a batching artifact, not part of the learned function).
    Compute: the n * k
    (token, expert) assignments are sorted by expert, the absent
    experts' last, so the held ones are the sort's first positions;
    their rows run through ONE grouped matmul a matrix
    (`_grouped_matmul`: row groups of the sorted tokens against the
    stacked expert weights), so the FLOPs are those of the experts
    chosen and the weights read are those of the experts touched.
    Experts are gated
    (`w_gate`: down(act(gate x) * up x)) or plain (down(act(up x)),
    parallel/moe.py's MoEMLP), by what the tree holds, `activation`
    SiLU or squared ReLU. Where the tree holds `latent_down` and
    `latent_up` the experts live in a latent width between the two
    (x -> latent, experts, -> hidden; the router still reads x);
    where it holds `shared_up` and `shared_down`, an expert every
    token takes is added in the hidden width (gated where the tree
    holds `shared_gate` too, plain otherwise).

    The tree holds `held = w_up.shape[0]` experts: routed experts
    `first .. first + held - 1`. Assignments to the others add
    nothing here, in the program and in the reference alike (on a
    deployment they are another chip's part of the sum; the latent
    up-projection is linear, so the shares still add up).

    What is laid out over all n * k assignments is INDICES (4-byte
    elements: the choices and gates, the sort, the counts). The sorted
    copies of the tokens, the grouped matmuls' float32 outputs, the
    activations and the gate products are [window, width], a window
    being `moe_window`'s rows of the held positions; a window's rows
    are added into the tokens' [n, width] float32 sums (a row
    scatter-add), cast once to `dtype`. As many windows run as the
    held rows need, under a loop whose trip count is read from the
    routing: whatever the routing, every held assignment is computed.
    Where the window is all n * k rows (every expert held; one tile of
    assignments) there is no loop, and the rows go back to their
    tokens by the sort's inverse and a sum over k.

    Returns (out [B, T, d], counts [E] int32: assignments to each
    routed expert from the rows `live` marks, all rows by default). A
    call that loops appends to `windows`, where the caller hands a
    list, the windows it ran past its chunks' first (int32 scalar).
    Token runs over the chunk (`_MOE_CHUNK` tokens, fewer where `k` is
    large: `_MOE_ROWS` assignments) go through a `lax.map`, so the
    index arrays stay bounded at prefill (`moe_layout`)."""
    router = moe["router"]["kernel"]
    bias = moe["router"].get("bias")
    e = router.shape[-1]
    gated = "w_gate" in moe
    w_up = kernel_of(moe["w_up"], dtype)
    w_gate = kernel_of(moe["w_gate"], dtype) if gated else None
    w_down = kernel_of(moe["w_down"], dtype)
    held = w_up.shape[0]
    if not 0 < k <= e or first < 0 or first + held > e:
        raise ValueError(
            f"experts {first}..{first + held - 1} top-{k} of {e} routed")
    if scoring not in ROUTER_SCORING:
        raise ValueError(f"unknown router scoring {scoring!r}")
    act = _activation(activation)
    latent = "latent_down" in moe
    shared = "shared_up" in moe
    shared_gated = "shared_gate" in moe

    grouped = functools.partial(_grouped_matmul, mesh=mesh)

    def run(args):  # tok [n, d], counted [n] -> out [n, d], counts [E]
        tok, counted = args
        n = tok.shape[0]
        window = moe_window(n, k, e, held)
        looped = window < n * k  # else ONE window of all the rows
        with part("moe_route"):
            logits = tok.astype(jnp.float32) @ router.astype(jnp.float32)
            if scoring == "softmax":
                gates = jax.nn.softmax(logits, axis=-1)
                top_g, top_i = jax.lax.top_k(gates, k)  # [n, k]
            else:
                gates = jax.nn.sigmoid(logits)
                _, top_i = jax.lax.top_k(
                    gates if bias is None else gates + bias, k)
                top_g = jnp.take_along_axis(gates, top_i, axis=-1)
            top_g = top_g / jnp.maximum(top_g.sum(-1, keepdims=True), 1e-9)
            if scale != 1.0:
                top_g = top_g * scale

        def tally(idx, weights, bins):
            # weights summed by idx, by compare-and-sum: the serial
            # scatter-add of 32,768 updates is 0.29 ms on a TPU v5e, this
            # 0.0x (PERF.md section 6, PR 37). The one-window programs
            # keep the scatter, and so the text, they had
            return jnp.sum(jnp.where(
                idx[:, None] == jnp.arange(bins, dtype=idx.dtype),
                weights[:, None], 0), axis=0)

        with part("moe_route"):
            if looped:
                counts = tally(top_i.reshape(-1),
                               jnp.repeat(counted.astype(jnp.int32), k), e)
            else:
                counts = jnp.zeros(e, jnp.int32).at[top_i.reshape(-1)].add(
                    jnp.repeat(counted.astype(jnp.int32), k))
            local = top_i.reshape(-1) - first
            here = (local >= 0) & (local < held)
            key = jnp.where(here, local, held)  # absent experts sort last
            order = jnp.argsort(key)
            if looped:
                sizes = tally(key, jnp.ones_like(key), held)
            else:
                sizes = jnp.zeros(held + 1, jnp.int32).at[key].add(1)[:held]
        with part("moe_experts"):
            src = (tok @ kernel_of(moe["latent_down"], dtype) if latent
                   else tok)

        def experts(xs, sizes):  # rows grouped by expert -> f32 rows
            h = grouped(xs, w_up, sizes)
            if gated:
                h = act(grouped(xs, w_gate, sizes)) * h
            else:
                h = act(h)
            return grouped(h.astype(dtype), w_down, sizes)

        def weighed(o, g):
            # rows past the last group hold nothing that counts: select,
            # so that whatever they read as cannot leak
            return jnp.where(g[:, None] > 0.0, o * g[:, None], 0.0)

        if not looped:
            with part("moe_experts"):
                o = experts(src[order // k], sizes)  # [n * k, d] f32
                o = weighed(
                    o, jnp.where(here, top_g.reshape(-1), 0.0)[order])
                back = jnp.zeros(n * k, jnp.int32).at[order].set(
                    jnp.arange(n * k, dtype=jnp.int32))
                out = o[back].reshape(n, k, -1).sum(1).astype(dtype)
            further = ()
        else:
            # the held assignments are the sort's first `ends[-1]`
            # positions: window w takes positions [w window, (w + 1)
            # window) of them, its groups the experts' runs clipped to it
            with part("moe_route"):
                ends = jnp.cumsum(sizes)
                trips = jnp.maximum(-(-ends[-1] // window), 1)
                order = jnp.pad(order, (0, -(n * k) % window))
                row = jnp.arange(window, dtype=jnp.int32)

            def one(w, acc):
                lo = w * window
                at = jax.lax.dynamic_slice(order, (lo,), (window,))
                of = at // k  # the rows' tokens
                o = experts(src[of], jnp.clip(ends - lo, 0, window)
                            - jnp.clip(ends - sizes - lo, 0, window))
                g = jnp.where(lo + row < ends[-1], top_g.reshape(-1)[at], 0.0)
                return acc.at[of].add(weighed(o, g))

            with part("moe_experts"):
                out = jax.lax.fori_loop(0, trips, one, jnp.zeros(
                    (n, w_down.shape[-1]), jnp.float32)).astype(dtype)
            further = (trips - 1,)
        if latent:
            with part("moe_experts"):
                out = out @ kernel_of(moe["latent_up"], dtype)
        if shared:
            with part("moe_shared"):
                up = tok @ kernel_of(moe["shared_up"], dtype)
                mid = (act(tok @ kernel_of(moe["shared_gate"], dtype)) * up
                       if shared_gated else act(up))
                out = out + mid @ kernel_of(moe["shared_down"], dtype)
        return (out, counts) + further

    d = y.shape[-1]
    tok = y.reshape(-1, d)
    n = tok.shape[0]
    counted = jnp.ones(n, bool) if live is None else jnp.repeat(
        live, n // live.shape[0])
    chunks, chunk, _ = moe_layout(n, k, e, held)
    if chunks == 1:
        out, counts, *further = run((tok, counted))
        out = out.reshape(y.shape)
    else:
        pad = (-n) % chunk
        out, counts, *further = jax.lax.map(run, (
            jnp.pad(tok, ((0, pad), (0, 0))).reshape(-1, chunk, d),
            jnp.pad(counted, (0, pad)).reshape(-1, chunk),
        ))
        out, counts = out.reshape(-1, d)[:n].reshape(y.shape), counts.sum(0)
    if windows is not None and further:
        windows.append(further[0].sum())
    return out, counts


def _moe_ffn(moe: Dict[str, Any], y: jax.Array, dtype, k: int = 2,
             first: int = 0) -> jax.Array:
    """`expert_ffn`'s output alone. The defaults are parallel/moe.py's
    MoEMLP at serve time (top-2, every expert held), for a tree
    trained there."""
    return expert_ffn(moe, y, dtype, k, first)[0]


def ssm_scan_chunked(
    x: jax.Array,  # [B, T, H, P]
    dt: jax.Array,  # [B, T, H] float32 step sizes; 0 = a position to skip
    a: jax.Array,  # [H] float32, negative
    bm: jax.Array,  # [B, T, G, N]
    cm: jax.Array,  # [B, T, G, N]
    h0: jax.Array,  # [B, H, P, N] float32
    chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """The Mamba-2 recurrence `h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x)
    B_t`, `y_t = h_t C_t` over T positions, chunk-wise: inside a chunk
    of `chunk` positions by matrix products under the decay mask
    (`y_i += sum_{j<=i} (C_i . B_j) exp(sum_{j<m<=i} dt_m a) dt_j x_j`),
    across chunks by carrying `h` (a `lax.scan` over the chunks'
    summed states). Head h reads group `h // (H / G)`'s B and C. The
    products take their operands in `x`'s dtype and accumulate in
    float32; decays, cumulative sums and the carried state are
    float32. Returns (y [B, T, H, P] float32, h_T [B, H, P, N]).

    A position with dt = 0 leaves the state exactly as it was (decay
    1, input 0), which is how a row padded to a bucket keeps the state
    of its own length, and how T is padded to whole chunks here."""
    b, t, hh, p = x.shape
    g, n = bm.shape[2:]
    r = hh // g
    f32 = jnp.float32
    pad = (-t) % chunk
    if pad:
        x, dt, bm, cm = (
            jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, bm, cm))
    c = (t + pad) // chunk
    x = x.reshape(b, c, chunk, g, r, p)
    bm = bm.reshape(b, c, chunk, g, n)
    cm = cm.reshape(b, c, chunk, g, n)
    dt = dt.reshape(b, c, chunk, g, r)
    cum = jnp.cumsum(dt * a.reshape(g, r), axis=2)  # [B, C, L, G, R]
    xdt = x * dt[..., None].astype(x.dtype)
    # inside a chunk: position l takes from s <= l
    scores = jnp.einsum("bclgn,bcsgn->bcgls", cm, bm,
                        preferred_element_type=f32)
    seg = cum[:, :, :, None] - cum[:, :, None, :]  # [B, C, L, S, G, R]
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]
    decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    w = scores[..., None].transpose(0, 1, 3, 4, 2, 5) * decay
    y = jnp.einsum("bclsgr,bcsgrp->bclgrp", w.astype(x.dtype), xdt,
                   preferred_element_type=f32)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[:, :, -1:] - cum)  # [B, C, L, G, R]
    states = jnp.einsum(
        "bclgn,bclgrp->bcgrpn", bm, xdt * to_end[..., None].astype(x.dtype),
        preferred_element_type=f32)
    whole = jnp.exp(cum[:, :, -1])  # [B, C, G, R]: a chunk's total decay

    def carry(h, args):  # h: the state at a chunk's start
        dec, st = args
        return h * dec[..., None, None] + st, h

    h_end, starts = jax.lax.scan(
        carry, h0.reshape(b, g, r, p, n),
        (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(states, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)  # [B, C, G, R, P, N]
    y = y + jnp.einsum(
        "bclgn,bcgrpn->bclgrp", cm, starts.astype(x.dtype),
        preferred_element_type=f32) * jnp.exp(cum)[..., None]
    return (y.reshape(b, t + pad, hh, p)[:, :t],
            h_end.reshape(b, hh, p, n))


def ssm_scan_step(x, dt, a, bm, cm, h):
    """One position of `ssm_scan_chunked`'s recurrence, elementwise in
    float32 over the state: x [B, H, P], dt [B, H], bm and cm
    [B, G, N], h [B, H, P, N] -> (y [B, H, P], h')."""
    b, hh, p = x.shape
    g, n = bm.shape[1:]
    f32 = jnp.float32
    bh = jnp.repeat(bm.astype(f32), hh // g, axis=1)  # [B, H, N]
    ch = jnp.repeat(cm.astype(f32), hh // g, axis=1)
    h = h * jnp.exp(dt * a)[..., None, None] + (
        (dt[..., None] * x.astype(f32))[..., None] * bh[:, :, None, :])
    return jnp.sum(h * ch[:, :, None, :], axis=-1), h


def causal_conv(
    x: jax.Array,  # [B, T, C]: the convolution's input
    kernel: jax.Array,  # [K, C], tap K - 1 on the current position
    state: Optional[jax.Array] = None,  # [B, K-1, C]: the rows before x
    lengths: Optional[jax.Array] = None,  # [B] int32: rows' own lengths
    bias: Optional[jax.Array] = None,  # [C]
    activation=None,
) -> Tuple[jax.Array, jax.Array]:
    """The causal depthwise convolution both stateful mixers share:
    `c_t = bias + sum_i kernel[i] x_{t - (K-1) + i}` a channel, in
    float32, through `activation` where one is given; positions before
    the sequence's start are `state` (zeros without one). Returns (c
    [B, T, C] float32, the window [B, K-1, C] in x's dtype: the last K
    - 1 rows of the input, what the next call is handed as `state`).
    With `lengths` the window is taken at row b's OWN length,
    positions lengths[b] - (K-1) .. lengths[b] - 1 (zeros or `state`
    on the left of a row shorter than the window), so a row padded to
    a bucket hands back the window of the unpadded row."""
    b, t, c = x.shape
    kk = kernel.shape[0]
    f32 = jnp.float32
    left = (jnp.zeros((b, kk - 1, c), x.dtype) if state is None
            else state.astype(x.dtype))
    full = jnp.concatenate([left, x], axis=1)  # [B, K-1+T, C]
    w = kernel.astype(f32)
    conv = sum(full[:, i:i + t].astype(f32) * w[i] for i in range(kk))
    if bias is not None:
        conv = bias.astype(f32) + conv
    if activation is not None:
        conv = activation(conv)
    if lengths is None:
        window = full[:, t:]
    else:
        window = jax.vmap(
            lambda row, n: jax.lax.dynamic_slice_in_dim(row, n, kk - 1, 0)
        )(full, lengths.astype(jnp.int32))
    return conv, window


def conv_mixer(
    p: Dict[str, Any],  # a block's "short_conv" subtree
    cfg: LMConfig,
    y: jax.Array,  # [B, T, d], normalised
    state: Optional[Dict[str, jax.Array]] = None,
    lengths: Optional[jax.Array] = None,  # [B] int32: rows' own lengths
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The gated short convolution (the LFM2 family's operator): `[B |
    C | X] = in_proj y`, thirds of `d` columns in that order; `z = B *
    X`; `c = causal_conv(z)` over the last K positions (K the kernel's
    rows; depthwise, no bias, no activation); `out_proj (C * c)`. No
    scan, no step size: what a sequence carries is the K - 1 rows of z
    before its next position, {"conv": [B, K-1, d]}, whatever its
    length. `state` None = a sequence's start; with `lengths` the
    window handed back is the row's own (`causal_conv`). One position
    (a decode step) is the same arithmetic at T = 1: the cached rows
    and the new one."""
    d = cfg.d_model
    with part("conv_proj"):
        bcx = y @ kernel_of(p["in_proj"], cfg.dtype)  # [B, T, 3d]
        z = bcx[..., :d] * bcx[..., 2 * d:]
    with part("conv_mix"):
        c, window = causal_conv(
            z, p["conv"]["kernel"],
            None if state is None else state["conv"], lengths)
    with part("conv_proj"):
        out = (bcx[..., d:2 * d] * c.astype(cfg.dtype)) @ kernel_of(
            p["out_proj"], cfg.dtype)
    return out, {"conv": window}


def ssm_mixer(
    p: Dict[str, Any],  # a block's "ssm" subtree
    cfg: LMConfig,
    y: jax.Array,  # [B, T, d], normalised
    state: Optional[Dict[str, jax.Array]] = None,
    lengths: Optional[jax.Array] = None,  # [B] int32: rows' own lengths
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The Mamba-2 mixer in both its forms, which share everything but
    the recurrence: `[z | xBC | dt] = in_proj y`; `xBC <- SiLU(conv(xBC)
    + bias)`, a causal depthwise convolution over the last
    `conv_kernel` positions; `dt <- softplus(dt + dt_bias)`, `A =
    -exp(A_log)` a head; the recurrence (`ssm_scan_chunked` over T
    positions from `state`, or `ssm_scan_step` for the one position of
    a decode step); `+ D x`; the gate and the norm `RMSNorm(y *
    SiLU(z))` over each group's channels; `out_proj`.

    `state` is {"conv": the last K-1 rows of xBC BEFORE the
    convolution [B, K-1, C], "ssm": h [B, H, P, N] float32}, None = a
    sequence's start (zeros). Returns (out [B, T, d], the state after
    the T positions). With `lengths` row b holds lengths[b] <= T real
    positions: past them dt is 0, so h stays, and the convolution
    window is taken at the row's own length (zeros on the left of a
    row shorter than the window): the state a row padded to a bucket
    hands back is the state of the unpadded row."""
    s = cfg.ssm
    b, t, _ = y.shape
    f32 = jnp.float32
    di, gn = s.d_inner, s.groups * s.state
    with part("ssm_proj"):
        zxbcdt = y @ kernel_of(p["in_proj"], cfg.dtype)
        z = zxbcdt[..., :di]
        xbc = zxbcdt[..., di:di + s.conv_width]
        dt = zxbcdt[..., di + s.conv_width:]
        conv, window = causal_conv(
            xbc, p["conv"]["kernel"],
            None if state is None else state["conv"], lengths,
            bias=p["conv"]["bias"], activation=jax.nn.silu)
        xbc = conv.astype(cfg.dtype)
        dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
        if lengths is not None:
            dt = jnp.where(
                jnp.arange(t)[None, :, None] < lengths[:, None, None],
                dt, 0.0)
        a = -jnp.exp(p["A_log"].astype(f32))
        x = xbc[..., :di].reshape(b, t, s.heads, s.head_dim)
        bm = xbc[..., di:di + gn].reshape(b, t, s.groups, s.state)
        cm = xbc[..., di + gn:].reshape(b, t, s.groups, s.state)
    with part("ssm_scan"):
        if state is not None and t == 1:
            out, h = ssm_scan_step(
                x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], state["ssm"])
            out = out[:, None]
        else:
            h0 = (jnp.zeros((b, s.heads, s.head_dim, s.state), f32)
                  if state is None else state["ssm"])
            out, h = ssm_scan_chunked(x, dt, a, bm, cm, h0, s.chunk)
    with part("ssm_proj"):
        out = out + p["D"].astype(f32)[:, None] * x.astype(f32)
        out = out.reshape(b, t, di) * jax.nn.silu(z.astype(f32))
        grp = out.reshape(b, t, s.groups, di // s.groups)
        grp = grp * jax.lax.rsqrt(
            jnp.mean(grp * grp, axis=-1, keepdims=True) + cfg.norm_eps)
        out = (grp.reshape(b, t, di)
               * p["norm"]["scale"].astype(f32)).astype(cfg.dtype)
        return out @ kernel_of(p["out_proj"], cfg.dtype), {
            "conv": window.astype(cfg.dtype), "ssm": h}


def rope_interleaved(x: jax.Array, positions: jax.Array,
                     base: float = 10000.0) -> jax.Array:
    """`rope` under the other pairing: columns (2i, 2i + 1) rotate
    together by `positions * base ** (-2i / D)`. x [B, T, H, D];
    positions [T] or [B, T]. The pair's partner comes by ONE product
    with a constant signed permutation (exact in any dtype: each output
    is a single +-x), which keeps the columns on the lanes where a
    reshape to [..., D/2, 2] would put a 2 there."""
    d = x.shape[-1]
    freqs = base ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    angles = jnp.repeat(
        positions[..., None].astype(jnp.float32) * freqs, 2, axis=-1)
    if positions.ndim == 1:
        angles = angles[None]
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    i = jnp.arange(d)
    # partner[2i] = -x[2i + 1], partner[2i + 1] = x[2i]
    swap = jnp.zeros((d, d), x.dtype).at[i ^ 1, i].set(
        jnp.where(i % 2 == 0, -1, 1).astype(x.dtype))
    partner = jnp.matmul(x, swap, precision=jax.lax.Precision.HIGHEST)
    return (x * cos + partner * sin).astype(x.dtype)


def _rope_of(cfg: LMConfig):
    return rope_interleaved if cfg.rope_pairing == "interleaved" else rope


def _latent_attention(blk, cfg: LMConfig, y, positions, attn_fn, absorbed):
    """Latent attention on normalised `y`, the ONE copy of its
    projections, norms and rope, in its two algebraically equal forms:
    (its output through `proj`, the rows to cache [B, T, row_width]).

    Query: `c_q = RMSNorm(q_a y)`, `[q_nope | q_rope] = q_b c_q` a
    head, rope on `q_rope`. Keys and values: `[c | k_r] = kv_a y`, `c
    <- RMSNorm(c)`, `k_r <- rope(k_r)`, one rope key a token for every
    head; a head's `k_nope = c w_uk[h]` and `v = c w_uv[h]` (the
    published `kv_b`, held as its two halves a head, [H, kv_rank, .]
    each). Scores are `(q_nope . k_nope + q_rope . k_r) / sqrt(nope_dim
    + rope_dim)`. What a token caches is `[c | k_r]`.

    Expanded (`absorbed` False; prefill): per-head keys `[k_nope |
    k_r]` and values are rebuilt from the rows of THIS call and go
    through `attn_fn(q, k, v)`, keys wider than values. Absorbed
    (decode, the multi-token cached step): `q~ = w_uk[h] q_nope` (a
    head's query in the latent's own space), `attn_fn([q~ | q_rope],
    rows)` attends the cached rows as ONE shared head of key width
    `row_width` whose values are its first `kv_rank` columns, and
    `w_uv[h]` is applied after: no per-head key or value is ever
    made."""
    m, h, dt = cfg.latent, cfg.n_heads, cfg.dtype
    b, t = y.shape[:2]
    rope_fn = _rope_of(cfg)
    f32 = jnp.float32
    with part("attn_proj"):
        c_q = _rms_norm(y @ kernel_of(blk["q_a"], dt),
                        blk["q_a_norm"]["scale"], dt, cfg.norm_eps)
        q = (c_q @ kernel_of(blk["q_b"], dt)).reshape(b, t, h, m.key_width)
        q_nope = q[..., :m.nope_dim]
        q_rope = rope_fn(q[..., m.nope_dim:], positions, cfg.rope_theta)
        kva = y @ kernel_of(blk["kv_a"], dt)
        c = _rms_norm(kva[..., :m.kv_rank], blk["kv_a_norm"]["scale"], dt,
                      cfg.norm_eps)
        k_r = rope_fn(kva[..., None, m.kv_rank:], positions, cfg.rope_theta)
        rows = jnp.concatenate([c, k_r[:, :, 0]], axis=-1)
        w_uk, w_uv = kernel_of(blk["w_uk"], dt), kernel_of(blk["w_uv"], dt)
        if absorbed:
            q_lat = jnp.einsum("bthn,hcn->bthc", q_nope, w_uk,
                               preferred_element_type=f32).astype(dt)
            q = jnp.concatenate([q_lat, q_rope], axis=-1)
        else:
            k_nope = jnp.einsum("btc,hcn->bthn", c, w_uk,
                                preferred_element_type=f32).astype(dt)
            v = jnp.einsum("btc,hcv->bthv", c, w_uv,
                           preferred_element_type=f32).astype(dt)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_r, (b, t, h, m.rope_dim))],
                axis=-1)
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
    # the closure names its own parts (`attn_core`, `cache_write`)
    attn = attn_fn(q, rows) if absorbed else attn_fn(q, k, v)
    with part("attn_proj"):
        if absorbed:
            attn = jnp.einsum("bthc,hcv->bthv", attn.astype(dt), w_uv,
                              preferred_element_type=f32)
        attn = attn.reshape(b, t, h * m.v_dim).astype(dt)
        return attn @ kernel_of(blk["proj"], dt), rows


def _attention(blk, cfg: LMConfig, y, positions, attn_fn, absorbed=False,
               lay: Optional[AttentionType] = None):
    """The attention mixer on normalised `y`: (its output through
    `proj`, k, v); under latent attention (the rows to cache, None) in
    k's and v's place, by the form `absorbed` names
    (`_latent_attention`). `lay` is the layer's type (`LMConfig.attn`;
    None = the one type of a stack without `attention_layers`): its
    query heads over the config's
    KV heads, its rope (from the base, or from `rope_table` where the
    rope rotates part of a head or scales its frequencies), and its
    gate: `sigmoid(head_gate y)`, a number a head from the same normed
    input, on the heads' outputs before `proj`."""
    if cfg.latent is not None:
        out, rows = _latent_attention(
            blk, cfg, y, positions, attn_fn, absorbed)
        return out, rows, None
    b, t = y.shape[:2]
    if lay is None:
        if cfg.attention_layers is not None:
            raise ValueError(
                "a layer of a stack whose attention layers go by type "
                "comes with its type (`LMConfig.attn`)")
        lay = cfg.attn(0)
    hd, kv = cfg.head_dim, cfg.kv_heads
    h, qw, ro = lay.n_heads, lay.n_heads * hd, lay.rope
    with part("attn_proj"):
        qkv = y @ kernel_of(blk["qkv"], cfg.dtype)  # [B, T, qw + 2*kv*hd]
        q = qkv[..., :qw].reshape(b, t, h, hd)
        k = qkv[..., qw : qw + kv * hd].reshape(b, t, kv, hd)
        v = qkv[..., qw + kv * hd :]
        if cfg.qk_norm:
            q = _rms_norm(q, blk["q_norm"]["scale"], cfg.dtype, cfg.norm_eps)
            k = _rms_norm(k, blk["k_norm"]["scale"], cfg.dtype, cfg.norm_eps)
        if cfg.rope and ro.plain:
            q = _rope_of(cfg)(q, positions, ro.theta)
            k = _rope_of(cfg)(k, positions, ro.theta)
        elif cfg.rope:
            table = rope_table(ro, hd)
            q = rope_by_table(q, positions, *table)
            k = rope_by_table(k, positions, *table)
        v = v.reshape(b, t, kv, hd)
    # k/v carry kv heads; the closure decides, and names its own parts
    # (`attn_core`, `cache_write`)
    attn = attn_fn(q, k, v)
    with part("attn_proj"):
        if lay.gate:
            gate = jax.nn.sigmoid(jnp.matmul(
                y, kernel_of(blk["head_gate"], cfg.dtype),
                preferred_element_type=jnp.float32))  # [B, T, H]
            attn = attn.astype(jnp.float32) * gate[..., None]
        attn = attn.reshape(b, t, qw).astype(cfg.dtype)
        return attn @ kernel_of(blk["proj"], cfg.dtype), k, v


def _feed_forward(blk, cfg: LMConfig, y, experts, mesh):
    """The feed-forward mixer on normalised `y`: the expert layer where
    the block holds one (`moe`), else the dense MLP: gated,
    `down(act(gate y) * up y)`, where the block holds a `gate` matrix,
    else the two-matrix `down(act(up y))`. A stack may hold both kinds
    (leading dense layers under expert layers): the block's own leaves
    decide."""
    if "moe" in blk:  # names its own parts (`moe_*`)
        out, counts = expert_ffn(
            blk["moe"], y, cfg.dtype, cfg.experts_per_token,
            cfg.experts_first,
            live=None if experts is None else experts["live"], mesh=mesh,
            scoring=cfg.router_scoring, scale=cfg.router_scale,
            activation=cfg.activation,
            windows=None if experts is None else experts.get("windows"),
        )
        if experts is not None:
            experts["counts"].append(counts)
        return out
    act = _activation(cfg.activation)
    with part("mlp"):
        if "gate" in blk:
            y = act(y @ kernel_of(blk["gate"], cfg.dtype)) * (
                y @ kernel_of(blk["up"], cfg.dtype))
        else:
            y = act(y @ kernel_of(blk["up"], cfg.dtype))
        return y @ kernel_of(blk["down"], cfg.dtype)


def _apply_block(
    blk: Dict[str, Any],
    cfg: LMConfig,
    x: jax.Array,  # [B, T, d]
    positions: jax.Array,  # [T] shared or [B, T] per-example
    attn_fn,  # (q, k, v) [B,T,H,D] -> [B,T,H,D]
    experts: Optional[Dict[str, Any]] = None,
    mesh: Optional[Mesh] = None,
    kind: Optional[str] = None,
    ssm_fn=None,  # (a stateful mixer's subtree, y [B,T,d]) -> [B,T,d]
    absorbed: bool = False,  # latent attention's form (`_latent_attention`)
    lay: Optional[AttentionType] = None,  # the layer's type (`_attention`)
) -> Tuple[jax.Array, Optional[jax.Array], Optional[jax.Array]]:
    """ONE layer — the single copy of the layer math that decode (T=1,
    cache attention, one recurrence step) and prefill (T=Tp, flash
    attention, the chunked scan) both run, so they cannot drift apart.
    Returns (x_out, k, v); the caller owns what the attention closure
    and the cache do with k/v (None from a layer without attention).

    `kind` None is the classic block, attention then feed-forward, each
    under its own norm: it matches models/transformer.py
    layer-for-layer where `cfg` is at its defaults; `cfg.d_head`,
    `rope_theta`, `qk_norm` and the expert keys are the architectures
    `lm_spec` describes beyond it; where the layer's type (`lay`) is a
    gated short convolution, that operator stands in the attention's
    place under the same norm, run by the caller's `ssm_fn` closure
    (`conv_mixer` with the window it owns), and no k/v come back. A
    kind of `LAYER_KINDS` (a
    `layer_pattern`'s layer) is `x + mixer(norm(x))` with that ONE
    mixer: attention, the expert feed-forward, or the state-space
    mixer, which the caller's `ssm_fn` closure runs (`ssm_mixer` with
    the state it owns).

    `positions` is [T] (shared across the batch: prefill, plain
    decode) or [B, T] (per-example: continuous-batching decode, where
    every slot sits at its own position) — rope handles both forms.
    `experts` (a caller that wants the routing's counts) is
    {"live": [B] bool or None, "counts": []}: every expert layer
    appends its [E] assignment counts to the list, and to a list under
    "windows", where the caller put one, the windows it ran past its
    first (`expert_ffn`; nothing where its shapes give one window).
    """
    # a mixer's norm goes by the mixer's (first) part
    ffn = "moe_route" if "moe" in blk else "mlp"
    if kind is None:
        conv = lay is not None and lay.conv_kernel is not None
        with part("conv_proj" if conv else "attn_proj"):
            y = _rms_norm(x, blk["ln_attn"]["scale"], cfg.dtype, cfg.norm_eps)
        if conv:
            out, k, v = ssm_fn(blk["short_conv"], y), None, None
        else:
            out, k, v = _attention(
                blk, cfg, y, positions, attn_fn, absorbed, lay)
        x = x + out
        with part(ffn):
            y = _rms_norm(x, blk["ln_mlp"]["scale"], cfg.dtype, cfg.norm_eps)
        return x + _feed_forward(blk, cfg, y, experts, mesh), k, v
    with part({"*": "attn_proj", "E": ffn}.get(kind, "ssm_proj")):
        y = _rms_norm(x, blk["ln"]["scale"], cfg.dtype, cfg.norm_eps)
    if kind == "*":
        out, k, v = _attention(blk, cfg, y, positions, attn_fn)
        return x + out, k, v
    if kind == "E":
        return x + _feed_forward(blk, cfg, y, experts, mesh), None, None
    return x + ssm_fn(blk["ssm"], y), None, None


def _lm_head(params: Dict[str, Any], cfg: LMConfig, x: jax.Array) -> jax.Array:
    """Final norm + lm head on [..., d] -> [..., V] f32 logits. A head
    stored in float32 (or int8) multiplies in float32, as
    TransformerLM's does; one stored in the model's compute dtype
    (`lm_spec`'s `param_dtype`) multiplies in it and accumulates in
    float32, so no float32 copy of it is ever made. A tree without an
    `lm_head` ties the head to the embedding: the logits are the normed
    rows against the table [V, d], contracted over d in the same
    dtypes."""
    with part("head"):
        x = _rms_norm(x, params["ln_out"]["scale"], cfg.dtype, cfg.norm_eps)
        if "lm_head" not in params:
            table = params["embed"]["embedding"]
            if table.dtype != cfg.dtype:
                x, table = x.astype(jnp.float32), table.astype(jnp.float32)
            return jnp.einsum("...d,vd->...v", x, table,
                              preferred_element_type=jnp.float32)
        kern = params["lm_head"]["kernel"]
        if (not isinstance(kern, dict)
                and kern.dtype == cfg.dtype != jnp.float32):
            return jnp.matmul(x, kern, preferred_element_type=jnp.float32)
        return x.astype(jnp.float32) @ kernel_of(
            params["lm_head"], jnp.float32)


def _head(params: Dict[str, Any], cfg: LMConfig, x_last: jax.Array) -> jax.Array:
    """Final norm + lm head on [B, 1, d] -> [B, V] f32 logits."""
    with part("head"):
        return _lm_head(params, cfg, x_last)[:, 0, :]


def decode_step(
    params: Dict[str, Any],
    cfg: LMConfig,
    cache: Dict[str, Any],
    tokens: jax.Array,  # [B] int32 — the tokens at position `idx`
    idx: jax.Array,  # scalar int32 position being written
) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decode step: logits for position `idx` + updated cache.

    Matches TransformerLM.apply on the prefix up to `idx` exactly
    (same layer math, same dtypes). The shared-position special case
    of `batched_decode_step` — ONE implementation, so the
    single-request and continuous-batching paths cannot diverge.
    """
    b = tokens.shape[0]
    return batched_decode_step(
        params, cfg, cache, tokens, jnp.full((b,), idx, jnp.int32)
    )


def heads_axis(mesh: Optional[Mesh], *head_counts: int) -> Optional[str]:
    """The mesh axis attention heads shard over: "tp" when every given
    head count divides over it, else None (replicated). The one rule
    for where a head lives — the KV cache's placement (LMServer) and
    the per-device kernel wrappers below must agree on it."""
    tp = 1 if mesh is None else mesh.shape.get("tp", 1)
    return "tp" if tp > 1 and all(n % tp == 0 for n in head_counts) else None


def _kernel_on_mesh(kernel, mesh: Optional[Mesh], in_specs, out_specs):
    """A Pallas kernel placed per device under `mesh`. GSPMD cannot
    partition a Mosaic custom call — lowering for a real multi-chip
    mesh raises "Mosaic kernels cannot be automatically partitioned.
    Please wrap the call in a shard_map" (the CPU mesh, where kernels
    interpret to plain XLA ops, never showed it) — so each device runs
    the kernel on its own heads, or whole where heads do not divide.
    check_vma off: a pallas_call's out_shape carries no vma metadata."""
    if mesh is None:
        return kernel
    return jax.shard_map(
        kernel, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def _typed_on_one_device(cfg: LMConfig, mesh: Optional[Mesh]) -> None:
    """Raise for a stack whose attention layers go by type under a
    mesh: `heads_axis` places ONE head count."""
    if cfg.attention_layers is not None and mesh is not None:
        raise ValueError(
            "attention layers by type are served on one device: no rule "
            "divides layers of different head counts over a mesh yet")


def uses_decode_kernel() -> bool:
    """Whether `batched_decode_step` hands cache attention to the
    Pallas kernel (ops/decode_attention.py): on a TPU, for every cache
    layout (MHA, grouped, MQA; bf16 or int8). Elsewhere the kernel
    would only interpret, and the einsum below is the route and the
    tests' oracle. The one place that decides, so a caller can ask.

    The kernel fetches only the k-blocks that hold live rows of each
    slot; the einsum streams the whole [B, T] grid behind a mask.
    Grouped bf16 caches were the one layout an older policy kept on
    the einsum; at no length measured is the einsum ahead there now,
    so there is no policy and no lever (MHA, MQA and int8 caches took
    the kernel before and were not measured again). On one TPU v5e
    (my chip run, PR 26) at the benchmark's grouped bf16 cache (32
    heads / 8 KV, D 128, 16 slots x 4,096 rows, 8 layers):

    - a 32-step scan of `batched_decode_step`, bf16 weights, ms a
      step: einsum 7.03 whatever the lengths; the dense kernel this
      one replaces 6.88; this kernel 6.88 with every slot at ~4,000
      rows (2% ahead of the einsum at full context, where the old A/B
      on a chip that is gone had the dense kernel 13% behind), 4.52
      with 13 slots live at the cells' lengths (median ~350 rows),
      4.22 with 4 live;
    - the jobs cell (`mistral7b_widths_l8.jobs`), tokens/s: the
      einsum 1,175.7, the dense kernel forced onto the grouped cache
      1,164.0, this kernel 1,486.3 — kernel against einsum is nothing,
      skipping dead blocks is all of it.

    Since PR 39 the kernel's grid is the list of live (slot, k-block)
    pairs, where PR 26 kept a (slots, blocks of T) grid and skipped the
    dead steps' DMAs and bodies at ~0.5 us each; the KV heads' softmax
    chains run side by side, and a block is 1 MB of copies a step (256
    rows here, where PR 26 took 512). The kernel alone (my chip runs,
    PR 39; a 32-step scan of 8 calls, us a call, PR 26's kernel -> the
    dead steps gone at its blocks -> as landed): 116.9 -> 64.4 -> 51.2
    at the jobs cell's lengths (16 slots live, 20 of 128 blocks of
    512), 42.3 -> 16.0 -> 14.0 with 3 slots live, 367.7 -> 367.8 ->
    360.5 with every slot at 4,000 rows (no dead step to drop there);
    an int8 cache 108.2 -> 59.8 -> 39.5 and 339.2 -> 326.3 -> 212.8;
    the other cells' shapes 179.5 -> 74.3 (32 slots, KV 4, four query
    rows), 299.5 -> 112.0 (64 slots, KV 2), 115.0 -> 92.1 (the shared
    640-column plane). The jobs cell 2,077.8 -> 2,250.2 tokens/s."""
    return jax.default_backend() == "tpu"


def decode_block_rows(
    cfg: LMConfig, max_len: int, mesh: Optional[Mesh] = None,
    layer: int = 0,
) -> Optional[int]:
    """Cache rows per k-block that `batched_decode_step`'s kernel
    fetches in attention layer `layer` of a `max_len`-row cache of
    `cfg` (per device under `mesh`): a window layer's plane is its
    ring (`LMConfig.layer_rows`), so its blocks go by the ring's rows.
    None on the einsum route, which streams every row. For a caller
    that reckons the rows a step reads from the slots' lengths
    (LMServer's `kv_rows` accounting)."""
    if not uses_decode_kernel():
        return None
    from ..ops.decode_attention import block_rows

    if cfg.latent is not None:  # one shared plane of latent rows
        return block_rows(
            1, cfg.latent.row_stride, cfg.dtype, max_len, shared=True)
    tp = mesh.shape["tp"] if heads_axis(
        mesh, cfg.n_heads, cfg.kv_heads) else 1
    return block_rows(
        cfg.kv_heads // cfg.kv_pack // tp, cfg.kv_pack * cfg.head_dim,
        jnp.int8 if cfg.kv_quant else cfg.dtype,
        cfg.layer_rows(layer, max_len),
    )


def _write_rows(c: jax.Array, u: jax.Array, pos: jax.Array,
                axis: int) -> jax.Array:
    """Cache leaf `c` with slot b's rows `u[b]` written from row
    `pos[b]` along `axis`. Per-slot writes are an UNROLLED chain of
    dynamic_update_slice — a vmap over per-slot positions lowers to a
    scatter, and XLA scatters on TPU copy the whole operand (measured:
    the copy tripled decode's cache traffic)."""
    with part("cache_write"):
        for bi in range(c.shape[0]):
            start = [bi] + [0] * (c.ndim - 1)
            start[axis] = pos[bi]
            c = jax.lax.dynamic_update_slice(c, u[bi : bi + 1], start)
        return c


def pack_rows(cfg: LMConfig, x: jax.Array) -> jax.Array:
    """K or V rows [B, T, KV, D] as the cache holds them, head-major:
    [B, KV / r, T, r D], `LMConfig.kv_pack` r neighbouring heads' rows
    side by side (r = 1: [B, KV, T, D], a transpose and no more)."""
    b, t, kv, d = x.shape
    r = cfg.kv_pack
    return jnp.swapaxes(x.reshape(b, t, kv // r, r * d), 1, 2)


def unpack_rows(cfg: LMConfig, c: jax.Array) -> jax.Array:
    """A cache plane [B, KV / r, T, r D] by head, [B, KV, T, D]: the
    einsum route's view (the tests' oracle), a copy the kernel route
    never makes."""
    r = cfg.kv_pack
    if r == 1:
        return c
    b, kvp, t, _ = c.shape
    return jnp.swapaxes(c.reshape(b, kvp, t, r, cfg.head_dim), 2, 3).reshape(
        b, kvp * r, t, cfg.head_dim)


def packed_attention(cfg: LMConfig, kernel, q: jax.Array, ck, cv, n_rows):
    """`kernel(q, ck, cv, n_rows)` over planes that hold `kv_pack` r
    heads a row: query head h (of KV head h // G, the j-th of its row's
    r) is widened to the row, zeros in the other heads' columns, so its
    scores against a packed row are its scores against its own head's
    key and no other; a row then serves r G query heads as ONE head of
    r D columns, which is a shape the kernel has always taken. Of the r
    D columns that come back a head keeps its own D: the others are its
    probabilities over ANOTHER head's values. The zeros cost arithmetic
    the memory-bound step does not miss, and no byte of cache. q
    [B, Q, H, D] -> [B, Q, H, D] f32."""
    r, hd = cfg.kv_pack, cfg.head_dim
    if r == 1:
        return kernel(q, ck, cv, n_rows)
    b, qn, h, _ = q.shape
    g = h // cfg.kv_heads
    place = jnp.eye(r, dtype=q.dtype)
    wide = jnp.einsum(
        "bqpjgd,jk->bqpjgkd", q.reshape(b, qn, -1, r, g, hd), place)
    out = kernel(wide.reshape(b, qn, h, r * hd), ck, cv, n_rows)
    out = out.reshape(b, qn, -1, r, g, r, hd)
    return jnp.einsum("bqpjgjd->bqpjgd", out).reshape(b, qn, h, hd)


def _latent_rows(cfg: LMConfig, rows: jax.Array) -> jax.Array:
    """The rows `_latent_attention` hands back [B, T, row_width] as the
    cache holds them: [B, 1, T, row_stride], zeros in the spare
    columns (a query's spare columns are zeros too, so they add nothing
    to a score)."""
    m = cfg.latent
    return jnp.pad(rows[:, None].astype(cfg.dtype),
                   ((0, 0),) * 3 + ((0, m.row_stride - m.row_width),))


def _latent_cached(cfg: LMConfig, leaf: jax.Array, q: jax.Array,
                   rows: jax.Array, pos: jax.Array, lengths: jax.Array,
                   valid: Optional[jax.Array], mask_block: int = 1):
    """The absorbed form against the cache, for both cached steps:
    write `rows` [B, Q, W] into `leaf` [B, 1, T, S] from `pos` (W the
    row's width, S its stride: `_latent_rows`), then attend q [B, Q,
    H, W] over slot b's rows < lengths[b] (query i of Q stopping short
    as `decode_attention` says). Returns (the heads' outputs in the
    latent's space [B, Q, H, kv_rank] f32, the leaf).

    On a TPU the Pallas kernel reads each live block ONCE, as keys and
    as values (`decode_attention`'s shared plane); elsewhere (`valid`
    [B, Q, T] given) an einsum over the grid in float32, the oracle."""
    m = cfg.latent
    scale = m.key_width ** -0.5
    with part("cache_write"):
        leaf = _write_rows(leaf, _latent_rows(cfg, rows), pos, axis=2)
    with part("attn_core"):
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, m.row_stride - m.row_width),))
        if valid is None:
            from ..ops.decode_attention import decode_attention

            return decode_attention(
                q, leaf, None, lengths, scale=scale, v_width=m.kv_rank,
                mask_block=mask_block), leaf
        lat = leaf[:, 0].astype(jnp.float32)  # [B, T, W]
        s = jnp.einsum("bqhw,btw->bhqt", q.astype(jnp.float32), lat) * scale
        vmask = valid[:, None]
        s = jnp.where(vmask, s, -1e30)
        # zeros for an empty slot, as the kernel returns
        p = jnp.where(vmask, jax.nn.softmax(s, axis=-1), 0.0)
        return jnp.einsum("bhqt,btc->bqhc", p, lat[..., :m.kv_rank]), leaf


def batched_decode_step(
    params: Dict[str, Any],
    cfg: LMConfig,
    cache: Dict[str, Any],
    tokens: jax.Array,  # [B] int32 — each slot's current input token
    pos: jax.Array,  # [B] int32 — each slot's own write position
    mesh: Optional[Mesh] = None,
    lengths: Optional[jax.Array] = None,  # [B] int32 — rows each slot attends
    experts: Optional[Dict[str, Any]] = None,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """decode_step with PER-SLOT positions — the continuous-batching
    primitive (inference/lm_server.py): every slot advances through
    its own sequence independently, so requests of different lengths
    decode together in one program. Identical math to decode_step
    (which is the pos-broadcast special case). `mesh` is the mesh the
    params are sharded over, if any: the Pallas cache-attention
    kernel is then placed per device (`_kernel_on_mesh`).

    Slot b attends cache rows < `lengths[b]`: `pos + 1` (the row just
    written included) unless the caller knows better. A caller that
    knows a slot is EMPTY passes 0 for it: its attention output is
    zeros, its logits are garbage nobody reads, and the kernel route
    fetches none of its cache (its row at `pos` is still written).

    A state-space layer advances its slot's state by the one position
    (`ssm_mixer`'s step form), whatever the slot holds: an empty
    slot's state is garbage nobody reads, overwritten whole by the
    next placement. A gated short convolution does the same with its
    window (`conv_mixer`: the cached rows and the new one). `experts`
    is `_apply_block`'s.

    A WINDOW layer (`LMConfig.attention_layers`) writes its row at
    `pos mod W` of its ring of W rows and attends the ring's first
    `min(lengths, W)` rows: the slot's last min(length, W) positions,
    the one just written among them, and no other (`init_cache`). Same
    kernel, same oracle, a shorter plane."""
    hd = cfg.head_dim
    b = tokens.shape[0]
    _typed_on_one_device(cfg, mesh)
    with part("embed"):
        x = params["embed"]["embedding"][tokens].astype(cfg.dtype)[:, None, :]
    positions = pos[:, None]  # [B, 1] — rope's per-example form
    # layout-generic (bf16 {k, v} or kv_quant {k_q, ...}): every K/V leaf
    # carries [B, KV, max_len, ...]
    max_len = cache_rows(cache)
    if lengths is None:
        lengths = pos + 1
    # per-slot validity: slot b sees cache rows < lengths[b]
    valid = jnp.arange(max_len)[None, :] < lengths[:, None]  # [B, T]
    use_kernel = uses_decode_kernel()
    if use_kernel and cfg.latent is None:
        from ..ops.decode_attention import decode_attention

        ax = heads_axis(mesh, cfg.n_heads, cfg.kv_heads)
        q_spec = P(None, None, ax, None)  # [B, 1, H, D]
        c_spec = P(None, ax, None, None)  # [B, KV, T, D] / [B, KV, 1, T]
        kernel = _kernel_on_mesh(
            lambda q, k, v, n, ks=None, vs=None: decode_attention(
                q, k, v, n, k_scale=ks, v_scale=vs, scale=hd ** -0.5),
            mesh,
            in_specs=(q_spec, c_spec, c_spec, P())
            + ((c_spec, c_spec) if cfg.kv_quant else ()),
            out_specs=q_spec,
        )

    new_cache: Dict[str, Any] = {}
    for i, kind in enumerate(cfg.kinds):
        name = f"block_{i}"

        typ = cfg.attn(i)

        def ssm_fn(p, y, name=name,
                   mixer=ssm_mixer if typ.conv_kernel is None else conv_mixer):
            out, new_cache[name] = mixer(p, cfg, y, cache[name])
            return out

        def latent_fn(q, rows, name=name):
            out, leaf = _latent_cached(
                cfg, cache[name]["latent"], q, rows, pos, lengths,
                None if use_kernel else valid[:, None])
            new_cache[name] = {"latent": leaf}
            return out

        ring = (cfg.layer_rows(i, max_len) if typ.window is not None
                else None)

        def attn_fn(q, k, v, name=name, ring=ring):
            # k/v arrive [B, 1, KV, D]; the cache is head-major
            # (`_write_rows` on how the rows are written)
            grp = q.shape[2] // cfg.kv_heads
            n_rows, vmask = lengths, valid
            with part("cache_write"):
                upd = functools.partial(_write_rows, pos=pos)
                kh = pack_rows(cfg, k)  # [B, KV, 1, D]
                vh = pack_rows(cfg, v)
                if ring is not None:
                    # the ring's rows < min(lengths[b], W), written at
                    # pos mod W
                    n_rows = jnp.minimum(lengths, ring)
                    vmask = jnp.arange(ring)[None, :] < lengths[:, None]
                    upd = functools.partial(_write_rows, pos=pos % ring)
                    ck = upd(cache[name]["k_ring"], kh.astype(cfg.dtype),
                             axis=2)
                    cv = upd(cache[name]["v_ring"], vh.astype(cfg.dtype),
                             axis=2)
                    new_cache[name] = {"k_ring": ck, "v_ring": cv}
                elif cfg.kv_quant:
                    kq, ks = _kv_quantize(kh)
                    vq, vs = _kv_quantize(vh)
                    lay = {
                        "k_q": upd(cache[name]["k_q"], kq, axis=2),
                        "k_s": upd(cache[name]["k_s"],
                                   jnp.swapaxes(ks, 2, 3), axis=3),
                        "v_q": upd(cache[name]["v_q"], vq, axis=2),
                        "v_s": upd(cache[name]["v_s"],
                                   jnp.swapaxes(vs, 2, 3), axis=3),
                    }
                    new_cache[name] = lay
                else:
                    ck = upd(cache[name]["k"], kh.astype(cfg.dtype), axis=2)
                    cv = upd(cache[name]["v"], vh.astype(cfg.dtype), axis=2)
                    new_cache[name] = {"k": ck, "v": cv}
            with part("attn_core"):
                if cfg.kv_quant:
                    if use_kernel:
                        return kernel(
                            q, lay["k_q"], lay["v_q"], lengths,
                            lay["k_s"], lay["v_s"],
                        )
                    ck = _kv_dequant(
                        lay["k_q"], jnp.swapaxes(lay["k_s"], 2, 3)
                    )
                    cv = _kv_dequant(
                        lay["v_q"], jnp.swapaxes(lay["v_s"], 2, 3)
                    )
                elif use_kernel:
                    return packed_attention(cfg, kernel, q, ck, cv, n_rows)
                ck, cv = unpack_rows(cfg, ck), unpack_rows(cfg, cv)
                qg = q.astype(jnp.float32).reshape(
                    b, 1, cfg.kv_heads, grp, hd)
                s = jnp.einsum(
                    "bqkgd,bktd->bkgqt", qg, ck.astype(jnp.float32)
                ) * (hd**-0.5)
                vmask = vmask[:, None, None, None, :]
                s = jnp.where(vmask, s, -1e30)
                # a live slot's p is already exactly 0 on dead rows; the
                # select makes an EMPTY slot (all rows dead, softmax
                # uniform) return zeros, as the kernel does
                p = jnp.where(vmask, jax.nn.softmax(s, axis=-1), 0.0)
                attn = jnp.einsum(
                    "bkgqt,bktd->bqkgd", p, cv.astype(jnp.float32))
                return attn.reshape(b, 1, q.shape[2], hd)

        x, _, _ = _apply_block(
            params[name], cfg, x, positions,
            attn_fn if cfg.latent is None else latent_fn, experts, mesh,
            kind, ssm_fn, absorbed=True, lay=typ)

    return _head(params, cfg, x), new_cache


def _rows_back(t: int, mask_block: int) -> Tuple[int, ...]:
    """For T query rows a slot written at rows pos .. pos+T-1: how many
    of those rows, counted from the last, query t does NOT attend.
    Causal (1): T-1-t. Blocks of B (pos a multiple of B): the rows of
    later blocks, T - (t // B + 1) * B."""
    return tuple(t - (i // mask_block + 1) * mask_block for i in range(t))


def batched_block_step(
    params: Dict[str, Any],
    cfg: LMConfig,
    cache: Dict[str, Any],
    tokens: jax.Array,  # [B, T] int32 — T tokens a slot
    pos: jax.Array,  # [B] int32 — each slot's first write position
    *,
    mask_block: int = 1,
    live: Optional[jax.Array] = None,  # [B] bool; None = every slot
    head: bool = True,
    mesh: Optional[Mesh] = None,
    experts: Optional[Dict[str, Any]] = None,
) -> Tuple[Optional[jax.Array], Dict[str, Any]]:
    """The multi-token cached forward: slot b runs `tokens[b]` at
    positions pos[b] .. pos[b]+T-1 against its cache rows in ONE
    dispatch, and returns logits for every position ([B, T, V] f32;
    None without `head`) and the cache with those T rows written.
    One weight stream covers T tokens a slot. Two callers:

    - speculative decoding's VERIFY (`mask_block` 1, causal: query t
      attends rows <= pos+t): identical math to T successive
      `batched_decode_step` calls (tests/test_specdec.py pins it);
    - block diffusion (`mask_block` B = T, `pos` a multiple of B: every
      query attends every row < pos+B, the block's own included). A
      denoising forward and the commit forward are the same call: both
      write their rows, and a denoising forward's rows are scratch
      that the block's later forwards overwrite before any other row
      can see them (rows >= pos are attended by no earlier block), so
      "nothing is stored" until the commit holds in effect.

    Rows are written before any read (an unrolled chain of
    `dynamic_update_slice`, one contiguous [KV, T, D] block a slot: a
    vmap'd scatter copies the whole cache on a TPU). Attention reads
    the cache in its own dtype, never a float32 copy of it: on a TPU
    through the length-aware Pallas kernel (`ops/decode_attention.py`,
    T * G query rows a KV head; only the k-blocks under pos+T are
    fetched, none for a slot that is not `live`), elsewhere through
    an einsum over the grid with float32 accumulation (the tests'
    oracle). `live` false = an empty slot: it attends nothing, returns
    garbage nobody reads, and its rows are still written.

    The caller must ensure pos[b] + T <= max_len for every live slot;
    starts are clamped so a freed slot's garbage position stays
    in-bounds (its rows are erased by the next insert's full-row
    overwrite — LMServer._insert_impl's invariant)."""
    hd = cfg.head_dim
    b, t = tokens.shape
    grp = cfg.n_heads // cfg.kv_heads
    if t % mask_block:
        raise ValueError(f"{t} rows under blocks of {mask_block}")
    if cfg.has_state:
        # rows written here may be dropped (a rejected draft) or written
        # again (a block's next forward): K/V rows allow both, a scan
        # state that has taken them in allows neither
        raise ValueError(
            "the multi-token cached forward cannot roll a state-space "
            "layer's state or a convolution's window back; speculation "
            "and block diffusion need it to")
    if cfg.attention_layers is not None:
        # T rows written at once overwrite positions the step's first
        # queries still attend (a ring holds the window's rows and no
        # more), and a rejected draft's rows have overwritten what a
        # rollback would need; nor does this step take a layer's heads,
        # rope and gate from its type yet
        raise ValueError(
            "the multi-token cached forward serves no stack of attention "
            "layers by type: a window layer's ring holds the window and "
            "no more, so a step of several rows overwrites what its "
            "first rows attend")
    with part("embed"):
        x = params["embed"]["embedding"][tokens].astype(cfg.dtype)  # [B,T,d]
    max_len = cache_rows(cache)
    pos = jnp.minimum(pos, max_len - t)
    positions = pos[:, None] + jnp.arange(t)[None, :]  # [B, T] per-example
    back = jnp.asarray(_rows_back(t, mask_block), jnp.int32)
    lengths = pos + t if live is None else jnp.where(live, pos + t, 0)
    # the kernel takes T * G rows a KV head in whole sublane tiles (the
    # latent plane is one KV head under all H query heads)
    per_kv = cfg.n_heads if cfg.latent is not None else grp
    use_kernel = uses_decode_kernel() and (t * per_kv) % 8 == 0
    if use_kernel and cfg.latent is None:
        from ..ops.decode_attention import decode_attention

        ax = heads_axis(mesh, cfg.n_heads, cfg.kv_heads)
        q_spec = P(None, None, ax, None)  # [B, T, H, D]
        c_spec = P(None, ax, None, None)  # [B, KV, T, D] / [B, KV, 1, T]
        kernel = _kernel_on_mesh(
            lambda q, k, v, n, ks=None, vs=None: decode_attention(
                q, k, v, n, k_scale=ks, v_scale=vs, mask_block=mask_block),
            mesh,
            in_specs=(q_spec, c_spec, c_spec, P())
            + ((c_spec, c_spec) if cfg.kv_quant else ()),
            out_specs=q_spec,
        )
    if not use_kernel:
        # per-(slot, query) validity: query i sees rows < its limit
        valid = (
            jnp.arange(max_len)[None, None, :]
            < (lengths[:, None] - back[None, :])[:, :, None]
        )  # [B, T, max_len]

    new_cache: Dict[str, Any] = {}
    for i, kind in enumerate(cfg.kinds):
        name = f"block_{i}"

        def latent_fn(q, rows, name=name):
            out, leaf = _latent_cached(
                cfg, cache[name]["latent"], q, rows, pos, lengths,
                None if use_kernel else valid, mask_block)
            new_cache[name] = {"latent": leaf}
            return out

        def attn_fn(q, k, v, name=name):
            # k/v arrive [B, T, KV, D]; write each slot's contiguous
            # [KV, T, D] block at its own start row (`_write_rows`)
            with part("cache_write"):
                upd = functools.partial(_write_rows, pos=pos)
                kh = jnp.swapaxes(k, 1, 2)  # [B, KV, T, D]
                vh = jnp.swapaxes(v, 1, 2)
                if cfg.kv_quant:
                    kq, ks = _kv_quantize(kh)
                    vq, vs = _kv_quantize(vh)
                    lay = {
                        "k_q": upd(cache[name]["k_q"], kq, axis=2),
                        "k_s": upd(cache[name]["k_s"],
                                   jnp.swapaxes(ks, 2, 3), axis=3),
                        "v_q": upd(cache[name]["v_q"], vq, axis=2),
                        "v_s": upd(cache[name]["v_s"],
                                   jnp.swapaxes(vs, 2, 3), axis=3),
                    }
                    new_cache[name] = lay
                else:
                    ck = upd(cache[name]["k"], kh.astype(cfg.dtype), axis=2)
                    cv = upd(cache[name]["v"], vh.astype(cfg.dtype), axis=2)
                    new_cache[name] = {"k": ck, "v": cv}
            with part("attn_core"):
                if cfg.kv_quant:
                    if use_kernel:
                        return kernel(q, lay["k_q"], lay["v_q"], lengths,
                                      lay["k_s"], lay["v_s"])
                    ck = _kv_dequant(
                        lay["k_q"], jnp.swapaxes(lay["k_s"], 2, 3)
                    )
                    cv = _kv_dequant(
                        lay["v_q"], jnp.swapaxes(lay["v_s"], 2, 3)
                    )
                elif use_kernel:
                    return kernel(q, ck, cv, lengths)
                # the oracle's route, in float32 as `batched_decode_step`'s
                # (it widens the cache: what the kernel route never does)
                qg = q.astype(jnp.float32).reshape(
                    b, t, cfg.kv_heads, grp, hd)
                s = jnp.einsum(
                    "bqkgd,bktd->bkgqt", qg, ck.astype(jnp.float32)
                ) * (hd**-0.5)
                vmask = valid[:, None, None, :, :]
                s = jnp.where(vmask, s, -1e30)
                # the select returns zeros for an empty slot (all rows
                # dead, softmax uniform), as the kernel does
                p = jnp.where(vmask, jax.nn.softmax(s, axis=-1), 0.0)
                attn = jnp.einsum(
                    "bkgqt,bktd->bqkgd", p, cv.astype(jnp.float32))
                return attn.reshape(b, t, cfg.n_heads, hd)

        x, _, _ = _apply_block(
            params[name], cfg, x, positions,
            attn_fn if cfg.latent is None else latent_fn, experts, mesh,
            kind, absorbed=True)

    # logits at EVERY position (not _head's single-row squeeze): the
    # verifier needs the target's next-token argmax after each
    # candidate, the denoiser every position's own distribution
    return (_lm_head(params, cfg, x) if head else None), new_cache


def batched_verify_step(
    params: Dict[str, Any],
    cfg: LMConfig,
    cache: Dict[str, Any],
    tokens: jax.Array,  # [B, T] int32 — T candidate tokens per slot
    pos: jax.Array,  # [B] int32 — each slot's first write position
    mesh: Optional[Mesh] = None,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """Speculative decoding's VERIFY primitive: `batched_block_step`
    under the causal mask, logits [B, T, V] for every consumed
    position (the target's next-token distribution after each
    candidate)."""
    return batched_block_step(params, cfg, cache, tokens, pos, mesh=mesh)


def ring_positions(lengths: jax.Array, rows: int) -> jax.Array:
    """[B, rows] int32: the position ring row r of a slot holds after
    `lengths[b]` positions were written at p mod rows each: the newest
    p < length with p mod rows = r; negative where the row is still
    unwritten (r >= length: behind every mask, as `min(length, rows)`
    rows are attended)."""
    last = lengths[:, None].astype(jnp.int32) - 1
    r = jnp.arange(rows, dtype=jnp.int32)[None, :]
    return last - (last - r) % rows


def prefill(
    params: Dict[str, Any],
    cfg: LMConfig,
    prompt: jax.Array,  # [B, Tp] int32
    max_len: int,
    logits_index: Optional[jax.Array] = None,
    mesh: Optional[Mesh] = None,
    head: bool = True,
    experts: Optional[Dict[str, Any]] = None,
) -> Tuple[Optional[jax.Array], Dict[str, Any]]:
    """Process the WHOLE prompt in one forward: returns (logits at the
    last prompt position [B, V], cache filled for positions < Tp).
    Under `cfg.attention_mask` "block_causal" the flash kernel masks by
    blocks; `head=False` (a block-diffusion server, whose first tokens
    come from denoising forwards) skips the head and returns None for
    the logits.

    `mesh` is the mesh the params are sharded over, if any: the flash
    kernel is then placed per device (`_kernel_on_mesh`). `experts` is
    `_apply_block`'s, for a caller that wants the expert layers' counts.

    `logits_index` (scalar) selects which position's logits to return
    instead of the last — the continuous-batching server prefills
    bucket-PADDED prompts, and causal masking guarantees the logits at
    the true last prompt position are untouched by the pad tail, so
    reading them here keeps the server's first token numerically
    IDENTICAL to an unpadded `generate` call. A state-space layer is
    causal too, but its STATE is what the last position left, so with
    a `logits_index` every row's state is taken at its own length,
    `logits_index + 1` (`ssm_mixer`'s `lengths`): the cache a padded
    row hands back holds, beside its K/V rows, the convolution window
    and scan state of the unpadded prompt (a gated short convolution's
    window the same way: `conv_mixer`). A WINDOW layer
    (`LMConfig.attention_layers`) attends under the banded mask (`0 <=
    i - j < W`; the flash kernel visits the band's k-blocks alone) and
    hands back its RING, filled as decode would have left it after the
    row's own length n (`logits_index + 1`, else Tp): ring row r holds
    the newest position p < n with p mod W = r (`ring_positions`), so
    a row padded to a bucket caches the last W positions of the
    unpadded prompt, and the pad tail none.

    The old path pushed the prompt through the decode scan one token
    at a time — O(Tp) sequential [B,1] steps that leave the MXU idle.
    This runs the same layer math at sequence granularity with the
    Pallas flash kernel doing causal attention (interpreted off-TPU),
    so a 4k-token prompt costs one batched forward instead of 4096
    round trips through the scan."""
    from ..ops.flash_attention import flash_attention

    b, tp = prompt.shape
    with part("embed"):
        x = params["embed"]["embedding"][prompt].astype(cfg.dtype)  # [B,Tp,d]
    positions = jnp.arange(tp)
    pad = max_len - tp
    _typed_on_one_device(cfg, mesh)

    h_spec = P(None, None, heads_axis(mesh, cfg.n_heads), None)  # [B,T,H,D]
    mask = {"mask_block": cfg.mask_block} if cfg.mask_block > 1 else {}
    flash = _kernel_on_mesh(
        functools.partial(flash_attention, causal=True, **mask), mesh,
        in_specs=(h_spec, h_spec, h_spec), out_specs=h_spec,
    )

    def attn_fn(q, k, v, window=None):
        with part("attn_core"):
            if cfg.attention_layers is not None:
                # a layer that has a type: K and V by KV head, as
                # `_apply_block` returned them, and the kernel's live
                # blocks alone (a window layer: the band's)
                return flash_attention(q, k, v, causal=True, window=window)
            # the untyped call is head-symmetric: broadcast GQA kv heads
            # to full heads for the prefill pass (the cache below keeps
            # the compact layout _apply_block returned)
            grp = q.shape[2] // cfg.kv_heads
            if grp > 1:
                k = jnp.repeat(k, grp, axis=2)
                v = jnp.repeat(v, grp, axis=2)
            return flash(q, k, v)

    # rows' own lengths, where the caller gave them: a state-space
    # layer must not take a padded position into its state, nor a
    # window layer's ring a padded position's row
    lengths = None
    if (cfg.has_state or cfg.has_ring) and logits_index is not None:
        lengths = jnp.broadcast_to(
            jnp.asarray(logits_index, jnp.int32) + 1, (b,))

    cache: Dict[str, Any] = {}
    pad4 = ((0, 0), (0, 0), (0, pad), (0, 0))  # head-major: pad T axis 2
    for i, kind in enumerate(cfg.kinds):
        name = f"block_{i}"

        typ = cfg.attn(i)
        window = typ.window

        def ssm_fn(p, y, name=name,
                   mixer=ssm_mixer if typ.conv_kernel is None else conv_mixer):
            out, cache[name] = mixer(p, cfg, y, lengths=lengths)
            return out

        x, k, v = _apply_block(
            params[name], cfg, x, positions,
            attn_fn if window is None else functools.partial(
                attn_fn, window=window),
            experts=experts, mesh=mesh, kind=kind, ssm_fn=ssm_fn, lay=typ,
        )
        if k is None:
            continue
        with part("cache_write"):  # the call's rows in the cache's layout
            if window is not None:
                at = ring_positions(
                    jnp.full((b,), tp, jnp.int32) if lengths is None
                    else lengths, cfg.layer_rows(i, max_len))  # [B, R]
                take = lambda u: jnp.swapaxes(jnp.take_along_axis(
                    u, jnp.maximum(at, 0)[:, :, None, None], axis=1),
                    1, 2).astype(cfg.dtype)  # [B, Tp, KV, D] -> [B, KV, R, D]
                cache[name] = {"k_ring": take(k), "v_ring": take(v)}
                continue
            if cfg.latent is not None:  # k: the rows to cache [B, Tp, W]
                cache[name] = {
                    "latent": jnp.pad(_latent_rows(cfg, k), pad4)}
                continue
            kh = pack_rows(cfg, k)  # [B, KV, Tp, D] — cache layout
            vh = pack_rows(cfg, v)
            if cfg.kv_quant:
                kq, ks = _kv_quantize(kh)
                vq, vs = _kv_quantize(vh)
                # scales: T on lanes
                padT = ((0, 0), (0, 0), (0, 0), (0, pad))
                cache[f"block_{i}"] = {
                    "k_q": jnp.pad(kq, pad4),
                    "k_s": jnp.pad(jnp.swapaxes(ks, 2, 3), padT),
                    "v_q": jnp.pad(vq, pad4),
                    "v_s": jnp.pad(jnp.swapaxes(vs, 2, 3), padT),
                }
            else:
                cache[f"block_{i}"] = {
                    "k": jnp.pad(kh.astype(cfg.dtype), pad4),
                    "v": jnp.pad(vh.astype(cfg.dtype), pad4),
                }

    if not head:
        return None, cache
    with part("head"):
        if logits_index is None:
            x_last = x[:, -1:]
        elif jnp.ndim(logits_index) == 0:
            x_last = jax.lax.dynamic_slice_in_dim(x, logits_index, 1, axis=1)
        else:
            # per-row indices: a batched-placement prefill packs prompts
            # of different true lengths into one bucket, so each row reads
            # its own last-prompt position (LMServer group placement)
            x_last = jax.vmap(
                lambda row, i: jax.lax.dynamic_slice_in_dim(
                    row, i, 1, axis=0)
            )(x, logits_index.astype(jnp.int32))
        return _head(params, cfg, x_last), cache


def _sample(logits, rng, temperature: float, top_k: Optional[int]):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k is not None:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def generate(
    params: Dict[str, Any],
    cfg: LMConfig,
    prompt: jax.Array,  # [B, Tp] int32
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    seed: int = 0,
    rng: Optional[jax.Array] = None,
) -> jax.Array:
    """Greedy/temperature/top-k decoding; returns [B, max_new_tokens].

    The prompt runs through `prefill` (one flash-attention forward
    filling the cache); the scan then covers ONLY the new tokens, each
    a single [B,1] decode step against the cache. One jit compilation
    per (shape, config). Pass `rng` (a PRNGKey) instead of `seed` when
    calling under jit — a traced key doesn't force a retrace per seed.
    """
    b, tp = prompt.shape
    if max_new_tokens <= 0:  # cache-warm / degenerate budgets: [B, 0]
        return jnp.zeros((b, 0), jnp.int32)
    total = tp + max_new_tokens
    if rng is None:
        rng = jax.random.PRNGKey(seed)

    logits0, cache = prefill(params, cfg, prompt, total)
    rng, sub = jax.random.split(rng)
    first = _sample(logits0, sub, temperature, top_k)  # token at pos Tp

    def step(carry, t):
        cache, cur, rng = carry
        logits, cache = decode_step(params, cfg, cache, cur, t)
        rng, sub = jax.random.split(rng)
        sampled = _sample(logits, sub, temperature, top_k)
        return (cache, sampled, rng), sampled

    # steps write positions Tp .. total-2, predicting Tp+1 .. total-1
    (_, _, _), samples = jax.lax.scan(
        step,
        (cache, first, rng),
        jnp.arange(tp, total - 1),
    )
    return jnp.concatenate([first[:, None], samples.T], axis=1)
