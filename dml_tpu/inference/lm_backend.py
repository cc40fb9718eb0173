"""LM serving backend for the distributed job pipeline.

Makes LM generation a first-class JOB TYPE of the cluster: prompts
live in the replicated store as token files, `submit-job <lm> <N>`
fans batches out to workers exactly like image jobs (same fair-share
scheduler, same preemption/requeue recovery, same hot-standby
relays — jobs/scheduler.py, jobs/service.py), and each worker decodes
its batch through the continuous-batching `LMServer`. The reference
has nothing like this (SURVEY §0: no sequence models); it is the
distributed analog of its image pipeline (worker.py:518-537) for the
framework's net-new LM stack.

Prompt file contract (tokenizer-free core — plug a tokenizer at the
edge): a text file of whitespace/comma-separated integer token ids,
e.g. ``12 7 998 4``. Output per file: ``{"tokens": [...]}`` — the
greedy completion, EXACTLY equal to an isolated
`generate(prompt, max_new_tokens)` call for that prompt (the
LMServer batching-exactness contract, tests/test_lm_server.py),
regardless of which worker served it or what else shared the batch.
"""

from __future__ import annotations

import asyncio
import logging
import re
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

log = logging.getLogger(__name__)

from ..jobs.cost_model import ModelCost, lm_request_forwards
from ..tracing import current_all_ctxs
from .generate import (
    ACTIVATIONS, ATTENTION_KINDS, LAYER_KINDS, ROPE_PAIRINGS, ROUTER_SCORING,
    AttentionLayers, AttentionType, LatentConfig, LMConfig, RopeConfig,
    SSMConfig, YarnConfig,
)
from .lm_server import REMASKING, BlockDiffusion, LMDriver, LMServer


def parse_prompt_file(
    path: str, vocab_size: int
) -> Tuple[np.ndarray, Optional[int]]:
    """(token ids, per-request budget or None) from a prompt file;
    raises with the offending path on malformed content (the job
    pipeline surfaces it as a batch FAIL).

    A line starting with ``#`` is a directive; ``# max_new_tokens: N``
    sets this request's generation budget (else the backend's
    default). Mixed budgets are where continuous batching earns its
    keep: a batch-synchronous server holds every slot until the
    SLOWEST request finishes, while the slot grid refills the moment
    each one retires (bench `lm.mixed_budget_batching`)."""
    budget: Optional[int] = None
    body: List[str] = []
    with open(path) as f:
        for line in f:
            s = line.strip()
            if s.startswith("#"):
                m = s[1:].split(":", 1)
                if len(m) == 2 and m[0].strip() == "max_new_tokens":
                    try:
                        budget = int(m[1])
                    except ValueError:
                        raise ValueError(
                            f"{path}: bad max_new_tokens directive {s!r}"
                        ) from None
                    if budget < 1:
                        raise ValueError(
                            f"{path}: max_new_tokens must be >= 1"
                        )
                elif re.match(r"^#\s*max_new_tokens\b", s):
                    # a near-miss DIRECTIVE ('# max_new_tokens 64',
                    # missing colon) must be LOUD, not silently served
                    # at the default budget. Only comments that START
                    # with the directive name trip this: an innocuous
                    # mention ('# see max_new_tokens docs') is prose,
                    # not a failed directive, and must not hard-fail
                    # the whole batch
                    raise ValueError(
                        f"{path}: unparseable max_new_tokens "
                        f"directive {s!r} (expected "
                        f"'# max_new_tokens: N')"
                    )
                continue
            body.append(line)
    toks = [t for t in " ".join(body).replace(",", " ").split() if t]
    if not toks:
        raise ValueError(f"{path}: empty prompt file")
    try:
        ids = np.array([int(t) for t in toks], np.int32)
    except ValueError as e:
        raise ValueError(f"{path}: non-integer token ({e})") from None
    if (ids < 0).any() or (ids >= vocab_size).any():
        raise ValueError(
            f"{path}: token id out of range [0, {vocab_size})"
        )
    return ids, budget


#: `lm_spec` keys that describe an architecture beyond TransformerLM's
#: block. A spec that sets none of them is served as before: the flax
#: module's tree, float32 storage.
_ARCH_KEYS = (
    "head_dim", "rope_theta", "qk_norm", "num_experts", "experts_per_token",
    "expert_d_ff", "gated", "experts_held", "attention_mask",
    "block_length", "param_dtype", "layer_pattern", "ssm", "rope",
    "norm_eps", "router", "expert_latent", "shared_expert_d_ff",
    "activation", "attention", "latent_attention", "rope_pairing",
    "dense_layers", "attention_layers", "tied_head",
)
_SSM_KEYS = ("heads", "head_dim", "state", "groups", "conv_kernel", "chunk")
_ROUTER_KEYS = ("scoring", "bias", "scale")
#: latent attention's widths under the names the DeepSeek-V3 family's
#: configs publish them by, in `LatentConfig`'s order
_LATENT_KEYS = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim")
#: the range a state-space head's initial step size is drawn from
#: (log-uniform) and its floor: the `nemotron_h` configs' `time_step_min`,
#: `time_step_max`, `time_step_floor`, which shape the initialisation only
_DT_INIT = (0.001, 0.1, 1e-4)
_DTYPES = ("bfloat16", "float32")
#: `attention_layers`: the layers' types as data. `layers` names each
#: layer's type, `types` describes each name once. An attention type: its
#: query heads, its rope (`theta`; `rotary_dim`, the columns of a head it
#: rotates; `yarn`, the frequencies' scaling), its `window` (a type that
#: has one caches a ring of that many rows a slot), its `gate`. A type
#: whose operator is the gated short convolution: `conv_kernel`, the
#: positions it runs over, and no other key
_LAYER_TYPE_KEYS = ("n_heads", "rope", "window", "gate", "conv_kernel")
_ROPE_KEYS = ("theta", "rotary_dim", "yarn")
_YARN_KEYS = ("factor", "original_max_position", "beta_fast", "beta_slow",
              "attention_factor")
HEAD_GATES = ("per_head",)


def _attention_layers(al: Any, n_layers: int) -> AttentionLayers:
    """`lm_spec["attention_layers"]` checked and as the config holds it."""
    if (not isinstance(al, dict) or set(al) != {"layers", "types"}
            or not isinstance(al["types"], dict) or not al["types"]):
        raise ValueError(
            f"attention_layers {al!r}: `layers` (each layer's type by "
            f"name) and `types` (each name's description)")
    layers = tuple(al["layers"])
    if len(layers) != n_layers or set(layers) - set(al["types"]):
        raise ValueError(
            f"attention_layers names {len(layers)} layers of types "
            f"{sorted(set(layers))}: n_layers is {n_layers}, the types "
            f"described are {sorted(al['types'])}")
    types = []
    for name, t in al["types"].items():
        if isinstance(t, dict) and t.get("conv_kernel") is not None:
            if set(t) != {"conv_kernel"}:
                raise ValueError(
                    f"layer type {name!r} {t!r}: a convolution type is "
                    f"its conv_kernel and no other key")
            types.append((name, AttentionType(
                conv_kernel=int(t["conv_kernel"]))))
            continue
        if not isinstance(t, dict) or set(t) - set(_LAYER_TYPE_KEYS) or (
                "n_heads" not in t):
            raise ValueError(
                f"attention layer type {name!r} {t!r}: n_heads, and of "
                f"{_LAYER_TYPE_KEYS} no other key")
        rope = dict(t.get("rope") or {})
        yarn = rope.get("yarn")
        if set(rope) - set(_ROPE_KEYS) or (yarn is not None and (
                not isinstance(yarn, dict) or set(yarn) - set(_YARN_KEYS)
                or not {"factor", "original_max_position"} <= set(yarn))):
            raise ValueError(
                f"attention layer type {name!r}'s rope {rope!r}: keys of "
                f"{_ROPE_KEYS}, a yarn of {_YARN_KEYS}")
        if t.get("gate") not in (None,) + HEAD_GATES:
            raise ValueError(
                f"attention layer type {name!r}'s gate {t['gate']!r} "
                f"({' | '.join(HEAD_GATES)})")
        types.append((name, AttentionType(
            n_heads=int(t["n_heads"]),
            rope=RopeConfig(
                theta=float(rope.get("theta", 10000.0)),
                rotary_dim=(None if rope.get("rotary_dim") is None
                            else int(rope["rotary_dim"])),
                yarn=None if yarn is None else YarnConfig(
                    factor=float(yarn["factor"]),
                    original_max_position=int(yarn["original_max_position"]),
                    beta_fast=float(yarn.get("beta_fast", 32.0)),
                    beta_slow=float(yarn.get("beta_slow", 1.0)),
                    attention_factor=(
                        None if yarn.get("attention_factor") is None
                        else float(yarn["attention_factor"])))),
            window=None if t.get("window") is None else int(t["window"]),
            gate=t.get("gate") is not None)))
    return AttentionLayers(tuple(types), layers)


def lm_arch(spec: Dict[str, Any]) -> Dict[str, Any]:
    """The architecture an `lm_spec` describes, checked: every value
    the serving code cannot honour raises here, before any weight is
    made. Keys of other layers (`name`, `max_slots`, `kv_cache_mb`,
    `spec_k`, ...) are not this function's and pass."""
    d_model, heads = int(spec["d_model"]), int(spec.get("n_heads", 8))
    a: Dict[str, Any] = {
        "head_dim": int(spec.get("head_dim") or d_model // heads),
        "rope_theta": float(spec.get("rope_theta", 10000.0)),
        "qk_norm": bool(spec.get("qk_norm", False)),
        "num_experts": int(spec.get("num_experts", 0) or 0),
        "experts_per_token": int(spec.get("experts_per_token", 2)),
        "gated": bool(spec.get("gated", False)),
        "attention_mask": spec.get("attention_mask", "causal"),
        "block_length": int(spec.get("block_length", 1)),
        "param_dtype": spec.get("param_dtype", "float32"),
    }
    a["expert_d_ff"] = int(
        spec.get("expert_d_ff") or spec.get("d_ff", 4 * d_model))
    a["layer_pattern"] = spec.get("layer_pattern")
    a["ssm"] = spec.get("ssm")
    a["rope"] = spec.get("rope", "rotary")
    a["norm_eps"] = float(spec.get("norm_eps", 1e-6))
    router = dict(spec.get("router") or {})
    a["router"] = {
        "scoring": router.get("scoring", "softmax"),
        "bias": bool(router.get("bias", False)),
        "scale": float(router.get("scale", 1.0)),
    }
    a["expert_latent"] = int(spec.get("expert_latent", 0) or 0)
    a["shared_expert_d_ff"] = int(spec.get("shared_expert_d_ff", 0) or 0)
    a["activation"] = spec.get("activation", "silu")
    a["attention"] = spec.get("attention", "grouped")
    a["latent_attention"] = spec.get("latent_attention")
    a["rope_pairing"] = spec.get("rope_pairing", "half")
    a["dense_layers"] = int(spec.get("dense_layers", 0) or 0)
    a["tied_head"] = bool(spec.get("tied_head", False))
    a["attention_layers"] = None
    if spec.get("attention_layers") is not None:
        # what a stack of typed layers cannot be, or what no code here
        # does with one (`LMConfig` raises on the same; said here by key)
        for key, why in (
                ("latent_attention", "its layers are grouped attention"),
                ("layer_pattern", "it is served in classic blocks"),
                ("denoising_steps", "neither a ring nor a convolution's "
                                    "window can be rewritten by a block's "
                                    "forwards")):
            if spec.get(key):
                raise ValueError(f"{key} under attention_layers: {why}")
        if (a["attention_mask"] != "causal" or a["rope"] != "rotary"
                or a["rope_pairing"] != "half"):
            raise ValueError(
                "attention_layers are served under the causal mask (a "
                "window layer under its band), rope in halves: no "
                "block_causal mask, rope none or interleaved pairing")
        if spec.get("n_kv_heads") is None:
            raise ValueError(
                "attention_layers: n_kv_heads says the K and V heads "
                "every type's query heads are grouped over")
        a["attention_layers"] = _attention_layers(
            spec["attention_layers"], int(spec.get("n_layers", 2)))
        if spec.get("kv_quant") and any(
                t.window is not None for _, t in a["attention_layers"].types):
            raise ValueError(
                "kv_quant under a window layer: its ring of rows is "
                "cached unquantized")
    if a["attention"] not in ATTENTION_KINDS:
        raise ValueError(
            f"unknown attention {a['attention']!r} "
            f"({' | '.join(ATTENTION_KINDS)})")
    if a["rope_pairing"] not in ROPE_PAIRINGS:
        raise ValueError(
            f"unknown rope_pairing {a['rope_pairing']!r} "
            f"({' | '.join(ROPE_PAIRINGS)})")
    if (a["attention"] == "latent") != (a["latent_attention"] is not None):
        raise ValueError(
            f"attention {a['attention']!r} with latent_attention "
            f"{a['latent_attention']!r}: latent attention and its widths "
            f"come together")
    if a["latent_attention"] is not None:
        lat = a["latent_attention"]
        if not isinstance(lat, dict) or set(lat) != set(_LATENT_KEYS):
            raise ValueError(
                f"latent_attention {lat!r}: exactly {_LATENT_KEYS}")
        a["latent_attention"] = LatentConfig(
            *(int(lat[k]) for k in _LATENT_KEYS))
        # what a latent row cannot be, or what no code here does with one
        for key, why in (
                ("kv_quant", "its one row a token is cached unquantized"),
                ("qk_norm", "its norms are on the two latents, not a head"),
                ("n_kv_heads", "every head reads the one shared latent"),
                ("head_dim", "a head's widths are latent_attention's"),
                ("layer_pattern", "it is served in classic blocks")):
            if spec.get(key):
                raise ValueError(f"{key} under latent attention: {why}")
        if a["attention_mask"] != "causal" or a["rope"] != "rotary":
            raise ValueError(
                "latent attention is served under the causal mask with "
                "rope on its rope columns")
    if a["dense_layers"] < 0 or (a["dense_layers"] and (
            not a["num_experts"] or a["layer_pattern"] is not None)):
        raise ValueError(
            f"dense_layers {a['dense_layers']}: the leading layers of a "
            f"stack of classic blocks whose other layers hold experts")
    pat = a["layer_pattern"]
    if pat is not None:
        if not isinstance(pat, str) or not pat or set(pat) - set(LAYER_KINDS):
            raise ValueError(
                f"layer_pattern {pat!r}: a string of one mixer a layer, "
                f"{LAYER_KINDS}")
        if int(spec.get("n_layers", len(pat))) != len(pat):
            raise ValueError(
                f"n_layers {spec['n_layers']} is not layer_pattern "
                f"{pat!r}'s length {len(pat)}")
        if ("E" in pat) != bool(a["num_experts"]):
            raise ValueError(
                f"layer_pattern {pat!r} and num_experts "
                f"{a['num_experts']}: expert layers and routed experts "
                f"come together")
    if ("M" in (pat or "")) != (a["ssm"] is not None):
        raise ValueError(
            f"ssm sizes {a['ssm']!r} under layer_pattern {pat!r}: a "
            f"state-space layer and its sizes come together")
    if a["ssm"] is not None:
        ssm = a["ssm"]
        if not isinstance(ssm, dict) or set(ssm) - set(_SSM_KEYS) or not {
                "heads", "head_dim", "state"} <= set(ssm):
            raise ValueError(
                f"ssm {ssm!r}: heads, head_dim and state, and of "
                f"{_SSM_KEYS} no other key")
        a["ssm"] = SSMConfig(**{k: int(v) for k, v in ssm.items()})
        if a["attention_mask"] != "causal":
            raise ValueError(
                "a state-space layer reads its sequence in order: "
                "attention_mask block_causal (generation by diffusion) "
                "has no meaning for it")
    if a["rope"] not in ("rotary", "none"):
        raise ValueError(f"unknown rope {a['rope']!r} (rotary | none)")
    if not 0 < a["norm_eps"] < 1:
        raise ValueError(f"norm_eps {a['norm_eps']}")
    if set(router) - set(_ROUTER_KEYS):
        raise ValueError(f"router {router!r}: keys of {_ROUTER_KEYS}")
    if a["router"]["scoring"] not in ROUTER_SCORING:
        raise ValueError(
            f"unknown router scoring {a['router']['scoring']!r} "
            f"({' | '.join(ROUTER_SCORING)})")
    if a["router"]["bias"] and a["router"]["scoring"] != "sigmoid":
        raise ValueError(
            "a router's selection bias corrects sigmoid scores; under "
            "softmax scoring the serving code has no use for one")
    if a["router"]["scale"] <= 0:
        raise ValueError(f"router scale {a['router']['scale']}")
    if a["activation"] not in ACTIVATIONS:
        raise ValueError(
            f"unknown activation {a['activation']!r} "
            f"({' | '.join(ACTIVATIONS)})")
    if min(a["expert_latent"], a["shared_expert_d_ff"]) < 0:
        raise ValueError("expert_latent / shared_expert_d_ff below 0")
    first, count = (
        int(n) for n in spec.get("experts_held") or (0, a["num_experts"]))
    a["experts_held"] = (first, count)
    if a["head_dim"] < 2 or a["head_dim"] % 2:
        raise ValueError(
            f"head_dim {a['head_dim']}: rope rotates halves of a head "
            f"and the attention kernels tile it; it must be even")
    if a["rope_theta"] <= 0:
        raise ValueError(f"rope_theta {a['rope_theta']}")
    if a["attention_mask"] not in ("causal", "block_causal"):
        raise ValueError(
            f"unknown attention_mask {a['attention_mask']!r} "
            f"(causal | block_causal)")
    if a["block_length"] < 1 or (
        a["attention_mask"] == "causal" and a["block_length"] != 1
    ):
        raise ValueError(
            f"block_length {a['block_length']} under "
            f"{a['attention_mask']!r} attention")
    if a["param_dtype"] not in _DTYPES or spec.get(
            "dtype", "bfloat16") not in _DTYPES:
        raise ValueError(
            f"dtype {spec.get('dtype')!r} / param_dtype "
            f"{a['param_dtype']!r}: one of {_DTYPES}")
    if a["num_experts"]:
        e, k = a["num_experts"], a["experts_per_token"]
        if not 0 < k <= e:
            raise ValueError(f"experts_per_token {k} of {e} experts")
        if first < 0 or count < 1 or first + count > e:
            raise ValueError(
                f"experts_held {[first, count]} lies outside the "
                f"{e} routed experts")
    elif (spec.get("experts_held") or spec.get("router")
          or a["expert_latent"] or a["shared_expert_d_ff"]):
        raise ValueError(
            "experts_held / router / expert_latent / "
            "shared_expert_d_ff without num_experts")
    if spec.get("denoising_steps") is not None or a["block_length"] > 1:
        if a["attention_mask"] != "block_causal":
            raise ValueError(
                "denoising_steps needs attention_mask block_causal")
        if int(spec.get("denoising_steps", 1)) < 1:
            raise ValueError(
                f"denoising_steps {spec.get('denoising_steps')}")
        if spec.get("remasking", REMASKING[0]) not in REMASKING:
            raise ValueError(
                f"unknown remasking {spec.get('remasking')!r} "
                f"(known: {REMASKING})")
        mask_id = spec.get("mask_token_id")
        if mask_id is None or not 0 <= int(mask_id) < int(
                spec["vocab_size"]):
            raise ValueError(
                f"mask_token_id {mask_id!r} is no id of the vocabulary")
    return a


def init_lm_params(cfg: LMConfig, arch: Dict[str, Any], seed: int):
    """The weight tree of an architecture `lm_arch` describes, in ONE
    jitted call: every matrix normal(0, 1 / sqrt(fan_in)) rounded to
    `param_dtype` (what flax's defaults give TransformerLM), norms at 1
    in float32, the router in float32. The layout is the one the
    serving code indexes (`generate._apply_block`): `qkv` fused
    [d, H*D + 2*KV*D], `proj` [H*D, d], `q_norm`/`k_norm` [D] under
    `qk_norm`, and either `up`/`down` (and `gate` under `gated`) or,
    in an expert layer, `moe`
    {router [d, E] (and its selection `bias` [E]), w_up and w_down
    (and w_gate) stacked over the experts HELD, in the latent width
    between `latent_down` [d, L] and `latent_up` [L, d] where the
    experts live in one, `shared_up`/`shared_down` (and `shared_gate`
    under `gated`) where a shared expert stands beside them}. Of a
    stack with experts the first `dense_layers` blocks hold the dense
    MLP of `d_ff` instead. Under latent attention a block holds, in
    `qkv`'s place, `q_a` [d, q_rank], `q_a_norm`, `q_b` [q_rank, H *
    (nope + rope)], `kv_a` [d, kv_rank + rope], `kv_a_norm`
    [kv_rank], and the published `kv_b` as its two halves a head,
    `w_uk` [H, kv_rank, nope] and `w_uv` [H, kv_rank, v] (the absorbed
    form multiplies them apart); `proj` is [H * v, d]. Under
    `attention_layers` a block's `qkv` and `proj` take its TYPE's query
    heads, and a gated type's block holds `head_gate` [d, H] beside
    them; a block whose type is the gated short convolution holds, in
    their place, `short_conv` {in_proj [d, B | C | X = 3d], conv
    {kernel [K, d]} (float32, uniform within 1 / sqrt(K)), out_proj
    [d, d]}. Under `tied_head` the tree holds no `lm_head`: the head is
    the embedding (`generate._lm_head`).

    Under a `layer_pattern` a block holds one norm (`ln`) and its
    mixer's leaves alone: `qkv` and `proj`, or `moe`, or `ssm`
    {in_proj [d, z | xBC | dt], conv {kernel [K, C], bias [C]}, A_log,
    D, dt_bias [H], norm {scale [d_inner]}, out_proj [d_inner, d]}. A
    state-space layer's small leaves are float32 and start as Mamba-2's
    do: A_log the log of uniform(1, 16), D at 1, dt_bias the inverse
    softplus of a log-uniform step (`_DT_INIT`), the convolution
    uniform within 1 / sqrt(K)."""
    import jax
    import jax.numpy as jnp

    d, hd, kvw = cfg.d_model, cfg.head_dim, cfg.kv_heads * cfg.head_dim
    pdt = jnp.dtype(arch["param_dtype"])
    held, f = arch["experts_held"][1], arch["expert_d_ff"]
    latent, shared = arch["expert_latent"], arch["shared_expert_d_ff"]
    attention: Dict[str, Any] = {
        "qkv": {"kernel": (d, cfg.q_width + 2 * kvw)},
        "proj": {"kernel": (cfg.q_width, d)},
    }
    if cfg.latent is not None:
        m, h = cfg.latent, cfg.n_heads
        attention = {
            "q_a": {"kernel": (d, m.q_rank)},
            "q_a_norm": {"scale": (m.q_rank,)},
            "q_b": {"kernel": (m.q_rank, h * m.key_width)},
            "kv_a": {"kernel": (d, m.row_width)},
            "kv_a_norm": {"scale": (m.kv_rank,)},
            "w_uk": (h, m.kv_rank, m.nope_dim),
            "w_uv": (h, m.kv_rank, m.v_dim),
            "proj": {"kernel": (h * m.v_dim, d)},
        }
    if cfg.qk_norm:
        attention["q_norm"] = {"scale": (hd,)}
        attention["k_norm"] = {"scale": (hd,)}
    if arch["num_experts"]:
        width = latent or d
        moe: Dict[str, Any] = {
            "router": {"kernel": (d, arch["num_experts"])},
            "w_up": (held, width, f), "w_down": (held, f, width),
        }
        if arch["router"]["bias"]:
            moe["router"]["bias"] = (arch["num_experts"],)
        if arch["gated"]:
            moe["w_gate"] = (held, width, f)
        if latent:
            moe["latent_down"] = {"kernel": (d, latent)}
            moe["latent_up"] = {"kernel": (latent, d)}
        if shared:
            moe["shared_up"] = {"kernel": (d, shared)}
            moe["shared_down"] = {"kernel": (shared, d)}
            if arch["gated"]:
                moe["shared_gate"] = {"kernel": (d, shared)}
        ffn: Dict[str, Any] = {"moe": moe}
    dense: Dict[str, Any] = {"up": {"kernel": (d, cfg.d_ff)},
                             "down": {"kernel": (cfg.d_ff, d)}}
    if arch["gated"]:
        dense["gate"] = {"kernel": (d, cfg.d_ff)}
    if not arch["num_experts"]:
        ffn = dense
    ssm: Dict[str, Any] = {}
    if cfg.ssm is not None:
        s = cfg.ssm
        ssm = {"ssm": {
            "in_proj": {"kernel": (d, s.in_width)},
            "conv": {"kernel": (s.conv_kernel, s.conv_width),
                     "bias": (s.conv_width,)},
            "A_log": (s.heads,), "D": (s.heads,), "dt_bias": (s.heads,),
            "norm": {"scale": (s.d_inner,)},
            "out_proj": {"kernel": (s.d_inner, d)},
        }}
    mixers = {"*": attention, "E": ffn, "M": ssm}

    def typed(i):  # layer i's attention leaves, by its type
        if cfg.attention_layers is None:
            return attention
        t = cfg.attn(i)
        if t.conv_kernel is not None:
            return {"short_conv": {
                "in_proj": {"kernel": (d, 3 * d)},
                "conv": {"kernel": (t.conv_kernel, d)},
                "out_proj": {"kernel": (d, d)}}}
        norms = {k: attention[k] for k in ("q_norm", "k_norm")
                 if k in attention}
        return {
            "qkv": {"kernel": (d, t.n_heads * hd + 2 * kvw)},
            "proj": {"kernel": (t.n_heads * hd, d)},
            **({"head_gate": {"kernel": (d, t.n_heads)}} if t.gate else {}),
            **norms,
        }

    shapes: Dict[str, Any] = {"embed": {"embedding": (cfg.vocab_size, d)}}
    for i, kind in enumerate(cfg.kinds):
        shapes[f"block_{i}"] = (
            {"ln_attn": {"scale": (d,)}, "ln_mlp": {"scale": (d,)},
             **typed(i), **(dense if i < arch["dense_layers"] else ffn)}
            if kind is None
            else {"ln": {"scale": (d,)}, **mixers[kind]})
    shapes["ln_out"] = {"scale": (d,)}
    if not arch["tied_head"]:
        shapes["lm_head"] = {"kernel": (d, cfg.vocab_size)}
    is_shape = lambda x: isinstance(x, tuple)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=is_shape)

    def make(key):
        out = []
        for i, (path, shape) in enumerate(flat):
            name = [getattr(p, "key", "") for p in path]
            k = jax.random.fold_in(key, i)
            if name[-1] in ("scale", "D"):
                out.append(jnp.ones(shape, jnp.float32))
            elif name[-1] == "A_log":
                out.append(jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1.0, 16.0)))
            elif name[-1] == "dt_bias":
                lo, hi, floor = _DT_INIT
                dt = jnp.maximum(floor, jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, jnp.log(lo), jnp.log(hi))))
                out.append(dt + jnp.log(-jnp.expm1(-dt)))
            elif "conv" in name:
                # a convolution's [K, C] kernel (and a state-space
                # layer's bias beside it)
                lim = (shape[0] if name[-1] == "kernel"
                       else cfg.ssm.conv_kernel) ** -0.5
                out.append(jax.random.uniform(
                    k, shape, jnp.float32, -lim, lim))
            elif name[-1] == "bias":  # the router's selection bias
                out.append(0.05 * jax.random.normal(k, shape, jnp.float32))
            else:
                # the contracted axis: the second to last of a (stacked)
                # kernel, the last of the embedding table
                fan_in = shape[-1] if name[-1] == "embedding" else shape[-2]
                w = jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
                out.append(w if "router" in name else w.astype(pdt))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax.random.PRNGKey(int(seed)))


def lm_spec_parts(spec: Dict[str, Any]):
    """(params, LMConfig) from a JSON-able LM spec — the construction
    half of `LMBackend.from_spec`, shared with the tp-sharded serving
    forms (inference/lm_sharded.py) which place the SAME deterministic
    tree with mesh shardings instead of single-device. Weights init
    from `seed` (identical tree on every node that loads the spec)
    unless `weights` names a flax-msgpack file.

    The architecture is data (`lm_arch` checks it and raises on a
    value the serving code cannot honour). A spec with none of
    `_ARCH_KEYS` is TransformerLM's block, initialised by the flax
    module and stored in float32 as ever; one that sets any of them
    (another head size, a rope base or pairing, q/k norms, latent
    attention, layers by type (attention, or a gated short convolution),
    a gated MLP, gated top-k experts under leading dense layers, the
    block-causal mask, a tied head, `param_dtype`) gets
    `init_lm_params`' tree,
    its matrices stored in `param_dtype`, every layer an expert layer
    where `num_experts` is set, or, under `layer_pattern`, one mixer a
    layer (state-space, attention, expert feed-forward) with
    `n_layers` the pattern's length.

    What is returned is the tree as STORED. A server does not multiply
    a float32 tree under bfloat16 compute as stored, nor cast it in
    every program: `LMServer` keeps `quantize.resident_params(params,
    cfg.dtype)`, the block matrices and expert tensors cast once, and
    holds no reference to this tree."""
    import jax
    import jax.numpy as jnp

    from ..models.transformer import TransformerLM

    arch = lm_arch(spec)
    dtype = {
        "bfloat16": jnp.bfloat16, "float32": jnp.float32,
    }[spec.get("dtype", "bfloat16")]
    d_model = int(spec["d_model"])
    described = any(spec.get(k) is not None for k in _ARCH_KEYS)
    cfg = LMConfig(
        vocab_size=int(spec["vocab_size"]),
        d_model=d_model,
        n_heads=int(spec.get("n_heads", 8)),
        n_layers=(len(arch["layer_pattern"]) if arch["layer_pattern"]
                  else int(spec.get("n_layers", 2))),
        d_ff=int(spec.get("d_ff", 4 * d_model)),
        dtype=dtype,
        n_kv_heads=(
            int(spec["n_kv_heads"])
            if spec.get("n_kv_heads") is not None else None
        ),
        kv_quant=bool(spec.get("kv_quant", False)),
        **({
            "d_head": (None if arch["attention"] == "latent"
                       else arch["head_dim"]),
            "rope_theta": arch["rope_theta"],
            "qk_norm": arch["qk_norm"],
            "experts_per_token": arch["experts_per_token"],
            "experts_first": arch["experts_held"][0],
            "attention_mask": arch["attention_mask"],
            "block_length": arch["block_length"],
            "layer_pattern": arch["layer_pattern"],
            "ssm": arch["ssm"],
            "rope": arch["rope"] == "rotary",
            "norm_eps": arch["norm_eps"],
            "router_scoring": arch["router"]["scoring"],
            "router_scale": arch["router"]["scale"],
            "activation": arch["activation"],
            "latent": arch["latent_attention"],
            "rope_pairing": arch["rope_pairing"],
            "attention_layers": arch["attention_layers"],
        } if described else {}),
    )
    if described:
        params = init_lm_params(cfg, arch, int(spec.get("seed", 0)))
    else:
        model = TransformerLM(
            vocab_size=cfg.vocab_size, d_model=cfg.d_model,
            n_heads=cfg.n_heads, n_layers=cfg.n_layers, d_ff=cfg.d_ff,
            dtype=cfg.dtype, n_kv_heads=cfg.n_kv_heads,
        )
        params = model.init(
            jax.random.PRNGKey(int(spec.get("seed", 0))),
            jnp.zeros((1, 8), jnp.int32),
        )["params"]
    if spec.get("weights"):
        from ..models.params_io import variables_from_bytes

        with open(spec["weights"], "rb") as f:
            data = f.read()
        params = variables_from_bytes(
            data, {"params": params}
        )["params"]
    return params, cfg


class LMBackend:
    """A worker-side serving backend compatible with
    `JobService(infer_backend=...)`'s contract:
    ``await backend(model, paths) -> (results, infer_time, cost)``.

    Holds one `LMServer` (slot grid + KV cache allocated once); each
    job batch submits its prompts and drains the server. Greedy by
    default so distributed outputs are reproducible; temperature>0
    stays per-request-deterministic via the server's fold_in streams.

    >>> be = LMBackend(params, cfg, max_new_tokens=32)
    >>> jobs = JobService(node, store, infer_backend=None)
    >>> jobs.register_lm("MyLM", backend=be.backend, cost=be.cost())
    """

    def __init__(
        self,
        params: Any,
        cfg: LMConfig,
        max_new_tokens: int = 32,
        max_slots: int = 8,
        max_len: int = 1024,
        chunk: int = 16,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        seed: int = 0,
        kv_cache_bytes: int = 0,
        spec_k: int = 0,
        spec_draft: Optional[Dict[str, Any]] = None,
        spec_min_accept: Optional[float] = None,
        diffusion: Optional[BlockDiffusion] = None,
    ):
        self.cfg = cfg
        self.max_new_tokens = max_new_tokens
        self.server = LMServer(
            params, cfg, max_slots=max_slots, max_len=max_len,
            chunk=chunk, temperature=temperature, top_k=top_k, seed=seed,
            diffusion=diffusion,
        )
        # speculative decoding (spec_k > 0): a deterministic DRAFT
        # model from `spec_draft` (a model spec dict — normally
        # config.draft_lm_spec(lm_spec)) proposes spec_k tokens per
        # slot per round; the target verifies them in one batched
        # forward. Greedy-exactness is the server's contract either
        # way. spec_k > 0 WITHOUT a draft spec arms shipped-draft
        # verification only (the disaggregated remote-draft form).
        self.spec_k = int(spec_k)
        if self.spec_k > 0:
            from ..config import (
                SPEC_MIN_ACCEPT_DEFAULT,
                SPEC_MIN_SAMPLES_DEFAULT,
            )

            dp = dcfg = None
            if spec_draft is not None:
                dp, dcfg = lm_spec_parts(spec_draft)
            self.server.enable_spec_decode(
                self.spec_k, draft_params=dp, draft_cfg=dcfg,
                min_accept=(
                    SPEC_MIN_ACCEPT_DEFAULT if spec_min_accept is None
                    else float(spec_min_accept)
                ),
                min_samples=SPEC_MIN_SAMPLES_DEFAULT,
            )
        # worker-resident KV prefix cache (inference/kv_cache.py):
        # retired requests' KV rows are retained under this host-bytes
        # budget and prompts extending a cached prefix warm-start with
        # a suffix-only prefill. 0 (the default) = disabled — the
        # serve path stays bit-identical to a cache-less build.
        self.kv_cache = None
        if int(kv_cache_bytes) > 0:
            from .kv_cache import KVPrefixCache

            self.kv_cache = KVPrefixCache(int(kv_cache_bytes))
            self.server.enable_kv_cache(self.kv_cache)
        # measured serving constants for the scheduler's cost model
        # (folded from real ACKs after the first batch either way)
        self._per_query = 0.05
        # a block-diffusion model is priced by forwards, not tokens:
        # seconds one forward over the grid took in the last dispatch
        # (None until one was measured), times the forwards a request
        # of the default budget costs, shared by the grid's slots
        self._per_forward: Optional[float] = None
        # Concurrency: the LMServer is single-threaded MUTABLE state,
        # but serving callers are many (co-located workers, preemption
        # orphans). Two modes (VERDICT r4 item 2):
        #
        # - overlap=True (default): all callers feed ONE LMDriver —
        #   their prompts merge into the same slot grid, so batch N+1
        #   prefills into freed slots while batch N is still decoding
        #   and per-chunk readbacks amortize over everything in
        #   flight (see LMDriver's docstring for why this beats
        #   per-worker servers on one chip).
        # - overlap=False: the round-3/4 lock-serialized path, kept as
        #   the bench's in-run serial baseline. When the scheduler
        #   preempts a worker the host-side task is cancelled at its
        #   await but the to_thread decode keeps running — the lock
        #   stops the replacement batch from corrupting the slot grid;
        #   under the driver the same orphan simply finishes its
        #   ticket and nobody reads it.
        self.overlap = True
        self._serve_lock = threading.Lock()
        # the driver takes the SAME lock the serial mode holds across
        # a whole run(): a mode flip racing an orphaned serial decode
        # can never interleave two drivers of one slot grid
        self.driver = LMDriver(self.server, server_lock=self._serve_lock)

    @staticmethod
    def _token_cbs(
        paths: Sequence[str], on_token
    ) -> Optional[list]:
        """Per-prompt LMServer delivery callbacks from the service's
        ``on_token(local_path, text)`` streaming contract
        (ingress/streaming.py): each delivered token id streams as its
        decimal text + separator, so the streamed concatenation is
        exactly the result's token list in prompt-file format."""
        if on_token is None:
            return None
        return [
            (lambda t, p=p: on_token(p, f"{int(t)} ")) for p in paths
        ]

    def serve_files(
        self, paths: Sequence[str], on_dispatch=None, on_token=None
    ) -> Tuple[Dict[str, Any], float, Dict[str, float]]:
        """Decode every prompt file; returns (results keyed by path,
        decode seconds, cost constants) — the sync core of
        `backend()`. `on_dispatch` (overlap mode) fires once the
        prompts are submitted to the shared driver, so the caller's
        pipeline can promote its next staged batch immediately.
        `on_token(local_path, text)` (the ingress streaming contract)
        fires per DELIVERED token from the decode grid's packed
        readbacks — real-engine `request-load` streaming, with the
        streamed text concatenating to exactly the final result."""
        parsed = [
            parse_prompt_file(p, self.cfg.vocab_size) for p in paths
        ]
        prompts = [ids for ids, _ in parsed]
        # per-file `# max_new_tokens: N` directives override the
        # backend default — mixed budgets let the slot grid refill
        # per-request instead of per-batch
        budgets = [
            b if b is not None else self.max_new_tokens
            for _, b in parsed
        ]
        # validate EVERY prompt against server capacity before
        # submitting ANY: a mid-batch submit() failure would leave the
        # earlier requests queued in the shared server (decoded and
        # discarded on the next batch — and again per requeue retry),
        # and the server's own error has no file path in it
        for p, prompt, budget in zip(paths, prompts, budgets):
            if prompt.size + budget > self.server.max_len:
                raise ValueError(
                    f"{p}: prompt of {prompt.size} tokens + budget "
                    f"{budget} exceeds the server's "
                    f"max_len {self.server.max_len}"
                )
        cbs = self._token_cbs(paths, on_token)
        # a traced ingress request's `lm_request` span hangs under the
        # worker's `infer` span: the job service keys the batch's trace
        # contexts by local path (contextvars ride asyncio.to_thread)
        by_path = {c.key: c for c in current_all_ctxs() if c.sampled}
        trace = [by_path.get(p) for p in paths] if by_path else None
        fixed: List[Dict[str, Any]] = []
        if self.overlap:
            t0 = time.monotonic()
            toks = self.driver.serve(
                prompts, budgets, on_dispatch=on_dispatch,
                on_token=cbs, trace=trace, fixed_at=fixed,
            )
            infer_time = time.monotonic() - t0
            results = {
                p: {"tokens": [int(t) for t in ts]}
                for p, ts in zip(paths, toks)
            }
        else:
            with self._serve_lock:
                # clock starts INSIDE the lock: waiting out an orphaned
                # preempted decode is queueing, not this batch's cost —
                # it must not inflate the scheduler's per_query model
                t0 = time.monotonic()
                rids = self.server.submit_many(
                    prompts, budgets, on_token=cbs, trace=trace
                )
                # run(rids): drain only OUR requests — a bare run()
                # would also consume (and discard) results of any
                # in-flight driver tickets sharing the grid
                done = self.server.run(rids)
                steps = self.server.take_fixed_at(rids)
                fixed = [steps.get(rid) for rid in rids]
                infer_time = time.monotonic() - t0
            results = {
                p: {"tokens": [int(t) for t in done[rid]]}
                for p, rid in zip(paths, rids)
            }
        if self.server.diffusion is not None:
            # per token, the denoising step of its block that fixed it
            for p, steps in zip(paths, fixed):
                results[p].update(steps)
        if paths:
            # overlap mode: a ticket's wall includes sharing the grid
            # with other in-flight batches — that IS its marginal
            # serving cost, which is what the fair-share model wants
            self._per_query = infer_time / len(paths)
            self._per_forward = self.server.forward_seconds
        return results, infer_time, self.cost_constants()

    async def backend(
        self, model: str, paths: Sequence[str], on_dispatch=None,
        on_token=None,
    ) -> Tuple[Dict[str, Any], float, Dict[str, float]]:
        """JobService-compatible coroutine; the blocking decode runs in
        a thread so the node's event loop stays live (same pattern as
        the engine's infer_files_async). Declaring `on_dispatch` opts
        in to the job pipeline's promote-at-dispatch (jobs/service.py
        detects the parameter): the staged next batch starts the
        moment this batch's prompts are in the driver's grid.
        Declaring `on_token` opts in to ingress per-request token
        streaming: the service fans each delivered token out to the
        request's data-plane stream as the grid reads it back."""
        del model
        return await asyncio.to_thread(
            self.serve_files, paths, on_dispatch, on_token
        )

    def close(self) -> None:
        """Stop the driver thread (idempotent); in-flight work
        finishes first."""
        self.driver.stop()
        if self.kv_cache is not None:
            self.kv_cache.close()

    def set_kv_cache_enabled(self, enabled: bool) -> None:
        """Toggle the prefix cache WITHOUT dropping its contents —
        the bench's warm-vs-cold comparison flips this to run the
        same backend both ways. No-op when the backend was built
        without a cache budget."""
        if self.kv_cache is None:
            return
        self.server.enable_kv_cache(self.kv_cache if enabled else None)

    def kv_cache_stats(self) -> Optional[Dict[str, int]]:
        """Prefix-cache counters (None when disabled) — the bench's
        multi-turn phase aggregates these per worker."""
        return None if self.kv_cache is None else self.kv_cache.stats()

    def spec_stats(self) -> Optional[Dict[str, Any]]:
        """Speculative-decoding acceptance accounting (None when spec
        was never enabled) — LMServer.spec_stats passthrough; the
        bench's declared-acceptance gate reads the MEASURED rate from
        here."""
        return self.server.spec_stats()

    def decode_tokens_total(self) -> int:
        """Delivered-token count of THIS backend's server — the
        steady-state bench samples this on a fixed cadence to build
        its tok/s-vs-wall curve (the registry's
        lm_server_decode_tokens_total is process-global and would
        conflate co-resident servers)."""
        return int(self.server.tokens_delivered)

    def forwards_per_request(self, new_tokens: Optional[int] = None) -> int:
        """Forwards over the grid a request of `new_tokens` (default:
        the backend's budget) costs (`cost_model.lm_request_forwards`)."""
        df = self.server.diffusion
        return lm_request_forwards(
            self.max_new_tokens if new_tokens is None else new_tokens,
            self.cfg.block_length, df.steps if df is not None else 1)

    def _priced(self) -> float:
        """Seconds a request costs the scheduler: the measured wall a
        query of the last ticket, or, for a block-diffusion model once
        a dispatch was measured, forwards x seconds a forward ÷ slots."""
        if self._per_forward is None:
            return self._per_query
        return (self._per_forward * self.forwards_per_request()
                / self.server.max_slots)

    def cost_constants(self) -> Dict[str, float]:
        return {
            "load_time": 0.0,
            "first_query": self._priced(),
            "per_query": self._priced(),
            "batch_size": self.server.max_slots,
        }

    def cost(self) -> ModelCost:
        """Initial scheduler cost (refined from ACK measurements)."""
        return ModelCost(
            load_time=0.0,
            first_query=self._priced(),
            per_query=self._priced(),
            download_time=0.0,
            batch_size=self.server.max_slots,
        )

    def serve_prefilled(
        self,
        prompts: Sequence[np.ndarray],
        budgets: Sequence[int],
        slabs: Sequence[Dict[str, Any]],
        on_token=None,
    ) -> Tuple[List[np.ndarray], float]:
        """Decode a batch whose prefill happened ELSEWHERE: each slab
        ({"rows": per-layer KV cache for positions < len(prompt),
        "first_token": the token prefill sampled}) adopts a slot via
        `LMServer.submit_prefilled` and decodes to its budget. Returns
        (per-prompt generated tokens in order, decode seconds).

        The whole-slab convenience form of `serve_prefilled_stream`:
        every slab is already host-side, so the arrival queue is
        pre-filled. Failure discipline is PER REQUEST (a slab that
        cannot be adopted falls back to a local prefill of that one
        prompt); greedy outputs are identical either way."""
        import queue as _queue

        if len(prompts) != len(slabs) or len(prompts) != len(budgets):
            raise ValueError("prompts/budgets/slabs length mismatch")
        arrivals: "_queue.Queue" = _queue.Queue()
        for i, slab in enumerate(slabs):
            arrivals.put((i, slab))
        toks, infer_time, _ = self.serve_prefilled_stream(
            prompts, budgets, arrivals, on_token=on_token
        )
        return toks, infer_time

    def serve_prefilled_stream(
        self,
        prompts: Sequence[np.ndarray],
        budgets: Sequence[int],
        arrivals,  # queue.Queue of (index, slab_entry_or_None)
        on_token=None,
        on_first_token=None,
        arrival_timeout: float = 120.0,
    ) -> Tuple[List[np.ndarray], float, Dict[str, int]]:
        """Decode a batch whose KV slabs ARRIVE INCREMENTALLY (the
        chunk-streamed handoff, inference/lm_sharded.py): `arrivals`
        is a thread-safe queue that eventually yields exactly one
        ``(index, entry)`` item per prompt — `entry` is the slab dict
        to adopt, or None to run a LOCAL prefill for that request (a
        failed/faulted handoff). Requests adopt slots AS THEIR SLABS
        LAND, so decode of early arrivals overlaps the peer's
        remaining prefill compute — the first decoded token can leave
        before the last slab chunk is even computed.

        Failure discipline is PER REQUEST: an entry whose adoption
        fails (drifted peer spec, lying shapes) demotes to a local
        prefill of that one prompt; nothing fails the batch. Returns
        ``(per-prompt tokens in order, decode seconds,
        {"adopted": n, "local": n})``.

        `on_token` is the per-prompt callback list/None (the streaming
        contract, see serve_files); `on_first_token` fires ONCE at the
        batch's first delivered token (TTFT measurement hook). Drives
        the raw server serially under the serve lock (the
        disaggregated group primary is ONE scheduler slot)."""
        import queue as _queue

        if len(prompts) != len(budgets):
            raise ValueError("prompts/budgets length mismatch")
        if self.server.temperature != 0.0:
            # sampled streams are keyed by THIS server's rids, which
            # the prefill node cannot know — disaggregation is a
            # greedy-serving form (see LMServer.submit_prefilled)
            raise ValueError(
                "disaggregated decode requires temperature == 0"
            )
        n = len(prompts)
        first_fired = [False]

        def _cb(i: int):
            inner = on_token[i] if on_token is not None else None

            def fire(t: int) -> None:
                if not first_fired[0]:
                    first_fired[0] = True
                    if on_first_token is not None:
                        try:
                            on_first_token()
                        except Exception as e:
                            # a TTFT probe hook, never a decode error —
                            # but a broken hook must be visible
                            log.warning("on_first_token hook failed: %r", e)
                if inner is not None:
                    inner(t)

            return fire

        srv = self.server
        stats = {"adopted": 0, "local": 0}
        with self._serve_lock:
            t0 = time.monotonic()
            received = 0
            to_adopt: List[Tuple[int, Dict[str, Any]]] = []
            rids: List[Optional[int]] = [None] * n
            done: Dict[int, np.ndarray] = {}

            def submit_local(idx: int) -> None:
                rids[idx] = srv.submit_many(
                    [prompts[idx]], [budgets[idx]],
                    on_token=[_cb(idx)],
                )[0]
                stats["local"] += 1

            try:
                while True:
                    # 1) drain arrivals; block only when the grid has
                    # nothing to chew on (otherwise decode overlaps
                    # the wait for the next slab)
                    block = (
                        received < n and not to_adopt
                        and not srv.has_work()
                    )
                    while received < n:
                        try:
                            idx, entry = arrivals.get(
                                block=block,
                                timeout=arrival_timeout if block else None,
                            ) if block else arrivals.get_nowait()
                        except _queue.Empty:
                            if block:
                                raise TimeoutError(
                                    "KV slab arrivals stalled "
                                    f"({received}/{n} after "
                                    f"{arrival_timeout:g}s idle)"
                                )
                            break
                        block = False
                        received += 1
                        if entry is None:
                            submit_local(idx)
                        else:
                            to_adopt.append((idx, entry))
                    # 2) adopt landed slabs into free slots; a bad slab
                    # demotes to a local prefill of ITS request only
                    while to_adopt and srv.free_slot_count() > 0:
                        idx, entry = to_adopt.pop(0)
                        try:
                            rids[idx] = srv.submit_prefilled(
                                prompts[idx], budgets[idx],
                                entry["rows"], entry["first_token"],
                                on_token=_cb(idx),
                                # remote-draft shipment: a prefill
                                # peer's speculative proposals rode
                                # the slab; they seed this request's
                                # first verify round (dropped when
                                # spec decode is off — values never
                                # depend on them)
                                draft_tokens=entry.get("draft"),
                            )
                            stats["adopted"] += 1
                        except Exception as e:
                            log.warning(
                                "slab adoption failed for request %d "
                                "(%r); local prefill", idx, e,
                            )
                            submit_local(idx)
                    # 3) advance the grid
                    if srv.has_work():
                        srv.step()
                    done.update(srv.take_done())
                    if (
                        received >= n and not to_adopt
                        and all(r is not None for r in rids)
                        and all(r in done for r in rids)
                    ):
                        break
            except Exception:
                # arrivals stalling/dying must not leave the earlier
                # requests occupying the grid: drain them to completion
                # and discard, so the caller's fallback starts clean
                live = [r for r in rids if r is not None and r not in done]
                if live:
                    srv.run(live)
                raise
            infer_time = time.monotonic() - t0
        if n:
            self._per_query = infer_time / n
        return [done[rid] for rid in rids], infer_time, stats

    @staticmethod
    def _draft_spec_of(spec: Dict[str, Any]) -> Optional[Dict[str, Any]]:
        """The draft-model spec a serving spec implies: absent/None +
        spec_k>0 derives one via `config.draft_lm_spec`; a dict is
        treated as OVERRIDES onto the derived spec (full replacement
        when it carries its own vocab_size/d_model); False opts out
        of a local draft (shipped-draft-only verification)."""
        if int(spec.get("spec_k", 0) or 0) <= 0:
            return None
        sd = spec.get("spec_draft")
        if sd is False:
            return None
        from ..config import draft_lm_spec

        if sd is None:
            return draft_lm_spec(spec)
        if not isinstance(sd, dict):
            raise ValueError(
                f"spec_draft must be a dict or false, got {sd!r}"
            )
        if "vocab_size" in sd and "d_model" in sd:
            return dict(sd)  # a complete draft spec of its own
        return draft_lm_spec(spec, **sd)

    @classmethod
    def from_spec(cls, spec: Dict[str, Any]) -> "LMBackend":
        """Build from a JSON-able spec — the CLI's `--lm-spec` file,
        so operators register LM serving without writing Python:

            {"name": "LM", "vocab_size": 256, "d_model": 64,
             "n_heads": 4, "n_kv_heads": 2, "n_layers": 2,
             "max_new_tokens": 32, "max_slots": 4, "max_len": 1024,
             "weights": null}

        Weights are DETERMINISTIC from `seed` — every node that loads
        the same spec builds the IDENTICAL tree (the LM analog of the
        engine's deterministic CNN init; required for exactness across
        workers) — unless `weights` names a local flax-msgpack file
        produced by `params_io.variables_to_bytes({"params": ...})`
        (e.g. fetched from the replicated store with `get`).
        """
        params, cfg = lm_spec_parts(spec)
        max_new = int(spec.get("max_new_tokens", 32))
        # default chunk ≈ the per-request budget (capped): every step's
        # packed readback is a host synchronization, so a 32-token budget
        # at chunk 16 pays twice as many for the same tokens.
        # Operators with mixed budgets set chunk explicitly (smaller =
        # finer continuous-batching join granularity).
        chunk_default = max(1, min(max_new, 32))
        be = cls(
            params, cfg,
            max_new_tokens=max_new,
            max_slots=int(spec.get("max_slots", 4)),
            max_len=int(spec.get("max_len", 1024)),
            chunk=int(spec.get("chunk", chunk_default)),
            temperature=float(spec.get("temperature", 0.0)),
            top_k=(
                int(spec["top_k"]) if spec.get("top_k") is not None
                else None
            ),
            seed=int(spec.get("seed", 0)),
            # {"kv_cache_mb": 256} turns on the worker-resident KV
            # prefix cache with that host-bytes budget (0/absent =
            # off, today's behavior)
            kv_cache_bytes=int(
                float(spec.get("kv_cache_mb", 0) or 0) * (1 << 20)
            ),
            # {"spec_k": 4} turns on speculative decoding with a
            # config.draft_lm_spec-derived draft (or {"spec_draft":
            # {...}} overrides / a full replacement draft spec);
            # {"spec_draft": false} arms shipped-draft verification
            # only. Greedy outputs stay identical either way.
            spec_k=int(spec.get("spec_k", 0) or 0),
            spec_draft=LMBackend._draft_spec_of(spec),
            spec_min_accept=spec.get("spec_min_accept"),
            # a block_causal model generates by diffusion over blocks:
            # {"denoising_steps": 2, "remasking": ..., "mask_token_id": N}
            # (lm_arch has checked them); `chunk` is then the tokens a
            # slot a dispatch, in whole blocks
            diffusion=(
                BlockDiffusion(
                    steps=int(spec.get("denoising_steps", 1)),
                    mask_token_id=int(spec["mask_token_id"]),
                    remasking=spec.get("remasking", REMASKING[0]),
                ) if cfg.mask_block > 1 else None
            ),
        )
        # operators pick the serving concurrency mode per deployment
        # ({"overlap": false}): the driver's cross-batch batching wins
        # on multi-core TPU hosts; a 1-core co-located cluster can
        # prefer the lock-serialized path (bench `cluster_lm_serving`
        # measures both every round)
        if spec.get("overlap") is not None:
            be.overlap = bool(spec["overlap"])
        return be


def write_prompt_file(
    path: str,
    tokens: Sequence[int],
    max_new_tokens: Optional[int] = None,
) -> None:
    """Inverse of parse_prompt_file — the client-side helper for
    seeding prompt files into the store. `max_new_tokens` emits the
    per-request budget directive."""
    if max_new_tokens is not None and int(max_new_tokens) < 1:
        # reject at the WRITER: a bad budget seeded into the store
        # would otherwise fail at every worker's parse as repeated
        # batch FAILs instead of one loud client-side error
        raise ValueError("max_new_tokens must be >= 1")
    with open(path, "w") as f:
        if max_new_tokens is not None:
            f.write(f"# max_new_tokens: {int(max_new_tokens)}\n")
        f.write(" ".join(str(int(t)) for t in tokens))
