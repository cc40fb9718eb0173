"""Perf-claim hygiene (VERDICT r4 item 7): every performance number in
README.md / PARITY.md PROSE must either trace to the canonical bench
artifact (the file the generated BENCH-TABLE block is stamped with) or
carry an explicit run label.

Round 4 shipped three drifted claims (README "86.5 tok/s" vs artifact
79.6; a punch-list "197.7 q/s" from an unlabeled non-canonical run;
int8-KV prose "1.10×" vs artifact 1.02×) — numbers quoted from
whatever run looked best, not the artifact of record. The generated
table can't drift (sha-stamped, test-enforced); this module extends
the same discipline to prose: a perf number is OK iff

- it appears inside the generated BENCH-TABLE block (already checked
  by test_parity_table.py), or
- it matches an artifact number OF THE SAME KIND within claim
  rounding — × ratios match only ratio-like keys (speedup/gain/
  ratio/vs), MFU percents only mfu-like keys, rates/times any
  numeric leaf (plus rate<->ms conversions). Kind-scoping matters:
  against the artifact's thousands of numbers an unscoped 6%
  tolerance would have PASSED the very 1.10×-vs-1.02 drift this
  tool exists to catch, or
- its line (or its section's heading) carries a run label (``r3``,
  ``round-2``, ``git <sha>``, a ``BENCH_r*`` file name) or quotes
  the reference/baseline — i.e. the reader is told which run the
  number belongs to.

Used by tests/test_claim_hygiene.py; run standalone for a report:

    python -m dml_tpu.tools.claim_check
"""

from __future__ import annotations

import json
import math
import os
import re
from typing import Any, Dict, Iterator, List, Optional, Tuple

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# a number immediately followed by a perf unit = a perf claim. The ×
# form catches speedup claims ("1.10×"); percentages only when
# explicitly about MFU/util (bare % is too generic).
_UNIT = (
    r"(?:gen\s+)?tok/s|q/s|img/s|queries/sec|ms/image|ms/step|ms/tok"
    r"|ms\b|µs|MB/s|GB/s|TF/s|MB/slot|×"
)
CLAIM_RE = re.compile(
    rf"(~?)(\d[\d,]*(?:\.\d+)?)\s*(k?)\s*({_UNIT})"
)
MFU_RE = re.compile(r"(\d+(?:\.\d+)?)\s*%\s*(?:fwd\+bwd\s+)?(?:MFU|util)",
                    re.IGNORECASE)

# a line carrying any of these tells the reader which run/source the
# number belongs to — labeled claims are exempt from artifact matching.
# bound/ceiling/ideal need the lookbehind: "HBM-bound"/"control-plane-
# bound" is prose style, not a derivation label (an r4 drifted claim
# sat on exactly such a line)
LABEL_RE = re.compile(
    r"\br[1-9]\b|round[- ][1-9]|git [0-9a-f]{7,}|BENCH_r\d+"
    r"|reference|baseline|CS425|spec peak"
    r"|(?<!-)\b(?:ideal|ceiling|bound)\b"
    r"|roofline|test\.py|worker\.py",
    re.IGNORECASE,
)

RATIO_KEY_RE = re.compile(
    r"speedup|gain|ratio|vs_|pipelining|_x$", re.IGNORECASE
)
MFU_KEY_RE = re.compile(r"mfu|util", re.IGNORECASE)
# rate-like artifact keys (tok/s, q/s, img/s, MB/s...) — rate claims
# match ONLY these: against the unscoped number soup the r4 stale
# "197.7 q/s" false-passed by colliding with params_millions
RATE_KEY_RE = re.compile(
    r"per_s|qps|tok_s|img_s|mb_per|gb_per", re.IGNORECASE
)
TIME_KEY_RE = re.compile(
    r"_ms|ms_|\bms\b|latency|wall_s|_s$|time|detect", re.IGNORECASE
)
SIZE_KEY_RE = re.compile(r"mb|bytes|gb\b", re.IGNORECASE)

GEN_BEGIN = "<!-- BENCH-TABLE:BEGIN"
GEN_END = "<!-- BENCH-TABLE:END -->"


def canonical_artifact_path(
    parity_path: Optional[str] = None,
) -> Optional[str]:
    """The artifact of record = the file PARITY's generated table is
    stamped with (``source=...`` in the BENCH-TABLE marker). None when
    the marker says ``source=none``: nothing has been measured on the
    current installation, so there is no artifact and every unlabeled
    perf claim in the docs is a violation."""
    from .parity_table import NO_SOURCE

    parity_path = parity_path or os.path.join(REPO, "PARITY.md")
    with open(parity_path) as f:
        for line in f:
            m = re.search(r"BENCH-TABLE:BEGIN source=(\S+)", line)
            if m:
                if m.group(1) == NO_SOURCE:
                    return None
                return os.path.join(REPO, m.group(1))
    raise ValueError(f"no BENCH-TABLE source marker in {parity_path}")


def artifact_numbers(path: Optional[str]) -> Dict[str, List[float]]:
    """Kind-bucketed numeric leaves of the artifact (every bucket empty
    for ``path=None``, no artifact of record):

    - ``ratio``: values under ratio-like keys (speedup/gain/ratio/vs)
    - ``mfu``: values under mfu/util keys, plus their ×100 percents
    - ``rate``: values under rate-like keys (tok/s, q/s, MB/s...)
    - ``time``: values under time-like keys (ms, latency, wall) plus
      the two honest restatements — 1000/rate (rate -> ms/item) and
      seconds-keys × 1000
    - ``size``: values under MB/bytes keys
    - ``flops``: peak/flops values scaled to TF/s

    Every claim matches only its OWN kind — against the unscoped
    union a stale rate can false-pass by colliding with an unrelated
    leaf (r4's "197.7 q/s" equals the artifact's params_millions).

    The artifact may be a raw bench stdout OR a driver wrapper whose
    tail holds only the compact summary line — parity_table.load_bench
    recovers either form, so the artifact of record can be the driver
    capture itself."""
    from .parity_table import load_bench

    data = load_bench(path) if path is not None else {}
    buckets: Dict[str, List[float]] = {
        "ratio": [], "mfu": [], "rate": [], "time": [], "size": [],
        "flops": [],
    }

    def walk(x: Any, key: str) -> None:
        if isinstance(x, bool):
            return
        if isinstance(x, (int, float)):
            if not math.isfinite(x):
                return
            v = float(x)
            if RATIO_KEY_RE.search(key):
                buckets["ratio"].append(v)
            if MFU_KEY_RE.search(key):
                buckets["mfu"].append(v)
                buckets["mfu"].append(v * 100.0)
            if RATE_KEY_RE.search(key):
                buckets["rate"].append(v)
            if TIME_KEY_RE.search(key):
                buckets["time"].append(v)
                buckets["time"].append(v * 1000.0)  # s-keyed -> ms
            if SIZE_KEY_RE.search(key):
                buckets["size"].append(v)
            if "flops" in key.lower():
                buckets["flops"].append(v / 1e12)
            return
        if isinstance(x, dict):
            for k, v in x.items():
                walk(v, str(k))
        elif isinstance(x, (list, tuple)):
            for v in x:
                walk(v, key)

    walk(data, "")
    buckets["time"] += [
        1000.0 / n for n in buckets["rate"] if n > 0
    ]
    return buckets


_UNIT_BUCKET = {
    "×": "ratio", "%MFU": "mfu", "TF/s": "flops", "MB/slot": "size",
    "ms": "time", "µs": "time", "ms/image": "time", "ms/step": "time",
    "ms/tok": "time",
}


def _bucket_for(unit: str) -> str:
    return _UNIT_BUCKET.get(unit, "rate")


def _close(value: float, pool: List[float], rel: float) -> bool:
    return any(
        math.isclose(value, a, rel_tol=rel, abs_tol=1e-9) for a in pool
    )


def _claim_matches(value: float, unit: str, kilo: bool, approx: bool,
                   buckets: Dict[str, List[float]]) -> bool:
    if unit == "×":
        # ratios are quoted to 2-3 sig figs; 2.5% separates 1.10 from
        # 1.02 while passing honest rounding like 1.94 for 1.938. An
        # explicit "~" buys an approximation band ("~2×" for 1.94) —
        # wide, but a genuinely drifted ratio (1.10 for 1.02, or r4's
        # "~100×" README prefill claim vs the artifact's 162.7) still
        # trips it
        return _close(value, buckets["ratio"], 0.12 if approx else 0.025)
    if unit == "%MFU":
        return _close(value, buckets["mfu"], 0.02)
    digits = len(re.sub(r"\D", "", f"{value:g}"))
    rel = 0.03 if (kilo or digits <= 2) else 0.015 if digits == 3 else 0.006
    if approx:
        rel = max(rel, 0.12)
    return _close(value, buckets[_bucket_for(unit)], rel)


def iter_prose_claims(
    path: str,
) -> Iterator[Tuple[int, str, float, str, bool, bool]]:
    """(line_no, line, value, unit, kilo, approx) for every perf claim
    in UNLABELED prose — generated blocks, code fences, and sections
    whose heading carries a run label are skipped."""
    in_gen = False
    in_code = False
    heading_labeled = False
    with open(path) as f:
        for i, line in enumerate(f, 1):
            if GEN_BEGIN in line:
                in_gen = True
            if GEN_END in line:
                in_gen = False
                continue
            if line.strip().startswith("```"):
                in_code = not in_code
                continue
            if in_gen or in_code:
                continue
            if line.startswith("#"):
                # a run label on a heading covers its whole section
                # ("## LM decode analysis (round 4)")
                heading_labeled = bool(LABEL_RE.search(line))
                continue
            if heading_labeled or LABEL_RE.search(line):
                continue
            for m in CLAIM_RE.finditer(line):
                approx, raw, kilo, unit = m.groups()
                v = float(raw.replace(",", ""))
                if kilo:
                    v *= 1000.0
                yield i, line, v, unit, bool(kilo), bool(approx)
            for m in MFU_RE.finditer(line):
                yield i, line, float(m.group(1)), "%MFU", False, False


def check_file(
    path: str, buckets: Dict[str, List[float]]
) -> List[Tuple[int, str, float, str]]:
    """Violations: unlabeled prose perf claims matching nothing of
    their kind in the canonical artifact."""
    bad = []
    for i, line, v, unit, kilo, approx in iter_prose_claims(path):
        if not _claim_matches(v, unit, kilo, approx, buckets):
            bad.append((i, line.rstrip(), v, unit))
    return bad


def run_check(
    artifact_path: Optional[str] = None,
) -> Dict[str, List[Tuple[int, str, float, str]]]:
    buckets = artifact_numbers(
        artifact_path or canonical_artifact_path()
    )
    out = {}
    for name in ("README.md", "PARITY.md"):
        out[name] = check_file(os.path.join(REPO, name), buckets)
    return out


# ----------------------------------------------------------------------
# bench-artifact metrics block (observability.bench_metrics_block)
# ----------------------------------------------------------------------

#: first round whose bench ran with the typed metrics registry; older
#: BENCH_r* artifacts predate it and are exempt from the block check
METRICS_REQUIRED_FROM_ROUND = 6

_ROUND_RE = re.compile(r"BENCH_r(\d+)", re.IGNORECASE)


def artifact_round(path: str) -> Optional[int]:
    m = _ROUND_RE.search(os.path.basename(path))
    return int(m.group(1)) if m else None


def check_metrics_block(path: str) -> List[str]:
    """Validate that a bench artifact carries the observability
    registry's ``metrics`` block (counters/gauges/histograms summary,
    ``schema`` stamp) — a bench that silently dropped instrumentation
    would otherwise publish headline numbers with no per-stage
    breakdown behind them. Returns a list of problems (empty = OK).

    Artifacts from rounds before ``METRICS_REQUIRED_FROM_ROUND`` are
    exempt (the registry didn't exist); an unnumbered artifact is held
    to the new standard. When the artifact's LM sections actually ran
    (neither skipped by the wall budget nor errored), the lm_server
    decode counters must be nonzero — an instrumented serve that
    counted nothing means the hot path lost its hooks."""
    name = os.path.basename(path)
    rnd = artifact_round(path)
    if rnd is not None and rnd < METRICS_REQUIRED_FROM_ROUND:
        return []
    from .parity_table import load_bench

    data = load_bench(path)
    if data.get("_summary_only"):
        # driver-tail compact form: the matrix-level blocks live in
        # the same-round preview; nothing to validate here
        return []
    block = data.get("metrics")
    if not isinstance(block, dict):
        return [f"{name}: no `metrics` block (bench instrumentation "
                "dropped? see observability.bench_metrics_block)"]
    if "error" in block and "counters" not in block:
        return [f"{name}: metrics block capture failed: {block['error']}"]
    problems = []
    for key in ("counters", "gauges", "histograms"):
        if not isinstance(block.get(key), dict):
            problems.append(f"{name}: metrics.{key} missing or not a dict")
    if problems:
        return problems
    for k, h in block["histograms"].items():
        if not isinstance(h, dict) or "count" not in h:
            problems.append(
                f"{name}: metrics.histograms[{k!r}] lacks a count"
            )
            break
    matrix = data.get("matrix", {})
    not_run = set(matrix.get("_skipped", {})) | set(matrix.get("_errors", {}))
    lm_ran = not {"lm", "cluster_lm_serving"} <= not_run
    if lm_ran and not any(
        k.startswith("lm_server_decode_tokens_total") and v
        for k, v in block["counters"].items()
    ):
        problems.append(
            f"{name}: LM sections ran but lm_server_decode_tokens_total "
            "is zero/absent — the decode path lost its instrumentation"
        )
    return problems


def run_metrics_check(artifact_path: Optional[str] = None) -> List[str]:
    path = artifact_path or canonical_artifact_path()
    return check_metrics_block(path) if path is not None else []


# ----------------------------------------------------------------------
# chaos section (bench _bench_chaos / cluster/chaos.py)
# ----------------------------------------------------------------------

#: first round whose bench carries the chaos soak section; earlier
#: artifacts predate the chaos engine and are exempt
CHAOS_REQUIRED_FROM_ROUND = 7

#: first round whose chaos section must ALSO carry the per-family
#: adversarial scenario sweeps (asym/disk/dns/skew/fuzz) and the
#: malformed-drop evidence; earlier artifacts predate them
CHAOS_SCENARIOS_REQUIRED_FROM_ROUND = 8

#: the adversarial families the bench must sweep (mirror of
#: cluster/chaos.py SCENARIO_FAMILIES — kept literal here so this
#: tool stays importable without the cluster stack)
CHAOS_SCENARIO_FAMILIES = ("asym", "disk", "dns", "skew", "fuzz",
                           "churn", "elastic", "liar", "autoscale",
                           "train")

#: "churn" (sustained seeded join/leave) landed with the round-12
#: control-plane scale work; earlier artifacts predate the family
CHAOS_CHURN_REQUIRED_FROM_ROUND = 12

#: "elastic" (authenticated scale-out mid-load, graceful LEAVE,
#: join flapping, forged-join storms) landed with the round-18
#: elastic-membership work; earlier artifacts predate the family
CHAOS_ELASTIC_REQUIRED_FROM_ROUND = 18

#: "liar" (a worker whose self-reported batch walls understate its
#: real walls — the straggler cross-check's adversary) landed with
#: the round-19 signal-plane work; earlier artifacts predate it
CHAOS_LIAR_REQUIRED_FROM_ROUND = 19

#: "autoscale" (controller-aimed chaos: thrashing load, liar-fed
#: policy, scale-in racing a demand spike, leader kill mid-decision)
#: landed with the round-20 autoscaler work; earlier artifacts
#: predate the family
CHAOS_AUTOSCALE_REQUIRED_FROM_ROUND = 20

#: "train" (trainer-aimed chaos: trainer kill mid-epoch, leader kill
#: mid-checkpoint, capacity join racing a step boundary) landed with
#: the round-22 elastic-training work; earlier artifacts predate it
CHAOS_TRAIN_REQUIRED_FROM_ROUND = 22


def check_chaos_block(path: str) -> List[str]:
    """Validate a bench artifact's ``chaos`` section WHEN IT RAN
    (neither wall-budget-skipped nor errored): the invariant sweeps
    must all have passed, and the recovery walls — failover and
    replication repair — must be present, finite, and nonzero. A
    chaos section that 'ran' but recorded no recovery evidence means
    the fault events never actually bit. From round 8 on the section
    must also carry one green sweep per adversarial scenario family
    and, since the fuzz family ran, a nonzero malformed-drop counter
    (a fuzz run that dropped nothing means the byzantine datagrams
    never reached the wire). Returns problems (empty = OK)."""
    name = os.path.basename(path)
    rnd = artifact_round(path)
    if rnd is not None and rnd < CHAOS_REQUIRED_FROM_ROUND:
        return []
    from .parity_table import load_bench

    data = load_bench(path)
    if data.get("_summary_only"):
        return []  # matrix-level block lives in the same-round preview
    matrix = data.get("matrix", {})
    not_run = set(matrix.get("_skipped", {})) | set(matrix.get("_errors", {}))
    if "chaos" in not_run:
        return []  # honestly recorded as skipped/errored
    block = matrix.get("chaos")
    if block is None:
        if rnd is None and "cluster_serving" not in matrix:
            return []  # partial/preview artifact without cluster runs
        return [f"{name}: no `chaos` section and not recorded as "
                "skipped (bench lost its chaos soak?)"]
    problems = []
    if not block.get("all_invariants_ok"):
        bad = [s for s in block.get("per_seed", [])
               if not s.get("invariants_ok")]
        problems.append(
            f"{name}: chaos invariant sweep failed for seeds "
            f"{[s.get('seed') for s in bad]}"
        )
    for key in ("failover_recovery_s", "store_repair_s"):
        v = block.get(key)
        if not isinstance(v, (int, float)) or not math.isfinite(v) or v <= 0:
            problems.append(
                f"{name}: chaos.{key} = {v!r} (recovery wall missing, "
                "nonfinite, or zero — the fault plan never bit)"
            )
    if rnd is not None and rnd < CHAOS_SCENARIOS_REQUIRED_FROM_ROUND:
        return problems
    scenarios = block.get("scenarios")
    if not isinstance(scenarios, dict):
        problems.append(
            f"{name}: chaos.scenarios missing (the adversarial "
            "family sweeps were dropped from the bench?)"
        )
        return problems
    for fam in CHAOS_SCENARIO_FAMILIES:
        if (
            fam == "churn"
            and rnd is not None
            and rnd < CHAOS_CHURN_REQUIRED_FROM_ROUND
        ):
            continue  # the family predates this artifact
        if (
            fam == "elastic"
            and rnd is not None
            and rnd < CHAOS_ELASTIC_REQUIRED_FROM_ROUND
        ):
            continue  # the family predates this artifact
        if (
            fam == "liar"
            and rnd is not None
            and rnd < CHAOS_LIAR_REQUIRED_FROM_ROUND
        ):
            continue  # the family predates this artifact
        if (
            fam == "autoscale"
            and rnd is not None
            and rnd < CHAOS_AUTOSCALE_REQUIRED_FROM_ROUND
        ):
            continue  # the family predates this artifact
        if (
            fam == "train"
            and rnd is not None
            and rnd < CHAOS_TRAIN_REQUIRED_FROM_ROUND
        ):
            continue  # the family predates this artifact
        entry = scenarios.get(fam)
        if not isinstance(entry, dict):
            problems.append(f"{name}: chaos.scenarios[{fam!r}] missing")
        elif not entry.get("all_invariants_ok"):
            bad = [s.get("seed") for s in entry.get("per_seed", [])
                   if not s.get("invariants_ok")]
            problems.append(
                f"{name}: chaos scenario {fam!r} invariant sweep "
                f"failed for seeds {bad}"
            )
    if isinstance(scenarios.get("fuzz"), dict):
        dropped = block.get("malformed_dropped_total")
        if not isinstance(dropped, (int, float)) or dropped <= 0:
            problems.append(
                f"{name}: fuzz scenario ran but "
                f"malformed_dropped_total = {dropped!r} (byzantine "
                "datagrams never hit the transport, or the drop "
                "counter lost its hook)"
            )
    return problems


def run_chaos_check(artifact_path: Optional[str] = None) -> List[str]:
    return check_chaos_block(artifact_path or canonical_artifact_path())


# ----------------------------------------------------------------------
# round-6 serving fields: adaptive pipeline depth, steady-state LM
# (bench _bench_cluster_serving / _bench_cluster_lm; ISSUE 4 tentpole)
# ----------------------------------------------------------------------

#: first round whose bench carries the adaptive-depth verdict and the
#: steady-state LM phase; earlier artifacts predate them
SERVING_FIELDS_REQUIRED_FROM_ROUND = 6

#: adaptive-vs-best-static serving ratio below this is a controller
#: that committed to a LOSING depth — more than probe noise can excuse
#: (the r5 failure mode this machinery exists to end was 0.91×)
ADAPTIVE_RATIO_FLOOR = 0.9

#: the steady-state LM phase must cover at least this much post-ramp
#: decode wall, or it is still the transient the r5 verdict rejected
STEADY_MIN_S = 15.0


def check_serving_block(path: str) -> List[str]:
    """Validate the round-6 serving fields WHEN their sections ran:

    - ``cluster_serving.adaptive`` records the depth controller's
      verdict, and ``pipelining_speedup`` (adaptive vs the BETTER
      forced static on the same capture) is not below the probe-noise
      floor — a shipped mode that loses in the artifact of record is
      the r5 failure this exists to end;
    - ``cluster_lm_serving.steady_state`` covers >= ``STEADY_MIN_S``
      of post-ramp decode with a tok/s-vs-wall curve — the transient
      64×32 run cannot distinguish a control-plane ceiling from an
      unwarmed pipeline.

    Artifacts before round 6 are exempt; summary-only driver captures
    are spot-checked at summary level (the full fields live in the
    same-round preview)."""
    from .parity_table import load_bench

    name = os.path.basename(path)
    rnd = artifact_round(path)
    if rnd is not None and rnd < SERVING_FIELDS_REQUIRED_FROM_ROUND:
        return []
    data = load_bench(path)
    problems: List[str] = []
    if data.get("_summary_only"):
        s = data.get("summary") or {}
        ratio = s.get("cluster_pipelining")
        if (
            isinstance(ratio, (int, float))
            and ratio < ADAPTIVE_RATIO_FLOOR
        ):
            problems.append(
                f"{name}: summary cluster_pipelining = {ratio} < "
                f"{ADAPTIVE_RATIO_FLOOR} (adaptive depth lost to a "
                "forced static beyond probe noise)"
            )
        steady = s.get("cluster_lm_steady_s")
        if (
            s.get("cluster_lm_tok_s") is not None
            and isinstance(steady, (int, float))
            and steady < STEADY_MIN_S
        ):
            problems.append(
                f"{name}: summary cluster_lm_steady_s = {steady} < "
                f"{STEADY_MIN_S} (steady-state window too short)"
            )
        return problems
    matrix = data.get("matrix", {})
    not_run = set(matrix.get("_skipped", {})) | set(matrix.get("_errors", {}))
    cs = matrix.get("cluster_serving")
    if cs is not None and "cluster_serving" not in not_run:
        ad = cs.get("adaptive")
        if not isinstance(ad, dict) or not isinstance(
            ad.get("depth"), (int, float)
        ):
            problems.append(
                f"{name}: cluster_serving.adaptive verdict missing "
                "(the depth controller's decision was not recorded)"
            )
        ratio = cs.get("pipelining_speedup")
        if not isinstance(ratio, (int, float)) or not math.isfinite(ratio):
            problems.append(
                f"{name}: cluster_serving.pipelining_speedup = "
                f"{ratio!r} (adaptive-vs-best-static ratio missing)"
            )
        elif ratio < ADAPTIVE_RATIO_FLOOR:
            problems.append(
                f"{name}: cluster_serving.pipelining_speedup = {ratio} "
                f"< {ADAPTIVE_RATIO_FLOOR}: the adaptive controller "
                "committed to a depth that loses to a forced static "
                "beyond probe noise"
            )
    clm = matrix.get("cluster_lm_serving")
    if clm is not None and "cluster_lm_serving" not in not_run:
        ss = clm.get("steady_state")
        if not isinstance(ss, dict):
            problems.append(
                f"{name}: cluster_lm_serving.steady_state missing "
                "(only the transient ran — the r5 gap re-opened)"
            )
        else:
            dur = ss.get("measured_steady_s")
            if not isinstance(dur, (int, float)) or dur < STEADY_MIN_S:
                problems.append(
                    f"{name}: steady_state.measured_steady_s = {dur!r} "
                    f"< {STEADY_MIN_S} (still a transient)"
                )
            rate = ss.get("gen_tok_per_s_steady")
            if not isinstance(rate, (int, float)) or rate <= 0:
                problems.append(
                    f"{name}: steady_state.gen_tok_per_s_steady = "
                    f"{rate!r} (no sustained decode measured)"
                )
            curve = ss.get("curve_tok_per_s")
            if not isinstance(curve, list) or len(curve) < 5:
                problems.append(
                    f"{name}: steady_state.curve_tok_per_s has "
                    f"{len(curve) if isinstance(curve, list) else 0} "
                    "points (< 5: no tok/s-vs-wall shape to read)"
                )
    return problems


def run_serving_check(artifact_path: Optional[str] = None) -> List[str]:
    return check_serving_block(artifact_path or canonical_artifact_path())


# ----------------------------------------------------------------------
# sharded worker-group serving (bench _bench_cluster_sharded /
# jobs/groups.py; ISSUE 5 tentpole)
# ----------------------------------------------------------------------

#: first round whose bench carries the tensor-parallel worker-group
#: serving section; earlier artifacts predate the subsystem
SHARDED_REQUIRED_FROM_ROUND = 7


def check_sharded_block(path: str) -> List[str]:
    """Validate the ``cluster_sharded_serving`` section WHEN IT RAN
    (neither wall-budget-skipped, nor errored, nor honestly recorded
    as skipped-with-reason inside the block):

    - ``equal_outputs`` is True — the param_gather contract: a job
      served by a tp-sharded worker group returns bit-identical
      results to the single-chip path. A False here means sharded
      serving CHANGES ANSWERS and must not ship;
    - ``qps_sharded`` (and the single-chip comparison rate) are
      finite and positive — the serve actually measured something;
    - the group topology is echoed: at least one group with its
      members, primary, and dp/tp mesh, so the artifact records WHAT
      was serving, not just how fast.

    Artifacts before round 7 are exempt; summary-only driver captures
    are gated on the compact line's ``sharded_equal`` flag."""
    from .parity_table import load_bench

    name = os.path.basename(path)
    rnd = artifact_round(path)
    if rnd is not None and rnd < SHARDED_REQUIRED_FROM_ROUND:
        return []
    data = load_bench(path)
    if data.get("_summary_only"):
        s = data.get("summary") or {}
        if s.get("sharded_qps") is not None and s.get("sharded_equal") is False:
            return [
                f"{name}: summary sharded_equal is false — group-served "
                "outputs diverged from the single-chip path"
            ]
        return []
    matrix = data.get("matrix", {})
    not_run = set(matrix.get("_skipped", {})) | set(matrix.get("_errors", {}))
    if "cluster_sharded_serving" in not_run:
        return []
    block = matrix.get("cluster_sharded_serving")
    if block is None:
        if rnd is None and "cluster_serving" not in matrix:
            return []  # partial/preview artifact without cluster runs
        return [f"{name}: no `cluster_sharded_serving` section and not "
                "recorded as skipped (bench lost the worker-group serve?)"]
    if block.get("skipped"):
        return []  # honest in-block skip (e.g. single-device env)
    problems: List[str] = []
    if block.get("equal_outputs") is not True:
        problems.append(
            f"{name}: cluster_sharded_serving.equal_outputs = "
            f"{block.get('equal_outputs')!r} — tp-sharded group outputs "
            "must be bitwise-equal to the single-chip path"
        )
    for key in ("qps_sharded", "qps_single_chip"):
        v = block.get(key)
        if not isinstance(v, (int, float)) or not math.isfinite(v) or v <= 0:
            problems.append(
                f"{name}: cluster_sharded_serving.{key} = {v!r} "
                "(missing, nonfinite, or zero — the serve never ran?)"
            )
    groups = block.get("groups")
    ok_topology = isinstance(groups, dict) and any(
        isinstance(g, dict) and g.get("members") and g.get("mesh")
        for g in groups.values()
    )
    if not ok_topology:
        problems.append(
            f"{name}: cluster_sharded_serving.groups does not echo the "
            "group topology (members + dp/tp mesh per group)"
        )
    return problems


def run_sharded_check(artifact_path: Optional[str] = None) -> List[str]:
    return check_sharded_block(artifact_path or canonical_artifact_path())


#: first round whose bench carries the sharded-LM serving section
#: (weight-resident / param_gather / disaggregated on one group)
LM_SHARDED_REQUIRED_FROM_ROUND = 8
#: first round whose bench carries the pipeline-parallel serving form
#: and the chunk-streamed multi-prefill KV handoff ladder
LM_PP_STREAM_REQUIRED_FROM_ROUND = 10


def check_lm_sharded_block(path: str) -> List[str]:
    """Validate the ``cluster_lm_sharded`` section WHEN IT RAN:

    - ``tokens_equal_single_chip`` is True — every serving form's
      merged job outputs must equal isolated generate() per prompt
      (the dryrun tp-decode exactness contract carried end-to-end
      through the cluster). False means sharded LM serving CHANGES
      ANSWERS and must not ship;
    - ``tok_s_param_gather`` / ``tok_s_resident`` / ``tok_s_disagg``
      are finite and positive — all three forms actually served;
    - ``kv_handoff_bytes`` > 0 when the disaggregated form ran with
      any successful handoff — the slab really moved over the data
      plane (a zero here with handoffs recorded means the bench
      measured the fallback path and labeled it disaggregation).

    From round ``LM_PP_STREAM_REQUIRED_FROM_ROUND`` additionally:

    - ``tok_s_pp`` finite and positive (the pipeline-parallel form
      served) with ``hbm.fits_only_pipelined`` True — the recorded
      budget story must actually be "full tree does not fit a
      member, the pp slice does";
    - ``ttft_stream_ms`` finite/positive and
      ``stream_vs_slab_ttft`` > 1 — the chunk-streamed handoff must
      STRICTLY reduce time-to-first-token vs the whole-slab pull on
      the same seed (that overlap is the entire point of streaming);
    - ``fanout_ctx_speedup`` > 1 — two prefill peers must raise
      context-phase throughput over one;
    - the member-kill-mid-stream ``chaos.verdict_green`` is True
      (completed exactly once, tokens unchanged, the kill actually
      felt as typed fallbacks or a degradation edge).

    Artifacts before round 8 are exempt; summary-only driver captures
    gate on the compact line's ``lm_sharded_equal`` flag (and the
    round-10 ``lm_pp_toks`` / ``lm_stream_vs_slab`` keys)."""
    from .parity_table import load_bench

    name = os.path.basename(path)
    rnd = artifact_round(path)
    if rnd is not None and rnd < LM_SHARDED_REQUIRED_FROM_ROUND:
        return []
    data = load_bench(path)
    if data.get("_summary_only"):
        s = data.get("summary") or {}
        problems = []
        if (
            s.get("lm_sharded_toks") is not None
            and s.get("lm_sharded_equal") is False
        ):
            problems.append(
                f"{name}: summary lm_sharded_equal is false — group-"
                "sharded LM outputs diverged from isolated generate()"
            )
        if (
            rnd is not None
            and rnd >= LM_PP_STREAM_REQUIRED_FROM_ROUND
            and s.get("lm_sharded_toks") is not None
        ):
            v = s.get("lm_pp_toks")
            if v is not None and (
                not isinstance(v, (int, float))
                or not math.isfinite(v) or v <= 0
            ):
                problems.append(
                    f"{name}: summary lm_pp_toks = {v!r} (nonfinite "
                    "or zero — the pipeline-parallel form never ran?)"
                )
            r = s.get("lm_stream_vs_slab")
            if r is not None and (
                not isinstance(r, (int, float)) or not r > 1.0
            ):
                problems.append(
                    f"{name}: summary lm_stream_vs_slab = {r!r} — the "
                    "chunk-streamed handoff must strictly reduce TTFT "
                    "vs the whole-slab pull"
                )
        return problems
    matrix = data.get("matrix", {})
    not_run = set(matrix.get("_skipped", {})) | set(matrix.get("_errors", {}))
    if "cluster_lm_sharded" in not_run:
        return []
    block = matrix.get("cluster_lm_sharded")
    if block is None:
        if rnd is None and "cluster_serving" not in matrix:
            return []  # partial/preview artifact without cluster runs
        return [f"{name}: no `cluster_lm_sharded` section and not "
                "recorded as skipped (bench lost the sharded-LM serve?)"]
    if block.get("skipped"):
        return []  # honest in-block skip (e.g. single-device env)
    problems: List[str] = []
    if block.get("tokens_equal_single_chip") is not True:
        problems.append(
            f"{name}: cluster_lm_sharded.tokens_equal_single_chip = "
            f"{block.get('tokens_equal_single_chip')!r} — sharded/"
            "disaggregated LM outputs must be token-identical to the "
            "single-chip path"
        )
    for key in ("tok_s_param_gather", "tok_s_resident", "tok_s_disagg"):
        v = block.get(key)
        if not isinstance(v, (int, float)) or not math.isfinite(v) or v <= 0:
            problems.append(
                f"{name}: cluster_lm_sharded.{key} = {v!r} (missing, "
                "nonfinite, or zero — the serving form never ran?)"
            )
    disagg = (block.get("modes") or {}).get("disagg") or {}
    handoffs = disagg.get("handoffs", 0)
    if handoffs and not block.get("kv_handoff_bytes"):
        problems.append(
            f"{name}: cluster_lm_sharded recorded {handoffs} handoffs "
            "but kv_handoff_bytes is 0/absent — no slab bytes actually "
            "moved over the data plane"
        )
    if block.get("tok_s_disagg") and not handoffs and not disagg.get(
        "fallbacks"
    ):
        problems.append(
            f"{name}: cluster_lm_sharded disagg served with neither "
            "handoffs nor fallbacks recorded — the mode accounting "
            "is broken"
        )
    groups = block.get("groups")
    ok_topology = isinstance(groups, dict) and any(
        isinstance(g, dict) and g.get("members") and g.get("mesh")
        for g in groups.values()
    )
    if not ok_topology:
        problems.append(
            f"{name}: cluster_lm_sharded.groups does not echo the "
            "group topology (members + dp/tp mesh per group)"
        )
    if rnd is not None and rnd >= LM_PP_STREAM_REQUIRED_FROM_ROUND:
        pp_v = block.get("tok_s_pp")
        if not isinstance(pp_v, (int, float)) or not math.isfinite(pp_v) \
                or pp_v <= 0:
            problems.append(
                f"{name}: cluster_lm_sharded.tok_s_pp = {pp_v!r} "
                "(missing, nonfinite, or zero — the pipeline-parallel "
                "form never served)"
            )
        hbm = block.get("hbm") or {}
        if hbm.get("fits_only_pipelined") is not True:
            problems.append(
                f"{name}: cluster_lm_sharded.hbm.fits_only_pipelined "
                f"= {hbm.get('fits_only_pipelined')!r} — the recorded "
                "budget must sit between the pp slice and the full "
                "tree (the models-bigger-than-one-member claim)"
            )
        ttft = block.get("ttft_stream_ms")
        if not isinstance(ttft, (int, float)) or not math.isfinite(ttft) \
                or ttft <= 0:
            problems.append(
                f"{name}: cluster_lm_sharded.ttft_stream_ms = {ttft!r} "
                "(the streamed handoff never recorded a first token)"
            )
        ratio = block.get("stream_vs_slab_ttft")
        if not isinstance(ratio, (int, float)) or not ratio > 1.0:
            problems.append(
                f"{name}: cluster_lm_sharded.stream_vs_slab_ttft = "
                f"{ratio!r} — chunk-streamed handoff must strictly "
                "reduce time-to-first-token vs the whole-slab pull"
            )
        fan = block.get("fanout_ctx_speedup")
        if not isinstance(fan, (int, float)) or not fan > 1.0:
            problems.append(
                f"{name}: cluster_lm_sharded.fanout_ctx_speedup = "
                f"{fan!r} — 2-peer prefill fan-out must raise "
                "context-phase throughput over 1 peer"
            )
        chaos = block.get("chaos") or {}
        if chaos.get("verdict_green") is not True:
            problems.append(
                f"{name}: cluster_lm_sharded.chaos.verdict_green = "
                f"{chaos.get('verdict_green')!r} — the member-kill-"
                "mid-stream case must complete exactly once with "
                "unchanged tokens and a felt kill (typed fallbacks "
                "or a degradation edge)"
            )
    return problems


def run_lm_sharded_check(artifact_path: Optional[str] = None) -> List[str]:
    return check_lm_sharded_block(
        artifact_path or canonical_artifact_path()
    )


#: first round whose bench carries the request front door section
#: (per-request SLO serving under open-loop load, dml_tpu/ingress/)
REQUEST_REQUIRED_FROM_ROUND = 9


def check_request_block(path: str) -> List[str]:
    """Validate the ``request_serving`` section WHEN IT RAN:

    - the sustained-load percentiles (``p50_ms``/``p95_ms``/``p99_ms``)
      are finite, positive, and ordered — the tail was actually
      measured, not defaulted;
    - ``goodput_qps`` is finite and positive, ``shed_ratio`` in
      [0, 1) — a shed ratio of 1.0 means the door rejected everything
      and the 'serving' numbers scored nothing;
    - continuous batch formation beat the naive fixed-size-batch
      baseline on light-load p99 (``continuous_vs_fixed_p99`` > 1)
      while matching its throughput at saturation
      (``saturation_goodput_ratio`` >= 0.8) — the tentpole claim;
    - the leader-failover-mid-traffic case is green:
      ``all_terminal_exactly_once`` True with completions after the
      failover — in-flight requests either complete or are explicitly
      rejected, never silently lost. The verdict is observational
      (zero conflicting late terminals across routers, zero
      completions missing their result payload, completions > 0),
      not an accounting identity.

    Artifacts before round 9 are exempt; summary-only driver captures
    gate on the compact line's ``req_*`` keys."""
    from .parity_table import load_bench

    name = os.path.basename(path)
    rnd = artifact_round(path)
    if rnd is not None and rnd < REQUEST_REQUIRED_FROM_ROUND:
        return []
    data = load_bench(path)
    if data.get("_summary_only"):
        s = data.get("summary") or {}
        problems = []
        if s.get("req_p99_ms") is not None:
            v = s["req_p99_ms"]
            if not isinstance(v, (int, float)) or not math.isfinite(v) \
                    or v <= 0:
                problems.append(
                    f"{name}: summary req_p99_ms = {v!r} (nonfinite or "
                    "nonpositive)"
                )
            sr = s.get("req_shed_ratio")
            if sr is not None and (
                not isinstance(sr, (int, float)) or not 0 <= sr < 1
            ):
                problems.append(
                    f"{name}: summary req_shed_ratio = {sr!r} not in "
                    "[0, 1)"
                )
            if s.get("req_failover_ok") is False:
                problems.append(
                    f"{name}: summary req_failover_ok is false — a "
                    "request was lost or double-terminated across the "
                    "failover"
                )
        return problems
    matrix = data.get("matrix", {})
    not_run = set(matrix.get("_skipped", {})) | set(matrix.get("_errors", {}))
    if "request_serving" in not_run:
        return []
    block = matrix.get("request_serving")
    if block is None:
        if rnd is None and "cluster_serving" not in matrix:
            return []  # partial/preview artifact without cluster runs
        return [f"{name}: no `request_serving` section and not recorded "
                "as skipped (bench lost the front-door serve?)"]
    if block.get("skipped"):
        return []
    problems: List[str] = []
    pcts = []
    for key in ("p50_ms", "p95_ms", "p99_ms"):
        v = block.get(key)
        if not isinstance(v, (int, float)) or not math.isfinite(v) or v <= 0:
            problems.append(
                f"{name}: request_serving.{key} = {v!r} (missing, "
                "nonfinite, or zero — the sustained load never served?)"
            )
        else:
            pcts.append(v)
    if len(pcts) == 3 and not (pcts[0] <= pcts[1] <= pcts[2]):
        problems.append(
            f"{name}: request_serving percentiles not ordered "
            f"(p50={pcts[0]}, p95={pcts[1]}, p99={pcts[2]})"
        )
    gp = block.get("goodput_qps")
    if not isinstance(gp, (int, float)) or not math.isfinite(gp) or gp <= 0:
        problems.append(
            f"{name}: request_serving.goodput_qps = {gp!r} (missing, "
            "nonfinite, or zero)"
        )
    sr = block.get("shed_ratio")
    if not isinstance(sr, (int, float)) or not 0 <= sr < 1:
        problems.append(
            f"{name}: request_serving.shed_ratio = {sr!r} not in [0, 1)"
        )
    ratio = block.get("continuous_vs_fixed_p99")
    if not isinstance(ratio, (int, float)) or ratio <= 1.0:
        problems.append(
            f"{name}: request_serving.continuous_vs_fixed_p99 = {ratio!r}"
            " — continuous formation must beat the fixed-batch baseline "
            "on light-load p99"
        )
    sat = block.get("saturation_goodput_ratio")
    if not isinstance(sat, (int, float)) or sat < 0.8:
        problems.append(
            f"{name}: request_serving.saturation_goodput_ratio = {sat!r}"
            " — continuous formation must MATCH fixed-batch throughput "
            "at saturation (>= 0.8)"
        )
    fo = block.get("failover") or {}
    if fo.get("all_terminal_exactly_once") is not True:
        problems.append(
            f"{name}: request_serving.failover.all_terminal_exactly_once"
            f" = {fo.get('all_terminal_exactly_once')!r} — every request "
            "in the failover-mid-traffic run must reach exactly one "
            "terminal"
        )
    if not fo.get("completed", 0):
        problems.append(
            f"{name}: request_serving.failover completed 0 requests — "
            "the cluster never resumed serving after the leader kill"
        )
    if rnd is not None and rnd >= LM_PP_STREAM_REQUIRED_FROM_ROUND:
        # per-class weighted fair share inside the scheduler landed
        # with round 10: the mixed-class rerun must show interactive
        # p99 better under the weighted split than under one FIFO
        cf = block.get("class_fair")
        if not isinstance(cf, dict):
            problems.append(
                f"{name}: request_serving.class_fair missing — the "
                "weighted-vs-FIFO mixed-class rerun never happened"
            )
        elif cf.get("interactive_p99_improved") is not True:
            problems.append(
                f"{name}: request_serving.class_fair."
                "interactive_p99_improved = "
                f"{cf.get('interactive_p99_improved')!r} — weighted "
                "per-class shares must improve interactive p99 over "
                "FIFO under the sustained mixed-class load"
            )
    return problems


def run_request_check(artifact_path: Optional[str] = None) -> List[str]:
    return check_request_block(artifact_path or canonical_artifact_path())


# ----------------------------------------------------------------------
# round-14 distributed request tracing: the request_serving section
# embeds a `tracing` block (dml_tpu/tracing.py — per-request span
# collection, p99 cohort attribution, deadline-miss exemplars, flight
# recorder budget, sampling-off overhead rerun)
# ----------------------------------------------------------------------

#: first round whose request_serving section must carry the tracing
#: block (cross-node span collection + tail attribution)
TRACING_REQUIRED_FROM_ROUND = 14


def check_tracing_block(path: str) -> List[str]:
    """Validate the ``request_serving.tracing`` block WHEN the section
    ran:

    - ``p99_attrib_ok`` True with ``attributed_fraction`` >= 0.9 — the
      p99 cohort's per-stage breakdown explains at least 90% of its
      measured e2e latency (an attribution that explains less is a
      broken stitch, not an observability layer);
    - ``miss_exemplar_coverage`` == 1.0 — every deadline miss has an
      exemplar trace regardless of the sampling rate (the misses ARE
      the requests that need explaining);
    - the flight recorder stayed within its configured span budget
      (``recorder.within_budget``);
    - the sampling=0 overhead rerun was recorded and its p99 sits
      within noise of the traced run (ratio <= 2.0 — a tracer that
      doubles the tail is measuring itself).

    Artifacts before round ``TRACING_REQUIRED_FROM_ROUND`` are exempt;
    summary-only driver captures gate on the compact line's
    ``trace_p99_attrib_ok`` key."""
    from .parity_table import load_bench

    name = os.path.basename(path)
    rnd = artifact_round(path)
    if rnd is not None and rnd < TRACING_REQUIRED_FROM_ROUND:
        return []
    data = load_bench(path)
    if data.get("_summary_only"):
        s = data.get("summary") or {}
        if s.get("trace_p99_attrib_ok") is False:
            return [f"{name}: summary trace_p99_attrib_ok is false — "
                    "the p99 cohort's stage attribution did not explain "
                    ">= 90% of its e2e latency"]
        return []
    matrix = data.get("matrix", {})
    not_run = set(matrix.get("_skipped", {})) | set(matrix.get("_errors", {}))
    if "request_serving" in not_run:
        return []
    block = matrix.get("request_serving")
    if block is None or block.get("skipped"):
        return []  # the request gate already flags a missing section
    tb = block.get("tracing")
    if not isinstance(tb, dict):
        if rnd is None:
            return []  # partial/preview artifact
        return [f"{name}: request_serving ran without a `tracing` "
                "block — per-request tracing is required from round "
                f"{TRACING_REQUIRED_FROM_ROUND}"]
    problems: List[str] = []
    if tb.get("p99_attrib_ok") is not True:
        problems.append(
            f"{name}: tracing.p99_attrib_ok = "
            f"{tb.get('p99_attrib_ok')!r} — the p99 cohort's stage "
            "attribution must explain >= 90% of its measured e2e"
        )
    af = (tb.get("p99_attribution") or {}).get("attributed_fraction")
    if not isinstance(af, (int, float)) or not math.isfinite(af) \
            or af < 0.9:
        problems.append(
            f"{name}: tracing attributed_fraction = {af!r} (< 0.9 or "
            "missing)"
        )
    cov = tb.get("miss_exemplar_coverage")
    if not isinstance(cov, (int, float)) or cov < 0.999:
        problems.append(
            f"{name}: tracing.miss_exemplar_coverage = {cov!r} — every "
            "deadline miss must have an exemplar trace (sampling must "
            "not hide the tail)"
        )
    rec = tb.get("recorder") or {}
    if rec.get("within_budget") is not True:
        problems.append(
            f"{name}: tracing.recorder.within_budget = "
            f"{rec.get('within_budget')!r} — the flight recorder "
            "exceeded its configured span budget"
        )
    ov = tb.get("overhead") or {}
    ratio = ov.get("p99_traced_vs_untraced")
    if not isinstance(ratio, (int, float)) or not math.isfinite(ratio) \
            or ratio <= 0:
        problems.append(
            f"{name}: tracing.overhead.p99_traced_vs_untraced = "
            f"{ratio!r} — the sampling=0 overhead rerun was never "
            "measured"
        )
    elif ratio > 2.0:
        problems.append(
            f"{name}: tracing overhead ratio {ratio!r} > 2.0 — tracing "
            "is perturbing the tail it claims to measure"
        )
    return problems


def run_tracing_check(artifact_path: Optional[str] = None) -> List[str]:
    return check_tracing_block(artifact_path or canonical_artifact_path())


# ----------------------------------------------------------------------
# round-17 KV prefix cache: the request_serving section's multi-turn
# phase (inference/kv_cache.py — warm-start decode from resident KV
# slabs, suffix-only prefill) embeds a `kv_cache` block
# ----------------------------------------------------------------------

#: first round whose request_serving section must carry the kv_cache
#: block (growing-history session trace scored warm vs cold)
KV_CACHE_REQUIRED_FROM_ROUND = 17


def check_kv_cache_block(path: str) -> List[str]:
    """Validate the ``request_serving.kv_cache`` block WHEN the
    section ran:

    - ``hit_ratio`` > 0 — the multi-turn session trace actually
      warm-started (a zero here means session affinity never landed a
      turn on its KV holder, i.e. the locality promise is still
      unfunded);
    - ``warm_vs_cold_ttft`` > 1 — TTFT with the cache strictly beats
      the cold full-re-prefill run of the SAME trace;
    - ``tokens_saved`` > 0 — prompt tokens the suffix-only prefill
      skipped, from the worker-side counter;
    - ``warm_equals_cold`` True — every warm-start completion is
      token-identical to the cold path (the exactness contract);
    - the mid-session leader-failover sub-case ran and stayed
      token-identical too (``failover.warm_equals_cold`` True with
      completions > 0) — relayed session affinity plus exactly-once.

    Artifacts before round ``KV_CACHE_REQUIRED_FROM_ROUND`` are
    exempt; summary-only driver captures gate on the compact line's
    ``kv_hit_ratio`` / ``kv_warm_vs_cold_ttft`` keys."""
    from .parity_table import load_bench

    name = os.path.basename(path)
    rnd = artifact_round(path)
    if rnd is not None and rnd < KV_CACHE_REQUIRED_FROM_ROUND:
        return []
    data = load_bench(path)
    if data.get("_summary_only"):
        s = data.get("summary") or {}
        problems = []
        hr = s.get("kv_hit_ratio")
        if hr is not None and (
            not isinstance(hr, (int, float)) or not 0 < hr <= 1
        ):
            problems.append(
                f"{name}: summary kv_hit_ratio = {hr!r} — the "
                "multi-turn trace never warm-started"
            )
        rt = s.get("kv_warm_vs_cold_ttft")
        if rt is not None and (
            not isinstance(rt, (int, float)) or not math.isfinite(rt)
            or rt <= 1.0
        ):
            problems.append(
                f"{name}: summary kv_warm_vs_cold_ttft = {rt!r} — "
                "warm TTFT must strictly beat the cold re-prefill"
            )
        return problems
    matrix = data.get("matrix", {})
    not_run = set(matrix.get("_skipped", {})) | set(matrix.get("_errors", {}))
    if "request_serving" in not_run:
        return []
    block = matrix.get("request_serving")
    if block is None or block.get("skipped"):
        return []  # the request gate already flags a missing section
    kb = block.get("kv_cache")
    if not isinstance(kb, dict):
        if rnd is None:
            return []  # partial/preview artifact
        return [f"{name}: request_serving ran without a `kv_cache` "
                "block — the multi-turn prefix-cache phase is required "
                f"from round {KV_CACHE_REQUIRED_FROM_ROUND}"]
    problems: List[str] = []
    hr = kb.get("hit_ratio")
    if not isinstance(hr, (int, float)) or not 0 < hr <= 1:
        problems.append(
            f"{name}: kv_cache.hit_ratio = {hr!r} — the session trace "
            "must actually hit the prefix cache (> 0)"
        )
    rt = kb.get("warm_vs_cold_ttft")
    if not isinstance(rt, (int, float)) or not math.isfinite(rt) \
            or rt <= 1.0:
        problems.append(
            f"{name}: kv_cache.warm_vs_cold_ttft = {rt!r} — warm-start "
            "TTFT must strictly beat the cold full-re-prefill run"
        )
    ts = kb.get("tokens_saved")
    if not isinstance(ts, int) or ts <= 0:
        problems.append(
            f"{name}: kv_cache.tokens_saved = {ts!r} — suffix-only "
            "prefill never skipped a prompt token"
        )
    if kb.get("warm_equals_cold") is not True:
        problems.append(
            f"{name}: kv_cache.warm_equals_cold = "
            f"{kb.get('warm_equals_cold')!r} — warm-start completions "
            "must be token-identical to the cold path"
        )
    fo = kb.get("failover")
    if not isinstance(fo, dict):
        problems.append(
            f"{name}: kv_cache.failover missing — the mid-session "
            "leader-kill sub-case never ran"
        )
    else:
        if fo.get("warm_equals_cold") is not True:
            problems.append(
                f"{name}: kv_cache.failover.warm_equals_cold = "
                f"{fo.get('warm_equals_cold')!r} — completions must "
                "stay token-identical across the leader failover"
            )
        if not fo.get("completed", 0):
            problems.append(
                f"{name}: kv_cache.failover completed 0 turns — the "
                "sessions never resumed after the leader kill"
            )
    return problems


def run_kv_cache_check(artifact_path: Optional[str] = None) -> List[str]:
    return check_kv_cache_block(artifact_path or canonical_artifact_path())


# ----------------------------------------------------------------------
# static-analysis verdict: the bench preamble runs tools/dmllint.py and
# records the result; from round 11 on an artifact must say the tree
# is lint-clean (zero un-baselined async-hazard/drift findings) with a
# bounded grandfather baseline
# ----------------------------------------------------------------------

#: first round whose bench carries the dmllint verdict block
LINT_REQUIRED_FROM_ROUND = 11

#: first round whose lint block must ALSO carry the flow-aware pass
#: counts (tools/dmlflow.py: race-yield-hazard + drift-wire-payloads,
#: landed with the round-16 build) — their presence proves both passes
#: ran, and lint_clean covers their findings from that round on
FLOW_LINT_REQUIRED_FROM_ROUND = 16

#: the baseline may only shrink; tests/test_dmllint.py enforces the
#: same bound at tier-1 time, this enforces it on the artifact record
#: (raised 10 -> 25 with the flow-aware rules: justified benign
#: interleavings/echo keys are grandfathered per ISSUE 13)
LINT_BASELINE_MAX = 25


def check_lint_block(path: str) -> List[str]:
    """Validate the ``lint`` preamble block: ``lint_clean`` must be
    True (an artifact built from a tree with un-baselined hazard or
    drift findings is not a clean round), the finding count must be
    recorded, and the grandfather baseline must stay within
    ``LINT_BASELINE_MAX`` entries.

    From round ``FLOW_LINT_REQUIRED_FROM_ROUND`` the block must also
    carry integer ``race_findings`` / ``payload_findings`` counts —
    the proof that the flow-aware passes (race-yield-hazard,
    drift-wire-payloads) ran under lint_clean.

    Artifacts before round ``LINT_REQUIRED_FROM_ROUND`` are exempt;
    summary-only driver captures gate on the compact line's
    ``lint_clean`` key (plus ``lint_race`` / ``lint_payload`` from the
    flow round on)."""
    from .parity_table import load_bench

    name = os.path.basename(path)
    rnd = artifact_round(path)
    if rnd is not None and rnd < LINT_REQUIRED_FROM_ROUND:
        return []
    flow_required = rnd is not None and rnd >= FLOW_LINT_REQUIRED_FROM_ROUND
    data = load_bench(path)
    if data.get("_summary_only"):
        s = data.get("summary") or {}
        if s.get("lint_clean") is False:
            return [f"{name}: summary lint_clean is false — the round "
                    "ran on a tree with un-baselined dmllint findings"]
        problems: List[str] = []
        if flow_required:
            for key in ("lint_race", "lint_payload"):
                if not isinstance(s.get(key), int):
                    problems.append(
                        f"{name}: summary {key} = {s.get(key)!r} — the "
                        "flow-aware lint pass counts must ride the "
                        "compact line from round "
                        f"{FLOW_LINT_REQUIRED_FROM_ROUND} on"
                    )
        return problems
    matrix = data.get("matrix", {})
    block = matrix.get("lint")
    if block is None:
        if rnd is None:
            return []  # partial/preview artifact without the preamble
        return [f"{name}: no `lint` block — the bench preamble must "
                "record the dmllint verdict from round "
                f"{LINT_REQUIRED_FROM_ROUND} on"]
    problems: List[str] = []
    if block.get("lint_clean") is not True:
        problems.append(
            f"{name}: lint.lint_clean = {block.get('lint_clean')!r} "
            f"(error: {block.get('error')!r}) — un-baselined dmllint "
            "findings (or a broken linter) at bench time"
        )
    n = block.get("findings")
    if not isinstance(n, int) or n < 0:
        problems.append(
            f"{name}: lint.findings = {n!r} (missing or not a count)"
        )
    b = block.get("baseline_size")
    if not isinstance(b, int) or not 0 <= b <= LINT_BASELINE_MAX:
        problems.append(
            f"{name}: lint.baseline_size = {b!r} — the grandfather "
            f"baseline must hold <= {LINT_BASELINE_MAX} justified "
            "entries (it only ever shrinks)"
        )
    if flow_required:
        for key in ("race_findings", "payload_findings"):
            if not isinstance(block.get(key), int):
                problems.append(
                    f"{name}: lint.{key} = {block.get(key)!r} — the "
                    "flow-aware passes (race-yield-hazard / "
                    "drift-wire-payloads) must record their counts "
                    f"from round {FLOW_LINT_REQUIRED_FROM_ROUND} on"
                )
        rules = block.get("rules")
        if isinstance(rules, list) and not (
                {"race-yield-hazard", "drift-wire-payloads"} <= set(rules)):
            problems.append(
                f"{name}: lint.rules is missing the flow-aware rules — "
                "the verdict does not cover race-yield-hazard / "
                "drift-wire-payloads"
            )
    return problems


def run_lint_check(artifact_path: Optional[str] = None) -> List[str]:
    return check_lint_block(artifact_path or canonical_artifact_path())


# ----------------------------------------------------------------------
# round-12 control-plane scale: the `control_plane_scale` bench
# section scores the delta-gossip + relay-metrics protocol against
# the reference full-table protocol at N in {16, 64, 128} and sweeps
# a sustained-churn invariant run (bench _bench_control_plane_scale;
# ISSUE 11 tentpole)
# ----------------------------------------------------------------------

#: first round whose bench must carry the control_plane_scale section
SCALE_REQUIRED_FROM_ROUND = 12

#: big-N failure detection may be at most this multiple of small-N
SCALE_DETECT_RATIO_MAX = 1.5


def check_scale_block(path: str) -> List[str]:
    """Validate the ``control_plane_scale`` section WHEN IT RAN:

    - the scored walls (convergence, failure detection, election at
      the biggest N under the delta protocol) are finite and
      positive — a probe that timed out records None and is a
      violation, not a skip;
    - the delta protocol's control-plane bytes/node/s is STRICTLY
      below full-table gossip at every N >= 64 (the tentpole claim);
    - cluster-wide failure detection at the biggest N is within
      ``SCALE_DETECT_RATIO_MAX`` of small-N;
    - the relay metrics-aggregation wall grows sub-linearly in N;
    - the sustained-churn run swept green (exactly one leader, no
      lost store files, no dead coroutines, under continuous
      join/leave).

    Artifacts before round ``SCALE_REQUIRED_FROM_ROUND`` are exempt;
    summary-only driver captures gate on the compact line's
    ``scale_*`` keys."""
    from .parity_table import load_bench

    name = os.path.basename(path)
    rnd = artifact_round(path)
    if rnd is not None and rnd < SCALE_REQUIRED_FROM_ROUND:
        return []
    data = load_bench(path)
    if data.get("_summary_only"):
        s = data.get("summary") or {}
        problems = []
        for key in ("scale_converge_s", "scale_detect_s",
                    "scale_bytes_per_node_s"):
            v = s.get(key)
            if v is not None and (
                not isinstance(v, (int, float))
                or not math.isfinite(v) or v <= 0
            ):
                problems.append(
                    f"{name}: summary {key} = {v!r} (nonfinite or "
                    "nonpositive — the scale probe never measured)"
                )
        if s.get("scale_ok") is False:
            problems.append(
                f"{name}: summary scale_ok is false — a control-plane "
                "scale verdict (bytes-below-full / detection-ratio / "
                "metrics-sublinear / churn) failed"
            )
        return problems
    matrix = data.get("matrix", {})
    not_run = set(matrix.get("_skipped", {})) | set(matrix.get("_errors", {}))
    if "control_plane_scale" in not_run:
        return []  # honestly recorded as skipped/errored
    block = matrix.get("control_plane_scale")
    if block is None:
        if rnd is None and "cluster_serving" not in matrix:
            return []  # partial/preview artifact without cluster runs
        return [f"{name}: no `control_plane_scale` section and not "
                "recorded as skipped (bench lost the scale matrix?)"]
    problems: List[str] = []
    for key in ("scale_converge_s", "scale_detect_s",
                "scale_election_s", "scale_bytes_per_node_s"):
        v = block.get(key)
        if not isinstance(v, (int, float)) or not math.isfinite(v) or v <= 0:
            problems.append(
                f"{name}: control_plane_scale.{key} = {v!r} (missing, "
                "nonfinite, or zero — the big-N probe timed out or "
                "never measured)"
            )
    bvf = block.get("bytes_vs_full_by_n")
    if not isinstance(bvf, dict) or not bvf:
        problems.append(
            f"{name}: control_plane_scale.bytes_vs_full_by_n missing — "
            "the old-vs-new protocol comparison never ran"
        )
    else:
        for n, v in sorted(bvf.items()):
            try:
                big_enough = int(n) >= 64
            except (TypeError, ValueError):
                continue
            if big_enough and (
                not isinstance(v, (int, float)) or not v < 1.0
            ):
                problems.append(
                    f"{name}: control_plane_scale delta/full bytes "
                    f"ratio at N={n} is {v!r} — the delta protocol "
                    "must be strictly below full-table gossip"
                )
    dr = block.get("detect_ratio_vs_small_n")
    if not isinstance(dr, (int, float)) or dr > SCALE_DETECT_RATIO_MAX:
        problems.append(
            f"{name}: control_plane_scale.detect_ratio_vs_small_n = "
            f"{dr!r} — big-N failure detection must stay within "
            f"{SCALE_DETECT_RATIO_MAX}x of small-N"
        )
    mr = block.get("metrics_wall_ratio_vs_small_n")
    ns = block.get("ns") or []
    n_ratio = (
        ns[-1] / ns[0]
        if len(ns) >= 2 and all(isinstance(x, (int, float)) for x in ns)
        and ns[0] else None
    )
    if not isinstance(mr, (int, float)) or (
        n_ratio is not None and mr >= n_ratio
    ):
        problems.append(
            f"{name}: control_plane_scale.metrics_wall_ratio_vs_small_n"
            f" = {mr!r} — the relay metrics-pull wall must grow "
            f"sub-linearly in N (< {n_ratio!r})"
        )
    rvs = block.get("straggler_serial_vs_relay")
    if not isinstance(rvs, (int, float)) or rvs <= 1.5:
        problems.append(
            f"{name}: control_plane_scale.straggler_serial_vs_relay = "
            f"{rvs!r} — with dead peers on the pull list the "
            "aggregated pull must stay bounded by ~one timeout while "
            "the serial shape pays one per straggler (> 1.5x)"
        )
    churn = block.get("churn") or {}
    if churn.get("ok") is not True:
        problems.append(
            f"{name}: control_plane_scale.churn not green "
            f"(failures: {churn.get('failures')!r}) — the sustained "
            "join/leave invariant sweep must pass"
        )
    if not churn.get("crash_restart_pairs", 0):
        problems.append(
            f"{name}: control_plane_scale.churn ran zero crash/restart "
            "pairs — sustained churn never actually churned"
        )
    return problems


def run_scale_check(artifact_path: Optional[str] = None) -> List[str]:
    return check_scale_block(artifact_path or canonical_artifact_path())


# ----------------------------------------------------------------------
# round-18 elastic capacity: authenticated runtime join/leave must
# RAISE throughput when capacity joins mid-load, with zero restarts
# (bench _bench_elastic; ROADMAP item 2's done-condition)
# ----------------------------------------------------------------------

ELASTIC_REQUIRED_FROM_ROUND = 18

#: scale-out must beat the load-window noise floor, not merely tie it
ELASTIC_GAIN_MIN = 1.05


def check_elastic_block(path: str) -> List[str]:
    """Validate the ``elastic_capacity`` section WHEN IT RAN:

    - both q/s windows measured (finite, positive) and the post-join
      window STRICTLY above the pre-join one (``scaleout_gain`` >
      ``ELASTIC_GAIN_MIN``) — capacity added mid-load must raise
      measured throughput;
    - zero restarts (the gain must be admitted capacity, not a
      bounce);
    - every scale-in was graceful (LEAVE sent, not a silent exit);
    - the forged-join storm moved the typed rejection counters;
    - the end-of-run invariant sweep was green (one leader, files at
      factor, no phantom in any universe, no dead coroutines).

    Artifacts before round ``ELASTIC_REQUIRED_FROM_ROUND`` are
    exempt; summary-only driver captures gate on the compact line's
    ``elastic_*`` keys."""
    from .parity_table import load_bench

    name = os.path.basename(path)
    rnd = artifact_round(path)
    if rnd is not None and rnd < ELASTIC_REQUIRED_FROM_ROUND:
        return []
    data = load_bench(path)
    if data.get("_summary_only"):
        s = data.get("summary") or {}
        problems = []
        gain = s.get("elastic_scaleout_gain")
        if gain is not None and (
            not isinstance(gain, (int, float))
            or not math.isfinite(gain) or gain <= ELASTIC_GAIN_MIN
        ):
            problems.append(
                f"{name}: summary elastic_scaleout_gain = {gain!r} — "
                "capacity joining mid-load must raise q/s above the "
                f"{ELASTIC_GAIN_MIN} noise floor"
            )
        if s.get("elastic_ok") is False:
            problems.append(
                f"{name}: summary elastic_ok is false — an elastic-"
                "capacity verdict (gain / zero-restarts / graceful "
                "scale-in / storm-rejections / sweep) failed"
            )
        return problems
    matrix = data.get("matrix", {})
    not_run = set(matrix.get("_skipped", {})) | set(matrix.get("_errors", {}))
    if "elastic_capacity" in not_run:
        return []  # honestly recorded as skipped/errored
    block = matrix.get("elastic_capacity")
    if block is None:
        if rnd is None and "cluster_serving" not in matrix:
            return []  # partial/preview artifact without cluster runs
        return [f"{name}: no `elastic_capacity` section and not "
                "recorded as skipped (bench lost the elastic run?)"]
    problems: List[str] = []
    for key in ("qps_before", "qps_after"):
        v = block.get(key)
        if not isinstance(v, (int, float)) or not math.isfinite(v) or v <= 0:
            problems.append(
                f"{name}: elastic_capacity.{key} = {v!r} (missing, "
                "nonfinite, or zero — a load window never measured)"
            )
    gain = block.get("scaleout_gain")
    if not isinstance(gain, (int, float)) or not math.isfinite(gain) \
            or gain <= ELASTIC_GAIN_MIN:
        problems.append(
            f"{name}: elastic_capacity.scaleout_gain = {gain!r} — "
            "nodes joining mid-load must RAISE measured throughput "
            f"(> {ELASTIC_GAIN_MIN})"
        )
    if block.get("restarts") != 0:
        problems.append(
            f"{name}: elastic_capacity.restarts = "
            f"{block.get('restarts')!r} — the scale-out gain must "
            "come with zero restarts"
        )
    graceful = block.get("scale_in_graceful")
    if not isinstance(graceful, list) or not graceful \
            or not all(v is True for v in graceful):
        problems.append(
            f"{name}: elastic_capacity.scale_in_graceful = "
            f"{graceful!r} — every scale-in must announce LEAVE"
        )
    storm = block.get("storm") or {}
    if not isinstance(storm, dict) or not storm.get("sent") \
            or not isinstance(storm.get("rejected"), (int, float)) \
            or storm.get("rejected", 0) <= 0:
        problems.append(
            f"{name}: elastic_capacity.storm = {storm!r} — the "
            "forged-join storm must run and move the typed rejection "
            "counters"
        )
    if block.get("sweep_ok") is not True:
        problems.append(
            f"{name}: elastic_capacity invariant sweep not green "
            f"(failures: {block.get('sweep_failures')!r})"
        )
    if block.get("elastic_ok") is not True:
        problems.append(
            f"{name}: elastic_capacity.elastic_ok = "
            f"{block.get('elastic_ok')!r} — the section's own verdict "
            "must be true"
        )
    return problems


def run_elastic_check(artifact_path: Optional[str] = None) -> List[str]:
    return check_elastic_block(artifact_path or canonical_artifact_path())


# ----------------------------------------------------------------------
# round-19 signal plane: burn-rate alerts must FIRE under chaos
# overload with trace exemplars, the straggler cross-check must catch
# a lying worker, and the alert ledger must survive leader failover
# (bench _bench_signal_plane; ISSUE 16 tentpole)
# ----------------------------------------------------------------------

SIGNAL_REQUIRED_FROM_ROUND = 19


def check_signal_block(path: str) -> List[str]:
    """Validate the ``signal_plane`` section WHEN IT RAN:

    - the chaos-overload arm fired a typed burn-rate alert carrying
      an exemplar trace id (an alert without an exemplar cannot be
      drilled into — the flight recorder hook was lost);
    - the lying-metrics arm flagged the liar via the ACK-observed
      wall cross-check WHILE its self-reported walls stayed clean —
      evidence the detection used the leader's own clock, not the
      worker's word;
    - the failover arm carried a firing alert across a leader kill
      and resolved it on the promoted leader (ledger relay worked);
    - the replay arm produced byte-identical alert streams from the
      same seed (the alert pipeline is deterministic given the same
      observations and clock).

    Artifacts before round ``SIGNAL_REQUIRED_FROM_ROUND`` are
    exempt; summary-only driver captures gate on the compact line's
    ``alert_fired_ok`` / ``liar_flagged_ok`` keys."""
    from .parity_table import load_bench

    name = os.path.basename(path)
    rnd = artifact_round(path)
    if rnd is not None and rnd < SIGNAL_REQUIRED_FROM_ROUND:
        return []
    data = load_bench(path)
    if data.get("_summary_only"):
        s = data.get("summary") or {}
        problems = []
        if s.get("alert_fired_ok") is False:
            problems.append(
                f"{name}: summary alert_fired_ok is false — chaos "
                "overload never fired a typed burn-rate alert"
            )
        if s.get("liar_flagged_ok") is False:
            problems.append(
                f"{name}: summary liar_flagged_ok is false — the "
                "ACK-wall cross-check missed the lying worker"
            )
        return problems
    matrix = data.get("matrix", {})
    not_run = set(matrix.get("_skipped", {})) | set(matrix.get("_errors", {}))
    if "signal_plane" in not_run:
        return []  # honestly recorded as skipped/errored
    block = matrix.get("signal_plane")
    if block is None:
        if rnd is None and "cluster_serving" not in matrix:
            return []  # partial/preview artifact without cluster runs
        return [f"{name}: no `signal_plane` section and not recorded "
                "as skipped (bench lost the signal-plane run?)"]
    problems: List[str] = []
    if block.get("alert_fired_ok") is not True:
        problems.append(
            f"{name}: signal_plane.alert_fired_ok = "
            f"{block.get('alert_fired_ok')!r} — chaos overload must "
            "fire a typed burn-rate alert"
        )
    ex = block.get("exemplar_trace_id")
    if not isinstance(ex, str) or not ex:
        problems.append(
            f"{name}: signal_plane.exemplar_trace_id = {ex!r} — the "
            "fired alert must carry a flight-recorder exemplar"
        )
    if block.get("liar_flagged_ok") is not True:
        problems.append(
            f"{name}: signal_plane.liar_flagged_ok = "
            f"{block.get('liar_flagged_ok')!r} — the ACK-wall "
            "cross-check must flag the lying worker"
        )
    if block.get("liar_self_report_clean") is not True:
        problems.append(
            f"{name}: signal_plane.liar_self_report_clean = "
            f"{block.get('liar_self_report_clean')!r} — the liar's "
            "self-reported walls must have LOOKED healthy (otherwise "
            "the cross-check proved nothing)"
        )
    if block.get("ledger_survived_ok") is not True:
        problems.append(
            f"{name}: signal_plane.ledger_survived_ok = "
            f"{block.get('ledger_survived_ok')!r} — a firing alert "
            "must survive leader kill and resolve on the promoted "
            "leader"
        )
    if block.get("replay_deterministic_ok") is not True:
        problems.append(
            f"{name}: signal_plane.replay_deterministic_ok = "
            f"{block.get('replay_deterministic_ok')!r} — the same "
            "seed must produce a byte-identical alert stream"
        )
    if block.get("signal_ok") is not True:
        problems.append(
            f"{name}: signal_plane.signal_ok = "
            f"{block.get('signal_ok')!r} — the section's own verdict "
            "must be true"
        )
    return problems


def run_signal_check(artifact_path: Optional[str] = None) -> List[str]:
    return check_signal_block(artifact_path or canonical_artifact_path())


# ----------------------------------------------------------------------
# round-20 closed-loop autoscaler: the diurnal provisioning duel and
# the decision-stream determinism arm (bench _bench_autoscale;
# ISSUE 17 tentpole)
# ----------------------------------------------------------------------

#: first round whose bench must carry the autoscale section; earlier
#: artifacts predate the controller
AUTOSCALE_REQUIRED_FROM_ROUND = 20


def check_autoscale_block(path: str) -> List[str]:
    """Validate the ``autoscale`` section WHEN IT RAN:

    - the autoscaled arm beat static provisioning on BOTH integrals
      of the shared diurnal trace — SLO-violation minutes AND
      chip-idle minutes (winning only one is the provisioning
      dilemma restated, not dissolved);
    - neither arm restarted a node and both invariant sweeps came
      back green (capacity moved through the authenticated join/
      LEAVE path, never through crashes);
    - the controller actually exercised the loop: at least one
      applied scale-out AND one applied scale-in;
    - the replay arm produced byte-identical decision streams from
      the same snapshot schedule (the decision plane is a pure
      function of its observations).

    Artifacts before round ``AUTOSCALE_REQUIRED_FROM_ROUND`` are
    exempt; summary-only driver captures gate on the compact line's
    ``autoscale_ok`` / ``autoscale_slo_min_saved`` keys."""
    from .parity_table import load_bench

    name = os.path.basename(path)
    rnd = artifact_round(path)
    if rnd is not None and rnd < AUTOSCALE_REQUIRED_FROM_ROUND:
        return []
    data = load_bench(path)
    if data.get("_summary_only"):
        s = data.get("summary") or {}
        problems = []
        if s.get("autoscale_ok") is False:
            problems.append(
                f"{name}: summary autoscale_ok is false — the "
                "closed-loop arm lost the diurnal duel or the "
                "decision stream went nondeterministic"
            )
        saved = s.get("autoscale_slo_min_saved")
        if isinstance(saved, (int, float)) and saved <= 0:
            problems.append(
                f"{name}: summary autoscale_slo_min_saved = "
                f"{saved!r} — the controller saved no SLO-violation "
                "minutes over static provisioning"
            )
        return problems
    matrix = data.get("matrix", {})
    not_run = set(matrix.get("_skipped", {})) | set(matrix.get("_errors", {}))
    if "autoscale" in not_run:
        return []  # honestly recorded as skipped/errored
    block = matrix.get("autoscale")
    if block is None:
        if rnd is None and "cluster_serving" not in matrix:
            return []  # partial/preview artifact without cluster runs
        return [f"{name}: no `autoscale` section and not recorded "
                "as skipped (bench lost the diurnal duel?)"]
    problems: List[str] = []
    for key in ("autoscale_slo_min_saved", "autoscale_idle_min_saved"):
        v = block.get(key)
        if not isinstance(v, (int, float)) or v <= 0:
            problems.append(
                f"{name}: autoscale.{key} = {v!r} — the closed-loop "
                "arm must beat static on BOTH diurnal integrals"
            )
    for arm in ("static", "autoscaled"):
        sub = block.get(arm) or {}
        if sub.get("restarts") != 0:
            problems.append(
                f"{name}: autoscale.{arm}.restarts = "
                f"{sub.get('restarts')!r} — capacity must move "
                "through join/LEAVE, never crashes"
            )
        if sub.get("sweep_ok") is not True:
            problems.append(
                f"{name}: autoscale.{arm}.sweep_ok = "
                f"{sub.get('sweep_ok')!r} — the post-run invariant "
                "sweep must be green"
            )
    applied = block.get("decisions_applied") or {}
    for kind in ("scale_out", "scale_in"):
        if not applied.get(kind):
            problems.append(
                f"{name}: autoscale.decisions_applied[{kind!r}] = "
                f"{applied.get(kind)!r} — the diurnal trace must "
                "exercise both directions of the loop"
            )
    if block.get("replay_deterministic_ok") is not True:
        problems.append(
            f"{name}: autoscale.replay_deterministic_ok = "
            f"{block.get('replay_deterministic_ok')!r} — the same "
            "snapshot schedule must produce a byte-identical "
            "decision stream"
        )
    if block.get("autoscale_ok") is not True:
        problems.append(
            f"{name}: autoscale.autoscale_ok = "
            f"{block.get('autoscale_ok')!r} — the section's own "
            "verdict must be true"
        )
    return problems


def run_autoscale_check(artifact_path: Optional[str] = None) -> List[str]:
    return check_autoscale_block(
        artifact_path or canonical_artifact_path())


# ----------------------------------------------------------------------
# raw decode speed: speculative decoding + step-granular continuous
# batching (ISSUE 19). The bench's cluster_lm_sharded section grows
# `specdec` and `cb` sub-blocks (inference/lm_sharded.py
# bench_specdec_arm / bench_cb_arm); a round-21+ artifact must show
# the speculative arm beating plain chunked decode token-identically
# at its declared acceptance, the miscalibrated draft auto-disabling
# instead of dragging, and overlap adoption beating the batch-drain
# baseline on p99 TTFT.
# ----------------------------------------------------------------------

SPECDEC_REQUIRED_FROM_ROUND = 21


def check_specdec_block(path: str) -> List[str]:
    """Validate the raw-decode arms inside ``cluster_lm_sharded``
    WHEN THE SECTION RAN:

    - the speculative arm's outputs are token-identical to the plain
      chunked path (greedy verify is exactness-preserving — any drift
      means the verify/commit seam is wrong, not "close enough");
    - measured acceptance lands near the bench's declared rate (the
      oracle proposer's corruption schedule pins it — drift means the
      acceptance accounting lies);
    - steady tok/s speedup > 1 at that acceptance (below break-even
      the feature must auto-disable, not ship);
    - the miscalibrated-draft arm DID auto-disable (reason recorded)
      and still produced exact outputs;
    - the continuous-batching overlap arm strictly beat the
      batch-drain baseline on p99 TTFT with equal outputs.

    Artifacts before round ``SPECDEC_REQUIRED_FROM_ROUND`` are
    exempt; summary-only driver captures gate on the compact line's
    ``lm_specdec_speedup`` / ``lm_specdec_accept`` /
    ``lm_cb_ttft_ms`` keys."""
    from .parity_table import load_bench

    name = os.path.basename(path)
    rnd = artifact_round(path)
    if rnd is not None and rnd < SPECDEC_REQUIRED_FROM_ROUND:
        return []
    data = load_bench(path)
    if data.get("_summary_only"):
        s = data.get("summary") or {}
        problems = []
        speedup = s.get("lm_specdec_speedup")
        if isinstance(speedup, (int, float)) and speedup <= 1.0:
            problems.append(
                f"{name}: summary lm_specdec_speedup = {speedup!r} — "
                "the speculative arm must beat plain chunked decode "
                "on steady tok/s (below break-even it must disable, "
                "not ship a loss)"
            )
        accept = s.get("lm_specdec_accept")
        if isinstance(accept, (int, float)) and not (
                0.0 < accept <= 1.0):
            problems.append(
                f"{name}: summary lm_specdec_accept = {accept!r} — "
                "measured acceptance must be a fraction in (0, 1]"
            )
        ttft = s.get("lm_cb_ttft_ms")
        if isinstance(ttft, (int, float)) and ttft <= 0:
            problems.append(
                f"{name}: summary lm_cb_ttft_ms = {ttft!r} — the "
                "overlap-adoption arm's p99 TTFT must be a positive "
                "wall-clock measurement"
            )
        return problems
    matrix = data.get("matrix", {})
    not_run = set(matrix.get("_skipped", {})) | set(matrix.get("_errors", {}))
    if "cluster_lm_sharded" in not_run:
        return []  # honestly recorded as skipped/errored
    block = matrix.get("cluster_lm_sharded")
    if block is None:
        if rnd is None and "cluster_serving" not in matrix:
            return []  # partial/preview artifact without cluster runs
        return [f"{name}: no `cluster_lm_sharded` section and not "
                "recorded as skipped (raw-decode arms unproven)"]
    if block.get("skipped") or block.get("error"):
        return []  # section self-reported a skip/error payload
    problems: List[str] = []
    spec = block.get("specdec")
    if not isinstance(spec, dict):
        problems.append(
            f"{name}: cluster_lm_sharded.specdec = {spec!r} — "
            "round-21+ artifacts must carry the speculative-decode "
            "arm"
        )
    else:
        if spec.get("outputs_equal") is not True:
            problems.append(
                f"{name}: specdec.outputs_equal = "
                f"{spec.get('outputs_equal')!r} — greedy speculative "
                "decode must be token-identical to the plain path"
            )
        accept = spec.get("accept_rate")
        declared = spec.get("declared_accept")
        if not isinstance(accept, (int, float)) or not (
                0.0 < accept <= 1.0):
            problems.append(
                f"{name}: specdec.accept_rate = {accept!r} — "
                "measured acceptance must be a fraction in (0, 1]"
            )
        elif isinstance(declared, (int, float)) and abs(
                accept - declared) > 0.15:
            problems.append(
                f"{name}: specdec.accept_rate = {accept!r} vs "
                f"declared_accept = {declared!r} — the oracle arm's "
                "measured acceptance must land near the declared "
                "rate (acceptance accounting drifted)"
            )
        speedup = spec.get("speedup")
        if not isinstance(speedup, (int, float)) or speedup <= 1.0:
            problems.append(
                f"{name}: specdec.speedup = {speedup!r} — the "
                "speculative arm must beat plain chunked decode on "
                "steady tok/s"
            )
        auto = spec.get("auto_disable") or {}
        if auto.get("disabled") is not True:
            problems.append(
                f"{name}: specdec.auto_disable.disabled = "
                f"{auto.get('disabled')!r} — the miscalibrated draft "
                "must trip the break-even guard"
            )
        if auto.get("outputs_equal") is not True:
            problems.append(
                f"{name}: specdec.auto_disable.outputs_equal = "
                f"{auto.get('outputs_equal')!r} — outputs must stay "
                "exact even while a bad draft is being rejected"
            )
        if spec.get("verdict_green") is not True:
            problems.append(
                f"{name}: specdec.verdict_green = "
                f"{spec.get('verdict_green')!r} — the arm's own "
                "verdict must be true"
            )
    cb = block.get("cb")
    if not isinstance(cb, dict):
        problems.append(
            f"{name}: cluster_lm_sharded.cb = {cb!r} — round-21+ "
            "artifacts must carry the continuous-batching arm"
        )
    else:
        if cb.get("outputs_equal") is not True:
            problems.append(
                f"{name}: cb.outputs_equal = "
                f"{cb.get('outputs_equal')!r} — step-boundary "
                "adoption must not perturb decoded tokens"
            )
        ratio = cb.get("drain_vs_overlap_p99")
        if not isinstance(ratio, (int, float)) or ratio <= 1.0:
            problems.append(
                f"{name}: cb.drain_vs_overlap_p99 = {ratio!r} — "
                "overlap adoption must strictly beat the batch-drain "
                "baseline on p99 TTFT under staggered load"
            )
        if cb.get("verdict_green") is not True:
            problems.append(
                f"{name}: cb.verdict_green = "
                f"{cb.get('verdict_green')!r} — the arm's own "
                "verdict must be true"
            )
    return problems


def run_specdec_check(artifact_path: Optional[str] = None) -> List[str]:
    return check_specdec_block(
        artifact_path or canonical_artifact_path())


# ----------------------------------------------------------------------
# round-22 elastic cluster training: TrainJob as a first-class
# workload (jobs/train.py; bench _bench_cluster_training; ISSUE 20
# tentpole). The claim is step-exact elasticity: examples/s must RISE
# when capacity joins mid-run via re-shard at a step boundary (zero
# restarts), no global step lost or double-applied, and the trainer
# must not evict interactive work past its SLO deadline.
# ----------------------------------------------------------------------

#: first round whose bench must carry the cluster_training section;
#: earlier artifacts predate the TrainJob subsystem
TRAIN_REQUIRED_FROM_ROUND = 22


def check_train_block(path: str) -> List[str]:
    """Validate the ``cluster_training`` section WHEN IT RAN:

    - the scaling arm's examples/s strictly rose after capacity
      joined mid-run (``scaleout_gain`` > 1 with a world-growing
      curve) — an elastic trainer that cannot convert joins into
      throughput is elastic in name only;
    - at least one ``join`` re-shard happened at a step boundary and
      zero nodes were restarted to get it (capacity moves through
      the authenticated join path, never through crashes);
    - the post-run invariant sweep came back green — it replays the
      step ledger against the exactly-once oracle, so a green sweep
      IS the no-step-lost/no-step-double-applied proof;
    - the mixed arm kept interactive p99 within its SLO deadline
      while the trainer shared the pool.

    Artifacts before round ``TRAIN_REQUIRED_FROM_ROUND`` are exempt;
    summary-only driver captures gate on the compact line's
    ``train_step_qps`` / ``train_elastic_ok`` keys."""
    from .parity_table import load_bench

    name = os.path.basename(path)
    rnd = artifact_round(path)
    if rnd is not None and rnd < TRAIN_REQUIRED_FROM_ROUND:
        return []
    data = load_bench(path)
    if data.get("_summary_only"):
        s = data.get("summary") or {}
        problems = []
        if s.get("train_elastic_ok") is False:
            problems.append(
                f"{name}: summary train_elastic_ok is false — the "
                "trainer lost a step, failed to scale on join, or "
                "evicted interactive work past its deadline"
            )
        qps = s.get("train_step_qps")
        if isinstance(qps, (int, float)) and qps <= 0:
            problems.append(
                f"{name}: summary train_step_qps = {qps!r} — the "
                "mixed arm's trainer examples/s must be positive"
            )
        return problems
    matrix = data.get("matrix", {})
    not_run = set(matrix.get("_skipped", {})) | set(matrix.get("_errors", {}))
    if "cluster_training" in not_run:
        return []  # honestly recorded as skipped/errored
    block = matrix.get("cluster_training")
    if block is None:
        if rnd is None and "cluster_serving" not in matrix:
            return []  # partial/preview artifact without cluster runs
        return [f"{name}: no `cluster_training` section and not "
                "recorded as skipped (elastic-training claim unproven)"]
    problems: List[str] = []
    gain = block.get("scaleout_gain")
    if not isinstance(gain, (int, float)) or gain <= 1.0:
        problems.append(
            f"{name}: cluster_training.scaleout_gain = {gain!r} — "
            "examples/s must strictly rise after capacity joins "
            "mid-run"
        )
    curve = block.get("scaling_curve")
    if (not isinstance(curve, list) or len(curve) < 2
            or not all(isinstance(p, dict) for p in curve)):
        problems.append(
            f"{name}: cluster_training.scaling_curve = {curve!r} — "
            "the section must record the step-throughput curve "
            "across at least two pool sizes"
        )
    else:
        worlds = [p.get("world") for p in curve]
        if worlds != sorted(worlds) or worlds[-1] <= worlds[0]:
            problems.append(
                f"{name}: cluster_training.scaling_curve worlds = "
                f"{worlds!r} — the data-parallel world must grow "
                "across the curve (joins never re-sharded the run?)"
            )
    if not block.get("join_reshards"):
        problems.append(
            f"{name}: cluster_training.join_reshards = "
            f"{block.get('join_reshards')!r} — at least one join "
            "must land as a step-boundary re-shard"
        )
    if block.get("restarts") != 0:
        problems.append(
            f"{name}: cluster_training.restarts = "
            f"{block.get('restarts')!r} — elasticity must come from "
            "re-sharding, never from restarting nodes"
        )
    if block.get("sweep_ok") is not True:
        problems.append(
            f"{name}: cluster_training.sweep_ok = "
            f"{block.get('sweep_ok')!r} — the invariant sweep replays "
            "the step ledger against the exactly-once oracle; it "
            "must be green"
        )
    mixed = block.get("mixed") or {}
    p99 = mixed.get("interactive_p99_with_trainer_s")
    deadline = mixed.get("interactive_deadline_s")
    if (isinstance(p99, (int, float)) and isinstance(
            deadline, (int, float)) and p99 > deadline):
        problems.append(
            f"{name}: cluster_training.mixed interactive p99 = "
            f"{p99!r}s > deadline {deadline!r}s — the trainer must "
            "not push interactive work past its SLO class"
        )
    if block.get("train_elastic_ok") is not True:
        problems.append(
            f"{name}: cluster_training.train_elastic_ok = "
            f"{block.get('train_elastic_ok')!r} — the section's own "
            "verdict must be true"
        )
    return problems


def run_train_check(artifact_path: Optional[str] = None) -> List[str]:
    return check_train_block(
        artifact_path or canonical_artifact_path())


# ----------------------------------------------------------------------
# artifact-of-record provenance: the PARITY table must not stay
# stamped from a builder preview once the same round's DRIVER capture
# exists and parses (ISSUE 4 satellite; VERDICT r5 item 1)
# ----------------------------------------------------------------------

_PREVIEW_RE = re.compile(r"BENCH_r(\d+)_preview\.json$")


def check_parity_source(parity_path: Optional[str] = None) -> List[str]:
    """Flag a PARITY table whose ``source=`` is a preview while a
    parseable same-round driver capture exists. `latest_bench_path`
    already tie-breaks driver over preview; this makes skipping the
    post-driver re-stamp a visible violation instead of a silent
    dependence on builder-run numbers."""
    from .parity_table import load_bench

    parity_path = parity_path or os.path.join(REPO, "PARITY.md")
    with open(parity_path) as f:
        text = f.read()
    m = re.search(r"BENCH-TABLE:BEGIN source=(\S+)", text)
    if not m:
        return [f"{os.path.basename(parity_path)}: no BENCH-TABLE "
                "source marker"]
    src = m.group(1)
    pm = _PREVIEW_RE.match(os.path.basename(src))
    if not pm:
        return []
    driver = f"BENCH_r{pm.group(1)}.json"
    dpath = os.path.join(os.path.dirname(parity_path) or REPO, driver)
    if not os.path.exists(dpath):
        return []
    if load_bench(dpath).get("_unparseable_wrapper"):
        return []  # driver capture exists but is unrecoverable
    return [
        f"PARITY.md table is stamped from the builder preview {src} "
        f"while the same-round driver capture {driver} exists and "
        f"parses — regenerate: python -m dml_tpu.tools.parity_table "
        f"--bench {driver} --write"
    ]


def main() -> None:
    art_path = canonical_artifact_path()
    print("artifact of record: " + (
        os.path.basename(art_path) if art_path is not None
        else "none (not measured on the current installation)"
    ))
    total = 0
    for name, bad in run_check().items():
        for i, line, v, unit in bad:
            total += 1
            print(f"{name}:{i}: unlabeled {v:g} {unit} not in artifact")
            print(f"    {line[:120]}")
    block_checks = (
        ("metrics", run_metrics_check), ("chaos", run_chaos_check),
        ("serving", run_serving_check), ("sharded", run_sharded_check),
        ("lm-sharded", run_lm_sharded_check),
        ("request", run_request_check), ("tracing", run_tracing_check),
        ("kv-cache", run_kv_cache_check), ("lint", run_lint_check),
        ("scale", run_scale_check), ("elastic", run_elastic_check),
        ("signal", run_signal_check), ("autoscale", run_autoscale_check),
        ("specdec", run_specdec_check), ("train", run_train_check),
    )
    for label, check in block_checks if art_path is not None else ():
        for problem in check(art_path):
            total += 1
            print(f"{label} block: {problem}")
    for problem in check_parity_source():
        total += 1
        print(f"parity source: {problem}")
    print(f"{total} violation(s)")
    raise SystemExit(1 if total else 0)


if __name__ == "__main__":
    main()
