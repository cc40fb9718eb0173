"""Generate PARITY.md's performance table from a BENCH_r*.json.

VERDICT r2 item 2: round 2's hand-maintained perf table claimed a
cluster-serving number (196 q/s) that the driver's own capture
contradicted (110.6 q/s). Hand-edited tables drift; this tool makes
the table a pure function of the bench artifact:

- every cell is computed from named keys of ONE bench json (the file
  and its short sha1 are recorded on the marker line);
- `--write` splices the table into PARITY.md between
  `<!-- BENCH-TABLE:BEGIN ... -->` / `<!-- BENCH-TABLE:END -->`;
- tests/test_parity_table.py regenerates from the recorded source and
  fails if the committed table was edited by hand or went stale.

Run: ``python -m dml_tpu.tools.parity_table [--bench FILE] [--write]``
(default --bench: the highest-numbered BENCH_r*.json in the repo
root, preview files included).

Reference baseline numbers quoted in the left column come from the
reference's own measurements (reference test.py:109-131; SURVEY §6).
"""

from __future__ import annotations

import argparse
import glob
import hashlib
import json
import os
import re
from typing import Any, Dict, List, Optional

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
PARITY_PATH = os.path.join(REPO_ROOT, "PARITY.md")
BEGIN_RE = re.compile(
    r"<!-- BENCH-TABLE:BEGIN source=(?P<src>\S+) sha1=(?P<sha>[0-9a-f]+) -->"
)
END_MARK = "<!-- BENCH-TABLE:END -->"
#: the marker's source when no bench artifact exists for the current
#: installation (tools/claim_check.py then has no artifact of record)
NO_SOURCE = "none"


def latest_bench_path() -> Optional[str]:
    """Highest-round BENCH_r*.json in the repo root. Previews count,
    but on a same-round tie the driver's capture wins (the preview is
    the builder's stale stand-in once BENCH_rNN.json exists); ties
    otherwise break by name for determinism."""
    best = None
    best_key = (-1, -1, "")
    for p in sorted(glob.glob(os.path.join(REPO_ROOT, "BENCH_r*.json"))):
        name = os.path.basename(p)
        m = re.search(r"BENCH_r(\d+)", name)
        if not m:
            continue
        key = (int(m.group(1)), 0 if "preview" in name else 1, name)
        if key > best_key:
            best, best_key = p, key
    return best


def _short_sha1(path: str) -> str:
    with open(path, "rb") as f:
        return hashlib.sha1(f.read()).hexdigest()[:12]


def _num(x, nd=0):
    """Format a number; anything non-numeric renders as n/a (schema
    drift must degrade the cell, not crash the generator)."""
    if not isinstance(x, (int, float)):
        return "n/a"
    return f"{x:,.{nd}f}"


def _mfu_txt(mfu, label="MFU", prefix=" (", suffix=")"):
    """'(54% MFU)'-style fragment, or empty when absent — the ONE
    formatting site for MFU cells."""
    if not isinstance(mfu, (int, float)):
        return ""
    return f"{prefix}{mfu * 100:.0f}% {label}{suffix}"


def _summary_rows(s: Dict[str, Any]) -> List[List[str]]:
    """Rows computable from the compact driver summary alone (the
    artifact of record when only the driver's stdout tail survived).
    Fewer rows than the full matrix — every cell still traces to the
    driver capture, which is the point."""
    rows: List[List[str]] = []

    def row(metric: str, ref: str, ours: str) -> None:
        rows.append([metric, ref, ours])

    qps = s.get("headline_qps")
    if isinstance(qps, (int, float)) and qps > 0:
        row(
            "ResNet50 steady inference",
            "250 ms/image (4 q/s/node)",
            f"≈{1000.0/qps:.3f} ms/image at batch 32 (≈{_num(qps)} "
            f"q/s/chip{_mfu_txt(s.get('headline_mfu'), prefix=', ', suffix='')})",
        )
    if isinstance(s.get("c4_qps"), (int, float)):
        row(
            "Dual-model C4 fair-share", "manual 10-VM runs",
            f"{s['c4_qps']} q/s serving with the probe-chosen "
            f"'{s.get('c4_mode', 'n/a')}' dispatch "
            f"({s.get('pipelining', 'n/a')}× vs the reference-shaped "
            "sync loop)",
        )
    if isinstance(s.get("cluster_qps"), (int, float)):
        depth = s.get("cluster_depth")
        b128 = s.get("cluster_qps_b128")
        b128_txt = (
            f"; b128 {b128} q/s" if isinstance(b128, (int, float)) else ""
        )
        if depth is not None:  # r6+ schema: probe-adaptive serving
            detail = (
                f"adaptive depth (committed {depth}) — forced "
                f"statics: depth-1 {s.get('cluster_qps_unpipelined', 'n/a')} "
                f"q/s / depth-2 "
                f"{s.get('cluster_qps_pipelined_static', 'n/a')} q/s; "
                f"adaptive vs best static "
                f"{s.get('cluster_pipelining', 'n/a')}×"
            )
        else:  # r3..r5 schema: static depth-2 pipelining keys
            detail = (
                f"serial depth-1 {s.get('cluster_qps_unpipelined', 'n/a')} "
                f"q/s; static depth-2 pipelining ratio "
                f"{s.get('cluster_pipelining', 'n/a')}× (cold cache)"
            )
        row(
            "Cluster serving end-to-end (4 nodes, SDFS-replicated "
            "JPEGs, batch 32)",
            "≈0.8 q/s/node (25-image task in ~31 s)",
            f"≈{s['cluster_qps']} q/s through the full stack with "
            f"{detail}{b128_txt}",
        )
    if isinstance(s.get("cluster_lm_tok_s"), (int, float)):
        steady = s.get("cluster_lm_steady_tok_s")
        steady_txt = (
            f"; steady state (≥{_num(s.get('cluster_lm_steady_s', 15))} s "
            f"refill, ramp excluded) {_num(steady)} tok/s"
            if isinstance(steady, (int, float)) else ""
        )
        row(
            "Distributed LM serving end-to-end (4 nodes, "
            "store-replicated prompts)",
            "— (reference has no sequence serving)",
            f"{_num(s['cluster_lm_tok_s'])} gen tok/s transient"
            f"{steady_txt}",
        )
    lm_tok = s.get("lm_tok_s")
    if isinstance(lm_tok, dict) and lm_tok:
        row(
            "LM decode by weight form (B=1)", "—",
            ", ".join(
                f"{k} {_num(v)} tok/s" for k, v in lm_tok.items()
                if isinstance(v, (int, float))
            ),
        )
    if isinstance(s.get("cb_gain"), (int, float)):
        row("Continuous-batching decode (8 vs 1 slots)", "—",
            f"{s['cb_gain']}× aggregate")
    if isinstance(s.get("train_img_s"), (int, float)):
        row(
            "ResNet50 train step (fwd+bwd+SGD, b32)",
            "— (reference does no training)",
            f"{_num(s['train_img_s'])} img/s"
            + _mfu_txt(s.get("train_mfu"), label="fwd+bwd MFU"),
        )
    if isinstance(s.get("train_lm_tok_s"), (int, float)):
        row(
            "LM train step (198M, T=2048)",
            "— (reference does no training)",
            f"{_num(s['train_lm_tok_s'])} tok/s",
        )
    if isinstance(qps, (int, float)) and qps > 0:
        row("`vs_baseline` (bench.py headline)", "1×",
            f"≈{_num(qps / 4.0)}×")
    return rows


def render_table(bench: Dict[str, Any], source: str, sha1: str) -> str:
    """The markdown block, markers included. Missing sections render
    as 'n/a (pending next bench run)' so a schema change degrades the
    table instead of faking numbers. A driver capture recovered as
    summary-only renders the summary-derived rows and says so."""
    if bench.get("_summary_only"):
        rows = _summary_rows(bench.get("summary") or {})
        lines = [
            f"<!-- BENCH-TABLE:BEGIN source={source} sha1={sha1} -->",
            "",
            f"*Generated by `python -m dml_tpu.tools.parity_table` from "
            f"`{source}` (sha1 {sha1}) — do not edit by hand; "
            "tests/test_parity_table.py enforces this.*",
            "",
            "*Source is the DRIVER capture's compact summary (the "
            "artifact of record); per-section detail beyond these rows "
            "lives in the same-round preview artifact.*",
            "",
            "| Metric | Reference (CPU, CS425 VMs) | dml_tpu (1× TPU v5e) |",
            "|---|---|---|",
        ]
        for r in rows:
            lines.append("| " + " | ".join(r) + " |")
        if not rows:
            lines.append("| (driver summary carried no renderable "
                         "rows) | — | — |")
        lines += ["", END_MARK]
        return "\n".join(lines)
    m = bench.get("matrix", bench)
    rows: List[List[str]] = []

    def row(metric: str, ref: str, ours: str) -> None:
        rows.append([metric, ref, ours])

    hl = m.get("headline_resnet50_b32") or {}
    qps = hl.get("qps")
    if isinstance(qps, (int, float)) and qps > 0:
        mfu_txt = _mfu_txt(hl.get("mfu"), prefix=", ", suffix="")
        row(
            "ResNet50 steady inference",
            "250 ms/image (4 q/s/node)",
            f"≈{1000.0/qps:.3f} ms/image at batch 32 "
            f"(≈{_num(qps)} q/s/chip{mfu_txt})",
        )
    sweep = m.get("resnet50_sweep") or []
    if sweep:
        sweep_qps = [p["qps"] for p in sweep if "qps" in p]
        row(
            f"ResNet50 batch sweep {sweep[0]['batch']}..{sweep[-1]['batch']}",
            "—",
            f"{min(sweep_qps)/1000:.1f}k–{max(sweep_qps)/1000:.1f}k q/s; "
            f"batch {m.get('resnet50_throughput_optimal_batch', '?')} is "
            "throughput-optimal",
        )
    def _model_pts(points):
        out = []
        for p in points:
            if not isinstance(p.get("qps"), (int, float)):
                continue
            out.append(
                f"b{p.get('batch', '?')} ≈{p['qps']/1000:.1f}k q/s"
                + _mfu_txt(p.get("mfu"))
            )
        return ", ".join(out)

    inc = m.get("inceptionv3") or []
    if inc:
        row("InceptionV3 steady inference",
            "325 ms/image (3.1 q/s/node)", _model_pts(inc) + " per chip")
    b4 = m.get("efficientnet_b4") or []
    if b4:
        row("EfficientNet-B4 (plug-in model)", "—",
            _model_pts(b4) + " per chip")
    c4 = m.get("dual_model_c4") or {}
    if c4:
        if "combined_qps_auto" in c4:  # r5 schema: auto-chosen mode
            ours = (
                f"{c4['combined_qps_auto']} q/s serving with the "
                f"probe-chosen '{c4.get('dispatch_mode_auto', 'n/a')}' "
                f"dispatch ({c4.get('pipelining_speedup', 'n/a')}× vs "
                f"the reference-shaped sync loop); forced modes: sync "
                f"{c4.get('combined_qps_sync', 'n/a')} / pipelined "
                f"{c4.get('combined_qps_pipelined', 'n/a')} q/s "
                f"({c4.get('pipelined_vs_sync_forced', 'n/a')}×) "
                "through the real fair-share scheduler (per-batch "
                "dispatch included)"
            )
        elif "combined_qps_pipelined" in c4:  # r3/r4 schema
            ours = (
                f"{c4['combined_qps_sync']} q/s sync → "
                f"{c4['combined_qps_pipelined']} q/s with pipelined "
                f"dispatch ({c4.get('pipelining_speedup', 'n/a')}×) "
                "through the real fair-share scheduler (per-batch "
                "dispatch included)"
            )
        else:  # r2 schema
            ours = (
                f"{c4.get('combined_qps_incl_dispatch', 'n/a')} q/s "
                "incl. per-batch dispatch (capability, not peak "
                "— see sweep)"
            )
        row("Dual-model C4 fair-share", "manual 10-VM runs", ours)
    cs = m.get("cluster_serving") or {}
    if cs:
        extra = ""
        if "breakdown" in cs:
            b = cs["breakdown"]
            extra = " (" + ", ".join(
                f"{k} {v}" for k, v in b.items()
            ) + ")"
        fi = m.get("cluster_serving_failure") or {}
        fi_txt = ""
        if fi:
            fi_txt = (
                f"; worker killed mid-job "
                f"({fi.get('model', 'same model')}): "
                f"{fi.get('completed', 'n/a')}/{fi.get('queries', 'n/a')} "
                f"completed, detect→requeue "
                f"{fi.get('detect_to_requeue_s', 'n/a')} s, wall "
                f"{fi.get('wall_s', 'n/a')} s"
            )
        pipe_txt = ""
        if "adaptive" in cs:  # r6 schema: probe-adaptive depth
            ad = cs.get("adaptive") or {}
            d1c = cs.get("qps_depth1_static",
                         cs.get("qps_unpipelined", "n/a"))
            pipe_txt = (
                f" — reference serial loop "
                f"{cs.get('qps_unpipelined', 'n/a')} q/s; cache-matched "
                f"forced statics: depth-1 {d1c} / depth-2 "
                f"{cs.get('qps_pipelined_static', 'n/a')} q/s "
                f"({cs.get('pipelining_speedup_static', 'n/a')}×); the "
                f"adaptive controller committed depth "
                f"{ad.get('depth', 'n/a')} and served "
                f"{cs.get('qps_end_to_end', 'n/a')} q/s "
                f"({cs.get('pipelining_speedup', 'n/a')}× vs the "
                "better static)"
            )
        elif "qps_unpipelined" in cs:  # r3..r5 schema: static depth 2
            pipe_txt = (
                f" — serial worker loop {cs['qps_unpipelined']} q/s → "
                f"depth-2 pipelined "
                f"{cs.get('qps_pipelined_cold_cache', 'n/a')} q/s "
                f"({cs.get('pipelining_speedup', 'n/a')}×) → + decode "
                f"cache {cs.get('qps_end_to_end', 'n/a')} q/s"
            )
        row(
            f"Cluster serving end-to-end ({cs.get('nodes', '?')} nodes, "
            "SDFS-replicated JPEGs, batch 32)",
            "≈0.8 q/s/node (25-image task in ~31 s)",
            f"≈{cs.get('qps_end_to_end', 'n/a')} q/s through the full "
            f"stack{pipe_txt}{extra}{fi_txt}",
        )
    pl = m.get("pallas_on_device") or {}
    if pl:
        row(
            f"Flash-attention kernel ({pl.get('shape', '?')})",
            "—",
            f"{pl.get('flash_fwd_ms', 'n/a')} ms fwd, "
            f"{pl.get('flash_vs_naive_speedup', 'n/a')}× naive XLA; "
            f"ring body {pl.get('ring_flash_speedup', 'n/a')}× its "
            "dense form"
            + ("" if pl.get("parity_pass", True) else
               " — PARITY CHECK FAILED, see bench json"),
        )
    lm = m.get("lm") or {}
    if lm:
        forms = lm.get("decode_weight_forms_b1") or {}
        if forms:
            row(
                "LM decode by weight form "
                f"({lm.get('params_millions', '?')}M params, B=1)",
                "—",
                ", ".join(
                    f"{k} {_num(forms[k].get('tok_per_s'))} tok/s"
                    for k in ("f32", "bf16", "int8")
                    if isinstance(forms.get(k), dict)
                ),
            )
        heads = lm.get("decode_kv_heads_4k_ctx_b1") or {}
        if heads:
            row(
                "LM decode at 4k context by KV heads (B=1, bf16)",
                "—",
                ", ".join(
                    f"{k.upper()} {_num(heads[k].get('tok_per_s'))} tok/s"
                    for k in ("mha", "gqa4", "mqa")
                    if isinstance(heads.get(k), dict)
                )
                + f"; GQA-4 = {heads.get('gqa4_vs_mha_speedup', 'n/a')}× MHA",
            )
        kq = lm.get("kv_cache_int8_4k_ctx_b8") or {}
        if kq:
            row(
                "int8 KV cache at 4k context (B=8, GQA-4)",
                "—",
                f"bf16 cache {_num(kq.get('bf16_cache_tok_per_s'))} → "
                f"int8 cache {_num(kq.get('int8_cache_tok_per_s'))} "
                f"tok/s ({kq.get('speedup', 'n/a')}×); "
                f"{kq.get('cache_mb_per_slot_bf16', 'n/a')} → "
                f"{kq.get('cache_mb_per_slot_int8', 'n/a')} MB/slot",
            )
        pf = lm.get("prefill_2k_prompt") or {}
        if pf:
            row(
                "LM prefill vs token-by-token scan (2k prompt)",
                "—",
                f"{pf.get('prefill_ms', 'n/a')} ms vs "
                f"{pf.get('scan_ms_est', 'n/a')} ms "
                f"({pf.get('speedup', 'n/a')}×)",
            )
        cb = lm.get("continuous_batching") or {}
        if cb:
            s1 = (cb.get("slots_1") or {}).get("aggregate_tok_per_s")
            s8 = (cb.get("slots_8") or {}).get("aggregate_tok_per_s")
            row(
                "Continuous-batching decode (device program)",
                "—",
                f"1 slot {_num(s1)} → 8 slots {_num(s8)} tok/s aggregate "
                f"({cb.get('batching_gain_8_vs_1', 'n/a')}×)",
            )
    clm = m.get("cluster_lm_serving") or {}
    if clm and "gen_tok_per_s_end_to_end" in clm:
        row(
            f"Distributed LM serving end-to-end ({clm.get('nodes', '?')} "
            f"nodes, store-replicated prompts)",
            "— (reference has no sequence serving)",
            f"{clm.get('prompts', 'n/a')} prompts × "
            f"{clm.get('new_tokens_per_prompt', 'n/a')} new tokens in "
            f"{clm.get('wall_s', 'n/a')} s = "
            f"{_num(clm['gen_tok_per_s_end_to_end'])} gen tok/s through "
            "the full stack",
        )
    tr = m.get("train") or {}
    cnn_tr = tr.get("resnet50_b32") or {}
    if cnn_tr:
        row(
            "ResNet50 train step (fwd+bwd+SGD, b32)",
            "— (reference does no training)",
            f"{_num(cnn_tr.get('img_per_s'))} img/s"
            + _mfu_txt(cnn_tr.get("mfu_fwd_bwd"), label="fwd+bwd MFU")
            + f", {cnn_tr.get('step_ms', 'n/a')} ms/step",
        )
    lm_tr = tr.get("lm_198m_t2048") or {}
    if lm_tr:
        row(
            "LM train step (198M, T=2048)",
            "— (reference does no training)",
            f"{_num(lm_tr.get('tok_per_s'))} tok/s"
            + _mfu_txt(lm_tr.get("mfu_fwd_bwd"), label="fwd+bwd MFU")
            + f", {lm_tr.get('step_ms', 'n/a')} ms/step",
        )
    if isinstance(qps, (int, float)) and qps > 0:
        row("`vs_baseline` (bench.py headline)", "1×",
            f"≈{_num(qps / 4.0)}×")

    lines = [
        f"<!-- BENCH-TABLE:BEGIN source={source} sha1={sha1} -->",
        "",
        f"*Generated by `python -m dml_tpu.tools.parity_table` from "
        f"`{source}` (sha1 {sha1}) — do not edit by hand; "
        "tests/test_parity_table.py enforces this.*",
        "",
        "| Metric | Reference (CPU, CS425 VMs) | dml_tpu (1× TPU v5e) |",
        "|---|---|---|",
    ]
    for r in rows:
        lines.append("| " + " | ".join(r) + " |")
    if not rows:
        lines.append(
            "| (source file is a truncated driver wrapper — "
            "regenerate from a raw bench.py output) | — | — |"
        )
    lines += ["", END_MARK]
    return "\n".join(lines)


def load_bench(bench_path: str) -> Dict[str, Any]:
    """A bench artifact in any of its shipped forms:

    - the raw bench.py stdout saved as JSON (preview files) — the
      giant artifact line, parsed whole;
    - the driver's wrapper ({"cmd", "rc", "tail", ...}) whose 2,000-
      char `tail` usually truncates the artifact line. Recovery, in
      preference order: (1) the artifact line survived whole; (2) the
      bench's final STANDALONE compact summary line
      (``bench_summary_v1``, emitted since round 6 precisely to
      survive this tail); (3) the trailing ``summary`` object salvaged
      from the truncated artifact line (it is the artifact's LAST key
      by design). Salvaged forms carry ``_summary_only=True`` — the
      table renders from summary keys and says so.

    Only when none of that works does this degrade to
    ``{"_unparseable_wrapper": True}`` (deterministic empty table with
    a note) rather than aborting."""
    with open(bench_path) as f:
        data = json.load(f)
    if "tail" not in data or "metric" in data:
        return data
    tail = data["tail"]
    try:
        # raw_decode, not loads: a round-6+ tail holds the artifact
        # line FOLLOWED by the compact summary line — trailing data
        # must not disqualify an intact full artifact
        doc, _ = json.JSONDecoder().raw_decode(tail[tail.index("{"):])
        if isinstance(doc, dict) and (
            "matrix" in doc or "metric" in doc
        ):
            return doc
    except ValueError:
        pass  # no "{" / not JSON: fall through to the compact line
    for line in reversed(tail.splitlines()):
        line = line.strip()
        if '"bench_summary_v1"' not in line:
            continue
        try:
            doc = json.loads(line[line.index("{"):])
        except Exception:
            continue
        doc["_summary_only"] = True
        return doc
    pos = tail.rfind('"summary"')
    if pos >= 0:
        try:
            start = tail.index("{", pos)
            summ, _ = json.JSONDecoder().raw_decode(tail[start:])
            if isinstance(summ, dict):
                return {"summary": summ, "_summary_only": True}
        except ValueError:
            pass  # truncated mid-summary: genuinely unparseable
    return {"_unparseable_wrapper": True}


def sanity_check(bench: Dict[str, Any]) -> List[str]:
    """Plausibility screen for a bench artifact — catches degenerate
    slope measurements (an r3 run recorded flash_fwd_ms = 0.0 and an
    8.8e6x 'speedup' when clock jitter swallowed a short chain)
    before they're committed into the published table. Returns a list
    of violations; empty = plausible. Ranges are generous physical
    bounds for one v5e-class chip, not expectations."""
    m = bench.get("matrix", bench)
    bad: List[str] = []

    def rng(path, val, lo, hi):
        if val is None:
            return
        if not isinstance(val, (int, float)) or not (lo <= val <= hi):
            bad.append(f"{path} = {val!r} outside [{lo}, {hi}]")

    if bench.get("_summary_only"):
        # driver-capture compact form: screen the summary-level numbers
        s = bench.get("summary") or {}
        rng("summary.headline_qps", s.get("headline_qps"), 1e3, 1e5)
        rng("summary.headline_mfu", s.get("headline_mfu"), 0.05, 1.0)
        rng("summary.cluster_qps", s.get("cluster_qps"), 1, 1e4)
        rng("summary.cluster_pipelining",
            s.get("cluster_pipelining"), 0.2, 20)
        rng("summary.cluster_lm_tok_s", s.get("cluster_lm_tok_s"), 0.5, 1e5)
        rng("summary.cluster_lm_steady_tok_s",
            s.get("cluster_lm_steady_tok_s"), 0.5, 1e5)
        rng("summary.train_img_s", s.get("train_img_s"), 10, 1e5)
        return bad

    hl = m.get("headline_resnet50_b32") or {}
    rng("headline.qps", hl.get("qps"), 1e3, 1e5)
    rng("headline.mfu", hl.get("mfu"), 0.05, 1.0)
    for pt in m.get("resnet50_sweep") or []:
        rng(f"sweep.b{pt.get('batch')}.qps", pt.get("qps"), 1e3, 1e5)
        rng(f"sweep.b{pt.get('batch')}.mfu", pt.get("mfu"), 0.01, 1.0)
    for section, lo, hi in (
        ("inceptionv3", 100, 5e4), ("efficientnet_b4", 50, 2e4)
    ):
        for pt in m.get(section) or []:
            rng(f"{section}.b{pt.get('batch')}.qps", pt.get("qps"), lo, hi)
            rng(f"{section}.b{pt.get('batch')}.mfu", pt.get("mfu"), 0.01, 1.0)
    pl = m.get("pallas_on_device") or {}
    rng("pallas.flash_fwd_ms", pl.get("flash_fwd_ms"), 0.2, 50)
    rng("pallas.flash_vs_naive_speedup",
        pl.get("flash_vs_naive_speedup"), 1, 100)
    rng("pallas.ring_flash_speedup", pl.get("ring_flash_speedup"), 1, 100)
    lm = m.get("lm") or {}
    for k, form in (lm.get("decode_weight_forms_b1") or {}).items():
        if isinstance(form, dict):
            rng(f"lm.forms.{k}.tok_per_s", form.get("tok_per_s"), 50, 5e4)
    for k, h in (lm.get("decode_kv_heads_4k_ctx_b1") or {}).items():
        if isinstance(h, dict):
            rng(f"lm.heads.{k}.tok_per_s", h.get("tok_per_s"), 50, 5e4)
    pf = lm.get("prefill_2k_prompt") or {}
    rng("lm.prefill_ms", pf.get("prefill_ms"), 1, 500)
    rng("lm.prefill_speedup", pf.get("speedup"), 2, 1000)
    cb = lm.get("continuous_batching") or {}
    rng("lm.cb.gain", cb.get("batching_gain_8_vs_1"), 0.5, 16)
    kq = lm.get("kv_cache_int8_4k_ctx_b8") or {}
    rng("lm.kv_int8.bf16_tok_per_s",
        kq.get("bf16_cache_tok_per_s"), 50, 1e5)
    rng("lm.kv_int8.int8_tok_per_s",
        kq.get("int8_cache_tok_per_s"), 50, 1e5)
    rng("lm.kv_int8.speedup", kq.get("speedup"), 0.05, 20)
    cs = m.get("cluster_serving") or {}
    rng("cluster.qps", cs.get("qps_end_to_end"), 1, 1e4)
    rng("cluster.qps_unpipelined", cs.get("qps_unpipelined"), 1, 1e4)
    rng("cluster.qps_depth1_static", cs.get("qps_depth1_static"), 1, 1e4)
    rng("cluster.qps_pipelined_static",
        cs.get("qps_pipelined_static"), 1, 1e4)
    rng("cluster.decode_cache_speedup",
        cs.get("decode_cache_speedup"), 0.2, 50)
    rng("cluster.pipelining_speedup", cs.get("pipelining_speedup"), 0.2, 20)
    rng("cluster.pipelining_speedup_static",
        cs.get("pipelining_speedup_static"), 0.2, 20)
    clm = m.get("cluster_lm_serving") or {}
    rng("cluster_lm.gen_tok_per_s",
        clm.get("gen_tok_per_s_end_to_end"), 0.5, 1e5)
    rng("cluster_lm.steady_tok_per_s",
        (clm.get("steady_state") or {}).get("gen_tok_per_s_steady"),
        0.5, 1e5)
    tr = m.get("train") or {}
    cnn_tr = tr.get("resnet50_b32") or {}
    rng("train.cnn.img_per_s", cnn_tr.get("img_per_s"), 10, 1e5)
    rng("train.cnn.step_ms", cnn_tr.get("step_ms"), 0.5, 1e4)
    rng("train.cnn.mfu", cnn_tr.get("mfu_fwd_bwd"), 0.01, 1.0)
    lm_tr = tr.get("lm_198m_t2048") or {}
    rng("train.lm.tok_per_s", lm_tr.get("tok_per_s"), 100, 1e7)
    rng("train.lm.step_ms", lm_tr.get("step_ms"), 0.5, 1e4)
    rng("train.lm.mfu", lm_tr.get("mfu_fwd_bwd"), 0.01, 1.0)
    # a numerically broken kernel must not publish its speedup rows:
    # parity_pass=False is a hard refusal, not a table footnote
    if pl and pl.get("parity_pass", True) is False:
        bad.append(
            "pallas_on_device.parity_pass = False (kernel output "
            "diverged from the XLA oracle; timings are meaningless)"
        )
    return bad


def generate(bench_path: Optional[str]) -> str:
    """The marked block for `bench_path` — or, for None (no bench
    artifact in the repo root), the block that says so: a table cell
    is a chip measurement or it is absent, never a stale number."""
    if bench_path is None:
        return "\n".join([
            f"<!-- BENCH-TABLE:BEGIN source={NO_SOURCE} sha1=0 -->",
            "",
            "*Not measured on the current installation.* No bench "
            "artifact exists for it: `python bench.py` on the chip "
            "produces one, and `python -m dml_tpu.tools.parity_table "
            "--bench FILE --write` renders it here. A number is a "
            "speed only if a chip run printed it with its "
            "`device_kind`.",
            "",
            END_MARK,
        ])
    return render_table(
        load_bench(bench_path),
        os.path.basename(bench_path),
        _short_sha1(bench_path),
    )


def splice(parity_text: str, table: str) -> str:
    begin = BEGIN_RE.search(parity_text)
    end = parity_text.find(END_MARK)
    if not begin or end < 0:
        raise ValueError(
            "PARITY.md has no BENCH-TABLE markers; add "
            "'<!-- BENCH-TABLE:BEGIN source=x sha1=0 -->' and "
            f"'{END_MARK}' around the perf table once"
        )
    return (
        parity_text[: begin.start()]
        + table
        + parity_text[end + len(END_MARK):]
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--bench", default=None, help="bench json path")
    ap.add_argument(
        "--write", action="store_true",
        help="splice the table into PARITY.md (default: print)",
    )
    args = ap.parse_args()
    bench_path = args.bench or latest_bench_path()
    # the plausibility screen gates generation, not just CI: a
    # degenerate slope artifact must be refused here, before an
    # implausible table can land in PARITY.md at all
    violations = (
        sanity_check(load_bench(bench_path)) if bench_path else []
    )
    if violations:
        raise SystemExit(
            f"{bench_path} fails the plausibility screen "
            f"(degenerate measurement?): {violations} — re-run the "
            "bench; see sanity_check()"
        )
    table = generate(bench_path)
    if args.write:
        with open(PARITY_PATH) as f:
            text = f.read()
        with open(PARITY_PATH, "w") as f:
            f.write(splice(text, table))
        print(f"PARITY.md table regenerated from {bench_path or NO_SOURCE}")
    else:
        print(table)


if __name__ == "__main__":
    main()
