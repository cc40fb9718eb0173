"""Perf-claim hygiene (VERDICT r4 item 7): README/PARITY prose numbers
must trace to the canonical artifact or carry a run label. Two layers:
the real docs must be clean right now, and the checker itself must
actually catch the r4 failure modes (a drifted ratio, an unlabeled
stale rate) — a hygiene gate that can't detect drift is decoration."""

import json

from dml_tpu.tools import claim_check as cc


def test_readme_and_parity_are_clean():
    violations = cc.run_check()
    msgs = [
        f"{name}:{i}: {v:g} {unit} | {line[:90]}"
        for name, bad in violations.items()
        for i, line, v, unit in bad
    ]
    assert not msgs, "unlabeled perf claims not in the artifact:\n" + "\n".join(msgs)


def test_checker_catches_r4_failure_modes(tmp_path):
    art = tmp_path / "BENCH_x.json"
    art.write_text(json.dumps({
        "lm": {"kv_speedup": 1.02, "gen_tok_per_s": 79.6,
               # the collision that false-passed r4's stale 197.7 q/s
               # before rate claims were scoped to rate-like keys: a
               # parameter COUNT numerically equal to the stale rate
               "params_millions": 197.7},
        "qps": 14224.2, "mfu": 0.54,
    }))
    buckets = cc.artifact_numbers(str(art))

    md = tmp_path / "doc.md"
    md.write_text("\n".join([
        "# Title",
        "",
        "Measured 1.02× over the bf16 cache.",          # ok: matches ratio key
        "The kernel measured 1.10× over the cache.",    # DRIFT (r4's int8-KV)
        "Serving reached 86 gen tok/s end-to-end.",     # DRIFT (r4's 86-vs-79.6)
        "Serving reached 79.6 gen tok/s end-to-end.",   # ok: artifact value
        "An older run measured 86.5 gen tok/s (r4 capture).",  # ok: labeled
        "Headline ≈14,224 q/s at 54% MFU.",             # ok: value + mfu key
        # DRIFT: a stale rate that collides with params_millions must
        # still be caught (kind-scoped buckets)
        "Cluster serving measured 197.7 q/s that day.",
        # DRIFT: "-bound" prose style must NOT exempt the line (the
        # bare word 'bound' as a derivation label still does)
        "Serving (86 gen tok/s) is control-plane-bound today.",
        "A bandwidth bound of 6.4× applies here.",      # ok: labeled (bound)
        "",
        "## Historical analysis (round 3)",
        "That round served 12,400 q/s.",                # ok: heading label
    ]))
    bad = cc.check_file(str(md), buckets)
    flagged = {v for _, _, v, _ in bad}
    assert flagged == {1.10, 86.0, 197.7}, f"got: {bad}"
    assert sum(v == 86.0 for _, _, v, _ in bad) == 2  # both 86 lines


def test_checker_skips_generated_block(tmp_path):
    art = tmp_path / "a.json"
    art.write_text(json.dumps({"x": 1.0}))
    buckets = cc.artifact_numbers(str(art))
    md = tmp_path / "doc.md"
    md.write_text("\n".join([
        "<!-- BENCH-TABLE:BEGIN source=a.json sha1=abc -->",
        "| table row with 9,999 q/s and 77× claims |",
        "<!-- BENCH-TABLE:END -->",
    ]))
    assert cc.check_file(str(md), buckets) == []


def test_canonical_artifact_path_parses_parity_marker(tmp_path):
    parity = tmp_path / "PARITY.md"
    parity.write_text(
        "intro\n<!-- BENCH-TABLE:BEGIN source=BENCH_x.json sha1=abc -->\n"
        "<!-- BENCH-TABLE:END -->\n"
    )
    path = cc.canonical_artifact_path(str(parity))
    assert path.endswith("BENCH_x.json")
    # the repo itself: nothing measured on the current installation,
    # so no artifact of record — and every bucket a claim could match
    # against is empty (only labeled claims pass run_check)
    assert cc.canonical_artifact_path() is None
    assert not any(cc.artifact_numbers(None).values())
    parity.write_text("no marker here\n")
    import pytest

    with pytest.raises(ValueError):
        cc.canonical_artifact_path(str(parity))
