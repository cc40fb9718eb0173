"""PARITY.md's perf table is machine-generated (VERDICT r2 item 2).

Round 2 shipped a hand-edited table whose cluster-serving number
contradicted the driver's own bench capture by 1.8x. The contract now:
the table between the BENCH-TABLE markers is a pure function of the
bench json named on the marker line, and this test regenerates it and
fails on any hand edit, stale number, or missing/changed source file.
"""

import os

import pytest

from dml_tpu.tools import parity_table as pt


def _read_parity():
    with open(pt.PARITY_PATH) as f:
        return f.read()


#: a small full-matrix artifact: enough sections to render real rows
FIXTURE_BENCH = {
    "matrix": {
        "headline_resnet50_b32": {"qps": 14000.0, "mfu": 0.54,
                                  "batch_ms": 2.29},
        "cluster_serving": {"nodes": 4, "qps_end_to_end": 75.0,
                            "qps_unpipelined": 62.0},
        "pallas_on_device": {"flash_fwd_ms": 1.5,
                             "flash_vs_naive_speedup": 5.0,
                             "parity_pass": True, "shape": "B4 T2048"},
    },
}


@pytest.fixture
def stamped(tmp_path, monkeypatch):
    """A repo root under tmp_path: one bench artifact and a PARITY.md
    whose table was generated from it (the records of the installation
    that is gone were deleted; the contract is pinned on a fixture)."""
    import json

    src = tmp_path / "BENCH_r09.json"
    src.write_text(json.dumps(FIXTURE_BENCH))
    monkeypatch.setattr(pt, "REPO_ROOT", str(tmp_path))
    parity = tmp_path / "PARITY.md"
    parity.write_text("# Parity\n\n" + pt.generate(str(src)) + "\n\ntail\n")
    return parity


def test_markers_present_and_source_exists(stamped):
    # the repo's own PARITY.md: markers present, stamped "not measured"
    text = _read_parity()
    m = pt.BEGIN_RE.search(text)
    assert m, "PARITY.md lost its BENCH-TABLE:BEGIN marker"
    assert pt.END_MARK in text, "PARITY.md lost its BENCH-TABLE:END marker"
    assert m.group("src") == pt.NO_SOURCE
    assert pt.latest_bench_path() == os.path.join(
        pt.REPO_ROOT, "BENCH_r09.json")
    # a stamped table names a source that sits in the repo root
    m = pt.BEGIN_RE.search(stamped.read_text())
    assert os.path.exists(os.path.join(pt.REPO_ROOT, m.group("src")))


def test_table_matches_regeneration(stamped):
    """The committed table must be byte-identical to regenerating from
    its recorded source (hand edits and stale numbers both fail)."""

    def committed_vs_regenerated(text):
        m = pt.BEGIN_RE.search(text)
        src = m.group("src")
        regenerated = pt.generate(
            None if src == pt.NO_SOURCE
            else os.path.join(pt.REPO_ROOT, src))
        end = text.find(pt.END_MARK) + len(pt.END_MARK)
        return text[m.start():end], regenerated

    # the repo's table: exactly the "not measured" block, no hand edits
    committed, regenerated = committed_vs_regenerated(_read_parity())
    assert committed == regenerated, (
        "PARITY.md's bench table differs from regeneration — run "
        "python -m dml_tpu.tools.parity_table --write"
    )
    assert "Not measured on the current installation" in committed
    # a stamped table regenerates byte-identically; a hand edit shows
    text = stamped.read_text()
    committed, regenerated = committed_vs_regenerated(text)
    assert committed == regenerated and "14,000" in committed
    committed, regenerated = committed_vs_regenerated(
        text.replace("14,000", "15,000"))
    assert committed != regenerated


def test_splice_roundtrip(tmp_path):
    text = _read_parity()
    table = "<!-- BENCH-TABLE:BEGIN source=f.json sha1=abc123 -->\nX\n" + pt.END_MARK
    spliced = pt.splice(text, table)
    assert "\nX\n" in spliced
    # idempotent: splicing again replaces, not duplicates
    again = pt.splice(spliced, table)
    assert again == spliced
    with pytest.raises(ValueError):
        pt.splice("no markers here", table)


def test_committed_artifact_is_plausible(stamped):
    """The artifact PARITY's table is generated from must pass the
    plausibility screen — a degenerate slope measurement (0.0 ms
    flash fwd, 8.8e6x speedup: seen in an r3 capture) must fail CI,
    not get published."""
    m = pt.BEGIN_RE.search(stamped.read_text())
    src = os.path.join(pt.REPO_ROOT, m.group("src"))
    bench = pt.load_bench(src)
    violations = pt.sanity_check(bench)
    assert not violations, f"implausible bench values: {violations}"


def test_sanity_check_catches_degenerate_slope():
    bad = {"matrix": {"pallas_on_device": {
        "flash_fwd_ms": 0.0, "flash_vs_naive_speedup": 8864486.6,
    }}}
    v = pt.sanity_check(bad)
    assert any("flash_fwd_ms" in x for x in v)
    assert any("speedup" in x for x in v)
    assert pt.sanity_check({"matrix": {}}) == []


def test_sanity_check_refuses_failed_parity():
    """A kernel whose output diverged from the XLA oracle must be
    refused outright — not published with a footnote on one row."""
    bad = {"matrix": {"pallas_on_device": {
        "flash_fwd_ms": 1.5, "flash_vs_naive_speedup": 5.0,
        "parity_pass": False,
    }}}
    v = pt.sanity_check(bad)
    assert any("parity_pass" in x for x in v)
    ok = {"matrix": {"pallas_on_device": {
        "flash_fwd_ms": 1.5, "flash_vs_naive_speedup": 5.0,
        "parity_pass": True,
    }}}
    assert pt.sanity_check(ok) == []
