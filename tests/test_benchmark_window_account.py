"""Tier-1 collects `benchmark/tests/test_window_account.py` here (the driver runs `pytest tests/`).

Every test of that module runs here, the one below too: it holds PR 38's
five metrics to the LAST five entries of `per_layer`, and a PR that adds to
the benchmark puts its entries at the end of their lists (one put in the
middle reads as a change to what was there) and edits no file the benchmark
has. PR 40 added two, so that one assertion cannot hold until a `benchmark`
PR rewrites it (PERF.md section 7); it runs as an expected failure, strictly,
so the day it passes again this mark has to go. What it held beside the
position is held by the test after it."""
import pytest

from benchmark.tests import test_window_account as _theirs
from benchmark.tests.test_window_account import *  # noqa: F401,F403
from benchmark.tests.test_window_account import CHAT, NEW, mf


@pytest.mark.xfail(strict=True, reason="per_layer[-5:]: two entries of PR 40 "
                   "follow PR 38's five; the file is a benchmark PR's to mend")
def test_the_manifest_lists_the_five_in_their_cells_and_no_other():  # noqa: F811
    _theirs.test_the_manifest_lists_the_five_in_their_cells_and_no_other()


def test_the_five_stand_together_in_their_cells_and_no_other():
    m = mf.load()
    rows = {r["name"]: r for r in m["per_layer"]}
    names = [r["name"] for r in m["per_layer"]]
    jobs = [w["name"] for w in m["workloads"] if w["name"].endswith(".jobs")]
    at = names.index(NEW[0])
    assert names[at:at + 5] == list(NEW)
    for name in NEW:
        assert rows[name]["source"] == "program_span"
        assert rows[name]["better"] == "lower"
    assert rows["lm_exposed_share.jobs"]["workloads"] == jobs
    assert rows["lm_turn_ms.jobs"]["workloads"] == jobs
    assert rows["lm_exposed_share.chat"]["workloads"] == [CHAT]
    assert rows["lm_turn_ms.chat"]["workloads"] == [CHAT]
    # the cells whose model has an expert layer
    assert rows["lm_route_ms.jobs"]["workloads"] == [
        w for w in jobs if not w.startswith("mistral7b")]
    assert rows["lm_route_ms.jobs"]["layer"] == "expert layer"
