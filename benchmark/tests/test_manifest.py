"""BENCHMARK.json keeps the contract, and every name in it finds its file."""

import copy
import os

import pytest

from benchmark.harness import manifest as mf


@pytest.fixture(scope="module")
def manifest():
    return mf.load()


def test_manifest_is_valid(manifest):
    assert mf.validate(manifest) == []


def test_every_name_and_unit_is_well_formed(manifest):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for row in manifest[group]:
            assert mf.NAME.match(row["name"]), row["name"]
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            assert mf.UNIT.match(m["unit"]), m
    for w in manifest["workloads"]:
        assert len(w["why"]) <= 200


def test_every_layer_metric_lists_cells_that_report_what_it_moves(manifest):
    reports = {w["name"]: {e["name"] for e in mf.metrics_of(
        manifest, w["name"], "end_to_end")} for w in manifest["workloads"]}
    for m in manifest["per_layer"]:
        for cell in m["workloads"]:
            assert m["moves"] in reports[cell], (m["name"], cell)


def test_every_cell_finds_its_files(manifest):
    from benchmark.harness.cell import Cell

    for w in manifest["workloads"]:
        cell = Cell(manifest, w["name"])
        for group in ("end_to_end", "per_layer"):
            for m in mf.metrics_of(manifest, w["name"], group):
                assert hasattr(mf.load_module("metrics", m["name"]), "read")
        assert os.path.exists(os.path.join(mf.ROOT, "benchmark", "traffic",
                                           w["traffic"] + ".json"))
        assert cell.config["name"] == w["config"]


@pytest.mark.parametrize("breakage,word", [
    (lambda m: m["workloads"][0].update(name="has space"), "not a name"),
    (lambda m: m["end_to_end"][0].update(unit="tokens per second"), "unit"),
    (lambda m: m["end_to_end"][0].update(bound=0.5), "bound"),
    (lambda m: m.update(run_seconds=52), "run_seconds"),
    (lambda m: m["per_layer"][0].update(moves="throughput"), "does not report"),
    (lambda m: m["per_layer"][0].update(why="x"), "keys"),
    (lambda m: m["workloads"][0].update(chips=2), "chips"),
    (lambda m: m["end_to_end"].pop(), "setup_s"),
    (lambda m: m["command"].append("../x"), "leaves the repo"),
])
def test_validation_catches(manifest, breakage, word):
    m = copy.deepcopy(manifest)
    breakage(m)
    assert any(word in e for e in mf.validate(m)), mf.validate(m)
