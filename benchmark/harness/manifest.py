"""`BENCHMARK.json`: loading, the rules it has to keep, and finding the
files that belong to a cell by the names it gives."""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_.\-/%]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def load(path: str = None) -> Dict[str, Any]:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(kind: str, name: str) -> Dict[str, Any]:
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`benchmark/<kind>/<name>.py`, found by name (a name may hold dots,
    so this goes by path, not by `import`)."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"{kind[:-1] if kind.endswith('s') else kind} {name!r} has no "
            f"file benchmark/{kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_of(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in manifest['workloads']]}")


def config_of(manifest: Dict[str, Any], cell: Dict[str, Any]) -> Dict[str, Any]:
    for c in manifest["configs"]:
        if c["name"] == cell["config"]:
            with open(os.path.join(ROOT, c["file"])) as f:
                return json.load(f)
    raise KeyError(f"cell {cell['name']} names no known configuration")


def metrics_of(manifest: Dict[str, Any], cell: str, group: str) -> List[Dict[str, Any]]:
    """The metrics of `group` (`end_to_end` or `per_layer`) that `cell`
    reports: those that list it, and those that list no cells at all."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def _line(s: Any, what: str, errs: List[str]) -> None:
    if not isinstance(s, str) or not 1 <= len(s) <= 200 or "\n" in s or "\t" in s:
        errs.append(f"{what}: 1 to 200 characters on one line, no tab")


def validate(m: Dict[str, Any]) -> List[str]:
    """Every way `m` breaks the benchmark's contract (empty = none)."""
    errs: List[str] = []
    if set(m) != TOP_KEYS:
        errs.append(f"top-level keys are {sorted(m)}, want {sorted(TOP_KEYS)}")
        return errs
    if not (isinstance(m["command"], list) and 1 <= len(m["command"]) <= 32):
        errs.append("command: a list of 1 to 32 strings")
    for w in m["command"]:
        _line(w, f"command word {w!r}", errs)
        if w.startswith("/") or ".." in w.split("/"):
            errs.append(f"command word {w!r} leaves the repo")
    paths = m["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        errs.append("paths: 1 to 16 directories")
    for p in paths:
        if not re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) or p.startswith("/"):
            errs.append(f"path {p!r}: relative, of letters digits _ . - /")
    if not (isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51):
        errs.append("run_seconds: a whole number from 1 to 51")

    def names(rows, what):
        seen = set()
        for r in rows:
            n = r.get("name", "")
            if not NAME.match(n):
                errs.append(f"{what} name {n!r} is not a name")
            if n in seen:
                errs.append(f"{what} name {n!r} appears twice")
            seen.add(n)
        return seen

    configs = names(m["configs"], "config")
    if not 1 <= len(m["configs"]) <= 24:
        errs.append("configs: 1 to 24")
    files = set()
    for c in m["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            errs.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        _line(c["source"], f"config {c['name']} source", errs)
        _line(c["why"], f"config {c['name']} why", errs)
        if not any(c["file"].startswith(p.rstrip("/") + "/") for p in paths):
            errs.append(f"config file {c['file']} is not under paths")
        if c["file"] in files:
            errs.append(f"config file {c['file']} is used twice")
        files.add(c["file"])
        if len(c["reduced"]) > 16 or not all(NAME.match(k) for k in c["reduced"]):
            errs.append(f"config {c['name']}: reduced is at most 16 names")
    cells = names(m["workloads"], "workload")
    if not 1 <= len(m["workloads"]) <= 24:
        errs.append("workloads: 1 to 24")
    pairs, used = set(), set()
    for w in m["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            errs.append(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        if w["config"] not in configs:
            errs.append(f"workload {w['name']}: unknown config {w['config']}")
        used.add(w["config"])
        if not NAME.match(w["traffic"]):
            errs.append(f"workload {w['name']}: traffic {w['traffic']!r}")
        if w["chips"] not in (1, 4):
            errs.append(f"workload {w['name']}: chips is 1 or 4")
        _line(w["why"], f"workload {w['name']} why", errs)
        if (w["config"], w["traffic"]) in pairs:
            errs.append(f"workload {w['name']}: its pair appears twice")
        pairs.add((w["config"], w["traffic"]))
    for c in configs - used:
        errs.append(f"config {c} is used by no cell")
    four = sum(1 for w in m["workloads"] if w.get("chips") == 4)
    if four > max(1, len(m["workloads"]) // 4):
        errs.append("too many cells on 4 chips")

    e2e = names(m["end_to_end"], "end_to_end")
    names(m["per_layer"], "per_layer")
    if e2e & {p["name"] for p in m["per_layer"]}:
        errs.append("a metric name is in both groups")
    if not 1 <= len(m["end_to_end"]) <= 16 or not 1 <= len(m["per_layer"]) <= 128:
        errs.append("end_to_end: 1 to 16 metrics; per_layer: 1 to 128")
    if "setup_s" not in e2e:
        errs.append("end_to_end has no setup_s")
    reports: Dict[str, set] = {c: set() for c in cells}
    for x in m["end_to_end"]:
        want = {"name", "unit", "better", "bound", "source"}
        if set(x) - {"workloads"} != want:
            errs.append(f"metric {x.get('name')}: keys {sorted(x)}")
            continue
        if x["source"] not in ("host_clock", "device_trace"):
            errs.append(f"metric {x['name']}: source {x['source']}")
        if not (isinstance(x["bound"], (int, float)) and 0 < x["bound"] <= 0.1):
            errs.append(f"metric {x['name']}: bound {x['bound']}")
        for c in x.get("workloads", cells):
            if c not in cells:
                errs.append(f"metric {x['name']}: unknown cell {c}")
            else:
                reports[c].add(x["name"])
    layered = {c: 0 for c in cells}
    for x in m["per_layer"]:
        want = {"name", "unit", "better", "source", "layer", "moves"}
        if set(x) - {"workloads"} != want:
            errs.append(f"metric {x.get('name')}: keys {sorted(x)}")
            continue
        if x["source"] not in SOURCES:
            errs.append(f"metric {x['name']}: source {x['source']}")
        _line(x["layer"], f"metric {x['name']} layer", errs)
        if x["moves"] not in e2e:
            errs.append(f"metric {x['name']} moves unknown {x['moves']}")
            continue
        for c in x.get("workloads", [c for c in cells if x["moves"] in reports[c]]):
            if c not in cells or x["moves"] not in reports[c]:
                errs.append(f"metric {x['name']}: cell {c} does not report "
                            f"{x['moves']}")
            else:
                layered[c] += 1
    for x in m["end_to_end"] + m["per_layer"]:
        if not UNIT.match(str(x.get("unit", ""))):
            errs.append(f"metric {x.get('name')}: unit {x.get('unit')!r}")
        if x.get("better") not in ("lower", "higher"):
            errs.append(f"metric {x.get('name')}: better {x.get('better')!r}")
    for c in cells:
        if "setup_s" not in reports[c] or len(reports[c]) < 2:
            errs.append(f"cell {c} reports {sorted(reports[c])}: wants "
                        f"setup_s and one more")
        if not layered[c]:
            errs.append(f"cell {c} has no per-layer metric")
    if len(json.dumps(m)) > 64 * 1024:
        errs.append("the file is over 64 KiB")
    return errs
