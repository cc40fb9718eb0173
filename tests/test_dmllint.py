"""dmllint coverage: rule-by-rule positive/negative fixtures, baseline
add/expire round-trip, output-ordering determinism, exit codes, and —
the point of the whole exercise — the tier-1 enforcement test that
holds THIS repo to zero un-baselined findings from this PR forward.

Fixture sources live as string literals (string literals are data to
the AST scan, so deliberately-hazardous fixture code here cannot trip
the enforcement test on this very file).
"""

import ast
import json
import os
import textwrap

import pytest

from dml_tpu.tools import dmllint
from dml_tpu.tools.dmllint import (
    Finding,
    LintInternalError,
    analyze_source,
    apply_baseline,
    check_alert_names,
    check_markers,
    check_metrics,
    check_span_names,
    check_wire,
    collect_alert_call_sites,
    collect_metric_registrations,
    collect_span_call_sites,
    collect_tracing_literals,
    extract_handler_owners,
    extract_msgtype_members,
    extract_msgtype_refs,
    extract_registrations,
    load_baseline,
    parse_ini_markers,
    parse_metric_map,
    run_lint,
)

pytestmark = pytest.mark.lint


def rules_of(findings):
    return [f.rule for f in findings]


# ----------------------------------------------------------------------
# async-hazard rules: positives and negatives
# ----------------------------------------------------------------------


def test_naked_task_positive():
    src = textwrap.dedent("""
        import asyncio

        async def go(self):
            asyncio.create_task(self.loop())
            asyncio.ensure_future(self.other())
            asyncio.get_running_loop().create_task(self.third())
    """)
    fs = analyze_source(src, "dml_tpu/x.py")
    assert rules_of(fs) == ["naked-task"] * 3


def test_naked_task_negative():
    src = textwrap.dedent("""
        import asyncio

        async def go(self):
            t = asyncio.create_task(self.loop())        # stored
            self._bg.add(asyncio.create_task(self.a())) # tracked
            await asyncio.create_task(self.b())         # awaited
            return asyncio.create_task(self.c())        # returned
    """)
    assert analyze_source(src, "dml_tpu/x.py") == []


def test_silent_except_positive():
    src = textwrap.dedent("""
        def f():
            try:
                g()
            except Exception:
                pass
            try:
                g()
            except:
                pass
            try:
                g()
            except (ValueError, Exception):
                pass
    """)
    fs = analyze_source(src, "dml_tpu/x.py")
    assert rules_of(fs) == ["silent-except"] * 3


def test_silent_except_negative():
    src = textwrap.dedent("""
        import logging

        def f():
            try:
                g()
            except ValueError:
                pass              # narrow type: fine
            try:
                g()
            except Exception as e:
                logging.warning("boom: %r", e)  # logged: fine
    """)
    assert analyze_source(src, "dml_tpu/x.py") == []


def test_blocking_in_async_positive():
    src = textwrap.dedent("""
        import time, subprocess

        async def f():
            time.sleep(1)
            subprocess.run(["ls"])
    """)
    fs = analyze_source(src, "dml_tpu/x.py")
    assert rules_of(fs) == ["blocking-async"] * 2


def test_blocking_in_async_negative():
    src = textwrap.dedent("""
        import asyncio, time

        def sync_f():
            time.sleep(1)         # not in async context

        async def f():
            await asyncio.sleep(1)

            def worker():
                time.sleep(1)     # nested SYNC def: runs off-loop
            await asyncio.to_thread(worker)
    """)
    assert analyze_source(src, "dml_tpu/x.py") == []


def test_unseeded_seam_positive():
    src = textwrap.dedent("""
        import random, time
        from random import choice

        def plan():
            return random.randint(0, 5), time.time()
    """)
    fs = analyze_source(src, "dml_tpu/cluster/chaos.py")
    assert sorted(rules_of(fs)) == ["unseeded-seam"] * 3


def test_unseeded_seam_negative_and_scoped():
    seeded = textwrap.dedent("""
        import random

        def plan(seed):
            rng = random.Random(seed)
            return rng.randint(0, 5)
    """)
    assert analyze_source(seeded, "dml_tpu/ingress/loadgen.py") == []
    # same unseeded source OUTSIDE a determinism seam: not flagged
    unseeded = "import random\nx = random.random()\n"
    assert analyze_source(unseeded, "dml_tpu/jobs/service.py") == []


def test_finding_keys_survive_line_drift():
    src = "async def f():\n    import asyncio\n    asyncio.create_task(g())\n"
    shifted = "\n\n# a comment\n\n" + src
    (a,) = analyze_source(src, "dml_tpu/x.py")
    (b,) = analyze_source(shifted, "dml_tpu/x.py")
    assert a.key == b.key  # scope-anchored, not line-anchored
    assert a.line != b.line


# ----------------------------------------------------------------------
# drift-wire-handlers (pure-core + extractor fixtures)
# ----------------------------------------------------------------------

WIRE_SRC = textwrap.dedent("""
    class MsgType:
        PING = 1
        PING_ACK = 2
        SNAP = 3
        DEAD = 4

    RID_FALLBACK = "rid-fallback"

    HANDLER_OWNERS = {
        MsgType.PING: "Node",
        MsgType.PING_ACK: RID_FALLBACK,
        MsgType.SNAP: "Node",
        MsgType.DEAD: "Node",
    }
""")

NODE_SRC = textwrap.dedent("""
    class Node:
        def start(self):
            self.register(MsgType.PING, self._h_ping)
            self.register(MsgType.SNAP, self._h_snap)
            self.register(MsgType.DEAD, self._h_dead)

        def pong(self):
            return MsgType.PING_ACK
""")


def _wire_inputs(wire_src=WIRE_SRC, node_src=NODE_SRC):
    wire_tree = ast.parse(wire_src)
    node_tree = ast.parse(node_src)
    members = extract_msgtype_members(wire_tree)
    owners = extract_handler_owners(wire_tree)
    regs = {"dml_tpu/node.py": extract_registrations(node_tree, "dml_tpu/node.py")}
    refs = {
        "dml_tpu/wire.py": extract_msgtype_refs(wire_tree),
        "dml_tpu/node.py": extract_msgtype_refs(node_tree),
    }
    return members, owners, regs, refs


def _run_wire(members, owners, regs, refs):
    return check_wire(members, owners, regs, refs,
                      "dml_tpu/wire.py", "dml_tpu/introducer.py")


def test_wire_clean_fixture():
    assert _run_wire(*_wire_inputs()) == []


def test_wire_extractors():
    members, owners, regs, refs = _wire_inputs()
    assert members == {"PING": 3, "PING_ACK": 4, "SNAP": 5, "DEAD": 6}
    assert owners["PING_ACK"] == "rid-fallback"
    assert [(m, c, h) for m, c, h, _ in regs["dml_tpu/node.py"]] == [
        ("PING", "Node", "_h_ping"),
        ("SNAP", "Node", "_h_snap"),
        ("DEAD", "Node", "_h_dead"),
    ]


def test_wire_detects_missing_owner():
    members, owners, regs, refs = _wire_inputs()
    del owners["SNAP"]
    fs = _run_wire(members, owners, regs, refs)
    assert any("no HANDLER_OWNERS entry" in f.msg for f in fs)


def test_wire_detects_unregistered_owned_type():
    members, owners, regs, refs = _wire_inputs(
        node_src=NODE_SRC.replace(
            "        self.register(MsgType.SNAP, self._h_snap)\n",
            "        snap = MsgType.SNAP  # still referenced, not registered\n"))
    fs = _run_wire(members, owners, regs, refs)
    assert any("never registers a handler" in f.msg and "SNAP" in f.msg
               for f in fs)


def test_wire_detects_wrong_owner_and_fallback_registration():
    members, owners, regs, refs = _wire_inputs()
    owners["SNAP"] = "StoreService"     # Node registers it -> mismatch
    owners["DEAD"] = "rid-fallback"     # but Node registers it
    fs = _run_wire(members, owners, regs, refs)
    msgs = " | ".join(f.msg for f in fs)
    assert "owned by StoreService but Node registers" in msgs
    assert "declared rid-fallback but Node registers" in msgs


def test_wire_detects_dead_member_and_undeclared_reference():
    # GHOST registered but not declared; PING_ACK referenced nowhere
    # outside wire.py -> dead member
    node_src = NODE_SRC.replace(
        "    def pong(self):\n        return MsgType.PING_ACK\n", ""
    ) + "\n    def late(self):\n        self.register(MsgType.GHOST, self._h_ghost)\n"
    members, owners, regs, refs = _wire_inputs(node_src=node_src)
    fs = _run_wire(members, owners, regs, refs)
    msgs = " | ".join(f.msg for f in fs)
    assert "undeclared MsgType.GHOST" in msgs
    assert "MsgType.PING_ACK is referenced nowhere" in msgs


def test_wire_detects_handler_naming_violation():
    node_src = NODE_SRC.replace("self._h_dead", "self.on_dead")
    members, owners, regs, refs = _wire_inputs(node_src=node_src)
    fs = _run_wire(members, owners, regs, refs)
    assert any("breaks the _h_* naming contract" in f.msg for f in fs)


# ----------------------------------------------------------------------
# drift-metrics-map
# ----------------------------------------------------------------------

MAP_DOC = textwrap.dedent("""
    Some prose.

    Metric map (lint-enforced)
    --------------------------

    Preamble line about the map.

        foo_total        things fooed
        bar_seconds      bar wall

    Next section
    ------------
    not_a_metric_line
""")


def test_parse_metric_map():
    assert parse_metric_map(MAP_DOC) == {"foo_total", "bar_seconds"}
    assert parse_metric_map("no map here") is None


def test_metric_map_drift_detected():
    code_src = textwrap.dedent("""
        M1 = METRICS.counter("foo_total", "help")
        M2 = METRICS.histogram("baz_seconds", "help")
    """)
    code = collect_metric_registrations(
        {"dml_tpu/m.py": ast.parse(code_src)})
    fs = check_metrics({"foo_total", "bar_seconds"}, code, "dml_tpu/obs.py")
    msgs = " | ".join(f.msg for f in fs)
    assert "'bar_seconds' is in the docstring map but no code" in msgs
    assert "'baz_seconds' is registered here but missing" in msgs
    assert check_metrics({"foo_total"}, {"foo_total": ("dml_tpu/m.py", 2)},
                         "dml_tpu/obs.py") == []


def test_metric_map_missing_section_detected():
    fs = check_metrics(None, {}, "dml_tpu/obs.py")
    assert len(fs) == 1 and "no 'Metric map" in fs[0].msg


# ----------------------------------------------------------------------
# drift-span-names
# ----------------------------------------------------------------------

TRACING_FIXTURE = textwrap.dedent("""
    SPAN_ROOT = "request"

    SPAN_NAMES = (
        "request",   # root
        "fetch",     # worker fetch
        "marker",    # exemplar marker (tracer-internal)
        "ghost",     # registered, never emitted anywhere
    )

    def _note(tracer):
        # direct Span construction counts as tracer-internal usage;
        # the set below must NOT (incidental literal, not an emit)
        _detail = {"ghost"}
        return Span(tracer, "marker")
""")

SPAN_USER_FIXTURE = textwrap.dedent("""
    from ..tracing import TRACER

    def ok(ctx):
        TRACER.start_span("fetch", ctx=ctx).end()

    def bad(ctx):
        TRACER.start_span("not_a_stage", ctx=ctx).end()

    def dynamic(ctx, name):
        TRACER.start_span(name, ctx=ctx).end()
""")

LOOP_SPAN_USER_FIXTURE = textwrap.dedent("""
    from ..tracing import TRACER

    def ok(step):
        with TRACER.loop_span("fetch", step, rows=2):
            pass

    def bad(t0, t1):
        with TRACER.loop_span("lm_not_a_phase"):
            pass
        TRACER.loop_record("lm_not_a_request", t0, t1)

    def dynamic(name):
        TRACER.loop_span(name).end()
""")


def test_span_name_extractors():
    trees = {
        "dml_tpu/tracing.py": ast.parse(TRACING_FIXTURE),
        "dml_tpu/jobs/x.py": ast.parse(SPAN_USER_FIXTURE),
    }
    literal, dynamic = collect_span_call_sites(trees)
    assert set(literal) == {"fetch", "not_a_stage"}
    assert len(dynamic) == 1 and dynamic[0][0] == "dml_tpu/jobs/x.py"
    lits = collect_tracing_literals(ast.parse(TRACING_FIXTURE))
    assert {"request", "marker"} <= lits


def test_span_name_drift_detected():
    tr = ast.parse(TRACING_FIXTURE)
    trees = {
        "dml_tpu/tracing.py": tr,
        "dml_tpu/jobs/x.py": ast.parse(SPAN_USER_FIXTURE),
    }
    literal, dynamic = collect_span_call_sites(trees)
    fs = check_span_names(
        dmllint._module_const_strs(tr, "SPAN_NAMES"),
        literal, dynamic, collect_tracing_literals(tr),
        "dml_tpu/tracing.py",
    )
    msgs = " | ".join(f.msg for f in fs)
    # unknown literal name at a call site
    assert "'not_a_stage'" in msgs
    # registered name nothing ever emits
    assert "'ghost'" in msgs
    # names referenced only inside tracing.py count as used
    assert "'request'" not in msgs and "'marker'" not in msgs
    # non-literal call sites in dml_tpu/ are unverifiable
    assert "non-literal" in msgs
    # missing registry degrades to its own finding
    fs2 = check_span_names(None, literal, dynamic, set(),
                           "dml_tpu/tracing.py")
    assert any("no module-level SPAN_NAMES" in f.msg for f in fs2)
    # tests/ may pass computed names (only dml_tpu/ is gated)
    fs3 = check_span_names(
        dmllint._module_const_strs(tr, "SPAN_NAMES"),
        {"fetch": [("tests/t.py", 3)]}, [("tests/t.py", 9)],
        collect_tracing_literals(tr), "dml_tpu/tracing.py",
    )
    assert not any("non-literal" in f.msg for f in fs3)


def test_loop_span_call_sites_are_checked_like_start_span():
    """`loop_span("<name>")` and `loop_record("<name>")` literals count
    as call sites: an unregistered one is a finding, a registered one
    keeps its name off the never-emitted list, a computed one in
    dml_tpu/ is unverifiable."""
    tr = ast.parse(TRACING_FIXTURE)
    trees = {
        "dml_tpu/tracing.py": tr,
        "dml_tpu/inference/y.py": ast.parse(LOOP_SPAN_USER_FIXTURE),
    }
    literal, dynamic = collect_span_call_sites(trees)
    assert set(literal) == {"fetch", "lm_not_a_phase", "lm_not_a_request"}
    assert [p for p, _ in dynamic] == ["dml_tpu/inference/y.py"]
    fs = check_span_names(
        dmllint._module_const_strs(tr, "SPAN_NAMES"),
        literal, dynamic, collect_tracing_literals(tr),
        "dml_tpu/tracing.py",
    )
    msgs = " | ".join(f.msg for f in fs)
    assert "'lm_not_a_phase'" in msgs and "'lm_not_a_request'" in msgs
    assert "'fetch'" not in msgs  # the loop_span call site emits it
    assert "'ghost'" in msgs and "non-literal" in msgs


# ----------------------------------------------------------------------
# drift-alert-names
# ----------------------------------------------------------------------

SIGNAL_FIXTURE = textwrap.dedent("""
    ALERT_NAMES = (
        "slo_burn_rate",   # emitted below
        "phantom_alert",   # registered, never emitted anywhere
    )

    class SignalPlane:
        def _drive(self, name, labels):
            # machinery passes names through variables by design —
            # dynamic sites inside signal.py are NOT findings
            self.alerts.fire_alert(name, labels)

        def burn(self):
            self.fire_alert("slo_burn_rate", {"slo": "interactive"})
""")

ALERT_USER_FIXTURE = textwrap.dedent("""
    def ok(plane):
        plane.resolve_alert("slo_burn_rate", {"slo": "batch"})

    def bad(plane):
        plane.fire_alert("undeclared_page", {})

    def dynamic(plane, name):
        plane.fire_alert(name, {})
""")


def test_alert_name_extractors():
    trees = {
        "dml_tpu/signal.py": ast.parse(SIGNAL_FIXTURE),
        "dml_tpu/jobs/x.py": ast.parse(ALERT_USER_FIXTURE),
    }
    literal, dynamic = collect_alert_call_sites(trees)
    assert set(literal) == {"slo_burn_rate", "undeclared_page"}
    # BOTH dynamic sites are collected (signal.py's own included);
    # the signal.py one is exempted by check_alert_names, not here
    assert {p for p, _ in dynamic} == {
        "dml_tpu/signal.py", "dml_tpu/jobs/x.py"
    }


def test_alert_name_drift_detected():
    sig = ast.parse(SIGNAL_FIXTURE)
    trees = {
        "dml_tpu/signal.py": sig,
        "dml_tpu/jobs/x.py": ast.parse(ALERT_USER_FIXTURE),
    }
    literal, dynamic = collect_alert_call_sites(trees)
    fs = check_alert_names(
        dmllint._module_const_strs(sig, "ALERT_NAMES"),
        literal, dynamic, "dml_tpu/signal.py",
    )
    msgs = " | ".join(f.msg for f in fs)
    # unknown literal name at a call site
    assert "'undeclared_page'" in msgs
    # registered name nothing ever emits
    assert "'phantom_alert'" in msgs
    # signal.py's OWN literal emission counts as used
    assert "'slo_burn_rate'" not in msgs
    # exactly one non-literal finding: the user module's, not the
    # manager machinery's own dispatcher
    dyn = [f for f in fs if "non-literal" in f.msg]
    assert [f.path for f in dyn] == ["dml_tpu/jobs/x.py"]
    # missing registry degrades to its own finding
    fs2 = check_alert_names(None, literal, dynamic, "dml_tpu/signal.py")
    assert any("no module-level ALERT_NAMES" in f.msg for f in fs2)
    # tests/ may pass computed names (only dml_tpu/ is gated)
    fs3 = check_alert_names(
        dmllint._module_const_strs(sig, "ALERT_NAMES"),
        {"slo_burn_rate": [("tests/t.py", 3)],
         "phantom_alert": [("tests/t.py", 4)]},
        [("tests/t.py", 9)], "dml_tpu/signal.py",
    )
    assert not fs3


def test_alert_rule_skips_fixture_trees_without_signal():
    # fixture trees without dml_tpu/signal.py exercise other rules
    # without tripping a no-registry finding
    assert dmllint.rule_alerts(
        ".", {"dml_tpu/jobs/x.py": ast.parse(ALERT_USER_FIXTURE)}
    ) == []


# ----------------------------------------------------------------------
# drift-pytest-markers
# ----------------------------------------------------------------------

INI_FIXTURE = textwrap.dedent("""
    [pytest]
    markers =
        slow: heavyweight test (keras builds, chaos
            soaks etc. continuation line)
        lint: static-analysis coverage
""")


def test_parse_ini_markers():
    assert set(parse_ini_markers(INI_FIXTURE)) == {"slow", "lint"}
    assert parse_ini_markers("[pytest]\naddopts = -q\n") is None


def test_marker_drift_detected():
    ini = parse_ini_markers(INI_FIXTURE)
    conftest = {"slow": 10}  # mirror missing 'lint', extra none
    used = {"slow": ("tests/t.py", 3), "chaos": ("tests/t.py", 9),
            "parametrize": ("tests/t.py", 1)}
    fs = check_markers(ini, conftest, used, "pytest.ini", "tests/conftest.py")
    msgs = " | ".join(f.msg for f in fs)
    assert "'chaos' used here is not registered" in msgs
    assert "'lint' is in pytest.ini but missing from the" in msgs
    assert "'lint' is used by no test" in msgs
    assert "parametrize" not in msgs  # builtin marks exempt
    # conftest-only direction
    fs2 = check_markers(ini, {"slow": 1, "lint": 2, "extra": 3},
                        {"slow": ("tests/t.py", 3),
                         "lint": ("tests/t.py", 4)},
                        "pytest.ini", "tests/conftest.py")
    assert any("'extra' is in the conftest mirror but not" in f.msg
               for f in fs2)


# ----------------------------------------------------------------------
# baseline: add/expire round-trip, malformed forms
# ----------------------------------------------------------------------

HAZARD_SRC = "async def f():\n    import asyncio\n    asyncio.create_task(g())\n"


def test_baseline_round_trip(tmp_path):
    findings = analyze_source(HAZARD_SRC, "dml_tpu/x.py")
    assert len(findings) == 1
    # add: baselining the key suppresses the finding
    baseline = {findings[0].key: "held handle lands with PR N+1"}
    new, suppressed = apply_baseline(findings, baseline, "baseline.json")
    assert new == [] and len(suppressed) == 1
    # expire: fixing the hazard turns the entry into baseline-stale
    new2, _ = apply_baseline([], baseline, "baseline.json")
    assert [f.rule for f in new2] == ["baseline-stale"]
    assert findings[0].key in new2[0].msg


def test_baseline_loader_contract(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({"entries": [
        {"key": "k1", "justification": "a real reason"}]}))
    assert load_baseline(str(p)) == {"k1": "a real reason"}
    # missing justification is a malformed baseline, not a suppression
    p.write_text(json.dumps({"entries": [{"key": "k1"}]}))
    with pytest.raises(LintInternalError, match="justification"):
        load_baseline(str(p))
    p.write_text(json.dumps({"entries": [
        {"key": "k1", "justification": "x y z"},
        {"key": "k1", "justification": "dup"}]}))
    with pytest.raises(LintInternalError, match="duplicate"):
        load_baseline(str(p))
    p.write_text("{not json")
    with pytest.raises(LintInternalError):
        load_baseline(str(p))
    assert load_baseline(str(tmp_path / "absent.json")) == {}


# ----------------------------------------------------------------------
# driver: determinism, exit codes, fixture-tree scan
# ----------------------------------------------------------------------


def _fixture_tree(tmp_path, src=HAZARD_SRC):
    (tmp_path / "dml_tpu").mkdir()
    (tmp_path / "dml_tpu" / "bad.py").write_text(src)
    return str(tmp_path)


def test_exit_codes(tmp_path, capsys):
    root = _fixture_tree(tmp_path)
    assert dmllint.main(["--root", root]) == 1      # findings
    out = capsys.readouterr().out
    assert "dml_tpu/bad.py" in out and "naked-task" in out
    # baseline the finding -> clean
    res = run_lint(root)
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"entries": [
        {"key": res.findings[0].key, "justification": "fixture waiver"}]}))
    assert dmllint.main(["--root", root, "--baseline", str(bl)]) == 0
    # malformed baseline -> internal error
    bl.write_text("{broken")
    assert dmllint.main(["--root", root, "--baseline", str(bl)]) == 2


def test_output_ordering_deterministic(tmp_path):
    root = _fixture_tree(tmp_path, textwrap.dedent("""
        import asyncio, time

        async def z():
            asyncio.create_task(g())

        async def a():
            time.sleep(1)
            try:
                g()
            except Exception:
                pass
    """))
    (tmp_path / "dml_tpu" / "also.py").write_text(HAZARD_SRC)
    r1 = run_lint(root)
    r2 = run_lint(root)
    assert [f.key for f in r1.findings] == [f.key for f in r2.findings]
    ordered = [(f.path, f.line, f.rule) for f in r1.findings]
    assert ordered == sorted(ordered)
    assert len(r1.findings) == 4


def test_json_output_shape(tmp_path, capsys):
    root = _fixture_tree(tmp_path)
    assert dmllint.main(["--root", root, "--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["clean"] is False
    assert doc["findings"][0]["rule"] == "naked-task"
    assert {"path", "line", "rule", "msg", "key"} <= set(doc["findings"][0])


def test_syntax_error_is_internal_error(tmp_path):
    root = _fixture_tree(tmp_path, "def broken(:\n")
    assert dmllint.main(["--root", root]) == 2


# ----------------------------------------------------------------------
# the tier-1 enforcement test: THIS repo is clean
# ----------------------------------------------------------------------


def test_repo_zero_unbaselined_findings():
    """The contract of ISSUE 9: zero un-baselined findings on the real
    tree, with a near-empty justified baseline. A finding here means a
    hazard/drift regression landed — fix it or (exceptionally) baseline
    it WITH a justification."""
    res = run_lint()
    assert res.findings == [], "un-baselined dmllint findings:\n" + "\n".join(
        f.render() for f in res.findings
    )
    assert res.baseline_size <= 25  # ISSUE 13 budget (was 10 pre-flow)
    # every suppression corresponds to a live finding (no stale
    # entries — apply_baseline would have surfaced them above)
    assert len(res.suppressed) == res.baseline_size


# ----------------------------------------------------------------------
# flow-aware rules (dml_tpu/tools/dmlflow.py): race-yield-hazard
# ----------------------------------------------------------------------

from dml_tpu.tools import dmlflow
from dml_tpu.tools.dmlflow import (
    analyze_race_source,
    parse_payload_map,
    run_payload_check,
)


def test_race_check_then_act_positive():
    """The dedup-map form: test, yield, mutate — the exact class behind
    the hand-found ACK-freshness / promoted-leader bugs."""
    src = textwrap.dedent("""
        class C:
            async def handle(self, key):
                if key in self.done:
                    return
                data = await self.fetch(key)
                self.done[key] = data
    """)
    fs = analyze_race_source(src, "dml_tpu/x.py")
    assert [f.rule for f in fs] == ["race-yield-hazard"]
    assert "self.done" in fs[0].msg and "yield point" in fs[0].msg


def test_race_recheck_suppression():
    src = textwrap.dedent("""
        class C:
            async def handle(self, key):
                if key in self.done:
                    return
                data = await self.fetch(key)
                if key in self.done:
                    return
                self.done[key] = data
    """)
    assert analyze_race_source(src, "dml_tpu/x.py") == []


def test_race_lock_suppression_and_prelock_window():
    held = textwrap.dedent("""
        class C:
            async def handle(self, key):
                async with self._lock:
                    if key in self.done:
                        return
                    data = await self.fetch(key)
                    self.done[key] = data
    """)
    assert analyze_race_source(held, "dml_tpu/x.py") == []
    # testing BEFORE taking the lock is still a window: the acquire
    # itself yields, so the test is stale inside the critical section
    prelock = textwrap.dedent("""
        class C:
            async def handle(self, key):
                if key in self.done:
                    return
                async with self._lock:
                    self.done[key] = 1
    """)
    fs = analyze_race_source(prelock, "dml_tpu/x.py")
    assert [f.rule for f in fs] == ["race-yield-hazard"]


def test_race_snapshot_suppression():
    src = textwrap.dedent("""
        class C:
            async def handle(self, key):
                snap = dict(self.done)
                if key in snap:
                    return
                await self.fetch(key)
                self.done[key] = 1
    """)
    assert analyze_race_source(src, "dml_tpu/x.py") == []


def test_race_marker_leak_and_try_finally_suppression():
    src = textwrap.dedent("""
        class C:
            async def leaky(self, k):
                self.inflight.add(k)
                await self.work(k)
                self.inflight.discard(k)

            async def safe(self, k):
                self.inflight.add(k)
                try:
                    await self.work(k)
                finally:
                    self.inflight.discard(k)
    """)
    fs = analyze_race_source(src, "dml_tpu/x.py")
    assert len(fs) == 1 and "leaky" in fs[0].msg
    assert "cancellation" in fs[0].msg


def test_race_counter_marker_leak():
    src = textwrap.dedent("""
        class C:
            async def run(self):
                self.in_flight += 1
                await self.step()
                self.in_flight -= 1
    """)
    fs = analyze_race_source(src, "dml_tpu/x.py")
    assert len(fs) == 1 and "self.in_flight" in fs[0].msg


def test_race_module_global_tracked():
    src = textwrap.dedent("""
        PENDING = {}

        async def claim(key):
            if key in PENDING:
                return
            await fetch(key)
            PENDING[key] = 1
    """)
    fs = analyze_race_source(src, "dml_tpu/x.py")
    assert len(fs) == 1 and "PENDING" in fs[0].msg


def test_race_prefix_form_of_fixed_stop_bug():
    """The pre-fix IntroducerService/DataPlane/RequestRouter.stop shape
    (fixed in this PR): null-test, await the join, null the attribute.
    The fixed snapshot form must be clean."""
    prefix = textwrap.dedent("""
        class S:
            async def stop(self):
                if self._task is not None:
                    self._task.cancel()
                    await self._task
                    self._task = None
    """)
    fs = analyze_race_source(prefix, "dml_tpu/x.py")
    assert [f.rule for f in fs] == ["race-yield-hazard"]
    assert "self._task" in fs[0].msg
    fixed = textwrap.dedent("""
        class S:
            async def stop(self):
                task, self._task = self._task, None
                if task is not None:
                    task.cancel()
                    await task
    """)
    assert analyze_race_source(fixed, "dml_tpu/x.py") == []


def test_race_prefix_form_of_fixed_submit_leak():
    """The pre-fix RequestRouter.submit shape (fixed in this PR): the
    future registered before the await was popped only in `except
    Exception` — a CANCELLED await skips that and leaks the entry. The
    try/finally form must be clean."""
    prefix = textwrap.dedent("""
        class R:
            async def submit(self, req_id):
                self._futs[req_id] = make_future()
                try:
                    reply = await self.leader_retry(req_id)
                except Exception:
                    self._futs.pop(req_id, None)
                    raise
                return reply
    """)
    fs = analyze_race_source(prefix, "dml_tpu/x.py")
    assert any("self._futs" in f.msg and "cancellation" in f.msg for f in fs)
    fixed = textwrap.dedent("""
        class R:
            async def submit(self, req_id):
                self._futs[req_id] = make_future()
                ok = False
                try:
                    reply = await self.leader_retry(req_id)
                    ok = True
                    return reply
                finally:
                    if not ok:
                        self._futs.pop(req_id, None)
    """)
    assert analyze_race_source(fixed, "dml_tpu/x.py") == []


def test_race_keys_survive_line_drift():
    src = textwrap.dedent("""
        class C:
            async def f(self, k):
                if k in self.m:
                    return
                await g()
                self.m[k] = 1
    """)
    (a,) = analyze_race_source(src, "dml_tpu/x.py")
    (b,) = analyze_race_source("\n\n# pad\n" + src, "dml_tpu/x.py")
    assert a.key == b.key and a.line != b.line


# ----------------------------------------------------------------------
# flow-aware rules: drift-wire-payloads
# ----------------------------------------------------------------------

FLOW_WIRE_TMPL = '''
"""Fixture wire.

Payload map (lint-enforced)
---------------------------

{map_lines}
"""


class MsgType:
    PING = 1
    DATA = 2
    DATA_ACK = 3


RID_FALLBACK = "rid-fallback"

HANDLER_OWNERS = {{
    MsgType.PING: "Node",
    MsgType.DATA: "Node",
    MsgType.DATA_ACK: RID_FALLBACK,
}}
'''

FLOW_NODE_SRC = textwrap.dedent('''
    class Node:
        def start(self):
            self.register(MsgType.PING, self._h_ping)
            self.register(MsgType.DATA, self._h_data)

        def kick(self, peer):
            self.send(peer, MsgType.PING, {})

        async def _h_ping(self, msg, addr):
            self.send(msg.sender, MsgType.DATA, {"seq": 1, "body": "x"})

        async def _h_data(self, msg, addr):
            d = msg.data
            use(d["seq"])
            use(d.get("body"))
            self.send(msg.sender, MsgType.DATA_ACK,
                      {"rid": d.get("rid"), "ok": True, "echo": d["seq"]})

        async def ask(self):
            reply = await self.request(peer, MsgType.DATA, {"seq": 2, "body": "y"})
            return_value(reply.get("ok"), reply.get("echo"))
''')


def _flow_trees(map_lines, node_src=FLOW_NODE_SRC):
    return {
        "dml_tpu/cluster/wire.py": ast.parse(
            FLOW_WIRE_TMPL.format(map_lines=map_lines)),
        "dml_tpu/cluster/node.py": ast.parse(node_src),
    }


CLEAN_MAP = """    PING: -
    DATA: seq body?
    DATA_ACK: echo? ok? <- DATA"""


def test_payload_clean_fixture():
    assert run_payload_check(_flow_trees(CLEAN_MAP)) == []


def test_payload_map_parser():
    parsed = parse_payload_map(
        "x\n\nPayload map (lint-enforced)\n---\n\n" +
        "    A: k1 k2? - * <- B\n        k3?\n")
    assert parsed is not None
    entries, bad = parsed
    assert entries["A"].required == {"k1"}
    assert entries["A"].optional == {"k2", "k3"}
    assert entries["A"].open and entries["A"].reply_to == "B"
    assert bad == []
    assert parse_payload_map("no map") is None
    _, bad2 = parse_payload_map(
        "Payload map (lint-enforced)\n---\n\n    A: K1!\n")
    assert bad2 and bad2[0][1] == "K1!"


def test_payload_required_never_sent():
    node = FLOW_NODE_SRC.replace('use(d["seq"])', 'use(d["seq"], d["ghost"])')
    fs = run_payload_check(_flow_trees(
        CLEAN_MAP.replace("DATA: seq body?", "DATA: seq ghost body?"), node))
    assert any("ghost" in f.msg and "no sender of the type ever ships"
               in f.msg for f in fs)


def test_payload_conditional_send_vs_required_read():
    """The named positive case: one sender ships a required key only
    inside a branch — a skipped branch is a KeyError at the reader."""
    node = FLOW_NODE_SRC.replace(
        '        self.send(msg.sender, MsgType.DATA, {"seq": 1, "body": "x"})',
        '        data = {"body": "x"}\n'
        '        if flag():\n'
        '            data["seq"] = 1\n'
        '        self.send(msg.sender, MsgType.DATA, data)',
    )
    fs = run_payload_check(_flow_trees(CLEAN_MAP, node))
    assert any("ships 'seq' only conditionally" in f.msg for f in fs)
    # sender disagreement: a second sender that never ships it at all
    node2 = FLOW_NODE_SRC.replace(
        'self.send(msg.sender, MsgType.DATA, {"seq": 1, "body": "x"})',
        'self.send(msg.sender, MsgType.DATA, {"body": "x"})',
    )
    fs2 = run_payload_check(_flow_trees(CLEAN_MAP, node2))
    assert any("never ships 'seq'" in f.msg and "senders disagree" in f.msg
               for f in fs2)


def test_payload_sent_never_read():
    node = FLOW_NODE_SRC.replace(
        '{"seq": 1, "body": "x"}', '{"seq": 1, "body": "x", "junk": 0}')
    fs = run_payload_check(_flow_trees(
        CLEAN_MAP.replace("DATA: seq body?", "DATA: seq body? junk?"), node))
    assert any("'junk'" in f.msg and "dead wire bytes" in f.msg for f in fs)


def test_payload_map_desync_both_directions():
    """The acceptance fixture: deliberately desync map and wire — an
    unknown key in the map AND an undeclared key on the wire are both
    findings."""
    desynced = CLEAN_MAP.replace("DATA: seq body?", "DATA: seq phantom")
    fs = run_payload_check(_flow_trees(desynced))
    msgs = " | ".join(f.msg for f in fs)
    assert "'phantom'" in msgs and "nothing on the wire sends or reads" in msgs
    assert "'body'" in msgs and "missing from the payload map" in msgs
    # requiredness drift: a .get-read key declared required
    wrong_req = CLEAN_MAP.replace("DATA: seq body?", "DATA: seq body")
    fs2 = run_payload_check(_flow_trees(wrong_req))
    assert any("'body'" in f.msg and "marked" in f.msg for f in fs2)


def test_payload_map_completeness_and_ghosts():
    missing = "    PING: -\n    DATA: seq body?"  # DATA_ACK line gone
    fs = run_payload_check(_flow_trees(missing))
    assert any("DATA_ACK has no payload-map line" in f.msg for f in fs)
    ghost = CLEAN_MAP + "\n    GHOST: k?"
    fs2 = run_payload_check(_flow_trees(ghost))
    assert any("MsgType.GHOST which is not an enum member" in f.msg
               for f in fs2)


def test_payload_missing_reply_annotation():
    unannotated = CLEAN_MAP.replace(" <- DATA", "")
    fs = run_payload_check(_flow_trees(unannotated))
    assert any("missing `<- DATA` annotation" in f.msg for f in fs)


def test_payload_open_star_honesty():
    # '*' on a fully-resolved type is itself a finding
    starred = CLEAN_MAP.replace("DATA: seq body?", "DATA: seq body? *")
    fs = run_payload_check(_flow_trees(starred))
    assert any("inference fully resolves" in f.msg for f in fs)
    # an opaque sender without '*' is the opposite finding
    node = FLOW_NODE_SRC.replace(
        '{"seq": 1, "body": "x"}', '{"seq": 1, "body": "x", **extra}')
    fs2 = run_payload_check(_flow_trees(CLEAN_MAP, node))
    assert any("does not mark it '*'" in f.msg for f in fs2)


def test_payload_discriminator_gated_reader():
    """A reader that probes reply.get("ok") indexes the rest of the
    payload conditionally — an error-shaped reply omitting the success
    fields is not a contract violation (the SUBMIT_JOB_REQUEST_ACK
    shape)."""
    node = FLOW_NODE_SRC.replace(
        '        return_value(reply.get("ok"), reply.get("echo"))',
        '        if not reply.get("ok"):\n'
        '            raise RuntimeError("nope")\n'
        '        return_value(reply["echo"])',
    ).replace(
        '{"rid": d.get("rid"), "ok": True, "echo": d["seq"]}',
        '{"rid": d.get("rid"), "ok": False}',
    )
    # the ok=False ACK sender never ships echo; the ok-gated required
    # read must NOT flag it (echo? stays optional in the map)
    fs = run_payload_check(_flow_trees(CLEAN_MAP, node))
    assert not any("required" in f.msg and "echo" in f.msg for f in fs)


def test_payload_prefix_form_of_fixed_error_drop():
    """The pre-fix REPLICATE_FILE_FAIL shape (fixed in this PR): the
    holder ships why the repair failed, the leader never reads it."""
    node = FLOW_NODE_SRC.replace(
        'use(d.get("body"))', 'pass_on()'
    )
    fs = run_payload_check(_flow_trees(CLEAN_MAP, node))
    assert any("'body'" in f.msg and "dead wire bytes" in f.msg for f in fs)


def test_payload_real_map_matches_enum():
    """The repo's actual payload map covers the complete MsgType range
    — including the 60-101 job/ingress/metrics/trace span — in both
    directions (any gap would fail test_repo_zero_unbaselined_findings,
    this pins the mechanism)."""
    import dml_tpu.cluster.wire as wire

    parsed = parse_payload_map(wire.__doc__ or "")
    assert parsed is not None, "wire.py lost its payload map section"
    entries, bad = parsed
    assert bad == []
    enum_names = {m.name for m in wire.MsgType}
    assert set(entries) == enum_names
    # every rid-fallback reply read at an await site is annotated
    for req in ("PUT_REQUEST", "GET_FILE_REQUEST", "SUBMIT_JOB_REQUEST",
                "METRICS_PULL", "TRACE_PULL", "REQUEST_SUBMIT"):
        assert any(e.reply_to == req for e in entries.values()), req


# ----------------------------------------------------------------------
# driver: rule/path filters, schema_version, baseline round-trip
# ----------------------------------------------------------------------

RACY_SRC = textwrap.dedent("""
    class C:
        async def f(self, k):
            if k in self.m:
                return
            await g()
            self.m[k] = 1
""")


def test_rules_and_paths_filters(tmp_path):
    (tmp_path / "dml_tpu").mkdir()
    (tmp_path / "dml_tpu" / "racy.py").write_text(RACY_SRC)
    (tmp_path / "dml_tpu" / "hazard.py").write_text(HAZARD_SRC)
    # the lint surface is dml_tpu/ + tests/: a script at the root is
    # not scanned
    (tmp_path / "bench.py").write_text(HAZARD_SRC)
    root = str(tmp_path)
    res = run_lint(root)
    assert sorted({f.rule for f in res.findings}) == [
        "naked-task", "race-yield-hazard"]
    assert {f.path for f in res.findings} == {
        "dml_tpu/hazard.py", "dml_tpu/racy.py"}
    only_race = run_lint(root, rules=["race-yield-hazard"])
    assert {f.rule for f in only_race.findings} == {"race-yield-hazard"}
    only_file = run_lint(root, paths=["dml_tpu/hazard.py"])
    assert {f.path for f in only_file.findings} == {"dml_tpu/hazard.py"}
    # unknown rule name is an internal error (exit 2 via CLI)
    for gone in ("no-such-rule", "drift-summary-keys"):
        with pytest.raises(LintInternalError, match="unknown rule"):
            run_lint(root, rules=[gone])
    assert dmllint.main(["--root", root, "--rules", "no-such-rule"]) == 2


def test_filtered_runs_suppress_stale_reporting(tmp_path):
    (tmp_path / "dml_tpu").mkdir()
    (tmp_path / "dml_tpu" / "clean.py").write_text("x = 1\n")
    bl = tmp_path / "b.json"
    bl.write_text(json.dumps({"entries": [
        {"key": "naked-task:gone.py:f:0", "justification": "old"}]}))
    full = run_lint(str(tmp_path), str(bl))
    assert [f.rule for f in full.findings] == ["baseline-stale"]
    # a filtered view cannot judge staleness: no stale reports
    part = run_lint(str(tmp_path), str(bl), rules=["race-yield-hazard"])
    assert part.findings == []


def test_json_schema_version(tmp_path, capsys):
    (tmp_path / "dml_tpu").mkdir()
    (tmp_path / "dml_tpu" / "racy.py").write_text(RACY_SRC)
    assert dmllint.main(["--root", str(tmp_path), "--json",
                         "--rules", "race-yield-hazard"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["schema_version"] == dmllint.JSON_SCHEMA_VERSION
    assert doc["rules"] == ["race-yield-hazard"]
    assert doc["findings"][0]["rule"] == "race-yield-hazard"


def test_baseline_round_trip_flow_rule_keys():
    findings = analyze_race_source(RACY_SRC, "dml_tpu/x.py")
    assert len(findings) == 1
    key = findings[0].key
    assert key.startswith("race-yield-hazard:dml_tpu/x.py:C.f:self.m:")
    baseline = {key: "benign single-writer loop"}
    new, supp = apply_baseline(findings, baseline, "b.json")
    assert new == [] and len(supp) == 1
    stale, _ = apply_baseline([], baseline, "b.json")
    assert [f.rule for f in stale] == ["baseline-stale"]


def test_flow_findings_deterministic(tmp_path):
    (tmp_path / "dml_tpu").mkdir()
    (tmp_path / "dml_tpu" / "racy.py").write_text(RACY_SRC + textwrap.dedent("""
        class D:
            async def g(self, k):
                self.w.add(k)
                await h()
                self.w.discard(k)
    """))
    r1 = run_lint(str(tmp_path))
    r2 = run_lint(str(tmp_path))
    assert [f.key for f in r1.findings] == [f.key for f in r2.findings]
    assert len(r1.findings) == 2
