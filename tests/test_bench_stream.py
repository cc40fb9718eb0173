"""bench.py section-runner contract (VERDICT r4 item 1): streaming
per-section output, the global wall budget, fail-soft vs fatal
sections, and interrupt unwind. Pure-logic — drives `run_sections`
with fake sections; the real sections are exercised on hardware by the
driver."""

import json
import time

import pytest

from bench import _Interrupted, run_sections


def _collect():
    lines = []

    def stream(line):
        lines.append(json.loads(line))

    return lines, stream


def test_streams_one_line_per_section_with_new_keys():
    out = {"pre": 1}
    lines, stream = _collect()

    def a():
        out["alpha"] = {"x": 1}

    def b():
        out["beta"] = [2, 3]

    run_sections([("a", a), ("b", b)], out, t_start=time.monotonic(),
                 budget_s=1e9, stream=stream)
    assert [ln["section"] for ln in lines] == ["a", "b"]
    # each line carries exactly the keys its section added
    assert lines[0]["data"] == {"alpha": {"x": 1}}
    assert lines[1]["data"] == {"beta": [2, 3]}
    assert lines[0]["error"] is None
    # per-section walls recorded for next-round budget planning
    assert set(out["_section_wall_s"]) == {"a", "b"}


def test_budget_skips_remaining_but_not_fatal():
    out = {}
    lines, stream = _collect()
    ran = []

    def mk(name):
        def f():
            ran.append(name)
            out[name] = True

        return f

    # budget already exhausted at start: only the fatal section runs
    run_sections(
        [("headline", mk("headline")), ("x", mk("x")), ("y", mk("y"))],
        out, t_start=time.monotonic() - 100.0, budget_s=1.0,
        fatal={"headline"}, stream=stream)
    assert ran == ["headline"]
    assert set(out["_skipped"]) == {"x", "y"}
    assert "budget" in out["_skipped"]["x"]
    by_name = {ln["section"]: ln for ln in lines}
    assert by_name["x"]["skipped"] == "wall_budget"
    assert "data" in by_name["headline"]


def test_failing_section_is_soft_and_keeps_partials():
    out = {}
    lines, stream = _collect()

    def bad():
        out["partial"] = "kept"
        raise RuntimeError("boom")

    def after():
        out["after"] = True

    run_sections([("bad", bad), ("after", after)], out,
                 t_start=time.monotonic(), budget_s=1e9, stream=stream)
    assert out["_errors"]["bad"] == "RuntimeError('boom')"
    assert out["after"] is True
    # the streamed line still carries the partial data + the error
    assert lines[0]["data"] == {"partial": "kept"}
    assert "boom" in lines[0]["error"]


def test_fatal_section_propagates():
    out = {}
    _, stream = _collect()

    def bad():
        raise RuntimeError("no headline")

    with pytest.raises(RuntimeError):
        run_sections([("models", bad)], out, t_start=time.monotonic(),
                     budget_s=1e9, fatal={"models"}, stream=stream)


def test_interrupt_unwinds_past_fail_soft_with_prior_lines_streamed():
    """A SIGTERM mid-run raises _Interrupted (BaseException): it must
    NOT be swallowed by the fail-soft net, and every line streamed
    before the kill must already be out (main() then prints the final
    combined artifact from `out`)."""
    out = {}
    lines, stream = _collect()

    def ok():
        out["done"] = 1

    def killed():
        raise _Interrupted("signal 15")

    with pytest.raises(_Interrupted):
        run_sections([("ok", ok), ("killed", killed), ("never", ok)],
                     out, t_start=time.monotonic(), budget_s=1e9,
                     stream=stream)
    assert [ln["section"] for ln in lines] == ["ok"]
    assert out["done"] == 1 and "_errors" not in out


def test_bench_refuses_a_non_tpu_device():
    """A bench number is a TPU number or it is nothing: on the CPU the
    bench must fail at start, before any section runs or prints."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "bench.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=root,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert proc.stdout == ""
