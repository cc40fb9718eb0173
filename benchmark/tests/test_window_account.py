"""The readers of the serving thread's own account (`lm_exposed`, `lm_turn`,
`lm_route`): what they read from the loop ring, that the rehearsals of the
cells that list them find something to read, that a program without those
spans gives None, and the tool that prints the whole account."""

import json
import time

import pytest

from benchmark.harness import cell as hc
from benchmark.harness import manifest as mf

CHAT, JOBS = "mistral7b_widths_l8.chat", "mistral7b_widths_l8.jobs"
NEW = ("lm_exposed_share.jobs", "lm_exposed_share.chat", "lm_turn_ms.jobs",
       "lm_turn_ms.chat", "lm_route_ms.jobs")


def _window(a, b):
    return {"counters": {"start": {"t": a}, "end": {"t": b}}}


def _read(name, run):
    return mf.load_module("metrics", name).read(run)


def test_the_manifest_lists_the_five_in_their_cells_and_no_other():
    m = mf.load()
    rows = {r["name"]: r for r in m["per_layer"]}
    jobs = [w["name"] for w in m["workloads"] if w["name"].endswith(".jobs")]
    assert [r["name"] for r in m["per_layer"][-5:]] == list(NEW)
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


def test_readers_read_what_ended_inside_the_window():
    from dml_tpu.tracing import TRACER

    TRACER.reset()
    now = time.monotonic()
    with TRACER.loop_span("lm_step") as step:
        pass
    TRACER.loop_record("lm_exposed", now - 6.0, now - 4.75,
                       after="readback")  # clipped to 0.25
    TRACER.loop_record("lm_exposed", now - 3.0, now - 2.75, after="firsts")
    TRACER.loop_record("lm_turn", now - 4.0, now - 3.996)
    TRACER.loop_record("lm_turn", now - 2.0, now - 1.998)
    TRACER.loop_record("lm_turn", now - 9.0, now - 8.0)  # before
    TRACER.loop_record("lm_route", now - 1.5, now - 1.4995, step)
    TRACER.loop_record("lm_route", now - 1.0, now - 0.9)  # under no step
    run = _window(now - 5.0, now)
    for kind in ("jobs", "chat"):
        assert _read(f"lm_exposed_share.{kind}", run) == pytest.approx(
            10.0, abs=0.001)
        assert _read(f"lm_turn_ms.{kind}", run) == pytest.approx(
            3.0, abs=0.01)
    assert _read("lm_route_ms.jobs", run) == pytest.approx(0.5, abs=0.01)
    empty = _window(now - 20.0, now - 15.0)  # nothing ended in it
    assert all(_read(name, empty) is None for name in NEW)
    TRACER.reset()


def test_a_program_without_the_spans_gives_none(monkeypatch):
    """The parent commit records no `lm_exposed`, `lm_turn`, `lm_route`:
    its traced runs, under these readers, leave the metrics out."""
    import dml_tpu.tracing as trc

    trc.TRACER.reset()
    now = time.monotonic()
    trc.TRACER.loop_record("lm_step", now - 2.0, now - 1.0)
    run = _window(now - 5.0, now)
    assert _read("lm_exposed_share.jobs", run) == 0.0  # ran, none exposed
    monkeypatch.setattr(trc, "SPAN_NAMES", tuple(
        n for n in trc.SPAN_NAMES
        if n not in ("lm_exposed", "lm_turn", "lm_route")))
    assert all(_read(name, run) is None for name in NEW)
    trc.TRACER.reset()


@pytest.mark.parametrize("workload,wanted", [
    (CHAT, {"lm_exposed_share.chat", "lm_turn_ms.chat"}),
    (JOBS, {"lm_exposed_share.jobs", "lm_turn_ms.jobs"}),
])
def test_rehearsal_lists_the_new_readers(workload, wanted):
    r = hc.run_cell(workload, 5, 4.0, True, t_start=time.monotonic(),
                    rehearse=True)
    assert r["correct"] is True and r["failed"] == 0
    assert wanted <= set(r["readers"]), wanted - set(r["readers"])
    assert not {n for n in NEW if n not in wanted} & set(r["readers"])


def test_window_account_tool_prints_the_accounts_in_rehearsal(
        monkeypatch, capsys):
    """The tool end to end on the CPU at the rehearsal size: the profiler's
    file goes through the program's reader (no device plane on a CPU, so
    the window is idle and named by the spans open in it), and the
    exposure account reads both windows."""
    from benchmark.tools import window_account as wa

    real = hc.run_cell
    monkeypatch.setattr(
        hc, "run_cell", lambda *a, **kw: real(*a, rehearse=True, **kw))
    assert wa.main(["--workload", JOBS, "--seed", "7",
                    "--seconds", "4"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    said = {x["bench"]: x for x in lines if "bench" in x}
    acc = said["window_account"]
    assert acc["busy_s"] + acc["idle_s"] == pytest.approx(acc["window_s"])
    assert sum(acc["idle"].values()) == pytest.approx(acc["idle_s"])
    assert acc["annotations"] > 0 and acc["harness"]["window_s"] > 0
    exp = said["exposed_account"]
    for key in ("window", "traced"):
        assert 0.0 < exp[f"lm_exposed_share_{key}"] < 1.0
        assert exp[f"exposed_plus_idle_share_{key}"] <= 1.0 + 1e-9
    assert set(exp["lm_exposed_by_after"]) <= {
        "readback", "firsts", "insert_wait"}
    assert said["step_account"]["with_five_phases"] >= 1
    assert "readers" in lines[-1]
