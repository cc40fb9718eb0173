"""The readers of the program's own serve-loop spans: each cell's
rehearsal finds something to read for every one of them, and a window in
which nothing ran, or a program that records no loop spans, gives None."""

import time

import pytest

from benchmark.harness import cell as hc
from benchmark.harness import program_spans as ps

CHAT, JOBS = "mistral7b_widths_l8.chat", "mistral7b_widths_l8.jobs"
PHASES = ("lm_readback_ms", "lm_pack_ms", "lm_deliver_ms", "lm_place_ms",
          "lm_idle_share", "prefill_useful_share", "ack_wall_ms")


@pytest.mark.parametrize("workload,wanted", [
    (CHAT, {m + ".chat" for m in PHASES}
     | {"worker_wait_ms.chat", "first_token_ms.chat"}),
    (JOBS, {m + ".jobs" for m in PHASES}),
])
def test_rehearsal_lists_every_new_reader(workload, wanted):
    r = hc.run_cell(workload, 5, 4.0, True, t_start=time.monotonic(),
                    rehearse=True)
    assert r["correct"] is True and r["failed"] == 0
    assert r["metrics"] == {}  # a CPU's times are no device metrics
    assert wanted <= set(r["readers"]), wanted - set(r["readers"])


def _window(a, b):
    return {"counters": {"start": {"t": a}, "end": {"t": b}}}


def test_nothing_to_read_where_nothing_ran():
    from dml_tpu.tracing import TRACER

    TRACER.reset()
    now = time.monotonic()
    TRACER.loop_record("lm_step", now - 9.0, now - 8.5)
    TRACER.loop_record("lm_idle", now - 8.5, now - 8.0)
    empty = _window(now - 5.0, now)  # the spans ended before it
    assert ps.program_spans(empty, "lm_step") is None
    assert ps.mean_ms(empty, "lm_step") is None
    assert ps.share_pct(empty, "lm_idle") is None
    assert ps.label_ratio_pct(empty, "lm_prefill_group", "a", "b") is None
    assert ps.event_gap_mean_ms(empty, "lm_request", "a", "b") is None
    assert ps.program_spans({"counters": {"start": {"t": now}}},
                            "lm_step") is None  # a window never closed
    TRACER.reset()


def test_reads_what_ended_inside_the_window():
    from dml_tpu.tracing import TRACER

    TRACER.reset()
    now = time.monotonic()
    with TRACER.loop_span("lm_step") as step:
        pass
    TRACER.loop_record("lm_readback", now - 4.0, now - 3.75, step)
    TRACER.loop_record("lm_readback", now - 3.0, now - 2.5)  # no parent
    TRACER.loop_record("lm_readback", now - 9.0, now - 8.0, step)  # before
    TRACER.loop_record("lm_idle", now - 6.0, now - 4.5)  # clipped to 0.5
    TRACER.loop_record("lm_prefill_group", now - 2.0, now - 1.9,
                       prompt_tokens=30, padded_tokens=160)
    TRACER.loop_record("lm_prefill_group", now - 1.0, now - 0.9,
                       prompt_tokens=50, padded_tokens=160)
    TRACER.loop_record("lm_request", now - 4.0, now - 1.0, events=(
        ("placed", now - 3.5), ("first_token", now - 3.25)))
    run = _window(now - 5.0, now)
    assert len(ps.program_spans(run, "lm_readback")) == 2
    assert ps.mean_ms(run, "lm_readback", under="lm_step") == \
        pytest.approx(250.0, abs=0.01)
    assert ps.mean_ms(run, "lm_readback", under="lm_submit") is None
    assert ps.mean_ms(run, "lm_readback") == pytest.approx(375.0, abs=0.01)
    assert ps.share_pct(run, "lm_idle") == pytest.approx(10.0, abs=0.001)
    assert ps.share_pct(run, "lm_submit") == 0.0  # ran, none of this name
    assert ps.label_ratio_pct(run, "lm_prefill_group", "prompt_tokens",
                              "padded_tokens") == pytest.approx(25.0)
    assert ps.event_gap_mean_ms(run, "lm_request", "placed",
                                "first_token") == pytest.approx(250.0, abs=0.01)
    TRACER.reset()


def test_a_program_without_loop_spans_gives_none(monkeypatch):
    """The parent commit's recorder has no loop ring: every reader
    returns None and raises nothing."""
    import dml_tpu.tracing as trc

    class Old:
        pass

    monkeypatch.setattr(trc, "TRACER", Old())
    now = time.monotonic()
    run = _window(now - 1.0, now)
    assert ps.program_spans(run, "lm_step") is None
    assert ps.mean_ms(run, "lm_step") is None
    assert ps.share_pct(run, "lm_idle") is None


def test_span_gaps_names_nothing_in_a_trace_without_annotations():
    """The recorded fixture (two programs on the chip, no `dml.*`
    annotation in it): every idle gap stays `unattributed`."""
    import os

    from benchmark.tools import span_gaps as sg

    path = os.path.join(os.path.dirname(__file__), "data",
                        "v5e_two_programs.xplane.pb")
    facts = sg.read_trace(path)
    table = sg.span_gaps(facts)
    assert table["gaps"] > 0 and table["annotations"] == 0
    assert table["attributed_share"] == 0.0
    assert [row[0] for row in table["by_span"]] == ["unattributed"]
    assert table["idle_in_gaps_s"] == pytest.approx(
        table["by_span"][0][1])
    assert sg.span_offset(facts)["matched"] == 0


def test_span_gaps_tool_closes_both_accounts_in_rehearsal(
        monkeypatch, capsys):
    """The tool end to end on the CPU at the rehearsal size: the step
    account and the TTFT account print, the phases lie inside the step,
    the spans' step agrees with the counters', and each `dml.lm_step`
    annotation in the profiler's file lies within a millisecond of its
    span."""
    import json

    from benchmark.tools import span_gaps as sg

    real = hc.run_cell
    monkeypatch.setattr(
        hc, "run_cell", lambda *a, **kw: real(*a, rehearse=True, **kw))
    assert sg.main(["--workload", CHAT, "--seed", "7",
                    "--seconds", "4"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    said = {x["bench"]: x for x in lines if "bench" in x}
    step = said["step_account"]
    assert step["with_five_phases"] >= 1
    # the phases lie inside the step; how much of it they cover is a
    # time, and a CPU's at this size says nothing (99.6% on the chip)
    assert 0.0 < step["phases_over_step"] <= 1.0 and step["self_ms"] >= 0
    assert step["span_step_ms_per_token_step"] == pytest.approx(
        step["counter_lm_step_ms"], rel=0.02)
    ttft = said["ttft_account"]
    assert ttft["first_token_ms"] > 0 and ttft["formation_ms"] >= \
        ttft["of_it_worker_wait_ms"] >= 0
    assert ttft["left_over_ms"] == pytest.approx(
        ttft["ttft_mean_ms"] - ttft["named_ms"])
    off = said["span_offset"]
    assert off["matched"] >= 1 and abs(off["median_us"]) < 1000.0
    assert said["span_gaps"]["gaps"] == 0  # a CPU has no device plane
    assert lines[-1]["metrics"] == {} and lines[-1]["correct"] is True
