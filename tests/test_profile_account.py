"""`tracing.read_profile`: one profiler trace as an account of its window
in the program's own names, on a trace recorded on the chip
(`benchmark/tools/record_account_trace.py`: a program with the parts
`attn_proj` and `mlp` inside a `while` and one operation under no part,
run eight times; four idle gaps of 10 ms under a `dml.lm_turn` annotation
and, inside the window the device was watched over, three of 5 ms under
none)."""

import os

import pytest

from dml_tpu.tracing import (PARTS, UNATTRIBUTED, UNSCOPED, find_profile,
                             read_profile)

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark", "tests", "data")
PARTS_IN_A_WHILE = os.path.join(DATA, "v5e_parts_in_a_while.xplane.pb")
TWO_PROGRAMS = os.path.join(DATA, "v5e_two_programs.xplane.pb")


@pytest.fixture(scope="module")
def account():
    return read_profile(PARTS_IN_A_WHILE)


def test_parts_and_unscoped_add_up_to_busy(account):
    (program, row), = account["busy"].items()
    assert program == "jit_parts_in_a_while"
    assert set(row) == {"attn_proj", "mlp", UNSCOPED} and set(row) - {
        UNSCOPED} <= set(PARTS)
    assert sum(row.values()) == pytest.approx(account["busy_s"], rel=1e-9)
    # the two parts are a matmul each of the same shapes
    assert row["attn_proj"] == pytest.approx(row["mlp"], rel=0.05)
    assert row[UNSCOPED] < 0.05 * account["busy_s"]


def test_a_while_is_counted_by_its_self_time(account):
    """The `while` spans its body: by self time it is what the loop
    costs beside its body, and the account's busy time is the union of
    the intervals, as the benchmark's own reduction has it."""
    from benchmark.harness import trace as tr

    ops = {op: s for op, s, _ in account["unscoped_ops"]}
    paths = {op: path for op, _, path in account["unscoped_ops"]}
    # the program wrote the `+ 1.0` under no part; the compiler made the
    # `while` itself and gave it no path at all
    assert paths["jit_parts_in_a_while:broadcast_add_fusion"] == \
        "jit(parts_in_a_while)/add:"
    assert paths["jit_parts_in_a_while:while"] == ""
    assert 0.0 <= ops["jit_parts_in_a_while:while"] < 0.01 * account["busy_s"]
    assert "jit_parts_in_a_while:broadcast_add_fusion" in ops  # the `+ 1.0`
    reduced = tr.reduce_trace(PARTS_IN_A_WHILE)
    assert account["busy_s"] == pytest.approx(reduced["busy_s"], rel=1e-5)
    # summed without regard to nesting the operations come to about twice
    assert sum(s for _, s in reduced["device_ops"]) > 1.9 * account["busy_s"]


def test_spans_and_unattributed_add_up_to_idle(account):
    idle = account["idle"]
    assert set(idle) == {"lm_turn", UNATTRIBUTED}
    assert sum(idle.values()) == pytest.approx(account["idle_s"], rel=1e-9)
    assert account["busy_s"] + account["idle_s"] == pytest.approx(
        account["window_s"], rel=1e-12)
    assert account["annotations"] == 4
    # four sleeps of 10 ms under the span; three of 5 ms under none (the
    # fourth lies past the device's last operation: outside the window,
    # like the profiler's own start and stop, 0.28 s of this file)
    assert 0.040 <= idle["lm_turn"] < 0.060
    assert 0.015 <= idle[UNATTRIBUTED] < 0.025
    assert account["window_s"] < 0.1


def test_a_trace_without_scopes_or_spans_names_nothing():
    acc = read_profile(TWO_PROGRAMS)
    assert set(acc["busy"]) == {"jit_big_step", "jit_small_step"}
    assert all(set(row) == {UNSCOPED} for row in acc["busy"].values())
    assert set(acc["idle"]) == {UNATTRIBUTED}
    assert acc["busy_s"] + acc["idle_s"] == pytest.approx(acc["window_s"])


def test_find_profile_takes_the_newest_under_a_log_directory(tmp_path):
    assert find_profile(str(tmp_path)) is None
    for stamp in ("2026_01_01", "2026_01_02"):
        d = tmp_path / "plugins" / "profile" / stamp
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(b"")
    assert find_profile(str(tmp_path)).endswith(
        os.path.join("2026_01_02", "host.xplane.pb"))
