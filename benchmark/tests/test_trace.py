"""The trace reducer on a small trace recorded on a TPU v5e
(`benchmark/tools/record_trace.py`, PR 24's first chip call): four
executions each of `jit_big_step` (a 2048^2 bf16 matmul + tanh) and
`jit_small_step` (a 128-element add), with the host asleep between them.
"""

import os

import numpy as np
import pytest

from benchmark.harness import trace as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "v5e_two_programs.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_trace(FIXTURE, window_s=0.0719)


def test_modules_are_found_by_name_and_counted(reduced):
    assert set(reduced["modules"]) == {"jit_big_step", "jit_small_step"}
    assert reduced["modules"]["jit_big_step"]["count"] == 4
    assert reduced["modules"]["jit_small_step"]["count"] == 4
    # as the recording run read them on the chip
    assert reduced["modules"]["jit_big_step"]["seconds"] == pytest.approx(
        0.00041029, rel=1e-3)
    n, secs = tr.module_seconds(reduced, "^jit_big")
    assert n == 4 and secs == pytest.approx(0.00041029, rel=1e-3)
    # a floor on an execution's length leaves the small program out
    assert tr.module_seconds(reduced, "^jit_", min_us=50.0)[0] == 4


def test_busy_time_is_the_union_of_operations(reduced):
    assert reduced["chips"] == 1
    assert reduced["busy_s"] == pytest.approx(0.000412458, rel=1e-3)
    assert reduced["window_s"] == 0.0719
    # the host slept 15 ms a round: the device was idle nearly always
    assert 1.0 - reduced["busy_s"] / reduced["window_s"] > 0.99
    # eight executions leave seven gaps, all unnamed (no Python frames
    # were recorded in the fixture) and together about the traced span
    assert [n for n, _ in reduced["idle_gaps"]] == ["unattributed"]
    assert reduced["idle_gaps"][0][1] == pytest.approx(0.065, rel=0.05)


def test_device_ops_are_short_names_in_order_of_time(reduced):
    names = [n for n, _ in reduced["device_ops"]]
    assert names[0] == "convolution_tanh_fusion"
    assert all(len(n) <= 80 and " = " not in n for n in names)
    secs = [s for _, s in reduced["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_union_merges_overlaps_and_nesting():
    iv = np.array([[0, 10], [2, 4], [8, 15], [20, 25], [25, 30]], float)
    total, merged = tr._union(iv)
    assert total == 25.0
    assert merged.tolist() == [[0, 15], [20, 30]]


def test_gaps_are_named_by_the_innermost_kept_frame():
    host = [(np.array([[0, 100], [10, 60], [20, 30]], float),
             ["$lm_server.py:1 step", "$lm_server.py:2 deliver", "$x.py:3 f"])]
    got = dict(tr._name_gaps([(22.0, 28.0), (70.0, 90.0), (200.0, 210.0)],
                             host, 10))
    assert got == {"x.py:3 f": pytest.approx(6e-9),
                   "lm_server.py:1 step": pytest.approx(20e-9),
                   "unattributed": pytest.approx(10e-9)}
