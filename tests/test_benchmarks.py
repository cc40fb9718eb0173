"""Slope-timing helpers (dml_tpu/benchmarks.py): dispersion stats and
the degenerate-rep guard (a jitter-swallowed rep must be counted, not
clamped into the published min)."""

import numpy as np

from dml_tpu import benchmarks as bm


def _fake_runner(times):
    """A callable whose wall time is scripted: pops from `times`."""
    import time as _t

    it = iter(times)

    def fn(*args):
        _t.sleep(next(it))
        return np.float32(0)

    return fn


def test_paired_slopes_stats():
    # c1 sleeps ~0, c2 sleeps 20ms -> slope ~= 20ms/10 iters = 2ms
    c1 = _fake_runner([0.0] * 4)
    c2 = _fake_runner([0.02] * 4)
    st = bm._paired_slopes(c1, c2, (), 10, 20, 3)
    assert st["reps"] == 3
    assert "degenerate_reps" not in st
    assert 1e-3 < st["median"] < 4e-3
    assert st["min"] <= st["median"] <= st["max"]


def test_paired_slopes_degenerate_rep_excluded():
    # one rep has t2 < t1 (negative slope): it must be excluded from
    # min/max and counted, not published as min=1e-9 (an absurd qps
    # range upper bound — r4 review finding)
    c1 = _fake_runner([0.0, 0.03, 0.0])  # warmup + 2 reps
    c2 = _fake_runner([0.0, 0.02, 0.02])
    st = bm._paired_slopes(c1, c2, (), 10, 20, 2)
    assert st["degenerate_reps"] == 1
    assert st["min"] > 1e-4  # the valid rep, not the clamp


def test_paired_slopes_all_degenerate():
    c1 = _fake_runner([0.0, 0.03, 0.03])
    c2 = _fake_runner([0.0, 0.0, 0.0])
    st = bm._paired_slopes(c1, c2, (), 10, 20, 2)
    assert st["degenerate_reps"] == 2
    assert st["median"] == 1e-9  # sentinel; sanity screens catch it


def test_dynamic_slope_stats_single_compile():
    """The dynamic-n protocol: one jitted program serves both chain
    lengths (one compile instead of two, one schedule for both), and
    the measured slope matches the body's
    per-iteration work."""
    import jax
    import jax.numpy as jnp

    traces = []

    def chain(n, x):
        traces.append(1)  # counts TRACES, not executions
        def body(i, acc):
            return acc + jnp.max(x) * 1e-6

        return jax.lax.fori_loop(0, n, body, jnp.float32(0))

    st = bm.dynamic_slope_stats(
        chain, (jnp.ones((8, 8)),), lengths=(4, 64), reps=2
    )
    assert len(traces) == 1  # ONE compile for both lengths
    assert st["reps"] == 2
    # result value sanity: the fn actually iterated n times
    out = jax.jit(chain)(jnp.int32(5), jnp.ones((8, 8)))
    np.testing.assert_allclose(float(out), 5e-6, rtol=1e-4)


def test_peak_flops_raises_on_unknown_device_kind():
    """One peaks table keyed by device_kind; a device it does not know
    is an error, never the v5e figure by default (on the CPU mesh every
    MFU would otherwise be computed against a chip that is not there)."""
    import types

    import pytest

    from dml_tpu.benchmarks import CHIP_PEAKS, chip_peaks, peak_flops

    v5e = types.SimpleNamespace(device_kind="TPU v5 lite")
    assert peak_flops(v5e) == CHIP_PEAKS["TPU v5 lite"]["bf16_flops"] == 197e12
    assert chip_peaks(v5e)["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        peak_flops(types.SimpleNamespace(device_kind="TPU v9 imaginary"))
    with pytest.raises(ValueError, match="no published peaks"):
        peak_flops()  # the CPU test mesh's own device
