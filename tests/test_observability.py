"""Typed metrics registry (observability.py): label fan-out, fixed
log-spaced histogram buckets, percentile math against numpy, cross-node
snapshot merging and the exposition surfaces (Prometheus text, the
summarized snapshot)."""

import json
import math

import numpy as np
import pytest

from dml_tpu import observability as obs
from dml_tpu.observability import (
    DEFAULT_TIME_BUCKETS,
    METRICS,
    MetricsRegistry,
    hist_quantile,
    log_buckets,
    merge_snapshots,
    strip_buckets,
    summarize_histogram,
    summarize_snapshot,
)


# ----------------------------------------------------------------------
# registry semantics
# ----------------------------------------------------------------------


def test_counter_gauge_label_fanout():
    reg = MetricsRegistry()
    c = reg.counter("queries_total", "q")
    c.inc(model="A")
    c.inc(3, model="A")
    c.inc(model="B")
    c.inc()  # unlabeled child is its own series
    assert c.value(model="A") == 4.0
    assert c.value(model="B") == 1.0
    assert c.value() == 1.0
    assert c.value(model="missing") == 0.0

    g = reg.gauge("depth", "d")
    g.set(7, model="A")
    g.labels(model="A").dec(2)
    assert g.value(model="A") == 5.0


def test_registry_get_or_create_and_kind_clash():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")


def test_label_order_is_canonical():
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.inc(model="A", role="w")
    c.inc(role="w", model="A")  # same label set, either kwarg order
    assert c.value(model="A", role="w") == 2.0


def test_reset_keeps_handles_valid():
    reg = MetricsRegistry()
    c = reg.counter("c")
    handle = c.labels(model="A")
    handle.inc(5)
    reg.reset()
    assert c.value(model="A") == 0.0
    handle.inc()  # cached child handle survives the reset
    assert c.value(model="A") == 1.0


# ----------------------------------------------------------------------
# histogram buckets + percentiles
# ----------------------------------------------------------------------


def test_log_buckets_constant_ratio_and_coverage():
    edges = log_buckets(1e-4, 100.0, per_decade=6)
    assert edges == DEFAULT_TIME_BUCKETS
    assert list(edges) == sorted(edges)
    assert edges[0] == pytest.approx(1e-4)
    assert edges[-1] >= 100.0
    ratios = [b / a for a, b in zip(edges, edges[1:])]
    for r in ratios:
        assert r == pytest.approx(10 ** (1 / 6), rel=1e-9)
    with pytest.raises(ValueError):
        log_buckets(0.0, 1.0)


def test_histogram_edges_must_increase():
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match="strictly increase"):
        reg.histogram("h", edges=[1.0, 1.0, 2.0])


@pytest.mark.parametrize("values", [
    [],
    [0.5],
    [1e-9, 1e-4, 1e-4 * 10 ** (1 / 6), 3.0, 100.0, 1e6],  # edges, overflow
    list(np.random.default_rng(11).lognormal(-3.0, 2.5, size=936)),
    list(np.random.default_rng(12).integers(0, 129, size=312)),
], ids=["none", "one", "edges", "floats", "counts"])
def test_observe_many_leaves_what_a_loop_of_observe_leaves(values):
    """Count, sum, min, max and every bucket, exactly (the sum is added
    left to right as the loop adds it), on top of what was there and
    under a label; an empty batch leaves no series behind."""
    reg = MetricsRegistry()
    loop = reg.histogram("loop_seconds", "one by one")
    many = reg.histogram("many_seconds", "all at once")
    for h in (loop, many):
        h.observe(0.125, layer="a")  # the running state both add onto
    for v in values:
        loop.observe(v, layer="a")
    many.observe_many(np.asarray(values, np.float64), layer="a")
    many.observe_many(values, layer="b")
    (_, was), = loop.items()
    got = dict(many.items())
    assert got[(("layer", "a"),)] == was
    if values:
        n, total, lo, hi, buckets = got[(("layer", "b"),)]
        assert (n, lo, hi) == (len(values), min(values), max(values))
        assert sum(buckets) == n and total == pytest.approx(sum(values))
    else:
        assert (("layer", "b"),) not in got


def test_percentiles_against_numpy():
    """Bucketed quantiles must land within one bucket RATIO of numpy's
    exact sample quantiles — that is the accuracy the fixed log-spaced
    edges promise, independent of the values' magnitude."""
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    rng = np.random.RandomState(7)
    samples = np.exp(rng.normal(loc=-3.0, scale=1.2, size=5000))
    for v in samples:
        h.observe(float(v), model="A")
    snap = reg.snapshot()
    entry = snap["histograms"]["lat{model=A}"]
    assert entry["count"] == 5000
    assert entry["sum"] == pytest.approx(float(samples.sum()), rel=1e-9)
    assert entry["min"] == pytest.approx(float(samples.min()))
    assert entry["max"] == pytest.approx(float(samples.max()))
    ratio = 10 ** (1 / 6)  # adjacent-edge ratio of the default buckets
    for q in (0.50, 0.95, 0.99):
        est = hist_quantile(entry, q)
        exact = float(np.quantile(samples, q))
        assert exact / ratio <= est <= exact * ratio, (q, est, exact)
    s = summarize_histogram(entry)
    assert s["mean"] == pytest.approx(float(samples.mean()), rel=1e-9)
    assert s["p50"] < s["p95"] < s["p99"]


def test_quantile_edge_cases():
    assert hist_quantile({"count": 0, "edges": [], "bkt": {}}, 0.5) is None
    # everything in the overflow bucket: only the max is known
    reg = MetricsRegistry()
    h = reg.histogram("h", edges=[1.0])
    h.observe(50.0)
    h.observe(70.0)
    entry = reg.snapshot()["histograms"]["h"]
    assert hist_quantile(entry, 0.5) == pytest.approx(70.0)
    # single observation: every quantile is clamped to it
    reg2 = MetricsRegistry()
    h2 = reg2.histogram("h2")
    h2.observe(0.003)
    e2 = reg2.snapshot()["histograms"]["h2"]
    for q in (0.01, 0.5, 0.99):
        assert hist_quantile(e2, q) == pytest.approx(0.003)


# ----------------------------------------------------------------------
# snapshot / merge / exposition
# ----------------------------------------------------------------------


def _fake_snap(proc, n=1, val=1.0, lo=None, step=0.01):
    reg = MetricsRegistry()
    reg.counter("c").inc(val, model="A")
    reg.gauge("g").set(val)
    h = reg.histogram("h")
    for i in range(n):
        h.observe(lo + step * i if lo is not None else 0.01 * (i + 1))
    snap = reg.snapshot(node=f"node{proc}")
    snap["proc"] = proc  # simulate distinct producing processes
    return snap


def test_snapshot_is_json_roundtrippable():
    snap = _fake_snap(1, n=3)
    again = json.loads(json.dumps(snap))
    assert again["counters"] == snap["counters"]
    assert again["histograms"]["h"]["count"] == 3


def test_merge_snapshots_sums_across_processes():
    merged = merge_snapshots([_fake_snap(1, n=2), _fake_snap(2, n=3)])
    assert merged["merged_from"] == 2
    assert merged["counters"]["c{model=A}"] == 2.0
    assert merged["gauges"]["g"] == 2.0
    h = merged["histograms"]["h"]
    assert h["count"] == 5
    assert h["min"] == pytest.approx(0.01)
    assert h["max"] == pytest.approx(0.03)
    # bucket counts merged -> percentiles still computable
    assert hist_quantile(h, 0.5) is not None


def test_merge_snapshots_dedupes_shared_process():
    """An in-process simulation pulls N identical snapshots of ONE
    registry; the merge must count the process once, not report an
    N-times-larger phantom cluster."""
    one = _fake_snap(42, n=2)
    merged = merge_snapshots([one, dict(one), dict(one)])
    assert merged["merged_from"] == 1
    assert merged["counters"]["c{model=A}"] == 1.0
    # real deployments (one process per node) opt out of nothing:
    merged2 = merge_snapshots(
        [one, dict(one)], dedupe_by_proc=False
    )
    assert merged2["merged_from"] == 2


def test_strip_buckets_keeps_mean_drops_percentiles():
    snap = _fake_snap(1, n=4)
    thin = strip_buckets(snap)
    assert thin["stripped"] is True
    h = thin["histograms"]["h"]
    assert h["count"] == 4 and "sum" in h
    assert "bkt" not in h and "edges" not in h
    assert summarize_histogram(h)["mean"] == pytest.approx(0.025)
    assert json.dumps(thin)  # still wire-able


def test_default_edges_compress_to_sentinel():
    """Default-bucket histograms ship a sentinel, not 37 floats per
    labeled entry — real pressure against the UDP frame cap — and the
    quantile math resolves the sentinel transparently. Non-default
    edges still travel explicitly."""
    reg = MetricsRegistry()
    reg.histogram("d").observe(0.02)
    reg.histogram("x", edges=[0.1, 1.0]).observe(0.05)
    snap = reg.snapshot()
    assert snap["histograms"]["d"]["edges"] == "default"
    assert snap["histograms"]["x"]["edges"] == [0.1, 1.0]
    assert hist_quantile(snap["histograms"]["d"], 0.5) == pytest.approx(
        0.02
    )
    merged = merge_snapshots([snap])
    assert hist_quantile(merged["histograms"]["d"], 0.5) == pytest.approx(
        0.02
    )


def test_merge_with_stripped_node_keeps_percentiles_honest():
    """A bucket-stripped node's samples must join count/sum (mean
    stays cluster-exact) WITHOUT corrupting the quantile rank: ranking
    the merged buckets over the inflated total count would report the
    full node's tail as the cluster median. Regression shape: node A
    holds 5 samples at ~10s, stripped node B holds 995 at ~1ms — the
    cluster p50 must not be 10s."""
    full = _fake_snap(1, n=5, lo=10.0)  # 5 samples around 10 s
    heavy = _fake_snap(2, n=995, lo=0.001, step=0.0)  # 995 @ 1 ms
    stripped = strip_buckets(heavy)
    merged = merge_snapshots([full, stripped])
    h = merged["histograms"]["h"]
    assert h["count"] == 1000
    assert h["bkt_count"] == 5  # only the full node's buckets exist
    # percentiles describe the bucketed subpopulation (node A), never
    # a rank-inflated fiction; the summary says how many they cover
    assert hist_quantile(h, 0.5) == pytest.approx(10.0, rel=0.5)
    s = summarize_histogram(h)
    assert s["percentile_count"] == 5
    assert s["mean"] == pytest.approx(
        (sum(10.0 + 0.01 * i for i in range(5)) + 995 * 0.001) / 1000,
        rel=1e-6,
    )
    # stripped-first merge order must not poison the edges either
    merged2 = merge_snapshots([stripped, full])
    assert hist_quantile(merged2["histograms"]["h"], 0.5) == pytest.approx(
        hist_quantile(h, 0.5)
    )
    # all-stripped: percentiles unknowable, not fabricated
    only = merge_snapshots([stripped])
    assert hist_quantile(only["histograms"]["h"], 0.5) is None


def test_rate_gauge_decays_via_collector():
    """jobs_query_rate_per_s must decay to zero on an idle
    coordinator: the scheduler registers a registry collector that
    recomputes the trailing window at exposition time, so a scrape an
    hour after the last ACK does not report phantom traffic."""
    from dml_tpu.jobs.cost_model import ModelCost
    from dml_tpu.jobs.scheduler import Scheduler

    clock = [1000.0]
    s = Scheduler(
        costs={"M": ModelCost(1.0, 0.5, 0.1, batch_size=4)},
        now=lambda: clock[0],
    )
    s.submit_job(1, "M", ["f1", "f2", "f3", "f4"], 4, "req")
    [a] = s.schedule(["w1"])
    s.on_batch_done(
        "w1", a.batch.job_id, a.batch.batch_id, exec_time=0.4, n_images=4
    )
    rate_key = "jobs_query_rate_per_s{model=M}"
    assert METRICS.snapshot()["gauges"][rate_key] == pytest.approx(0.4)
    clock[0] += 3600.0  # idle hour; no further scheduler events
    assert METRICS.snapshot()["gauges"][rate_key] == 0.0


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("reqs_total", "requests").inc(3, model="A")
    reg.gauge("depth").set(2)
    h = reg.histogram("lat", edges=[0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.to_prometheus_text()
    assert "# HELP reqs_total requests" in text
    assert "# TYPE reqs_total counter" in text
    assert 'reqs_total{model="A"} 3' in text
    assert "depth 2" in text
    # cumulative bucket counts, +Inf == count, sum/count series
    assert 'lat_bucket{le="0.1"} 1' in text
    assert 'lat_bucket{le="1"} 2' in text
    assert 'lat_bucket{le="+Inf"} 3' in text
    assert "lat_count 3" in text
    assert "lat_sum 5.55" in text


def test_summarize_snapshot_shape():
    s = summarize_snapshot(_fake_snap(1, n=2))
    assert set(s) == {"counters", "gauges", "histograms"}
    assert set(s["histograms"]["h"]) >= {"count", "mean", "p50", "p95", "p99"}


