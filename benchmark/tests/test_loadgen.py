"""Percentile, due-time and draw arithmetic on hand-computed fixtures."""

import random

import pytest

from benchmark.harness import loadgen as lg


def test_percentile_by_hand():
    vals = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert lg.percentile(vals, 50) == 30.0
    # rank 0.95 * 4 = 3.8 -> 40 + 0.8 * 10
    assert lg.percentile(vals, 95) == pytest.approx(48.0)
    assert lg.percentile([7.0], 95) == 7.0


def _req(i, due, first, last, done, items, first_items, chunks, ok=True):
    r = lg.Request(index=i, name=f"r{i}", size={}, due=due)
    r.sent, r.first, r.last, r.done = due + 0.001 * i, first, last, done
    r.items, r.first_chunk_items, r.chunks, r.ok = items, first_items, chunks, ok
    return r


def test_latency_counts_from_due_time_and_keeps_a_shed_request_in_the_tail():
    reqs = [
        # due 0.0, first token at 0.5, 1+10 tokens, last at 1.5, done 1.6
        _req(0, 0.0, 0.5, 1.5, 1.6, 11, 1, 3),
        # due 1.0 but SENT late: still timed from 1.0
        _req(1, 1.0, 1.2, 2.2, 2.3, 21, 1, 3),
        # one delivery only: no TPOT sample
        _req(2, 2.0, 2.4, 2.4, 2.5, 8, 8, 1),
        # shed at the door: stays in at the drain limit
        _req(3, 3.0, None, None, None, 0, 0, 0, ok=False),
    ]
    s = lg.open_loop_summary(reqs, drain_limit_s=30.0)
    assert s["attempted"] == 4 and s["failed"] == 1
    # ttft sample: 0.5, 0.2, 0.4, 30.0 -> sorted 0.2 0.4 0.5 30
    assert s["ttft_p50_ms"] == pytest.approx(450.0)
    # rank 0.95 * 3 = 2.85 -> 0.5 + 0.85 * 29.5
    assert s["ttft_p95_ms"] == pytest.approx((0.5 + 0.85 * 29.5) * 1000)
    # tpot: (1.5 - 0.5) / 10 = 0.1 and (2.2 - 1.2) / 20 = 0.05
    assert s["tpot_samples"] == 2
    assert s["tpot_p50_ms"] == pytest.approx(75.0)
    # lateness: 0, 1, 2 ms (the shed one was sent 3 ms late too)
    assert s["gen_late_max_ms"] == pytest.approx(3.0)
    # end-to-end latency of the shed request is the limit as well
    assert s["latency_p95_ms"] > 20000


def test_a_request_later_than_the_limit_is_held_at_the_limit():
    s = lg.open_loop_summary([_req(0, 0.0, 40.0, 41.0, 42.0, 5, 1, 2)], 30.0)
    assert s["ttft_p95_ms"] == 30000.0


def test_every_seed_gets_the_same_sequence_from_another_point():
    dist = {"dist": "lognormal", "median": 256, "sigma": 0.9,
            "min": 32, "max": 2048}
    a = lg.int_draws(dist, 200, random.Random(0), 1)
    b = lg.int_draws(dist, 200, random.Random(0), 2_147_480_042)
    assert a != b and sorted(a) == sorted(b)
    k = (2_147_480_042 - 1) % 200
    assert b == a[k:] + a[:k]  # a rotation: the bursts stay together
    assert min(a) >= 32 and max(a) <= 2048
    assert sorted(a)[100] == pytest.approx(256, abs=3)
    ta = lg.arrival_times(5.0, 40.0, random.Random(0), 1)
    tb = lg.arrival_times(5.0, 40.0, random.Random(0), 2)
    assert len(ta) == len(tb) == 200 and ta != tb
    assert ta[-1] == pytest.approx(40.0 - 0.1) == pytest.approx(tb[-1])
    gaps = lambda t: [round(y - x, 9) for x, y in zip([0.0] + t, t)]
    assert gaps(tb) == gaps(ta)[1:] + gaps(ta)[:1]
    assert all(x < y for x, y in zip(ta, ta[1:]))


def test_quantile_draws_of_an_exponential_have_its_mean():
    vals = lg.quantile_draws({"dist": "exponential", "mean": 0.25}, 1000)
    assert sum(vals) / len(vals) == pytest.approx(0.25, rel=0.01)
