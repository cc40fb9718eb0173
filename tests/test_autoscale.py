"""Closed-loop autoscaler coverage: the deterministic policy core
(hysteresis gating, floor/ceiling/cooldown guards, liar immunity,
victim selection, the scale-in confirm window and its spike cancel),
the decision ledger's exactly-once + relay/adoption surface, the
byte-identical replay contract, the diurnal trace generator, the
session-affinity purge on scale-in, and the controller-aimed chaos
family (slow).
"""

import asyncio
import contextlib
import json
import os
import shutil

import pytest

from dml_tpu.autoscale import (
    DECISION_KINDS,
    AutoscaleController,
    AutoscalePolicy,
    DecisionLedger,
    replay_decision_stream,
    slo_violation_minutes,
)

pytestmark = pytest.mark.autoscale


# ----------------------------------------------------------------------
# synthetic snapshot helpers
# ----------------------------------------------------------------------

POOL3 = ["h:7001", "h:7002", "h:7003"]

#: a fast-twitch policy so streaks resolve in a handful of ticks
POL = AutoscalePolicy(
    floor=2, ceiling=5, backlog_per_slot=2.0, idle_arrival_qps=1.0,
    out_fire_after=2, out_clear_after=2,
    in_fire_after=3, in_clear_after=1, confirm_ticks=1,
    out_cooldown_s=5.0, in_cooldown_s=5.0, realloc_cooldown_s=5.0,
    apply_timeout_s=20.0,
)


def snap(t, pool=None, backlog=0.0, arrivals=0.0, burn=(), liars=(),
         unhealthy=(), busy=(), culprits=(), weights=None):
    return {
        "t": float(t),
        "pool": list(pool if pool is not None else POOL3),
        "busy": list(busy),
        "backlog": {"m": backlog} if backlog else {},
        "arrivals_qps": {"interactive": arrivals} if arrivals else {},
        "burn_firing": list(burn),
        "liars": list(liars),
        "unhealthy": list(unhealthy),
        "culprit_classes": list(culprits),
        "class_weights": dict(weights or {}),
    }


def ctl(policy=POL):
    return AutoscaleController(policy=policy, clock=lambda: 0.0)


# ----------------------------------------------------------------------
# (a) scale-out: pressure hysteresis, ceiling, cooldown, liar mask
# ----------------------------------------------------------------------

def test_scale_out_requires_a_pressure_streak():
    c = ctl()
    assert c.step(snap(0.0, burn=["slo_burn_rate|interactive"])) == []
    acts = c.step(snap(1.0, burn=["slo_burn_rate|interactive"]))
    assert acts == [("scale_out", None)]
    rows = c.ledger.pending("scale_out")
    assert len(rows) == 1 and rows[0]["reason"] == "slo-burn"


def test_scale_out_single_pressure_blip_never_fires():
    c = ctl()
    c.step(snap(0.0, burn=["slo_burn_rate|interactive"]))
    for t in (1.0, 2.0):
        assert c.step(snap(t)) == []
    assert c.ledger.pending("scale_out") == []


def test_backlog_pressure_without_burn_alert_scales_out():
    # coordinator-side signal: job-queue depth alone counts
    c = ctl()
    c.step(snap(0.0, backlog=99.0))
    acts = c.step(snap(1.0, backlog=99.0))
    assert acts == [("scale_out", None)]
    assert c.ledger.pending("scale_out")[0]["reason"] == "backlog"


def test_scale_out_respects_ceiling_and_cooldown():
    pool5 = [f"h:70{i:02d}" for i in range(5)]
    c = ctl()
    for t in (0.0, 1.0, 2.0):
        assert c.step(snap(t, pool=pool5, burn=["b|x"])) == []
    # below ceiling but inside the cooldown armed by a fresh proposal
    c2 = ctl()
    c2.step(snap(0.0, burn=["b|x"]))
    assert c2.step(snap(1.0, burn=["b|x"])) == [("scale_out", None)]
    c2.ledger.settle(c2.ledger.pending()[0]["id"], "applied", now=1.5)
    assert c2.step(snap(2.0, burn=["b|x"])) == []  # cooldown holds
    assert c2.step(snap(7.0, burn=["b|x"])) == [("scale_out", None)]


def test_liar_conviction_masks_scale_out_pressure():
    """A convicted liar manufactures backlog/burn; the controller must
    not buy chips for forged evidence — the streak HOLDS, and even a
    pre-armed streak cannot propose while the conviction is live."""
    c = ctl()
    for t in (0.0, 1.0, 2.0, 3.0):
        assert c.step(
            snap(t, burn=["b|x"], backlog=99.0, liars=["h:7003"])
        ) == []
    assert c.ledger.pending() == []
    # conviction lifts -> the pressure streak resumes from where the
    # mask held it and fires on schedule
    acts = []
    for t in (4.0, 5.0):
        acts += c.step(snap(t, burn=["b|x"]))
    assert ("scale_out", None) in acts


# ----------------------------------------------------------------------
# (b) scale-in: idle streak, floor, confirm window, spike cancel,
#     victim selection
# ----------------------------------------------------------------------

def idle_ticks(c, t0, n, pool=None):
    out = []
    for i in range(n):
        out += c.step(snap(t0 + i, pool=pool))
    return out


def test_scale_in_retires_newest_idle_slot_after_streak():
    c = ctl()
    acts = idle_ticks(c, 0.0, 5)
    assert acts == [("scale_in", "h:7003")]  # newest = highest port
    row = c.ledger.rows()[-1]
    assert row["kind"] == "scale_in" and row["detail"]["actuated"]


def test_scale_in_never_proposes_at_or_below_floor():
    c = ctl()
    assert idle_ticks(c, 0.0, 8, pool=POOL3[:2]) == []
    assert c.ledger.pending() == []


def test_scale_in_excludes_busy_and_convicted_victims():
    c = ctl()
    for t in range(2):
        c.step(snap(float(t)))
    acts = c.step(snap(
        2.0, busy=["h:7003"], unhealthy=["h:7002"],
    ))
    # only h:7001 eligible; two more ticks ride out the confirm window
    acts += c.step(snap(3.0, busy=["h:7003"], unhealthy=["h:7002"]))
    acts += c.step(snap(4.0, busy=["h:7003"], unhealthy=["h:7002"]))
    assert ("scale_in", "h:7001") in acts


def test_spike_inside_confirm_window_cancels_scale_in():
    c = ctl(AutoscalePolicy(
        floor=2, ceiling=5, idle_arrival_qps=1.0,
        in_fire_after=2, in_clear_after=1, confirm_ticks=3,
        in_cooldown_s=5.0,
    ))
    c.step(snap(0.0))
    c.step(snap(1.0))  # proposes, confirm_left=3
    assert len(c.ledger.pending("scale_in")) == 1
    acts = c.step(snap(2.0, burn=["b|x"]))  # spike
    assert acts == []
    row = c.ledger.rows()[-1]
    assert row["state"] == "cancelled" and row["reason"] == "spike"


def test_actuated_scale_in_is_past_cancelling():
    """Once the LEAVE fired, a spike must not 'cancel' a departure
    that is already happening — the row rides to settlement instead."""
    c = ctl()
    idle_ticks(c, 0.0, 5)  # proposes + actuates h:7003
    c.step(snap(5.0, burn=["b|x"]))  # spike after actuation
    row = [r for r in c.ledger.rows() if r["kind"] == "scale_in"][-1]
    assert row["state"] == "proposed" and row["detail"]["actuated"]
    # the node leaving settles it applied by observation
    c.step(snap(6.0, pool=POOL3[:2]))
    row = [r for r in c.ledger.rows() if r["kind"] == "scale_in"][-1]
    assert row["state"] == "applied"


def test_pool_observation_settles_scale_out_and_timeout_cancels():
    c = ctl()
    c.step(snap(0.0, burn=["b|x"]))
    c.step(snap(1.0, burn=["b|x"]))  # proposes at pool_n=3
    did = c.ledger.pending("scale_out")[0]["id"]
    c.step(snap(2.0, pool=POOL3 + ["h:7104"]))  # capacity joined
    assert c.ledger._rows[did]["state"] == "applied"
    # a proposal whose join never lands cancels on apply_timeout
    c2 = ctl()
    c2.step(snap(0.0, burn=["b|x"]))
    c2.step(snap(1.0, burn=["b|x"]))
    did2 = c2.ledger.pending("scale_out")[0]["id"]
    c2.step(snap(50.0))
    assert c2.ledger._rows[did2]["state"] == "cancelled"
    assert c2.ledger._rows[did2]["reason"] == "timeout"


# ----------------------------------------------------------------------
# (c) reallocation
# ----------------------------------------------------------------------

def test_single_culprit_class_reallocates_weight_capped():
    c = ctl()
    w = {"batch": 1.0, "interactive": 2.0}
    acts = c.step(snap(0.0, culprits=["interactive"], weights=w))
    assert acts == [("reallocate", "interactive")]
    row = c.ledger.rows()[-1]
    assert row["state"] == "applied"
    assert row["detail"]["weights"]["interactive"] == pytest.approx(3.0)
    assert row["detail"]["weights"]["batch"] == pytest.approx(1.0)
    # inside the cooldown nothing re-fires; at the cap nothing changes
    assert c.step(snap(1.0, culprits=["interactive"], weights=w)) == []
    c2 = ctl()
    capped = {"batch": 1.0, "interactive": POL.realloc_cap}
    assert c2.step(
        snap(0.0, culprits=["interactive"], weights=capped)
    ) == []


def test_two_culprits_or_unknown_class_never_reallocate():
    c = ctl()
    w = {"batch": 1.0, "interactive": 2.0}
    assert c.step(
        snap(0.0, culprits=["batch", "interactive"], weights=w)
    ) == []
    assert c.step(snap(1.0, culprits=["ghost"], weights=w)) == []
    assert c.ledger.rows() == []


# ----------------------------------------------------------------------
# (d) ledger: exactly-once, adoption, bounds
# ----------------------------------------------------------------------

def test_ledger_settle_and_actuate_are_exactly_once():
    led = DecisionLedger(clock=lambda: 0.0)
    row = led.propose("scale_in", "h:7003", now=0.0)
    assert led.mark_actuated(row["id"], now=1.0)
    assert not led.mark_actuated(row["id"], now=2.0)
    assert led.settle(row["id"], "applied", now=3.0)
    assert not led.settle(row["id"], "cancelled", now=4.0)
    assert not led.settle("scale_in:ghost:99", "applied", now=5.0)
    events = [e["event"] for e in led.stream()]
    assert events == ["propose", "actuate", "apply"]


def test_ledger_adopt_newest_wins_and_cooldowns_merge_by_max():
    a = DecisionLedger(clock=lambda: 0.0)
    row = a.propose("scale_out", None, now=1.0)
    a.arm_cooldown("scale_out", 10.0)
    b = DecisionLedger(clock=lambda: 0.0)
    b.arm_cooldown("scale_out", 4.0)
    assert b.adopt(a.rows(), cooldowns=a.cooldowns) == 1
    assert b.cooldowns["scale_out"] == 10.0
    # a STALE copy of the same row must not regress the adopted state
    a.settle(row["id"], "applied", now=2.0)
    fresh = a.rows()
    assert b.adopt(fresh, cooldowns=None) == 1
    stale = [dict(r, last=0.5, state="proposed") for r in fresh]
    assert b.adopt(stale) == 0
    assert b._rows[row["id"]]["state"] == "applied"
    # successor ids can never collide with adopted ones
    nxt = b.propose("scale_out", None, now=3.0)
    assert nxt["seq"] > max(r["seq"] for r in fresh)


def test_ledger_adopt_drops_malformed_rows():
    led = DecisionLedger(clock=lambda: 0.0)
    assert led.adopt([
        "nope", {"id": 7}, {"id": "x", "kind": "explode"},
        {"id": "y", "kind": "scale_in", "state": "vaporized"},
    ], cooldowns={"scale_in": "NaN-ish", "ghost": 99.0}) == 0
    assert led.rows() == [] and led.cooldowns == {}


def test_ledger_bound_evicts_settled_rows_first():
    led = DecisionLedger(clock=lambda: 0.0, max_rows=2)
    r1 = led.propose("scale_out", None, now=0.0)
    led.settle(r1["id"], "applied", now=0.5)
    r2 = led.propose("scale_in", "a", now=1.0)
    led.propose("scale_in", "b", now=2.0)
    assert r1["id"] not in led._rows
    assert r2["id"] in led._rows


# ----------------------------------------------------------------------
# (e) failover mid-decision: the promoted leader inherits the actuated
#     row + cooldowns through the relay and never re-issues the LEAVE
# ----------------------------------------------------------------------

def test_promoted_leader_inherits_actuated_decision_exactly_once():
    leader = ctl()
    standby = ctl()
    # the standby adopts every relayed transition, exactly as
    # _h_autoscale does with each datagram's (row, cooldowns) pair
    leader.ledger.on_event.append(
        lambda ev, row: standby.ledger.adopt(
            [row], cooldowns=leader.ledger.cooldowns)
    )
    idle_ticks(leader, 0.0, 5)  # propose + actuate scale_in h:7003
    # leader dies between the LEAVE firing and the universe shrinking.
    # The successor sees the SAME pool (target not yet gone):
    acts = standby.step(snap(5.0))
    assert acts == []  # actuated row inherited -> no second LEAVE
    assert standby.ledger.in_cooldown("scale_in", 6.0)
    # the departure lands; the successor settles by observation
    standby.step(snap(7.0, pool=POOL3[:2]))
    merged = leader.ledger.stream() + standby.ledger.stream()
    per_id = {}
    for ev in merged:
        per_id.setdefault(ev["id"], []).append(ev["event"])
    for did, evs in per_id.items():
        assert evs.count("actuate") <= 1, (did, evs)
        assert evs.count("apply") <= 1, (did, evs)


def test_promoted_leader_reapplies_adopted_reallocation():
    class _Sched:
        class_weights = {"batch": 1.0, "interactive": 2.0}
        applied = None

        def reweight_classes(self, w):
            self.applied = dict(w)
            return {}

    class _Jobs:
        scheduler = _Sched()

    dead = ctl()
    dead.step(snap(0.0, culprits=["interactive"],
                   weights={"batch": 1.0, "interactive": 2.0}))
    successor = ctl()
    successor.jobs = _Jobs()
    successor.ledger.adopt(dead.ledger.rows())
    successor._on_promoted()
    assert successor.jobs.scheduler.applied == {
        "batch": 1.0, "interactive": 3.0,
    }


# ----------------------------------------------------------------------
# (f) replay determinism
# ----------------------------------------------------------------------

def _tick_schedule():
    ticks = []
    t = 0.0
    for i in range(40):
        if i < 6:
            ticks.append(snap(t, burn=["slo_burn_rate|interactive"]))
        elif i < 10:
            ticks.append(snap(t, pool=POOL3 + ["h:7104"]))
        elif i == 10:
            ticks.append(snap(
                t, pool=POOL3 + ["h:7104"],
                culprits=["interactive"],
                weights={"batch": 1.0, "interactive": 2.0},
            ))
        elif i < 30:
            ticks.append(snap(t, pool=POOL3 + ["h:7104"]))
        else:
            ticks.append(snap(t, pool=POOL3))
        t += 1.0
    return ticks


def test_replay_decision_stream_is_byte_identical():
    ticks = _tick_schedule()
    a = replay_decision_stream(ticks, policy=POL)
    b = replay_decision_stream(
        json.loads(json.dumps(ticks)), policy=POL
    )
    ja = json.dumps(a, sort_keys=True, separators=(",", ":"))
    jb = json.dumps(b, sort_keys=True, separators=(",", ":"))
    assert ja == jb
    kinds = {e["kind"] for e in a}
    assert {"scale_out", "scale_in", "reallocate"} <= kinds


def test_replay_diverges_when_the_snapshot_schedule_does():
    ticks = _tick_schedule()
    mutated = json.loads(json.dumps(ticks))
    # break the INITIAL pressure streak: the scale-out proposal lands
    # two ticks later, shifting every stamp after it
    mutated[1]["burn_firing"] = []
    a = replay_decision_stream(ticks, policy=POL)
    b = replay_decision_stream(mutated, policy=POL)
    assert json.dumps(a, sort_keys=True) != json.dumps(b, sort_keys=True)


# ----------------------------------------------------------------------
# (g) scoring + diurnal trace generator
# ----------------------------------------------------------------------

def test_slo_violation_minutes_buckets_by_arrival_time():
    from dml_tpu.ingress.loadgen import Arrival, ArrivalTrace, Outcome

    arrivals = tuple(
        Arrival(t=float(i), model="m", slo="interactive")
        for i in range(10)
    )
    trace = ArrivalTrace(
        seed=1, duration_s=10.0, rate_qps=1.0, arrivals=arrivals
    )

    def o(ok):
        return Outcome(
            slo="interactive", terminal="completed" if ok else "shed",
            e2e_s=0.1, deadline_met=ok,
        )

    # bucket [0,5) all good; bucket [5,10) 40% bad -> 5s = 1/12 min
    outcomes = [o(True)] * 5 + [o(False), o(False), o(True), o(True),
                                o(True)]
    assert slo_violation_minutes(trace, outcomes) == round(5 / 60.0, 4)
    assert slo_violation_minutes(trace, [o(True)] * 10) == 0.0


def test_diurnal_trace_deterministic_and_json_round_trips():
    from dml_tpu.ingress.loadgen import ArrivalTrace, diurnal_trace

    a = diurnal_trace(11, duration_s=12.0, base_qps=2.0, peak_qps=30.0)
    b = diurnal_trace(11, duration_s=12.0, base_qps=2.0, peak_qps=30.0)
    assert a.to_json() == b.to_json()
    assert ArrivalTrace.from_json(a.to_json()).to_json() == a.to_json()
    assert diurnal_trace(
        12, duration_s=12.0, base_qps=2.0, peak_qps=30.0
    ).to_json() != a.to_json()


def test_diurnal_trace_envelope_has_plateau_peak_and_trough():
    from dml_tpu.ingress.loadgen import diurnal_trace

    tr = diurnal_trace(
        3, duration_s=40.0, base_qps=2.0, peak_qps=40.0,
        ramp_frac=0.2, plateau_frac=0.3,
    )

    def rate(lo, hi):
        n = sum(1 for a in tr.arrivals if lo <= a.t < hi)
        return n / (hi - lo)

    plateau = rate(9.0, 19.0)    # inside [8, 20)
    trough = rate(31.0, 40.0)    # past the down-ramp
    assert plateau > 0.7 * 40.0
    assert trough < 0.35 * plateau
    assert all(
        x.t <= y.t for x, y in zip(tr.arrivals, tr.arrivals[1:])
    )


# ----------------------------------------------------------------------
# (h) session-affinity purge on departure (scale-in satellite)
# ----------------------------------------------------------------------

@pytest.mark.asyncio
def test_affinity_purge_labels_leave_vs_failure(tmp_path):
    from dml_tpu.cluster.chaos import LocalCluster
    from dml_tpu.observability import METRICS

    async def run():
        root = str(tmp_path / "aff")
        cluster = LocalCluster(3, root, 45610, with_ingress=True)
        try:
            await cluster.start()
            await cluster.wait_for(
                cluster.converged, 20.0, "affinity purge convergence"
            )
            sn = next(iter(cluster.nodes.values()))
            router = sn.ingress
            alive = {n.unique_name for n in cluster.spec.nodes}
            crashed = sorted(alive)[-1]

            def count(reason):
                key = ("request_session_affinity_evictions_total"
                       f"{{reason={reason}}}")
                return METRICS.snapshot()["counters"].get(key, 0)

            before_f, before_l = count("failure"), count("leave")
            # a crash leaves the universe row in place -> "failure"
            router._session_node["s-crash"] = crashed
            router._purge_sessions_for(crashed)
            assert "s-crash" not in router._session_node
            assert count("failure") == before_f + 1
            # a graceful LEAVE removed the row first -> "leave"
            router._session_node["s-leave"] = "h:9999"
            router._session_dirty.add("s-leave")
            router._purge_sessions_for("h:9999")
            assert "s-leave" not in router._session_node
            assert "s-leave" not in router._session_dirty
            assert count("leave") == before_l + 1
        finally:
            await cluster.stop()

    asyncio.run(run())


# ----------------------------------------------------------------------
# (i) controller-aimed chaos family (slow, >=3 seeds)
# ----------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.parametrize("seed,port", [(7, 45710), (8, 45910),
                                       (9, 46110)])
def test_autoscale_scenario_family_green(seed, port, tmp_path):
    from dml_tpu.cluster.chaos import run_plan_sync, scenario_plan

    plan = scenario_plan("autoscale", seed)
    assert plan.autoscale and plan.join_secret
    report = run_plan_sync(
        plan, base_port=port, root=str(tmp_path / f"as{seed}")
    )
    d = report.to_dict()
    assert d["ok"], d["invariants"]["failures"]
    checks = d["invariants"]["checks"]["autoscale"]
    assert checks["min_pool_seen"] >= checks["floor"]
    assert checks["distinct_ids"] >= 1


def test_autoscale_scenario_plan_is_seeded_and_round_trips():
    from dml_tpu.cluster.chaos import ChaosPlan, scenario_plan

    a = scenario_plan("autoscale", 7)
    assert a.to_dict() == scenario_plan("autoscale", 7).to_dict()
    assert a.to_dict() != scenario_plan("autoscale", 8).to_dict()
    assert ChaosPlan.from_dict(a.to_dict()) == a
    kinds = [e.kind for e in a.events]
    assert kinds.count("job") >= 6          # thrash square wave
    assert kinds.count("liar") == 2         # conviction + heal
    assert "crash" in kinds                 # leader kill mid-decision
