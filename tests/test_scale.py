"""Control-plane scale coverage (ISSUE 11): bounded delta gossip
(determinism, bit-compatibility at small N, counterfactual convergence
vs full-table exchange), two-level relay metrics aggregation, store
inventory delta re-reports, sustained-churn plan generation and the
in-process scale probe."""


import pytest

from dml_tpu.cluster.membership import ALIVE, SUSPECT, MembershipList
from dml_tpu.config import ClusterSpec


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def full_table(spec, clock, status=ALIVE):
    return {n.unique_name: (clock.t, status) for n in spec.nodes}


def make_list(spec, i, clock, seed=7):
    return MembershipList(
        spec=spec, me=spec.nodes[i], clock=clock, gossip_seed=seed
    )


# ----------------------------------------------------------------------
# delta gossip core
# ----------------------------------------------------------------------


def test_gossip_is_full_table_at_small_n():
    """Bit-compatibility: at N <= 1 + k + tail the delta protocol
    emits the reference full table, so every small-N tier-1 behavior
    is unchanged."""
    clock = FakeClock()
    spec = ClusterSpec.localhost(5)
    a = make_list(spec, 0, clock)
    a.merge(full_table(spec, clock))
    assert not a.delta_active()
    assert a.gossip() == a.snapshot()


def test_gossip_bounded_at_large_n_and_periodic_full():
    clock = FakeClock()
    spec = ClusterSpec.localhost(40)
    a = make_list(spec, 0, clock)
    a.merge(full_table(spec, clock))
    assert a.delta_active()
    bound = 1 + spec.gossip_delta_k + spec.gossip_delta_tail
    me = a.me.unique_name
    fulls = 0
    for _ in range(spec.gossip_full_every * 2):
        g = a.gossip()
        assert me in g  # own heartbeat always rides
        if len(g) == 40:
            fulls += 1
        else:
            assert len(g) <= bound
    # the periodic anti-entropy full exchange fired (every Nth)
    assert fulls == 2


def test_gossip_selection_deterministic_per_seed():
    """Same seed => identical piggyback selection stream; a different
    seed diverges (the seeded random tail)."""
    clock = FakeClock()
    spec = ClusterSpec.localhost(40)

    def stream(seed, rounds=12):
        m = make_list(spec, 0, clock, seed=seed)
        m.merge(full_table(spec, clock))
        return [tuple(sorted(m.gossip())) for _ in range(rounds)]

    assert stream(7) == stream(7)
    assert stream(7) != stream(8)


def test_status_change_gets_piggyback_priority():
    """A fresh suspicion must ride the very next bounded payload —
    freshness priority is what keeps failure detection fast when the
    payload no longer carries the whole table."""
    clock = FakeClock()
    spec = ClusterSpec.localhost(40)
    a = make_list(spec, 0, clock)
    a.merge(full_table(spec, clock))
    for _ in range(5):
        a.gossip()  # age the initial freshness
    victim = spec.nodes[20].unique_name
    a.suspect(victim)
    g = a.gossip()
    assert g[victim][1] == SUSPECT


def test_delta_only_convergence_matches_full_table_exchange():
    """Counterfactual: a node that hears a 40-member table ONLY via
    bounded delta payloads converges to the same membership view as
    one full-table exchange (the random tail + periodic anti-entropy
    close any gap the K-freshest selection leaves)."""
    clock = FakeClock()
    spec = ClusterSpec.localhost(40)
    b = make_list(spec, 1, clock, seed=3)
    b.merge(full_table(spec, clock))

    via_full = make_list(spec, 0, clock, seed=4)
    via_full.merge(b.snapshot())
    want = sorted(n.unique_name for n in via_full.alive_nodes())

    via_delta = make_list(spec, 0, clock, seed=5)
    for i in range(3 * spec.gossip_full_every):
        via_delta.merge(b.gossip())
        got = sorted(n.unique_name for n in via_delta.alive_nodes())
        if got == want:
            break
    assert got == want, f"delta-only view never converged ({len(got)}/40)"


def test_gossip_metrics_move():
    from dml_tpu.observability import METRICS

    def ctr(name):
        # sums every label variant of the counter (the payload mode
        # split is covered by the bounded/full assertions above)
        snap = METRICS.snapshot()["counters"]
        return sum(v for k, v in snap.items() if k.startswith(name))

    clock = FakeClock()
    spec = ClusterSpec.localhost(40)
    a = make_list(spec, 0, clock)
    a.merge(full_table(spec, clock))
    before = ctr("membership_gossip_exchanges_total")
    a.gossip()
    assert ctr("membership_gossip_exchanges_total") == before + 1


# ----------------------------------------------------------------------
# merge_snapshots: pre-merged relay blobs
# ----------------------------------------------------------------------


def test_merge_snapshots_dedupes_premerged_blobs_by_procs():
    from dml_tpu.observability import merge_snapshots

    def snap(proc, val):
        return {"proc": proc, "counters": {"c": val}, "gauges": {},
                "histograms": {}}

    # in-process shape: leader snapshot + a relay blob whose every
    # proc was already counted => the blob is skipped entirely
    leader = snap(10, 5.0)
    blob = merge_snapshots([snap(10, 5.0), snap(10, 5.0)])
    assert blob["procs"] == [10]
    merged = merge_snapshots([leader, blob])
    assert merged["counters"]["c"] == 5.0
    assert merged["merged_from"] == 1
    # multi-process shape: disjoint procs all count, nested
    # merged_from sums so the node count stays honest
    blob2 = merge_snapshots([snap(11, 1.0), snap(12, 2.0)])
    merged = merge_snapshots([leader, blob2])
    assert merged["counters"]["c"] == 8.0
    assert merged["merged_from"] == 3
    assert merged["procs"] == [10, 11, 12]


# ----------------------------------------------------------------------
# store inventory delta re-reports
# ----------------------------------------------------------------------


def _store_harness(tmp_path, n=3):
    """A StoreService on an UNSTARTED node with sends captured — the
    report logic is pure bookkeeping + send_unique calls."""
    from dml_tpu.cluster.node import Node
    from dml_tpu.cluster.store_service import StoreService

    spec = ClusterSpec.localhost(n, base_port=21890)
    node = Node(spec, spec.nodes[1])
    svc = StoreService(node, root=str(tmp_path / "st"))
    sent = []
    node.send_unique = lambda to, mtype, data: sent.append(
        (to, mtype, data)
    )
    node.joined = True
    node.membership.leader = spec.nodes[0].unique_name
    return spec, node, svc, sent


def test_inventory_report_full_then_delta_then_skip(tmp_path):
    from dml_tpu.cluster.store_service import REPORT_FULL_EVERY
    from dml_tpu.cluster.wire import MsgType

    spec, node, svc, sent = _store_harness(tmp_path)
    leader = spec.nodes[0].unique_name
    svc.store.put_bytes("a.bin", b"aaaa")
    svc._send_inventory_report(leader)
    assert len(sent) == 1
    assert sent[0][1] == MsgType.ALL_LOCAL_FILES
    assert "delta" not in sent[0][2]  # first report is a full table
    # unchanged inventory: the tick sends NOTHING
    sent.clear()
    svc._send_inventory_report(leader)
    assert sent == []
    # a new file rides a delta with only the changed entry
    svc.store.put_bytes("b.bin", b"bbbb")
    svc._send_inventory_report(leader)
    assert len(sent) == 1
    assert sent[0][2]["delta"] is True
    assert list(sent[0][2]["files"]) == ["b.bin"]
    # a deletion rides as an explicit removal
    sent.clear()
    svc.store.delete("a.bin")
    svc._send_inventory_report(leader)
    assert sent[0][2]["delta"] is True
    assert sent[0][2]["removed"] == ["a.bin"]
    # periodic anti-entropy: the Nth report is a full table again
    sent.clear()
    for _ in range(REPORT_FULL_EVERY):
        svc._send_inventory_report(leader)
    fulls = [s for s in sent if "delta" not in s[2]]
    assert len(fulls) == 1


def test_inventory_report_full_after_leader_change(tmp_path):
    spec, node, svc, sent = _store_harness(tmp_path)
    leader = spec.nodes[0].unique_name
    svc.store.put_bytes("a.bin", b"aaaa")
    svc._send_inventory_report(leader)
    sent.clear()
    # a new leader rebuilt its table from COORDINATE_ACKs: the next
    # report must be a FULL table, not a delta against lost state
    svc._on_new_leader_force_full(spec.nodes[2].unique_name)
    svc._send_inventory_report(spec.nodes[2].unique_name)
    assert len(sent) == 1 and "delta" not in sent[0][2]


async def test_leader_applies_delta_reports(tmp_path):
    from dml_tpu.cluster.wire import Message, MsgType

    spec, node, svc, sent = _store_harness(tmp_path)
    # make THIS node the leader so _h_all_local_files applies
    node.membership.leader = node.me.unique_name
    reporter = spec.nodes[2].unique_name
    base = Message(reporter, MsgType.ALL_LOCAL_FILES,
                   {"files": {"a.bin": [1], "b.bin": [1, 2]}})
    await svc._h_all_local_files(base, ("127.0.0.1", 0))
    assert svc.metadata.files[reporter] == {"a.bin": [1], "b.bin": [1, 2]}
    delta = Message(reporter, MsgType.ALL_LOCAL_FILES,
                    {"files": {"c.bin": [1]}, "removed": ["a.bin"],
                     "delta": True})
    await svc._h_all_local_files(delta, ("127.0.0.1", 0))
    assert svc.metadata.files[reporter] == {"b.bin": [1, 2], "c.bin": [1]}
    # duplicate delta: no change, no standby relay
    sent.clear()
    await svc._h_all_local_files(delta, ("127.0.0.1", 0))
    assert not any(
        m == MsgType.ALL_LOCAL_FILES_RELAY for _, m, _ in sent
    )


async def test_partial_full_report_prunes_stale_entries(tmp_path):
    """Multi-chunk full reports merge add-only at the leader, so the
    leading all_names datagram is what repairs a removal whose delta
    was lost: anything the leader holds beyond the sender's complete
    name list is stale and must be pruned."""
    from dml_tpu.cluster.wire import Message, MsgType

    spec, node, svc, sent = _store_harness(tmp_path)
    node.membership.leader = node.me.unique_name
    reporter = spec.nodes[2].unique_name
    seed = Message(reporter, MsgType.ALL_LOCAL_FILES,
                   {"files": {"a.bin": [1], "b.bin": [2]}})
    await svc._h_all_local_files(seed, ("127.0.0.1", 0))
    names = Message(reporter, MsgType.ALL_LOCAL_FILES,
                    {"files": {}, "partial": True,
                     "all_names": ["b.bin", "c.bin"]})
    await svc._h_all_local_files(names, ("127.0.0.1", 0))
    assert svc.metadata.files[reporter] == {"b.bin": [2]}
    chunk = Message(reporter, MsgType.ALL_LOCAL_FILES,
                    {"files": {"c.bin": [3]}, "partial": True})
    await svc._h_all_local_files(chunk, ("127.0.0.1", 0))
    assert svc.metadata.files[reporter] == {"b.bin": [2], "c.bin": [3]}


def test_report_phase_jitter_desynchronizes_nodes(tmp_path):
    from dml_tpu.cluster.node import Node
    from dml_tpu.cluster.store_service import StoreService

    spec = ClusterSpec.localhost(12, base_port=21930)
    phases = set()
    for i in range(12):
        node = Node(spec, spec.nodes[i])
        svc = StoreService(node, root=str(tmp_path / f"st{i}"))
        phases.add(svc._report_phase)
    # identity-derived phases spread over the period (not one spike)
    assert len(phases) >= 4


# ----------------------------------------------------------------------
# churn plan generation
# ----------------------------------------------------------------------


def test_churn_plan_deterministic_paired_and_rotating():
    from dml_tpu.cluster.chaos import churn_plan

    a = churn_plan(5, n_nodes=8, rate_per_s=1.5, duration=8.0)
    b = churn_plan(5, n_nodes=8, rate_per_s=1.5, duration=8.0)
    assert [e.to_dict() for e in a.events] == [
        e.to_dict() for e in b.events
    ]
    crashes = [e for e in a.events if e.kind == "crash"]
    restarts = [e for e in a.events if e.kind == "restart"]
    # sustained: several pairs, every crash paired with a restart
    assert len(crashes) >= 3
    assert sorted(e.target for e in crashes) == sorted(
        e.target for e in restarts
    )
    # rotation: churn hits multiple distinct nodes, never the
    # leader/standby ranks
    victims = {e.target for e in crashes}
    assert len(victims) >= 2
    assert not victims & {"H1", "H2"}
    # a restart always follows its crash
    last_crash = {}
    for e in a.events:
        if e.kind == "crash":
            last_crash[e.target] = e.t
        elif e.kind == "restart":
            assert e.t > last_crash[e.target]


def test_churn_is_a_scenario_family():
    from dml_tpu.cluster.chaos import SCENARIO_FAMILIES, scenario_plan

    assert "churn" in SCENARIO_FAMILIES
    plan = scenario_plan("churn", 2)
    kinds = {e.kind for e in plan.events}
    assert {"crash", "restart", "put", "get"} <= kinds


# ----------------------------------------------------------------------
# the in-process scale probe + relay metrics path (tier-1 smoke)
# ----------------------------------------------------------------------


@pytest.mark.scale
async def test_scale_probe_smoke(tmp_path):
    """One bounded-size probe through the REAL machinery: a 16-node
    membership-only cluster on the delta protocol converges, carries
    bounded gossip, aggregates metrics through relays (covering every
    node, in-process totals deduped), detects a crash cluster-wide,
    and re-elects after the leader dies."""
    from dml_tpu.cluster.chaos import control_plane_probe

    r = await control_plane_probe(
        16, 21960, root=str(tmp_path / "probe"), seed=2,
        protocol="delta", measure_s=1.0,
    )
    assert r["converge_s"] > 0
    assert r["bytes_per_node_s"] > 0
    # a strong majority must report; == 16 would flake whenever this
    # sandbox host stalls the loop past a pull timeout mid-suite
    assert r["metrics_direct"]["nodes_covered"] >= 12
    assert r["metrics_relay"]["nodes_covered"] >= 12
    # shared in-process registry: dedupe keeps the total honest
    assert r["metrics_relay"]["merged_from"] == 1
    assert r["detect_s"] and r["detect_s"] > 0
    assert r["election_s"] and r["election_s"] > 0
    assert r["new_leader"] is not None
    # the straggler phase ran and the serial shape paid per-peer
    strag = r["metrics_straggler"]
    assert strag["dead_peers"] == 4
    assert strag["serial_wall_s"] > strag["relay_wall_s"]


@pytest.mark.scale
async def test_relay_fallback_covers_dead_relay(tmp_path):
    """A dead relay must not blind the leader to its shard: the
    leader falls back to direct pulls and the fallback is counted."""
    from dml_tpu.cluster.chaos import LocalCluster

    c = LocalCluster(5, str(tmp_path / "c"), 21985, seed=3,
                     services="core")
    try:
        await c.start()
        await c.wait_for(c.converged, 15.0, "convergence")
        leader = c.nodes[c.leader_uname()].node
        peers = sorted(
            (n for n in leader.membership.alive_nodes()
             if n.unique_name != leader.me.unique_name),
            key=lambda n: n.unique_name,
        )
        # the deterministic relay pick is the head of the sorted list
        await c.crash_node(peers[0].unique_name)
        view = await leader.pull_cluster_metrics(
            timeout=1.0, relays=1, peers=peers
        )
        assert view["relay"]["fallbacks"] == 1
        # every LIVE peer still reported (direct fallback pulls)
        live = {p.unique_name for p in peers[1:]}
        assert live <= set(view["nodes"])
        assert peers[0].unique_name in view["unreachable"]
    finally:
        await c.stop()
