"""Elastic membership: authenticated runtime join/leave, the
versioned universe, adaptive group re-formation, and the
capacity-change chaos family.

Layers covered:

- config: universe mutation (add/remove/absorb), the HMAC-stamped
  change log, delta/full catch-up forms, forged-entry refusal
- node: the JOIN_REQUEST handshake end to end (admission, stale-epoch
  re-claim, typed rejections), graceful LEAVE retirement with no
  false-failure accounting, epoch propagation over the gossip
  piggyback with PRIVATE per-node specs (nothing short-circuited
  through a shared object)
- groups: the reform ladder (best dp×tp×pp mesh the survivors
  support), reshape edges, reformed bitwise equality on the real
  param_gather path
- scheduler: the DepthController pool-size re-probe trigger
- chaos: the `elastic` scenario family, JOIN forgeries in
  fuzz_datagrams, scale_out/scale_in on LocalCluster
"""

import asyncio
import json
import socket

import pytest

from dml_tpu.config import (
    ClusterSpec, MeshSpec, NodeId, Timing, WorkerGroupSpec, join_mac,
    leave_mac, universe_entry_mac,
)

pytestmark = pytest.mark.elastic

FAST = Timing(
    ping_interval=0.05,
    ack_timeout=0.15,
    cleanup_time=0.3,
    missed_acks_to_suspect=2,
    leader_rpc_timeout=5.0,
)

SECRET = "test-elastic-secret"


def _spec(n=3, base_port=24100, **kw):
    s = ClusterSpec.localhost(
        n, base_port=base_port, introducer_port=base_port - 1,
        timing=FAST, **kw,
    )
    s.join_secret = SECRET
    return s


def _copy(spec):
    return ClusterSpec.from_json(spec.to_json())


async def _until(cond, timeout=10.0, what=""):
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while loop.time() < deadline:
        if cond():
            return
        await asyncio.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


def _counter(name):
    from dml_tpu.observability import METRICS

    snap = METRICS.snapshot()["counters"]
    return float(sum(v for k, v in snap.items() if k.startswith(name)))


# ----------------------------------------------------------------------
# config: MACs + universe mutation + catch-up forms
# ----------------------------------------------------------------------


def test_join_mac_binds_identity_nonce_and_epoch():
    node = {"host": "10.0.0.1", "port": 9001, "name": "J1", "rank": 0}
    base = join_mac(SECRET, node, "n1", 3)
    assert base == join_mac(SECRET, dict(node), "n1", 3)  # deterministic
    assert base != join_mac(SECRET, dict(node, port=9002), "n1", 3)
    assert base != join_mac(SECRET, node, "n2", 3)
    assert base != join_mac(SECRET, node, "n1", 4)
    assert base != join_mac("other-secret", node, "n1", 3)
    # the requested worker group is MAC-bound too: an on-path rewrite
    # of the topology-changing field invalidates the request
    assert base != join_mac(SECRET, node, "n1", 3, group="g0")
    assert base == join_mac(SECRET, node, "n1", 3, group="")
    assert leave_mac(SECRET, "10.0.0.1:9001", "n1", 3) != base


def test_spec_add_remove_bump_epoch_and_stamp_log():
    s = _spec(3)
    j = NodeId("127.0.0.1", 24990, name="J1")
    assert s.add_node(j)
    assert s.universe_epoch == 1
    assert not s.add_node(j)  # rejoin: no bump
    assert s.universe_epoch == 1
    ent = s._universe_log[-1]
    assert ent["op"] == "join"
    assert ent["mac"] == universe_entry_mac(SECRET, ent)
    assert s.remove_node(j.unique_name)
    assert s.universe_epoch == 2
    assert s.node_by_unique_name(j.unique_name) is None
    assert s._universe_log[-1]["op"] == "leave"
    # local form: bookkeeping only, no epoch, no entry
    k = NodeId("127.0.0.1", 24991, name="J2")
    assert s.add_node(k, local=True)
    assert s.universe_epoch == 2
    assert s.node_by_unique_name(k.unique_name) is not None


def test_group_absorption_and_strip():
    g = WorkerGroupSpec("g0", ("H2", "H3"), MeshSpec(dp=1, tp=2))
    s = _spec(4, worker_groups=[g])
    j = NodeId("127.0.0.1", 24992, name="J1")
    s.add_node(j, group="g0")
    assert j.unique_name in s.group_members_unique("g0")
    assert s.group_of_unique(j.unique_name).name == "g0"
    s.remove_node(j.unique_name)
    assert j.unique_name not in s.group_members_unique("g0")
    # a genesis member leaving is stripped too: the remaining members
    # ARE the group's new full strength
    h2 = s.node_by_name("H2").unique_name
    s.remove_node(h2)
    assert s.group_members_unique("g0") == (s.node_by_name("H3").unique_name,)
    with pytest.raises(ValueError, match="unknown worker group"):
        s.add_node(NodeId("127.0.0.1", 24993), group="nope")


def test_universe_delta_and_apply():
    s = _spec(3)
    peer = _spec(3)
    s.add_node(NodeId("127.0.0.1", 24994, name="J1"))
    s.add_node(NodeId("127.0.0.1", 24995, name="J2"))
    s.remove_node("127.0.0.1:24994")
    d = s.universe_delta(0)
    assert d["e"] == 3 and len(d["log"]) == 3
    assert peer.apply_universe(d)
    assert peer.universe_epoch == 3
    assert peer.node_by_unique_name("127.0.0.1:24995") is not None
    assert peer.node_by_unique_name("127.0.0.1:24994") is None
    # idempotent + partial re-delivery is a no-op
    assert not peer.apply_universe(s.universe_delta(1))
    # out-of-order entry lists apply in epoch order
    peer2 = _spec(3)
    shuffled = {"e": d["e"], "log": list(reversed(d["log"]))}
    assert peer2.apply_universe(shuffled)
    assert peer2.universe_epoch == 3


def test_apply_universe_refuses_forged_and_gapped_entries():
    s = _spec(3)
    # forged: right shape, wrong stamp
    forged = {"e": 1, "log": [{
        "e": 1, "op": "join",
        "node": {"host": "6.6.6.6", "port": 666, "name": "EVIL",
                 "rank": 99},
        "mac": "00" * 32,
    }]}
    assert not s.apply_universe(forged)
    assert s.node_by_unique_name("6.6.6.6:666") is None
    # gap: an entry past epoch+1 stops application (stay behind)
    src = _spec(3)
    src.add_node(NodeId("127.0.0.1", 24996, name="J1"))
    src.add_node(NodeId("127.0.0.1", 24997, name="J2"))
    gapped = {"e": 2, "log": src._universe_log[1:]}  # only entry e=2
    assert not s.apply_universe(gapped)
    assert s.universe_epoch == 0
    # a bounded window catches a far-behind peer up INCREMENTALLY:
    # one entry per exchange still converges
    peer3 = _spec(3)
    assert peer3.apply_universe(src.universe_delta(0, max_entries=1))
    assert peer3.universe_epoch == 1
    assert peer3.apply_universe(src.universe_delta(
        peer3.universe_epoch, max_entries=1))
    assert peer3.universe_epoch == 2
    # only a log that no longer reaches back (front-trimmed past the
    # cap) falls to the FULL form — which rides authenticated reply
    # paths alone
    del src._universe_log[0]
    full = src.universe_delta(0)
    assert "full" in full
    assert not s.apply_universe(full)
    assert s.apply_universe(full, verified=True)
    assert s.universe_epoch == 2
    assert s.node_by_unique_name("127.0.0.1:24997") is not None
    # garbage shapes never throw
    assert not s.apply_universe(None)
    assert not s.apply_universe({"e": "x", "log": "y"})
    assert not s.apply_universe({"e": 9, "log": [{"e": "a"}, 7]})


# ----------------------------------------------------------------------
# groups: the reform ladder + reshape edges
# ----------------------------------------------------------------------


def test_reform_ladder_shapes():
    from dml_tpu.jobs.groups import reform_ladder

    # 4-member dp2×tp2: 3 survivors -> dp3 (tp=2 doesn't divide 3)
    assert reform_ladder(MeshSpec(dp=2, tp=2), 4, 3) == {
        "dp": 3, "tp": 1, "pp": 1}
    # 2 survivors -> keep the tp width (per-chip HBM budget holds)
    assert reform_ladder(MeshSpec(dp=2, tp=2), 4, 2) == {
        "dp": 1, "tp": 2, "pp": 1}
    # pp divisors survive: dp2×tp2×pp2 over 4 members = 2 chips each
    assert reform_ladder(MeshSpec(dp=2, tp=2, pp=2), 4, 3) == {
        "dp": 3, "tp": 2, "pp": 1}
    # fewer than two survivors / not degraded -> no rung
    assert reform_ladder(MeshSpec(dp=1, tp=2), 2, 1) is None
    assert reform_ladder(MeshSpec(dp=2, tp=2), 4, 4) is None


def test_collapse_reforms_to_survivor_mesh():
    g = WorkerGroupSpec("g0", ("H2", "H3", "H4"), MeshSpec(dp=3, tp=1))
    spec = ClusterSpec.localhost(5, worker_groups=[g])
    from dml_tpu.jobs.groups import GroupDirectory

    d = GroupDirectory(spec)
    u = {n.name: n.unique_name for n in spec.nodes}
    pool, w = d.collapse([u["H2"], u["H3"], u["H4"], u["H5"]])
    assert w == {u["H2"]: 3.0}
    assert d.stats()["g0"]["mesh_in_force"] == "full"
    # lose H4: reform to a 2-chip mesh under the SAME primary —
    # NOT the single-chip fallback
    pool, w = d.collapse([u["H2"], u["H3"], u["H5"]])
    assert pool == [u["H2"], u["H5"]]
    assert w == {u["H2"]: 2.0}
    st = d.stats()["g0"]
    assert st["mesh_in_force"] == {"dp": 2, "tp": 1, "pp": 1}
    assert st["reshapes"] == 1
    assert st["active_members"] == [u["H2"], u["H3"]]
    assert d.is_reformed("g0")
    # LM rounds withhold the reformed group (fixed-mesh LM engines)
    pool, w = d.collapse([u["H2"], u["H3"], u["H5"]], lm_active=["lm"])
    assert w == {}
    # losing the PRIMARY is still the single-chip fallback (the
    # group engine lives on it)
    pool, w = d.collapse([u["H3"], u["H4"], u["H5"]])
    assert w == {} and pool == [u["H3"], u["H4"], u["H5"]]
    # everyone back: full again, reform edge counted
    pool, w = d.collapse([u["H2"], u["H3"], u["H4"], u["H5"]])
    assert w == {u["H2"]: 3.0}
    assert d.stats()["g0"]["reforms"] == 1
    assert not d.is_reformed("g0")
    # kill switch restores the pre-elastic single-chip-only behavior
    d.reform_enabled = False
    pool, w = d.collapse([u["H2"], u["H3"], u["H5"]])
    assert w == {}


def test_on_node_failed_requeues_reformed_primary_once():
    g = WorkerGroupSpec("g0", ("H2", "H3", "H4"), MeshSpec(dp=3, tp=1))
    spec = ClusterSpec.localhost(5, worker_groups=[g])
    from dml_tpu.jobs.groups import GroupDirectory

    d = GroupDirectory(spec)
    u = {n.name: n.unique_name for n in spec.nodes}
    d.collapse([u["H2"], u["H3"], u["H4"], u["H5"]])
    # full -> member death: degrade edge + requeue, latched
    assert d.on_node_failed(u["H4"]) == ("g0", u["H2"])
    assert d.on_node_failed(u["H4"]) is None
    # collapse reforms on the survivors; ANOTHER death while reformed
    # must requeue again (that mesh is gone too)
    d.collapse([u["H2"], u["H3"], u["H5"]])
    assert d.on_node_failed(u["H3"]) == ("g0", u["H2"])
    assert d.on_node_failed(u["H3"]) is None


def test_stub_backend_serves_reformed_and_degrades_midbatch():
    from dml_tpu.jobs.groups import GroupDegraded, stub_group_backend

    alive = {"a:1", "a:2", "a:3"}
    be = stub_group_backend(
        "g", ("a:1", "a:2", "a:3"), lambda: alive, per_file_s=0.01)

    async def run():
        # full strength
        results, _, _ = await be("M", ["p1"])
        assert be.capacity == 3.0
        # a member dies: the 2-survivor reform still serves, at
        # reformed capacity — NOT a permanent degradation
        alive.discard("a:3")
        results, _, _ = await be("M", ["p1", "p2"])
        assert set(results) == {"p1", "p2"}
        assert be.capacity == 2.0
        # mid-batch membership change breaks the mesh the batch ran on
        task = asyncio.create_task(be("M", ["p1", "p2"]))
        await asyncio.sleep(0.005)
        alive.discard("a:2")
        with pytest.raises(GroupDegraded):
            await task
        # one live member of a 3-group: no sharded mesh at all
        with pytest.raises(GroupDegraded, match="lost member"):
            await be("M", ["p1"])

    asyncio.run(run())


@pytest.mark.sharded
def test_reformed_mesh_bitwise_equality():
    """The acceptance claim: a group re-formed to a SMALLER dp×tp
    shape after member loss still produces bitwise the single-chip
    outputs — param_gather re-sharding re-groups the same parameter
    tree, it never changes the math."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dml_tpu.jobs.groups import reform_ladder
    from dml_tpu.models.params_io import init_variables
    from dml_tpu.parallel.inference import ShardedInference
    from dml_tpu.parallel.mesh import make_mesh

    from _tinynet import ensure_tinynet

    spec = ensure_tinynet()
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 virtual devices")
    variables = init_variables(spec, seed=0, dtype=jnp.float32)
    imgs = np.random.RandomState(0).randint(
        0, 255, (6, 32, 32, 3), np.uint8)
    one = ShardedInference(
        "TinyNet", make_mesh(MeshSpec(), devices=devs[:1]),
        batch_size=6, variables=variables, dtype=jnp.float32,
    )
    ref = one(imgs)
    full_mesh = MeshSpec(dp=2, tp=2)
    # walk the ladder the way member loss would: 4 -> 3 -> 2 members
    for n_active in (3, 2):
        rung = reform_ladder(full_mesh, 4, n_active)
        assert rung is not None
        mesh = make_mesh(
            MeshSpec(dp=rung["dp"], tp=rung["tp"]),
            devices=devs[: rung["dp"] * rung["tp"]],
        )
        reformed = ShardedInference(
            "TinyNet", mesh, batch_size=6, variables=variables,
            dtype=jnp.float32, param_gather=True,
        )
        np.testing.assert_array_equal(reformed(imgs), ref)


# ----------------------------------------------------------------------
# scheduler: pool-size re-probe trigger
# ----------------------------------------------------------------------


@pytest.mark.adaptive
def test_depth_controller_reprobes_on_pool_change():
    from dml_tpu.jobs.scheduler import DepthController

    t = [0.0]
    ctl = DepthController(probe_batches=2, now=lambda: t[0])
    # drive a full probe cycle to settle
    ctl.tick(ctl.min_probe_backlog)
    for depth in (1, 2):
        for worker in ("w1",):
            ctl.on_ack(8, worker=worker)  # transition discard
        for _ in range(2):
            t[0] += 0.1
            ctl.on_ack(8, worker="w1")
    assert ctl.state == "settled"
    # first observation is bring-up, not drift
    ctl.on_pool_size(3)
    assert ctl.state == "settled"
    # same size: no-op
    ctl.on_pool_size(3)
    assert ctl.state == "settled"
    # a join/leave changed the slot count: re-arm with trigger "pool"
    ctl.on_pool_size(5)
    assert ctl.state == "warmup"
    assert ctl.reprobes == 1
    assert ctl._trigger == "pool"
    assert ctl.explain()["pool_size"] == 5
    # a pool change MID-PROBE aborts the half-measured cycle
    ctl.tick(ctl.min_probe_backlog)
    assert ctl.state == "probing"
    ctl.on_pool_size(4)
    assert ctl.state == "warmup"
    assert ctl.aborted_probes == 1


# ----------------------------------------------------------------------
# membership: graceful retirement
# ----------------------------------------------------------------------


def test_retire_is_immediate_and_tombstoned():
    from dml_tpu.cluster.membership import ALIVE, MembershipList

    spec = ClusterSpec.localhost(3, base_port=24200)
    me = spec.nodes[0]
    ml = MembershipList(spec, me, clock=lambda: 100.0)
    other = spec.nodes[1].unique_name
    ml.merge({other: (99.0, ALIVE)})
    assert ml.is_alive(other)
    fails_before = ml.false_positives
    assert ml.retire(other)
    assert not ml.is_alive(other)
    # stale gossip about the retiree cannot resurrect it
    ml.merge({other: (99.5, ALIVE)})
    assert not ml.is_alive(other)
    # retirement fired no failure accounting
    assert ml.false_positives == fails_before
    assert not ml.retire(other)  # idempotent


def test_prune_unknown_drops_departed_members():
    from dml_tpu.cluster.membership import ALIVE, MembershipList

    spec = ClusterSpec.localhost(3, base_port=24210)
    spec.join_secret = SECRET
    ml = MembershipList(spec, spec.nodes[0], clock=lambda: 100.0)
    j = NodeId("127.0.0.1", 24219, name="J1")
    spec.add_node(j)
    ml.merge({j.unique_name: (99.0, ALIVE)})
    assert ml.is_alive(j.unique_name)
    spec.remove_node(j.unique_name)
    assert ml.prune_unknown() == [j.unique_name]
    assert not ml.is_alive(j.unique_name)
    assert ml.prune_unknown() == []


# ----------------------------------------------------------------------
# node protocol: join / leave / forgery rejection / epoch gossip
# (private per-node specs — nothing rides a shared object)
# ----------------------------------------------------------------------


async def _bring_up(base_port, n=3):
    from dml_tpu.cluster.introducer import IntroducerService
    from dml_tpu.cluster.node import Node

    genesis = _spec(n, base_port=base_port)
    dns = IntroducerService(_copy(genesis))
    await dns.start()
    nodes = []
    for nid in genesis.nodes:
        node = Node(_copy(genesis), nid, seed=1)
        await node.start()
        nodes.append(node)
    await _until(lambda: all(n_.joined and n_.leader_unique
                             for n_ in nodes), what="genesis converge")
    return genesis, dns, nodes


async def _teardown(dns, nodes):
    for n in nodes:
        await n.stop()
    await dns.stop()


def test_authenticated_join_propagates_and_stale_epoch_reclaims():
    from dml_tpu.cluster.node import Node

    async def run():
        genesis, dns, nodes = await _bring_up(24220)
        try:
            # joiner 1: genesis view + itself, admitted at epoch 1
            j1 = NodeId("127.0.0.1", 24230, name="J1")
            s1 = _copy(genesis)
            s1.add_node(j1, local=True)
            n1 = Node(s1, j1, seed=2)
            await n1.start()
            nodes.append(n1)
            await _until(lambda: n1.joined, what="J1 admitted")
            assert s1.universe_epoch == 1
            # every genesis node learns J1 via gossip change entries
            await _until(
                lambda: all(
                    n_.spec.node_by_unique_name(j1.unique_name)
                    for n_ in nodes),
                what="universe propagation",
            )
            # joiner 2 starts from the STALE genesis view (epoch 0)
            # while the cluster is at 1: the authenticated stale_epoch
            # rejection teaches it the current epoch, it re-claims,
            # and the JOIN_ACK catch-up delivers J1's entry
            j2 = NodeId("127.0.0.1", 24231, name="J2")
            s2 = _copy(ClusterSpec.localhost(
                3, base_port=24220, introducer_port=24219, timing=FAST))
            s2.join_secret = SECRET
            s2.add_node(j2, local=True)
            assert s2.universe_epoch == 0
            n2 = Node(s2, j2, seed=3)
            await n2.start()
            nodes.append(n2)
            await _until(lambda: n2.joined, what="J2 admitted via re-claim")
            assert s2.universe_epoch == 2
            assert s2.node_by_unique_name(j1.unique_name) is not None
            await _until(
                lambda: all(
                    any(a.unique_name == j2.unique_name
                        for a in n_.membership.alive_nodes())
                    for n_ in nodes),
                what="J2 alive everywhere",
            )
        finally:
            await _teardown(dns, nodes)

    asyncio.run(run())


def test_graceful_leave_retires_without_false_failure():
    from dml_tpu.cluster.node import Node
    from dml_tpu.observability import METRICS

    async def run():
        genesis, dns, nodes = await _bring_up(24240)
        try:
            j = NodeId("127.0.0.1", 24250, name="J1")
            s = _copy(genesis)
            s.add_node(j, local=True)
            jn = Node(s, j, seed=2)
            await jn.start()
            await _until(lambda: jn.joined, what="join")
            await _until(
                lambda: all(
                    any(a.unique_name == j.unique_name
                        for a in n_.membership.alive_nodes())
                    for n_ in nodes),
                what="joiner alive everywhere",
            )
            failures_before = METRICS.snapshot()["counters"].get(
                "cluster_node_failures_total", 0.0)
            leaves_before = _counter("membership_leaves_total")
            assert await jn.leave_cluster()
            # retired from EVERY genesis node's view + universe — with
            # no suspicion window and no failure counter movement
            await _until(
                lambda: all(
                    not any(a.unique_name == j.unique_name
                            for a in n_.membership.alive_nodes())
                    and n_.spec.node_by_unique_name(j.unique_name)
                    is None
                    for n_ in nodes),
                what="graceful retirement everywhere",
            )
            assert all(n_.spec.universe_epoch == 2 for n_ in nodes)
            assert _counter("membership_leaves_total") == leaves_before + 1
            assert METRICS.snapshot()["counters"].get(
                "cluster_node_failures_total", 0.0) == failures_before
            await jn.stop()
        finally:
            await _teardown(dns, nodes)

    asyncio.run(run())


def test_forged_joins_rejected_and_counted():
    async def run():
        genesis, dns, nodes = await _bring_up(24260)
        try:
            from dml_tpu.cluster.wire import Message, MsgType

            leader = next(n for n in nodes if n.is_leader)
            laddr = (leader.me.host, leader.me.port)

            def c(reason):
                from dml_tpu.observability import METRICS

                return METRICS.snapshot()["counters"].get(
                    f"membership_join_rejected_total{{reason={reason}}}",
                    0.0)

            base = {r: c(r) for r in
                    ("bad_mac", "garbled", "stale_epoch", "replay")}
            phantom = {"host": "127.0.0.1", "port": 39998,
                       "name": "EVIL", "rank": 99}
            sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            try:
                sock.sendto(Message(
                    "127.0.0.1:39998", MsgType.JOIN_REQUEST,
                    {"node": phantom, "nonce": "x1", "epoch": 0,
                     "mac": "00" * 32}).pack(), laddr)
                sock.sendto(Message(
                    "127.0.0.1:39998", MsgType.JOIN_REQUEST,
                    {"node": "garbage", "nonce": 3, "epoch": "x",
                     "mac": None}).pack(), laddr)
                sock.sendto(Message(
                    "127.0.0.1:39998", MsgType.JOIN_REQUEST,
                    {"node": phantom, "nonce": "x2", "epoch": 9,
                     "mac": join_mac(SECRET, phantom, "x2", 9)}).pack(),
                    laddr)
                known = nodes[-1].me
                kd = {"host": known.host, "port": known.port,
                      "name": known.name, "rank": known.rank}
                frame = Message(
                    known.unique_name, MsgType.JOIN_REQUEST,
                    {"node": kd, "nonce": "x3", "epoch": 0,
                     "mac": join_mac(SECRET, kd, "x3", 0)}).pack()
                sock.sendto(frame, laddr)
                sock.sendto(frame, laddr)
            finally:
                sock.close()
            await _until(
                lambda: all(c(r) > base[r] for r in base),
                what="all four rejection reasons counted",
            )
            # no phantom entered any table or any alive view
            for n_ in nodes:
                assert n_.spec.node_by_unique_name(
                    "127.0.0.1:39998") is None
                assert not any(
                    a.unique_name == "127.0.0.1:39998"
                    for a in n_.membership.alive_nodes())
            assert leader.spec.universe_epoch == 0
        finally:
            await _teardown(dns, nodes)

    asyncio.run(run())


def test_introducer_learns_joined_nodes():
    """The DNS must accept a runtime joiner as leader: the
    UPDATE_INTRODUCER universe piggyback teaches it the table (with
    per-entry MAC verification — a forged update teaches nothing)."""
    from dml_tpu.cluster.introducer import IntroducerService
    from dml_tpu.cluster.wire import Message, MsgType

    async def run():
        spec = _spec(2, base_port=24280)
        dns = IntroducerService(_copy(spec))
        await dns.start()
        try:
            src = _copy(spec)
            j = NodeId("127.0.0.1", 24290, name="J1")
            src.add_node(j)
            uni = src.universe_delta(0)
            sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            try:
                # forged entries (bad stamp) teach the DNS nothing
                bad = {"e": 1, "log": [dict(uni["log"][0], mac="00")]}
                sock.sendto(Message(
                    spec.nodes[0].unique_name, MsgType.UPDATE_INTRODUCER,
                    {"introducer": j.unique_name, "uni": bad}).pack(),
                    (dns.me.host, dns.me.port))
                await asyncio.sleep(0.2)
                assert dns.current_introducer != j.unique_name
                # the genuine stamped entry admits the joiner as a
                # valid introducer target
                sock.sendto(Message(
                    spec.nodes[0].unique_name, MsgType.UPDATE_INTRODUCER,
                    {"introducer": j.unique_name, "uni": uni}).pack(),
                    (dns.me.host, dns.me.port))
                await _until(
                    lambda: dns.current_introducer == j.unique_name,
                    what="DNS accepting the runtime joiner as leader",
                )
            finally:
                sock.close()
        finally:
            await dns.stop()

    asyncio.run(run())


# ----------------------------------------------------------------------
# chaos: scenario family, JOIN forgeries, LocalCluster scale verbs
# ----------------------------------------------------------------------


def test_elastic_scenario_plan_determinism():
    from dml_tpu.cluster.chaos import (
        SCENARIO_FAMILIES, ChaosPlan, scenario_plan,
    )

    assert "elastic" in SCENARIO_FAMILIES
    a = scenario_plan("elastic", 5)
    b = scenario_plan("elastic", 5)
    assert a == b
    assert a != scenario_plan("elastic", 6)
    kinds = {e.kind for e in a.events}
    assert {"scale_out", "scale_in", "join_storm", "job"} <= kinds
    assert a.join_secret
    # JSON round-trip keeps the policy + schedule
    rt = ChaosPlan.from_dict(json.loads(json.dumps(a.to_dict())))
    assert rt == a


def test_fuzz_join_forgeries_contract():
    from dml_tpu.cluster.chaos import fuzz_datagrams
    from dml_tpu.cluster.wire import Message, MsgType

    senders = ("127.0.0.1:24301", "127.0.0.1:24302")
    malformed, byz = fuzz_datagrams(
        3, 40, senders, join_secret=SECRET, universe_epoch=2,
        kinds=("join_bad_mac", "join_garbled", "join_stale",
               "join_replay"),
    )
    assert not malformed  # join forgeries all parse
    assert byz
    saw_stale_valid = saw_replay_pair = False
    seen = []
    for frame in byz:
        msg = Message.unpack(frame)
        assert msg is not None and msg.type == MsgType.JOIN_REQUEST
        d = msg.data
        if d.get("epoch") == 1 and isinstance(d.get("node"), dict):
            # stale frame: the MAC must be VALID for its (old) epoch,
            # so it reaches — and dies at — the epoch check
            if d.get("mac") == join_mac(
                SECRET, d["node"], d["nonce"], 1
            ):
                saw_stale_valid = True
        if frame in seen:
            saw_replay_pair = True
        seen.append(frame)
    assert saw_stale_valid
    assert saw_replay_pair
    # replay frames only target EXISTING members (a valid-MAC join of
    # a brand-new identity would be an admission, not a forgery)
    for frame in byz:
        d = Message.unpack(frame).data
        node = d.get("node")
        if isinstance(node, dict) and d.get("epoch") == 2 \
                and isinstance(d.get("mac"), str) \
                and d["mac"] == join_mac(SECRET, node, d["nonce"], 2):
            assert f"{node['host']}:{node['port']}" in senders


@pytest.mark.chaos
def test_cluster_scale_out_in_and_storm(tmp_path):
    """Tier-1-speed elastic smoke on the product LocalCluster: a
    brand-new node joins mid-job and takes a pool slot, a forged-join
    storm moves the rejection counters without admitting a phantom,
    the joiner leaves gracefully, and the invariant sweep ends green."""
    from dml_tpu.cluster.chaos import (
        LocalCluster, invariant_sweep, STUB_MODEL,
    )

    async def run():
        import os as _os
        import shutil as _sh

        root = str(tmp_path / "elastic_smoke")
        _sh.rmtree(root, ignore_errors=True)
        _os.makedirs(root)
        cluster = LocalCluster(4, root, 24310, timing=FAST,
                               join_secret=SECRET)
        try:
            await cluster.start()
            await cluster.wait_for(cluster.converged, 15.0, "converge")
            client = cluster.client()
            for i in range(3):
                p = str(tmp_path / f"img_{i}.jpeg")
                with open(p, "wb") as f:
                    f.write(b"\xff\xd8fake" + bytes([i]))
                await client.store.put(p, f"img_{i}.jpeg")
                cluster.expect_files.add(f"img_{i}.jpeg")
            leader = next(sn for sn in cluster.nodes.values()
                          if sn.node.is_leader)
            pool_before = len(leader.jobs.worker_pool())
            # a job in flight while capacity joins
            job = asyncio.create_task(
                client.jobs.submit_job(STUB_MODEL, 24, timeout=10.0))
            sn = await cluster.scale_out()
            jid = await job
            done = await client.jobs.wait_job(jid, timeout=60.0)
            assert int(done["total_queries"]) == 24
            await cluster.wait_for(
                lambda: len(leader.jobs.worker_pool()) > pool_before,
                10.0, "joiner taking a pool slot",
            )
            # forged storm: counters move, no phantom
            from dml_tpu.cluster.chaos import (
                _join_rejected_total, fuzz_datagrams,
            )

            base = _join_rejected_total()
            _, frames = fuzz_datagrams(
                9, 16, tuple(sorted(cluster.nodes)),
                join_secret=SECRET,
                universe_epoch=cluster.spec.universe_epoch,
                kinds=("join_bad_mac", "join_garbled", "join_stale",
                       "join_replay"),
            )
            lid = cluster.spec.node_by_unique_name(
                cluster.leader_uname())
            sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            try:
                for fr in frames:
                    sock.sendto(fr, (lid.host, lid.port))
            finally:
                sock.close()
            await cluster.wait_for(
                lambda: _join_rejected_total() > base, 5.0,
                "storm rejections counted",
            )
            # graceful scale-in of the joiner
            assert await cluster.scale_in(sn.node.me.unique_name)
            report = await invariant_sweep(
                cluster, {}, {},
                forged_joins_sent=len(frames),
                join_reject_baseline=base,
            )
            assert report.ok, report.failures
        finally:
            await cluster.stop()

    asyncio.run(run())


@pytest.mark.chaos
def test_scale_out_absorbs_into_under_formed_group(tmp_path):
    """A joiner asking for a worker group is absorbed into its member
    list: an under-formed group (a member died) regains collapsed
    strength through the reform ladder with the joiner on board."""
    from dml_tpu.cluster.chaos import LocalCluster

    async def run():
        import os as _os
        import shutil as _sh

        root = str(tmp_path / "absorb")
        _sh.rmtree(root, ignore_errors=True)
        _os.makedirs(root)
        group = WorkerGroupSpec("g0", ("H3", "H4"), MeshSpec(dp=2, tp=1))
        cluster = LocalCluster(4, root, 24340, timing=FAST,
                               join_secret=SECRET,
                               worker_groups=[group])
        try:
            await cluster.start()
            await cluster.wait_for(cluster.converged, 15.0, "converge")
            sn = await cluster.scale_out(group="g0")
            uname = sn.node.me.unique_name
            await cluster.wait_for(
                lambda: uname in cluster.spec.group_members_unique("g0"),
                10.0, "absorption into g0",
            )
            # the joiner's OWN private spec agrees (JOIN_ACK catch-up)
            assert uname in sn.node.spec.group_members_unique("g0")
            leader = next(s for s in cluster.nodes.values()
                          if s.node.is_leader)
            # collapse sees a 3-member group; kill one original
            # member: survivors (incl. the joiner) reform rather than
            # falling to single chips
            await cluster.wait_for(
                lambda: leader.jobs.group_stats()
                .get("g0", {}).get("mesh_in_force") == "full",
                10.0, "3-member group fully formed",
            )
            await cluster.crash_node(
                cluster.spec.node_by_name("H4").unique_name)
            await cluster.wait_for(
                lambda: isinstance(
                    leader.jobs.group_stats()
                    .get("g0", {}).get("mesh_in_force"), dict),
                10.0, "reform onto survivors incl. the joiner",
            )
            st = leader.jobs.group_stats()["g0"]
            assert uname in st["active_members"]
        finally:
            await cluster.stop()

    asyncio.run(run())


@pytest.mark.slow
@pytest.mark.chaos
def test_elastic_scenario_sweeps_green():
    from dml_tpu.cluster.chaos import run_plan_sync, scenario_plan

    rep = run_plan_sync(scenario_plan("elastic", 1), base_port=24370)
    assert rep.ok, rep.invariants.failures
    kinds = {r["kind"] for r in rep.executed if "resolved" in r
             or "injected" in r}
    assert {"scale_out", "scale_in", "join_storm"} <= kinds
    assert rep.invariants.checks.get("forged_joins", {}).get(
        "rejected", 0) > 0
