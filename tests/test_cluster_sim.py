"""In-process multi-node cluster simulation on localhost UDP ports.

The reference was tested by hand on 10 VMs, with a commented-out
localhost node table as its only local mode (config.py:41-50,
README.md:16-25). Here that pattern is a first-class automated test:
introducer + N nodes in one event loop, real UDP datagrams + TCP data
plane, aggressive timing so joins/failures/elections resolve in
hundreds of milliseconds.

Covers the reference call stacks of SURVEY §3.1 (join), §3.2 (failure
detection), §3.3 (put), §3.5 (leader failover).
"""

import asyncio
import contextlib
import os

import pytest

from dml_tpu.config import ClusterSpec, StoreConfig, Timing
from dml_tpu.cluster.introducer import IntroducerService
from dml_tpu.cluster.node import Node
from dml_tpu.cluster.store_service import StoreService

FAST = Timing(
    ping_interval=0.05,
    ack_timeout=0.15,
    cleanup_time=0.3,
    missed_acks_to_suspect=2,
    leader_rpc_timeout=5.0,
)


class Sim:
    """A running localhost cluster: introducer + nodes + stores."""

    def __init__(self, spec: ClusterSpec, tmp_path):
        self.spec = spec
        self.tmp_path = tmp_path
        self.dns = IntroducerService(spec)
        self.nodes = {}
        self.stores = {}

    async def start_node(self, node_id):
        node = Node(self.spec, node_id)
        store = StoreService(
            node, root=str(self.tmp_path / f"store_{node_id.port}")
        )
        await node.start()
        await store.start()
        self.nodes[node_id.unique_name] = node
        self.stores[node_id.unique_name] = store
        return node, store

    async def start_all(self):
        await self.dns.start()
        for n in self.spec.nodes:
            await self.start_node(n)

    async def stop_node(self, unique_name):
        node = self.nodes.pop(unique_name)
        store = self.stores.pop(unique_name)
        await store.stop()
        await node.stop()

    async def stop_all(self):
        for uname in list(self.nodes):
            await self.stop_node(uname)
        await self.dns.stop()

    async def wait_for(self, cond, timeout=10.0, what="condition"):
        deadline = asyncio.get_running_loop().time() + timeout
        while asyncio.get_running_loop().time() < deadline:
            if cond():
                return
            await asyncio.sleep(0.05)
        raise AssertionError(f"timed out waiting for {what}")

    async def wait_converged(self, expect_leader=None, timeout=10.0):
        n = len(self.nodes)

        def ok():
            for node in self.nodes.values():
                if not node.joined or node.leader_unique is None:
                    return False
                if len(node.membership.alive_nodes()) != n:
                    return False
                if expect_leader and node.leader_unique != expect_leader:
                    return False
            return True

        await self.wait_for(ok, timeout, f"membership convergence of {n} nodes")

    def leader_store(self) -> StoreService:
        any_node = next(iter(self.nodes.values()))
        return self.stores[any_node.leader_unique]

    def by_unique(self, name: str) -> str:
        return self.spec.node_by_name(name).unique_name

    def partition(self, *groups):
        """Bidirectional CONTROL-PLANE partition: UDP datagrams
        between groups are dropped (the introducer DNS stays
        reachable — it is a rendezvous, not a router). Scope: the TCP
        data plane is NOT gated — membership/election/metadata all
        ride UDP, which is what these scenarios exercise; a test that
        must forbid cross-partition file transfer needs its own data-
        plane gate."""
        port_group = {}
        for gi, names in enumerate(groups):
            for uname in names:
                port_group[self.nodes[uname].me.port] = gi
        for uname, node in self.nodes.items():
            mine = port_group.get(node.me.port)

            def blocked(addr, mine=mine):
                other = port_group.get(addr[1])
                return other is not None and other != mine

            node.transport.partition_filter = blocked

    def heal(self):
        for node in self.nodes.values():
            node.transport.partition_filter = None


# Fixed base ports below 21000 or above 21500: the benchmark's CPU
# rehearsals (`benchmark/harness/cluster.free_base_port`) take the first
# free block of 21001 + 16 k, and walk upward while the last blocks' TCP
# ports sit in TIME_WAIT: under xdist a rehearsal in another worker held
# 21304-21308 whenever the 21300 test started (PR 37: three runs of three).
@contextlib.asynccontextmanager
async def cluster(n, tmp_path, base_port):
    spec = ClusterSpec.localhost(
        n,
        base_port=base_port,
        introducer_port=base_port - 1,
        timing=FAST,
        store=StoreConfig(root=str(tmp_path / "roots")),
    )
    sim = Sim(spec, tmp_path)
    try:
        await sim.start_all()
        yield sim
    finally:
        await sim.stop_all()


async def test_join_and_membership(tmp_path):
    async with cluster(4, tmp_path, 20100) as sim:
        # H1 has the highest rank -> initial leader per the DNS default
        h1 = sim.spec.node_by_name("H1")
        await sim.wait_converged(expect_leader=h1.unique_name)
        for node in sim.nodes.values():
            assert node.leader_unique == h1.unique_name
            assert len(node.membership.alive_nodes()) == 4


async def test_put_get_ls_delete(tmp_path):
    async with cluster(4, tmp_path, 20200) as sim:
        await sim.wait_converged()
        src = tmp_path / "hello.txt"
        src.write_bytes(b"hello sdfs")
        client = sim.stores[sim.spec.node_by_name("H4").unique_name]

        r = await client.put(str(src), "hello.txt")
        assert r["ok"] and r["version"] == 1
        assert len(r["replicas"]) == 4  # replication_factor capped by n

        # second put -> version 2
        src.write_bytes(b"hello again")
        r2 = await client.put(str(src), "hello.txt")
        assert r2["version"] == 2

        dst = tmp_path / "out.txt"
        got = await client.get("hello.txt", str(dst))
        assert got == 2 and dst.read_bytes() == b"hello again"
        got1 = await client.get("hello.txt", str(dst), version=1)
        assert got1 == 1 and dst.read_bytes() == b"hello sdfs"

        # get-versions concatenates both
        multi = tmp_path / "versions.txt"
        vs = await client.get_versions("hello.txt", 5, str(multi))
        assert vs == [1, 2]
        blob = multi.read_bytes()
        assert b"hello sdfs" in blob and b"hello again" in blob

        replicas = await client.ls("hello.txt")
        assert len(replicas) == 4
        listing = await client.ls_all("*.txt")
        assert listing == {"hello.txt": [1, 2]}

        r3 = await client.delete("hello.txt")
        assert r3["ok"]
        assert await client.ls_all("*") == {}
        for store in sim.stores.values():
            assert store.local_files() == {}


async def test_node_failure_rereplication(tmp_path):
    async with cluster(5, tmp_path, 20300) as sim:
        await sim.wait_converged()
        src = tmp_path / "data.bin"
        src.write_bytes(os.urandom(4096))
        leader = sim.leader_store()
        client = sim.stores[sim.spec.node_by_name("H5").unique_name]
        r = await client.put(str(src), "data.bin")
        holders = set(r["replicas"])
        assert len(holders) == 4

        # kill one replica holder that is not the leader or the client
        victim = next(
            h
            for h in holders
            if h != leader.node.me.unique_name
            and h != client.node.me.unique_name
        )
        await sim.stop_node(victim)

        # the leader must detect the death and restore 4 live replicas
        def repaired():
            reps = [
                rr
                for rr in leader.metadata.replicas_of("data.bin")
                if rr in sim.stores
            ]
            return victim not in leader.metadata.files and len(reps) == 4

        await sim.wait_for(repaired, timeout=15.0, what="re-replication to 4 copies")

        # and the file is still fetchable
        dst = tmp_path / "back.bin"
        await client.get("data.bin", str(dst))
        assert dst.read_bytes() == src.read_bytes()


async def test_leader_failover(tmp_path):
    async with cluster(4, tmp_path, 20400) as sim:
        h1 = sim.spec.node_by_name("H1")
        h2 = sim.spec.node_by_name("H2")
        await sim.wait_converged(expect_leader=h1.unique_name)

        src = tmp_path / "f.txt"
        src.write_bytes(b"survives failover")
        client = sim.stores[sim.spec.node_by_name("H3").unique_name]
        await client.put(str(src), "f.txt")

        await sim.stop_node(h1.unique_name)

        # bully election: H2 (next-highest rank) must win and every
        # survivor must agree (reference hardcodes this winner;
        # we compute it, SURVEY §7 quirk #1)
        await sim.wait_converged(expect_leader=h2.unique_name, timeout=20.0)

        # the new leader rebuilt the global file table from
        # COORDINATE_ACK inventories and serves requests
        listing = await client.ls_all("f.txt")
        assert "f.txt" in listing
        dst = tmp_path / "f_back.txt"
        await client.get("f.txt", str(dst))
        assert dst.read_bytes() == b"survives failover"

        # the introducer DNS now points at the new leader
        assert sim.dns.current_introducer == h2.unique_name


async def test_put_retry_across_failover_is_idempotent(tmp_path):
    """A client PUT retry crossing a leader failover must NOT mint a
    duplicate version: the resolved idempotency token is relayed to
    the standby, which answers the retry from the recorded outcome
    (round-1 documented this window as open; now closed)."""
    from dml_tpu.cluster.store_service import data_addr
    from dml_tpu.cluster.wire import MsgType

    async with cluster(4, tmp_path, 21700) as sim:
        h1 = sim.spec.node_by_name("H1")
        await sim.wait_converged(expect_leader=h1.unique_name)
        client_u = sim.spec.node_by_name("H4").unique_name
        cstore = sim.stores[client_u]
        cnode = sim.nodes[client_u]

        src = tmp_path / "idem.txt"
        src.write_bytes(b"exactly once")
        # PUT through the normal client path but with a hand-held
        # token, so the post-failover retry can reuse it exactly
        token = cstore.data_plane.expose(str(src))
        reply = await cnode.leader_request(
            MsgType.PUT_REQUEST,
            {
                "file": "idem.txt",
                "token": token,
                "data_addr": list(data_addr(cnode.me)),
            },
            timeout=10.0,
        )
        assert reply["ok"] and reply["version"] == 1

        standby_u = sim.stores[h1.unique_name].standby_node().unique_name
        sb_store = sim.stores[standby_u]
        await sim.wait_for(
            lambda: token in sb_store._put_tokens,
            what="idempotency token relayed to standby",
        )

        await sim.stop_node(h1.unique_name)
        await sim.wait_for(
            lambda: all(
                n.leader_unique == standby_u for n in sim.nodes.values()
            ),
            what="failover to standby",
        )
        # the client's reply datagram "was lost": it retries the same
        # PUT (same token) against the new leader
        retry = await cnode.leader_request(
            MsgType.PUT_REQUEST,
            {
                "file": "idem.txt",
                "token": token,
                "data_addr": list(data_addr(cnode.me)),
            },
            timeout=10.0,
        )
        cstore.data_plane.unexpose(token)
        assert retry["ok"] and retry["version"] == 1  # SAME version
        files = await cstore.ls_all("idem.txt")
        assert files["idem.txt"] == [1]  # exactly one version exists


async def test_delete_retry_across_failover_converges(tmp_path):
    """A DELETE retry crossing a failover converges to success (the
    completed-delete marker is relayed), not 'file not found'."""
    from dml_tpu.cluster.wire import MsgType

    async with cluster(4, tmp_path, 21800) as sim:
        h1 = sim.spec.node_by_name("H1")
        await sim.wait_converged(expect_leader=h1.unique_name)
        client_u = sim.spec.node_by_name("H4").unique_name
        cstore = sim.stores[client_u]
        cnode = sim.nodes[client_u]

        src = tmp_path / "gone.txt"
        src.write_bytes(b"bye")
        await cstore.put(str(src), "gone.txt")
        await cstore.delete("gone.txt")

        standby_u = sim.stores[h1.unique_name].standby_node().unique_name
        sb_store = sim.stores[standby_u]
        await sim.wait_for(
            lambda: "gone.txt" in sb_store._recent_deletes,
            what="delete marker relayed to standby",
        )
        await sim.stop_node(h1.unique_name)
        await sim.wait_for(
            lambda: all(
                n.leader_unique == standby_u for n in sim.nodes.values()
            ),
            what="failover to standby",
        )
        retry = await cnode.leader_request(
            MsgType.DELETE_FILE_REQUEST, {"file": "gone.txt"}, timeout=10.0
        )
        assert retry["ok"], retry  # success, not "file not found"


async def test_voluntary_leave_rejoin(tmp_path):
    async with cluster(3, tmp_path, 20500) as sim:
        await sim.wait_converged()
        h3 = sim.spec.node_by_name("H3")
        node = sim.nodes[h3.unique_name]
        node.leave()

        def others_dropped():
            return all(
                len(n.membership.alive_nodes()) == 2
                for u, n in sim.nodes.items()
                if u != h3.unique_name
            )

        await sim.wait_for(others_dropped, timeout=15.0, what="leave detected")

        node.rejoin()
        await sim.wait_converged(timeout=15.0)


async def test_partition_heal_reconverges_single_leader(tmp_path):
    """A network partition splits the cluster into two working halves
    (each elects/keeps a leader — availability); when the network
    heals, the anti-entropy probe re-establishes contact, the
    piggybacked leader fields expose the disagreement, and a fresh
    bully election converges EVERY node on one leader with a rebuilt
    global file table. (The reference has no partition story at all:
    a cleaned node could only ever return via a manual re-join.)"""
    async with cluster(5, tmp_path, 21900) as sim:
        h1 = sim.spec.node_by_name("H1")
        await sim.wait_converged(expect_leader=h1.unique_name)
        src = tmp_path / "p.txt"
        src.write_bytes(b"survives partitions")
        client = sim.stores[sim.spec.node_by_name("H5").unique_name]
        await client.put(str(src), "p.txt")

        minority = [sim.by_unique(n) for n in ("H1", "H2")]
        majority = [sim.by_unique(n) for n in ("H3", "H4", "H5")]
        sim.partition(minority, majority)

        # majority side: H1 unreachable -> cleanup -> elects H3 (its
        # highest rank); minority keeps H1
        await sim.wait_for(
            lambda: all(
                sim.nodes[u].leader_unique == sim.by_unique("H3")
                for u in majority
            ),
            timeout=20.0,
            what="majority elects its own leader",
        )
        assert all(
            sim.nodes[u].leader_unique == h1.unique_name for u in minority
        )
        # both sides remain AVAILABLE: each serves a put
        maj_file = tmp_path / "maj.txt"
        maj_file.write_bytes(b"majority side")
        r = await sim.stores[majority[2]].put(str(maj_file), "maj.txt")
        assert r["ok"]

        sim.heal()
        # anti-entropy probes re-establish contact; leader conflict
        # triggers a re-election; H1 (global rank winner) retakes
        await sim.wait_converged(expect_leader=h1.unique_name, timeout=30.0)
        # the rebuilt global table serves BOTH sides' files everywhere
        for uname, store in sim.stores.items():
            dst = tmp_path / f"got_{store.node.me.port}.txt"
            await store.get("p.txt", str(dst))
            assert dst.read_bytes() == b"survives partitions", uname
        dst = tmp_path / "got_maj.txt"
        await sim.stores[minority[0]].get("maj.txt", str(dst))
        assert dst.read_bytes() == b"majority side"


async def test_false_positive_cleanup_self_heals(tmp_path):
    """A node wrongly cleaned up (e.g. a long GC pause) used to be
    gone forever unless it manually re-joined; the anti-entropy probe
    rediscovers it."""
    async with cluster(4, tmp_path, 22000) as sim:
        await sim.wait_converged()
        victim_u = sim.by_unique("H4")
        victim = sim.nodes[victim_u]
        # simulate a pause: victim can't talk to anyone, then recovers
        sim.partition([victim_u],
                      [sim.by_unique(n) for n in ("H1", "H2", "H3")])
        await sim.wait_for(
            lambda: all(
                victim_u not in {
                    n.unique_name
                    for n in sim.nodes[u].membership.alive_nodes()
                }
                for u in (sim.by_unique("H1"), sim.by_unique("H2"),
                          sim.by_unique("H3"))
            ),
            timeout=20.0,
            what="victim cleaned up by ALL the others",
        )
        sim.heal()
        await sim.wait_converged(timeout=30.0)
        assert victim.joined


async def test_metrics_pull_leader_aggregation(tmp_path):
    """Leader-side METRICS_PULL aggregation (the TPU-native analog of
    the reference coordinator's C1-C5 console): every node answers
    with its registry snapshot, the merge yields one cluster view, and
    the summary carries the paper's per-model stats — query count,
    trailing rate, latency mean + p50/p95/p99 (PAPER C1/C2)."""
    from dml_tpu.jobs.service import JobService
    from dml_tpu.observability import hist_quantile

    async def backend(model, paths):
        await asyncio.sleep(0.002)
        results = {p: [{"label": model, "score": 1.0}] for p in paths}
        return results, 0.002 * max(1, len(paths)), None

    async with cluster(3, tmp_path, 22050) as sim:
        jobs = {}
        try:
            for u, node in sim.nodes.items():
                jobs[u] = JobService(node, sim.stores[u],
                                     infer_backend=backend)
                await jobs[u].start()
            await sim.wait_converged()
            leader_u = next(iter(sim.nodes.values())).leader_unique
            client_u = next(u for u in sim.nodes if u != leader_u)
            for i in range(3):
                p = tmp_path / f"img_{i}.jpeg"
                p.write_bytes(b"\xff\xd8fakejpeg" + bytes([i]))
                await sim.stores[client_u].put(str(p), f"img_{i}.jpeg")
            job_id = await jobs[client_u].submit_job("ResNet50", 8)
            await jobs[client_u].wait_job(job_id, timeout=15.0)

            view = await sim.nodes[leader_u].pull_cluster_metrics()
            # one snapshot per alive node, keyed by unique name
            assert set(view["nodes"]) == set(sim.nodes)
            for snap in view["nodes"].values():
                assert snap["v"] == 1 and "counters" in snap
            # in-process sim: all three nodes share ONE registry, so
            # the dedupe-by-process merge counts it once (a real
            # deployment is one process per node and sums normally)
            assert view["cluster"]["merged_from"] == 1

            summary = view["summary"]
            # C1: per-model query count + trailing rate gauge
            assert summary["counters"][
                "jobs_queries_total{model=ResNet50}"] >= 8
            assert "jobs_query_rate_per_s{model=ResNet50}" in summary["gauges"]
            # C2: per-model latency histogram -> count/mean/percentiles
            lat = summary["histograms"][
                "jobs_query_latency_seconds{model=ResNet50}"]
            assert lat["count"] >= 1
            for stat in ("mean", "p50", "p95", "p99"):
                assert lat[stat] is not None and lat[stat] > 0, stat
            assert lat["p50"] <= lat["p99"]
            # the merged (un-summarized) view keeps raw buckets, so
            # any quantile stays computable cluster-wide
            raw = view["cluster"]["histograms"][
                "jobs_query_latency_seconds{model=ResNet50}"]
            assert hist_quantile(raw, 0.5) == pytest.approx(
                lat["p50"], rel=1e-6)
            # control-plane accounting saw this test's real datagrams
            assert any(
                k.startswith("transport_packets_sent_total") and v > 0
                for k, v in summary["counters"].items()
            )
            # worker-side stage histograms populated by the batch
            assert any(
                k.startswith("worker_infer_seconds")
                for k in summary["histograms"]
            )
        finally:
            for j in jobs.values():
                await j.stop()


async def test_join_repairs_under_replication(tmp_path):
    """A file PUT while the cluster is smaller than the replication
    factor gains copies when nodes JOIN (the reference repairs only on
    deaths, worker.py:1308-1321, so early files stay thin forever)."""
    spec = ClusterSpec.localhost(
        4, base_port=21900, introducer_port=21899, timing=FAST,
        store=StoreConfig(root=str(tmp_path / "roots")),
    )
    sim = Sim(spec, tmp_path)
    try:
        await sim.dns.start()
        first = spec.nodes[0]
        await sim.start_node(first)
        await sim.wait_for(
            lambda: sim.nodes[first.unique_name].is_leader, what="solo leader"
        )
        u1 = first.unique_name
        # PUT with only one node up: 1 replica
        p = tmp_path / "thin.bin"
        p.write_bytes(b"thin-file-data")
        store = sim.stores[u1]
        r = await store.put(str(p), "thin.bin")
        assert len(r["replicas"]) == 1

        # the rest join; repair must bring the file to factor copies
        for n in spec.nodes[1:]:
            await sim.start_node(n)
        want = min(spec.store.replication_factor, 4)
        await sim.wait_for(
            lambda: len(store.metadata.replicas_of("thin.bin")) >= want,
            timeout=15.0, what="join-time re-replication",
        )
    finally:
        await sim.stop_all()
