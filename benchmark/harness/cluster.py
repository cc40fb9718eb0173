"""The in-process cluster a cell is served by, and the compile meter.

`serving_cluster` and `CompileMeter` are copied from `chip_smoke.py`
(`smoke_cluster`, `CompileMeter`; sound there, PERF.md inventory): a
4-node localhost `LocalCluster` with the scaled-down SWIM timing and a
`RequestRouter` on every node, in ONE process that owns the chip.
"""

from __future__ import annotations

import contextlib
import socket
from typing import Any, Dict

N_NODES = 4
_DATA_PORT_OFFSET = 10_000  # the store's TCP data plane sits this far up


class CompileMeter:
    """Seconds JAX spent compiling (or fetching from the persistent
    cache) and cache hits/misses, from JAX's own monitoring events."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        # one (function name, seconds) per backend compile or cache load
        self.compiled = []
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event: str, secs: float, **kw: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            name = str(kw.get("fun_name", "?"))
            if name.startswith("jit(") and name.endswith(")"):
                name = name[4:-1]
            self.compiled.append((name, secs))

    def _on_event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> Dict[str, Any]:
        return {"compile_s": self.seconds, "compiles": len(self.compiled),
                "cache_hits": self.hits, "cache_misses": self.misses}


def free_base_port(start: int = 21001, tries: int = 200) -> int:
    """A base port whose block (introducer at base - 1, nodes at base ..
    base + 3 on UDP, their data planes 10,000 above on TCP) is free now,
    so that a run never collides with a leftover of another."""
    for k in range(tries):
        base = start + 16 * k
        held = []
        try:
            for port in range(base - 1, base + N_NODES):
                s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
                held.append(s)
                s.bind(("127.0.0.1", port))
            for port in range(base, base + N_NODES):
                s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
                held.append(s)
                s.bind(("127.0.0.1", port + _DATA_PORT_OFFSET))
            return base
        except OSError:
            continue
        finally:
            for s in held:
                s.close()
    raise RuntimeError("no free block of localhost ports for the cluster")


@contextlib.asynccontextmanager
async def serving_cluster(root: str, make_jobs, slo_classes=None):
    """`slo_classes` (name -> SLOClass fields, from the traffic file) are
    added to the router's class table as an operator would add them."""
    from dml_tpu.cluster.chaos import LocalCluster
    from dml_tpu.config import Timing
    from dml_tpu.ingress.slo import DEFAULT_CLASSES, SLOClass

    classes = None
    if slo_classes:
        classes = {**DEFAULT_CLASSES, **{
            name: SLOClass(name, **fields)
            for name, fields in slo_classes.items()}}
    base_port = free_base_port()
    cluster = LocalCluster(
        N_NODES, root, base_port,
        timing=Timing(ping_interval=0.2, ack_timeout=0.3,
                      cleanup_time=1.0, leader_rpc_timeout=10.0),
        make_jobs=make_jobs, with_ingress=True, ingress_classes=classes,
    )
    try:
        await cluster.start()
        await cluster.wait_for(
            cluster.converged, 20.0,
            f"cluster convergence on ports {base_port - 1}-"
            f"{base_port + N_NODES - 1}",
        )
        yield cluster
    finally:
        await cluster.stop()


def leader_of(cluster):
    for sn in cluster.nodes.values():
        if sn.node.is_leader:
            return sn
    raise RuntimeError("the cluster has no leader")
