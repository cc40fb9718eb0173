"""One cell, one run: set-up, the measured window, the drain, the
comparison with the plain reference, the result.

Everything that belongs to one configuration, one traffic mix, one kind
of item, one driver, one system, one check or one metric is a file found
by the name `BENCHMARK.json` or the configuration or traffic file gives
(`manifest.load_module`). This module knows none of them.
"""

from __future__ import annotations

import asyncio
import json
import os
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional

from . import manifest as mf
from .cluster import CompileMeter, leader_of, serving_cluster
from .loadgen import open_loop_summary


def say(what: str, **fields: Any) -> None:
    print(json.dumps({"bench": what, **fields}, default=str), flush=True)


#: A function that compiles (or loads from the persistent cache) inside the
#: window fails the run: warm-up has to have run every program of the
#: model. The configuration may name exceptions (`tolerated_compiles`):
#: single eager operations that the program issues with a varying arity
#: (`LMServer._chunk_step` reads back one `jnp.concatenate` of the chunk's
#: tokens and however many placement groups are pending), which no set-up
#: can run exhaustively. Their seconds in the window are printed in every
#: run, reported as a per-layer metric, and bounded: over this share of
#: the window they fail the run too, since a stall of that size is no
#: longer the same measurement.
TOLERATED_SHARE = 0.10


class NoAccelerator(RuntimeError):
    pass


class CompiledInWindow(RuntimeError):
    pass


def require_device(chips: int, rehearse: bool):
    """The chips the cell asks for, or no run at all."""
    import jax

    devs = jax.devices()
    if rehearse:
        return devs[:chips]
    if devs[0].platform != "tpu":
        raise NoAccelerator(
            f"needs a TPU; JAX's first device is {devs[0]} "
            f"(platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoAccelerator(f"needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def configure_compile_cache() -> str:
    """The persistent cache in the checkout's own `.jax_cache` (the
    program's place when nothing else is named), wherever the machine's
    `JAX_COMPILATION_CACHE_DIR` points, and with no size cap, whatever its
    `JAX_COMPILATION_CACHE_MAX_SIZE` says. A capped cache that is full
    (the chip machine's 192 MiB, filled by earlier trees' programs) evicts
    at every write: a packed readback then takes 0.5-1 s to compile and
    store, not 60 ms, and inside a window that stalls every live request
    (PERF.md, PR 24 finding 7). Every program is cached however quickly it
    compiled, so that a second run's set-up finds them all."""
    import jax

    from dml_tpu.compile_cache import DEFAULT_CACHE_DIR
    from dml_tpu.compile_cache import configure_compile_cache as program_rule

    program_rule()
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return DEFAULT_CACHE_DIR


class Cell:
    """A cell's files, loaded by name."""

    def __init__(self, manifest: Dict[str, Any], name: str,
                 rehearse: bool = False):
        self.manifest = manifest
        self.entry = mf.cell_of(manifest, name)
        self.name = name
        self.config = mf.config_of(manifest, self.entry)
        self.traffic = mf.load_json("traffic", self.entry["traffic"])
        if rehearse:
            self.config = {**self.config, **self.config["rehearsal"]}
            self.traffic = {**self.traffic, **self.traffic["rehearsal"]}
        self.driver = mf.load_module("drivers", self.traffic["driver"])
        self.items = mf.load_module("items", self.traffic["items"]["kind"])
        self.backend = mf.load_module("backends", self.config["system"])
        self.reference = mf.load_module("references", self.config["reference"])
        self.costs = mf.load_module("costs", self.config["costs"])
        self.check = mf.load_module("checks", self.config["check"])


class Served:
    """A cell's system, built and warm behind its cluster: what one
    set-up gives, and what `run.py` uses once and `sweep.py` many times."""

    def __init__(self, cell: Cell, seed: int, *,
                 variant: Optional[str] = None):
        self.cell, self.seed, self.variant = cell, seed, variant
        self.split: Dict[str, float] = {}
        self.system = None
        self.root = tempfile.mkdtemp(prefix="dml_bench_")
        self._stack = None
        self.cluster = None
        self._put = 0

    def _timed(self, key: str, t0: float) -> None:
        self.split[key] = self.split.get(key, 0.0) + time.monotonic() - t0

    async def start(self, sizes: List[Dict[str, int]]) -> None:
        t0 = time.monotonic()
        self.system = self.cell.backend.System(
            self.cell.config, self.cell.reference, self.seed,
            variant=self.variant)
        self.split["weights_s"] = self.system.weights_s
        self.split["backend_s"] = self.system.backend_s
        t0 = time.monotonic()
        warm = await asyncio.to_thread(self.system.warm, sizes)
        self._timed("warm_programs_s", t0)
        say("warm", **warm)
        t0 = time.monotonic()
        self._stack = serving_cluster(
            self.root, self.system.make_jobs,
            self.cell.traffic.get("slo_classes"))
        self.cluster = await self._stack.__aenter__()
        self._timed("cluster_s", t0)

    async def put(self, stored) -> None:
        t0 = time.monotonic()
        client = self.cluster.client()
        for r in stored:
            path = os.path.join(self.root, f"put_{self._put}_{r.name}")
            self.cell.items.write(r, path)
            await client.store.put(path, r.name)
            self._put += 1
        self._timed("puts_s", t0)

    async def warm_path(self, stored) -> None:
        t0 = time.monotonic()
        await self.cell.driver.warm(
            self.cluster, self.system, self.cell.traffic, stored,
            self.cell.items)
        self._timed("warm_path_s", t0)

    async def stop(self) -> None:
        if self._stack is not None:
            await self._stack.__aexit__(None, None, None)
            self._stack = None

    def close(self) -> None:
        shutil.rmtree(self.root, ignore_errors=True)


async def _trace_window(trace: Dict[str, Any], start_s: float,
                        length_s: float) -> None:
    """Profile `length_s` seconds starting `start_s` into the window."""
    import jax

    await asyncio.sleep(start_s)
    await asyncio.to_thread(jax.profiler.start_trace, trace["dir"])
    trace["t0"] = time.monotonic()
    await asyncio.sleep(length_s)
    trace["t1"] = time.monotonic()
    await asyncio.to_thread(jax.profiler.stop_trace)


async def window(served: Served, reqs, seconds: float, meter: CompileMeter,
                 trace_dir: Optional[str] = None) -> Dict[str, Any]:
    """Drive one measured window and its drain; returns the run's facts."""
    cell, system = served.cell, served.system
    leader = leader_of(served.cluster)
    before = meter.snapshot()
    counters = {"start": system.counters()}
    batches0 = len(leader.jobs.batch_timing)
    tap0 = len(system.tap.served)
    compiled: Dict[str, Any] = {}

    def at_window_end() -> None:
        counters["end"] = system.counters()
        after = meter.snapshot()
        inside = meter.compiled[before["compiles"]:]
        tolerated = cell.config.get("tolerated_compiles", [])
        compiled["misses"] = after["cache_misses"] - before["cache_misses"]
        compiled["count"] = len(inside)
        compiled["seconds"] = sum(s for _, s in inside)
        compiled["names"] = sorted({n for n, _ in inside})
        compiled["not_tolerated"] = sorted(
            {n for n, _ in inside if n not in tolerated})
        compiled["seconds_allowed"] = seconds * TOLERATED_SHARE

    trace: Dict[str, Any] = {"dir": trace_dir}
    tracer = None
    if trace_dir:
        # the last seconds of the window: stopping a trace holds the
        # interpreter for a second or more, which must not fall inside it
        length = min(5.0, max(1.0, 0.15 * seconds))
        tracer = asyncio.ensure_future(
            _trace_window(trace, seconds - length - 0.25, length))
    out = await cell.driver.run(
        served.cluster, system, cell.traffic, reqs, cell.items, seconds,
        served.root, at_window_end)
    if tracer is not None:
        await tracer
    run: Dict[str, Any] = {
        "cell": cell.name, "config": cell.config, "traffic": cell.traffic,
        "costs": cell.costs, "seed": served.seed, "seconds": seconds,
        "requests": reqs, "driver": out, "counters": counters,
        "compiled_in_window": compiled,
        "tap": system.tap.served[tap0:],
        "batches": list(leader.jobs.batch_timing)[batches0:],
        "system": {"chunk": system.chunk, "slots": system.slots,
                   "spec": system.spec},
        "trace_window": (trace.get("t0"), trace.get("t1")),
        "trace": None,
    }
    if reqs and reqs[0].due is not None:
        run["summary"] = open_loop_summary(
            reqs, float(cell.traffic["drain_limit_s"]))
        run["attempted"] = run["summary"]["attempted"]
        run["failed"] = run["summary"]["failed"]
    else:
        jobs = out["jobs"]
        run["summary"] = None
        run["attempted"] = sum(j["queries"] for j in jobs)
        run["failed"] = sum(
            j["queries"] if not j.get("done") else j["bad"] for j in jobs)
    return run


def compiled_too_much(inside: Dict[str, Any]) -> Optional[str]:
    """Why what compiled inside the window fails the run, or None."""
    if inside["not_tolerated"]:
        return (f"{inside['not_tolerated']} compiled inside the measured "
                f"window: warm-up missed a shape")
    if inside["seconds"] > inside["seconds_allowed"]:
        return (f"{inside['names']} took {inside['seconds']:.3f} s of the "
                f"window to compile or load, over the "
                f"{inside['seconds_allowed']:.3f} s the configuration allows")
    return None


def read_metrics(cell: Cell, run: Dict[str, Any], group: str) -> Dict[str, Any]:
    """Each of the cell's metrics of `group`, from its own reader; a
    reader that finds nothing to read returns None and is left out."""
    out: Dict[str, Any] = {}
    for m in mf.metrics_of(cell.manifest, cell.name, group):
        value = mf.load_module("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def device_facts(devices, run: Dict[str, Any]) -> Dict[str, Any]:
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    d0 = devices[0]
    out = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    if run.get("trace"):
        out["busy_s"] = run["trace"]["busy_s"]
        out["window_s"] = run["trace"]["window_s"]
    return out


def run_cell(
    workload: str, seed: int, seconds: float, trace: bool, *,
    t_start: float, rehearse: bool = False, variant: Optional[str] = None,
    control: bool = False,
) -> Dict[str, Any]:
    """One run of one cell; returns the result line as a dict.

    `rehearse` (tests only; `run.py` has no such option) lets the run go
    ahead on the CPU at the configuration's `rehearsal` size and leaves
    every metric out of the result: a CPU's times are never written
    under the name of a device metric. `variant` serves the control
    (`int8w`: the program's own int8 weight path). `control` also reads
    the reference's int8 gap."""
    cell = Cell(mf.load(), workload, rehearse)
    devices = require_device(int(cell.entry["chips"]), rehearse)
    import jax

    cache_dir = configure_compile_cache()
    meter = CompileMeter()
    say("start", workload=workload, seed=seed, seconds=seconds, trace=trace,
        device_kind=devices[0].device_kind, compile_cache_dir=cache_dir,
        compile_cache_max_size=jax.config.jax_compilation_cache_max_size,
        import_s=time.monotonic() - t_start)
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="dml_bench_trace_")
    served = Served(cell, seed, variant=variant)

    async def go() -> Dict[str, Any]:
        reqs = cell.driver.plan(cell.traffic, seconds, seed, cell.config,
                                cell.items)
        stored = cell.driver.store_items(cell.traffic, reqs, seed,
                                         cell.config, cell.items)
        await served.start([r.size for r in stored])
        try:
            await served.put(stored)
            await served.warm_path(stored)
            setup_s = time.monotonic() - t_start
            say("setup", setup_s=setup_s, **served.split, **meter.snapshot())
            run = await window(served, reqs, seconds, meter, trace_dir)
            run["setup_s"] = setup_s
        finally:
            await served.stop()
        return run

    try:
        run = asyncio.run(go())
        say("compiled_in_window", **run["compiled_in_window"])
        fault = compiled_too_much(run["compiled_in_window"])
        if fault:
            raise CompiledInWindow(fault)
        run["device_kind"] = devices[0].device_kind
        if trace_dir:
            from . import trace as tr

            path = tr.find_xplane(trace_dir)
            t0, t1 = run["trace_window"]
            if path and t0 is not None:
                run["trace"] = tr.reduce_trace(
                    path, window_s=t1 - t0,
                    host_frames=cell.config.get("trace_host_frames"))
        device = device_facts(devices, run)  # the program's peak, before
        served.system.free()                 # the reference takes the chip
        jax.clear_caches()
        t0 = time.monotonic()
        numbers = cell.check.check(run, cell.reference, seed, control=control)
        say("checked", check_s=time.monotonic() - t0,
            run_s=time.monotonic() - t_start)
    finally:
        served.close()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    for n in numbers:
        say("compare", **n)
    correct = all(n["ok"] for n in numbers if "limit" in n)
    if run["summary"]:
        say("window", **run["summary"], **{
            k: v for k, v in run["driver"].items() if k != "jobs"})
    else:
        say("window", jobs=len(run["driver"]["jobs"]), **{
            k: v for k, v in run["driver"].items() if k != "jobs"})
    group = "per_layer" if trace else "end_to_end"
    result: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": int(run["attempted"]),
        "failed": int(run["failed"]),
        "metrics": {} if rehearse else read_metrics(cell, run, group),
        "device": device,
        "numbers": numbers,
    }
    if run.get("trace") and not rehearse:
        result["breakdown"] = {
            "device_ops": run["trace"]["device_ops"],
            "idle_gaps": run["trace"]["idle_gaps"],
        }
    if rehearse:
        # which readers found something to read, never what they read
        result["readers"] = sorted({**read_metrics(cell, run, "end_to_end"),
                                    **read_metrics(cell, run, "per_layer")})
    return result
