"""Bench matrix for the TPU serving stack.

Output protocol (VERDICT r4 item 1 + r5 item 3): one compact JSON line
per section AS IT COMPLETES (so a mid-run kill leaves every finished
measurement in the stdout tail), then the combined artifact line with
the summary as its last key, then a FINAL standalone compact summary
line (<1,500 chars, ``bench_summary_v1``) that survives the driver's
2,000-char stdout tail — the driver's structured parse reads it, and
parity_table/claim_check accept either form. A global wall budget (default 1,400 s hard
cap, `DML_TPU_BENCH_BUDGET_S`) skips any section whose cold-cache
estimate would overrun it rather than running into the driver's
timeout; SIGTERM/SIGINT jump straight to the final combined print.

Headline: ResNet50 batch=32 inference throughput per chip (the
BASELINE.json north-star). The final line also carries the full matrix:

- ResNet50 batch sweep 16..256 with q/s + MFU per point (the headline
  batch is justified by the sweep, not assumed);
- InceptionV3 b8 (BASELINE config 2) and b32;
- EfficientNet-B4 b32 (BASELINE config 5's plug-in model);
- dual-model C4: ResNet50 + InceptionV3 concurrent jobs through the
  REAL fair-share scheduler on one chip, with its C1/C2 outputs;
- Pallas-on-device: flash attention fwd/bwd vs naive XLA attention,
  fused_normalize vs jnp, numeric parity asserted compiled via Mosaic;
- imagenet label parity vs the reference goldens when pretrained
  weights are obtainable, skipped-with-reason when not.

Timing methodology (dml_tpu/benchmarks.py): every throughput number is
the SLOPE between two on-device fori_loop chain lengths with a
loop-carried input poke and full-output max consumption — it cancels
the fixed dispatch/readback cost around a millisecond kernel and
defeats XLA hoisting/slice-pushdown eating the work. Numbers are
medians across reps (best-of-N overstates). Latency numbers are
end-to-end submit->host-result times and INCLUDE dispatch and the
result's copy to the host.

The bench refuses to start unless JAX's first device is a TPU, and
exits non-zero when any section raised (partial results still stream).

Baseline (BASELINE.md): the reference's ResNet50 steady-state CPU
predict is 250 ms/image (reference test.py:120, worker.py:74) => 4
queries/sec per node. `vs_baseline` is the speedup over that.
"""

from __future__ import annotations

import json
import os
import time


class _Interrupted(BaseException):
    """Raised from the SIGTERM/SIGINT handler: unwinds the section loop
    (past the fail-soft `except Exception` nets) into main()'s final
    print, so a driver kill still emits the combined artifact for
    everything measured. BaseException on purpose."""


# Cold-cache wall estimates per section (measured on the r5 priming
# run's installation: uncached compiles, idle host, dynamic-n slope
# protocol; not re-measured on the current one; warm
# runs take a fraction of these and never trip the gate). The budget
# gate uses them to skip a section that WOULD overrun the hard cap,
# not just one that already has — a section started at budget-1s
# can't blow the envelope. Estimates err ~30% high on purpose.
SECTION_EST_S = {
    "models": 800.0,
    "dual_model_c4": 120.0,
    "cluster_serving": 210.0,  # + cache-matched static + adaptive serves
    # CPU-subprocess: 5-node cluster, 2 ShardedInference compiles,
    # group + single-chip serves (measured ~150 s warm on 1 core)
    "cluster_sharded_serving": 300.0,
    # CPU-subprocess: 5-node cluster, 4 sharded-LM serving forms
    # (param_gather / weight-resident / pipeline-parallel /
    # disaggregated, with shipped-draft verification on the disagg
    # form) + the whole-slab-vs-streamed handoff ladder with 1- and
    # 2-peer fan-out + the member-kill-mid-stream chaos case + the
    # round-21 raw-decode arms (speculative A/B at a declared
    # acceptance w/ auto-disable, continuous-batching TTFT A/B)
    "cluster_lm_sharded": 640.0,
    "lm": 450.0,
    "cluster_lm_serving": 210.0,  # + >=15 s steady-state refill phase
    "chaos": 230.0,  # 2 soak seeds + 7 adversarial scenario families
    # elastic capacity: one live cluster — saturated load window,
    # authenticated scale-out of 2 joiners mid-load, re-measure,
    # graceful scale-in + forged-join storm + invariant sweep
    "elastic_capacity": 120.0,
    # signal plane: one live cluster — overload shed burst until the
    # burn-rate alert fires, liar-flagging job rounds, leader kill +
    # ledger inheritance, plus the pure-replay determinism arm
    "signal_plane": 120.0,
    # autoscaler: the 52 s seeded diurnal trace served twice (static
    # pool vs closed-loop controller) + invariant sweeps + the
    # pure-replay decision-stream determinism arm
    "autoscale": 150.0,
    # elastic cluster training: one live cluster — a TrainJob's
    # examples/s window-measured at world 1 -> 2 -> 3 as capacity
    # joins mid-run (checkpoint-restore re-shard at step boundaries,
    # zero restarts), then a mixed arm scoring interactive-stream
    # p99 with and without a trainer sharing the pool + the step-
    # exact invariant sweep
    "cluster_training": 160.0,
    # control-plane scale matrix: 16/64/128-node membership-only
    # clusters x full-vs-delta gossip (bring-up, traffic window,
    # metrics aggregation, kill + election each) + the 64-node
    # store-services churn run (measured ~120 s warm on 1 core)
    "control_plane_scale": 300.0,
    # per-request front door under open-loop load: light (continuous
    # vs fixed formation), saturation, sustained mixed-class (+ the
    # weighted-class-vs-FIFO rerun), and the leader-failover-mid-
    # traffic case, all on one CPU stub cluster
    "request_serving": 600.0,
    "train": 750.0,  # + b64/b128/grad-accum sweep points
    # isolated concat slope-timings at InceptionV3's 11 block shapes
    # + the CPU-safe jaxpr byte count (VERDICT r5 weak #5)
    "inception_fusion": 150.0,
    # two jitted b128 B4 forward-slope measurements (stock vs s2d
    # stem) on already-resident weights
    "b4_s2d_stem": 120.0,
    "pallas_on_device": 200.0,
    "ring_vs_ulysses": 60.0,
    "imagenet_parity": 30.0,
}


def run_sections(sections, out, *, t_start, budget_s, fatal=(),
                 stream=None):
    """Run bench sections with streaming output + a global wall budget
    (VERDICT r4 item 1).

    `sections` is [(name, thunk)]. After each section completes, the
    top-level keys it added to `out` are printed as ONE compact JSON
    line (``{"section": ..., "wall_s": ..., "data": {...}}``) so any
    mid-run kill leaves every finished measurement in the stdout tail.
    Before each section, the global wall budget is checked: once
    ``budget_s`` is exceeded, remaining non-fatal sections are recorded
    under ``out["_skipped"]`` and not run — the run jumps to the final
    summary print instead of being timeout-killed into an empty
    artifact (the round-4 failure mode: rc=124, no numbers).

    Sections in `fatal` propagate exceptions (a run without the
    headline is not an artifact); others fail soft under
    ``out["_errors"]``, keeping any partial results they wrote.
    Per-section wall times land in ``out["_section_wall_s"]`` so the
    next round can see where the budget went.
    """
    if stream is None:
        def stream(line):
            print(line, flush=True)

    for name, thunk in sections:
        elapsed = time.monotonic() - t_start
        # skip a section that WOULD overrun the cap, not just one
        # whose start is already past it — a section started at
        # cap-1s must not blow the driver's envelope. Estimates are
        # COLD-cache worst cases; on a warm-cache run elapsed stays
        # low and nothing trips.
        est = SECTION_EST_S.get(name, 120.0)
        if elapsed + est > budget_s and name not in fatal:
            reason = (
                f"wall budget {budget_s:.0f}s: at {elapsed:.0f}s, "
                f"{name} (~{est:.0f}s cold est) would overrun"
            )
            out.setdefault("_skipped", {})[name] = reason
            stream(json.dumps(
                {"section": name, "skipped": "wall_budget",
                 "elapsed_s": round(elapsed, 1)},
                separators=(",", ":")))
            continue
        before = set(out)
        t0 = time.monotonic()
        try:
            thunk()
        except Exception as e:
            if name in fatal:
                raise
            import traceback

            traceback.print_exc()
            # errors live under their own key: a section that wrote
            # partial results before tripping keeps what it measured
            out.setdefault("_errors", {})[name] = repr(e)
        wall = time.monotonic() - t0
        out.setdefault("_section_wall_s", {})[name] = round(wall, 1)
        new = {
            k: out[k] for k in out
            if k not in before and not k.startswith("_")
        }
        stream(json.dumps(
            {"section": name, "wall_s": round(wall, 1),
             "elapsed_s": round(time.monotonic() - t_start, 1),
             "error": out.get("_errors", {}).get(name),
             "data": new},
            separators=(",", ":"), default=str))
    return out


def _bench_models(engine, out):
    """Model throughput matrix: sweep + secondary models."""
    import jax
    import jax.numpy as jnp

    from dml_tpu.benchmarks import (
        compiled_flops,
        dispatch_latency,
        forward_rate_stats,
        peak_flops,
    )

    peak = peak_flops()
    out["peak_flops_assumed"] = peak

    def measure(name, batch_size, chains=(10, 50)):
        lm = engine.load_model(name, batch_size=batch_size, warmup=False)
        batch = jnp.zeros(
            (batch_size, *lm.spec.input_size, 3), jnp.uint8
        )
        batch = jax.device_put(batch, engine.device)
        st = forward_rate_stats(
            lm.forward, lm.variables, batch, chains=chains
        )
        secs = st["median"]
        flops = compiled_flops(lm.forward, lm.variables, batch)
        return {
            "batch": batch_size,
            "qps": round(batch_size / secs, 1),
            # min/max over the independent paired slopes — the
            # dispersion that makes cross-round drift visible
            # (VERDICT r3 item 1)
            "qps_range": [
                round(batch_size / st["max"], 1),
                round(batch_size / st["min"], 1),
            ],
            "batch_ms": round(secs * 1e3, 3),
            "mfu": round(flops / secs / peak, 4) if flops else None,
        }, lm, batch

    # ResNet50 sweep (BASELINE config 4 family); headline at b32.
    # Chain lengths scale INVERSELY with batch so every point
    # accumulates >=150 ms of device work between the two chain
    # lengths — short chains at small batches let host-clock jitter
    # through the slope
    sweep = []
    for b, ch in (
        (16, (20, 160)), (32, (20, 120)), (64, (15, 90)),
        (128, (10, 60)), (256, (5, 35)),
    ):
        point, lm, batch = measure("ResNet50", b, chains=ch)
        sweep.append(point)
        if b == 32:
            p50, p99 = dispatch_latency(lm.forward, lm.variables, batch)
            out["headline_resnet50_b32"] = {
                **point,
                "batch_latency_p50_ms": round(p50 * 1e3, 2),
                "batch_latency_p99_ms": round(p99 * 1e3, 2),
                "query_latency_p50_ms": round(p50 / b * 1e3, 4),
                "query_latency_p99_ms": round(p99 / b * 1e3, 4),
            }
    out["resnet50_sweep"] = sweep
    best = max(sweep, key=lambda p: p["qps"])
    out["resnet50_throughput_optimal_batch"] = best["batch"]

    i8, _, _ = measure("InceptionV3", 8, chains=(20, 160))  # config 2
    i32, _, _ = measure("InceptionV3", 32, chains=(15, 90))
    # b128 is InceptionV3's throughput point (the ratio to b32 lives
    # in this run's own `inceptionv3` points; b256 regresses) — the
    # branchy blocks need a deep batch before XLA's tilings fill the
    # MXU
    i128, _, _ = measure("InceptionV3", 128, chains=(8, 40))
    out["inceptionv3"] = [i8, i32, i128]
    e32, _, _ = measure("EfficientNetB4", 32, chains=(5, 30))
    e128, _, _ = measure("EfficientNetB4", 128, chains=(3, 13))
    out["efficientnet_b4"] = [e32, e128]


def _bench_dual_c4(engine, out):
    """BASELINE config 3: concurrent ResNet50 + InceptionV3 jobs pushed
    through the real fair-share scheduler; the engine executes every
    assigned batch on the chip. Wall-clock here includes per-batch
    dispatch — it demonstrates the C4 capability and the
    scheduler's fair split, not peak chip rate (see the sweep).

    Two dispatch modes measured (VERDICT r2 item 6): `sync` executes
    one synchronous round-trip per batch (the reference's shape —
    worker.py:518-537 overlaps nothing); `pipelined` enqueues every
    assignment in a scheduling round via `infer_arrays_nowait` and
    drains in order, so transfers and forwards of later batches
    overlap earlier readbacks. The SERVING run uses whichever mode
    `engine.choose_dispatch_mode` picked by probing the actual
    first-round composition (VERDICT r4 item 3) — one mode for the
    whole round, chosen per run; both forced modes are still
    reported for the cross-round record (the chosen one doubles as
    the serving run, so only two full serves execute). C1 comes from
    the serving (auto) run; C2 from the sync run — its per-batch
    sample is dispatch -> result with nothing else in flight, the
    r01 measurement point. Both models are warmed through the EXACT
    execution path first (same arrays, same shapes), so C2 reports
    serving latency, not first-call XLA compilation (item 5)."""
    import numpy as np

    from dml_tpu.jobs.cost_model import ModelCost
    from dml_tpu.jobs.scheduler import Scheduler

    rng = np.random.RandomState(0)
    workers = ["W1", "W2", "W3", "W4"]
    costs = {}
    for m, bs in (("ResNet50", 32), ("InceptionV3", 8)):
        lm = engine.load_model(m, batch_size=bs, warmup=True)
        costs[m] = ModelCost(
            load_time=lm.load_time, first_query=lm.first_query,
            per_query=lm.per_query, download_time=0.0, batch_size=bs,
        )
    files = [f"img_{i}.jpeg" for i in range(64)]
    n_r, n_i = 512, 256
    imgs = {
        "ResNet50": rng.randint(0, 255, (32, 224, 224, 3), dtype=np.uint8),
        "InceptionV3": rng.randint(0, 255, (8, 299, 299, 3), dtype=np.uint8),
    }
    # warm the exact serving path (infer_arrays' device_put + forward +
    # readback at the exact shapes) so no compile lands in a C2 sample
    for m in imgs:
        engine.infer_arrays(m, imgs[m])

    def make_sched():
        """The bench's job mix, ONE definition: the probe must measure
        the same round composition the serve dispatches."""
        sched = Scheduler()
        for m, c in costs.items():
            sched.set_cost(m, c)
        sched.submit_job(1, "ResNet50", files, n_r, "bench")
        sched.submit_job(2, "InceptionV3", files, n_i, "bench")
        return sched

    def run(mode_by_model):
        """One full dual-job serve; `mode_by_model[m]` picks each
        assignment's dispatch: 'sync' = one blocking round-trip per
        batch (the reference's shape, worker.py:518-537), 'pipelined'
        = enqueue the whole scheduling round then drain in order."""
        sched = make_sched()
        t0 = time.monotonic()
        done = 0
        while sched.jobs:
            assigns = sched.schedule(workers)
            if not assigns and not sched.in_progress:
                break
            round_handles = []
            for a in assigns:
                bt0 = time.monotonic()
                h = engine.infer_arrays_nowait(
                    a.batch.model, imgs[a.batch.model][: len(a.batch.files)]
                )
                if mode_by_model[a.batch.model] == "pipelined":
                    round_handles.append((a, bt0, h))
                else:
                    h()
                    sched.on_batch_done(
                        a.worker, a.batch.job_id, a.batch.batch_id,
                        time.monotonic() - bt0, len(a.batch.files),
                    )
                    done += 1
            for a, bt0, h in round_handles:
                h()
                sched.on_batch_done(
                    a.worker, a.batch.job_id, a.batch.batch_id,
                    time.monotonic() - bt0, len(a.batch.files),
                )
                done += 1
        return time.monotonic() - t0, done, sched

    ALL_SYNC = {"ResNet50": "sync", "InceptionV3": "sync"}
    ALL_PIPE = {"ResNet50": "pipelined", "InceptionV3": "pipelined"}
    # the engine times both dispatch modes with the ACTUAL round
    # composition the fair-share scheduler will dispatch (a throwaway
    # scheduler instance yields the first round's assignment mix) and
    # the SERVING run uses what it chose — the mode comparison rows
    # stay for the cross-round record (VERDICT r4 item 3: a mode the
    # artifact proves counterproductive must not be the one the
    # engine runs)
    probe_sched = make_sched()
    round_spec = [
        (a.batch.model, imgs[a.batch.model][: len(a.batch.files)])
        for a in probe_sched.schedule(workers)
    ]
    mode = engine.choose_dispatch_mode(round_spec)
    # the auto serve IS one of the two forced configurations, so run
    # the chosen mode FIRST (it doubles as the serving run) and the
    # other mode second for the comparison row — no third redundant
    # 768-query serve
    wall_a, done_a, sched_a = run(ALL_PIPE if mode == "pipelined" else ALL_SYNC)
    wall_b, done_b, sched_b = run(ALL_SYNC if mode == "pipelined" else ALL_PIPE)
    if mode == "pipelined":
        (wall_pipe, done_pipe, sched_pipe) = (wall_a, done_a, sched_a)
        (wall_sync, done_sync, sched_sync) = (wall_b, done_b, sched_b)
    else:
        (wall_sync, done_sync, sched_sync) = (wall_a, done_a, sched_a)
        (wall_pipe, done_pipe, sched_pipe) = (wall_b, done_b, sched_b)
    wall_auto, done_auto, sched_auto = wall_a, done_a, sched_a
    out["dual_model_c4"] = {
        "resnet50_queries": n_r,
        "inceptionv3_queries": n_i,
        "batches_executed": done_auto,
        "dispatch_mode_auto": mode,
        "probe_round": [m for m, _ in round_spec],
        "wall_s_sync": round(wall_sync, 2),
        "wall_s_pipelined": round(wall_pipe, 2),
        "wall_s_auto": round(wall_auto, 2),
        "combined_qps_sync": round((n_r + n_i) / wall_sync, 1),
        "combined_qps_pipelined": round((n_r + n_i) / wall_pipe, 1),
        "combined_qps_auto": round((n_r + n_i) / wall_auto, 1),
        # the serving path (auto) vs the reference-shaped sync loop —
        # >= 1.0 when the probe chose right; the raw both-mode walls
        # above keep the comparison honest
        "pipelining_speedup": round(wall_sync / wall_auto, 2),
        "pipelined_vs_sync_forced": round(wall_sync / wall_pipe, 2),
        "c1": sched_auto.c1_stats(window=wall_auto),
        # C2 from the SYNC run: its per-batch sample is dispatch ->
        # result with nothing else in flight (the r01 measurement
        # point, comparable across rounds). The pipelined run's
        # enqueue->drain spans include waiting on earlier batches in
        # the round — a queueing number, not a processing-time one.
        "c2_resnet50": sched_sync.c2_stats("ResNet50"),
        "c2_inceptionv3": sched_sync.c2_stats("InceptionV3"),
        "note": "dispatch_mode_auto is measured per RUN by probing "
                "the actual first scheduling round's composition "
                "(engine.choose_dispatch_mode): whether enqueue-then-"
                "drain beats one blocking call per batch is measured, "
                "not assumed — the engine probes and picks instead of "
                "publishing a losing mode, and the serving run IS the "
                "chosen forced run. "
                "The worker-pipeline win is separate: "
                "cluster_serving.pipelining_speedup (depth-2 "
                "prepare/dispatch overlap)",
    }


def _cluster_stack(tmp, base_port, make_jobs, n_nodes=4):
    """Shared bring-up/teardown for the cluster bench sections, now
    assembled via ``chaos.LocalCluster`` — the SAME cluster chassis
    the chaos soaks validate, so every bench number is produced by an
    assembly whose failure behavior is invariant-checked elsewhere
    (previously this was a second, parallel bring-up harness that
    could drift). Yields ``(cluster, stack)`` where ``stack`` =
    [(node, store, jobs), ...] sorted by node name; crash a member
    mid-section with ``cluster.crash_node(uname)``."""
    import contextlib
    import shutil

    from dml_tpu.cluster.chaos import LocalCluster
    from dml_tpu.config import Timing

    @contextlib.asynccontextmanager
    async def ctx():
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp, exist_ok=True)
        cluster = LocalCluster(
            n_nodes, tmp, base_port,
            timing=Timing(ping_interval=0.2, ack_timeout=0.3,
                          cleanup_time=1.0, leader_rpc_timeout=10.0),
            make_jobs=make_jobs,
        )
        try:
            await cluster.start()
            await cluster.wait_for(
                cluster.converged, 20.0,
                f"bench cluster convergence (stale process on ports "
                f"{base_port - 1}-{base_port + n_nodes - 1}?)",
            )
            stack = [
                (sn.node, sn.store, sn.jobs)
                for _, sn in sorted(cluster.nodes.items())
            ]
            yield cluster, stack
        finally:
            await cluster.stop()

    return ctx()


def _bench_chaos(out, *, seeds=(1, 2), scenario_seeds=(1,),
                 base_port=28861):
    """Deterministic chaos soak (cluster/chaos.py): per seed, the
    canonical recovery composition — leader killed mid-put and
    mid-job, a partition that heals, 2% loss, duplicate delivery —
    with the invariant sweep at the end, PLUS one sweep per
    adversarial scenario family (asymmetric partition, disk
    full/corruption, introducer-DNS outage mid-failover, clock skew,
    byzantine datagram fuzz). Records failover-recovery and
    replication-repair walls and per-family green/red; claim_check
    validates the walls are finite, every family swept green, and the
    fuzz run left a nonzero malformed-drop counter. CPU-only (stub
    inference backend): the control plane's survival story is what's
    under test."""
    import statistics

    from dml_tpu.cluster.chaos import (
        SCENARIO_FAMILIES, run_plan_sync, scenario_plan, soak_plan,
    )
    from dml_tpu.observability import METRICS

    per_seed = []
    failover, repair = [], []
    port = base_port
    for seed in seeds:
        rep = run_plan_sync(soak_plan(seed), base_port=port)
        port += 20
        per_seed.append({
            "seed": seed,
            "invariants_ok": rep.ok,
            "invariant_failures": rep.invariants.failures,
            "events": len(rep.plan.events),
            "failover_recovery_s": [
                round(x, 3) for x in rep.failover_recovery_s
            ],
            "store_repair_s": [round(x, 3) for x in rep.store_repair_s],
            "jobs": {str(k): v["outcome"] for k, v in rep.jobs.items()},
            "wall_s": round(rep.wall_s, 1),
        })
        failover += rep.failover_recovery_s
        repair += rep.store_repair_s
    scenarios = {}
    for fam in SCENARIO_FAMILIES:
        fam_runs = []
        for seed in scenario_seeds:
            rep = run_plan_sync(scenario_plan(fam, seed), base_port=port)
            port += 20
            fam_runs.append({
                "seed": seed,
                "invariants_ok": rep.ok,
                "invariant_failures": rep.invariants.failures,
                "wall_s": round(rep.wall_s, 1),
            })
        scenarios[fam] = {
            "seeds": list(scenario_seeds),
            "all_invariants_ok": all(r["invariants_ok"] for r in fam_runs),
            "per_seed": fam_runs,
        }
    malformed = METRICS.snapshot()["counters"].get(
        "transport_malformed_dropped_total", 0.0
    )
    out["chaos"] = {
        "plan": "soak (leader-kill-mid-put/job + partition heal + "
                "2% loss + duplicate delivery) + per-family "
                "adversarial scenarios",
        "seeds": list(seeds),
        "all_invariants_ok": all(s["invariants_ok"] for s in per_seed)
        and all(s["all_invariants_ok"] for s in scenarios.values()),
        "failover_recovery_s": (
            round(statistics.median(failover), 3) if failover else None
        ),
        "store_repair_s": (
            round(statistics.median(repair), 3) if repair else None
        ),
        "failover_samples": len(failover),
        "repair_samples": len(repair),
        "per_seed": per_seed,
        "scenarios": scenarios,
        "malformed_dropped_total": int(malformed),
        "note": "medians over every observed recovery; timing envelope "
                "is the FAST sim profile (ping 50ms, cleanup 300ms), "
                "so walls measure protocol rounds, not deployed "
                "wall-clock",
    }


def _bench_elastic(out, *, base_port=29940, n_nodes=4, window_s=5.0,
                   joiners=2):
    """Elastic capacity (ROADMAP item 2's done-condition): capacity
    added MID-LOAD raises measured throughput with ZERO restarts.

    One CPU stub cluster with the authenticated join policy on; a
    continuous job stream keeps the pool saturated while q/s is
    measured over a window, then `joiners` brand-new nodes join
    through JOIN_REQUEST (no node restarts, no cluster restart), the
    scheduler absorbs them as weighted slots, and the same window
    re-measures. Afterwards the joiners leave GRACEFULLY (retired
    immediately — scale-in must not read as an outage), a forged-join
    storm is blasted at the live nodes (typed rejections must move,
    no phantom may enter any table), and the full chaos invariant
    sweep must end green. claim_check gates the block from round 18."""
    import asyncio
    import shutil

    from dml_tpu.cluster.chaos import (
        FAST_TIMING, LocalCluster, fuzz_datagrams, invariant_sweep,
        STUB_MODEL, _join_rejected_total,
    )

    root = f"/tmp/dml_tpu_bench_elastic_{os.getpid()}"
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root, exist_ok=True)

    async def run():
        import socket as _socket

        cluster = LocalCluster(
            n_nodes, root, base_port, timing=FAST_TIMING,
            join_secret="bench-elastic",
        )
        try:
            await cluster.start()
            await cluster.wait_for(cluster.converged, 20.0,
                                   "elastic bench convergence")
            client = cluster.client()
            for i in range(4):
                p = os.path.join(root, f"img_{i}.jpeg")
                with open(p, "wb") as f:
                    f.write(b"\xff\xd8fakejpeg" + bytes([i]))
                await client.store.put(p, f"img_{i}.jpeg")
                cluster.expect_files.add(f"img_{i}.jpeg")

            completed = {"q": 0}
            stop = asyncio.Event()

            async def loader():
                # closed-loop per slot, open across slots: 3 jobs kept
                # in flight so the pool is saturated before AND after
                # the scale-out — the q/s delta isolates capacity
                async def one():
                    while not stop.is_set():
                        c = cluster.client()
                        try:
                            jid = await c.jobs.submit_job(
                                STUB_MODEL, 24, timeout=10.0, retries=3)
                            done = await c.jobs.wait_job(jid, timeout=60.0)
                            completed["q"] += int(
                                done.get("total_queries", 0))
                        except Exception:
                            if stop.is_set():
                                return
                            await asyncio.sleep(0.1)
                await asyncio.gather(*(one() for _ in range(3)))

            load_task = asyncio.create_task(loader(), name="elastic-load")

            async def measure() -> float:
                q0 = completed["q"]
                t0 = asyncio.get_running_loop().time()
                await asyncio.sleep(window_s)
                wall = asyncio.get_running_loop().time() - t0
                return (completed["q"] - q0) / wall

            await asyncio.sleep(1.5)  # ramp: fill the pipeline
            leader = next(sn for sn in cluster.nodes.values()
                          if sn.node.is_leader)
            pool_before = len(leader.jobs.worker_pool())
            qps_before = await measure()

            joined = []
            for _ in range(joiners):
                sn = await cluster.scale_out()
                joined.append(sn.node.me.unique_name)
            await cluster.wait_for(
                lambda: len(leader.jobs.worker_pool()) > pool_before,
                15.0, "joined capacity taking pool slots",
            )
            await asyncio.sleep(1.0)  # let the new slots fill
            pool_after = len(leader.jobs.worker_pool())
            qps_after = await measure()

            # graceful scale-in of every joiner, mid-load
            scale_in_sent = []
            for u in joined:
                scale_in_sent.append(await cluster.scale_in(u))

            # forged-join storm at the live cluster
            reject_base = _join_rejected_total()
            _, frames = fuzz_datagrams(
                7, 24, tuple(sorted(cluster.nodes)),
                join_secret="bench-elastic",
                universe_epoch=cluster.spec.universe_epoch,
                kinds=("join_bad_mac", "join_garbled", "join_stale",
                       "join_replay"),
            )
            lid = cluster.spec.node_by_unique_name(
                cluster.leader_uname() or "")
            storm_sent = 0
            if lid is not None:
                sock = _socket.socket(_socket.AF_INET,
                                      _socket.SOCK_DGRAM)
                try:
                    for fr in frames:
                        sock.sendto(fr, (lid.host, lid.port))
                        storm_sent += 1
                finally:
                    sock.close()
            await asyncio.sleep(0.5)
            storm_rejected = _join_rejected_total() - reject_base

            stop.set()
            await asyncio.wait_for(load_task, 90.0)
            report = await invariant_sweep(cluster, {}, {})
            gain = qps_after / qps_before if qps_before > 0 else None
            elastic_ok = bool(
                gain is not None and gain > 1.0
                and cluster._restart_counter == 0
                and all(scale_in_sent)
                and storm_rejected > 0
                and report.ok
            )
            return {
                "nodes": n_nodes,
                "joiners": joined,
                "window_s": window_s,
                "qps_before": round(qps_before, 1),
                "qps_after": round(qps_after, 1),
                # `is not None`: a measured-zero collapse must record
                # 0.0 (gated), never masquerade as "window not run"
                "scaleout_gain": (
                    round(gain, 2) if gain is not None else None),
                "pool_slots_before": pool_before,
                "pool_slots_after": pool_after,
                "restarts": cluster._restart_counter,
                "scale_in_graceful": scale_in_sent,
                "storm": {"sent": storm_sent,
                          "rejected": int(storm_rejected)},
                "sweep_ok": report.ok,
                "sweep_failures": report.failures,
                "elastic_ok": elastic_ok,
                "note": "q/s windows measured on the SAME live "
                        "cluster, load never paused, zero process "
                        "restarts — the gain is pure admitted "
                        "capacity; CPU stub backend, so the ratio "
                        "(not the absolute q/s) is the claim",
            }
        finally:
            await cluster.stop()
            shutil.rmtree(root, ignore_errors=True)

    out["elastic_capacity"] = asyncio.run(run())


def _bench_cluster_training(out, *, base_port=30040, n_nodes=3,
                            window_s=3.0):
    """Elastic cluster training (ROADMAP item 3's done-condition):
    a TrainJob's step throughput SCALES as capacity joins mid-run,
    and interactive latency survives a trainer sharing the pool.

    Arm 1 — scaling curve on ONE live cluster: a data-parallel
    TrainJob runs on a 3-node cluster (world 1: a single dp shard per
    step); examples/s is window-measured, then a brand-new node joins
    through the authenticated path (no restarts) and the run
    checkpoint-restore re-shards onto the grown pool at the next step
    boundary (LR rescaled to the new effective global batch);
    re-measure at world 2 and world 3. PR 4's b64/b128/ga4 sweep
    (the `train` section) is the single-node baseline this curve
    grows out of. Per-shard work is real wall (20 ms/file stub), so
    the examples/s slope measures genuine data-parallel spread — a
    scheduler that serialized the shards onto one worker would show
    a flat curve.

    Arm 2 — mixed workload: a fresh TrainJob shares the pool with a
    closed-loop interactive job stream; the stream's p99 is compared
    against a trainer-free window on the same cluster and must stay
    inside the interactive SLO class deadline (the scheduler's
    `train` class weight 0.5 keeps the trainer in the idle slots).

    The step-exact invariant sweep (chaos section 9) must end green:
    contiguous exactly-once ledger, replay-equal final state.
    claim_check gates the block from round 22."""
    import asyncio
    import shutil

    from dml_tpu.cluster.chaos import (
        FAST_TIMING, LocalCluster, invariant_sweep, STUB_MODEL,
    )
    from dml_tpu.ingress.slo import DEFAULT_CLASSES
    from dml_tpu.jobs.train import TrainJobSpec

    root = f"/tmp/dml_tpu_bench_train_{os.getpid()}"
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root, exist_ok=True)
    shard_batch = 4
    interactive_deadline = DEFAULT_CLASSES["interactive"].deadline_s

    async def run():
        cluster = LocalCluster(
            n_nodes, root, base_port, timing=FAST_TIMING,
            join_secret="bench-train", train=True,
        )
        try:
            await cluster.start()
            await cluster.wait_for(cluster.converged, 20.0,
                                   "training bench convergence")
            client = cluster.client()
            dataset = []
            for i in range(8):
                name = f"train_shard_{i:02d}.bin"
                p = os.path.join(root, name)
                with open(p, "wb") as f:
                    f.write(bytes([i]) * 256)
                await client.store.put(p, name)
                cluster.expect_files.add(name)
                dataset.append(name)
            for i in range(4):
                p = os.path.join(root, f"img_{i}.jpeg")
                with open(p, "wb") as f:
                    f.write(b"\xff\xd8fakejpeg" + bytes([i]))
                await client.store.put(p, f"img_{i}.jpeg")
                cluster.expect_files.add(f"img_{i}.jpeg")
            leader = next(sn for sn in cluster.nodes.values()
                          if sn.node.is_leader)

            # ---- arm 1: the scaling curve, one run, live joins ----
            spec = TrainJobSpec(
                name="scale", dataset=dataset, steps=240,
                shard_batch=shard_batch, base_lr=0.05,
                checkpoint_every=25, seed=11,
            )
            run1 = await leader.jobs.train.start_run(spec)
            cluster.train_runs.append(spec.name)

            async def measure():
                """(examples/s, world at window end). Examples/s is
                the scaling claim: per-shard batch is fixed, so the
                global batch per step grows with world and the
                curve measures real parallel spread."""
                a0 = run1.ledger.applied
                t0 = asyncio.get_running_loop().time()
                await asyncio.sleep(window_s)
                wall = asyncio.get_running_loop().time() - t0
                sps = (run1.ledger.applied - a0) / wall
                return sps * shard_batch * run1.world, run1.world

            await asyncio.sleep(1.0)  # ramp
            curve = []
            eps, world = await measure()
            curve.append({"world": world,
                          "examples_per_s": round(eps, 1)})
            for _ in range(2):
                pool0 = len(leader.jobs.worker_pool())
                w_before = run1.world
                await cluster.scale_out()
                await cluster.wait_for(
                    lambda: len(leader.jobs.worker_pool()) > pool0,
                    15.0, "joined capacity taking pool slots",
                )
                await cluster.wait_for(
                    lambda: run1.world > w_before or run1.done,
                    15.0, "run re-sharding onto the joined capacity",
                )
                eps, world = await measure()
                curve.append({"world": world,
                              "examples_per_s": round(eps, 1)})
            scale_status = await leader.jobs.train.wait(
                "scale", timeout=120.0
            )
            gain = (
                curve[-1]["examples_per_s"] / curve[0]["examples_per_s"]
                if curve[0]["examples_per_s"] > 0 else None
            )

            # ---- arm 2: mixed workload, p99 with/without trainer --
            async def stream(stop_when, max_s=25.0):
                lat: list = []

                async def one():
                    t_end = (asyncio.get_running_loop().time()
                             + max_s)
                    while (not stop_when()
                           and asyncio.get_running_loop().time()
                           < t_end):
                        c = cluster.client()
                        t0 = asyncio.get_running_loop().time()
                        try:
                            jid = await c.jobs.submit_job(
                                STUB_MODEL, 8, timeout=10.0,
                                retries=3)
                            await c.jobs.wait_job(jid, timeout=30.0)
                            lat.append(
                                asyncio.get_running_loop().time()
                                - t0)
                        except Exception:
                            await asyncio.sleep(0.1)
                await asyncio.gather(one(), one())
                return lat

            spec2 = TrainJobSpec(
                name="mixed", dataset=dataset, steps=120,
                shard_batch=shard_batch, base_lr=0.05,
                checkpoint_every=40, seed=12,
            )
            run2 = await leader.jobs.train.start_run(spec2)
            cluster.train_runs.append(spec2.name)
            t_mix0 = asyncio.get_running_loop().time()
            lat_with = await stream(lambda: run2.done)
            mixed_status = await leader.jobs.train.wait(
                "mixed", timeout=120.0
            )
            mixed_wall = asyncio.get_running_loop().time() - t_mix0
            mixed_eps = (
                sum(e["world"] for e in run2.ledger.history)
                * shard_batch / mixed_wall
            )
            done_flag = {"v": False}
            lat_without = await stream(
                lambda: done_flag["v"], max_s=2 * window_s
            )

            def p99(xs):
                if not xs:
                    return None
                xs = sorted(xs)
                return xs[min(len(xs) - 1, int(0.99 * len(xs)))]

            p99_with, p99_without = p99(lat_with), p99(lat_without)
            report = await invariant_sweep(cluster, {}, {})
            join_reshards = int(
                scale_status["resharding"].get("join", 0)
            )
            train_elastic_ok = bool(
                gain is not None and gain > 1.0
                and curve[-1]["world"] > curve[0]["world"]
                and join_reshards >= 1
                and cluster._restart_counter == 0
                and scale_status["done"] and mixed_status["done"]
                and p99_with is not None
                and p99_with <= interactive_deadline
                and report.ok
            )
            return {
                "nodes": n_nodes,
                "window_s": window_s,
                "shard_batch": shard_batch,
                "scaling_curve": curve,
                "scaleout_gain": (
                    round(gain, 2) if gain is not None else None),
                "join_reshards": join_reshards,
                "restarts": cluster._restart_counter,
                "scale_run": scale_status,
                "mixed": {
                    "run": mixed_status,
                    "examples_per_s": round(mixed_eps, 1),
                    "interactive_p99_with_trainer_s": (
                        round(p99_with, 3)
                        if p99_with is not None else None),
                    "interactive_p99_without_trainer_s": (
                        round(p99_without, 3)
                        if p99_without is not None else None),
                    "interactive_deadline_s": interactive_deadline,
                    "jobs_with": len(lat_with),
                    "jobs_without": len(lat_without),
                },
                "sweep_ok": report.ok,
                "sweep_failures": report.failures,
                "train_elastic_ok": train_elastic_ok,
                "note": "examples/s windows measured on the SAME "
                        "live run as capacity joins mid-flight; "
                        "re-shard happens at a step boundary via "
                        "checkpoint-restore, zero process restarts. "
                        "CPU stub shard executor (20 ms/file), so "
                        "the scaling RATIO is the claim; the p99 "
                        "bound is against the interactive SLO class "
                        "deadline",
            }
        finally:
            await cluster.stop()
            shutil.rmtree(root, ignore_errors=True)

    out["cluster_training"] = asyncio.run(run())


def _bench_signal_plane(out, *, base_port=29960, n_nodes=4):
    """SLO signal plane (round 19): burn-rate alerts, the lying-worker
    cross-check, ledger failover, and alert-stream determinism.

    Four arms on one CPU stub cluster (plus one pure replay):

    - OVERLOAD: open-loop arrivals past pool capacity shed at the
      door; the leader's burn monitors must FIRE a typed
      ``slo_burn_rate`` alert carrying a flight-recorder exemplar
      trace id (an alert you cannot drill into is a page without a
      lead);
    - LIAR: one worker's ACKs report pre-stall exec walls (the chaos
      ``liar`` seam) while its real walls carry a ~0.8 s stall; the
      leader's ACK-wall cross-check must flag it as ``metrics_liar``
      WHILE its self-reported walls still z-score healthy — proof the
      verdict used the leader's own clock, not the worker's word;
    - FAILOVER: the leader is killed while the liar alert fires; the
      promoted leader must have inherited the firing row over the
      ALERT relay and must resolve it (organically once the liar is
      healed and clean evaluations accumulate, with a direct
      ``resolve_alert`` fallback recorded as such);
    - REPLAY: the same synthetic observation schedule driven twice
      through ``replay_alert_stream`` must produce byte-identical
      event streams containing at least one fire AND one resolve.

    claim_check gates the block from round 19."""
    import asyncio
    import random
    import shutil

    from dml_tpu import tracing as trc
    from dml_tpu.cluster.chaos import STUB_MODEL, LocalCluster
    from dml_tpu.config import Timing
    from dml_tpu.ingress import loadgen
    from dml_tpu.ingress.slo import SLOClass
    from dml_tpu.signal import replay_alert_stream

    root = f"/tmp/dml_tpu_bench_signal_{os.getpid()}"
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root, exist_ok=True)

    async def run():
        cluster = LocalCluster(
            n_nodes, root, base_port, with_ingress=True,
            timing=Timing(ping_interval=0.2, ack_timeout=0.3,
                          cleanup_time=1.0, leader_rpc_timeout=10.0),
            # TIGHT interactive SLO: offered load must exceed what the
            # pool can serve IN-DEADLINE (the burn definition), not
            # raw completion capacity — the stub backend absorbs any
            # driveable qps (p50 ~18 ms at 200 qps), so burn comes
            # from a strict 20 ms budget, the way a real pager is
            # provisioned against a latency SLO
            ingress_classes={
                "interactive": SLOClass(
                    "interactive", deadline_s=0.02,
                    queue_limit=64, linger_s=0.0),
            },
        )
        block = {"nodes": n_nodes}
        loop = asyncio.get_running_loop()
        try:
            await cluster.start()
            await cluster.wait_for(cluster.converged, 20.0,
                                   "signal bench convergence")
            client = cluster.client()
            await client.store.put_bytes("img.jpeg", b"stub-bytes",
                                         timeout=20.0)

            def leader_sn():
                u = cluster.leader_uname()
                return cluster.nodes.get(u) if u else None

            async def wait_row(name, pred, timeout):
                # poll the CURRENT leader's ledger for a row (any
                # state — rows persist after resolve, so a fast
                # fire->resolve cycle still counts as fired)
                deadline = loop.time() + timeout
                while loop.time() < deadline:
                    sn = leader_sn()
                    if sn is not None:
                        for row in sn.jobs.signal.alerts.rows():
                            if row.get("name") == name and pred(row):
                                return row
                    await asyncio.sleep(0.2)
                return None

            # ---- arm 1: overload -> burn-rate alert with exemplar ----
            trc.TRACER.configure(sample_rate=1.0, seed=21)
            trc.TRACER.reset()
            sat = loadgen.open_loop_trace(
                21, duration_s=8.0, rate_qps=200.0, model=STUB_MODEL
            )

            async def submit_one(a):
                return await loadgen.drive_one(
                    client.ingress, a, submit_timeout=8.0,
                    wait_timeout=45.0,
                )

            load_task = asyncio.create_task(
                loadgen.run_open_loop(submit_one, sat),
                name="signal-overload",
            )
            fired = await wait_row(
                "slo_burn_rate", lambda r: bool(r.get("exemplar")), 25.0
            )
            outcomes, wall = await load_task
            ov = loadgen.summarize(outcomes, wall)
            block["overload"] = {
                "seed": 21, "rate_qps": 200.0,
                "deadline_s": 0.02, "n": ov["n"],
                "shed": ov["shed"], "completed": ov["completed"],
                "shed_ratio": ov["shed_ratio"],
            }
            block["alert_fired_ok"] = fired is not None
            block["exemplar_trace_id"] = (fired or {}).get("exemplar")
            block["fired_alert"] = {
                k: (fired or {}).get(k)
                for k in ("name", "labels", "severity", "summary")
            }

            # ---- arm 2: lying worker flagged by the ACK cross-check --
            lsn = leader_sn()
            leader_u = lsn.node.me.unique_name
            sb = lsn.node.standby_node()
            standby_u = sb.unique_name if sb is not None else None
            liar_u = next(
                u for u in sorted(cluster.nodes)
                if u not in (leader_u, standby_u)
            )
            cluster.nodes[liar_u].jobs.liar_extra_s = 0.8

            async def jobs_round(n_jobs, n_queries):
                for _ in range(n_jobs):
                    c = cluster.client()
                    jid = await c.jobs.submit_job(
                        STUB_MODEL, n_queries, timeout=10.0, retries=3)
                    await c.jobs.wait_job(jid, timeout=60.0)

            liar_row = None
            for _ in range(6):
                await jobs_round(2, 24)
                liar_row = await wait_row(
                    "metrics_liar",
                    lambda r: (r.get("labels") or {}).get("node") == liar_u,
                    3.0,
                )
                if liar_row is not None:
                    break
            zs = lsn.jobs.signal.health.zscores()
            liar_z = zs.get(liar_u)
            block["liar"] = {
                "worker": liar_u, "extra_s": 0.8,
                "summary": (liar_row or {}).get("summary"),
                "self_report_z": (
                    round(liar_z, 2) if liar_z is not None else None),
                "pool_z": {w: round(z, 2) for w, z in sorted(zs.items())},
            }
            block["liar_flagged_ok"] = liar_row is not None
            # the liar's SELF-reported walls must still look healthy —
            # the detection has to come from the leader-observed side
            block["liar_self_report_clean"] = (
                liar_z is not None
                and abs(liar_z) < lsn.jobs.signal.health.z_fire
            )

            # ---- arm 3: alert ledger survives leader failover --------
            await asyncio.sleep(0.5)  # let the standby relay land
            await cluster.crash_node(leader_u)
            await cluster.wait_for(
                lambda: cluster.leader_uname() not in (None, leader_u),
                20.0, "signal bench leader promotion",
            )
            sn2 = leader_sn()
            inherited = sn2.jobs.signal.alerts.is_firing(
                "metrics_liar", {"node": liar_u}
            )
            # heal the liar, then drive ACKs through the promoted
            # leader: its seeded hysteresis must resolve the inherited
            # row once clean evaluations accumulate
            for sn in cluster.nodes.values():
                sn.jobs.liar_extra_s = 0.0
            resolve_mode = None
            if inherited:
                await jobs_round(2, 16)
                deadline = loop.time() + 10.0
                while loop.time() < deadline:
                    if not sn2.jobs.signal.alerts.is_firing(
                        "metrics_liar", {"node": liar_u}
                    ):
                        resolve_mode = "organic"
                        break
                    await asyncio.sleep(0.2)
                if resolve_mode is None and sn2.jobs.signal.resolve_alert(
                    "metrics_liar", {"node": liar_u}
                ):
                    resolve_mode = "manual"
            block["failover"] = {
                "killed_leader": leader_u,
                "promoted_leader": cluster.leader_uname(),
                "inherited_firing": inherited,
                "resolve_mode": resolve_mode,
            }
            block["ledger_survived_ok"] = bool(
                inherited and resolve_mode is not None
            )
        finally:
            await cluster.stop()
            shutil.rmtree(root, ignore_errors=True)
        return block

    block = asyncio.run(run())

    # ---- arm 4: seed-determinism of the alert stream (pure replay) --
    def synth_ticks(seed, n=120):
        rng = random.Random(seed)
        ticks = []
        totals = {"interactive": 0.0, "batch": 0.0}
        bads = {"interactive": 0.0, "batch": 0.0}
        for i in range(n):
            tick = {}
            for scope in ("interactive", "batch"):
                totals[scope] += rng.randint(5, 15)
                if scope == "interactive" and 20 <= i < 45:
                    bads[scope] += rng.randint(3, 9)
                tick[scope] = {
                    "bad": bads[scope], "total": totals[scope],
                    "exemplar": f"trace-{seed}-{i}",
                }
            ticks.append(tick)
        return ticks

    s1 = replay_alert_stream(synth_ticks(5))
    s2 = replay_alert_stream(synth_ticks(5))
    b1 = json.dumps(s1, sort_keys=True)
    b2 = json.dumps(s2, sort_keys=True)
    fires = sum(1 for e in s1 if e.get("event") == "fire")
    resolves = sum(1 for e in s1 if e.get("event") == "resolve")
    block["replay"] = {
        "seed": 5, "ticks": 120, "events": len(s1),
        "fires": fires, "resolves": resolves,
        "stream_bytes": len(b1),
    }
    block["replay_deterministic_ok"] = bool(
        b1 == b2 and fires > 0 and resolves > 0
    )
    block["signal_ok"] = bool(
        block.get("alert_fired_ok")
        and block.get("liar_flagged_ok")
        and block.get("liar_self_report_clean")
        and block.get("ledger_survived_ok")
        and block.get("replay_deterministic_ok")
    )
    block["note"] = (
        "CPU stub cluster: the alert machinery (windows, burn "
        "monitors, cross-check, relay, lifecycle) is what's measured, "
        "not model throughput; the determinism claim is scoped to "
        "replay_alert_stream (injected clock), since live walls are "
        "not reproducible"
    )
    out["signal_plane"] = block


def _bench_autoscale(out, *, seed=5, base_port=29990):
    """Closed-loop autoscaler (round 20): one seeded diurnal trace
    served twice, plus the pure-replay determinism arm.

    - STATIC: a fixed 3-slot pool rides the full diurnal swing — the
      plateau sheds (SLO-violation minutes) and the trough idles
      (chip-idle minutes); this is the provisioning dilemma the
      controller exists to dissolve;
    - AUTOSCALED: floor 2 / ceiling 4 under ``DIURNAL_AUTOSCALE_
      POLICY`` — burn/backlog pressure admits standby capacity up the
      ramp, idle streaks retire it down the ramp, a single-culprit p99
      re-weights the scheduler. The win condition is strict: beat
      static on BOTH integrals, zero restarts, green invariant sweep;
    - REPLAY: the same synthetic snapshot schedule driven twice
      through ``replay_decision_stream`` must produce byte-identical
      decision streams exercising all three decision kinds.

    claim_check gates the block from round 20."""
    import asyncio

    from dml_tpu.autoscale import replay_decision_stream
    from dml_tpu.cluster.chaos import diurnal_probe

    block = {"seed": seed}
    for mode, port in (("static", base_port),
                       ("autoscaled", base_port + 40)):
        block[mode] = asyncio.run(diurnal_probe(seed, port, mode=mode))
    st, au = block["static"], block["autoscaled"]
    slo_saved = round(
        st["slo_violation_min"] - au["slo_violation_min"], 4)
    idle_saved = round(st["chip_idle_min"] - au["chip_idle_min"], 4)
    block["autoscale_slo_min_saved"] = slo_saved
    block["autoscale_idle_min_saved"] = idle_saved
    applied = au.get("decisions_applied") or {}
    block["decisions_applied"] = applied

    # ---- replay arm: seed-determinism of the decision stream --------
    pool3 = ["h:7001", "h:7002", "h:7003"]

    def tick(t, pool, **kw):
        return {
            "t": float(t), "pool": list(pool),
            "busy": kw.get("busy", []),
            "backlog": kw.get("backlog", {}),
            "arrivals_qps": kw.get("arrivals_qps", {}),
            "burn_firing": kw.get("burn", []),
            "liars": [], "unhealthy": [],
            "culprit_classes": kw.get("culprits", []),
            "class_weights": kw.get("weights", {}),
        }

    def synth_ticks():
        ticks = []
        for i in range(40):
            if i < 6:
                ticks.append(tick(
                    i, pool3, burn=["slo_burn_rate|interactive"]))
            elif i == 10:
                ticks.append(tick(
                    i, pool3 + ["h:7104"],
                    culprits=["interactive"],
                    weights={"batch": 1.0, "interactive": 2.0}))
            elif i < 30:
                ticks.append(tick(i, pool3 + ["h:7104"]))
            else:
                ticks.append(tick(i, pool3))
        return ticks

    s1 = replay_decision_stream(synth_ticks())
    s2 = replay_decision_stream(synth_ticks())
    b1 = json.dumps(s1, sort_keys=True)
    kinds = {e.get("kind") for e in s1}
    block["replay"] = {
        "ticks": 40, "events": len(s1),
        "kinds": sorted(kinds), "stream_bytes": len(b1),
    }
    block["replay_deterministic_ok"] = bool(
        b1 == json.dumps(s2, sort_keys=True)
        and {"scale_out", "scale_in", "reallocate"} <= kinds
    )
    block["autoscale_ok"] = bool(
        st.get("sweep_ok") and au.get("sweep_ok")
        and st.get("restarts") == 0 and au.get("restarts") == 0
        and slo_saved > 0 and idle_saved > 0
        and applied.get("scale_out", 0) >= 1
        and applied.get("scale_in", 0) >= 1
        and block["replay_deterministic_ok"]
    )
    block["note"] = (
        "CPU stub cluster with a slowed backend sized so the diurnal "
        "plateau genuinely saturates a 3-slot pool; the decision loop "
        "(hysteresis, ledger, actuation, relay) is what's scored, and "
        "the determinism claim is scoped to replay_decision_stream "
        "(injected clock), since live cluster walls are not "
        "reproducible"
    )
    out["autoscale"] = block


def _bench_control_plane_scale(
    out, *, ns=(16, 64, 128), base_port=29500, seed=1, measure_s=3.0,
    churn_nodes=64, churn_rate=2.0, churn_duration=10.0,
):
    """Control-plane scale matrix (ROADMAP item 5): bring an N-node
    membership-only LocalCluster up under BOTH gossip protocols —
    "full" (the reference full-table piggyback) and "delta" (bounded
    freshness-prioritized piggyback + random epidemic ping, the
    product default) — at N ∈ {16, 64, 128}, and score per cell:
    gossip convergence wall, steady-state control-plane bytes/node/s,
    cluster-wide failure-detection latency, election wall, and the
    leader's metrics-aggregation wall + ingress bytes for direct
    bounded fan-out vs two-level relay aggregation. Then a sustained
    CHURN run (seeded join/leave stream, store services up) proves
    the invariants — exactly one leader, no lost store files, no dead
    coroutines — hold while the membership plane never settles.

    Verdicts claim_check holds round-12+ artifacts to: the delta
    protocol's bytes/node/s strictly below full-table at N >= 64,
    failure detection within 1.5x of small-N, the relay metrics wall
    sub-linear in N, and a green churn sweep. CPU-only; every N runs
    the same SCALE timing envelope so walls compare across N."""
    from dml_tpu.cluster.chaos import (
        SCALE_TIMING, churn_plan, control_plane_probe_sync,
        run_plan_sync,
    )

    matrix = {}
    port = base_port
    for n in ns:
        row = {}
        for proto in ("full", "delta"):
            row[proto] = control_plane_probe_sync(
                n, port, seed=seed, protocol=proto, measure_s=measure_s,
            )
            port += n + 12
        matrix[str(n)] = row

    churn_rep = run_plan_sync(
        churn_plan(seed, n_nodes=churn_nodes, rate_per_s=churn_rate,
                   duration=churn_duration, with_jobs=False),
        base_port=port,
        timing=SCALE_TIMING,
        services="store",
    )
    churn = {
        "n_nodes": churn_nodes,
        "rate_per_s": churn_rate,
        "duration_s": churn_duration,
        "crash_restart_pairs": sum(
            1 for e in churn_rep.plan.events if e.kind == "crash"
        ),
        "ok": churn_rep.ok,
        "failures": churn_rep.invariants.failures,
        "wall_s": round(churn_rep.wall_s, 1),
    }

    small, big = str(ns[0]), str(ns[-1])

    def cell(n, proto, key, default=None):
        v = matrix.get(n, {}).get(proto, {}).get(key)
        return v if v is not None else default

    def ratio(a, b):
        return round(a / b, 3) if a and b else None

    bytes_vs_full = {
        n: ratio(cell(n, "delta", "bytes_per_node_s"),
                 cell(n, "full", "bytes_per_node_s"))
        for n in matrix
    }
    detect_small = cell(small, "delta", "detect_s")
    detect_big = cell(big, "delta", "detect_s")

    def mcell(n, mode, key):
        return (matrix[n]["delta"].get(f"metrics_{mode}") or {}).get(key)

    # sub-50ms walls are below the sim envelope's measurement
    # resolution (event-loop jitter + 250ms ping bursts on one core);
    # the sub-linearity ratio floors both ends there so it reflects
    # protocol growth, not scheduler noise
    mw_floor = 0.05
    mw_small = mcell(small, "relay", "wall_s")
    mw_big = mcell(big, "relay", "wall_s")
    mi_big_direct = mcell(big, "direct", "leader_ingress_bytes")
    mi_big_relay = mcell(big, "relay", "leader_ingress_bytes")
    straggler = matrix[big]["delta"].get("metrics_straggler") or {}
    strag_ratio = ratio(
        straggler.get("serial_wall_s"), straggler.get("relay_wall_s")
    )
    n_ratio = int(big) / int(small)
    detect_ratio = ratio(detect_big, detect_small)
    metrics_ratio = ratio(
        max(mw_big, mw_floor) if mw_big is not None else None,
        max(mw_small, mw_floor) if mw_small is not None else None,
    )
    verdicts = {
        # delta strictly below full-table traffic at every N >= 64
        "bytes_below_full_at_64plus": all(
            v is not None and v < 1.0
            for n, v in bytes_vs_full.items() if int(n) >= 64
        ),
        # big-N failure detection within 1.5x of small-N
        "detect_within_1p5x_of_small_n": (
            detect_ratio is not None and detect_ratio <= 1.5
        ),
        # metrics-pull wall grows slower than N on the healthy
        # cluster — and with dead peers on the list, the aggregated
        # pull stays bounded by ~one timeout while the serial shape
        # pays one PER straggler (that is what used to melt)
        "metrics_wall_sublinear": (
            metrics_ratio is not None and metrics_ratio < n_ratio
            and strag_ratio is not None and strag_ratio > 1.5
        ),
        "churn_green": bool(churn["ok"]),
    }
    out["control_plane_scale"] = {
        "ns": list(ns),
        "seed": seed,
        "matrix": matrix,
        "churn": churn,
        "bytes_vs_full_by_n": bytes_vs_full,
        "detect_ratio_vs_small_n": detect_ratio,
        "metrics_wall_ratio_vs_small_n": metrics_ratio,
        "metrics_wall_floor_s": mw_floor,
        "metrics_straggler": straggler,
        "straggler_serial_vs_relay": strag_ratio,
        "relay_vs_direct_ingress": ratio(mi_big_direct, mi_big_relay),
        "scale_converge_s": cell(big, "delta", "converge_s"),
        "scale_detect_s": detect_big,
        "scale_election_s": cell(big, "delta", "election_s"),
        "scale_bytes_per_node_s": cell(big, "delta", "bytes_per_node_s"),
        "scale_metrics_wall_s": mw_big,
        "verdicts": verdicts,
        "scale_ok": all(verdicts.values()),
        "note": "membership-only nodes for the N x protocol matrix "
                "(services=core; store/jobs planes scored by churn + "
                "the small-N sections); SCALE timing envelope (ping "
                "250ms, cleanup 2.5s) shared by every N, so walls "
                "measure protocol rounds, comparable across N",
    }


async def _kv_cache_phase(cluster, crashed_leader):
    """The `request_serving` section's round-17 phase: multi-turn
    session traffic against a REAL continuous-batching LMBackend with
    the worker-resident KV prefix cache, warm vs cold on the same
    seeded growing-history trace (ingress/loadgen.py
    `multi_turn_trace`/`run_sessions`).

    Measurement discipline: each arm runs the trace TWICE and scores
    the second pass — the first pass absorbs the arm's one-time XLA
    compiles (cold prefill buckets / warm suffix-prefill shapes), so
    the TTFT comparison measures prefill work, not compiler walls.
    The warm arm's warmup also seeds the cache, so the measured pass
    hits from turn 1 — which is exactly the steady multi-turn state
    the cache exists for. Equality: warm transcripts must be token-
    identical to the cold run's AND to client-side `generate()`
    references (the LMServer exactness contract end-to-end through
    the front door). The failover sub-case reruns warm sessions with
    the leader killed mid-session: relayed session affinity + turn
    retries must keep the transcripts token-identical."""
    import asyncio

    import jax.numpy as jnp
    import numpy as np

    from dml_tpu.inference.generate import LMConfig, generate
    from dml_tpu.inference.lm_backend import LMBackend, lm_spec_parts
    from dml_tpu.ingress import loadgen

    # the phase-4 failover left the old leader down: bring it back so
    # the phase runs on the full pool (its own kill comes later)
    if crashed_leader and crashed_leader not in cluster.nodes:
        await cluster.restart_node(crashed_leader)
    await cluster.wait_for(
        cluster.converged, 30.0, "kv-cache phase convergence"
    )
    # big enough that prefill dominates TTFT on CPU, small enough to
    # stay inside the section budget; identical deterministic weights
    # on every node (the lm_spec_parts seed contract)
    spec = {
        "name": "KvLM", "vocab_size": 256, "d_model": 384,
        "n_heads": 8, "n_kv_heads": 4, "n_layers": 5, "d_ff": 768,
        "dtype": "float32", "seed": 5,
    }
    params, cfg = lm_spec_parts(spec)
    backends = {}
    from dml_tpu.ingress.slo import SLOClass

    for uname, sn in cluster.nodes.items():
        be = LMBackend(
            params, cfg, max_new_tokens=32, max_slots=4, max_len=512,
            chunk=8, kv_cache_bytes=256 << 20,
        )
        be.set_kv_cache_enabled(False)  # cold arm first
        sn.jobs.register_lm(
            "KvLM", backend=be.backend, cost=be.cost(),
            patterns=("*.tokens.txt", "ingress_*.req"),
        )
        backends[uname] = be
        if sn.ingress is not None:
            # the phase measures PREFILL work, so the batch tier's
            # 100 ms coalescing linger (a formation knob, identical
            # on both arms) is trimmed to keep the TTFT comparison
            # about the compute the cache removes
            sn.ingress.classes["batch"] = SLOClass(
                "batch", deadline_s=30.0, queue_limit=4096,
                linger_s=0.02,
            )
    client = cluster.client()
    trace = loadgen.multi_turn_trace(
        21, n_sessions=3, turns=5, model="KvLM", slo="batch",
        start_gap_s=0.4, think_s=0.6, suffix_len=16, vocab=256,
        budget=32,
    )

    def mean_ttft_ms(outcomes):
        tt = [
            o.ttft_s for o in outcomes
            if o.turn >= 2 and o.ttft_s is not None
            and o.terminal == loadgen.TERMINAL_COMPLETED
        ]
        return round(sum(tt) / len(tt) * 1e3, 1) if tt else None

    async def run_arm():
        return await loadgen.run_sessions(
            client.ingress, trace, wait_timeout=60.0,
        )

    def expected_transcripts(tr):
        """Client-side generate() references for a multi-turn trace —
        the chain every serving path must reproduce token-for-token."""
        by_sess = {}
        for a in tr.arrivals:
            by_sess.setdefault(a.session, []).append(a)
        out = {}
        for sess, turns in by_sess.items():
            history = []
            out[sess] = []
            for a in sorted(turns, key=lambda x: x.turn):
                prompt = history + list(a.suffix)
                toks = [int(t) for t in np.asarray(generate(
                    params, cfg,
                    jnp.asarray(np.asarray(prompt, np.int32)[None]),
                    int(a.budget),
                ))[0]]
                out[sess].append(toks)
                history = prompt + toks
        return out

    expect = expected_transcripts(trace)

    # Pre-warm every node's compile shapes OUTSIDE both arms (one
    # XLA compile per distinct dispatch shape per server; at this
    # model size a first-turn compile wall would eat the session's
    # turn timeout, and it is exactly the thing the warmup/measured
    # split exists to exclude). Cold shapes: the prompt buckets the
    # trace will hit + the chunk program, driven through the RAW
    # server (cache still disabled). Warm shapes: the suffix-prefill
    # (prefix-bucket, suffix-bucket) pairs, driven through the
    # prefiller directly — it is pure, so nothing touches the cache.
    def _prewarm_cold(be):
        import numpy as _np

        prompts = [
            _np.arange(n, dtype=_np.int32) % 256
            for n in (16, 64, 112, 208)
        ]
        be.server.run(be.server.submit_many(prompts, 2))

    await asyncio.gather(*(
        asyncio.to_thread(_prewarm_cold, be)
        for be in backends.values()
    ))

    # cold arm: warmup pass (residual walls), then the measured pass
    await run_arm()
    cold_out, _, cold_tx = await run_arm()
    # warm arm: enable the cache everywhere; warmup seeds it + the
    # measured pass scores steady state
    for be in backends.values():
        be.set_kv_cache_enabled(True)

    def _prewarm_warm(be):
        import numpy as _np

        kv = be.cfg.kv_heads
        hd = be.cfg.head_dim
        # prefix buckets 16..256 x suffix buckets 16/32: the measured
        # pass sees BOTH the fresh-turn shape (suffix = new turn, ~17
        # tokens) and the rerun shape (prompt fully covered by a
        # warmup-pass entry, suffix clamps to 1 token)
        for m in (12, 24, 48, 96, 144, 200):
            rows = {
                f"block_{i}": {
                    "k": _np.zeros((kv, m, hd), _np.float32),
                    "v": _np.zeros((kv, m, hd), _np.float32),
                }
                for i in range(be.cfg.n_layers)
            }
            for ts in (1, 17):
                be.server._warm.prefiller(
                    be.server.params, rows, m,
                    _np.arange(max(ts, 1), dtype=_np.int32) % 256,
                )

    await asyncio.gather(*(
        asyncio.to_thread(_prewarm_warm, be)
        for be in backends.values()
    ))
    await run_arm()
    stats0 = [be.kv_cache_stats() for be in backends.values()]
    warm_out, _, warm_tx = await run_arm()
    stats = [be.kv_cache_stats() for be in backends.values()]
    # deltas over the MEASURED pass only (the warmup pass paid the
    # cold-cache first-turn misses on purpose)
    hits = sum(s["hits"] for s in stats) - sum(
        s["hits"] for s in stats0
    )
    misses = sum(s["misses"] for s in stats) - sum(
        s["misses"] for s in stats0
    )
    tokens_saved = sum(s["tokens_saved"] for s in stats) - sum(
        s["tokens_saved"] for s in stats0
    )
    ttft_cold = mean_ttft_ms(cold_out)
    ttft_warm = mean_ttft_ms(warm_out)
    warm_sum = loadgen.summarize(warm_out, 1.0)
    kv = {
        "model": spec["name"], "sessions": 3, "turns": 5,
        "trace_seed": 21,
        "hit_ratio": (
            round(hits / max(1, hits + misses), 4) if hits else 0.0
        ),
        "hits": hits, "misses": misses,
        "tokens_saved": int(tokens_saved),
        "cache_bytes": sum(s["bytes"] for s in stats),
        "evictions": sum(s["evictions"] for s in stats),
        "ttft_ms_cold": ttft_cold,
        "ttft_ms_warm": ttft_warm,
        "warm_vs_cold_ttft": (
            round(ttft_cold / ttft_warm, 2)
            if ttft_cold and ttft_warm else None
        ),
        "warm_equals_cold": (
            cold_tx == warm_tx == expect and bool(cold_tx)
        ),
        "by_turn_warm": warm_sum.get("by_turn"),
        "by_turn_cold": loadgen.summarize(cold_out, 1.0).get("by_turn"),
        # per-request TPOT percentiles over the warm sessions'
        # client-observed stream chunks (loadgen Outcome.tpot_s):
        # TTFT scores queue+prefill, this scores the decode loop
        "tpot_ms_warm": warm_sum.get("tpot_ms"),
    }
    # ---- failover sub-case: leader killed MID-SESSION (warm) --------
    fail_trace = loadgen.multi_turn_trace(
        22, n_sessions=2, turns=4, model="KvLM", slo="batch",
        start_gap_s=0.3, think_s=1.0, suffix_len=16, vocab=256,
        budget=32,
    )
    fo_expect = expected_transcripts(fail_trace)
    await cluster.wait_for(
        lambda: cluster.leader_uname() is not None, 20.0,
        "kv failover leader agreement",
    )
    leader1 = cluster.leader_uname()
    # the client must survive the kill — route around it if needed
    fo_client = cluster.client(avoid=(leader1,))

    async def killer():
        await asyncio.sleep(2.0)
        if leader1 in cluster.nodes:
            await cluster.crash_node(leader1)

    kill = asyncio.ensure_future(killer())
    fo_out, _, fo_tx = await loadgen.run_sessions(
        fo_client.ingress, fail_trace, wait_timeout=60.0,
        turn_retries=5,
    )
    await kill
    fo_completed = sum(
        1 for o in fo_out
        if o.terminal == loadgen.TERMINAL_COMPLETED
    )
    kv["failover"] = {
        "killed_leader": leader1,
        "completed": fo_completed,
        "turns_total": len(fail_trace.arrivals),
        "warm_equals_cold": fo_tx == fo_expect,
    }
    for be in backends.values():
        be.close()
    return kv


def _bench_request_serving(out, *, base_port=28741, n_nodes=4):
    """Per-request serving under seeded open-loop load through the
    request front door (dml_tpu/ingress/): clients submit individual
    requests with SLO classes against one chaos.LocalCluster (stub
    backend — CPU-only; the admission/formation/completion machinery
    is what's measured, like the chaos section), scoring the regime
    the Gemma-on-TPU comparison scores (arxiv 2605.25645): tail
    latency percentiles and goodput under sustained arrival, not
    batch-job wall clock.

    Four phases on ONE cluster:

    - light load, continuous formation vs the naive fixed-size-batch
      baseline (same trace): continuous must win p99 — at 3 qps a
      fixed batch of 8 waits ~deadline to fill while the hungry-
      pipeline path serves at single-request latency;
    - saturation (arrivals past pool capacity), both modes: full
      batches either way, so throughput must MATCH (the same
      machinery that serves one request fast serves thousands at the
      committed rate) — admission sheds the overflow with typed
      rejections, never timeouts;
    - sustained mixed-class load: the headline p50/p95/p99, goodput,
      and shed ratio the compact summary carries;
    - leader failover MID-TRAFFIC: the leader is crashed while
      requests are in flight; every submitted request must reach
      exactly one terminal (completed or explicitly rejected — a
      client-side LOST conversion is an explicit typed terminal),
      never silently hang. claim_check validates all of it from
      round 9.
    """
    import asyncio
    import shutil
    import tempfile

    from dml_tpu import tracing as trc
    from dml_tpu.cluster.chaos import STUB_MODEL, LocalCluster
    from dml_tpu.config import Timing
    from dml_tpu.ingress import loadgen

    tmp = tempfile.mkdtemp(prefix="dml_req_bench_")

    def outcome_counts(summary):
        return {
            k: summary[k] for k in ("n", "completed", "shed", "rejected")
        }

    async def run():
        cluster = LocalCluster(
            n_nodes, tmp, base_port, with_ingress=True,
            timing=Timing(ping_interval=0.2, ack_timeout=0.3,
                          cleanup_time=1.0, leader_rpc_timeout=10.0),
        )
        await cluster.start()
        await cluster.wait_for(
            cluster.converged, 20.0, "request bench convergence"
        )
        client = cluster.client()
        await client.store.put_bytes("img.jpeg", b"stub-bytes",
                                     timeout=20.0)

        def set_formation(mode):
            for sn in cluster.nodes.values():
                if sn.ingress is not None:
                    sn.ingress.former.mode = mode

        async def submit_one(a):
            # the shared submit/wait/classify driver (one copy with
            # the CLI request-load verb); client-side deadline clock
            return await loadgen.drive_one(
                client.ingress, a, submit_timeout=8.0, wait_timeout=45.0,
                deadline_by_class={"interactive": 2.0, "batch": 30.0},
            )

        def quiescent():
            # phases must not bleed: no scheduler backlog and no
            # in-flight ingress requests anywhere before the next
            # trace starts, or a saturation phase's tail poisons the
            # following phase's percentiles
            for sn in cluster.nodes.values():
                sch = sn.jobs.scheduler
                if sch.jobs or any(sch.queues.values()):
                    return False
                if sn.ingress is not None and (
                    sn.ingress._active or sn.ingress.former.forming
                ):
                    return False
            return True

        async def run_trace(trace, mode):
            set_formation(mode)
            outcomes, wall = await loadgen.run_open_loop(
                submit_one, trace
            )
            try:
                await cluster.wait_for(quiescent, 30.0, "phase drain")
            except AssertionError:  # wait_for timeout
                pass  # a wedged tail is the next phase's problem; the
                # outcomes above are already terminal
            await asyncio.sleep(0.3)
            return outcomes, wall

        block = {"nodes": n_nodes, "model": STUB_MODEL, "classes": {
            "interactive": {"deadline_s": 2.0},
            "batch": {"deadline_s": 30.0},
        }}
        try:
            # ---- phase 1: light load, continuous vs fixed ------------
            light = loadgen.open_loop_trace(
                11, duration_s=8.0, rate_qps=3.0, model=STUB_MODEL
            )
            cont = loadgen.summarize(*await run_trace(light, "continuous"))
            fixed = loadgen.summarize(*await run_trace(light, "fixed"))
            block["light_load"] = {
                "rate_qps": 3.0, "seed": 11,
                "continuous": cont, "fixed_batch": fixed,
                "p99_ms_continuous": cont["latency_ms"]["p99"],
                "p99_ms_fixed": fixed["latency_ms"]["p99"],
            }
            # ---- phase 2: saturation, throughput must match ----------
            sat = loadgen.open_loop_trace(
                12, duration_s=6.0, rate_qps=220.0, model=STUB_MODEL
            )
            sat_cont = loadgen.summarize(*await run_trace(sat, "continuous"))
            sat_fixed = loadgen.summarize(*await run_trace(sat, "fixed"))
            block["saturation"] = {
                "rate_qps": 220.0, "seed": 12,
                "continuous": sat_cont, "fixed_batch": sat_fixed,
                "goodput_qps_continuous": sat_cont["goodput_qps"],
                "goodput_qps_fixed": sat_fixed["goodput_qps"],
            }
            # ---- phase 3: sustained mixed-class load (headline) ------
            main = loadgen.open_loop_trace(
                13, duration_s=10.0, rate_qps=60.0, model=STUB_MODEL,
                slo_mix={"interactive": 0.85, "batch": 0.15},
                session_pct=20.0,
            )
            # the headline sustained phase runs TRACED (sample
            # rate 1.0): every request's cross-node trace is collected
            # so the p99 cohort can be attributed stage by stage
            trc.TRACER.configure(sample_rate=1.0, seed=13)
            trc.TRACER.reset()
            sus_outcomes, sus_wall = await run_trace(main, "continuous")
            leader_sn = cluster.nodes.get(cluster.leader_uname())
            view = {"spans": [], "traces": {}}
            if leader_sn is not None:
                view = await leader_sn.node.pull_cluster_traces(
                    max_spans=2048, timeout=5.0
                )
            trace_stages = {
                tid: trc.stage_breakdown(sp)
                for tid, sp in view["traces"].items()
            }
            sustained = loadgen.summarize(
                sus_outcomes, sus_wall, trace_stages=trace_stages
            )
            block["sustained"] = {
                "rate_qps": 60.0, "seed": 13, **sustained,
            }
            block["p50_ms"] = sustained["latency_ms"]["p50"]
            block["p95_ms"] = sustained["latency_ms"]["p95"]
            block["p99_ms"] = sustained["latency_ms"]["p99"]
            block["goodput_qps"] = sustained["goodput_qps"]
            block["shed_ratio"] = sustained["shed_ratio"]
            # ---- phase 3a: tracing block -----------------------------
            # p99 stage attribution (joined via pulled cluster traces,
            # terminal-carried stages as fallback), exemplar coverage
            # of every deadline miss, the flight-recorder budget
            # verdict, and a sampling=0 overhead rerun of the SAME
            # trace: traced-vs-untraced p50/p99 must sit within noise
            misses = [
                o for o in sus_outcomes
                if o.terminal == loadgen.TERMINAL_COMPLETED
                and not o.deadline_met
            ]
            def _miss_covered(o):
                sp = view["traces"].get(o.trace_id) or []
                return any(
                    ev[0] == "deadline_miss"
                    for d in sp for ev in (d.get("ev") or ())
                )
            miss_cov = (
                sum(1 for o in misses if _miss_covered(o)) / len(misses)
                if misses else 1.0
            )
            attrib = sustained.get("p99_attribution") or {}
            rec = trc.TRACER.stats()
            # (the sampling=0 overhead rerun happens AFTER phase 3b:
            # the weighted-vs-FIFO class_fair comparison needs its two
            # runs back to back, same as before tracing existed)
            block["tracing"] = {
                "sample_rate": 1.0,
                "spans_collected": len(view["spans"]),
                "traces_collected": len(view["traces"]),
                "p99_attribution": attrib,
                "p99_attrib_ok": (
                    isinstance(attrib.get("attributed_fraction"),
                               (int, float))
                    and attrib["attributed_fraction"] >= 0.9
                ),
                "deadline_misses": len(misses),
                "miss_exemplar_coverage": round(miss_cov, 4),
                "recorder": {
                    k: rec[k] for k in (
                        "span_budget", "peak_spans", "dropped",
                        "recorded", "within_budget",
                    )
                },
            }
            # ---- phase 3b: per-class weighted fair share vs FIFO ----
            # same mixed-class trace with the scheduler's class
            # weights DISABLED (one FIFO per model queue — the pre-PR
            # behavior): interactive p99 must be better under the
            # weighted split, which is the whole point of giving
            # classes weighted shares of the queue
            for sn in cluster.nodes.values():
                sn.jobs.scheduler.class_weights = {}
            fifo = loadgen.summarize(*await run_trace(main, "continuous"))
            for sn in cluster.nodes.values():
                sn.jobs.scheduler.class_weights = {
                    "interactive": 3.0, "batch": 1.0,
                }

            def _class_p99(summary, cls):
                c = (summary.get("by_class") or {}).get(cls) or {}
                return (c.get("latency_ms") or {}).get("p99")

            p99_w = _class_p99(sustained, "interactive")
            p99_f = _class_p99(fifo, "interactive")
            block["class_fair"] = {
                "weights": {"interactive": 3.0, "batch": 1.0},
                "p99_ms_interactive_weighted": p99_w,
                "p99_ms_interactive_fifo": p99_f,
                "goodput_qps_fifo": fifo["goodput_qps"],
                "interactive_p99_improved": (
                    p99_w is not None and p99_f is not None
                    and p99_w < p99_f
                ),
            }
            # ---- phase 3c: tracing overhead rerun --------------------
            # same trace, sampling=0: traced-vs-untraced p50/p99 must
            # sit within noise (the round-14 gate bounds the ratio)
            trc.TRACER.configure(sample_rate=0.0)
            untraced = loadgen.summarize(*await run_trace(main, "continuous"))
            trc.TRACER.configure(sample_rate=1.0)
            p99_t = sustained["latency_ms"]["p99"]
            p99_u = untraced["latency_ms"]["p99"]
            block["tracing"]["overhead"] = {
                "p50_ms_traced": sustained["latency_ms"]["p50"],
                "p99_ms_traced": p99_t,
                "p50_ms_untraced": untraced["latency_ms"]["p50"],
                "p99_ms_untraced": p99_u,
                "p99_traced_vs_untraced": (
                    round(p99_t / p99_u, 3)
                    if isinstance(p99_t, (int, float))
                    and isinstance(p99_u, (int, float)) and p99_u
                    else None
                ),
            }
            # ---- phase 4: leader failover mid-traffic ----------------
            set_formation("continuous")
            fail_trace = loadgen.open_loop_trace(
                14, duration_s=10.0, rate_qps=25.0, model=STUB_MODEL
            )
            try:
                await cluster.wait_for(quiescent, 30.0, "pre-failover drain")
            except AssertionError:  # wait_for timeout: drain what we got
                pass
            # the leader is resolved AFTER the drain, and the phase
            # refuses to run leaderless: a None here (transient SWIM
            # disagreement off the sustained phase) would silently
            # skip the crash and score undisturbed traffic as a green
            # "failover" — the claim gate must never pass un-exercised
            await cluster.wait_for(
                lambda: cluster.leader_uname() is not None, 20.0,
                "pre-failover leader agreement",
            )
            leader0 = cluster.leader_uname()

            async def killer():
                await asyncio.sleep(3.0)
                if leader0 in cluster.nodes:
                    await cluster.crash_node(leader0)

            kill_task = asyncio.ensure_future(killer())
            outcomes, wall = await loadgen.run_open_loop(
                submit_one, fail_trace
            )
            await kill_task
            fo = loadgen.summarize(outcomes, wall)
            # the exactly-once verdict is built from OBSERVATIONS that
            # can actually fail, not from accounting identities
            # (summarize partitions outcomes exhaustively, so
            # "terminals == n" is true by construction):
            #  - terminal_conflicts: any router saw a late COMPLETED
            #    for a request already settled dead (work executed
            #    and delivered after a LOST/rejected terminal);
            #  - completed_missing_result: a completion whose terminal
            #    carried no result payload (the silent-loss class the
            #    router must type as result_unavailable instead);
            #  - and traffic must actually complete across the kill.
            conflicts = sum(
                sn.ingress.terminal_conflicts
                for sn in cluster.nodes.values()
                if sn.ingress is not None
            )
            missing_result = sum(
                1 for o in outcomes
                if o.terminal == loadgen.TERMINAL_COMPLETED
                and not o.has_result
            )
            block["failover"] = {
                "rate_qps": 25.0, "seed": 14,
                "killed_leader": leader0,
                **outcome_counts(fo),
                "lost_to_typed_rejection": sum(
                    1 for o in outcomes
                    if o.terminal == loadgen.TERMINAL_LOST
                ),
                "terminal_conflicts": conflicts,
                "completed_missing_result": missing_result,
                "all_terminal_exactly_once": (
                    fo["completed"] > 0
                    and conflicts == 0
                    and missing_result == 0
                ),
                "completed_after_failover": fo["completed"],
            }
            # ---- phase 5: KV prefix cache — multi-turn warm vs cold --
            # a REAL LMBackend (deterministic TinyLM weights) with the
            # worker-resident prefix cache (inference/kv_cache.py)
            # registered on every node: growing-history session
            # traffic through the same front door, scored warm
            # (suffix-only prefill from cached slabs) vs cold (full
            # re-prefill, cache disabled) on the SAME seeded trace —
            # per-turn TTFT, prefill tokens saved, and the token-
            # equality verdict, plus a leader-kill-mid-session rerun.
            # claim_check gates the block from round 17.
            block["kv_cache"] = await _kv_cache_phase(cluster, leader0)
        finally:
            await cluster.stop()
            shutil.rmtree(tmp, ignore_errors=True)
        return block

    block = asyncio.run(run())
    p99_c = block["light_load"]["p99_ms_continuous"]
    p99_f = block["light_load"]["p99_ms_fixed"]
    # either side can be None (a phase that completed nothing reports
    # no percentiles) — that is a measurement failure the claim gate
    # flags, not a reason to crash away the whole section's data
    block["continuous_vs_fixed_p99"] = (
        round(p99_f / p99_c, 2)
        if isinstance(p99_c, (int, float)) and p99_c
        and isinstance(p99_f, (int, float)) else None
    )
    gf = block["saturation"]["goodput_qps_fixed"]
    gc = block["saturation"]["goodput_qps_continuous"]
    block["saturation_goodput_ratio"] = (
        round(gc / gf, 3) if gf else None
    )
    out["request_serving"] = block


def _bench_cluster_serving(engine, out, *, model="ResNet50",
                           batch=32, big_batch=128, n_queries=512,
                           failure_model=None, base_port=28801):
    """BASELINE config 4's shape on available hardware: a real
    localhost cluster (UDP control plane + TCP data plane + SDFS
    replication) serving a batch=32 ResNet50 job with THE REAL ENGINE
    on the chip, inputs = the reference's own testfiles_more JPEGs
    (synthetic fallback when absent). One chip stands in for the
    reference's 10-VM ring; the 10-node control plane itself is
    exercised in tests/test_jobs_sim.py::test_ten_node_ring_full_stack."""
    import asyncio
    import glob

    async def run():
        from dml_tpu.jobs.service import JobService

        tmp = "/tmp/dml_tpu_bench_cluster"

        def make_jobs(node, store):
            # one SHARED engine across the co-located services (one
            # weights copy per chip) — this is the real product path:
            # prepare (fetch+decode) overlaps the previous batch's
            # in-flight inference at pipeline depth 2
            return JobService(node, store, engine=engine)

        async with _cluster_stack(tmp, base_port, make_jobs) as (cluster, stack):
            srcs = sorted(glob.glob("/root/reference/testfiles_more/*.jpeg"))[:32]
            client_store, client_jobs = stack[-1][1], stack[-1][2]
            if srcs:
                source = "reference testfiles_more"
                for p in srcs:
                    await client_store.put(p, os.path.basename(p))
            else:  # hermetic fallback
                source = "synthetic"
                from PIL import Image
                import numpy as np

                rng = np.random.RandomState(0)
                for i in range(32):
                    p = os.path.join(tmp, f"img_{i}.jpeg")
                    Image.fromarray(
                        rng.randint(0, 255, (256, 256, 3), np.uint8)
                    ).save(p)
                    await client_store.put(p, f"img_{i}.jpeg")
            await client_jobs.set_batch_size(model, batch)
            n_q = n_queries

            async def timed_job(m, n):
                t0 = time.monotonic()
                job_id = await client_jobs.submit_job(m, n)
                done = await client_jobs.wait_job(job_id, timeout=600.0)
                assert done["total_queries"] == n
                return time.monotonic() - t0

            # Four serves, VERDICT r5 item 2's cure. (1) depth-1 with
            # the cache OFF: the reference-faithful serial loop, the
            # historical qps_unpipelined point. (2)+(3) BOTH static
            # depths forced with the decode cache ON — the SAME
            # configuration the adaptive run gets, so (4) adaptive vs
            # best-static is a pure depth-choice comparison: with the
            # cache only on the adaptive side, its savings would mask
            # a wrong depth commit and the claim_check floor could
            # never fire. Run (2) first so the cache's one-time cold
            # fill (32 files) is paid before the static comparison.
            for _, _, j in stack:
                j.set_pipeline_depth(1)
                j.decode_cache_bytes = 0  # reference-faithful serial run
            wall_d1_nocache = await timed_job(model, n_q)
            for _, _, j in stack:
                j.decode_cache_bytes = 256 << 20
            wall_d1 = await timed_job(model, n_q)
            for _, _, j in stack:
                j.set_pipeline_depth(2)
            wall_d2 = await timed_job(model, n_q)
            for _, _, j in stack:
                j.set_pipeline_depth(None)  # adaptive (fresh controller)
                if j.depth_ctl is not None:
                    # probe sized to the job: two phases of 2 counted
                    # ACKs (+ per-worker transition discards) commit
                    # well inside the 16-batch serve, so the artifact
                    # records a full cycle
                    j.depth_ctl.probe_batches = 2
                    j.depth_ctl.min_probe_backlog = 4
                j.batch_timing.clear()  # breakdown = final run only
            wall = await timed_job(model, n_q)
            leader = next(
                (n, s, j) for n, s, j in stack if n.is_leader
            )
            hits = sum(j.decode_cache_hits for _, _, j in stack)
            misses = sum(j.decode_cache_misses for _, _, j in stack)
            wall_best_static = min(wall_d1, wall_d2)
            out["cluster_serving"] = {
                "nodes": 4,
                "input_source": source,
                "queries": n_q,
                "wall_s": round(wall, 2),
                "qps_end_to_end": round(n_q / wall, 1),
                "qps_unpipelined": round(n_q / wall_d1_nocache, 1),
                "qps_depth1_static": round(n_q / wall_d1, 1),
                "qps_pipelined_static": round(n_q / wall_d2, 1),
                # what the decode cache alone buys at depth 1
                "decode_cache_speedup": round(wall_d1_nocache / wall_d1, 2),
                # what forcing overlap does, cache-matched
                "pipelining_speedup_static": round(wall_d1 / wall_d2, 2),
                # the serving ratio that must never sit below ~1.0:
                # adaptive vs the better forced static, all three runs
                # in the identical cache configuration
                "pipelining_speedup": round(wall_best_static / wall, 2),
                # the probe-and-commit verdict the serve ran under:
                # chosen depth, per-phase probe rates, trigger, and
                # the drift signature it is now watching
                "adaptive": leader[2].depth_controller_stats(),
                "decode_cache_hit_rate": round(hits / max(hits + misses, 1), 3),
                # where each batch's wall time went, from ACK-carried
                # worker timings (VERDICT r2 item 9)
                "breakdown": leader[2].breakdown_stats(),
                "note": "full stack: UDP control plane + SDFS-replicated "
                        "inputs + host JPEG decode + engine on chip. "
                        "qps_unpipelined forces depth 1 with the decode "
                        "cache off (the reference worker loop, "
                        "worker.py:518-537); qps_depth1_static / "
                        "qps_pipelined_static force depths 1 / 2 with "
                        "the cache ON — the same configuration the "
                        "ADAPTIVE run gets, so pipelining_speedup "
                        "(adaptive vs the better static) is a pure "
                        "depth-choice ratio and < 1.0 beyond probe "
                        "noise means the controller chose wrong. "
                        "qps_end_to_end is the adaptive product path: "
                        "the coordinator probes both depths on the "
                        "job's first batches and commits to the "
                        "measured winner (the job wrap-around-samples "
                        "32 files, reference worker.py:188-245)",
            }

            # throughput variant: batch 128 amortizes the per-batch
            # dispatch cost 4x.
            # Compile+warm the big-batch shape BEFORE timing (the C3
            # fanout's engine warmup is async; without this the timed
            # job absorbs a one-time ~30 s compile)
            await asyncio.to_thread(engine.set_batch_size, model, big_batch)
            await client_jobs.set_batch_size(model, big_batch)
            t0 = time.monotonic()
            job_id = await client_jobs.submit_job(model, n_q)
            done = await client_jobs.wait_job(job_id, timeout=600.0)
            wall128 = time.monotonic() - t0
            assert done["total_queries"] == n_q
            out["cluster_serving_b128"] = {
                "queries": n_q,
                "wall_s": round(wall128, 2),
                "qps_end_to_end": round(n_q / wall128, 1),
            }

            # BASELINE config 5: failure injection during LIVE serving
            # (VERDICT r2 item 4) — kill a busy non-leader, non-standby
            # worker mid-job ABRUPTLY (transport closed, no goodbye:
            # the reference's crash case, worker.py:1279-1306) and
            # record completion, requeues, and detection latency.
            # Config 5 names EfficientNet-B4 as the model under
            # failure, exercising model switch + dynamic batching in
            # the same pass (the engine keeps every model resident —
            # switching costs nothing, unlike the reference's reload)
            fmodel = failure_model or model
            if fmodel != model:
                # (re)load the failure model at this job's batch size
                # (the sweep leaves it at b128; padding 32 -> 128 would
                # quadruple each batch's upload).
                # to_thread: a multi-second compile on the event loop
                # would stall SWIM heartbeats past cleanup_time and
                # make the live nodes falsely suspect each other
                await asyncio.to_thread(
                    engine.load_model, fmodel, batch_size=batch,
                    warmup=True,
                )
            await client_jobs.set_batch_size(fmodel, batch)
            # healthy baseline for THIS model (the b32 run above is a
            # different model when failure_model is set — comparing
            # against it would report model-speed delta as failure
            # cost)
            t0 = time.monotonic()
            job_id = await client_jobs.submit_job(fmodel, n_q)
            done = await client_jobs.wait_job(job_id, timeout=600.0)
            healthy_f = time.monotonic() - t0
            assert done["total_queries"] == n_q
            leader_jobs = leader[2]
            standby = leader[1].standby_node()
            client_node = stack[-1][0]
            victim = next(
                (n, s, j) for n, s, j in stack
                if not n.is_leader and n is not client_node
                and (standby is None or n.me.unique_name != standby.unique_name)
            )
            victim_name = victim[0].me.unique_name
            requeues_before = leader_jobs.scheduler.requeue_count
            t0 = time.monotonic()
            job_id = await client_jobs.submit_job(fmodel, n_q)
            # kill once the victim is actually running a batch
            for _ in range(500):
                if victim_name in leader_jobs.scheduler.in_progress:
                    break
                await asyncio.sleep(0.01)
            t_kill = time.monotonic()
            # abrupt kill through the shared chassis (transport closed,
            # no goodbye) — the same crash path the chaos engine uses
            await cluster.crash_node(victim_name)
            # detection latency: kill -> first requeue of its batch.
            # Bounded at 20 s (cleanup_time is 1 s; detection lands in
            # ~2 s) and exits early if the job finishes — a kill that
            # raced completion must be RECORDED as not-injected, not
            # spun on for a minute and emitted as a vacuous pass
            detect_s = None
            while time.monotonic() - t_kill < 20.0:
                if leader_jobs.scheduler.requeue_count > requeues_before:
                    detect_s = time.monotonic() - t_kill
                    break
                if job_id in leader_jobs.scheduler.done_jobs:
                    break
                await asyncio.sleep(0.01)
            done = await client_jobs.wait_job(job_id, timeout=600.0)
            wall_f = time.monotonic() - t0
            assert done["total_queries"] == n_q, "completion under failure"
            requeues = leader_jobs.scheduler.requeue_count - requeues_before
            out["cluster_serving_failure"] = {
                "model": fmodel,
                "queries": n_q,
                "completed": done["total_queries"],
                # False = the victim's work completed before the kill
                # could displace anything (a raced run, not evidence)
                "failure_injected": requeues > 0,
                "killed_worker": victim_name,
                "killed_at_s": round(t_kill - t0, 2),
                "detect_to_requeue_s": (
                    round(detect_s, 2) if detect_s is not None else None
                ),
                "requeues": requeues,
                "wall_s": round(wall_f, 2),
                "qps_end_to_end": round(n_q / wall_f, 1),
                "healthy_wall_s": round(healthy_f, 2),
                "note": "worker killed abruptly mid-job (no leave msg); "
                        "100% completion via SWIM detect -> requeue-at-"
                        "front -> reschedule",
            }

    asyncio.run(run())


def _bench_cluster_lm(out, *, n_prompts=64, new_tokens=32, base_port=28821,
                      lm_overrides=None, steady_s=16.0, ramp_s=2.0,
                      steady_sample_dt=1.0):
    """Distributed LM serving END-TO-END (net-new subsystem, r3
    PARITY row; device-level LM numbers live in `lm.*`): prompt-token
    files in the replicated store, `submit_job` through the SAME
    fair-share scheduler/standby pipeline as image jobs, workers
    decode via the continuous-batching server, outputs merge via
    get_output. Records end-to-end prompts/s and generated tok/s
    through the full stack — the cluster-pipeline analog of
    `cluster_serving` for sequences (the reference has no sequence
    serving at all, SURVEY §0). Uses the bench LM config (198M,
    GQA-4, bf16) so the gap to the device-level decode rate is
    directly readable.

    Two phases (VERDICT r5 item 4): the TRANSIENT comparison
    (interleaved serial/overlap pairs of one n_prompts job — ~1 s of
    wall, mostly prefill/placement) and a STEADY-STATE run: jobs
    continuously refilled for >= `steady_s` seconds of decode past a
    `ramp_s` warm-up window, with a tok/s-vs-wall curve sampled every
    `steady_sample_dt` s — so the transient figure either rises
    toward the device CB ceiling under sustained load or the curve
    shows exactly where the control plane flattens it."""
    import asyncio

    async def run():
        import numpy as np

        from dml_tpu.inference.lm_backend import LMBackend, write_prompt_file
        from dml_tpu.jobs.service import JobService

        lm_spec = {
            "name": "BenchLM", "vocab_size": 32000, "d_model": 1024,
            "n_heads": 16, "n_kv_heads": 4, "n_layers": 12,
            "d_ff": 4096, "dtype": "bfloat16",
            "max_new_tokens": new_tokens, "max_slots": 8,
            "max_len": 256, "seed": 0,
            **(lm_overrides or {}),
        }
        tmp = "/tmp/dml_tpu_bench_cluster_lm"
        # one shared backend: one weights copy + one compile per chip
        be = await asyncio.to_thread(LMBackend.from_spec, lm_spec)

        def make_jobs(node, store):
            jobs = JobService(node, store)
            jobs.register_lm("BenchLM", backend=be.backend, cost=be.cost())
            return jobs

        try:
            async with _cluster_stack(tmp, base_port, make_jobs) as (_, stack):
                client_store, client_jobs = stack[-1][1], stack[-1][2]
                rng = np.random.RandomState(0)
                for i in range(n_prompts):
                    prompt = rng.randint(
                        0, lm_spec["vocab_size"], int(rng.randint(8, 48))
                    )
                    p = os.path.join(tmp, f"prompt_{i}.tokens.txt")
                    write_prompt_file(p, prompt)
                    await client_store.put(p, f"prompt_{i}.tokens.txt")

                async def timed_job():
                    t0 = time.monotonic()
                    job_id = await client_jobs.submit_job(
                        "BenchLM", n_prompts
                    )
                    done = await client_jobs.wait_job(job_id, timeout=600.0)
                    wall = time.monotonic() - t0
                    assert done["total_queries"] == n_prompts
                    merged = await client_jobs.get_output(
                        job_id, os.path.join(tmp, "lm_out.json")
                    )
                    gen = sum(
                        len(v.get("tokens", [])) for v in merged.values()
                    )
                    return wall, gen

                # warm every compile the timed jobs will hit (prefill
                # buckets 16/32/64 for the 8..48-token prompts, the
                # chunk fn, insert) so the serial-vs-overlap ratio
                # compares pipelining, not who paid the XLA compiles
                warm = [
                    os.path.join(tmp, f"warm_{n}.tokens.txt")
                    for n in (8, 20, 40)
                ]
                for p, n in zip(warm, (8, 20, 40)):
                    write_prompt_file(
                        p, rng.randint(0, lm_spec["vocab_size"], n)
                    )
                await asyncio.to_thread(be.serve_files, warm)

                # serial = the r3/r4 shape (workers lock-serialize on
                # the shared server); overlapped = all workers feed one
                # continuous-batching LMDriver (cross-batch slot
                # sharing + promote-at-dispatch, VERDICT r4 item 2).
                # INTERLEAVED pairs: host load drifts over the
                # section, and a serial-then-overlap order charges
                # all of the drift to one mode
                import statistics

                walls = {True: [], False: []}
                gens = {True: [], False: []}
                driver_steps = 0  # ONE overlap run's step count
                for overlap in (True, False, True, False):
                    be.overlap = overlap
                    s0 = be.driver.steps
                    w, g = await timed_job()
                    if overlap and not driver_steps:
                        driver_steps = be.driver.steps - s0
                    walls[overlap].append(w)
                    gens[overlap].append(g)
                wall_over = statistics.median(walls[True])
                wall_serial = statistics.median(walls[False])
                gen_tokens = gens[True][0]
                gen_serial = gens[False][0]
                # C4's adaptive-dispatch principle applied here too:
                # the HEADLINE rate is the measured winner's, labeled.
                # On this 1-core co-located cluster the serial mode
                # usually wins (the driver funnel contends with the
                # asyncio loop for the core; isolated, driver ≈
                # serial); on a real multi-core TPU host the driver's
                # cross-batch batching is the right default.
                mode_chosen = (
                    "overlap" if wall_over <= wall_serial else "serial"
                )
                wall = min(wall_over, wall_serial)
                out["cluster_lm_serving"] = {
                    "nodes": 4,
                    "prompts": n_prompts,
                    "new_tokens_per_prompt": new_tokens,
                    "mode_chosen": mode_chosen,
                    "wall_s": round(wall, 2),
                    "prompts_per_s": round(n_prompts / wall, 2),
                    "gen_tok_per_s_end_to_end": round(gen_tokens / wall, 1),
                    "gen_tok_per_s_overlap": round(
                        gen_tokens / wall_over, 1),
                    "overlap_range": sorted(
                        round(gens[True][0] / w, 1) for w in walls[True]
                    ),
                    "gen_tok_per_s_serial": round(gen_serial / wall_serial, 1),
                    "serial_range": sorted(
                        round(gens[False][0] / w, 1) for w in walls[False]
                    ),
                    "overlap_vs_serial": round(wall_serial / wall_over, 2),
                    "driver_steps": driver_steps,
                    "note": "full stack: store-replicated prompt files -> "
                            "fair-share scheduler -> continuous-batching "
                            "LM server -> merged outputs; the headline "
                            "rate is the measured winner of interleaved "
                            "serial/overlap pairs (mode_chosen — the C4 "
                            "adaptive-dispatch principle): overlap = all "
                            "workers feed one LMDriver slot grid "
                            "(promote-at-dispatch), serial = the r4 "
                            "lock path, which on a 1-core co-located "
                            "cluster avoids contending with the asyncio "
                            "loop; outputs are exactly isolated "
                            "generate() per prompt (LMServer "
                            "batching-exactness contract)",
                }

                # ---- steady state: continuous refill (VERDICT r5
                # item 4). The transient job above is ~1 s of wall,
                # mostly prefill/placement — it cannot distinguish "the
                # stack sustains much more" from "a control-plane
                # ceiling". Keep 2 jobs in flight in the chosen mode
                # for >= steady_s past the ramp, sample the backend's
                # delivered-token count on a fixed cadence, and report
                # the post-ramp rate plus the tok/s-vs-wall curve.
                be.overlap = mode_chosen == "overlap"
                t0 = time.monotonic()
                samples = [(0.0, be.decode_tokens_total())]
                inflight: set = set()
                jobs_launched = 0
                jobs_done = [0]

                async def one_job():
                    job_id = await client_jobs.submit_job(
                        "BenchLM", n_prompts
                    )
                    await client_jobs.wait_job(job_id, timeout=600.0)
                    jobs_done[0] += 1

                def ramp_edge():
                    """First sample at/after the ramp cutoff, or None
                    while the ramp is still running."""
                    for s in samples:
                        if s[0] >= ramp_s:
                            return s
                    return None

                # refill until the POST-RAMP window itself covers
                # steady_s — a fixed wall deadline would undershoot by
                # sampling jitter + event-loop overshoot, and the
                # window is the number claim_check holds to >= 15 s
                while True:
                    lo = ramp_edge()
                    if lo is not None and (
                        samples[-1][0] - lo[0] >= steady_s
                    ):
                        break
                    while len(inflight) < 2:
                        t = asyncio.ensure_future(one_job())
                        inflight.add(t)
                        t.add_done_callback(inflight.discard)
                        jobs_launched += 1
                    await asyncio.sleep(steady_sample_dt)
                    samples.append(
                        (time.monotonic() - t0, be.decode_tokens_total())
                    )
                if inflight:
                    await asyncio.gather(
                        *list(inflight), return_exceptions=True
                    )

                (t_lo, c_lo) = ramp_edge()
                (t_hi, c_hi) = samples[-1]
                window = max(t_hi - t_lo, 1e-9)
                curve = []
                for (ta, ca), (tb, cb) in zip(samples, samples[1:]):
                    dt = tb - ta
                    if dt > 1e-9:
                        curve.append(
                            [round(tb, 2), round((cb - ca) / dt, 1)]
                        )
                out["cluster_lm_serving"]["steady_state"] = {
                    "mode": mode_chosen,
                    "target_steady_s": steady_s,
                    "ramp_excluded_s": round(t_lo, 2),
                    "measured_steady_s": round(window, 2),
                    "gen_tok_per_s_steady": round((c_hi - c_lo) / window, 1),
                    "tokens_post_ramp": int(c_hi - c_lo),
                    "jobs_launched": jobs_launched,
                    "jobs_completed": jobs_done[0],
                    "prompts_per_job": n_prompts,
                    "concurrent_jobs": 2,
                    # [wall_s, tok/s over the preceding sample
                    # interval] — ramp included so the climb (and any
                    # later sag) is visible, post-ramp rate excludes it
                    "curve_tok_per_s": curve,
                    "note": "continuous refill: 2 jobs kept in flight "
                            "in the transient winner's mode; rate = "
                            "decode-token counter delta over the post-"
                            "ramp window, curve sampled every "
                            f"{steady_sample_dt:g}s (ramp included)",
                }
        finally:
            be.close()

    asyncio.run(run())


def _bench_train(engine, out, *, cnn_model="ResNet50", cnn_batch=32,
                 cnn_hw=224, cnn_chains=(5, 45), phase_chains=((10, 80), (6, 46)),
                 cnn_sweep=((64, 1, (4, 28)), (128, 1, (3, 13)),
                            (128, 4, (3, 13))),
                 lm_dims=None, lm_chains=(3, 18), mesh=None):
    """Training-step throughput on the chip (VERDICT r3 item 6): the
    training subsystem (parallel/train.py, parallel/long_context.py)
    had correctness tests and a multichip dryrun but no driver-visible
    on-chip perf number. Rows:

    - ResNet50 train step (fwd+bwd+SGD update) at b32, img/s + MFU
      (XLA's own cost analysis counts the fwd+bwd FLOPs), plus a
      batch-scaling sweep (`cnn_sweep`: (batch, grad_accum, chains)
      points — b64/b128 and one grad-accum point) so the "b32 MFU is
      structural" claim is tested against batch scaling instead of
      argued from one point (VERDICT r5 item 7);
    - the bench LM (198M params, GQA-4) train step at T=2048, tok/s.

    Slope-timed over a lax.scan that CARRIES the train state and
    accumulates the per-step loss: every step's update feeds the next
    step's forward, so no iteration can hoist, and the consumed
    loss-sum depends on the whole chain.

    Reference analog: it publishes measured constants for everything
    it runs (test.py:109-131); training itself is net-new scope."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dml_tpu.benchmarks import dynamic_slope_stats, peak_flops
    from dml_tpu.parallel.mesh import local_mesh
    from dml_tpu.parallel.train import Trainer

    # training wants HBM headroom: drop the serving models first
    for name in list(engine.loaded_models):
        engine.unload_model(name)
    gc.collect()

    peak = peak_flops()
    mesh = mesh or local_mesh()
    rng = np.random.RandomState(0)
    tr = Trainer(cnn_model, mesh, batch_size=cnn_batch)
    imgs = jnp.asarray(rng.randint(
        0, 255, (cnn_batch, cnn_hw, cnn_hw, 3), np.uint8
    ))
    labels = jnp.asarray(
        rng.randint(0, 1000, (cnn_batch,)).astype(np.int32)
    )
    cnn_key = f"{cnn_model.lower()}_b{cnn_batch}"

    def cnn_chain(n, state, imgs, labels):
        def body(i, carry):
            st, acc = carry
            st, m = tr._step(st, imgs, labels)
            return (st, acc + m["loss"])

        _, acc = jax.lax.fori_loop(
            0, n, body, (state, jnp.float32(0))
        )
        return acc

    def _flops_of(jitted, *args):
        ca = jitted.lower(*args).compile().cost_analysis()
        return float(ca.get("flops", 0.0))

    # chains sized so the slope delta is >=400 ms of device work: a
    # short delta sits inside the host clock's jitter band and the
    # img/s dispersion widens with it
    st = dynamic_slope_stats(
        cnn_chain, (tr.state, imgs, labels), cnn_chains, 5
    )
    secs = st["median"]
    step_flops = _flops_of(tr._step, tr.state, imgs, labels)
    train = {
        cnn_key: {
            "img_per_s": round(cnn_batch / secs, 1),
            "img_per_s_range": [round(cnn_batch / st["max"], 1),
                                round(cnn_batch / st["min"], 1)],
            "step_ms": round(secs * 1e3, 3),
            "mfu_fwd_bwd": (
                round(step_flops / secs / peak, 4) if step_flops else None
            ),
        }
    }

    # -- where the train step's time goes (VERDICT r4 item 5): phase
    #    decomposition with per-phase MFU, so the train MFU has the
    #    same roofline treatment inference got. Three slope-timed
    #    programs at the same shapes: train-mode forward (probs +
    #    batch-stats update), fwd+bwd (value_and_grad, no update), and
    #    the full step (fwd+bwd+adamw apply, measured above). --------
    import optax

    from dml_tpu.benchmarks import device_seconds_per_iter_stats, poke
    from dml_tpu.parallel.train import (
        classification_metrics,
        normalize_sharded,
    )

    model, spec = tr.model, tr.spec

    def fwd_only(params, batch_stats, imgs_u8, labels):
        x = normalize_sharded(imgs_u8, spec.preprocess, jnp.bfloat16, mesh)
        probs, upd = model.apply(
            {"params": params, "batch_stats": batch_stats},
            x, train=True, mutable=["batch_stats"],
        )
        nll, _ = classification_metrics(probs, labels)
        # consume the batch-stats outputs too: unconsumed, XLA would
        # DCE the BN reduction updates and flatter the forward
        stats = sum(
            jnp.max(l) for l in jax.tree_util.tree_leaves(upd)
        )
        return nll + stats * jnp.float32(1e-20)

    def loss_fn(params, batch_stats, x, labels):
        probs, upd = model.apply(
            {"params": params, "batch_stats": batch_stats},
            x, train=True, mutable=["batch_stats"],
        )
        nll, acc = classification_metrics(probs, labels)
        return nll, (upd["batch_stats"], acc)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def fwd_bwd(params, batch_stats, imgs_u8, labels):
        x = normalize_sharded(imgs_u8, spec.preprocess, jnp.bfloat16, mesh)
        (nll, _), grads = grad_fn(params, batch_stats, x, labels)
        # global_norm consumes every gradient leaf
        return nll + optax.global_norm(grads) * jnp.float32(1e-20)

    p, bs = tr.state["params"], tr.state["batch_stats"]
    st_f = device_seconds_per_iter_stats(
        lambda i, acc, p, b, x, y: fwd_only(p, b, poke(x, acc), y),
        p, bs, imgs, labels, chains=phase_chains[0],
    )
    st_fb = device_seconds_per_iter_stats(
        lambda i, acc, p, b, x, y: fwd_bwd(p, b, poke(x, acc), y),
        p, bs, imgs, labels, chains=phase_chains[1],
    )
    fl_f = _flops_of(jax.jit(fwd_only), p, bs, imgs, labels)
    fl_fb = _flops_of(jax.jit(fwd_bwd), p, bs, imgs, labels)
    tf, tfb = st_f["median"], st_fb["median"]
    t_bwd = max(tfb - tf, 1e-9)
    t_upd = max(secs - tfb, 0.0)
    n_params = sum(
        l.size for l in jax.tree_util.tree_leaves(p)
    )
    train[cnn_key]["phase_split"] = {
        "fwd_ms": round(tf * 1e3, 3),
        "fwd_mfu": round(fl_f / tf / peak, 4) if fl_f else None,
        "bwd_ms": round(t_bwd * 1e3, 3),
        "bwd_mfu": (
            round((fl_fb - fl_f) / t_bwd / peak, 4) if fl_fb else None
        ),
        "fwd_bwd_ms": round(tfb * 1e3, 3),
        "fwd_bwd_mfu": round(fl_fb / tfb / peak, 4) if fl_fb else None,
        "optimizer_update_ms": round(t_upd * 1e3, 3),
        # adamw streams ~7 f32 arrays over every param (p, g, m, v
        # read + p, m, v write): the HBM-bound floor for the update
        "optimizer_hbm_mb": round(n_params * 4 * 7 / 2**20, 1),
        "note": "bwd = fwd_bwd - fwd; update = step - fwd_bwd. The "
                "MFU gap to the inference forward (which has no BN "
                "stats, no bwd) is attributed by phase: BN batch "
                "stats + f32 loss in fwd, input-gradient and "
                "weight-gradient convs (halo'd, smaller effective "
                "tiles) in bwd, and an HBM-bound elementwise adamw "
                "update that does no MXU work at all",
    }
    del tr
    gc.collect()

    # -- batch scaling (VERDICT r5 item 7): b64/b128 + one grad-accum
    #    point next to the b32 row, so "the b32 MFU is structural" is
    #    tested against batch scaling rather than asserted from one
    #    point. grad_accum splits the batch into micro-batches under a
    #    lax.scan — same effective batch, ~accum-fold lower activation
    #    memory — so its row shows what the memory-saving config costs
    #    in step time at the same FLOPs.
    for b, ga, chains in cnn_sweep:
        tr_b = Trainer(cnn_model, mesh, batch_size=b, grad_accum=ga)
        imgs_b = jnp.asarray(rng.randint(
            0, 255, (b, cnn_hw, cnn_hw, 3), np.uint8
        ))
        labels_b = jnp.asarray(
            rng.randint(0, 1000, (b,)).astype(np.int32)
        )

        def chain_b(n, state, imgs, labels, _tr=tr_b):
            def body(i, carry):
                st, acc = carry
                st, m = _tr._step(st, imgs, labels)
                return (st, acc + m["loss"])

            _, acc = jax.lax.fori_loop(
                0, n, body, (state, jnp.float32(0))
            )
            return acc

        st_b = dynamic_slope_stats(
            chain_b, (tr_b.state, imgs_b, labels_b), chains, 5
        )
        secs_b = st_b["median"]
        fl_b = _flops_of(tr_b._step, tr_b.state, imgs_b, labels_b)
        key = f"{cnn_model.lower()}_b{b}" + (f"_ga{ga}" if ga > 1 else "")
        train[key] = {
            "img_per_s": round(b / secs_b, 1),
            "img_per_s_range": [round(b / st_b["max"], 1),
                                round(b / st_b["min"], 1)],
            "step_ms": round(secs_b * 1e3, 3),
            "mfu_fwd_bwd": (
                round(fl_b / secs_b / peak, 4) if fl_b else None
            ),
        }
        if ga > 1:
            train[key]["grad_accum"] = ga
        del tr_b, imgs_b, labels_b
        gc.collect()

    from dml_tpu.parallel.long_context import LongContextLM

    dims = dict(
        seq_len=2048, vocab_size=32000, d_model=1024,
        n_heads=16, n_layers=12, d_ff=4096, n_kv_heads=4,
    )
    dims.update(lm_dims or {})
    lm = LongContextLM(mesh, **dims)
    seq = dims["seq_len"]
    toks = jnp.asarray(
        rng.randint(0, dims["vocab_size"], (1, seq)).astype(np.int32)
    )

    def lm_chain(n, state, toks):
        def body(i, carry):
            st, acc = carry
            st, loss = lm._train_step(st, toks)
            return (st, acc + loss)

        _, acc = jax.lax.fori_loop(
            0, n, body, (state, jnp.float32(0))
        )
        return acc

    # (3, 18): ~500 ms slope delta at ~33 ms/step — same jitter-band
    # sizing as the CNN chains above
    stl = dynamic_slope_stats(lm_chain, (lm.state, toks), lm_chains, 5)
    lm_flops = _flops_of(lm._train_step, lm.state, toks)
    train["lm_198m_t2048" if not lm_dims else f"lm_t{seq}"] = {
        "tok_per_s": round(seq / stl["median"], 1),
        "tok_per_s_range": [round(seq / stl["max"], 1),
                            round(seq / stl["min"], 1)],
        "step_ms": round(stl["median"] * 1e3, 3),
        "mfu_fwd_bwd": (
            round(lm_flops / stl["median"] / peak, 4) if lm_flops else None
        ),
    }
    out["train"] = train
    del lm
    gc.collect()


def _bench_pallas(out):
    """Flash-attention + fused_normalize compiled via Mosaic on the
    real chip: numeric parity vs jnp oracles asserted, then timed."""
    import jax
    import jax.numpy as jnp

    from dml_tpu.benchmarks import device_seconds_per_iter, poke
    from dml_tpu.models.preprocess import normalize_on_device
    from dml_tpu.ops import flash_attention, fused_normalize

    B, T, H, D = 4, 4096, 8, 128
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, T, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, T, H, D), jnp.bfloat16)

    def naive(q, k, v):
        s = jnp.einsum(
            "bthd,bshd->bhts", q.astype(jnp.float32), k.astype(jnp.float32)
        ) * (D ** -0.5)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
        return jnp.einsum(
            "bhts,bshd->bthd", jax.nn.softmax(s, -1), v.astype(jnp.float32)
        ).astype(q.dtype)

    # parity, compiled on device
    o_fa = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(q, k, v)
    o_nv = jax.jit(naive)(q, k, v)
    # parity is RECORDED (pass flag + value), not asserted: a marginal
    # tolerance miss on a different chip/toolchain must degrade the
    # report, not abort the whole matrix (advisor finding, r2)
    err = float(jnp.max(jnp.abs(
        o_fa.astype(jnp.float32) - o_nv.astype(jnp.float32)
    )))

    def g(fn):
        return jax.jit(jax.grad(
            lambda q: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)
        ))

    g_fa = g(lambda q, k, v: flash_attention(q, k, v, causal=True))(q)
    g_nv = g(naive)(q)  # multi-GB naive backward: run exactly once
    gerr = float(jnp.max(jnp.abs(
        g_fa.astype(jnp.float32) - g_nv.astype(jnp.float32)
    ))) / (float(jnp.max(jnp.abs(g_nv))) + 1e-6)

    def step_fa(i, acc, q, k, v):
        return jnp.max(
            flash_attention(poke(q, acc), k, v, causal=True).astype(jnp.float32)
        )

    def step_nv(i, acc, q, k, v):
        return jnp.max(naive(poke(q, acc), k, v).astype(jnp.float32))

    # a millisecond-scale flash kernel needs a 70+-iter delta or
    # host-clock jitter can swallow the slope entirely; the slower
    # naive body is fine with a smaller chain
    t_fa = device_seconds_per_iter(step_fa, q, k, v, chains=(10, 80))
    t_nv = device_seconds_per_iter(step_nv, q, k, v, chains=(5, 25))

    x = jax.random.randint(kq, (256, 224, 224, 3), 0, 256, jnp.uint8)
    err_n = float(jnp.max(jnp.abs(
        jax.jit(lambda x: fused_normalize(x, "caffe"))(x).astype(jnp.float32)
        - normalize_on_device(x, "caffe", jnp.bfloat16).astype(jnp.float32)
    )))

    # ring-attention body: Pallas-flash blocks vs dense-jnp blocks
    # (1-device sp mesh — the multi-device ring is validated on the
    # CPU mesh; this measures the per-device block compute that
    # dominates ring wall-time)
    import numpy as np
    from jax.sharding import Mesh

    from dml_tpu.parallel.ring_attention import ring_attention

    mesh = Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
        ("dp", "tp", "sp", "pp", "ep"),
    )
    qr = q[:2]
    kr, vr = k[:2], v[:2]
    ring_fl = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh, causal=True, use_flash=True))
    ring_dn = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh, causal=True, use_flash=False))
    err_r = float(jnp.max(jnp.abs(
        ring_fl(qr, kr, vr).astype(jnp.float32)
        - ring_dn(qr, kr, vr).astype(jnp.float32)
    )))
    # longer chains than the big-kernel timings: the flash ring body
    # is sub-millisecond, and a short chain's slope can drown in
    # host-clock jitter (a degenerate ~0 slipped through once)
    t_rf = device_seconds_per_iter(
        lambda i, acc, q, k, v: jnp.max(
            ring_fl(poke(q, acc), k, v).astype(jnp.float32)),
        qr, kr, vr, chains=(10, 80))
    t_rd = device_seconds_per_iter(
        lambda i, acc, q, k, v: jnp.max(
            ring_dn(poke(q, acc), k, v).astype(jnp.float32)),
        qr, kr, vr, chains=(10, 80))

    # decode-attention kernel parity vs the einsum oracle it replaces
    # on the TPU serving path (ops/decode_attention.py; both cache
    # forms — int8 folds scales into score rows, so its tolerance
    # covers the quantization-order difference)
    from dml_tpu.inference.generate import _kv_quantize
    from dml_tpu.ops.decode_attention import decode_attention

    Bd, Td, KVd, Hd, Dd = 4, 2048, 4, 16, 64
    kq2, kk2, kv2, kp2 = jax.random.split(jax.random.PRNGKey(7), 4)
    qd = jax.random.normal(kq2, (Bd, 1, Hd, Dd), jnp.bfloat16)
    ckd = jax.random.normal(kk2, (Bd, KVd, Td, Dd), jnp.bfloat16)
    cvd = jax.random.normal(kv2, (Bd, KVd, Td, Dd), jnp.bfloat16)
    posd = jax.random.randint(kp2, (Bd,), 0, Td)

    def decode_oracle(q, ck, cv, pos):
        grp = Hd // KVd
        valid = jnp.arange(Td)[None, :] <= pos[:, None]
        qg = q.astype(jnp.float32).reshape(Bd, 1, KVd, grp, Dd)
        s = jnp.einsum(
            "bqkgd,bktd->bkgqt", qg, ck.astype(jnp.float32)
        ) * (Dd ** -0.5)
        s = jnp.where(valid[:, None, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqt,bktd->bqkgd", p, cv.astype(jnp.float32))
        return o.reshape(Bd, 1, Hd, Dd)

    err_dk = float(jnp.max(jnp.abs(
        jax.jit(decode_attention)(qd, ckd, cvd, posd + 1)
        - jax.jit(decode_oracle)(qd, ckd, cvd, posd)
    )))
    ckq_, cks_ = _kv_quantize(ckd)
    cvq_, cvs_ = _kv_quantize(cvd)
    cks_, cvs_ = jnp.swapaxes(cks_, 2, 3), jnp.swapaxes(cvs_, 2, 3)
    err_dk8 = float(jnp.max(jnp.abs(
        jax.jit(lambda q, a, b2, c, d2, p: decode_attention(
            q, a, c, p + 1, k_scale=b2, v_scale=d2
        ))(qd, ckq_, cks_, cvq_, cvs_, posd)
        - jax.jit(lambda q, a, b2, c, d2, p: decode_oracle(
            q,
            a.astype(jnp.float32) * jnp.swapaxes(b2, 2, 3),
            c.astype(jnp.float32) * jnp.swapaxes(d2, 2, 3),
            p,
        ))(qd, ckq_, cks_, cvq_, cvs_, posd)
    )))

    out["pallas_on_device"] = {
        "flash_fwd_max_err": round(err, 5),
        "flash_bwd_rel_err": round(gerr, 5),
        "normalize_max_err": round(err_n, 5),
        "ring_parity_max_err": round(err_r, 5),
        "decode_kernel_max_err": round(err_dk, 5),
        "decode_kernel_int8_max_err": round(err_dk8, 5),
        "parity_pass": bool(
            err < 0.05 and gerr < 0.08 and err_n < 1.0 and err_r < 0.05
            and err_dk < 0.05 and err_dk8 < 0.05
        ),
        "flash_fwd_ms": round(t_fa * 1e3, 3),
        "naive_attn_fwd_ms": round(t_nv * 1e3, 3),
        "flash_vs_naive_speedup": round(t_nv / t_fa, 3),
        "ring_block_flash_ms": round(t_rf * 1e3, 3),
        "ring_block_dense_ms": round(t_rd * 1e3, 3),
        "ring_flash_speedup": round(t_rd / t_rf, 3),
        "shape": f"B{B} T{T} H{H} D{D} bf16 causal",
    }


def _bench_lm(
    out,
    *,
    engine=None,
    vocab=32000,
    d_model=1024,
    n_heads=16,
    n_layers=12,
    d_ff=4096,
    decode_lengths=(32, 160),  # 128-step delta: a sub-ms decode body
    # must accumulate well past the host clock's jitter, or a
    # degenerate ~0 slope slips through
    reps=5,
):
    """LM serving matrix — driver-captured versions of every number the
    inference/ docstrings claim (VERDICT r2 item 1):

    - decode tok/s for f32- / bf16- / int8-resident weights (B=1,
      short context: the weight-stream-bound regime);
    - MHA vs GQA-4 vs MQA decode at 4k context (B=1: the KV-cache-
      bound regime the compact cache exists for);
    - prefill (one flash-attention forward) vs token-by-token scan at
      a 2k prompt;
    - the continuous-batching server's device program
      (`batched_decode_step`, per-slot positions — exactly what
      LMServer._chunk_impl scans) at 1 vs 8 active slots.

    All rates are slope-timed (`dynamic_slope_stats`): each measured
    program runs the
    decode body under `lax.scan` with the sampled token chained into
    the next step (argmax of the previous logits), so the chain is
    sequential by construction and the two-length slope cancels the
    fixed dispatch cost. Weight trees are built directly as arrays (the
    param-tree layout `generate` consumes, matching
    models/transformer.py); throughput is value-independent.

    Reference analog: its published measured model constants
    (reference test.py:109-131); the LM stack itself is net-new scope.
    """
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dml_tpu.benchmarks import (
        device_seconds_per_iter,
        dynamic_slope_stats,
        poke,
    )
    from dml_tpu.inference.generate import (
        LMConfig,
        batched_decode_step,
        init_cache,
        prefill,
    )
    from dml_tpu.inference.quantize import quantize_lm_params

    # free the CNN weights first: the LM section allocates ~2 GB of
    # param trees + caches, and the int8 decode path is sensitive to
    # HBM headroom (with the CNN models still resident the r3
    # full-bench run measured int8 at 1056 tok/s vs 3658 standalone)
    if engine is not None:
        for name in list(engine.loaded_models):
            engine.unload_model(name)
        gc.collect()

    hd = d_model // n_heads

    def make_params(n_kv, seed=0):
        """f32 param tree in generate()'s layout (models/transformer.py
        naming), built host-side: bench needs shapes + HBM residency,
        not trained values."""
        rng = np.random.RandomState(seed)

        def m(*shape, scale):
            return jnp.asarray(
                rng.standard_normal(shape).astype(np.float32) * scale
            )

        p = {
            "embed": {"embedding": m(vocab, d_model, scale=0.02)},
            "ln_out": {"scale": jnp.ones((d_model,), jnp.float32)},
            "lm_head": {"kernel": m(d_model, vocab, scale=0.02)},
        }
        for i in range(n_layers):
            p[f"block_{i}"] = {
                "ln_attn": {"scale": jnp.ones((d_model,), jnp.float32)},
                "ln_mlp": {"scale": jnp.ones((d_model,), jnp.float32)},
                "qkv": {"kernel": m(
                    d_model, d_model + 2 * n_kv * hd, scale=d_model**-0.5
                )},
                "proj": {"kernel": m(d_model, d_model, scale=d_model**-0.5)},
                "up": {"kernel": m(d_model, d_ff, scale=d_model**-0.5)},
                "down": {"kernel": m(d_ff, d_model, scale=d_ff**-0.5)},
            }
        return p

    def tree_bytes(p):
        return sum(l.nbytes for l in jax.tree_util.tree_leaves(p))

    def tree_mb(p):
        return round(tree_bytes(p) / 2**20, 1)

    def decode_stats(params, cfg, batch, max_len, lengths=decode_lengths):
        """Per-step stats (median/min/max slope seconds) at ~max_len
        context (the chain starts at max_len - lengths[1] - 1 so both
        chain lengths run over the same cache footprint). The chain
        length is a traced fori_loop bound — one compile per config,
        not per length."""
        cache = init_cache(cfg, batch, max_len)
        tok = jnp.zeros((batch,), jnp.int32)
        start = max(0, max_len - lengths[1] - 1)
        pos = jnp.full((batch,), start, jnp.int32)

        def chain(n, params, cache, tok, pos):
            def body(i, carry):
                cache, tok, pos = carry
                logits, cache = batched_decode_step(
                    params, cfg, cache, tok, pos
                )
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                return (cache, nxt, pos + 1)

            cache, tok, pos = jax.lax.fori_loop(
                0, n, body, (cache, tok, pos)
            )
            return jnp.sum(tok)

        return dynamic_slope_stats(
            chain, (params, cache, tok, pos), lengths, reps
        )

    def rate_row(st, batch):
        """tok/s row with dispersion from a decode_stats dict."""
        return {
            "tok_per_s": round(batch / st["median"], 1),
            "tok_per_s_range": [round(batch / st["max"], 1),
                                round(batch / st["min"], 1)],
            "ms_per_tok": round(st["median"] * 1e3 / batch, 3),
        }

    def decode_rate(params, cfg, batch, max_len, lengths=decode_lengths):
        return decode_stats(params, cfg, batch, max_len, lengths)["median"]

    lm = {"config": {
        "vocab": vocab, "d_model": d_model, "n_heads": n_heads,
        "n_layers": n_layers, "d_ff": d_ff,
    }}
    out["lm"] = lm

    # -- weight-form sweep: f32 vs bf16 vs int8 (B=1, 512 ctx) --------
    cfg_gqa_f32 = LMConfig(vocab, d_model, n_heads, n_layers, d_ff,
                           dtype=jnp.float32, n_kv_heads=4)
    cfg_gqa = LMConfig(vocab, d_model, n_heads, n_layers, d_ff,
                       dtype=jnp.bfloat16, n_kv_heads=4)
    p32 = make_params(4)
    pbf = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), p32)
    pq8 = quantize_lm_params(p32)
    lm["params_millions"] = round(sum(
        l.size for l in jax.tree_util.tree_leaves(p32)
    ) / 1e6, 1)

    forms = {}
    for name, params, cfg in (
        ("f32", p32, cfg_gqa_f32),
        ("bf16", pbf, cfg_gqa),
        ("int8", pq8, cfg_gqa),
    ):
        st = decode_stats(params, cfg, batch=1, max_len=512)
        forms[name] = {
            **rate_row(st, 1),
            "weights_mb": tree_mb(params),
        }
    forms["bf16_vs_f32_speedup"] = round(
        forms["bf16"]["tok_per_s"] / forms["f32"]["tok_per_s"], 2)
    forms["int8_vs_bf16_capacity"] = round(
        tree_bytes(pbf) / tree_bytes(pq8), 2)
    lm["decode_weight_forms_b1"] = forms

    # -- KV-head sweep at 4k context (B=1, bf16). Longer chains than
    #    the b8 rows: a sub-ms b1 body over a 128-step delta drowns
    #    in host-clock jitter -
    ctx = 4096
    heads = {}
    for name, n_kv, params in (
        ("mha", n_heads, None),
        ("gqa4", 4, pbf),
        ("mqa", 1, None),
    ):
        if params is None:
            params = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.bfloat16), make_params(n_kv)
            )
        cfg = LMConfig(vocab, d_model, n_heads, n_layers, d_ff,
                       dtype=jnp.bfloat16, n_kv_heads=n_kv)
        st = decode_stats(params, cfg, batch=1, max_len=ctx,
                          lengths=(64, 576))
        cache_mb = round(
            n_layers * 2 * ctx * n_kv * hd * 2 / 2**20, 1
        )
        heads[name] = {
            "n_kv_heads": n_kv,
            **rate_row(st, 1),
            "cache_mb_per_slot_at_4k": cache_mb,
        }
    heads["gqa4_vs_mha_speedup"] = round(
        heads["gqa4"]["tok_per_s"] / heads["mha"]["tok_per_s"], 2)
    heads["mqa_vs_mha_speedup"] = round(
        heads["mqa"]["tok_per_s"] / heads["mha"]["tok_per_s"], 2)
    lm["decode_kv_heads_4k_ctx_b1"] = heads

    # -- int8 KV cache at 4k context (B=8, GQA-4, bf16 weights): the
    #    long-context serving regime where 8 slots' caches rival the
    #    weight stream (8 x 48 MB vs 377 MB) ------------------------
    import dataclasses

    cfgq = dataclasses.replace(cfg_gqa, kv_quant=True)

    def cache_mb(cfg):
        return round(sum(
            l.nbytes
            for l in jax.tree_util.tree_leaves(init_cache(cfg, 1, ctx))
        ) / 2**20, 1)

    st_f = decode_stats(pbf, cfg_gqa, batch=8, max_len=ctx)
    st_q = decode_stats(pbf, cfgq, batch=8, max_len=ctx)
    secs_f, secs_q = st_f["median"], st_q["median"]
    lm["kv_cache_int8_4k_ctx_b8"] = {
        "bf16_cache_tok_per_s": round(8 / secs_f, 1),
        "bf16_range": rate_row(st_f, 8)["tok_per_s_range"],
        "int8_cache_tok_per_s": round(8 / secs_q, 1),
        "int8_range": rate_row(st_q, 8)["tok_per_s_range"],
        "speedup": round(secs_f / secs_q, 2),
        "cache_mb_per_slot_bf16": cache_mb(cfg_gqa),
        "cache_mb_per_slot_int8": cache_mb(cfgq),
    }

    # -- prefill vs token-by-token scan at a 2k prompt ----------------
    tp = 2048
    prompt = jnp.zeros((1, tp), jnp.int32)

    def step_prefill(i, acc, params, prompt):
        logits, _ = prefill(params, cfg_gqa, poke(prompt, acc), tp)
        return jnp.max(logits)

    # 30-iter delta: a few-ms prefill at (3, 10) chains gave ratios
    # swinging run to run; accumulate well past the clock's jitter
    t_prefill = device_seconds_per_iter(
        step_prefill, pbf, prompt, chains=(10, 40), reps=reps
    )
    # scan baseline: per-step decode cost at the same cache footprint,
    # measured mid-prompt (~Tp/2 average context over the scan)
    t_step = decode_rate(pbf, cfg_gqa, batch=1, max_len=tp // 2)
    lm["prefill_2k_prompt"] = {
        "prefill_ms": round(t_prefill * 1e3, 2),
        "scan_ms_est": round(t_step * tp * 1e3, 2),
        "speedup": round(t_step * tp / t_prefill, 1),
        "note": "scan cost = measured per-step decode at ~Tp/2 context "
                "x Tp steps",
    }

    # -- continuous-batching slots: 1 vs 8 active (the LMServer device
    #    program: batched_decode_step with per-slot positions) --------
    slots = {}
    for b in (1, 8):
        st = decode_stats(pbf, cfg_gqa, batch=b, max_len=1024,
                          lengths=(64, 448) if b == 1 else decode_lengths)
        secs = st["median"]
        slots[f"slots_{b}"] = {
            "aggregate_tok_per_s": round(b / secs, 1),
            "tok_per_s_range": [round(b / st["max"], 1),
                                round(b / st["min"], 1)],
            "ms_per_step": round(secs * 1e3, 3),
        }
    slots["batching_gain_8_vs_1"] = round(
        slots["slots_8"]["aggregate_tok_per_s"]
        / slots["slots_1"]["aggregate_tok_per_s"], 2)
    lm["continuous_batching"] = slots

    # -- mixed per-request budgets over a request STREAM:
    #    batch-synchronous waves (the job pipeline's per-batch shape —
    #    fill max_slots, drain until the wave's SLOWEST request
    #    finishes, repeat) vs continuous slot refill. Every wave pays
    #    ~max(budgets)/chunk steps while refill pays ~mean, so with
    #    budgets 32..512 the barrier tax compounds per wave — the
    #    structural win uniform-budget rows can't show by
    #    construction. Wall-clock timed (includes per-step readbacks —
    #    an end-to-end serving measure, not a slope), modes
    #    interleaved so host-load drift biases neither. -------------
    from dml_tpu.inference.lm_server import LMServer

    rngb = np.random.RandomState(3)
    mixed = [
        (rngb.randint(0, vocab, 12).astype(np.int32), int(b))
        for b in rngb.choice([32, 64, 128, 256, 512], size=32)
    ]
    total_toks = sum(b for _, b in mixed)

    # ONE server reused across reps and modes: LMServer's jit wrappers
    # are per-instance, so a fresh server per rep would re-trace (and,
    # cold, recompile) INSIDE the timed window; its state fully drains
    # between run() calls, so reuse is exact
    srv_mixed = LMServer(
        pbf, cfg_gqa, max_slots=8, max_len=1024, chunk=32
    )

    def serve_mixed(continuous: bool) -> float:
        t0 = time.monotonic()
        if continuous:
            srv_mixed.submit_many(
                [p for p, _ in mixed], [b for _, b in mixed]
            )
            srv_mixed.run()
        else:  # waves of max_slots, drained to the slowest request
            for i in range(0, len(mixed), 8):
                srv_mixed.submit_many(
                    [p for p, _ in mixed[i:i + 8]],
                    [b for _, b in mixed[i:i + 8]],
                )
                srv_mixed.run()
        return time.monotonic() - t0

    serve_mixed(True)  # warm: traces + compiles for both modes
    import statistics as _st

    t_cont, t_sync = [], []
    for _ in range(2):
        t_cont.append(serve_mixed(True))
        t_sync.append(serve_mixed(False))
    tc, ts = _st.median(t_cont), _st.median(t_sync)
    lm["mixed_budget_batching"] = {
        "requests": len(mixed),
        "budgets": "32-512 mixed",
        "total_new_tokens": total_toks,
        "continuous_tok_per_s": round(total_toks / tc, 1),
        "batch_sync_tok_per_s": round(total_toks / ts, 1),
        "continuous_speedup": round(ts / tc, 2),
    }


def _run_cpu_subprocess(module, timeout, last_line=False):
    """Run `python -m <module>` on a virtual 8-device CPU mesh (the
    shared shape of the sections that need multiple devices while the
    bench chip is one). This process holds the chip and a chip belongs
    to one process, so the child is FORCED to the CPU — it must never
    reach for the accelerator. Parses the JSON from stdout
    (`last_line=True` when the module may chat above its one JSON
    line). Raises on nonzero rc with the stderr tail."""
    import subprocess
    import sys as _sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    proc = subprocess.run(
        [_sys.executable, "-m", module],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"rc={proc.returncode}: ...{proc.stderr[-400:]}"
        )
    text = proc.stdout.strip()
    return json.loads(text.splitlines()[-1] if last_line else text)


def _bench_cluster_sharded(out):
    """Tensor-parallel worker-group serving through the full cluster
    pipeline (jobs/groups.py; ISSUE 5 tentpole): a 5-node cluster
    with H4+H5 pooled into one dp=1×tp=2 group serving a ResNet50 job
    on a ``param_gather`` ShardedInference, then the identical job on
    single chips. Runs on a virtual 8-device CPU mesh in a subprocess
    (the group mesh needs multiple devices; the bench chip is one) —
    what transfers to a pod is the OUTPUT-EQUALITY contract (group
    outputs bitwise-equal to single-chip, validated by claim_check
    from round 7) and the group topology/degradation machinery; the
    q/s ratio on shared-core CPU devices is an honest lower bound."""
    try:
        out["cluster_sharded_serving"] = _run_cpu_subprocess(
            "dml_tpu.jobs.groups", timeout=600, last_line=True
        )
    except Exception as e:  # pragma: no cover
        out["cluster_sharded_serving"] = {"skipped": True, "reason": repr(e)}


def _bench_b4_s2d(engine, out, batch=128):
    """EfficientNet-B4 space-to-depth stem experiment (VERDICT r5
    carry-over #7, the named untried idea in README Known limits):
    the stock 3×3/2 stem conv contracts over C_in=3 — ~2.3% of a
    128-lane MXU contraction — while the s2d re-expression
    (models/efficientnet.py `_S2DStemConv`) folds 2×2 pixel blocks
    into 12 channels and runs the SAME function (same param, outputs
    bit-equal on CPU, float-reduction-order close on chip) at 4× the
    contraction depth. One measured b128 MFU delta either way, same
    slope protocol as the models sweep; the verdict line is
    mechanical from this run's own numbers."""
    import jax
    import jax.numpy as jnp

    from dml_tpu.benchmarks import (
        compiled_flops,
        forward_rate_stats,
        peak_flops,
    )
    from dml_tpu.models.efficientnet import build_variant
    from dml_tpu.models.registry import get_model

    spec = get_model("EfficientNetB4")
    lm = engine.load_model("EfficientNetB4", batch_size=batch,
                           warmup=False)
    variables = lm.variables  # ONE tree: the s2d stem reads the same
    peak = peak_flops()
    batch_arr = jax.device_put(
        jnp.zeros((batch, *spec.input_size, 3), jnp.uint8),
        engine.device,
    )
    res = {"batch": batch}
    for key, s2d in (("stock", False), ("s2d", True)):
        model = build_variant("b4", dtype=jnp.bfloat16, s2d_stem=s2d)
        fwd = jax.jit(
            lambda vs, x, m=model: m.apply(vs, x, train=False)
        )
        st = forward_rate_stats(fwd, variables, batch_arr, chains=(3, 13))
        secs = st["median"]
        flops = compiled_flops(fwd, variables, batch_arr)
        res[key] = {
            "batch_ms": round(secs * 1e3, 3),
            "qps": round(batch / secs, 1),
            "mfu": round(flops / secs / peak, 4) if flops else None,
        }
    # the headline ratio + verdict need only the two timed walls —
    # never gate them on MFU (compiled_flops legitimately returns 0
    # when cost analysis has no flops key, and that must not vanish
    # the satellite's measured delta)
    res["s2d_vs_stock"] = round(
        res["stock"]["batch_ms"] / res["s2d"]["batch_ms"], 3
    )
    mfu0, mfu1 = res["stock"]["mfu"], res["s2d"]["mfu"]
    if mfu0 is not None and mfu1 is not None:
        res["mfu_delta"] = round(mfu1 - mfu0, 4)
    res["verdict"] = (
        f"s2d stem {'WINS' if res['s2d_vs_stock'] > 1.0 else 'LOSES'}"
        f" at b128: {res['s2d_vs_stock']}x vs stock "
        f"(mfu {mfu0} -> {mfu1}); the stem is a small slice of "
        "B4's total FLOPs, so single-digit movement is the "
        "expected scale either way"
    )
    out["b4_s2d_stem"] = res


def _bench_cluster_lm_sharded(out):
    """Sharded LM serving forms through the full cluster pipeline
    (inference/lm_sharded.py): a 5-node cluster whose eligible pool
    IS one three-member group (H3 decode primary, H4+H5 prefill
    roles) serving an LM job four ways on the SAME topology —
    per-forward param_gather (the PR-5-analog pessimization),
    weight-resident tp=2, PIPELINE-parallel pp=2 (the layer stack
    split across members: models deeper than one member's HBM, with
    the per-member byte budget recorded), and disaggregated
    prefill/decode — plus the handoff ladder (whole-slab pull vs
    chunk-STREAMED handoff TTFT, 1- vs 2-prefill-peer fan-out on a
    prefill-heavy workload) and a member-kill-MID-STREAM chaos case
    (typed per-request fallback, exactly-once tokens).
    Runs on a virtual 8-device CPU mesh in a subprocess. What
    transfers to a pod: the token-equality contract (every mode's
    merged outputs == isolated generate(); claim_check-enforced from
    round 8, the pp/streamed keys from round 10), handoff bytes
    actually moving, and exactly-once token delivery under
    degradation. The tok/s and overlap ratios on shared-core CPU
    devices are an honest lower bound on the ICI story."""
    try:
        out["cluster_lm_sharded"] = _run_cpu_subprocess(
            "dml_tpu.inference.lm_sharded", timeout=1100,
            last_line=True,
        )
    except Exception as e:  # pragma: no cover
        out["cluster_lm_sharded"] = {"skipped": True, "reason": repr(e)}


def _probe_parity_weights():
    """Mechanical pretrained-weights probe for the bench preamble
    (VERDICT r5 carry-over): each round's artifact records WHERE the
    parity weights were looked for and whether any source exists, so
    'still environment-blocked' is a recorded fact instead of a
    remembered one. The store-delivery path (`parity-store`, PR 5)
    stages into the same candidate list the moment a weights file
    lands."""
    try:
        from dml_tpu.tools.imagenet_parity import (
            _KERAS_WEIGHT_FILES,
            candidate_class_index_paths,
            npz_sources,
            weight_sources,
        )

        models = {}
        any_found = False
        for m in sorted(_KERAS_WEIGHT_FILES):
            srcs = weight_sources(m) + npz_sources(m)
            models[m] = {"found": bool(srcs), "sources": srcs}
            any_found = any_found or bool(srcs)
        idx = [p for p in candidate_class_index_paths()
               if os.path.exists(p)]
        return {
            "any_weights_found": any_found,
            "class_index_found": bool(idx),
            "models": models,
            "note": "probed DML_TPU_KERAS_WEIGHTS_DIR, the keras "
                    "cache, and the store-staged parity dir "
                    "(parity-store); imagenet_parity runs full when "
                    "any source exists",
        }
    except Exception as e:  # pragma: no cover - defensive preamble
        return {"error": repr(e)}


def _probe_lint():
    """Static-analysis verdict for the bench preamble: dmllint's
    un-baselined finding count + baseline size (tools/dmllint.py),
    plus the flow-aware pass counts (tools/dmlflow.py) from round 16.
    The artifact records the tree's hazard/drift state mechanically —
    claim_check.check_lint_block holds round-11+ artifacts to
    lint_clean=true."""
    try:
        from dml_tpu.tools.dmllint import bench_block

        return bench_block()
    except Exception as e:  # pragma: no cover - defensive preamble
        return {"lint_clean": False, "error": repr(e)}


def _bench_inception_fusion(out, batch=128):
    """InceptionV3 concat accounting (ROADMAP open item, VERDICT r5
    weak #5): the conv roofline says 0.58 at b128 while the chip
    measures 0.43 — the per-block 4-way branch concats are pure HBM
    copies the roofline ignores. This section measures them: isolated
    slope-timed ``jnp.concatenate`` at the model's own concat shapes
    on the bench chip, folded into the serial roofline
    (``tools.conv_roofline.concat_microbench``). The emitted verdict
    is mechanical: if the concat-corrected ceiling comes down to the
    measured MFU (within the probe band), 0.43 is the honest ceiling
    and the open item closes as a B4-style measured bound; if a gap
    remains, the fused branch-concat epilogue stays on the table."""
    from dml_tpu.tools.conv_roofline import concat_microbench

    # one call: the microbench embeds the stream-bandwidth analytic
    # fields from the same jaxpr trace (a second concat_analysis call
    # would re-trace the full b128 model inside a budgeted section)
    res = concat_microbench("InceptionV3", batch)
    # measured headline for the comparison, from this run's own sweep
    meas = None
    for point in out.get("inceptionv3", []):
        if point.get("batch") == batch:
            meas = point.get("mfu")
    res["measured_mfu_b128"] = meas
    bound = res.get("mfu_bound_serial_with_concat")
    if meas is not None and bound is not None:
        # within ~12% of the corrected bound = the architecture's
        # honest ceiling; beyond it = implementation gap remains
        res["verdict"] = (
            "concat-corrected ceiling explains the measured MFU: "
            "honest ceiling" if meas >= 0.88 * bound else
            "gap to the concat-corrected ceiling remains: fused "
            "branch-concat epilogue still on the table"
        )
    out["inception_fusion"] = res


def _bench_ring_vs_ulysses(out):
    """Ring vs Ulysses collective footprint (VERDICT r3 item 10): runs
    on a virtual 8-device CPU mesh in a subprocess (the sp axis needs
    multiple devices; the bench chip is one) — the collective structure
    in the lowered HLO is what transfers to a pod."""
    try:
        out["ring_vs_ulysses"] = _run_cpu_subprocess(
            "dml_tpu.tools.ring_vs_ulysses", timeout=900
        )
    except Exception as e:  # pragma: no cover
        out["ring_vs_ulysses"] = {"skipped": True, "reason": repr(e)}


def _bench_imagenet_parity(out):
    """Imagenet parity vs reference goldens (skips with reason in
    hermetic environments; full label-match report when weights are
    obtainable at bench time)."""
    try:
        import contextlib
        import sys

        from dml_tpu.tools.imagenet_parity import run_parity

        # keras prints download progress to stdout; keep stdout pure
        # for the JSON artifact lines
        with contextlib.redirect_stdout(sys.stderr):
            out["imagenet_parity"] = run_parity()
    except Exception as e:  # pragma: no cover
        out["imagenet_parity"] = {"skipped": True, "reason": repr(e)}


def main() -> None:
    import signal

    import jax

    from dml_tpu.compile_cache import configure_compile_cache
    from dml_tpu.inference.engine import InferenceEngine

    # a bench number is a TPU number or it is nothing: no CPU fallback
    dev0 = jax.devices()[0]
    if dev0.platform != "tpu":
        raise SystemExit(
            f"bench.py needs a TPU; JAX's first device is {dev0} "
            f"(platform {dev0.platform!r})"
        )
    configure_compile_cache()

    out = {}
    t_start = time.monotonic()
    # Global wall budget (VERDICT r4 item 1): a HARD cap — a section
    # only starts if its cold-cache estimate fits under it (the r3
    # driver envelope accepted 1,750 s and killed the r4 2,214 s run;
    # 1,400 s is the judge's ≥25%-headroom target). Warm-cache runs
    # finish everything well under it.
    budget_s = float(os.environ.get("DML_TPU_BENCH_BUDGET_S", "1400"))

    def _on_signal(signum, frame):  # pragma: no cover - signal path
        raise _Interrupted(f"signal {signum}")

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)

    interrupted = None
    device_str = "unknown (init interrupted)"

    # The interrupt window covers EVERYTHING before the final print —
    # engine init included — so a driver kill at any point still falls
    # through to the combined artifact below.
    try:
        engine = InferenceEngine()  # bfloat16, first visible device
        # captured now, not at print time: the final artifact print
        # must be INFALLIBLE
        device_str = f"{engine.device} ({engine.device.device_kind})"

        # pretrained-weights probe rides the preamble: each round's
        # artifact mechanically records whether the parity weights
        # remain environment-blocked
        out["parity_store_probe"] = _probe_parity_weights()
        print(json.dumps(
            {"section": "parity_store_probe",
             "data": out["parity_store_probe"]},
            separators=(",", ":")), flush=True)

        # static-analysis verdict rides the preamble too: the artifact
        # mechanically records whether the tree is dmllint-clean and
        # how big the grandfather baseline is (claim_check gates on
        # this from round 11). Pure AST work — milliseconds, no jax.
        out["lint"] = _probe_lint()
        print(json.dumps({"section": "lint", "data": out["lint"]},
                         separators=(",", ":")), flush=True)

        # The headline section is FATAL — a run without it is not an
        # artifact. Secondary sections fail soft inside run_sections:
        # one section tripping on a chip-only path must not destroy
        # the whole round's perf record. Ordering: engine-model (CNN)
        # sections stay adjacent (no weight reloads), then the LM
        # sections (which unload the CNNs for HBM headroom), then
        # train/pallas; the CPU-subprocess and parity sections run
        # last — they are the right ones to lose to the wall budget.
        sections = [
            ("models", lambda: _bench_models(engine, out)),
            ("dual_model_c4", lambda: _bench_dual_c4(engine, out)),
            ("cluster_serving", lambda: _bench_cluster_serving(
                engine, out, failure_model="EfficientNetB4")),
            # cluster_lm before the device-lm matrix: under a cold
            # budget the end-to-end serving rows outrank another
            # device sweep (its backend is self-contained)
            ("cluster_lm_serving", lambda: _bench_cluster_lm(out)),
            # chaos soak is CPU-only (stub backend) and cheap; its
            # recovery walls are the robustness record of the round
            ("chaos", lambda: _bench_chaos(out)),
            # request front door under open-loop load: CPU-only like
            # chaos (stub backend; the admission/formation/failover
            # machinery is what's scored)
            ("request_serving", lambda: _bench_request_serving(out)),
            # elastic capacity: CPU-only like chaos — authenticated
            # scale-out mid-load must RAISE q/s with zero restarts
            # (ROADMAP item 2 done-condition, round 18)
            ("elastic_capacity", lambda: _bench_elastic(out)),
            # SLO signal plane: CPU-only like chaos — burn-rate alert
            # under overload, liar cross-check, ledger failover,
            # byte-identical replay (round 19)
            ("signal_plane", lambda: _bench_signal_plane(out)),
            # closed-loop autoscaler: CPU-only like chaos — the same
            # seeded diurnal trace must beat static provisioning on
            # BOTH SLO-violation-minutes and chip-idle-minutes
            # (round 20)
            ("autoscale", lambda: _bench_autoscale(out)),
            # elastic cluster training: CPU-only like chaos — a
            # TrainJob's examples/s must SCALE as capacity joins
            # mid-run (re-shard at step boundaries, zero restarts)
            # and interactive p99 must survive the trainer sharing
            # the pool (ROADMAP item 3 done-condition, round 22)
            ("cluster_training",
             lambda: _bench_cluster_training(out)),
            # control-plane scale matrix: CPU-only, membership-level —
            # the O(100)-node gossip/metrics/churn story (round 12)
            ("control_plane_scale",
             lambda: _bench_control_plane_scale(out)),
            # concat accounting needs the chip (isolated slope-timed
            # concats at Inception's shapes) and the models sweep's
            # b128 point above for its verdict line
            ("inception_fusion", lambda: _bench_inception_fusion(out)),
            # B4 s2d stem A/B wants the chip and the CNN weights
            # still resident (before the LM sections unload them)
            ("b4_s2d_stem", lambda: _bench_b4_s2d(engine, out)),
            ("lm", lambda: _bench_lm(out, engine=engine)),
            ("train", lambda: _bench_train(engine, out)),
            ("pallas_on_device", lambda: _bench_pallas(out)),
            # CPU-subprocess sections last (right ones to lose to the
            # wall budget): sharded worker-group serving, the ring/
            # ulysses HLO sweep, then parity
            ("cluster_sharded_serving", lambda: _bench_cluster_sharded(out)),
            ("cluster_lm_sharded", lambda: _bench_cluster_lm_sharded(out)),
            ("ring_vs_ulysses", lambda: _bench_ring_vs_ulysses(out)),
            ("imagenet_parity", lambda: _bench_imagenet_parity(out)),
        ]
        run_sections(sections, out, t_start=t_start, budget_s=budget_s,
                     fatal={"models"})
    except _Interrupted as e:  # driver kill: still print the artifact
        interrupted = str(e)
    # from here on signals are IGNORED either way: a follow-up SIGTERM
    # (drivers often send a second one before SIGKILL) must not
    # truncate the final combined print
    signal.signal(signal.SIGTERM, signal.SIG_IGN)
    signal.signal(signal.SIGINT, signal.SIG_IGN)

    # Per-stage metrics-registry snapshot (observability.py): every
    # counter/gauge/histogram the sections' serving paths updated —
    # lm_server decode counters, worker stage timings, scheduler
    # C1/C2, transport totals — summarized into the artifact so
    # BENCH_r*.json carries the breakdown behind its headline numbers.
    # tools/claim_check.py validates this block's presence from round
    # 6 on; the try guards the INFALLIBLE final print.
    try:
        from dml_tpu.observability import bench_metrics_block

        metrics_block = bench_metrics_block()
    except Exception as e:  # pragma: no cover - defensive
        metrics_block = {"error": repr(e)}

    hl = out.get("headline_resnet50_b32", {})
    baseline_qps = 4.0  # reference: 250 ms/image CPU steady state

    # Compact roll-up of every headline number, emitted as the LAST
    # top-level key so the driver's 2,000-char stdout tail is
    # self-sufficient (VERDICT r3 item 2: the r3 artifact truncated
    # away the whole image matrix; the canonical perf record must not
    # depend on builder-run preview files).
    def g(*path, default=None):
        cur = out
        for p in path:
            if not isinstance(cur, dict) or p not in cur:
                return default
            cur = cur[p]
        return cur

    lm_forms = g("lm", "decode_weight_forms_b1", default={})
    summary = {
        "headline_qps": hl.get("qps"),
        "headline_qps_range": hl.get("qps_range"),
        "headline_mfu": hl.get("mfu"),
        "opt_batch": g("resnet50_throughput_optimal_batch"),
        "inception_mfu_b128": g("inceptionv3", default=[{}])[-1].get("mfu"),
        "b4_mfu_b128": g("efficientnet_b4", default=[{}])[-1].get("mfu"),
        "cluster_qps": g("cluster_serving", "qps_end_to_end"),
        "cluster_qps_unpipelined": g("cluster_serving", "qps_unpipelined"),
        "cluster_qps_pipelined_static": g(
            "cluster_serving", "qps_pipelined_static"),
        # adaptive vs the BETTER forced static — the never-below-1 one
        "cluster_pipelining": g("cluster_serving", "pipelining_speedup"),
        "cluster_pipelining_static": g(
            "cluster_serving", "pipelining_speedup_static"),
        "cluster_depth": g("cluster_serving", "adaptive", "depth"),
        "cluster_qps_b128": g("cluster_serving_b128", "qps_end_to_end"),
        # tensor-parallel worker-group serving (jobs/groups.py):
        # sharded_qps + the bitwise output-equality flag claim_check
        # holds the artifact to from round 7
        "sharded_qps": g("cluster_sharded_serving", "qps_sharded"),
        "sharded_equal": g("cluster_sharded_serving", "equal_outputs"),
        "sharded_vs_single": g("cluster_sharded_serving", "sharded_vs_single"),
        # sharded LM serving forms (inference/lm_sharded.py): steady
        # tok/s weight-resident + disaggregated, the resident-vs-
        # gather ratio, the token-equality flag, and handoff bytes —
        # the round-8 claim_check gate reads these
        "lm_sharded_toks": g("cluster_lm_sharded", "tok_s_resident"),
        "lm_disagg_toks": g("cluster_lm_sharded", "tok_s_disagg"),
        "lm_sharded_vs_gather": g(
            "cluster_lm_sharded", "resident_vs_gather"),
        "lm_sharded_equal": g(
            "cluster_lm_sharded", "tokens_equal_single_chip"),
        "lm_kv_handoff_bytes": g("cluster_lm_sharded", "kv_handoff_bytes"),
        # pipeline-parallel + chunk-streamed handoff (round-10 gate):
        # pp-mode steady tok/s, streamed-handoff time-to-first-token,
        # the stream-vs-whole-slab TTFT ratio, and the 2-vs-1 prefill
        # peer context-phase speedup
        "lm_pp_toks": g("cluster_lm_sharded", "tok_s_pp"),
        "lm_stream_ttft_ms": g("cluster_lm_sharded", "ttft_stream_ms"),
        "lm_stream_vs_slab": g(
            "cluster_lm_sharded", "stream_vs_slab_ttft"),
        "lm_fanout_speedup": g(
            "cluster_lm_sharded", "fanout_ctx_speedup"),
        # round-21 raw-decode arms (inference/lm_sharded.py):
        # speculative-vs-plain steady tok/s at the bench's declared
        # acceptance, the MEASURED acceptance itself, and the
        # continuous-batching overlap-adoption p99 TTFT under
        # staggered sustained load
        "lm_specdec_speedup": g(
            "cluster_lm_sharded", "lm_specdec_speedup"),
        "lm_specdec_accept": g(
            "cluster_lm_sharded", "lm_specdec_accept"),
        "lm_cb_ttft_ms": g("cluster_lm_sharded", "lm_cb_ttft_ms"),
        "parity_weights_found": g(
            "parity_store_probe", "any_weights_found"),
        "inception_concat_bound": g(
            "inception_fusion", "mfu_bound_serial_with_concat"),
        "b4_s2d_vs_stock": g("b4_s2d_stem", "s2d_vs_stock"),
        "fail_completed": g("cluster_serving_failure", "completed"),
        "fail_detect_s": g("cluster_serving_failure", "detect_to_requeue_s"),
        # request front door (dml_tpu/ingress/): sustained open-loop
        # tail latency + goodput + shed ratio, the light-load p99 win
        # of continuous formation over the fixed-batch baseline, and
        # the failover-mid-traffic exactly-once verdict — the round-9
        # claim_check gate reads these
        "req_p99_ms": g("request_serving", "p99_ms"),
        "req_p50_ms": g("request_serving", "p50_ms"),
        "req_goodput_qps": g("request_serving", "goodput_qps"),
        "req_shed_ratio": g("request_serving", "shed_ratio"),
        "req_cont_vs_fixed_p99": g(
            "request_serving", "continuous_vs_fixed_p99"),
        "req_failover_ok": g(
            "request_serving", "failover", "all_terminal_exactly_once"),
        # KV prefix cache (dml_tpu/inference/kv_cache.py, round-17
        # gate): multi-turn session trace hit ratio, warm-vs-cold
        # TTFT on the same growing-history trace, and prefill tokens
        # the suffix-only warm starts skipped
        "kv_hit_ratio": g("request_serving", "kv_cache", "hit_ratio"),
        "kv_warm_vs_cold_ttft": g(
            "request_serving", "kv_cache", "warm_vs_cold_ttft"),
        "kv_tokens_saved": g(
            "request_serving", "kv_cache", "tokens_saved"),
        # per-request TPOT (loadgen Outcome.tpot_s, round-21): decode
        # cadence the client actually observed on the warm kv-cache
        # arm — TTFT scores prefill+queue, this scores the token loop
        "req_tpot_p95_ms": g(
            "request_serving", "kv_cache", "tpot_ms_warm", "p95"),
        # distributed request tracing (dml_tpu/tracing.py, round-14
        # gate): the p99 cohort's stage attribution explains >= 90% of
        # its e2e, every deadline miss has an exemplar trace, and the
        # flight recorder stayed inside its span budget
        "trace_p99_attrib_ok": g(
            "request_serving", "tracing", "p99_attrib_ok"),
        "trace_attrib_fraction": g(
            "request_serving", "tracing", "p99_attribution",
            "attributed_fraction"),
        "trace_miss_coverage": g(
            "request_serving", "tracing", "miss_exemplar_coverage"),
        # control-plane scale (cluster/chaos.py control_plane_probe,
        # round-12 gate): 128-node delta-protocol convergence wall,
        # cluster-wide failure-detection latency, steady control-plane
        # bytes/node/s, the relay metrics wall, and the overall
        # verdict (bytes below full-table at 64+, detection within
        # 1.5x of small-N, metrics wall sub-linear, churn green)
        "scale_converge_s": g("control_plane_scale", "scale_converge_s"),
        "scale_detect_s": g("control_plane_scale", "scale_detect_s"),
        "scale_bytes_per_node_s": g(
            "control_plane_scale", "scale_bytes_per_node_s"),
        "scale_metrics_wall_s": g(
            "control_plane_scale", "scale_metrics_wall_s"),
        "scale_ok": g("control_plane_scale", "scale_ok"),
        "scale_churn_ok": g("control_plane_scale", "churn", "ok"),
        # elastic capacity (cluster/node.py authenticated join/leave,
        # round-18 gate): q/s ratio after brand-new nodes joined
        # mid-load with zero restarts, and the overall verdict (gain
        # > 1, graceful scale-in, forged-join storm rejected+counted,
        # green invariant sweep)
        "elastic_scaleout_gain": g("elastic_capacity", "scaleout_gain"),
        "elastic_ok": g("elastic_capacity", "elastic_ok"),
        "elastic_qps_before": g("elastic_capacity", "qps_before"),
        "elastic_qps_after": g("elastic_capacity", "qps_after"),
        # SLO signal plane (dml_tpu/signal.py, round-19 gate): did
        # chaos overload fire a typed burn-rate alert with a trace
        # exemplar, did the ACK-wall cross-check flag the lying
        # worker, and the section's own verdict (those two + ledger
        # failover survival + byte-identical replay)
        "alert_fired_ok": g("signal_plane", "alert_fired_ok"),
        "liar_flagged_ok": g("signal_plane", "liar_flagged_ok"),
        "signal_ok": g("signal_plane", "signal_ok"),
        # closed-loop autoscaler (dml_tpu/autoscale.py, round-20
        # gate): how many SLO-violation / chip-idle minutes the
        # controller saved against static provisioning on the shared
        # diurnal trace, and the section's own verdict (both savings
        # positive + zero restarts + green sweeps + scale-out AND
        # scale-in applied + byte-identical decision-stream replay)
        "autoscale_slo_min_saved": g(
            "autoscale", "autoscale_slo_min_saved"),
        "autoscale_idle_min_saved": g(
            "autoscale", "autoscale_idle_min_saved"),
        "autoscale_ok": g("autoscale", "autoscale_ok"),
        # elastic cluster training (dml_tpu/jobs/train.py, round-22
        # gate): the mixed arm's trainer examples/s, and the
        # section's own verdict (positive examples/s slope across
        # the join-grown worlds, >=1 join re-shard at a step
        # boundary, zero restarts, both runs step-exact complete,
        # interactive p99 inside its SLO deadline, green sweep)
        "train_step_qps": g("cluster_training", "mixed",
                            "examples_per_s"),
        "train_elastic_ok": g("cluster_training",
                              "train_elastic_ok"),
        "train_scaleout_gain": g("cluster_training",
                                 "scaleout_gain"),
        # static-analysis verdict (tools/dmllint.py, round-11 gate);
        # the flow-aware pass counts (tools/dmlflow.py: race-yield-
        # hazard / drift-wire-payloads, baselined findings included)
        # are the round-16 gate
        "lint_clean": g("lint", "lint_clean"),
        "lint_findings": g("lint", "findings"),
        "lint_baseline": g("lint", "baseline_size"),
        "lint_race": g("lint", "race_findings"),
        "lint_payload": g("lint", "payload_findings"),
        "chaos_ok": g("chaos", "all_invariants_ok"),
        "chaos_failover_s": g("chaos", "failover_recovery_s"),
        "chaos_repair_s": g("chaos", "store_repair_s"),
        "chaos_scenarios_ok": {
            fam: v.get("all_invariants_ok")
            for fam, v in g("chaos", "scenarios", default={}).items()
            if isinstance(v, dict)
        },
        "chaos_malformed_dropped": g("chaos", "malformed_dropped_total"),
        "c4_qps": g("dual_model_c4", "combined_qps_auto"),
        "c4_mode": g("dual_model_c4", "dispatch_mode_auto"),
        "pipelining": g("dual_model_c4", "pipelining_speedup"),
        "lm_tok_s": {
            k: v.get("tok_per_s") for k, v in lm_forms.items()
            if isinstance(v, dict)
        },
        "kv_int8_speedup": g("lm", "kv_cache_int8_4k_ctx_b8", "speedup"),
        "kv_heads_tok_s": {
            k: v.get("tok_per_s")
            for k, v in g("lm", "decode_kv_heads_4k_ctx_b1", default={}).items()
            if isinstance(v, dict)
        },
        "cb_gain": g("lm", "continuous_batching", "batching_gain_8_vs_1"),
        "cluster_lm_tok_s": g("cluster_lm_serving", "gen_tok_per_s_end_to_end"),
        "cluster_lm_steady_tok_s": g(
            "cluster_lm_serving", "steady_state", "gen_tok_per_s_steady"),
        "cluster_lm_steady_s": g(
            "cluster_lm_serving", "steady_state", "measured_steady_s"),
        "train_img_s": g("train", "resnet50_b32", "img_per_s"),
        "train_mfu": g("train", "resnet50_b32", "mfu_fwd_bwd"),
        "train_mfu_b128": g("train", "resnet50_b128", "mfu_fwd_bwd"),
        "train_mfu_b128_ga4": g("train", "resnet50_b128_ga4", "mfu_fwd_bwd"),
        "train_lm_tok_s": g("train", "lm_198m_t2048", "tok_per_s"),
        "pallas_parity": g("pallas_on_device", "parity_pass"),
        "imagenet_parity": (
            "not_run" if "imagenet_parity" not in out
            else "skipped" if g("imagenet_parity", "skipped") else "ran"
        ),
        # fail-soft sections that tripped (empty = clean run); their
        # tracebacks are on stderr and partial results stay in place
        "section_errors": sorted(out.get("_errors", {})),
        # sections the wall budget skipped (empty = everything ran)
        "sections_skipped": sorted(out.get("_skipped", {})),
        "section_wall_s": out.get("_section_wall_s", {}),
    }
    if interrupted:
        summary["interrupted"] = interrupted

    print(json.dumps({
        "metric": "ResNet50 b32 inference throughput per chip",
        "value": hl.get("qps"),
        "unit": "queries/sec",
        "vs_baseline": (
            round(hl["qps"] / baseline_qps, 2) if hl.get("qps") else None
        ),
        "mfu": hl.get("mfu"),
        "batch_latency_p50_ms": hl.get("batch_latency_p50_ms"),
        "batch_latency_p99_ms": hl.get("batch_latency_p99_ms"),
        "query_latency_p50_ms": hl.get("query_latency_p50_ms"),
        "query_latency_p99_ms": hl.get("query_latency_p99_ms"),
        "device": device_str,
        "dtype": "bfloat16",
        "batch_size": 32,
        "bench_wall_s": round(time.monotonic() - t_start, 1),
        "wall_budget_s": budget_s,
        "matrix": out,
        "metrics": metrics_block,
        "summary": summary,  # keep LAST: must survive the driver tail
    }, default=str), flush=True)
    # Final STANDALONE compact summary line (VERDICT r5 item 3): the
    # driver keeps only a 2,000-char stdout tail and parses it — the
    # one giant artifact line above has failed that parse in all five
    # rounds (`parsed: null`). This line is < 1,500 chars by
    # construction (keys are dropped least-essential-first until it
    # fits), so the tail always ends with a complete, parseable JSON
    # object. parity_table.load_bench / claim_check accept either form.
    print(compact_summary_line(hl, device_str, baseline_qps, summary),
          flush=True)
    if out.get("_errors"):
        # every line above still went out; only the exit code says that
        # a section raised — rc=0 with a hollow artifact is how a run
        # once passed for a measurement
        raise SystemExit(
            f"bench sections raised: {sorted(out['_errors'])}"
        )


#: summary keys dropped (in order) until the compact line fits its
#: budget — least headline-worthy first. Everything always survives in
#: the full artifact line; this only bounds the driver-tail form.
_COMPACT_DROP_ORDER = (
    "section_wall_s", "kv_heads_tok_s", "chaos_scenarios_ok",
    "lint_findings", "lint_baseline",
    "scale_metrics_wall_s", "scale_churn_ok",
    "elastic_qps_before", "elastic_qps_after",
    "lm_tok_s", "fail_detect_s", "fail_completed",
    "chaos_malformed_dropped", "train_mfu_b128_ga4", "opt_batch",
    "inception_concat_bound", "sharded_vs_single",
    "parity_weights_found", "lm_kv_handoff_bytes",
    "lm_sharded_vs_gather", "lm_fanout_speedup", "b4_s2d_vs_stock",
    "req_p50_ms", "req_cont_vs_fixed_p99", "kv_tokens_saved",
    "trace_attrib_fraction", "trace_miss_coverage",
    "inception_mfu_b128", "b4_mfu_b128", "headline_qps_range",
)

COMPACT_SUMMARY_BUDGET = 1500

#: last-resort compact-line survivors: when even the drop-order trim
#: can't fit the budget, the summary collapses to EXACTLY these keys.
#: Every key a claim_check summary-only gate reads MUST be here (and
#: every entry must be a real summary key) — dmllint's
#: drift-summary-keys rule enforces both directions, which is why this
#: is a named module constant and not an inline tuple.
#: cluster_lm_tok_s + cluster_lm_steady_s ride with
#: cluster_lm_steady_tok_s (the steady-window gate keys off their
#: presence together); sharded_qps + sharded_equal are the round-7
#: worker-group gate; lm_sharded_toks / lm_disagg_toks /
#: lm_sharded_equal the round-8 sharded-LM gate; lm_pp_toks /
#: lm_stream_ttft_ms / lm_stream_vs_slab the round-10 pipeline+
#: streamed-handoff gate; req_* the round-9 request-serving gate;
#: lint_clean the round-11 static-analysis gate (lint_race /
#: lint_payload extend it to the round-16 flow-aware rules); scale_*
#: the round-12 control-plane-scale gate; elastic_scaleout_gain +
#: elastic_ok the round-18 elastic-capacity gate; alert_fired_ok +
#: liar_flagged_ok (+ signal_ok) the round-19 signal-plane gate;
#: autoscale_ok + autoscale_slo_min_saved the round-20 autoscaler
#: gate; lm_specdec_speedup + lm_specdec_accept + lm_cb_ttft_ms the
#: round-21 raw-decode gate (speculative verify speedup at the
#: measured acceptance, continuous-batching p99 TTFT); train_step_qps
#: + train_elastic_ok the round-22 elastic-training gate (trainer
#: examples/s under mixed load, step-exact elasticity verdict).
_COMPACT_KEEP_KEYS = (
    "headline_qps", "cluster_qps", "cluster_pipelining",
    "cluster_lm_tok_s", "cluster_lm_steady_tok_s",
    "cluster_lm_steady_s", "sharded_qps",
    "sharded_equal", "lm_sharded_toks",
    "lm_disagg_toks", "lm_sharded_equal",
    "lm_pp_toks", "lm_stream_ttft_ms",
    "lm_stream_vs_slab",
    "req_p99_ms", "req_goodput_qps",
    "req_shed_ratio", "req_failover_ok",
    "req_tpot_p95_ms",
    "kv_hit_ratio", "kv_warm_vs_cold_ttft",
    "trace_p99_attrib_ok",
    "lint_clean", "lint_race", "lint_payload",
    "scale_converge_s", "scale_detect_s",
    "scale_bytes_per_node_s", "scale_ok",
    "elastic_scaleout_gain", "elastic_ok",
    "alert_fired_ok", "liar_flagged_ok", "signal_ok",
    "autoscale_ok", "autoscale_slo_min_saved",
    "lm_specdec_speedup", "lm_specdec_accept",
    "lm_cb_ttft_ms",
    "train_step_qps", "train_elastic_ok",
    "section_errors", "sections_skipped",
)


def compact_summary_line(hl, device_str, baseline_qps, summary) -> str:
    """One JSON line, < COMPACT_SUMMARY_BUDGET chars, self-identifying
    via ``bench_summary_v1`` so downstream tools can find it in a
    truncated stdout tail."""
    doc = {
        "bench_summary_v1": True,
        "metric": "ResNet50 b32 inference throughput per chip",
        "value": hl.get("qps"),
        "unit": "queries/sec",
        "vs_baseline": (
            round(hl["qps"] / baseline_qps, 2) if hl.get("qps") else None
        ),
        "device": device_str,
        "summary": dict(summary),
    }
    line = json.dumps(doc, separators=(",", ":"), default=str)
    for key in _COMPACT_DROP_ORDER:
        if len(line) <= COMPACT_SUMMARY_BUDGET:
            break
        doc["summary"].pop(key, None)
        line = json.dumps(doc, separators=(",", ":"), default=str)
    if len(line) > COMPACT_SUMMARY_BUDGET:  # last resort: never exceed
        doc["summary"] = {
            k: doc["summary"].get(k) for k in _COMPACT_KEEP_KEYS
        }
        line = json.dumps(doc, separators=(",", ":"), default=str)
    return line


if __name__ == "__main__":
    main()
