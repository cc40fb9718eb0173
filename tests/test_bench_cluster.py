"""The bench's cluster-serving section logic, driven on CPU with the
tiny test model: healthy run with per-batch breakdown, the big-batch
variant, and BASELINE config 5's failure injection (a worker killed
abruptly mid-job must still yield 100% completion, with the requeue
and detection latency recorded). The real-chip numbers come from the
driver's bench run; this pins the MACHINERY so the TPU run can't hit
a code path for the first time."""

import numpy as np

from _tinynet import ensure_tinynet


def test_cluster_serving_bench_with_failure_injection():
    ensure_tinynet()
    from bench import _bench_cluster_serving
    from dml_tpu.inference import InferenceEngine
    import jax.numpy as jnp

    engine = InferenceEngine(dtype=jnp.float32)
    engine.load_model("TinyNet", batch_size=4)
    out = {}
    _bench_cluster_serving(
        engine, out, model="TinyNet", batch=4, big_batch=8,
        # 12 batches of 4: enough backlog for the 2-ACK probe to
        # commit even with per-worker transition discards
        n_queries=48, base_port=28901,
    )

    cs = out["cluster_serving"]
    assert cs["queries"] == 48
    assert cs["qps_end_to_end"] > 0
    bd = cs["breakdown"]
    assert bd["batches"] > 0
    assert bd["fetch_ms"] >= 0 and bd["infer_ms"] > 0
    # every exec stage is named (VERDICT r4 item 4): parked staged
    # time and the output PUT are explicit; other_ms is the residue
    # by construction (exec − all named stages)
    assert bd["stage_wait_ms"] >= 0 and bd["put_ms"] >= 0
    total_named = (bd["fetch_ms"] + bd["decode_ms"] + bd["infer_ms"]
                   + bd["stage_wait_ms"] + bd["put_ms"] + bd["other_ms"])
    assert abs(total_named - bd["exec_ms"]) < 1.0  # rounding only
    # exec spans first touch (prepare start) to ACK, so per batch it
    # still bounds fetch+infer — but with depth-2 pipelining the SUM
    # of per-batch exec exceeds the job wall (stages overlap; wall
    # tracks max(stage), see breakdown_stats docstring)
    assert bd["exec_ms"] >= bd["fetch_ms"] + bd["infer_ms"]
    # r6 schema: reference serial point + cache-matched forced
    # statics + the adaptive product serve
    assert cs["qps_unpipelined"] > 0
    assert cs["qps_depth1_static"] > 0
    assert cs["qps_pipelined_static"] > 0
    assert cs["decode_cache_speedup"] > 0
    assert cs["pipelining_speedup_static"] > 0
    # adaptive vs the better static (the never-below-~1.0 ratio)
    assert cs["pipelining_speedup"] > 0
    ad = cs["adaptive"]
    assert ad["mode"] == "adaptive"
    assert ad["depth"] in (1, 2)
    # the 12-batch CPU job feeds the bench-configured 2-ack probe to a
    # full commit, so the artifact records the verdict and why
    assert ad["state"] == "settled", ad
    assert ad["last_probe"]["winner"] == ad["depth"]
    assert "reason" in ad["last_probe"]

    assert out["cluster_serving_b128"]["queries"] == 48

    fi = out["cluster_serving_failure"]
    assert fi["completed"] == 48  # 100% completion under failure
    assert fi["killed_worker"]  # a real victim was chosen
    assert fi["qps_end_to_end"] > 0
    # failure_injected is defined as requeues > 0, so don't re-assert
    # the definition; detect_to_requeue_s can legitimately be None
    # when the requeue landed outside the bench's detection window —
    # when present it must be a positive latency
    if fi["detect_to_requeue_s"] is not None:
        assert fi["detect_to_requeue_s"] > 0
    # a raced kill records failure_injected=False honestly; the
    # completion assertion above is the load-bearing check either way


def test_chaos_bench_section_and_claim_check(tmp_path):
    """The bench `chaos` section machinery: one soak seed through the
    chaos engine yields nonzero failover/repair walls and a green
    invariant sweep, every adversarial scenario family sweeps green
    with the fuzz run leaving a nonzero malformed-drop counter, and
    the resulting artifact block passes claim_check's chaos
    validation (while gutted variants fail it)."""
    import json

    from bench import _bench_chaos
    from dml_tpu.tools import claim_check as cc

    out = {}
    _bench_chaos(out, seeds=(5,), scenario_seeds=(1,), base_port=28971)
    ch = out["chaos"]
    assert ch["all_invariants_ok"], ch["per_seed"]
    assert ch["failover_recovery_s"] > 0
    assert ch["store_repair_s"] > 0
    assert ch["failover_samples"] >= 1 and ch["repair_samples"] >= 1
    per = ch["per_seed"][0]
    assert per["seed"] == 5 and per["invariants_ok"]
    assert "done" in per["jobs"].values()
    # round 8: every adversarial family swept, fuzz left evidence
    assert set(ch["scenarios"]) == set(cc.CHAOS_SCENARIO_FAMILIES)
    for fam, entry in ch["scenarios"].items():
        assert entry["all_invariants_ok"], (fam, entry)
    assert ch["malformed_dropped_total"] > 0

    def artifact(tmpname, matrix):
        path = str(tmp_path / f"{tmpname}.json")
        with open(path, "w") as f:
            json.dump({"matrix": matrix}, f)
        return path

    # the real block is accepted
    assert cc.check_chaos_block(artifact("ok", {"chaos": ch})) == []
    # a wall-budget skip is honestly exempt
    assert cc.check_chaos_block(artifact("skip", {
        "_skipped": {"chaos": "wall budget"}, "cluster_serving": {},
    })) == []
    # a chaos section that "ran" but lost its recovery evidence fails
    gutted = dict(ch, failover_recovery_s=None)
    problems = cc.check_chaos_block(artifact("gut", {"chaos": gutted}))
    assert any("failover_recovery_s" in p for p in problems)
    # a failed invariant sweep fails the artifact
    red = dict(ch, all_invariants_ok=False,
               per_seed=[dict(per, invariants_ok=False)])
    problems = cc.check_chaos_block(artifact("red", {"chaos": red}))
    assert any("invariant sweep failed" in p for p in problems)
    # dropping the section without recording a skip fails
    problems = cc.check_chaos_block(
        artifact("lost", {"cluster_serving": {}})
    )
    assert any("no `chaos` section" in p for p in problems)
    # round 8: losing the scenario sweeps (or one family) fails
    problems = cc.check_chaos_block(
        artifact("noscen", {"chaos": {k: v for k, v in ch.items()
                                      if k != "scenarios"}})
    )
    assert any("chaos.scenarios missing" in p for p in problems)
    onefam = dict(ch, scenarios={
        **ch["scenarios"],
        "skew": dict(ch["scenarios"]["skew"], all_invariants_ok=False,
                     per_seed=[{"seed": 1, "invariants_ok": False}]),
    })
    problems = cc.check_chaos_block(artifact("redfam", {"chaos": onefam}))
    assert any("scenario 'skew'" in p for p in problems)
    # fuzz that ran but counted no drops fails
    nofuzz = dict(ch, malformed_dropped_total=0)
    problems = cc.check_chaos_block(artifact("nofuzz", {"chaos": nofuzz}))
    assert any("malformed_dropped_total" in p for p in problems)
    # pre-round-8 artifacts are exempt from the scenario requirement
    assert cc.check_chaos_block(artifact(
        "BENCH_r07", {"chaos": {k: v for k, v in ch.items()
                                if k not in ("scenarios",
                                             "malformed_dropped_total")}}
    )) == []


def test_nowait_window_bound():
    """infer_arrays_nowait must not enqueue more than its window of
    chunks eagerly (r3 review: a 10k-image call would otherwise pin
    O(n) buffers in HBM before the handle is drained)."""
    ensure_tinynet()
    from dml_tpu.inference import InferenceEngine
    import jax.numpy as jnp

    engine = InferenceEngine(dtype=jnp.float32)
    lm = engine.load_model("TinyNet", batch_size=2, warmup=False)
    calls = []
    orig = engine._dispatch_chunk

    def counting(lm, chunk, bs=None):
        calls.append(chunk.shape[0])
        return orig(lm, chunk, bs)

    engine._dispatch_chunk = counting
    imgs = np.zeros((20, 32, 32, 3), np.uint8)  # 10 chunks of 2
    h = engine.infer_arrays_nowait("TinyNet", imgs)
    assert len(calls) == 4  # the window, not all 10
    probs = h()
    assert len(calls) == 10  # the rest dispatched during drain
    assert probs.shape == (20, 1000)
    np.testing.assert_allclose(
        probs, engine.infer_arrays("TinyNet", imgs), rtol=1e-6
    )


def test_cluster_lm_serving_bench():
    """The bench's distributed-LM-serving section machinery on CPU
    with a tiny spec: prompts through the store -> scheduler -> LM
    server -> merged outputs, end-to-end rates recorded."""
    from bench import _bench_cluster_lm

    out = {}
    _bench_cluster_lm(
        out, n_prompts=6, new_tokens=8, base_port=28951,
        lm_overrides={"vocab_size": 128, "d_model": 32, "n_heads": 4,
                      "n_kv_heads": 2, "n_layers": 2, "d_ff": 64,
                      "dtype": "float32", "max_len": 64,
                      "max_slots": 4},
        # machinery-speed steady phase (the driver runs >= 15 s)
        steady_s=2.0, ramp_s=0.4, steady_sample_dt=0.2,
    )
    cs = out["cluster_lm_serving"]
    assert cs["prompts"] == 6
    assert cs["prompts_per_s"] > 0
    assert cs["gen_tok_per_s_end_to_end"] > 0
    # steady-state refill phase: post-ramp window covered, sustained
    # rate measured, tok/s-vs-wall curve recorded
    ss = cs["steady_state"]
    assert ss["mode"] == cs["mode_chosen"]
    assert ss["measured_steady_s"] >= 2.0
    assert ss["gen_tok_per_s_steady"] > 0
    assert ss["jobs_completed"] >= 1
    assert len(ss["curve_tok_per_s"]) >= 3
    assert all(len(pt) == 2 for pt in ss["curve_tok_per_s"])
    # the in-run serial baseline (lock-serialized r4 path) ran too
    assert cs["gen_tok_per_s_serial"] > 0
    assert cs["gen_tok_per_s_overlap"] > 0
    assert cs["overlap_vs_serial"] > 0
    assert cs["driver_steps"] > 0
    # the headline is the measured winner's rate (adaptive principle)
    assert cs["mode_chosen"] in ("overlap", "serial")
    assert cs["gen_tok_per_s_end_to_end"] == max(
        cs["gen_tok_per_s_overlap"], cs["gen_tok_per_s_serial"]
    )
