"""The bench's train section machinery on CPU with TinyNet + a tiny
LM: step rate, dispersion range, and the phase decomposition
(fwd / bwd / optimizer-update with per-phase MFU — VERDICT r4 item 5).
The real-chip numbers come from the driver's bench run; this pins the
code path so the TPU run can't hit it for the first time."""

from _tinynet import ensure_tinynet


def test_bench_train_section_with_phase_split(monkeypatch):
    ensure_tinynet()
    import jax.numpy as jnp

    import dml_tpu.benchmarks as benchmarks

    # the section divides by the chip's published peak, and the peaks
    # table rightly knows no CPU: the test answers for the chip
    monkeypatch.setattr(
        benchmarks, "peak_flops",
        lambda device=None: benchmarks.CHIP_PEAKS["TPU v5 lite"]["bf16_flops"],
    )

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from bench import _bench_train
    from dml_tpu.inference import InferenceEngine

    engine = InferenceEngine(dtype=jnp.float32)
    out = {}
    # 1-device mesh (the chip bench shape); the multi-device sharded
    # train path is covered by tests/test_parallel.py and the dryrun
    mesh = Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1),
        ("dp", "tp", "sp", "pp", "ep"),
    )
    _bench_train(
        engine, out, mesh=mesh,
        cnn_model="TinyNet", cnn_batch=4, cnn_hw=32,
        cnn_chains=(2, 6), phase_chains=((2, 6), (2, 6)),
        # machinery-speed sweep: one bigger batch + one grad-accum
        # point (the driver runs b64/b128/b128_ga4)
        cnn_sweep=((8, 1, (2, 6)), (8, 2, (2, 6))),
        lm_dims={"seq_len": 32, "vocab_size": 64, "d_model": 16,
                 "n_heads": 2, "n_layers": 1, "d_ff": 32,
                 "n_kv_heads": 1},
        lm_chains=(2, 6),
    )
    tr = out["train"]["tinynet_b4"]
    assert tr["img_per_s"] > 0 and tr["step_ms"] > 0
    lo, hi = tr["img_per_s_range"]
    assert lo <= tr["img_per_s"] <= hi

    # batch-scaling sweep rows (VERDICT r5 item 7): plain batch point
    # and the grad-accum point, keyed distinctly
    b8 = out["train"]["tinynet_b8"]
    assert b8["img_per_s"] > 0 and b8["step_ms"] > 0
    ga = out["train"]["tinynet_b8_ga2"]
    assert ga["img_per_s"] > 0 and ga["grad_accum"] == 2

    ps = tr["phase_split"]
    assert ps["fwd_ms"] > 0 and ps["fwd_bwd_ms"] > 0
    # bwd is the difference; update is the step residue — both are
    # clamped non-negative, and the phases tile the step
    assert ps["bwd_ms"] >= 0 and ps["optimizer_update_ms"] >= 0
    assert ps["optimizer_hbm_mb"] > 0

    lm = out["train"]["lm_t32"]
    assert lm["tok_per_s"] > 0 and lm["step_ms"] > 0
