"""chip_smoke.py: the refusal contract, and every phase rehearsed on
the CPU mesh at a tiny size — the same functions the chip run calls at
full width, so the smoke cannot rot between chip runs. What only the
chip can show (the Mosaic side of each switch) is asserted by
`chip_smoke.main`, not here: on the CPU every kernel flag is False."""

import asyncio
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY_LM = {
    "name": "TinyLM", "vocab_size": 256, "d_model": 64, "n_heads": 8,
    "n_kv_heads": 4, "n_layers": 2, "d_ff": 128, "dtype": "float32",
    "max_new_tokens": 6, "max_slots": 4, "max_len": 64, "seed": 0,
}


def test_refuses_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=ROOT,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_image_phase_tiny(tmp_path):
    from _tinynet import ensure_tinynet
    from dml_tpu.inference.engine import InferenceEngine

    ensure_tinynet()
    report = asyncio.run(chip_smoke.image_phase(
        InferenceEngine(dtype=jnp.float32), "TinyNet", 4, seed=3,
        root=str(tmp_path), base_port=29711, n_files=4, n_queries=8,
        n_requests=2, image_hw=48,
    ))
    assert report["answers_equal_direct_engine"]
    assert report["job_queries"] == 8 and report["ingress_requests"] == 2
    assert report["forward_has_tpu_custom_call"] is False  # CPU: jnp path


def test_lm_phase_tiny(tmp_path):
    report = asyncio.run(chip_smoke.lm_phase(
        TINY_LM, seed=1, root=str(tmp_path), base_port=29731,
        n_prompts=4, prompt_lengths=(5, 18), spec_k=2,
    ))
    # float32 on the CPU: exact, token for token, in every serving form
    for form, n in (("job", 4), ("streamed_request", 1), ("spec_job", 4)):
        assert report[form]["equal_generate"] == n
        assert report[form]["worst_reference_margin"] == 0.0
    assert report["spec_job"]["equal_plain_job"] == 4
    # the target drafting for itself is accepted (nearly) always
    assert report["spec_job"]["accept_rate"] > 0.9
    assert report["has_tpu_custom_call"] == {
        "prefill": False, "decode": False}
    assert report["decode_kernel_by_policy"] is False


def test_multichip_phase_tiny(tmp_path):
    report = chip_smoke.multichip_phase(
        TINY_LM, seed=2, root=str(tmp_path), tp=4, n_prompts=4,
        prompt_lengths=(5, 18),
    )
    assert report["equal_one_device"] == 4
    assert report["tp4"]["equal_generate"] == 4
    assert report["mesh"]["tp"] == 4
    for held in (report["param_bytes"], report["kv_cache_bytes"]):
        assert all(
            abs(s - 0.25) < 0.05 for s in held["share_per_device"].values()
        )


def test_kernel_phase_tiny():
    report = chip_smoke.kernel_phase(
        0, batch=2, heads=4, kv_heads=2, head_dim=8, context=64, image_hw=40,
    )
    assert report["fused_normalize_tf_max_err"] == 0.0
    assert report["decode_attention_bf16_max_err"] < 0.02


def test_a_failed_comparison_raises(tmp_path):
    from dml_tpu.inference.lm_backend import lm_spec_parts

    params, cfg = lm_spec_parts(TINY_LM)
    prompts = chip_smoke.make_prompts(0, cfg.vocab_size, 2, (5,))
    ref = chip_smoke.GreedyReference(params, cfg, prompts, 4)
    assert ref.check(ref.generated, "generate itself")["equal_generate"] == 2
    wrong = [list(ref.generated[0]), list(ref.generated[1])]
    wrong[1][2] = (wrong[1][2] + 1) % cfg.vocab_size
    with pytest.raises(AssertionError, match="prompt 1: served"):
        ref.check(wrong, "job")
    # everything on one device is not a spread
    tree = {"w": jnp.ones((8, 8))}
    with pytest.raises(AssertionError, match="not spread"):
        chip_smoke._require_spread(tree, jax.devices()[:4], "params")
