"""The block-diffusion cell on the CPU at its rehearsal size: the whole of
`run.py`'s path but the look for a chip; the two ways `correct` has to come
out false; the backend's refusal of a program that does not know the
architecture; the cost functions against counts worked by hand."""

import time

import jax
import numpy as np
import pytest

from benchmark.harness import cell as hc
from benchmark.harness import manifest as mf

CELL = "sdar30b_a3b_l6.jobs"
CONFIG = mf.load_json("configs", "sdar30b_a3b_l6")
SPEC = CONFIG["lm_spec"]
costs = mf.load_module("costs", "sdar_moe_block_diffusion")


def rehearse(seed=3, seconds=3.0, trace=False, **kw):
    return hc.run_cell(CELL, seed, seconds, trace, t_start=time.monotonic(),
                       rehearse=True, **kw)


def numbers(result):
    return {n["name"]: n for n in result["numbers"]}


def test_cell_runs_end_to_end_and_prints_no_device_metric():
    r = rehearse(trace=True, control=True)
    assert r["correct"] is True, r["numbers"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["metrics"] == {} and "breakdown" not in r
    assert r["device"]["platform"] == "cpu"
    assert {"throughput", "setup_s", "forward_ms.jobs",
            "tokens_per_forward.jobs", "experts_touched.jobs",
            "expert_load_max.jobs", "slot_occupancy.jobs", "fetch_ms.jobs",
            "ack_wall_ms.jobs", "lm_readback_ms.jobs", "lm_pack_ms.jobs",
            "lm_deliver_ms.jobs", "lm_place_ms.jobs", "lm_idle_share.jobs",
            "prefill_useful_share.jobs", "window_compile_ms.jobs",
            } <= set(r["readers"])
    n = numbers(r)
    assert n["tokens_missing"]["value"] == 0
    assert n["schedule_faults"]["value"] == 0
    assert n["served_gap_mean"]["tokens"] > 0
    for row in n.values():  # every number compared stands beside its limit
        assert "limit" in row or row["name"].startswith("control_")
    # the reference in int8 operands would not have passed
    assert all(n[k]["would_fail"] for k in n if k.startswith("control_"))


@pytest.mark.parametrize("seed", [3, 8, 10])
def test_the_control_at_test_size_comes_out_not_correct(seed):
    """The test size states float32; its control is the program served in
    the next precision below, bfloat16 (the configuration's
    `rehearsal.correct.limits_why` has the readings)."""
    sound = rehearse(seed=seed)
    assert sound["correct"] is True, sound["numbers"]
    control = rehearse(seed=seed, variant="bf16")
    assert control["failed"] == 0 and control["correct"] is False
    assert numbers(control)["served_gap_mean"]["ok"] is False


def test_a_commit_that_keeps_a_mask_row_makes_correct_false(monkeypatch):
    """The commit forward of ONE slot stores the rows of an all-mask block
    instead of the block's final tokens: nothing fails to complete, the
    schedule is kept, and the blocks after it sit far below the
    reference's best."""
    import dml_tpu.inference.generate as G
    import dml_tpu.inference.lm_server as ls

    good = G.batched_block_step
    mask_id = CONFIG["rehearsal"]["lm_spec"]["mask_token_id"]

    def broken(params, cfg, cache, tokens, pos, *, head=True, **kw):
        if not head:  # the commit forward
            tokens = tokens.at[1].set(mask_id)
        return good(params, cfg, cache, tokens, pos, head=head, **kw)

    monkeypatch.setattr(ls, "batched_block_step", broken)
    r = rehearse()
    n = numbers(r)
    assert r["failed"] == 0 and n["schedule_faults"]["value"] == 0
    assert r["correct"] is False
    assert n["served_gap_max"]["ok"] is False


def test_a_wrong_schedule_makes_correct_false(monkeypatch):
    import dml_tpu.inference.lm_backend as lb

    good = lb.LMBackend.serve_files

    def serve_files(self, paths, on_dispatch=None, on_token=None):
        results, secs, cost = good(self, paths, on_dispatch, on_token)
        for v in results.values():
            v["fixed_at"] = [1] * len(v["fixed_at"])  # all at the first step
        return results, secs, cost

    monkeypatch.setattr(lb.LMBackend, "serve_files", serve_files)
    r = rehearse()
    assert r["correct"] is False
    assert numbers(r)["schedule_faults"]["value"] > 0


def test_the_backend_refuses_a_program_that_declares_another_tree(monkeypatch):
    """What the parent commit does with this configuration: its
    `lm_spec_parts` ignores the keys it does not know and declares a dense
    decoder. The run has to stop before any weight is made."""
    import dml_tpu.inference.lm_backend as program

    parts = program.lm_spec_parts
    monkeypatch.setattr(program, "lm_spec_parts", lambda s: parts({
        k: s[k] for k in ("vocab_size", "d_model", "n_heads", "n_kv_heads",
                          "n_layers", "d_ff", "dtype")}))
    backend = mf.load_module("backends", "lm_block_diffusion")
    reference = mf.load_module("references", "sdar_moe_block_diffusion")
    small = {**CONFIG, **CONFIG["rehearsal"]}
    with pytest.raises(backend.UnknownArchitecture, match="another model"):
        backend.System(small, reference, seed=5)


def test_the_backend_serves_the_references_values_in_the_declared_tree():
    backend = mf.load_module("backends", "lm_block_diffusion")
    reference = mf.load_module("references", "sdar_moe_block_diffusion")
    small = {**CONFIG, **CONFIG["rehearsal"]}
    system = backend.System(small, reference, seed=5)
    try:
        made = reference.make_params(system.spec, 5)
        got, want = (jax.tree.leaves(system.be.server.params),
                     jax.tree.leaves(made))
        assert len(got) == len(want) > 0
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert system.be.server.diffusion.steps == 2
        assert system.be.server.blocks_per_dispatch == 2
    finally:
        system.free()


# by hand, per layer: q 2048 x 4096, k and v 2 x 2048 x 512, o 4096 x 2048,
# router 2048 x 128; one expert 3 x 2048 x 768
ATTN = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2048 * 128
EXPERT = 3 * 2048 * 768
HEAD = 2048 * 151936


def test_parameters_by_hand():
    assert ATTN == 19_136_512 and EXPERT == 4_718_592
    assert costs.attention_params(SPEC) == ATTN
    assert costs.expert_params(SPEC) == EXPERT
    assert costs.param_count(SPEC) == 6 * (ATTN + 128 * EXPERT) + 2 * HEAD \
        == 4_361_027_584
    ref = mf.load_module("references", "sdar_moe_block_diffusion")
    norms = 6 * (2 * 2048 + 2 * 128) + 2048
    assert ref.param_count(SPEC) == costs.param_count(SPEC) + norms


def test_forward_bytes_with_every_expert_touched_and_400_live_tokens():
    # K and V, 4 KV heads x 128, bf16, 6 layers: 2*4*128*2*6 = 12 KiB a token
    assert costs.kv_bytes_per_token(SPEC) == 12288
    body = 6 * (ATTN + 128 * EXPERT) * 2 + 400 * 12288
    denoise = body + HEAD * 2
    # a commit reads no logits: no head, and not the last layer's experts
    commit = body - 128 * EXPERT * 2
    assert costs.forward_bytes(SPEC, 400, 128, head=True) == denoise
    assert costs.forward_bytes(SPEC, 400, 128, head=False) == commit
    # more experts than the tree holds cannot be touched
    assert costs.forward_bytes(SPEC, 400, 500, head=False) == commit
    # a dispatch of 8 blocks: 16 denoising forwards and 8 commits
    assert costs.dispatch_bytes(SPEC, 8, 400, 128) == 16 * denoise + 8 * commit
    assert body == pytest.approx(7.482e9, rel=1e-3)
    assert denoise == pytest.approx(8.105e9, rel=1e-3)


def test_prefill_flops_of_a_256_token_prompt():
    t = 256
    want = 6 * (2 * (ATTN + 8 * EXPERT) * t + 2 * 4096 * t * (t + 4))
    assert costs.prefill_flops(SPEC, t) == want
    assert want == pytest.approx(1.780e11, rel=1e-3)
