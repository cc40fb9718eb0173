"""The state-space cell on the CPU at its rehearsal size: the whole of
`run.py`'s path but the look for a chip; the ways `correct` has to come out
false; the backend's refusal of a program that does not know the
architecture; the cost functions against counts worked by hand."""

import time

import jax
import numpy as np
import pytest

from benchmark.harness import cell as hc
from benchmark.harness import manifest as mf

CELL = "nemotron3_super_l11_ep4.jobs"
CONFIG = mf.load_json("configs", "nemotron3_super_l11_ep4")
SPEC = CONFIG["lm_spec"]
costs = mf.load_module("costs", "nemotron_h_latent_moe")


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
    assert {"throughput", "setup_s", "lm_step_ms.jobs",
            "experts_held_touched.jobs", "state_bytes_share.jobs",
            "state_slots.jobs", "expert_load_max.jobs", "slot_occupancy.jobs",
            "fetch_ms.jobs", "ack_wall_ms.jobs", "lm_readback_ms.jobs",
            "lm_pack_ms.jobs", "lm_deliver_ms.jobs", "lm_place_ms.jobs",
            "lm_idle_share.jobs", "prefill_useful_share.jobs",
            "window_compile_ms.jobs"} <= set(r["readers"])
    n = numbers(r)
    assert n["tokens_missing"]["value"] == 0
    assert n["served_gap_mean"]["tokens"] > 0
    # the configuration's sample, not twice the grid's slots
    assert n["served_gap_mean"]["over"] <= CONFIG["rehearsal"]["correct"]["sample"]
    for row in n.values():  # every number compared stands beside its limit
        assert "limit" in row or row["name"].startswith("control_")
    # the reference in int8 operands would not have passed
    assert n["control_int8_gap_mean"]["would_fail"]


@pytest.mark.parametrize("seed", [3, 8, 2_500_000_011])
def test_the_control_at_test_size_comes_out_not_correct(seed):
    """The test size states float32; its control is the program served in
    the next precision below, bfloat16 (the configuration's
    `rehearsal.correct.limits_why` has the readings)."""
    sound = rehearse(seed=seed)
    assert sound["correct"] is True, sound["numbers"]
    control = rehearse(seed=seed, variant="bf16")
    assert control["failed"] == 0 and control["correct"] is False
    assert numbers(control)["served_gap_mean"]["ok"] is False


def test_a_scan_state_left_by_the_last_occupant_makes_correct_false(
        monkeypatch):
    """A placement that copies every leaf of a prefilled row but the scan
    state: the slot goes on from what its last occupant (or an empty slot's
    garbage steps) left. Nothing fails to complete; the answers are
    another sequence's."""
    import dml_tpu.inference.lm_server as ls

    good = ls.LMServer._insert_impl

    def broken(self, cache, pcache, slot, row):
        kept = {name: lay["ssm"] for name, lay in cache.items()
                if "ssm" in lay}
        out = good(self, cache, pcache, slot, row)
        return {name: ({**lay, "ssm": kept[name]} if name in kept else lay)
                for name, lay in out.items()}

    monkeypatch.setattr(ls.LMServer, "_insert_impl", broken)
    r = rehearse()
    assert r["failed"] == 0 and r["correct"] is False
    assert numbers(r)["served_gap_max"]["ok"] is False


def test_a_token_altered_in_one_slot_makes_correct_false(monkeypatch):
    import dml_tpu.inference.lm_server as ls

    good = ls.LMServer.__init__

    def init(self, *args, **kw):
        good(self, *args, **kw)
        chunk_fn = self._chunk_fn

        def broken(*a):
            cache, cur, pos, toks, *rest = chunk_fn(*a)
            return (cache, cur, pos, toks.at[:, 2].set(
                (toks[:, 2] + 1) % self.cfg.vocab_size), *rest)

        self._chunk_fn = broken

    monkeypatch.setattr(ls.LMServer, "__init__", init)
    r = rehearse()
    assert r["failed"] == 0 and r["correct"] is False


def test_the_backend_refuses_a_program_that_declares_another_tree(monkeypatch):
    """What the parent commit does with this configuration: its
    `lm_spec_parts` ignores the keys it does not know and declares a
    decoder of classic blocks with experts in the hidden width. The run has
    to stop before any weight is made."""
    import dml_tpu.inference.lm_backend as program

    parts = program.lm_spec_parts
    known = ("vocab_size", "d_model", "n_heads", "n_kv_heads", "head_dim",
             "num_experts", "experts_per_token", "expert_d_ff", "gated",
             "experts_held", "dtype", "param_dtype")
    monkeypatch.setattr(program, "lm_spec_parts", lambda s: parts(
        {k: s[k] for k in known if k in s}))
    backend = mf.load_module("backends", "lm_state_space")
    reference = mf.load_module("references", "nemotron_h_latent_moe")
    small = {**CONFIG, **CONFIG["rehearsal"]}
    with pytest.raises(backend.UnknownArchitecture, match="another model"):
        backend.System(small, reference, seed=5)


def test_the_backend_serves_the_references_values_in_the_declared_tree():
    backend = mf.load_module("backends", "lm_state_space")
    reference = mf.load_module("references", "nemotron_h_latent_moe")
    small = {**CONFIG, **CONFIG["rehearsal"]}
    system = backend.System(small, reference, seed=5)
    try:
        made = reference.make_params(system.spec, 5)
        got, want = (jax.tree.leaves(system.be.server.params),
                     jax.tree.leaves(made))
        assert len(got) == len(want) > 0
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert system.be.server.cfg.layer_pattern == "EME*M"
        counters = system.counters()
        assert counters["state_bytes_scan"] == 2 * 4 * 8 * 16 * 16 * 4
    finally:
        system.free()


def test_warm_up_keeps_to_the_programs_bound_on_a_group(monkeypatch):
    """The (bucket, rows) groups warm-up runs at the REAL sizes, from the
    real traffic's lengths, without building the model: every bucket from
    512 up, rows in powers of two, no group of several rows over the
    program's bound of padded tokens."""
    backend = mf.load_module("backends", "lm_state_space")
    cell = hc.Cell(mf.load(), CELL)
    reqs = cell.driver.plan(cell.traffic, 50.0, 3, cell.config, cell.items)
    served = []

    class Driver:
        def serve(self, prompts, budgets):
            served.append((len(prompts), len(prompts[0])))

    class Server:
        max_len, _group_tokens = 4096, 8192

    class Backend:
        driver, server = Driver(), Server()

    system = object.__new__(backend.System)
    system.be, system.slots, system.pool_copies = Backend(), 64, 2
    system.spec = SPEC
    out = system.warm([r.size for r in reqs])
    assert out["groups"] == [
        [512, 1], [512, 2], [512, 4], [512, 8], [512, 16],
        [1024, 1], [1024, 2], [1024, 4], [1024, 8],
        [2048, 1], [2048, 2], [2048, 4]]
    assert [k for k, _ in served] == [k for _, k in out["groups"]]


# by hand. A state-space layer: in_proj 4096 x (8192 + 10240 + 128), out_proj
# 8192 x 4096, conv 4 x 10240 + its bias, A_log, D, dt_bias 128 each, the
# gated norm 8192. The attention layer: q and o 4096 x 4096, k and v 4096 x
# 256. An expert: 2 x 1024 x 2688. What every token of an expert layer takes:
# router 4096 x 512 + 512, latent projections 2 x 4096 x 1024, shared expert
# 2 x 4096 x 5376.
SSM = 4096 * 18560 + 8192 * 4096 + 5 * 10240 + 3 * 128 + 8192
ATTN = 4096 * (4096 + 2 * 256) + 4096 * 4096
EXPERT = 2 * 1024 * 2688
FIXED = 4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
HEAD = 4096 * 32768
# a slot: 5 layers x (128 x 64 x 128 float32 + 3 x 10240 bfloat16)
STATE = 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)


def test_parameters_by_hand():
    assert (SSM, ATTN, EXPERT, FIXED) == (
        109_635_968, 35_651_584, 5_505_024, 54_526_464)
    assert costs.ssm_params(SPEC) == SSM
    assert costs.attention_params(SPEC) == ATTN
    assert costs.expert_params(SPEC) == EXPERT
    assert costs.expert_layer_fixed_params(SPEC) == FIXED
    assert costs.param_count(SPEC) == (
        5 * SSM + ATTN + 5 * (FIXED + 128 * EXPERT) + 2 * HEAD
    ) == 4_648_114_560
    ref = mf.load_module("references", "nemotron_h_latent_moe")
    norms = 11 * 4096 + 4096
    assert ref.param_count(SPEC) == costs.param_count(SPEC) + norms


def test_one_decode_step_by_hand():
    """64 occupied slots, 115 held experts touched a layer, 30,000 live
    tokens: the issue's ~11 GB and 13.5 ms a step."""
    from benchmark.harness.peaks import least_seconds

    assert costs.kv_bytes_per_token(SPEC) == 1024  # 2 x 2 x 128 x 2 B
    assert costs.state_bytes_per_slot(SPEC) == STATE == 21_278_720
    parts = costs.decode_step_parts(SPEC, 30_000, 64, 115)
    assert parts == {
        "experts": 5 * 115 * EXPERT * 2,
        "state": 2 * 64 * STATE,
        "state_space_matrices": 5 * SSM * 2,
        "expert_layer_fixed": 5 * FIXED * 2,
        "head": HEAD * 2,
        "attention_matrices": ATTN * 2,
        "kv": 30_000 * 1024,
    }
    total = costs.decode_step_bytes(SPEC, 30_000, 64, 115)
    assert total == sum(parts.values()) == 11_066_536_704
    assert least_seconds(0, total, "TPU v5 lite") == pytest.approx(
        13.51e-3, rel=1e-3)
    assert parts["state"] / total == pytest.approx(0.2461, rel=1e-3)
    # more experts than the tree holds cannot be touched
    assert costs.decode_step_bytes(SPEC, 0, 64, 500) == \
        costs.decode_step_bytes(SPEC, 0, 64, 128)
    # an empty grid moves no state
    assert costs.decode_step_parts(SPEC, 0, 0, 0)["state"] == 0


def test_prefill_flops_of_a_256_token_prompt():
    t = 256
    scan = (8 * 128 + 8192) * 129 + 4 * 8192 * 128
    per_token = (5 * (2 * SSM + scan) + 2 * ATTN
                 + 5 * 2 * (FIXED + 22 * 128 / 512 * EXPERT))
    want = per_token * t + 2 * 4096 * t * t + 2 * HEAD
    assert costs.prefill_flops(SPEC, t) == want
    assert want == pytest.approx(5.237e11, rel=1e-3)
