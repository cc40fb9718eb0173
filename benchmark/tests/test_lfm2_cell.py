"""The short-convolution cell on the CPU at its rehearsal size: the whole of
`run.py`'s path but the look for a chip; the ways `correct` has to come out
false; the backend's refusal of a program that does not know the
architecture; the cost functions against counts worked by hand."""

import time

import jax
import numpy as np
import pytest

from benchmark.harness import cell as hc
from benchmark.harness import manifest as mf

CELL = "lfm2_8b_a1b_ep4.jobs"
CONFIG = mf.load_json("configs", "lfm2_8b_a1b_ep4")
SPEC = CONFIG["lm_spec"]
costs = mf.load_module("costs", "lfm2_conv_moe")


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
            "state_slots.jobs", "kv_bytes_share.jobs", "expert_load_max.jobs",
            "slot_occupancy.jobs", "fetch_ms.jobs", "ack_wall_ms.jobs",
            "lm_readback_ms.jobs", "lm_pack_ms.jobs", "lm_deliver_ms.jobs",
            "lm_place_ms.jobs", "lm_idle_share.jobs", "lm_route_ms.jobs",
            "lm_exposed_share.jobs", "lm_turn_ms.jobs",
            "prefill_useful_share.jobs", "window_compile_ms.jobs",
            } <= set(r["readers"])
    n = numbers(r)
    assert n["tokens_missing"]["value"] == 0
    assert n["served_gap_mean"]["tokens"] > 0
    # the configuration's sample, not twice the grid's slots
    assert n["served_gap_mean"]["over"] <= CONFIG["rehearsal"]["correct"]["sample"]
    for row in n.values():  # every number compared stands beside its limit
        assert "limit" in row or row["name"].startswith("control_")
    # the reference in int8 operands would not have passed
    assert n["control_int8_gap_mean"]["would_fail"]


def test_the_rehearsal_serves_prompts_shorter_than_the_window():
    cell = hc.Cell(mf.load(), CELL, rehearse=True)
    reqs = cell.driver.plan(cell.traffic, 3.0, 3, cell.config, cell.items)
    lengths = sorted(r.size["prompt_tokens"] for r in reqs)
    assert lengths[:2] == [1, 2] and lengths[-1] == 40


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


def test_a_window_left_by_the_last_occupant_makes_correct_false(monkeypatch):
    """A placement that copies every leaf of a prefilled row but the
    convolution windows: the slot goes on from what its last occupant (or
    an empty slot's garbage steps) left. Nothing fails to complete; the
    answers are another sequence's."""
    import dml_tpu.inference.lm_server as ls

    good = ls.LMServer._insert_impl

    def broken(self, cache, pcache, slot, row):
        kept = {name: lay["conv"] for name, lay in cache.items()
                if "conv" in lay}
        out = good(self, cache, pcache, slot, row)
        return {name: ({**lay, "conv": kept[name]} if name in kept else lay)
                for name, lay in out.items()}

    monkeypatch.setattr(ls.LMServer, "_insert_impl", broken)
    r = rehearse()
    assert r["failed"] == 0 and r["correct"] is False
    assert numbers(r)["served_gap_max"]["ok"] is False


def test_a_window_taken_at_the_padded_length_makes_correct_false(monkeypatch):
    """A prefill that hands back the window of the PADDED row (the
    bucket's last rows, the pad's) where the row's own length ends."""
    import dml_tpu.inference.generate as g

    good = g.causal_conv
    monkeypatch.setattr(g, "causal_conv", lambda x, k, state=None,
                        lengths=None, **kw: good(x, k, state, None, **kw))
    r = rehearse()
    assert r["failed"] == 0 and r["correct"] is False


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
    """A program that ignores the keys it does not know (`attention_layers`,
    `tied_head`, `qk_norm`, `dense_layers`) declares a decoder of classic
    attention blocks with an untied head. The run has to stop before any
    weight is made. (The parent commit stops earlier still: its `lm_arch`
    raises on `qk_norm` under `attention_layers`.)"""
    import dml_tpu.inference.lm_backend as program

    parts = program.lm_spec_parts
    known = ("vocab_size", "d_model", "n_heads", "n_kv_heads", "head_dim",
             "n_layers", "num_experts", "experts_per_token", "expert_d_ff",
             "gated", "experts_held", "dtype", "param_dtype")
    monkeypatch.setattr(program, "lm_spec_parts", lambda s: parts(
        {k: s[k] for k in known if k in s}))
    backend = mf.load_module("backends", CONFIG["system"])
    reference = mf.load_module("references", "lfm2_conv_moe")
    small = {**CONFIG, **CONFIG["rehearsal"]}
    with pytest.raises(backend.UnknownArchitecture, match="another model"):
        backend.System(small, reference, seed=5)


def test_the_backend_serves_the_references_values_in_the_declared_tree():
    backend = mf.load_module("backends", CONFIG["system"])
    reference = mf.load_module("references", "lfm2_conv_moe")
    small = {**CONFIG, **CONFIG["rehearsal"]}
    system = backend.System(small, reference, seed=5)
    try:
        made = reference.make_params(system.spec, 5)
        got, want = (jax.tree.leaves(system.be.server.params),
                     jax.tree.leaves(made))
        assert len(got) == len(want) > 0
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        cfg = system.be.server.cfg
        assert cfg.has_state and cfg.has_conv and cfg.ssm is None
        counters = system.counters()
        # four conv layers x 4 slots x 2 rows x 64 values, float32
        assert counters["state_bytes_conv"] == 4 * 4 * 2 * 64 * 4
        assert counters["state_bytes_scan"] == 0
    finally:
        system.free()


def test_warm_up_keeps_to_the_programs_bound_on_a_group():
    """The (bucket, rows) groups warm-up runs at the REAL sizes, from the
    real traffic's lengths, without building the model: every bucket from
    512 up, rows in powers of two, no group of several rows over the
    program's bound of padded tokens, four copies of the pool in flight."""
    backend = mf.load_module("backends", CONFIG["system"])
    cell = hc.Cell(mf.load(), CELL)
    assert cell.traffic["jobs_in_flight"] == CONFIG["warm_pool_copies"] == 4
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
    system.be, system.slots, system.pool_copies = Backend(), 128, 4
    system.spec = SPEC
    out = system.warm([r.size for r in reqs])
    assert out["groups"] == [
        [512, 1], [512, 2], [512, 4], [512, 8], [512, 16],
        [1024, 1], [1024, 2], [1024, 4], [1024, 8],
        [2048, 1], [2048, 2], [2048, 4]]
    assert [k for k, _ in served] == [k for _, k in out["groups"]]


# by hand. A conv layer: in_proj 2048 x 6144, out_proj 2048 x 2048 (its taps
# 3 x 2048 counted apart). An attention layer: q and o 2048 x 2048, k and v
# 2048 x 512, two head norms of 64. A dense layer 3 x 2048 x 7168. An expert
# 3 x 2048 x 1792. What every token of an expert layer takes: the router
# 2048 x 32 and its bias.
CONV = 2048 * 6144 + 2048 * 2048
TAPS = 18 * 3 * 2048
ATTN = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
DENSE = 3 * 2048 * 7168
EXPERT = 3 * 2048 * 1792
FIXED = 2048 * 32 + 32
HEAD = 65536 * 2048
# a slot: 18 layers x 2 rows x 2048 bfloat16
STATE = 18 * 2 * 2048 * 2


def test_parameters_by_hand():
    assert (CONV, ATTN, DENSE, EXPERT, FIXED) == (
        16_777_216, 10_485_888, 44_040_192, 11_010_048, 65_568)
    assert costs.conv_params(SPEC) == CONV
    assert costs.conv_taps(SPEC) == TAPS
    assert costs.attention_params(SPEC) == ATTN
    assert costs.dense_params(SPEC) == DENSE
    assert costs.expert_params(SPEC) == EXPERT
    assert costs.expert_layer_fixed_params(SPEC) == FIXED
    assert costs.param_count(SPEC) == (
        18 * CONV + TAPS + 6 * ATTN + 2 * DENSE + 22 * (FIXED + 8 * EXPERT)
        + HEAD) == 2_526_524_864
    ref = mf.load_module("references", "lfm2_conv_moe")
    norms = 24 * 2 * 2048 + 2048
    assert ref.param_count(SPEC) == costs.param_count(SPEC) + norms
    # whole, with every expert and the head tied: the card's 8.3B
    whole = {**SPEC, "experts_held": [0, 32]}
    assert costs.param_count(whole) == 8_339_830_208


def test_one_decode_step_by_hand():
    """128 occupied slots, all 8 held experts touched a layer, 700 live
    rows a slot: the issue's ~6.1 GB and 7.5 ms a step."""
    from benchmark.harness.peaks import least_seconds

    assert costs.kv_bytes_per_token(SPEC) == 12_288  # 6 x 2 x 8 x 64 x 2 B
    assert costs.state_bytes_per_slot(SPEC) == STATE == 147_456
    parts = costs.decode_step_parts(SPEC, 128 * 700, 128, 8)
    assert parts == {
        "experts": 22 * 8 * EXPERT * 2,
        "conv_matrices": (18 * CONV + TAPS) * 2,
        "attention_matrices": 6 * ATTN * 2,
        "dense": 2 * DENSE * 2,
        "expert_layer_fixed": 22 * FIXED * 2,
        "head": HEAD * 2,
        "kv": 128 * 700 * 12_288,
        "state": 2 * 128 * STATE,
    }
    total = costs.decode_step_bytes(SPEC, 128 * 700, 128, 8)
    assert total == sum(parts.values()) == 6_191_803_264
    assert least_seconds(0, total, "TPU v5 lite") == pytest.approx(
        7.56e-3, rel=1e-3)
    assert parts["kv"] / total == pytest.approx(0.1778, rel=1e-3)
    assert parts["state"] / total == pytest.approx(0.0061, rel=1e-2)
    # more experts than the tree holds cannot be touched
    assert costs.decode_step_bytes(SPEC, 0, 128, 50) == \
        costs.decode_step_bytes(SPEC, 0, 128, 8)
    # an empty grid moves no window
    assert costs.decode_step_parts(SPEC, 0, 0, 0)["state"] == 0


def test_prefill_flops_of_a_256_token_prompt():
    t = 256
    per_token = 2 * (18 * CONV + 6 * ATTN + 2 * DENSE
                     + 22 * (FIXED + 4 * 8 / 32 * EXPERT)
                     + TAPS + 18 * 2048)
    want = per_token * t + 2 * 32 * 64 * t * t * 6 + 2 * HEAD
    assert costs.prefill_flops(SPEC, t) == want
    assert want == pytest.approx(3.586e11, rel=1e-3)
