"""The latent-attention cell on the CPU at its rehearsal size: the whole of
`run.py`'s path but the look for a chip; the ways `correct` has to come out
false; the backend's refusal of a program that does not know the
architecture; the cost functions against counts worked by hand."""

import time

import jax
import numpy as np
import pytest

from benchmark.harness import cell as hc
from benchmark.harness import manifest as mf

CELL = "joyai_llm_flash_ep16.jobs"
CONFIG = mf.load_json("configs", "joyai_llm_flash_ep16")
SPEC = CONFIG["lm_spec"]
costs = mf.load_module("costs", "joyai_mla_moe")


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
    # every metric BENCHMARK.json lists for the cell finds something to
    # read, but the two device-trace shares (no device trace on a CPU)
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in mf.metrics_of(mf.load(), CELL, g)}
    assert {"latent_rows_share.jobs", "experts_held_touched.jobs",
            "throughput", "setup_s"} <= listed
    assert listed - set(r["readers"]) == {
        "latent_decode_roofline.jobs", "prefill_roofline.jobs"}
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


def test_rows_left_by_the_last_occupant_make_correct_false(monkeypatch):
    """A placement that writes no latent rows: the slot attends what its
    last occupant (or nobody) left under the new request's length. Nothing
    fails to complete; the answers are another sequence's."""
    import dml_tpu.inference.lm_server as ls

    monkeypatch.setattr(ls.LMServer, "_insert_impl",
                        lambda self, cache, pcache, slot, row: cache)
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
    decoder of classic grouped-attention blocks, every layer an expert
    layer. The run has to stop before any weight is made."""
    import dml_tpu.inference.lm_backend as program

    parts = program.lm_spec_parts
    unknown = ("attention", "latent_attention", "rope_pairing",
               "dense_layers")
    monkeypatch.setattr(program, "lm_spec_parts", lambda s: parts(
        {k: v for k, v in s.items() if k not in unknown}))
    backend = mf.load_module("backends", "lm_latent_attention")
    reference = mf.load_module("references", "joyai_mla_moe")
    small = {**CONFIG, **CONFIG["rehearsal"]}
    with pytest.raises(backend.UnknownArchitecture, match="another model"):
        backend.System(small, reference, seed=5)


def test_the_backend_serves_the_references_values_in_the_declared_tree():
    backend = mf.load_module("backends", "lm_latent_attention")
    reference = mf.load_module("references", "joyai_mla_moe")
    small = {**CONFIG, **CONFIG["rehearsal"]}
    system = backend.System(small, reference, seed=5)
    try:
        made = reference.make_params(system.spec, 5)
        got, want = (jax.tree.leaves(system.be.server.params),
                     jax.tree.leaves(made))
        assert len(got) == len(want) > 0
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert system.be.server.cfg.latent.row_width == 32 + 8
        counters = system.counters()
        # 4 slots x 128 rows x 3 layers x 128 columns (40 values) x 4 B
        assert counters["state_bytes_latent"] == 4 * 128 * 3 * 128 * 4
        assert counters["state_bytes_kv"] == 0
    finally:
        system.free()


def test_warm_up_runs_one_row_a_bucket(monkeypatch):
    """The (bucket, rows) groups warm-up runs at the REAL sizes, from the
    real traffic's lengths, without building the model: the program
    prefills every prompt of a latent-attention model alone, so one
    program a bucket."""
    backend = mf.load_module("backends", "lm_latent_attention")
    from dml_tpu.inference import lm_server as ls

    cell = hc.Cell(mf.load(), CELL)
    reqs = cell.driver.plan(cell.traffic, 50.0, 3, cell.config, cell.items)
    sizes = [r.size for r in reqs]
    assert len(sizes) == 64
    assert min(s["prompt_tokens"] for s in sizes) >= 512
    assert max(s["prompt_tokens"] + s["output_tokens"] for s in sizes) <= 3968
    served = []

    class Driver:
        def serve(self, prompts, budgets):
            served.append((len(prompts), len(prompts[0])))

    class Server:
        max_len, _group_tokens = 4096, ls._LATENT_GROUP_TOKENS

    class Backend:
        driver, server = Driver(), Server()

    system = object.__new__(backend.System)
    system.be, system.slots, system.pool_copies = Backend(), 16, 2
    system.spec = SPEC
    out = system.warm(sizes)
    assert [g for g in out["groups"] if g[0] > 512] == [
        [1024, 1], [2048, 1], [4096, 1]]
    assert all(rows == 1 for _, rows in out["groups"])
    # and the program forms no other: a round of sixteen long prompts
    groups = ls._prefill_groups(
        [s["prompt_tokens"] for s in sizes[:16]], 4096, 16,
        ls._LATENT_GROUP_TOKENS)
    assert all(rows == 1 and len(members) == 1 for _, rows, members in groups)


# by hand, at the published widths. Attention: q_a 2048 x 1536, q_b 1536 x
# (32 x 192), kv_a 2048 x 576, kv_b 512 x (32 x 256), o 4096 x 2048. A routed
# or the shared expert: 3 x 2048 x 768. What every token of an expert layer
# takes: router 2048 x 256 + 256 and the shared expert. The dense layer's
# MLP: 3 x 2048 x 7168.
ATTN = 2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
EXPERT = 3 * 2048 * 768
FIXED = 2048 * 256 + 256 + EXPERT
DENSE = 3 * 2048 * 7168
HEAD = 2048 * 129280


def test_parameters_by_hand():
    assert (ATTN, EXPERT, FIXED, DENSE) == (
        26_345_472, 4_718_592, 5_243_136, 44_040_192)
    assert costs.attention_params(SPEC) == ATTN
    assert costs.expert_params(SPEC) == EXPERT
    assert costs.expert_layer_fixed_params(SPEC) == FIXED
    assert costs.dense_params(SPEC) == DENSE
    assert costs.param_count(SPEC) == (
        40 * ATTN + DENSE + 39 * (FIXED + 16 * EXPERT) + 2 * HEAD
    ) == 4_776_273_664
    # equal to the tree's own count, norms apart (two a layer, the two
    # latents' and the final one)
    ref = mf.load_module("references", "joyai_mla_moe")
    norms = 40 * (2 * 2048 + 1536 + 512) + 2048
    assert ref.param_count(SPEC) == costs.param_count(SPEC) + norms
    from dml_tpu.inference.lm_backend import lm_spec_parts

    tree = jax.eval_shape(lambda: lm_spec_parts(SPEC)[0])
    assert sum(x.size for x in jax.tree.leaves(tree)) == ref.param_count(SPEC)


def test_one_decode_step_by_hand():
    """16 occupied slots at ~2,300 live rows each, 6.3 held experts touched
    a layer: the issue's ~7.2 GB and 8.7 ms a step, latent rows counted
    ONCE at 576 values."""
    from benchmark.harness.peaks import least_seconds

    assert costs.kv_bytes_per_token(SPEC) == 40 * 576 * 2 == 46_080
    parts = costs.decode_step_parts(SPEC, 16 * 2300, 16, 6.3)
    assert parts == {
        "latent_rows": 16 * 2300 * 46_080,
        "attention_matrices": 40 * ATTN * 2,
        "experts": 39 * 6.3 * EXPERT * 2,
        "expert_layer_fixed": 39 * FIXED * 2,
        "dense": DENSE * 2,
        "head": HEAD * 2,
    }
    total = costs.decode_step_bytes(SPEC, 16 * 2300, 16, 6.3)
    assert total == sum(parts.values())
    assert total == pytest.approx(7.149e9, rel=1e-3)
    assert least_seconds(0, total, "TPU v5 lite") == pytest.approx(
        8.73e-3, rel=1e-3)
    assert parts["latent_rows"] / total == pytest.approx(0.2372, rel=1e-3)
    # more experts than the tree holds cannot be touched
    assert costs.decode_step_bytes(SPEC, 0, 16, 500) == \
        costs.decode_step_bytes(SPEC, 0, 16, 16)


def test_prefill_flops_count_the_expanded_form_at_its_own_widths():
    t = 2048
    per_token = 2 * (40 * ATTN + DENSE + 39 * (FIXED + 0.5 * EXPERT))
    want = per_token * t + 40 * 32 * (192 + 128) * t * t + 2 * HEAD
    assert costs.prefill_flops(SPEC, t) == want
    assert want == pytest.approx(7.43e12, rel=1e-3)
    # attention's share: a quarter at 2,048, two fifths at 4,096
    attn = lambda t: 40 * 32 * 320 * t * t / costs.prefill_flops(SPEC, t)
    assert attn(2048) == pytest.approx(0.231, abs=0.005)
    assert attn(4096) == pytest.approx(0.376, abs=0.005)
    assert costs.prefill_bytes(SPEC, t) == (
        (costs.param_count(SPEC) - HEAD) * 2 + t * 46_080)
