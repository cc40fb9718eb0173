"""The window-attention cell on the CPU at its rehearsal size: the whole of
`run.py`'s path but the look for a chip; the ways `correct` has to come out
false (the control, a window one row too long or too short, a ring left by
the last occupant, a token altered); the backend's refusal of a program
that does not know the architecture; the cost functions against counts
worked by hand."""

import time

import jax
import numpy as np
import pytest

from benchmark.harness import cell as hc
from benchmark.harness import manifest as mf

CELL = "laguna_xs2_ep16.jobs"
CONFIG = mf.load_json("configs", "laguna_xs2_ep16")
SPEC = CONFIG["lm_spec"]
costs = mf.load_module("costs", "laguna_window_moe")


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
    assert {"window_rows_read.jobs", "experts_held_touched.jobs",
            "lm_route_ms.jobs", "throughput", "setup_s"} <= listed
    assert listed - set(r["readers"]) == {
        "window_decode_roofline.jobs", "prefill_roofline.jobs"}
    n = numbers(r)
    assert n["tokens_missing"]["value"] == 0
    assert n["served_gap_mean"]["tokens"] > 0
    # the configuration's sample, not twice the grid's slots
    assert n["served_gap_mean"]["over"] <= CONFIG["rehearsal"]["correct"]["sample"]
    for row in n.values():  # every number compared stands beside its limit
        assert "limit" in row or row["name"].startswith("control_")
    # the reference in int8 operands would not have passed
    assert n["control_int8_gap_mean"]["would_fail"]


def test_window_rows_read_is_rows_fetched_over_rows_needed():
    read = mf.load_module("metrics", "window_rows_read.jobs").read
    run = {"counters": {
        "start": {"kv_rows_window_live": 100.0, "kv_rows_window_read": 150.0},
        "end": {"kv_rows_window_live": 1100.0, "kv_rows_window_read": 1175.0}}}
    assert read(run) == 1.025
    # a program without the counter (the parent), an empty window: nothing
    assert read({"counters": {"start": {}, "end": {}}}) is None
    assert read({"counters": {"start": {}}}) is None
    same = {"kv_rows_window_live": 5.0, "kv_rows_window_read": 5.0}
    assert read({"counters": {"start": same, "end": same}}) is None


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


@pytest.mark.parametrize("off", [1, -1])
def test_a_window_one_row_off_makes_correct_false(monkeypatch, off):
    """The program serves a window of 9 (or 7) where the configuration
    says 8: every request completes, and its tokens are another model's
    (the reference masks at the configuration's own window)."""
    import dml_tpu.inference.lm_backend as program

    good = program._attention_layers

    def shifted(al, n_layers):
        types = {name: ({**t, "window": t["window"] + off}
                        if t.get("window") else t)
                 for name, t in al["types"].items()}
        return good({**al, "types": types}, n_layers)

    monkeypatch.setattr(program, "_attention_layers", shifted)
    r = rehearse()
    assert r["failed"] == 0 and r["correct"] is False
    assert numbers(r)["served_gap_mean"]["ok"] is False


def test_a_ring_left_by_the_last_occupant_makes_correct_false(monkeypatch):
    """A placement that writes no rows: the slot attends what its last
    occupant (or nobody) left in its planes and rings under the new
    request's length. Nothing fails to complete; the answers are another
    sequence's."""
    import dml_tpu.inference.lm_server as ls

    monkeypatch.setattr(ls.LMServer, "_insert_impl",
                        lambda self, cache, pcache, slot, row: cache)
    r = rehearse()
    assert r["failed"] == 0 and r["correct"] is False
    assert numbers(r)["served_gap_max"]["ok"] is False


def test_a_ring_filled_at_the_padded_length_makes_correct_false(monkeypatch):
    """A prefill that fills a window layer's ring at the BUCKET's length
    and not the row's own: the ring holds the pad tail's rows where the
    prompt's last eight belong."""
    import dml_tpu.inference.generate as g

    good = g.ring_positions
    monkeypatch.setattr(
        g, "ring_positions",
        lambda lengths, rows: good(lengths * 0 + 128, rows))
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
    """What the parent commit does with this configuration: its
    `lm_spec_parts` ignores the key it does not know and declares a
    decoder whose every layer has the spec's one head count and no gate.
    The run has to stop before any weight is made."""
    import dml_tpu.inference.lm_backend as program

    parts = program.lm_spec_parts
    monkeypatch.setattr(program, "lm_spec_parts", lambda s: parts(
        {k: v for k, v in s.items() if k != "attention_layers"}))
    backend = mf.load_module("backends", "lm_window_attention")
    reference = mf.load_module("references", "laguna_window_moe")
    small = {**CONFIG, **CONFIG["rehearsal"]}
    with pytest.raises(backend.UnknownArchitecture, match="another model"):
        backend.System(small, reference, seed=5)


def test_the_backend_serves_the_references_values_in_the_declared_tree():
    backend = mf.load_module("backends", "lm_window_attention")
    reference = mf.load_module("references", "laguna_window_moe")
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
        assert cfg.has_ring and cfg.attn(1).window == 8
        counters = system.counters()
        # K and V, 2 KV heads of 16, float32: 4 slots x 128 rows in the two
        # full layers, 4 slots x a ring of 8 in the three window layers
        row = 2 * 2 * 16 * 4
        assert counters["state_bytes_kv"] == 2 * 4 * 128 * row
        assert counters["state_bytes_kv_window"] == 3 * 4 * 8 * row
        assert {"kv_rows_window_live", "kv_rows_window_read",
                "kv_rows_full_live", "experts_held_touched_count"} <= set(
                    counters)
    finally:
        system.free()


def test_warm_up_runs_one_row_a_bucket(monkeypatch):
    """The (bucket, rows) groups warm-up runs at the REAL sizes, from the
    real traffic's lengths, without building the model: the program
    prefills every prompt of a model with window layers alone, so one
    program a bucket."""
    backend = mf.load_module("backends", "lm_window_attention")
    from dml_tpu.inference import lm_server as ls

    cell = hc.Cell(mf.load(), CELL)
    reqs = cell.driver.plan(cell.traffic, 50.0, 3, cell.config, cell.items)
    sizes = [r.size for r in reqs]
    assert len(sizes) == 32
    assert min(s["prompt_tokens"] for s in sizes) >= 512
    assert max(s["prompt_tokens"] for s in sizes) <= 3008
    assert max(s["prompt_tokens"] + s["output_tokens"] for s in sizes) <= 4032
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


def test_the_configuration_holds_the_sources_numbers():
    """Every number of the published config under its own key, but the
    one that is cut; the program's spec says the same model."""
    import json

    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    src = next(r for r in rows if r["name"] == "Laguna-XS.2")
    assert CONFIG["source"] == src["source_url"]
    for key, value in src["config"].items():
        if key == "num_experts":
            assert (CONFIG[key], value) == (16, 256)
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_experts"]
    al = SPEC["attention_layers"]
    assert [{"full": "full_attention", "window": "sliding_attention"}[n]
            for n in al["layers"]] == src["config"]["layer_types"]
    assert [al["types"][n]["n_heads"] for n in al["layers"]] == src[
        "config"]["num_attention_heads_per_layer"]
    full = src["config"]["rope_parameters"]["full_attention"]
    rope = al["types"]["full"]["rope"]
    assert rope["theta"] == full["rope_theta"]
    assert rope["rotary_dim"] == full["partial_rotary_factor"] * 128
    assert (rope["yarn"]["factor"], rope["yarn"]["beta_fast"],
            rope["yarn"]["beta_slow"], rope["yarn"]["attention_factor"],
            rope["yarn"]["original_max_position"]) == (
        full["factor"], full["beta_fast"], full["beta_slow"],
        full["attention_factor"], full["original_max_position_embeddings"])
    assert al["types"]["window"]["window"] == src["config"]["sliding_window"]
    assert al["types"]["window"]["rope"] == {"theta": 10000.0}
    assert (SPEC["num_experts"], SPEC["experts_held"]) == (256, [0, 16])
    assert SPEC["router"]["scale"] == src["config"][
        "moe_routed_scaling_factor"]


# by hand, at the published widths. A full layer's attention: q 2048 x
# (48 x 128), k and v 2048 x 1024 each, o 6144 x 2048, the gate 2048 x 48;
# a window layer's at 64 heads. A routed or the shared expert: 3 x 2048 x
# 512. What every token of an expert layer takes: router 2048 x 256 and the
# shared expert. The dense layer's MLP: 3 x 2048 x 8192.
FULL = 2048 * 6144 * 2 + 2 * 2048 * 1024 + 2048 * 48
WINDOW = 2048 * 8192 * 2 + 2 * 2048 * 1024 + 2048 * 64
EXPERT = 3 * 2048 * 512
FIXED = 2048 * 256 + EXPERT
DENSE = 3 * 2048 * 8192
HEAD = 2048 * 100352


def test_parameters_by_hand():
    assert (FULL, WINDOW, EXPERT, FIXED, DENSE) == (
        29_458_432, 37_879_808, 3_145_728, 3_670_016, 50_331_648)
    assert costs.attention_params(SPEC, 48, True) == FULL
    assert costs.attention_params(SPEC, 64, True) == WINDOW
    assert costs.expert_params(SPEC) == EXPERT
    assert costs.expert_layer_fixed_params(SPEC) == FIXED
    assert costs.dense_params(SPEC) == DENSE
    assert costs.param_count(SPEC) == (
        10 * FULL + 30 * WINDOW + DENSE + 39 * (FIXED + 16 * EXPERT)
        + 2 * HEAD) == 3_998_416_896
    # the uncut model: the issue's 33.44 B
    whole = {**SPEC, "experts_held": [0, 256]}
    assert costs.param_count(whole) == pytest.approx(33.44e9, rel=1e-3)
    # equal to the tree's own count, norms apart (two a layer, the final)
    ref = mf.load_module("references", "laguna_window_moe")
    norms = 40 * 2 * 2048 + 2048
    assert ref.param_count(SPEC) == costs.param_count(SPEC) + norms
    from dml_tpu.inference.lm_backend import lm_spec_parts

    tree = jax.eval_shape(lambda: lm_spec_parts(SPEC)[0])
    assert sum(x.size for x in jax.tree.leaves(tree)) == ref.param_count(SPEC)


def test_a_window_layers_rows_are_capped():
    kv = costs.kv_bytes_per_token(SPEC)
    assert kv == {"full": 10 * 4096, "window": 30 * 4096,
                  "window_rows": 512}
    assert costs.cached_bytes(SPEC, 100) == 100 * 40 * 4096
    assert costs.cached_bytes(SPEC, 512) == 512 * 40 * 4096
    assert costs.cached_bytes(SPEC, 4096) == (
        4096 * 40_960 + 512 * 122_880)
    # the grid: 2.68 GB of planes and 1.01 GB of rings, not 10.7 GB
    assert 16 * 4096 * kv["full"] == 2_684_354_560
    assert 16 * 512 * kv["window"] == 1_006_632_960
    assert 16 * 4096 * (kv["full"] + kv["window"]) == 10_737_418_240
    assert costs.attended_pairs(2048, 512) == 512 * 2048 - 512 * 511 / 2
    assert costs.attended_pairs(300, 512) == 300 * 301 / 2
    assert costs.attended_pairs(2048, 0) == 2048 * 2049 / 2


def test_one_decode_step_by_hand():
    """16 occupied slots at ~2,300 live rows each, 6.4 held experts touched
    a layer: the issue's ~7.7 GB and 9.4 ms a step; a window layer's rows
    counted as min(length, 512)."""
    from benchmark.harness.peaks import least_seconds

    parts = costs.decode_step_parts(SPEC, 16 * 2300, 16, 6.4)
    assert parts == {
        "full_rows": 16 * 2300 * 40_960,
        "window_rows": 16 * 512 * 122_880,
        "attention_matrices": (10 * FULL + 30 * WINDOW) * 2,
        "experts": 39 * 6.4 * EXPERT * 2,
        "expert_layer_fixed": 39 * FIXED * 2,
        "dense": DENSE * 2,
        "head": HEAD * 2,
    }
    total = costs.decode_step_bytes(SPEC, 16 * 2300, 16, 6.4)
    assert total == sum(parts.values())
    assert total == pytest.approx(7.744e9, rel=1e-3)
    assert least_seconds(0, total, "TPU v5 lite") == pytest.approx(
        9.46e-3, rel=1e-3)
    # a program that read every live row in the window layers: 11.2 GB
    assert total - parts["window_rows"] + 16 * 2300 * 122_880 == \
        pytest.approx(11.26e9, rel=1e-3)
    # slots shorter than the window need their own rows, no more
    short = costs.decode_step_parts(SPEC, 16 * 100, 16, 6.4)
    assert short["window_rows"] == 16 * 100 * 122_880
    # more experts than the tree holds cannot be touched
    assert costs.decode_step_bytes(SPEC, 0, 16, 500) == \
        costs.decode_step_bytes(SPEC, 0, 16, 16)


def test_prefill_flops_count_the_band_in_a_window_layer():
    t = 3100
    per_token = 2 * (10 * FULL + 30 * WINDOW + DENSE
                     + 39 * (FIXED + 0.5 * EXPERT))
    band = 512 * t - 512 * 511 / 2
    want = (per_token * t + 10 * 4 * 48 * 128 * t * (t + 1) / 2
            + 30 * 4 * 64 * 128 * band + 2 * HEAD)
    assert costs.prefill_flops(SPEC, t) == want
    assert want == pytest.approx(13.07e12, rel=1e-3)
    # unbanded, the window layers' attention would be the triangle: 2.4 x
    # the band's at this length
    assert (t * (t + 1) / 2) / band == pytest.approx(3.3, abs=0.1)
    assert costs.prefill_bytes(SPEC, t) == (
        (costs.param_count(SPEC) - HEAD) * 2 + t * 40_960 + 512 * 122_880)
