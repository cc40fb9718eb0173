"""The system under test is built through the operator's entry point, and
only the weights' values are the benchmark's."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import manifest as mf

CONFIG = mf.load_json("configs", "mistral7b_widths_l8")
SMALL = {**CONFIG, **CONFIG["rehearsal"]}


def build(monkeypatch=None, declare=None, **spec):
    import dml_tpu.inference.lm_backend as program

    if declare is not None:
        parts = program.lm_spec_parts

        def declared(s):
            params, cfg = parts(s)
            return jax.tree.map(lambda x: x.astype(declare), params), cfg

        monkeypatch.setattr(program, "lm_spec_parts", declared)
    backend = mf.load_module("backends", "lm")
    reference = mf.load_module("references", "dense_gqa_lm")
    config = {**SMALL, "lm_spec": {**SMALL["lm_spec"], **spec}}
    system = backend.System(config, reference, seed=5)
    return system, reference.make_params(system.spec, 5)


def test_from_spec_is_driven_with_the_whole_block_and_the_benchmarks_values():
    system, made = build(chunk=4, max_slots=2, temperature=0.5)
    try:
        srv = system.be.server
        assert (srv.chunk, srv.max_slots, srv.temperature) == (4, 2, 0.5)
        got, want = jax.tree.leaves(srv.params), jax.tree.leaves(made)
        assert len(got) == len(want) > 0
        for g, w in zip(got, want):
            assert g.dtype == jnp.float32  # what lm_spec_parts declares today
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    finally:
        system.free()


def test_weight_storage_follows_what_the_program_declares(monkeypatch):
    system, made = build(monkeypatch, declare=jnp.bfloat16)
    try:
        for g, w in zip(jax.tree.leaves(system.be.server.params),
                        jax.tree.leaves(made)):
            assert g.dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(w.astype(jnp.bfloat16)))
    finally:
        system.free()


def test_readback_sequences_are_those_that_placed_requests_can_make():
    seqs = mf.load_module("backends", "lm").readback_sequences
    # a small-bucket group is the whole grid whatever it holds
    assert seqs(4, True, False, 3) == [(4,), (4, 4), (4, 4, 4)]
    # long buckets: r rows stand for more than r / 2 prompts (1, 2, 3, 5, 9)
    assert set(seqs(16, False, True, 3)) == {
        (1,), (1, 1), (1, 1, 1), (1, 2), (2,), (2, 1), (4,)}
    both = seqs(16, True, True, 4)
    assert len(both) == len(set(both)) == 53  # f(n)=2f(n-1)+f(n-2)+f(n-3)
    assert (16, 1, 16, 1) in both and (4, 1) in both and (8,) not in both
