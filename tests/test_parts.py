"""The model's parts as `jax.named_scope`s (`tracing.PARTS`, applied by
`generate.part`): every part an architecture has is named in the debug
info of its serving programs, and the names are metadata alone: with
`jax.named_scope` made a null context the lowered programs are the same
text, byte for byte."""

import contextlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import manifest as mf  # noqa: E402
from dml_tpu.tracing import PARTS, part_of  # noqa: E402

ATTENTION = {"embed", "attn_proj", "attn_core", "cache_write", "head"}
#: configuration -> (parts of its decode program, parts its prefill
#: program has beside them or lacks). The CPU's decode programs take the
#: einsum route, so `decode_attention` is the chip's alone
#: (tests/test_tpu_compile.py compiles the kernel there).
WANTED = {
    "mistral7b_widths_l8": (ATTENTION | {"mlp"}, {"flash_attention"}, set()),
    "sdar30b_a3b_l6": (
        ATTENTION | {"moe_route", "moe_experts", "diffuse_select", "pack"},
        {"flash_attention"}, {"head", "diffuse_select", "pack"}),
    "nemotron3_super_l11_ep4": (
        ATTENTION | {"ssm_proj", "ssm_scan", "moe_route", "moe_experts",
                     "moe_shared"}, {"flash_attention"}, set()),
    "joyai_llm_flash_ep16": (
        ATTENTION | {"mlp", "moe_route", "moe_experts", "moe_shared"},
        {"flash_attention"}, set()),
    "lfm2_8b_a1b_ep4": (
        ATTENTION | {"conv_proj", "conv_mix", "mlp", "moe_route",
                     "moe_experts"}, {"flash_attention"}, set()),
}


def _programs(config_name):
    """The (name, lowered) serving programs of a fresh server of the
    configuration at its rehearsal size."""
    config = mf.load_json("configs", config_name)
    small = {**config, **config["rehearsal"]}
    system = mf.load_module("backends", small["system"]).System(
        small, mf.load_module("references", small["reference"]), seed=5)
    try:
        srv = system.be.server
        rid = jnp.asarray(srv.rid_vec)
        if srv.diffusion is not None:
            decode = srv._diffuse_fn.lower(
                srv.params, srv.cache, srv._blk_dev, srv._pos_dev, rid)
        else:
            decode = srv._chunk_fn.lower(
                srv.params, srv.cache, srv._cur_dev, srv._pos_dev, rid)
        prompt = jnp.zeros((1, min(srv.max_len, 64)), jnp.int32)
        last = jnp.zeros((1,), jnp.int32)
        prefill = srv._prefill.lower(srv.params, prompt, last)
        rows = jax.eval_shape(srv._prefill, srv.params, prompt, last)[1]
        insert = srv._insert.lower(
            srv.cache, rows, jnp.int32(0), jnp.int32(0))
        return {"decode": decode, "prefill": prefill, "insert": insert}
    finally:
        system.free()


def _named(lowered):
    text = lowered.as_text(debug_info=True)
    return {p for p in PARTS if re.search(r'["/]%s["/]' % p, text)}


@pytest.fixture
def fresh_traces():
    """A trace keeps the Python stack it was made under, and jax's
    caches hand the trace of an inner jitted function (`jnp.where`,
    `jnp.clip`) to the next caller with the same shapes: one made
    inside `decode_attention` by another file's test in this worker
    would put the kernel's name into a program here that never calls
    it (the joyai prefill did, one whole run in some)."""
    jax.clear_caches()


@pytest.mark.parametrize("config_name", sorted(WANTED))
def test_programs_name_their_parts_and_nothing_else_changes(
        config_name, monkeypatch, fresh_traces):
    decode, more, less = WANTED[config_name]
    scoped = _programs(config_name)
    assert _named(scoped["decode"]) == decode
    assert _named(scoped["prefill"]) == (decode | more) - less
    assert _named(scoped["insert"]) == {"insert"}
    # no switch in the program for this: the scopes are taken away here
    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = _programs(config_name)
    for name, lowered in scoped.items():
        assert _named(bare[name]) <= {"flash_attention"}, name  # pallas's own
        assert lowered.as_text() == bare[name].as_text(), name


def test_a_part_is_a_name_of_the_table():
    from dml_tpu.inference.generate import part

    assert len(PARTS) == len(set(PARTS)) <= 18
    with pytest.raises(ValueError, match="tracing.PARTS"):
        part("attention")
    with part("attn_core"):
        pass


@pytest.mark.parametrize("path,want", [
    ("jit(_chunk_impl)/jit(main)/while/body/attn_proj/dot_general:",
     "attn_proj"),
    ("jit(_chunk_impl)/jit(main)/while/body/attn_core/decode_attention/"
     "pallas_call", "decode_attention"),
    ("jit(f)/jit(main)/moe_experts/while/body/moe_route/add", "moe_route"),
    ("jit(f)/jit(main)/attn_core_x/add", "unscoped"),
    ("", "unscoped"),
])
def test_an_operation_goes_by_the_innermost_part_on_its_path(path, want):
    assert part_of(path) == want
