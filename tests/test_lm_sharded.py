"""Weight-resident tp-sharded LM decode + prefill/decode
disaggregation (inference/lm_sharded.py).

Exactness is the spine of every test here: the KV slab must
round-trip BIT-exact in both cache layouts, an adopted (externally
prefilled) request must decode token-identical to a local submit,
and the sharded/disaggregated cluster paths must return exactly what
isolated `generate()` produces per prompt — disaggregation and
sharding are throughput decisions, never semantics changes."""

import asyncio
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dml_tpu.config import ClusterSpec, MeshSpec, Timing, WorkerGroupSpec
from dml_tpu.inference.generate import LMConfig, generate
from dml_tpu.inference.lm_backend import (
    LMBackend,
    lm_spec_parts,
    write_prompt_file,
)
from dml_tpu.inference.lm_sharded import (
    DisaggLMBackend,
    LMPrefillBackend,
    PipelinedLMBackend,
    check_hbm_budget,
    iter_slab_stream,
    kv_slab_from_bytes,
    kv_slab_to_bytes,
    pp_hbm_report,
    push_slab_entry,
    push_slab_error,
    sharded_lm_backend,
    sharded_lm_group_backend,
)
from dml_tpu.parallel.mesh import make_mesh

SPEC = {
    "name": "ShardLM", "vocab_size": 64, "d_model": 32, "n_heads": 4,
    "n_kv_heads": 2, "n_layers": 2, "d_ff": 64, "dtype": "float32",
    "max_new_tokens": 8, "max_slots": 2, "max_len": 64, "chunk": 4,
    "seed": 0,
}
NEW_TOKENS = 8


@pytest.fixture(scope="module")
def parts():
    return lm_spec_parts(SPEC)


def _prompts(n=3, lens=(5, 11, 16)):
    rng = np.random.RandomState(0)
    return [
        rng.randint(0, SPEC["vocab_size"], tp).astype(np.int32)
        for tp in lens[:n]
    ]


def _expect(params, cfg, prompt, budget):
    return np.asarray(generate(
        params, cfg, jnp.asarray(np.asarray(prompt, np.int32)[None]),
        budget,
    ))[0]


# ----------------------------------------------------------------------
# KV slab serialization
# ----------------------------------------------------------------------


def _roundtrip(params, cfg, max_len=64):
    pf = LMPrefillBackend(params, cfg, max_len=max_len)
    entries = [pf.prefill_one(p, NEW_TOKENS) for p in _prompts()]
    blob = kv_slab_to_bytes(entries)
    back = kv_slab_from_bytes(blob)
    assert len(back) == len(entries)
    for a, b in zip(entries, back):
        assert a["prompt_len"] == b["prompt_len"]
        assert a["first_token"] == b["first_token"]
        assert a["budget"] == b["budget"]
        for name in a["rows"]:
            for key, arr in a["rows"][name].items():
                got = b["rows"][name][key]
                assert got.dtype == np.asarray(arr).dtype
                np.testing.assert_array_equal(np.asarray(arr), got)
    return blob


def test_kv_slab_roundtrip_bf16():
    """bf16 cache layout ({k, v}) survives serialize/deserialize
    bit-for-bit — bfloat16 rides as raw ml_dtypes bytes, not a f32
    widening."""
    spec = {**SPEC, "dtype": "bfloat16"}
    params, cfg = lm_spec_parts(spec)
    blob = _roundtrip(params, cfg)
    assert blob[:4] == b"KVS1"


def test_kv_slab_roundtrip_kv_quant():
    """kv_quant layout (int8 values + f32 scales with T on lanes)
    round-trips bit-exact through the same generic walker."""
    spec = {**SPEC, "kv_quant": True}
    params, cfg = lm_spec_parts(spec)
    pf = LMPrefillBackend(params, cfg, max_len=64)
    e = pf.prefill_one(_prompts()[0], NEW_TOKENS)
    # the layout really is the quantized one
    assert set(e["rows"]["block_0"]) == {"k_q", "k_s", "v_q", "v_s"}
    assert e["rows"]["block_0"]["k_q"].dtype == np.int8
    _roundtrip(params, cfg)


def test_kv_slab_rejects_garbage():
    with pytest.raises(ValueError, match="magic"):
        kv_slab_from_bytes(b"nope" + b"\0" * 32)
    params, cfg = lm_spec_parts(SPEC)
    pf = LMPrefillBackend(params, cfg, max_len=64)
    blob = kv_slab_to_bytes([pf.prefill_one(_prompts()[0], 4)])
    with pytest.raises(ValueError):
        kv_slab_from_bytes(blob[: len(blob) - 7])  # truncated tail


# ----------------------------------------------------------------------
# chunk-streamed slab framing (the streamed handoff wire form)
# ----------------------------------------------------------------------


class _FakeFeed:
    """Collects push() chunks like a data-plane StreamFeed; the frame
    boundaries it records are exactly what fetch_stream would yield."""

    def __init__(self):
        self.chunks = []

    def push(self, data: bytes) -> None:
        self.chunks.append(bytes(data))

    async def put(self, data: bytes) -> None:
        self.chunks.append(bytes(data))


async def _drain(chunks):
    async def it():
        for c in chunks:
            yield c

    out = []
    async for item in iter_slab_stream(it()):
        out.append(item)
    return out


def _stream_roundtrip(spec):
    """Frame entries through push_slab_entry -> iter_slab_stream and
    assert every leaf reassembles BIT-exact from its chunk pieces."""
    params, cfg = lm_spec_parts(spec)
    pf = LMPrefillBackend(params, cfg, max_len=64)
    entries = [pf.prefill_one(p, NEW_TOKENS) for p in _prompts()]
    feed = _FakeFeed()
    import dml_tpu.inference.lm_sharded as mod

    for i, e in enumerate(entries):
        asyncio.run(push_slab_entry(feed, i, kv_slab_to_bytes([e])))
    # per-request blobs really did split into multiple chunk pieces
    # (the overlap the streamed handoff exists for) when they exceed
    # the chunk size; force that by re-framing with a tiny chunk
    small = _FakeFeed()
    orig = mod.SLAB_STREAM_CHUNK
    mod.SLAB_STREAM_CHUNK = 1 << 10
    try:
        for i, e in enumerate(entries):
            asyncio.run(
                push_slab_entry(small, i, kv_slab_to_bytes([e])))
    finally:
        mod.SLAB_STREAM_CHUNK = orig
    assert len(small.chunks) > len(entries) * 2  # header + >1 piece
    for chunks in (feed.chunks, small.chunks):
        back = asyncio.run(_drain(chunks))
        assert [i for i, _ in back] == list(range(len(entries)))
        for (_, got), want in zip(back, entries):
            assert got is not None
            assert got["prompt_len"] == want["prompt_len"]
            assert got["first_token"] == want["first_token"]
            for name in want["rows"]:
                for key, arr in want["rows"][name].items():
                    g = got["rows"][name][key]
                    assert g.dtype == np.asarray(arr).dtype
                    np.testing.assert_array_equal(np.asarray(arr), g)


def test_slab_stream_chunks_bit_exact_bf16():
    _stream_roundtrip({**SPEC, "dtype": "bfloat16"})


def test_slab_stream_chunks_bit_exact_kv_quant():
    _stream_roundtrip({**SPEC, "kv_quant": True})


def test_slab_stream_rejects_garbage_and_truncation():
    params, cfg = lm_spec_parts(SPEC)
    pf = LMPrefillBackend(params, cfg, max_len=64)
    blob = kv_slab_to_bytes([pf.prefill_one(_prompts()[0], 4)])
    feed = _FakeFeed()
    asyncio.run(push_slab_entry(feed, 0, blob))
    # a garbage header frame kills the stream loudly
    with pytest.raises(ValueError, match="header"):
        asyncio.run(_drain([b"\xff\xfe not json"] + feed.chunks))
    # a stream dying mid-entry (peer crash) raises — the puller
    # demotes the share's remaining requests to local prefill
    with pytest.raises(ValueError, match="mid-entry"):
        asyncio.run(_drain(feed.chunks[:-1]))
    # a declared error entry yields (i, None): per-request fallback
    efeed = _FakeFeed()
    asyncio.run(push_slab_error(efeed, 2, "boom"))
    assert asyncio.run(_drain(efeed.chunks)) == [(2, None)]
    # an oversized payload (size lie) is rejected
    lied = _FakeFeed()
    asyncio.run(push_slab_entry(lied, 0, blob))
    import json as _json

    hdr = _json.loads(lied.chunks[0])
    hdr["size"] = 10
    with pytest.raises(ValueError, match="overran"):
        asyncio.run(_drain(
            [_json.dumps(hdr).encode()] + lied.chunks[1:]
        ))


# ----------------------------------------------------------------------
# pipeline-parallel serving (pp axis)
# ----------------------------------------------------------------------

PP_SPEC = {
    "name": "PPLM", "vocab_size": 64, "d_model": 32, "n_heads": 4,
    "n_kv_heads": 2, "n_layers": 4, "d_ff": 64, "dtype": "float32",
    "max_new_tokens": 8, "max_len": 64, "seed": 0,
}


@pytest.mark.pp
def test_pp_engine_token_exact():
    """The pipelined engine (layer stack sharded over pp, microbatched
    stage handoff with ring token feedback) is token-identical to
    isolated generate() per prompt — mixed prompt lengths AND mixed
    budgets, including budget 1 (prefill-only)."""
    params, cfg = lm_spec_parts(PP_SPEC)
    mesh = make_mesh(MeshSpec(dp=1, tp=1, pp=2),
                     devices=jax.devices()[:2])
    be = PipelinedLMBackend(PP_SPEC, mesh)
    prompts = _prompts() + [_prompts(1)[0]]
    budgets = [8, 3, 1, 5]
    toks = be.generate_batch(prompts, budgets)
    for p, b, t in zip(prompts, budgets, toks):
        np.testing.assert_array_equal(t, _expect(params, cfg, p, b))
    # per-member HBM accounting: each stage holds half the block
    # stack plus the replicated io params
    rep = be.hbm
    assert rep["per_member_bytes"] < rep["full_bytes"]
    assert rep["per_member_bytes"] == (
        rep["io_bytes"] + rep["block_bytes"] // 2
    )


@pytest.mark.pp
def test_pp_engine_serve_files(tmp_path):
    params, cfg = lm_spec_parts(PP_SPEC)
    mesh = make_mesh(MeshSpec(dp=1, tp=1, pp=2),
                     devices=jax.devices()[:2])
    be = PipelinedLMBackend(PP_SPEC, mesh)
    paths = []
    prompts = _prompts()
    for i, p in enumerate(prompts):
        fp = str(tmp_path / f"p{i}.tokens.txt")
        write_prompt_file(fp, p)
        paths.append(fp)
    results, infer_time, cost = be.serve_files(paths)
    for fp, p in zip(paths, prompts):
        np.testing.assert_array_equal(
            results[fp]["tokens"], _expect(params, cfg, p, 8)
        )
    assert be.decode_tokens_total() == 3 * 8
    assert cost["per_query"] > 0


@pytest.mark.pp
def test_pp_engine_rejects_bad_layouts():
    mesh = make_mesh(MeshSpec(dp=1, tp=1, pp=2),
                     devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="divisible"):
        PipelinedLMBackend({**PP_SPEC, "n_layers": 3}, mesh)
    with pytest.raises(ValueError, match="kv_quant|bf16"):
        PipelinedLMBackend({**PP_SPEC, "kv_quant": True}, mesh)
    with pytest.raises(ValueError, match="greedy"):
        PipelinedLMBackend({**PP_SPEC, "temperature": 0.7}, mesh)
    one = make_mesh(MeshSpec(dp=1, tp=1, pp=1),
                    devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="pp axis"):
        PipelinedLMBackend(PP_SPEC, one)
    both = make_mesh(MeshSpec(dp=1, tp=2, pp=2),
                     devices=jax.devices()[:4])
    with pytest.raises(ValueError, match="pp.*only|replicate"):
        PipelinedLMBackend(PP_SPEC, both)


@pytest.mark.pp
def test_hbm_budget_gate():
    """`WorkerGroupSpec.hbm_bytes` turns first-batch OOM into a
    startup config error: a model whose full tree exceeds the
    per-member budget must be served through a pp axis (whose slice
    fits), never silently attempted."""
    rep = pp_hbm_report(PP_SPEC, 2)
    budget = (rep["per_member_bytes"] + rep["full_bytes"]) // 2
    g_pp = WorkerGroupSpec(
        "g", ("H1", "H2"), MeshSpec(dp=1, tp=1, pp=2),
        lm_models=("PPLM",), hbm_bytes=budget,
    )
    out = check_hbm_budget(g_pp, PP_SPEC)
    assert out is not None and out["per_member_bytes"] <= budget
    # the same model on a NON-pp group busts the budget -> loud
    g_tp = WorkerGroupSpec(
        "g", ("H1", "H2"), MeshSpec(dp=1, tp=2),
        lm_models=("PPLM",), hbm_bytes=budget,
    )
    with pytest.raises(RuntimeError, match="pp axis"):
        check_hbm_budget(g_tp, PP_SPEC)
    # a pp budget smaller than even the slice is loud too
    g_tiny = WorkerGroupSpec(
        "g", ("H1", "H2"), MeshSpec(dp=1, tp=1, pp=2),
        lm_models=("PPLM",), hbm_bytes=1000,
    )
    with pytest.raises(RuntimeError, match="hbm_bytes"):
        check_hbm_budget(g_tiny, PP_SPEC)
    # no declared budget: unchecked
    assert check_hbm_budget(
        WorkerGroupSpec("g", ("H1", "H2"), MeshSpec(dp=1, tp=1, pp=2)),
        PP_SPEC,
    ) is None


@pytest.mark.pp
def test_wire_lm_group_pp_primary(tmp_path):
    """A group whose mesh has a pp axis wires its primary with the
    PIPELINED engine (mode 'pp' group backend) under the hbm budget
    gate."""
    from dml_tpu.cluster.node import Node
    from dml_tpu.cluster.store_service import StoreService
    from dml_tpu.config import StoreConfig
    from dml_tpu.inference.lm_sharded import wire_lm_group

    rep = pp_hbm_report(PP_SPEC, 2)
    budget = (rep["per_member_bytes"] + rep["full_bytes"]) // 2

    async def run():
        spec = ClusterSpec.localhost(
            4, base_port=19451, introducer_port=19450,
            store=StoreConfig(root=str(tmp_path / "roots"),
                              download_dir=str(tmp_path / "dl")),
            worker_groups=[WorkerGroupSpec(
                "pp0", ("H3", "H4"), MeshSpec(dp=1, tp=1, pp=2),
                lm_models=("PPLM",), hbm_bytes=budget,
            )],
        )
        nid = spec.node_by_name("H3")
        node = Node(spec, nid)
        store = StoreService(node, root=str(tmp_path / "st"))
        gb, pf = wire_lm_group(node, store, PP_SPEC)
        assert gb is not None and pf is None
        assert isinstance(gb.lm_backend, PipelinedLMBackend)
        assert gb.capacity == 2.0
        # lender gets nothing
        node4 = Node(spec, spec.node_by_name("H4"))
        store4 = StoreService(node4, root=str(tmp_path / "st4"))
        gb4, pf4 = wire_lm_group(node4, store4, PP_SPEC)
        assert gb4 is None and pf4 is None

        # a prefill ROLE on a pp group is ignored: the pipelined
        # engine never sends LM_PREFILL_REQUEST, and building the
        # full-tree prefill backend would hold weights the declared
        # budget says don't fit one member
        spec_roles = ClusterSpec.localhost(
            4, base_port=19451, introducer_port=19450,
            store=StoreConfig(root=str(tmp_path / "roots2"),
                              download_dir=str(tmp_path / "dl2")),
            worker_groups=[WorkerGroupSpec(
                "pp0", ("H3", "H4"), MeshSpec(dp=1, tp=1, pp=2),
                lm_models=("PPLM",), hbm_bytes=budget,
                roles={"H3": "decode", "H4": "prefill"},
            )],
        )
        node_pf = Node(spec_roles, spec_roles.node_by_name("H4"))
        store_pf = StoreService(node_pf, root=str(tmp_path / "st_pf"))
        gb_pf, pf_pf = wire_lm_group(node_pf, store_pf, PP_SPEC)
        assert gb_pf is None and pf_pf is None

    asyncio.run(run())


def test_hbm_budget_resolved_pp_override():
    """A mesh declared pp=-1 (fill remaining devices) must be
    budget-checked against the RESOLVED axis, not clamped to the
    non-pp full-tree bound."""
    rep = pp_hbm_report(PP_SPEC, 2)
    budget = (rep["per_member_bytes"] + rep["full_bytes"]) // 2
    g = WorkerGroupSpec(
        "g", ("H1", "H2"), MeshSpec(dp=1, tp=1, pp=-1),
        lm_models=("PPLM",), hbm_bytes=budget,
    )
    # spec-level view clamps -1 to non-pp and refuses
    with pytest.raises(RuntimeError, match="pp axis"):
        check_hbm_budget(g, PP_SPEC)
    # the resolved view passes on the slice
    out = check_hbm_budget(g, PP_SPEC, pp=2)
    assert out is not None and out["per_member_bytes"] <= budget


# ----------------------------------------------------------------------
# adopted decode exactness
# ----------------------------------------------------------------------


def test_serve_prefilled_token_identical(parts):
    """An adopted slab decodes to EXACTLY the isolated generate()
    output — the handoff moves bits, not approximations. Mixed
    budgets exercise slot-paced adoption (more slabs than slots)."""
    params, cfg = parts
    prompts = _prompts()
    budgets = [NEW_TOKENS, 3, 5]
    pf = LMPrefillBackend(params, cfg, max_len=64)
    slabs = kv_slab_from_bytes(kv_slab_to_bytes([
        pf.prefill_one(p, b) for p, b in zip(prompts, budgets)
    ]))
    be = LMBackend(params, cfg, max_new_tokens=NEW_TOKENS,
                   max_slots=2, max_len=64, chunk=4)
    toks, infer_time = be.serve_prefilled(prompts, budgets, slabs)
    assert infer_time > 0
    for p, b, ts in zip(prompts, budgets, toks):
        np.testing.assert_array_equal(ts, _expect(params, cfg, p, b))


def test_serve_prefilled_budget_one(parts):
    """A budget-1 adoption retires at placement: the slab's first
    token is the whole output and no decode step runs for it."""
    params, cfg = parts
    p = _prompts()[0]
    pf = LMPrefillBackend(params, cfg, max_len=64)
    slabs = [pf.prefill_one(p, 1)]
    be = LMBackend(params, cfg, max_new_tokens=NEW_TOKENS,
                   max_slots=2, max_len=64, chunk=4)
    toks, _ = be.serve_prefilled([p], [1], slabs)
    np.testing.assert_array_equal(toks[0], _expect(params, cfg, p, 1))


def test_serve_prefilled_requires_greedy(parts):
    params, cfg = parts
    be = LMBackend(params, cfg, max_new_tokens=4, max_slots=2,
                   max_len=64, chunk=4, temperature=0.7)
    with pytest.raises(ValueError, match="temperature"):
        be.serve_prefilled([], [], [])


# ----------------------------------------------------------------------
# sharded serving forms (virtual tp=2 mesh)
# ----------------------------------------------------------------------


@pytest.mark.sharded
@pytest.mark.parametrize("tp", [2, 4])
def test_sharded_resident_token_identical(tmp_path, parts, tp):
    """Weight-resident serving over a tp mesh produces token-identical
    outputs to single-chip generate() — the dryrun tp-decode contract
    through the backend adapter. tp=2 divides the 2 KV heads (the
    cache shards by head); tp=4 does not (4 query heads over 2 KV
    heads: `heads_axis` leaves the cache whole)."""
    params, cfg = parts
    mesh = make_mesh(MeshSpec(dp=1, tp=tp), devices=jax.devices()[:tp])
    prompts = _prompts()
    paths = []
    for i, p in enumerate(prompts):
        fp = str(tmp_path / f"p{i}.tokens.txt")
        write_prompt_file(fp, p)
        paths.append(fp)
    be = sharded_lm_backend(SPEC, mesh)
    assert be.overlap is False
    results, infer_time, cost = be.serve_files(paths)
    assert infer_time > 0 and cost["per_query"] > 0
    for fp, p in zip(paths, prompts):
        np.testing.assert_array_equal(
            results[fp]["tokens"],
            _expect(params, cfg, p, NEW_TOKENS),
        )


@pytest.mark.sharded
def test_sharded_group_backend_degrades(tmp_path, parts):
    """A member dying out from under the sharded LM engine raises
    GroupDegraded (-> TASK_FAIL -> requeue), never a wrong answer."""
    from dml_tpu.jobs.groups import GroupDegraded

    params, cfg = parts
    mesh = make_mesh(MeshSpec(dp=1, tp=2), devices=jax.devices()[:2])
    be = sharded_lm_backend(SPEC, mesh)
    alive = {"a", "b"}
    gb = sharded_lm_group_backend(
        be, model_name="ShardLM", group_name="g0",
        members=("a", "b"), alive_fn=lambda: set(alive), capacity=2.0,
    )
    assert gb.model == "ShardLM" and gb.capacity == 2.0
    fp = str(tmp_path / "p.tokens.txt")
    write_prompt_file(fp, _prompts()[0])
    results, _, _ = asyncio.run(gb("ShardLM", [fp]))
    np.testing.assert_array_equal(
        results[fp]["tokens"],
        _expect(params, cfg, _prompts()[0], NEW_TOKENS),
    )
    alive.discard("b")
    with pytest.raises(GroupDegraded):
        asyncio.run(gb("ShardLM", [fp]))


# ----------------------------------------------------------------------
# GroupDirectory: LM-aware collapse + memoization
# ----------------------------------------------------------------------


def _directory(lm_models=()):
    from dml_tpu.jobs.groups import GroupDirectory

    spec = ClusterSpec.localhost(5, base_port=9301, worker_groups=[
        WorkerGroupSpec("tp0", ("H4", "H5"), MeshSpec(dp=1, tp=2),
                        lm_models=tuple(lm_models)),
    ])
    pool = [spec.nodes[i].unique_name for i in (2, 3, 4)]  # H3..H5
    return GroupDirectory(spec), spec, pool


def test_collapse_lm_round_gating():
    """An LM round keeps a group collapsed ONLY when the group
    declares every active LM model in lm_models; otherwise the
    members fall back to single-chip slots (the PR-5 behavior)."""
    d, spec, pool = _directory(lm_models=("ShardLM",))
    primary = spec.group_members_unique("tp0")[0]
    # CNN round: collapsed
    p, w = d.collapse(pool)
    assert primary in p and len(p) == 2 and w[primary] == 2.0
    # declared LM round: still collapsed
    p, w = d.collapse(pool, lm_active={"ShardLM"})
    assert len(p) == 2 and w[primary] == 2.0
    # undeclared LM round: withheld — full single-chip pool
    p, w = d.collapse(pool, lm_active={"OtherLM"})
    assert sorted(p) == sorted(pool) and w == {}
    # mixed round with an undeclared model: withheld too
    p, w = d.collapse(pool, lm_active={"ShardLM", "OtherLM"})
    assert sorted(p) == sorted(pool) and w == {}


def test_collapse_memoizes_on_cache_key(monkeypatch):
    """Same cache key -> the cached pool returns without re-deriving
    (the SWIM-epoch memoization); key change or a capacity advert
    invalidates. Returned containers are copies — mutating them must
    not corrupt the memo."""
    d, spec, pool = _directory(lm_models=("ShardLM",))
    calls = {"n": 0}
    orig = spec.group_of_unique

    def counting(uname):
        calls["n"] += 1
        return orig(uname)

    monkeypatch.setattr(spec, "group_of_unique", counting)
    p1, w1 = d.collapse(pool, cache_key=(7, "L", "S"))
    n_first = calls["n"]
    assert n_first > 0
    p1.append("junk")  # caller-side mutation must not leak back
    w1["junk"] = 1.0
    p2, w2 = d.collapse(pool, cache_key=(7, "L", "S"))
    assert calls["n"] == n_first  # served from the memo
    assert "junk" not in p2 and "junk" not in w2
    d.collapse(pool, cache_key=(8, "L", "S"))  # epoch moved
    assert calls["n"] > n_first
    # a changed ACK-advertised capacity invalidates the memo even
    # under an unchanged key
    n_before = calls["n"]
    d.collapse(pool, cache_key=(8, "L", "S"))
    assert calls["n"] == n_before
    d.observe_ack("x", {"group": "tp0", "group_capacity": 4.0})
    p3, w3 = d.collapse(pool, cache_key=(8, "L", "S"))
    assert calls["n"] > n_before
    primary = spec.group_members_unique("tp0")[0]
    assert w3[primary] == 4.0


@pytest.mark.disagg
def test_disagg_adoption_failure_falls_back(tmp_path, parts, monkeypatch):
    """A slab that ARRIVES cleanly but cannot be adopted (a
    drifted-spec peer shipping rows that don't fit this server) is
    still a failed handoff — for exactly THAT request: it demotes to
    a local prefill (fallback counter) while its siblings adopt
    normally ('ok' counts), and the batch never fails or requeue-
    loops against the bad peer. Outputs stay exact either way."""
    params, cfg = parts
    prompts = _prompts()
    paths = []
    for i, p in enumerate(prompts):
        fp = str(tmp_path / f"p{i}.tokens.txt")
        write_prompt_file(fp, p)
        paths.append(fp)
    be = LMBackend(params, cfg, max_new_tokens=NEW_TOKENS,
                   max_slots=2, max_len=64, chunk=4)
    be.overlap = False
    gb = DisaggLMBackend.__new__(DisaggLMBackend)
    gb.be = be
    gb.model = "ShardLM"
    gb.group_name = "g0"
    gb.members = ()
    gb.alive_fn = None
    gb.handoff = "slab"
    gb.fanout = 0
    gb.prefill_timeout = 5.0
    gb.last_ttft_s = None
    gb.handoffs = gb.fallbacks = gb.handoff_bytes = 0
    gb.warm_locals = 0

    pf = LMPrefillBackend(params, cfg, max_len=64)

    def fake_peers():
        return ["peer0"]

    async def bad_share(peer, model, idxs, ps, budgets, arrivals,
                        ctxs=None):
        # right count, wrong shapes: first slab's T axis lies
        slabs = [pf.prefill_one(ps[i], budgets[i]) for i in idxs]
        import numpy as _np

        slabs[0]["rows"]["block_0"]["k"] = _np.zeros(
            (cfg.kv_heads, 1, cfg.head_dim),
            slabs[0]["rows"]["block_0"]["k"].dtype,
        )
        for i, entry in zip(idxs, slabs):
            arrivals.put_nowait((i, entry))

    monkeypatch.setattr(gb, "_prefill_peers", fake_peers)
    monkeypatch.setattr(gb, "_pull_share_slab", bad_share)
    results, _, _ = asyncio.run(gb("ShardLM", paths))
    assert gb.fallbacks == 1
    assert gb.handoffs == len(paths) - 1
    for fp, p in zip(paths, prompts):
        np.testing.assert_array_equal(
            results[fp]["tokens"],
            _expect(params, cfg, p, NEW_TOKENS),
        )


@pytest.mark.sharded
def test_wire_lm_group_roles(tmp_path):
    """Production NodeApp wiring: the decode primary of a role-split
    group gets the disaggregated backend, prefill-role members get
    the prefill backend, lenders/ungrouped nodes get neither, and a
    group NOT declaring the model wires nothing."""
    from dml_tpu.cluster.node import Node
    from dml_tpu.cluster.store_service import StoreService
    from dml_tpu.config import StoreConfig
    from dml_tpu.inference.lm_sharded import wire_lm_group

    async def run():
        spec = ClusterSpec.localhost(
            5, base_port=19401, introducer_port=19400,
            store=StoreConfig(root=str(tmp_path / "roots"),
                              download_dir=str(tmp_path / "dl")),
            worker_groups=[WorkerGroupSpec(
                "tp0", ("H4", "H5"), MeshSpec(dp=1, tp=2),
                lm_models=("ShardLM",),
                roles={"H4": "decode", "H5": "prefill"},
            )],
        )
        out = {}
        for name in ("H3", "H4", "H5"):
            nid = spec.node_by_name(name)
            node = Node(spec, nid)
            store = StoreService(
                node, root=str(tmp_path / f"st_{nid.port}")
            )
            out[name] = wire_lm_group(node, store, SPEC)
        gb4, pf4 = out["H4"]
        assert isinstance(gb4, DisaggLMBackend)
        assert gb4.model == "ShardLM" and gb4.capacity == 2.0
        assert pf4 is None
        gb5, pf5 = out["H5"]
        assert gb5 is None and isinstance(pf5, LMPrefillBackend)
        assert out["H3"] == (None, None)
        # a model the group does not declare wires nothing anywhere
        nid = spec.node_by_name("H4")
        node = Node(spec, nid)
        store = StoreService(node, root=str(tmp_path / "st_x"))
        assert wire_lm_group(
            node, store, {**SPEC, "name": "OtherLM"}
        ) == (None, None)

    asyncio.run(run())


# ----------------------------------------------------------------------
# cluster: sharded job equality + disaggregated handoff (full stack)
# ----------------------------------------------------------------------


async def _disagg_cluster_run(tmp):
    from dml_tpu.cluster.chaos import LocalCluster
    from dml_tpu.cluster.store.data_plane import TunnelFault
    from dml_tpu.jobs.service import JobService

    params, cfg = lm_spec_parts(SPEC)
    mesh = make_mesh(MeshSpec(dp=1, tp=2), devices=jax.devices()[:2])
    be_dis = sharded_lm_backend(SPEC, mesh)
    be_single = LMBackend(params, cfg, max_new_tokens=NEW_TOKENS,
                          max_slots=2, max_len=64, chunk=4)
    prefill_be = LMPrefillBackend(params, cfg, max_len=64)
    group = WorkerGroupSpec(
        "tp0", ("H4", "H5"), MeshSpec(dp=1, tp=2),
        lm_models=("ShardLM",),
        roles={"H4": "decode", "H5": "prefill"},
    )
    holder = {}
    services = {}

    def make_jobs(node, store):
        js = JobService(node, store)
        uname = node.me.unique_name
        alive = lambda: {  # noqa: E731
            n.unique_name for n in node.membership.alive_nodes()
        }
        members = node.spec.group_members_unique(group.name)
        gb = None
        if members and uname == members[0]:
            gb = DisaggLMBackend(
                be_dis, model_name="ShardLM", group_name=group.name,
                node=node, store=store, members=members,
                alive_fn=alive, capacity=2.0,
            )
            holder["gb"] = gb
            holder["store"] = store
        js.register_lm(
            "ShardLM", backend=be_single.backend,
            cost=be_single.cost(), prefill=prefill_be,
            group_backend=gb,
        )
        services[uname] = js
        return js

    cluster = LocalCluster(
        5, tmp, 19221,
        timing=Timing(ping_interval=0.2, ack_timeout=0.3,
                      cleanup_time=1.0, leader_rpc_timeout=10.0),
        worker_groups=[group],
        make_jobs=make_jobs,
    )
    try:
        await cluster.start()
        await cluster.wait_for(
            cluster.converged, 30.0, "disagg cluster convergence"
        )
        client = cluster.client()
        rng = np.random.RandomState(1)
        expected = {}
        local_paths = []
        for i in range(4):
            prompt = rng.randint(0, SPEC["vocab_size"],
                                 int(rng.randint(4, 20)))
            fname = f"p{i}.tokens.txt"
            p = os.path.join(tmp, fname)
            write_prompt_file(p, prompt)
            await client.store.put(p, fname)
            local_paths.append(p)
            expected[fname] = list(_expect(params, cfg, prompt,
                                           NEW_TOKENS))

        # 1) full-pipeline disaggregated job: store -> scheduler ->
        # decode primary -> prefill-role handoff -> merged output,
        # token-identical to isolated generate()
        job_id = await client.jobs.submit_job("ShardLM", 8)
        done = await client.jobs.wait_job(job_id, timeout=120.0)
        assert done["total_queries"] == 8
        merged = await client.jobs.get_output(
            job_id, os.path.join(tmp, "out.json")
        )
        assert merged
        for fname, out in merged.items():
            assert out["tokens"] == expected[fname], fname
        gb = holder["gb"]
        assert gb.handoffs >= 1, "no prefill->decode handoff happened"
        assert gb.handoff_bytes > 0
        assert gb.fallbacks == 0

        # the LM round kept the group collapsed: the leader's pool
        # shows the primary as one weighted slot (the lifted PR-5
        # restriction)
        leader_js = services[cluster.leader_uname()]
        pool = leader_js.worker_pool()
        primary = cluster.spec.group_members_unique(group.name)[0]
        lender = cluster.spec.group_members_unique(group.name)[1]
        assert primary in pool and lender not in pool
        assert leader_js._pool_weights[primary] == 2.0

        # 2) FAILING tunnel on the decode side's slab pull: the
        # backend falls back to local prefill, outputs unchanged,
        # and jobs_kv_handoff_total{result=fallback} ticks per
        # demoted request (the registry is process-global: deltas)
        from dml_tpu.observability import METRICS

        c_handoff = METRICS.counter("jobs_kv_handoff_total")
        fb_metric_before = c_handoff.value(result="fallback")
        handoffs_before = gb.handoffs
        holder["store"].data_plane.fault = TunnelFault(
            seed=3, fail_pct=100.0
        )
        results, _, _ = await gb("ShardLM", local_paths)
        assert gb.fallbacks >= 1
        assert gb.handoffs == handoffs_before
        assert (c_handoff.value(result="fallback") - fb_metric_before
                == gb.fallbacks)
        for p in local_paths:
            fname = os.path.basename(p)
            assert results[p]["tokens"] == expected[fname]

        # 3) SLOW tunnel: the handoff survives (just slower).
        # handoff accounting is per REQUEST now (multi-prefill
        # fan-out + per-request fallback): every request adopts
        holder["store"].data_plane.fault = TunnelFault(
            seed=4, delay_s=0.05
        )
        results, _, _ = await gb("ShardLM", local_paths)
        assert gb.handoffs == handoffs_before + len(local_paths)
        for p in local_paths:
            fname = os.path.basename(p)
            assert results[p]["tokens"] == expected[fname]
        # streamed handoff records a time-to-first-token
        assert gb.last_ttft_s is not None and gb.last_ttft_s > 0
        holder["store"].data_plane.fault = None
    finally:
        await cluster.stop()
        be_single.close()


@pytest.mark.sharded
@pytest.mark.disagg
def test_disagg_cluster_handoff_and_fallback(tmp_path):
    asyncio.run(_disagg_cluster_run(str(tmp_path)))
