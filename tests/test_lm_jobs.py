"""Distributed LM serving as a first-class job type.

The LM stack (generate/LMServer) plugs into the SAME job pipeline as
image inference: prompts replicated in the store, fair-share
scheduling, worker execution, output merge — and the results must be
EXACTLY what isolated `generate` produces per prompt, no matter which
worker served which batch (the LMServer exactness contract carried
end-to-end through the cluster)."""

import asyncio
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _tinynet import ensure_tinynet
from dml_tpu.inference.generate import LMConfig, generate
from dml_tpu.inference.lm_backend import (
    LMBackend,
    parse_prompt_file,
    write_prompt_file,
)
from dml_tpu.models.transformer import TransformerLM

CFG = LMConfig(vocab_size=61, d_model=32, n_heads=4, n_layers=2, d_ff=64,
               dtype=jnp.float32, n_kv_heads=2)
NEW_TOKENS = 8


@pytest.fixture(scope="module")
def params():
    model = TransformerLM(
        vocab_size=CFG.vocab_size, d_model=CFG.d_model,
        n_heads=CFG.n_heads, n_layers=CFG.n_layers, d_ff=CFG.d_ff,
        dtype=jnp.float32, n_kv_heads=CFG.n_kv_heads,
    )
    return model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]


def test_parse_prompt_file(tmp_path):
    p = tmp_path / "a.tokens.txt"
    write_prompt_file(str(p), [3, 1, 4, 1, 5])
    ids, budget = parse_prompt_file(str(p), 61)
    np.testing.assert_array_equal(ids, [3, 1, 4, 1, 5])
    assert budget is None
    (tmp_path / "b.tokens.txt").write_text("1, 2,3")
    ids, _ = parse_prompt_file(str(tmp_path / "b.tokens.txt"), 61)
    np.testing.assert_array_equal(ids, [1, 2, 3])
    (tmp_path / "bad.txt").write_text("7 99")
    with pytest.raises(ValueError, match="out of range"):
        parse_prompt_file(str(tmp_path / "bad.txt"), 61)
    (tmp_path / "empty.txt").write_text(" ")
    with pytest.raises(ValueError, match="empty"):
        parse_prompt_file(str(tmp_path / "empty.txt"), 61)
    (tmp_path / "nonint.txt").write_text("1 x")
    with pytest.raises(ValueError, match="non-integer"):
        parse_prompt_file(str(tmp_path / "nonint.txt"), 61)


def test_parse_prompt_file_budget_directive(tmp_path):
    """Per-request budgets ride the prompt file as a `#` directive
    (mixed budgets = the continuous-batching case; bench
    `lm.mixed_budget_batching`)."""
    p = tmp_path / "a.tokens.txt"
    write_prompt_file(str(p), [3, 1, 4], max_new_tokens=7)
    ids, budget = parse_prompt_file(str(p), 61)
    np.testing.assert_array_equal(ids, [3, 1, 4])
    assert budget == 7
    # unknown comment lines are ignored; bad budgets are loud
    (tmp_path / "c.tokens.txt").write_text("# note: hi\n5 6")
    ids, budget = parse_prompt_file(str(tmp_path / "c.tokens.txt"), 61)
    assert budget is None and list(ids) == [5, 6]
    (tmp_path / "d.tokens.txt").write_text("# max_new_tokens: zero\n5")
    with pytest.raises(ValueError, match="bad max_new_tokens"):
        parse_prompt_file(str(tmp_path / "d.tokens.txt"), 61)
    (tmp_path / "e.tokens.txt").write_text("# max_new_tokens: 0\n5")
    with pytest.raises(ValueError, match=">= 1"):
        parse_prompt_file(str(tmp_path / "e.tokens.txt"), 61)


def test_lm_backend_serve_files(params, tmp_path):
    """The worker-side backend alone: results keyed by path, exact
    greedy match vs isolated generation, measured cost constants."""
    rng = np.random.RandomState(0)
    paths = []
    prompts = []
    for i, tp in enumerate((5, 11, 16)):
        prompt = rng.randint(0, CFG.vocab_size, tp)
        p = str(tmp_path / f"p{i}.tokens.txt")
        write_prompt_file(p, prompt)
        paths.append(p)
        prompts.append(prompt)
    be = LMBackend(params, CFG, max_new_tokens=NEW_TOKENS,
                   max_slots=2, max_len=64, chunk=4)
    results, infer_time, cost = be.serve_files(paths)
    assert infer_time > 0 and cost["per_query"] > 0
    for p, prompt in zip(paths, prompts):
        expect = np.asarray(generate(
            params, CFG, jnp.asarray(np.asarray(prompt, np.int32)[None]),
            NEW_TOKENS,
        ))[0]
        np.testing.assert_array_equal(results[p]["tokens"], expect)


@pytest.mark.parametrize("overlap", [True, False],
                         ids=["driver", "serial"])
def test_lm_request_span_hangs_under_the_batch_trace_context(
        params, tmp_path, overlap):
    """A traced request's `lm_request` span takes the context the job
    service set for its file (the worker's `infer` span) as its parent;
    an unsampled or untraced file roots a trace of its own."""
    from dml_tpu.tracing import CURRENT_CTXS, TRACER, TraceContext

    rng = np.random.RandomState(5)
    paths = []
    for i, tp in enumerate((5, 9, 12)):
        p = str(tmp_path / f"q{i}.tokens.txt")
        write_prompt_file(p, rng.randint(0, CFG.vocab_size, tp))
        paths.append(p)
    be = LMBackend(params, CFG, max_new_tokens=4, max_slots=2,
                   max_len=64, chunk=4)
    be.overlap = overlap
    TRACER.reset()
    token = CURRENT_CTXS.set((
        TraceContext("tA", "sInferA", True, key=paths[0]),
        TraceContext("tB", "sInferB", False, key=paths[1]),
    ))
    try:
        be.serve_files(paths)
    finally:
        CURRENT_CTXS.reset(token)
        be.close()
    by_prompt = {d["lb"]["prompt_tokens"]: d
                 for d in TRACER.loop_spans("lm_request")}
    TRACER.reset()
    assert (by_prompt[5]["tid"], by_prompt[5]["par"]) == ("tA", "sInferA")
    for tp in (9, 12):  # unsampled, and no context at all
        assert by_prompt[tp]["par"] == ""
        assert by_prompt[tp]["tid"] not in ("tA", "tB")


async def _cluster_lm_run(params, tmp):
    from dml_tpu.cluster.introducer import IntroducerService
    from dml_tpu.cluster.node import Node
    from dml_tpu.cluster.store_service import StoreService
    from dml_tpu.config import ClusterSpec, StoreConfig, Timing
    from dml_tpu.inference import InferenceEngine
    from dml_tpu.jobs.service import JobService

    spec = ClusterSpec.localhost(
        4, base_port=18921, introducer_port=18920,
        timing=Timing(ping_interval=0.2, ack_timeout=0.3,
                      cleanup_time=1.0, leader_rpc_timeout=10.0),
        store=StoreConfig(root=os.path.join(tmp, "roots"),
                          download_dir=os.path.join(tmp, "dl")),
    )
    engine = InferenceEngine(dtype=jnp.float32)
    engine.load_model("TinyNet", batch_size=4)

    async def image_backend(model, paths):
        res = await engine.infer_files_async(model, paths)
        return res.to_json_dict(), res.infer_time, engine.cost_constants(model)

    dns = IntroducerService(spec)
    await dns.start()
    stack = []
    for n in spec.nodes:
        node = Node(spec, n)
        store = StoreService(node, root=os.path.join(tmp, f"st_{n.port}"))
        jobs = JobService(node, store, infer_backend=image_backend)
        be = LMBackend(params, CFG, max_new_tokens=NEW_TOKENS,
                       max_slots=2, max_len=64, chunk=4)
        jobs.register_lm("TinyLM", backend=be.backend, cost=be.cost())
        await node.start()
        await store.start()
        await jobs.start()
        stack.append((node, store, jobs))
    try:
        for _ in range(100):
            if all(n.joined and n.leader_unique for n, _, _ in stack):
                break
            await asyncio.sleep(0.1)
        else:
            raise RuntimeError("cluster failed to converge")

        client_store, client_jobs = stack[-1][1], stack[-1][2]
        # seed prompts AND images: the fair-share scheduler will split
        # workers between the LM job and the image job
        rng = np.random.RandomState(1)
        prompts = {}
        budgets = {}
        # p2 carries a per-request budget directive: it must flow
        # store -> scheduler -> worker backend -> merged output intact
        for i, tp in enumerate((4, 9, 13, 16)):
            prompt = rng.randint(0, CFG.vocab_size, tp)
            p = os.path.join(tmp, f"p{i}.tokens.txt")
            b = 3 if i == 2 else None
            write_prompt_file(p, prompt, max_new_tokens=b)
            await client_store.put(p, f"p{i}.tokens.txt")
            prompts[f"p{i}.tokens.txt"] = prompt
            budgets[f"p{i}.tokens.txt"] = b or NEW_TOKENS
        from PIL import Image

        for i in range(3):
            p = os.path.join(tmp, f"img_{i}.jpeg")
            Image.fromarray(
                rng.randint(0, 255, (48, 48, 3), np.uint8)
            ).save(p)
            await client_store.put(p, f"img_{i}.jpeg")

        lm_job = await client_jobs.submit_job("TinyLM", 6)
        img_job = await client_jobs.submit_job("TinyNet", 6)
        lm_done = await client_jobs.wait_job(lm_job, timeout=120.0)
        img_done = await client_jobs.wait_job(img_job, timeout=120.0)
        assert lm_done["total_queries"] == 6
        assert img_done["total_queries"] == 6

        dest = os.path.join(tmp, "lm_out.json")
        merged = await client_jobs.get_output(lm_job, dest)
        # every served prompt file's completion must be EXACTLY the
        # isolated generate() output (wrap-around sampling repeats
        # files; keys collapse to the sdfs names)
        assert merged, "no LM output shards"
        for fname, out in merged.items():
            expect = np.asarray(generate(
                params, CFG,
                jnp.asarray(np.asarray(prompts[fname], np.int32)[None]),
                budgets[fname],
            ))[0]
            np.testing.assert_array_equal(
                out["tokens"], expect, err_msg=fname
            )
        # the budget-directive file really produced ITS budget's
        # length — p2 MUST be present (6 wrap-around queries over 4
        # files cover every file), else this regression check is
        # vacuous
        assert "p2.tokens.txt" in merged
        assert len(merged["p2.tokens.txt"]["tokens"]) == 3
        # C1 saw both models through one scheduler
        leader_jobs = next(j for n, _, j in stack if n.is_leader)
        c1 = leader_jobs.scheduler.c1_stats()
        assert c1["TinyLM"]["total_queries"] == 6
        assert c1["TinyNet"]["total_queries"] == 6
    finally:
        for node, store, jobs in reversed(stack):
            await jobs.stop()
            await store.stop()
            await node.stop()
        await dns.stop()


def test_lm_job_through_cluster_with_image_fair_share(params, tmp_path):
    ensure_tinynet()
    asyncio.run(_cluster_lm_run(params, str(tmp_path)))


def test_lm_backend_concurrent_serves_are_serialized(params, tmp_path):
    """Preemption leaves an orphaned decode thread running while the
    replacement batch starts (jobs/service.py cancels the await, not
    the thread) — overlapping serve_files calls must serialize on the
    backend's lock and BOTH produce exact results."""
    import concurrent.futures

    rng = np.random.RandomState(2)
    batches = []
    for b in range(2):
        paths, prompts = [], []
        for i, tp in enumerate((6, 12)):
            prompt = rng.randint(0, CFG.vocab_size, tp)
            p = str(tmp_path / f"b{b}_p{i}.tokens.txt")
            write_prompt_file(p, prompt)
            paths.append(p)
            prompts.append(prompt)
        batches.append((paths, prompts))
    be = LMBackend(params, CFG, max_new_tokens=NEW_TOKENS,
                   max_slots=2, max_len=64, chunk=4)
    with concurrent.futures.ThreadPoolExecutor(2) as ex:
        futs = [ex.submit(be.serve_files, paths) for paths, _ in batches]
        outs = [f.result(timeout=300) for f in futs]
    for (paths, prompts), (results, _, _) in zip(batches, outs):
        for p, prompt in zip(paths, prompts):
            expect = np.asarray(generate(
                params, CFG,
                jnp.asarray(np.asarray(prompt, np.int32)[None]),
                NEW_TOKENS,
            ))[0]
            np.testing.assert_array_equal(results[p]["tokens"], expect)


def test_lm_backend_rejects_overlong_prompt_before_submitting(params, tmp_path):
    """Capacity is validated for the WHOLE batch before any submit, so
    a poisoned file can't orphan earlier requests in the shared server
    — and the error names the file (r3 review finding)."""
    ok = str(tmp_path / "ok.tokens.txt")
    big = str(tmp_path / "big.tokens.txt")
    write_prompt_file(ok, [1, 2, 3])
    write_prompt_file(big, list(range(50)) + [1] * 10)  # 60 + 8 > 64
    be = LMBackend(params, CFG, max_new_tokens=NEW_TOKENS,
                   max_slots=2, max_len=64, chunk=4)
    with pytest.raises(ValueError, match="big.tokens.txt"):
        be.serve_files([ok, big])
    # the server must be clean: a follow-up batch decodes exactly
    results, _, _ = be.serve_files([ok])
    expect = np.asarray(generate(
        params, CFG, jnp.asarray(np.array([1, 2, 3], np.int32)[None]),
        NEW_TOKENS,
    ))[0]
    np.testing.assert_array_equal(results[ok]["tokens"], expect)


def test_canon_lm_names_case_insensitive(params, tmp_path):
    """CLI users type model names freely; registered LM names resolve
    case-insensitively like the CNN registry's, and unknown-model
    errors list them (r3 review finding)."""
    import asyncio as aio

    from dml_tpu.cluster.node import Node
    from dml_tpu.cluster.store_service import StoreService
    from dml_tpu.config import ClusterSpec, StoreConfig
    from dml_tpu.jobs.service import JobService

    spec = ClusterSpec.localhost(
        1, base_port=18971, introducer_port=18970,
        store=StoreConfig(root=str(tmp_path / "r"),
                          download_dir=str(tmp_path / "d")),
    )

    async def run():
        node = Node(spec, spec.nodes[0])
        store = StoreService(node, root=str(tmp_path / "st"))
        jobs = JobService(node, store)
        be = LMBackend(params, CFG, max_new_tokens=4, max_slots=1,
                       max_len=32)
        jobs.register_lm("MyLM", backend=be.backend, cost=be.cost())
        assert jobs._canon("MyLM") == "MyLM"
        assert jobs._canon("mylm") == "MyLM"
        assert jobs._canon("MYLM") == "MyLM"
        with pytest.raises(KeyError, match="MyLM"):
            jobs._canon("other")

    aio.run(run())


@pytest.mark.sharded
def test_sharded_decode_token_identical_to_single_chip(params, tmp_path):
    """Weight-resident tp-sharded decode (the group-engine serving
    form, inference/lm_sharded.py) produces TOKEN-IDENTICAL results
    to the single-chip LMBackend on the same prompt files — the
    contract that lets an LM round keep a worker group's chips
    pooled without changing any answer. Same params tree, two
    placements."""
    from dml_tpu.config import MeshSpec
    from dml_tpu.inference.lm_sharded import shard_lm_params
    from dml_tpu.parallel.mesh import make_mesh

    rng = np.random.RandomState(3)
    paths = []
    for i, tp in enumerate((4, 9, 14)):
        p = str(tmp_path / f"p{i}.tokens.txt")
        write_prompt_file(p, rng.randint(0, CFG.vocab_size, tp))
        paths.append(p)
    single = LMBackend(params, CFG, max_new_tokens=NEW_TOKENS,
                       max_slots=2, max_len=64, chunk=4)
    mesh = make_mesh(MeshSpec(dp=1, tp=2), devices=jax.devices()[:2])
    sharded = LMBackend(
        shard_lm_params(params, mesh), CFG,
        max_new_tokens=NEW_TOKENS, max_slots=2, max_len=64, chunk=4,
    )
    sharded.overlap = False
    res_single, _, _ = single.serve_files(paths)
    res_sharded, _, _ = sharded.serve_files(paths)
    assert res_sharded == res_single


def test_budget_directive_near_miss_is_loud(tmp_path):
    """A malformed budget directive must raise, not silently serve the
    default budget; and write_prompt_file rejects bad budgets at the
    writer (review findings)."""
    p = tmp_path / "a.tokens.txt"
    p.write_text("# max_new_tokens 64\n5")  # missing colon
    with pytest.raises(ValueError, match="unparseable max_new_tokens"):
        parse_prompt_file(str(p), 61)
    with pytest.raises(ValueError, match=">= 1"):
        write_prompt_file(str(tmp_path / "b.tokens.txt"), [1], max_new_tokens=0)
