"""KV prefix cache (dml_tpu/inference/kv_cache.py) + failover-safe
session affinity (ISSUE 14).

Warm-start decode from worker-resident KV slabs must be TOKEN-
IDENTICAL to the cold full-prefill path (the repo's exactness
contract) while skipping the cached prefix's prefill work — covered
here at every layer: the trie/budget/refcount mechanics (pure units),
the LMServer warm placement (greedy equality vs `generate`, mixed
budgets, bucket boundaries, kv_quant), the LMBackend / DisaggLMBackend
hooks, the multi-turn loadgen chaining semantics, the router's
session-affinity counters and relayed session rows across a leader
kill."""

import asyncio
import contextlib
import os
import shutil

import numpy as np
import pytest

from dml_tpu.ingress import loadgen

# ----------------------------------------------------------------------
# pure cache units (no jax)
# ----------------------------------------------------------------------


def _rows(n, fill=1.0, width=4):
    """Synthetic slab for n positions: one layer, [1, n, width] f32."""
    return {
        "block_0": {
            "k": np.full((1, n, width), fill, np.float32),
            "v": np.full((1, n, width), fill, np.float32),
        }
    }


def _cache(max_bytes=1 << 20, **kw):
    from dml_tpu.inference.kv_cache import KVPrefixCache

    return KVPrefixCache(max_bytes, **kw)


@pytest.mark.kvcache
def test_trie_longest_match_and_partial_overlap():
    c = _cache()
    toks = np.arange(10, dtype=np.int32)
    assert c.offer(toks, _rows(10))
    # full-extension prompt matches the whole entry
    p = np.concatenate([toks, [77, 78]]).astype(np.int32)
    assert c.match_len(p) == 10
    # partial overlap: divergence at position 6 still yields 6 rows
    p2 = np.concatenate([toks[:6], [50, 51, 52]]).astype(np.int32)
    assert c.match_len(p2) == 6
    # an IDENTICAL prompt clamps to len-1 (one suffix token must
    # remain to produce the next-token logits)
    assert c.match_len(toks) == 9
    # no shared prefix at all
    assert c.match_len(np.asarray([99, 98], np.int32)) == 0
    # min_match gates shallow matches out
    c2 = _cache(min_match=8)
    assert c2.offer(toks, _rows(10))
    assert c2.match_len(p2) == 0      # 6 < min_match
    assert c2.match_len(p) == 10
    # acquire counts misses; match_len never does
    assert c.stats()["misses"] == 0
    assert c.acquire(np.asarray([99], np.int32)) is None
    assert c.stats()["misses"] == 1


@pytest.mark.kvcache
def test_budget_lru_eviction_order():
    one = _rows(8)
    from dml_tpu.inference.kv_cache import rows_nbytes

    sz = rows_nbytes(one)
    c = _cache(max_bytes=3 * sz)
    a = np.asarray([1, 2, 3, 4, 5, 6, 7, 8], np.int32)
    b = np.asarray([11, 12, 13, 14, 15, 16, 17, 18], np.int32)
    d = np.asarray([21, 22, 23, 24, 25, 26, 27, 28], np.int32)
    e = np.asarray([31, 32, 33, 34, 35, 36, 37, 38], np.int32)
    assert c.offer(a, _rows(8)) and c.offer(b, _rows(8))
    assert c.offer(d, _rows(8))
    # touch `a` (LRU refresh), then overflow: `b` is now the oldest
    lease = c.acquire(np.concatenate([a, [9]]).astype(np.int32))
    assert lease is not None and lease.m == 8
    lease.release()
    assert c.offer(e, _rows(8))
    assert c.match_len(np.concatenate([b, [9]]).astype(np.int32)) == 0
    assert c.match_len(np.concatenate([a, [9]]).astype(np.int32)) == 8
    assert c.stats()["evictions"] == 1
    # an entry bigger than the whole budget is refused outright
    assert not c.offer(
        np.arange(100, dtype=np.int32) + 100, _rows(100, width=4096)
    )


@pytest.mark.kvcache
def test_refcount_blocks_eviction_until_release():
    from dml_tpu.inference.kv_cache import rows_nbytes

    sz = rows_nbytes(_rows(8))
    c = _cache(max_bytes=2 * sz)
    a = np.asarray([1, 2, 3, 4, 5, 6, 7, 8], np.int32)
    b = np.asarray([11, 12, 13, 14, 15, 16, 17, 18], np.int32)
    assert c.offer(a, _rows(8)) and c.offer(b, _rows(8))
    # pin BOTH entries (in-flight adopters) and push the budget:
    # nothing may evict, so the insert is refused — never a corrupted
    # slab under a live adopter
    la = c.acquire(np.concatenate([a, [9]]).astype(np.int32))
    lb = c.acquire(np.concatenate([b, [9]]).astype(np.int32))
    assert la is not None and lb is not None
    d = np.asarray([21, 22, 23, 24, 25, 26, 27, 28], np.int32)
    assert not c.offer(d, _rows(8))
    assert c.stats()["entries"] == 2 and c.stats()["evictions"] == 0
    # release one pin: the oldest UNPINNED entry evicts and the
    # insert lands
    la.release()
    assert c.offer(d, _rows(8))
    assert c.match_len(np.concatenate([a, [9]]).astype(np.int32)) == 0
    assert c.match_len(np.concatenate([b, [9]]).astype(np.int32)) == 8
    lb.release()


@pytest.mark.kvcache
def test_dominated_prefix_entry_dropped_on_insert():
    c = _cache()
    a = np.asarray([1, 2, 3, 4], np.int32)
    longer = np.asarray([1, 2, 3, 4, 5, 6], np.int32)
    assert c.offer(a, _rows(4))
    assert c.offer(longer, _rows(6))
    st = c.stats()
    # the 4-token entry is a strict prefix of the 6-token one: dropped
    assert st["entries"] == 1 and st["evictions"] == 1
    assert c.match_len(np.concatenate([a, [9]]).astype(np.int32)) == 4
    # ...and an offer an existing entry already covers is skipped
    assert not c.offer(a, _rows(4))
    assert c.stats()["inserts"] == 2


@pytest.mark.kvcache
def test_close_refuses_inserts_and_drops_pinned_on_release():
    """close() racing an in-flight adopter: the pinned entry survives
    close (its slab is being read) but drops at lease release, new
    offers are refused, and the byte accounting returns to zero."""
    c = _cache()
    a = np.asarray([1, 2, 3, 4], np.int32)
    b = np.asarray([9, 8, 7, 6], np.int32)
    assert c.offer(a, _rows(4)) and c.offer(b, _rows(4))
    lease = c.acquire(np.concatenate([a, [5]]).astype(np.int32))
    assert lease is not None
    c.close()
    assert c.stats()["entries"] == 1  # only the pinned one remains
    assert not c.offer(np.asarray([5, 5, 5], np.int32), _rows(3))
    lease.release()
    st = c.stats()
    assert st["entries"] == 0 and st["bytes"] == 0


@pytest.mark.kvcache
def test_bounded_dict_on_evict_hook():
    from dml_tpu.cluster.util import BoundedDict

    evicted = []
    d = BoundedDict(2, on_evict=evicted.append)
    d["a"] = 1
    d["b"] = 2
    d["c"] = 3
    assert evicted == ["a"] and set(d) == {"b", "c"}
    del d["b"]  # explicit deletes are NOT evictions
    assert evicted == ["a"]


# ----------------------------------------------------------------------
# LMServer warm placement: token equality vs the cold path
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def lm():
    import jax
    import jax.numpy as jnp

    from dml_tpu.inference.generate import LMConfig
    from dml_tpu.models.transformer import TransformerLM

    cfg = LMConfig(vocab_size=61, d_model=32, n_heads=4, n_layers=2,
                   d_ff=64, dtype=jnp.float32, n_kv_heads=2)
    model = TransformerLM(
        vocab_size=61, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        dtype=jnp.float32, n_kv_heads=2,
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return params, cfg


def _expect(lm_parts, prompt, budget):
    import jax.numpy as jnp

    from dml_tpu.inference.generate import generate

    params, cfg = lm_parts
    return np.asarray(generate(
        params, cfg, jnp.asarray(np.asarray(prompt, np.int32)[None]),
        budget,
    ))[0]


@pytest.mark.kvcache
def test_warm_equals_cold_mixed_budgets_and_bucket_boundaries(lm):
    """Multi-turn warm starts across prompt-bucket boundaries (15/16/
    17 straddle the server's 16-token bucket) and mixed budgets must
    be token-identical to isolated `generate` — the exactness
    contract with the cache IN the loop."""
    from dml_tpu.inference.kv_cache import KVPrefixCache
    from dml_tpu.inference.lm_server import LMServer

    params, cfg = lm
    srv = LMServer(params, cfg, max_slots=2, max_len=128, chunk=4)
    cache = KVPrefixCache(64 << 20)
    srv.enable_kv_cache(cache)
    rng = np.random.RandomState(11)
    for tp, budget in ((15, 5), (16, 3), (17, 7), (9, 1)):
        base = rng.randint(0, 61, tp).astype(np.int32)
        r1 = srv.submit(base, budget)
        out1 = srv.run([r1])[r1]
        np.testing.assert_array_equal(out1, _expect(lm, base, budget))
        # the follow-up turn extends history (prompt + completion +
        # fresh suffix) with a DIFFERENT budget
        nxt = np.concatenate([
            base, out1, rng.randint(0, 61, 4).astype(np.int32),
        ])
        r2 = srv.submit(nxt, budget + 2)
        out2 = srv.run([r2])[r2]
        np.testing.assert_array_equal(
            out2, _expect(lm, nxt, budget + 2)
        )
    st = cache.stats()
    assert st["hits"] >= 4 and st["tokens_saved"] > 0

    # burst form: submit_many with mixed budgets, several warm at once
    hist = rng.randint(0, 61, 12).astype(np.int32)
    r = srv.submit(hist, 6)
    out = srv.run([r])[r]
    prompts = [
        np.concatenate([hist, out, rng.randint(0, 61, k).astype(np.int32)])
        for k in (2, 3)
    ]
    budgets = [4, 9]
    rids = srv.submit_many(prompts, budgets)
    done = srv.run(rids)
    for rid, p, b in zip(rids, prompts, budgets):
        np.testing.assert_array_equal(done[rid], _expect(lm, p, b))


@pytest.mark.kvcache
def test_warm_equals_cold_kv_quant(lm):
    """kv_quant slabs round through the cache (int8 + scale leaves)
    and the warm continuation matches a COLD server of the same
    config (quantization is a model config; equality holds within
    it)."""
    import dataclasses

    from dml_tpu.inference.kv_cache import KVPrefixCache
    from dml_tpu.inference.lm_server import LMServer

    params, cfg = lm
    qcfg = dataclasses.replace(cfg, kv_quant=True)
    warm = LMServer(params, qcfg, max_slots=2, max_len=128, chunk=4)
    warm.enable_kv_cache(KVPrefixCache(64 << 20))
    cold = LMServer(params, qcfg, max_slots=2, max_len=128, chunk=4)
    rng = np.random.RandomState(5)
    base = rng.randint(0, 61, 14).astype(np.int32)
    r1 = warm.submit(base, 6)
    out1 = warm.run([r1])[r1]
    nxt = np.concatenate([base, out1,
                          rng.randint(0, 61, 3).astype(np.int32)])
    rw = warm.submit(nxt, 5)
    got = warm.run([rw])[rw]
    rc = cold.submit(nxt, 5)
    want = cold.run([rc])[rc]
    np.testing.assert_array_equal(got, want)
    assert warm.kv_cache.stats()["hits"] == 1


@pytest.mark.kvcache
def test_sampled_serving_never_warm_starts(lm):
    """temperature > 0 streams are rid-keyed (submit_prefilled's
    documented discipline): neither adoption NOR capture happens — a
    sampled server must not pay per-retire readbacks into a cache
    nothing can ever read."""
    from dml_tpu.inference.kv_cache import KVPrefixCache
    from dml_tpu.inference.lm_server import LMServer

    params, cfg = lm
    srv = LMServer(params, cfg, max_slots=2, max_len=128, chunk=4,
                   temperature=0.8, seed=3)
    srv.enable_kv_cache(KVPrefixCache(64 << 20))
    rng = np.random.RandomState(7)
    base = rng.randint(0, 61, 10).astype(np.int32)
    r1 = srv.submit(base, 5)
    out1 = srv.run([r1])[r1]
    nxt = np.concatenate([base, out1, [3, 4]]).astype(np.int32)
    r2 = srv.submit(nxt, 5)
    srv.run([r2])
    st = srv.kv_cache.stats()
    assert st["hits"] == 0 and st["misses"] == 0
    assert st["inserts"] == 0


@pytest.mark.kvcache
def test_enable_disable_roundtrip_is_cold_path(lm):
    """Detaching the cache restores the stock path: no captures, no
    lookups, outputs equal `generate` (the acceptance criterion's
    'cache disabled => bit-identical to today')."""
    from dml_tpu.inference.kv_cache import KVPrefixCache
    from dml_tpu.inference.lm_server import LMServer

    params, cfg = lm
    srv = LMServer(params, cfg, max_slots=2, max_len=128, chunk=4)
    cache = KVPrefixCache(64 << 20)
    srv.enable_kv_cache(cache)
    srv.enable_kv_cache(None)
    assert srv.kv_cache is None and srv._warm is None
    rng = np.random.RandomState(9)
    p = rng.randint(0, 61, 12).astype(np.int32)
    r = srv.submit(p, 6)
    np.testing.assert_array_equal(srv.run([r])[r], _expect(lm, p, 6))
    assert cache.stats()["inserts"] == 0


# ----------------------------------------------------------------------
# backend hooks: LMBackend / from_spec / DisaggLMBackend
# ----------------------------------------------------------------------


@pytest.mark.kvcache
def test_lm_backend_serve_files_warm_start(lm, tmp_path):
    from dml_tpu.inference.lm_backend import LMBackend, write_prompt_file

    params, cfg = lm
    be = LMBackend(params, cfg, max_new_tokens=6, max_slots=2,
                   max_len=128, chunk=4, kv_cache_bytes=64 << 20)
    try:
        rng = np.random.RandomState(13)
        base = rng.randint(0, 61, 11)
        p1 = str(tmp_path / "t1.tokens.txt")
        write_prompt_file(p1, base)
        res1, _, _ = be.serve_files([p1])
        out1 = res1[p1]["tokens"]
        np.testing.assert_array_equal(out1, _expect(lm, base, 6))
        nxt = np.concatenate([base, out1,
                              rng.randint(0, 61, 4)]).astype(np.int32)
        p2 = str(tmp_path / "t2.tokens.txt")
        write_prompt_file(p2, nxt, max_new_tokens=4)
        res2, _, _ = be.serve_files([p2])
        np.testing.assert_array_equal(
            res2[p2]["tokens"], _expect(lm, nxt, 4)
        )
        st = be.kv_cache_stats()
        assert st["hits"] >= 1 and st["tokens_saved"] > 0
        # the toggle detaches without dropping contents
        be.set_kv_cache_enabled(False)
        res3, _, _ = be.serve_files([p2])
        np.testing.assert_array_equal(
            res3[p2]["tokens"], _expect(lm, nxt, 4)
        )
        assert be.kv_cache_stats()["hits"] == st["hits"]
        be.set_kv_cache_enabled(True)
        assert be.server.kv_cache is be.kv_cache
    finally:
        be.close()


@pytest.mark.kvcache
def test_from_spec_kv_cache_mb():
    from dml_tpu.inference.lm_backend import LMBackend

    spec = {"vocab_size": 61, "d_model": 32, "n_heads": 4,
            "n_layers": 1, "d_ff": 64, "dtype": "float32",
            "kv_cache_mb": 8}
    be = LMBackend.from_spec(spec)
    try:
        assert be.kv_cache is not None
        assert be.kv_cache.max_bytes == 8 << 20
        assert be.server.kv_cache is be.kv_cache
    finally:
        be.close()
    be2 = LMBackend.from_spec({k: v for k, v in spec.items()
                               if k != "kv_cache_mb"})
    try:
        assert be2.kv_cache is None and be2.server.kv_cache is None
    finally:
        be2.close()


@pytest.mark.kvcache
@pytest.mark.disagg
def test_disagg_local_fallback_warm_starts(lm, tmp_path):
    """DisaggLMBackend with the cache enabled: a prompt the decode
    server's cache covers is routed LOCAL (never shipped to a prefill
    peer) and warm-starts at placement — counted as `warm_locals`,
    not handoff fallbacks — with outputs still exactly `generate`."""
    from types import SimpleNamespace

    from dml_tpu.inference.lm_backend import LMBackend, write_prompt_file
    from dml_tpu.inference.lm_sharded import DisaggLMBackend

    params, cfg = lm
    be = LMBackend(params, cfg, max_new_tokens=6, max_slots=2,
                   max_len=128, chunk=4, kv_cache_bytes=64 << 20)
    be.overlap = False
    node = SimpleNamespace(
        spec=SimpleNamespace(group_roles_unique=lambda g: {}),
        me=SimpleNamespace(unique_name="sim1"),
    )
    gb = DisaggLMBackend(
        be, model_name="TinyLM", group_name="g0", node=node,
        store=None, members=(), alive_fn=lambda: set(),
    )
    try:
        rng = np.random.RandomState(17)
        base = rng.randint(0, 61, 10)
        p1 = str(tmp_path / "d1.tokens.txt")
        write_prompt_file(p1, base)
        res1, _, _ = asyncio.run(gb("TinyLM", [p1]))
        out1 = res1[p1]["tokens"]
        np.testing.assert_array_equal(out1, _expect(lm, base, 6))
        # no peers + no cache coverage: counted as fallback
        assert gb.fallbacks == 1 and gb.warm_locals == 0
        nxt = np.concatenate([base, out1,
                              rng.randint(0, 61, 3)]).astype(np.int32)
        p2 = str(tmp_path / "d2.tokens.txt")
        write_prompt_file(p2, nxt)
        res2, _, _ = asyncio.run(gb("TinyLM", [p2]))
        np.testing.assert_array_equal(
            res2[p2]["tokens"], _expect(lm, nxt, 6)
        )
        assert gb.warm_locals == 1 and gb.fallbacks == 1
        assert be.kv_cache.stats()["hits"] == 1
    finally:
        be.close()


# ----------------------------------------------------------------------
# multi-turn loadgen semantics (chained sessions, per-turn TTFT)
# ----------------------------------------------------------------------


@pytest.mark.kvcache
def test_multi_turn_trace_deterministic_json_roundtrip():
    a = loadgen.multi_turn_trace(7, 3, 4, "TinyLM", vocab=61,
                                 suffix_len=5, budget=9)
    b = loadgen.multi_turn_trace(7, 3, 4, "TinyLM", vocab=61,
                                 suffix_len=5, budget=9)
    assert a.to_json() == b.to_json()  # same seed => byte-identical
    c = loadgen.ArrivalTrace.from_json(a.to_json())
    assert c.arrivals == a.arrivals and c.to_json() == a.to_json()
    assert len(a.arrivals) == 12
    assert all(x.stream and x.turn >= 1 and x.budget == 9
               and len(x.suffix) == 5 for x in a.arrivals)
    assert len({x.session for x in a.arrivals}) == 3
    d = loadgen.multi_turn_trace(8, 3, 4, "TinyLM", vocab=61)
    assert d.to_json() != a.to_json()


class _FakeIngress:
    """Duck-typed RequestRouter client surface: deterministic
    'decode' (tokens = prompt length echoes) with a scripted failure
    hook — run_sessions' chaining, TTFT, retry, and abort semantics
    without a cluster."""

    def __init__(self, fail=None):
        self.fail = fail or (lambda payload, attempt: False)
        self.submitted = []  # payload prompt token lists, in order
        self._n = 0
        self._terms = {}
        self.attempts = {}

    async def submit(self, model, slo="interactive", payload=None,
                     session=None, stream=False, timeout=8.0):
        toks = [int(t) for t in payload.splitlines()[-1].split()]
        key = (session, len(toks))
        self.attempts[key] = self.attempts.get(key, 0) + 1
        self._n += 1
        rid = f"r{self._n}"
        if self.fail(toks, self.attempts[key]):
            self._terms[rid] = {"ok": False, "reason": "job_failed: x",
                                "terminal": "rejected"}
        else:
            self.submitted.append((session, toks))
            self._terms[rid] = {
                "ok": True, "terminal": "completed",
                "deadline_met": True, "worker": "w1",
                "result": {"tokens": [len(toks) % 61, 7]},
            }
        return rid

    async def stream_text(self, rid, timeout=30.0, on_first=None,
                          on_chunk=None):
        await asyncio.sleep(0.01)
        if self._terms[rid].get("ok"):
            if on_first is not None:
                on_first()
            if on_chunk is not None:
                on_chunk("7 ")
        return ["7 "]

    async def wait(self, rid, timeout=None):
        await asyncio.sleep(0.005)
        return dict(self._terms[rid], id=rid)


@pytest.mark.kvcache
def test_run_sessions_chains_history_and_measures_ttft():
    trace = loadgen.multi_turn_trace(
        3, 2, 3, "M", vocab=61, suffix_len=4, budget=5,
        start_gap_s=0.01, think_s=0.01,
    )
    fake = _FakeIngress()
    outcomes, wall, tx = asyncio.run(
        loadgen.run_sessions(fake, trace)
    )
    assert len(outcomes) == 6
    assert all(o.terminal == "completed" for o in outcomes)
    assert all(o.ttft_s is not None and o.ttft_s >= 0 for o in outcomes)
    # chaining: turn N's prompt == prior suffixes + completions
    by_sess = {}
    for a in sorted(trace.arrivals, key=lambda x: (x.session, x.turn)):
        by_sess.setdefault(a.session, []).append(a)
    for sess, turns in by_sess.items():
        sub = [t for s, t in fake.submitted if s == sess]
        history = []
        for a, got, completion in zip(turns, sub, tx[sess]):
            want = history + list(a.suffix)
            assert got == want
            history = want + completion
    # per-turn TTFT lands in summarize
    s = loadgen.summarize(outcomes, wall)
    assert set(s["by_turn"]) == {"1", "2", "3"}
    assert s["by_turn"]["2"]["ttft_ms"]["p50"] is not None
    assert s["by_turn"]["2"]["completed"] == 2


@pytest.mark.kvcache
def test_run_sessions_retries_then_aborts_broken_chain():
    trace = loadgen.multi_turn_trace(
        4, 1, 3, "M", vocab=61, suffix_len=4, budget=5,
        start_gap_s=0.01, think_s=0.01,
    )
    # turn 2 (prompt length 4 + 2 + 4 = 10) fails twice, succeeds on
    # the 3rd attempt: retried transparently, chain intact
    flaky = _FakeIngress(
        fail=lambda toks, attempt: len(toks) == 10 and attempt < 3
    )
    outcomes, _, tx = asyncio.run(
        loadgen.run_sessions(flaky, trace, turn_retries=3)
    )
    assert [o.terminal for o in outcomes] == ["completed"] * 3
    # a turn that NEVER completes aborts the session; remaining turns
    # settle as typed rejections (terminals stay exhaustive)
    dead = _FakeIngress(fail=lambda toks, attempt: len(toks) == 10)
    outcomes, _, tx = asyncio.run(
        loadgen.run_sessions(dead, trace, turn_retries=2)
    )
    kinds = [o.terminal for o in sorted(outcomes, key=lambda o: o.turn)]
    assert kinds == ["completed", "rejected", "rejected"]
    assert [o.reason for o in outcomes if o.turn == 3] == [
        "session_aborted"
    ]


# ----------------------------------------------------------------------
# end-to-end: multi-turn sessions through the front door on a real
# LMBackend with the cache — warm transcripts == generate references
# ----------------------------------------------------------------------


@contextlib.asynccontextmanager
async def _cluster(n, base_port, tmp_path, **kw):
    from dml_tpu.cluster.chaos import LocalCluster

    root = str(tmp_path / f"kvc_{base_port}")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    c = LocalCluster(n, root, base_port, with_ingress=True, **kw)
    try:
        await c.start()
        await c.wait_for(c.converged, 15.0, "initial convergence")
        yield c
    finally:
        await c.stop()


@pytest.mark.kvcache
@pytest.mark.ingress
def test_cluster_multi_turn_warm_equals_generate(lm, tmp_path):
    """The full pipeline: growing-history sessions through admission/
    formation/affinity into a REAL continuous-batching LMBackend with
    the prefix cache on every node. Completions must be token-
    identical to client-side `generate` references and the cache must
    actually hit (session affinity landing turns on the KV holder)."""
    from dml_tpu.inference.lm_backend import LMBackend

    params, cfg = lm

    async def run():
        async with _cluster(3, 24951, tmp_path) as c:
            backends = []
            for sn in c.nodes.values():
                be = LMBackend(params, cfg, max_new_tokens=6,
                               max_slots=4, max_len=256, chunk=4,
                               kv_cache_bytes=64 << 20)
                sn.jobs.register_lm(
                    "TinyLM", backend=be.backend, cost=be.cost(),
                    patterns=("*.tokens.txt", "ingress_*.req"),
                )
                backends.append(be)
            client = c.client()
            trace = loadgen.multi_turn_trace(
                6, n_sessions=2, turns=3, model="TinyLM", slo="batch",
                start_gap_s=0.6, think_s=0.4, suffix_len=6, vocab=61,
                budget=6,
            )
            outcomes, _, tx = await loadgen.run_sessions(
                client.ingress, trace, wait_timeout=60.0,
            )
            try:
                assert all(
                    o.terminal == "completed" for o in outcomes
                ), [(o.turn, o.terminal, o.reason) for o in outcomes]
                # token equality vs client-side generate references
                by_sess = {}
                for a in trace.arrivals:
                    by_sess.setdefault(a.session, []).append(a)
                for sess, turns in by_sess.items():
                    history = []
                    for a, got in zip(
                        sorted(turns, key=lambda x: x.turn), tx[sess]
                    ):
                        prompt = history + list(a.suffix)
                        np.testing.assert_array_equal(
                            got, _expect(lm, prompt, a.budget)
                        )
                        history = prompt + got
                hits = sum(
                    be.kv_cache_stats()["hits"] for be in backends
                )
                saved = sum(
                    be.kv_cache_stats()["tokens_saved"]
                    for be in backends
                )
                assert hits > 0 and saved > 0
                # streamed turns measured TTFT client-side
                assert any(o.ttft_s is not None for o in outcomes)
            finally:
                for be in backends:
                    be.close()

    asyncio.run(run())


# ----------------------------------------------------------------------
# failover-safe affinity: relayed session rows survive a leader kill
# ----------------------------------------------------------------------


@pytest.mark.kvcache
@pytest.mark.ingress
def test_session_rows_survive_leader_failover(tmp_path):
    """Deterministic leader-kill: after turn 1 completes, the
    session->worker row must reach the standby via INGRESS_RELAY (the
    piggyback/flush), so the PROMOTED router routes turn 2 to the
    worker holding the session's KV instead of a cold peer — plus the
    affinity hit/miss counters moving the right way."""
    from dml_tpu.ingress.streaming import STUB_LM_MODEL
    from dml_tpu.observability import METRICS

    def counter(snap, prefix):
        return sum(
            v for k, v in snap["counters"].items()
            if k.startswith(prefix)
        )

    async def run():
        async with _cluster(4, 24971, tmp_path) as c:
            client = c.client()
            await client.store.put_bytes(
                "p1.prompt.txt", b"1 2 3\n", timeout=20.0
            )
            snap0 = METRICS.snapshot()
            t1 = await client.ingress.request(
                STUB_LM_MODEL, session="sess-kv", timeout=30.0
            )
            assert t1["ok"] and t1["worker"]
            snap1 = METRICS.snapshot()
            # first turn had no binding: a miss, never a hit
            assert counter(
                snap1, "request_session_affinity_misses_total"
            ) > counter(snap0, "request_session_affinity_misses_total")
            leader0 = c.leader_uname()
            standby = next(
                sn for un, sn in c.nodes.items() if un != leader0
                and sn.store.standby_node() is not None
            )
            # the relayed row must land on the leader's standby
            leader_sn = c.nodes[leader0]
            sb = leader_sn.store.standby_node()
            assert sb is not None
            sb_sn = c.nodes[sb.unique_name]
            await c.wait_for(
                lambda: sb_sn.ingress._session_node.get("sess-kv")
                == t1["worker"],
                10.0, "session row relayed to standby",
            )
            # kill the leader mid-session
            await c.crash_node(leader0)
            await c.wait_for(
                lambda: c.leader_uname() is not None
                and c.leader_uname() != leader0,
                25.0, "re-election",
            )
            promoted = c.nodes[c.leader_uname()]
            assert promoted.ingress._session_node.get("sess-kv") == \
                t1["worker"]
            # turn 2 through the promoted router: affinity HIT when
            # the holder is still in the promoted leader's schedulable
            # pool (it may itself have been the killed leader, or be
            # promoted out of the pool — then the miss path is correct
            # behavior, not a relay failure)
            client2 = c.client(avoid=(leader0,))
            snap2 = METRICS.snapshot()
            holder_schedulable = (
                t1["worker"] in promoted.jobs.worker_pool()
            )
            t2 = await client2.ingress.request(
                STUB_LM_MODEL, session="sess-kv", timeout=30.0
            )
            assert t2["ok"]
            if holder_schedulable:
                snap3 = METRICS.snapshot()
                assert counter(
                    snap3, "request_session_affinity_hits_total"
                ) > counter(
                    snap2, "request_session_affinity_hits_total"
                )
                assert t2["worker"] == t1["worker"]
            del standby  # (first standby holder is enough)

    asyncio.run(run())


@pytest.mark.kvcache
def test_session_map_eviction_ticks_counter(tmp_path):
    """`_session_node` aging a session out under bound pressure must
    tick the eviction counter — a silent eviction is a guaranteed KV
    miss the operator could otherwise never see."""
    from dml_tpu.observability import METRICS

    async def run():
        async with _cluster(3, 24991, tmp_path) as c:
            sn = next(iter(c.nodes.values()))
            router = sn.ingress
            router._session_node.maxlen = 2

            def count():
                return sum(
                    v for k, v in METRICS.snapshot()["counters"].items()
                    if k.startswith(
                        "request_session_affinity_evictions_total"
                    )
                )

            before = count()
            router._session_node["s1"] = "w1"
            router._session_node["s2"] = "w2"
            router._session_node["s3"] = "w3"
            assert count() == before + 1
            assert "s1" not in router._session_node

    asyncio.run(run())
