"""Speculative decoding + step-granular continuous batching
(inference/lm_server.py, inference/generate.batched_verify_step,
ingress linger scaling).

The load-bearing contract is PROPOSAL INDEPENDENCE: verification
commits only TARGET-greedy tokens, so any proposal stream — a perfect
oracle, pure garbage, a device draft, a shipped remote draft, or
nothing at all — produces output bitwise-identical to the plain
chunked path (and to isolated `generate`). Proposals buy commit
LENGTH, never token values. The second contract is the continuous-
batching adoption seam: a request adopted mid-`step()` (from an
`on_token` callback, racing slot retirement) is delivered exactly
once and never reads another slot's stale verify/chunk column."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dml_tpu.inference.generate import (
    LMConfig,
    batched_decode_step,
    batched_verify_step,
    generate,
    prefill,
)
from dml_tpu.inference.lm_server import LMServer
from dml_tpu.models.transformer import TransformerLM

pytestmark = pytest.mark.specdec

CFG = LMConfig(vocab_size=61, d_model=32, n_heads=4, n_layers=2, d_ff=64,
               dtype=jnp.float32, n_kv_heads=2)


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


def _isolated(params, prompt, n):
    return np.asarray(generate(
        params, CFG, jnp.asarray(np.asarray(prompt, np.int32)[None]), n
    ))[0]


def _srv(params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("chunk", 4)
    return LMServer(params, CFG, **kw)


def _oracle_for(ref_of, vocab=None, corrupt_every=0):
    """Proposer reading precomputed isolated continuations; positions
    where ``e % corrupt_every == corrupt_every - 1`` are deliberately
    wrong (acceptance control — the bench arm's idiom)."""

    def oracle(reqs, k):
        rows = np.zeros((len(reqs), k), np.int32)
        for i, r in enumerate(reqs):
            ref = ref_of[r.rid]
            for j in range(k):
                e = r.emitted + j
                tok = ref[e] if e < len(ref) else 0
                if corrupt_every and e % corrupt_every == corrupt_every - 1:
                    tok = (tok + 1) % vocab
                rows[i, j] = tok
        return rows

    return oracle


# ----------------------------------------------------------------------
# the verify primitive: one multi-token forward == T decode steps
# ----------------------------------------------------------------------

def test_batched_verify_step_matches_sequential_decode(params):
    """batched_verify_step's logits AND cache writes must be the
    exact math of T successive batched_decode_step calls — this
    equivalence is what makes greedy speculation lossless."""
    rng = np.random.RandomState(3)
    pp = rng.randint(0, CFG.vocab_size, (2, 8)).astype(np.int32)
    logits0, cache = prefill(
        params, CFG, jnp.asarray(pp), 32, logits_index=jnp.int32(7)
    )
    pos = jnp.asarray([8, 8], jnp.int32)
    toks = jnp.asarray(
        rng.randint(0, CFG.vocab_size, (2, 3)), jnp.int32
    )
    lg_seq = []
    cache_s = cache
    for t in range(3):
        lg, cache_s = batched_decode_step(
            params, CFG, cache_s, toks[:, t], pos + t
        )
        lg_seq.append(np.asarray(lg).reshape(2, -1))
    lg_v, cache_v = batched_verify_step(params, CFG, cache, toks, pos)
    lg_v = np.asarray(lg_v)
    for t in range(3):
        np.testing.assert_allclose(
            lg_v[:, t], lg_seq[t], rtol=2e-5, atol=2e-5,
            err_msg=f"logits diverge at candidate position {t}",
        )
    for name in cache_v:
        for key in cache_v[name]:
            np.testing.assert_allclose(
                np.asarray(cache_v[name][key]),
                np.asarray(cache_s[name][key]),
                rtol=2e-5, atol=2e-5,
                err_msg=f"cache rows diverge at {name}/{key}",
            )


# ----------------------------------------------------------------------
# proposal independence: every source yields identical tokens
# ----------------------------------------------------------------------

def test_oracle_proposer_exact_with_high_acceptance(params):
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, CFG.vocab_size, n) for n in (7, 16, 11)]
    refs = [_isolated(params, p, 12) for p in prompts]
    ref_of = {}
    srv = _srv(params)
    srv.enable_spec_decode(3, proposer=_oracle_for(ref_of))
    rids = srv.submit_many(prompts, 12)
    for rid, ref in zip(rids, refs):
        ref_of[rid] = [int(t) for t in ref]
    out = srv.run()
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(out[rid], ref)
    st = srv.spec_stats()
    assert st["enabled"] and st["proposed"] > 0
    # the oracle only whiffs past each ref's end (pad zeros)
    assert st["accept_rate"] > 0.6
    assert st["rounds"] > 0


def test_garbage_proposals_never_change_tokens(params):
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, CFG.vocab_size, n) for n in (9, 14)]
    refs = [_isolated(params, p, 10) for p in prompts]
    grng = np.random.RandomState(99)

    def garbage(reqs, k):
        return grng.randint(
            0, CFG.vocab_size, (len(reqs), k)
        ).astype(np.int32)

    srv = _srv(params)
    srv.enable_spec_decode(4, proposer=garbage)
    rids = srv.submit_many(prompts, 10)
    out = srv.run()
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(out[rid], ref)
    st = srv.spec_stats()
    # random proposals against a 61-way argmax: acceptance collapses,
    # but every round still commits >= 1 correct target token
    assert st["accept_rate"] < 0.5
    assert st["enabled"]  # min_accept=0: no auto-disable armed


def test_device_self_draft_is_exact_and_fully_accepted(params):
    """Draft == target: every proposal IS the target argmax, so
    acceptance is exactly 1.0 and outputs stay identical — pins the
    device-draft propose/verify/commit path with no oracle help."""
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, CFG.vocab_size, n) for n in (8, 13)]
    refs = [_isolated(params, p, 11) for p in prompts]
    srv = _srv(params)
    srv.enable_spec_decode(3, draft_params=params, draft_cfg=CFG)
    rids = srv.submit_many(prompts, 11)
    out = srv.run()
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(out[rid], ref)
    st = srv.spec_stats()
    assert st["accept_rate"] == 1.0
    assert st["proposed"] == st["accepted"] > 0


def test_auto_disable_below_break_even_is_typed_and_exact(params):
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, CFG.vocab_size, n) for n in (6, 10)]
    refs = [_isolated(params, p, 16) for p in prompts]
    grng = np.random.RandomState(123)

    def garbage(reqs, k):
        return grng.randint(
            0, CFG.vocab_size, (len(reqs), k)
        ).astype(np.int32)

    srv = _srv(params)
    srv.enable_spec_decode(
        4, proposer=garbage, min_accept=0.6, min_samples=8
    )
    rids = srv.submit_many(prompts, 16)
    out = srv.run()
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(out[rid], ref)
    st = srv.spec_stats()
    assert st["enabled"] is False
    assert st["disabled_reason"] == "acceptance"
    # counters survive the disable for post-mortems
    assert st["proposed"] >= 8


def test_shipped_draft_seeds_exactly_one_verify_round(params):
    """The disaggregated form: a prefill-role peer ships k draft
    tokens in the slab; the decode server (NO local proposal source)
    verifies them once, then falls back to the chunk path — exact
    output, acceptance accounted."""
    from dml_tpu.inference.lm_sharded import LMPrefillBackend

    rng = np.random.RandomState(8)
    prompt = rng.randint(0, CFG.vocab_size, 12).astype(np.int32)
    ref = _isolated(params, prompt, 10)
    pf = LMPrefillBackend(
        params, CFG, max_len=64, draft=(params, CFG), draft_k=3
    )
    entry = pf.prefill_one(prompt, 10)
    assert len(entry["draft"]) == 3
    assert pf.drafts_shipped == 1
    srv = _srv(params)
    srv.enable_spec_decode(3)  # shipped-draft-only mode
    rid = srv.submit_prefilled(
        prompt, 10, entry["rows"], entry["first_token"],
        draft_tokens=entry["draft"],
    )
    out = srv.run()
    np.testing.assert_array_equal(out[rid], ref)
    st = srv.spec_stats()
    # exactly ONE real verify round consumed the shipment (draft ==
    # target here, so all 3 rode home); later dispatches had no
    # proposal source and fell back to the chunk scan
    assert st["proposed"] == 3 and st["accepted"] == 3


def test_spec_near_max_len_falls_back_exactly(params):
    """Slots within k+1 of max_len must fall back to the chunk path
    for that dispatch (a clamped verify start would relocate live
    rows) — outputs stay exact right up to a full max_len."""
    rng = np.random.RandomState(9)
    prompt = rng.randint(0, CFG.vocab_size, 20).astype(np.int32)
    srv = _srv(params, max_len=32)
    ref_of = {}
    srv.enable_spec_decode(4, proposer=_oracle_for(ref_of))
    ref = _isolated(params, prompt, 12)  # 20 + 12 == max_len exactly
    rid = srv.submit(prompt, 12)
    ref_of[rid] = [int(t) for t in ref]
    out = srv.run()
    np.testing.assert_array_equal(out[rid], ref)


def test_enable_spec_decode_validation(params):
    srv = _srv(params)
    with pytest.raises(ValueError, match="k must be >= 1"):
        srv.enable_spec_decode(0)
    with pytest.raises(ValueError, match="no room in max_len"):
        _srv(params, max_len=8).enable_spec_decode(7)
    with pytest.raises(ValueError, match="come together"):
        srv.enable_spec_decode(2, draft_params=params)
    with pytest.raises(ValueError, match="ONE of"):
        srv.enable_spec_decode(
            2, draft_params=params, draft_cfg=CFG,
            proposer=lambda r, k: np.zeros((len(r), k), np.int32),
        )
    bad_cfg = LMConfig(
        vocab_size=7, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        dtype=jnp.float32, n_kv_heads=2,
    )
    with pytest.raises(ValueError, match="vocab"):
        srv.enable_spec_decode(2, draft_params=params, draft_cfg=bad_cfg)
    with pytest.raises(ValueError, match="temperature"):
        _srv(params, temperature=0.8).enable_spec_decode(2)
    busy = _srv(params)
    busy.submit(np.arange(1, 5, dtype=np.int32), 4)
    with pytest.raises(RuntimeError, match="busy"):
        busy.enable_spec_decode(2)


# ----------------------------------------------------------------------
# step-granular adoption races (satellite: submit_prefilled vs
# mid-step retirement — exactly-once delivery, no KV-row aliasing)
# ----------------------------------------------------------------------

def test_adoption_from_on_token_mid_step_is_exactly_once(params):
    """An on_token callback adopts a prefilled request DURING the
    dispatching step (the callback fires inside the step's packed-
    readback delivery). The adoptee lands in a slot this step never
    dispatched for — it must NOT receive this step's stale column:
    its first token arrives exactly once (from the slab) and its
    decode starts at the next dispatch, token-identical to isolated
    generation."""
    from dml_tpu.inference.lm_sharded import LMPrefillBackend

    rng = np.random.RandomState(10)
    p1 = rng.randint(0, CFG.vocab_size, 9).astype(np.int32)
    p2 = rng.randint(0, CFG.vocab_size, 13).astype(np.int32)
    ref1 = _isolated(params, p1, 8)
    ref2 = _isolated(params, p2, 8)
    pf = LMPrefillBackend(params, CFG, max_len=64)
    entry = pf.prefill_one(p2, 8)
    srv = _srv(params, max_slots=2)
    holder = {}

    def adopt(_tok):
        if "rid" not in holder:
            holder["rid"] = srv.submit_prefilled(
                p2, 8, entry["rows"], entry["first_token"]
            )

    rid1 = srv.submit_many([p1], [8], on_token=[adopt])[0]
    out = srv.run()
    assert set(out) == {rid1, holder["rid"]}
    np.testing.assert_array_equal(out[rid1], ref1)
    np.testing.assert_array_equal(out[holder["rid"]], ref2)
    # exactly-once: precisely the budget, no duplicated first token
    assert len(out[holder["rid"]]) == 8


def test_adoption_races_slot_retirement_no_kv_aliasing(params):
    """A short request retires mid-run; a long request's on_token
    callback then adopts a prefilled request into the freed slot
    while the long one keeps decoding. The adoptee's slab insert must
    fully overwrite the retired slot's rows (no aliasing into the
    live neighbor) and every request's tokens stay exact."""
    from dml_tpu.inference.lm_sharded import LMPrefillBackend

    rng = np.random.RandomState(11)
    p_short = rng.randint(0, CFG.vocab_size, 8).astype(np.int32)
    p_long = rng.randint(0, CFG.vocab_size, 10).astype(np.int32)
    p_new = rng.randint(0, CFG.vocab_size, 15).astype(np.int32)
    ref_s = _isolated(params, p_short, 4)
    ref_l = _isolated(params, p_long, 16)
    ref_n = _isolated(params, p_new, 6)
    pf = LMPrefillBackend(params, CFG, max_len=64)
    entry = pf.prefill_one(p_new, 6)
    srv = _srv(params, max_slots=2)
    state = {"seen": 0}

    def adopt_late(_tok):
        state["seen"] += 1
        # by token 8 the short request (budget 4) has retired and
        # its slot is free; adopt into it from inside the step
        if state["seen"] == 8 and "rid" not in state:
            state["rid"] = srv.submit_prefilled(
                p_new, 6, entry["rows"], entry["first_token"]
            )

    rid_s, rid_l = srv.submit_many(
        [p_short, p_long], [4, 16], on_token=[None, adopt_late]
    )
    out = srv.run()
    assert "rid" in state, "adoption callback never fired"
    np.testing.assert_array_equal(out[rid_s], ref_s)
    np.testing.assert_array_equal(out[rid_l], ref_l)
    np.testing.assert_array_equal(out[state["rid"]], ref_n)
    assert len(out[state["rid"]]) == 6


def test_adoption_mid_spec_step_is_exact(params):
    """Same race under SPECULATIVE dispatch: the adoptee must not
    consume the in-flight verify round's columns, and the oracle's
    per-request emitted accounting stays correct across the
    adoption."""
    from dml_tpu.inference.lm_sharded import LMPrefillBackend

    rng = np.random.RandomState(12)
    p1 = rng.randint(0, CFG.vocab_size, 7).astype(np.int32)
    p2 = rng.randint(0, CFG.vocab_size, 12).astype(np.int32)
    ref1 = _isolated(params, p1, 10)
    ref2 = _isolated(params, p2, 10)
    pf = LMPrefillBackend(params, CFG, max_len=64)
    entry = pf.prefill_one(p2, 10)
    ref_of = {}
    srv = _srv(params, max_slots=2)
    srv.enable_spec_decode(3, proposer=_oracle_for(ref_of))
    holder = {}

    def adopt(_tok):
        if "rid" not in holder:
            holder["rid"] = srv.submit_prefilled(
                p2, 10, entry["rows"], entry["first_token"]
            )
            ref_of[holder["rid"]] = [int(t) for t in ref2]

    rid1 = srv.submit_many([p1], [10], on_token=[adopt])[0]
    ref_of[rid1] = [int(t) for t in ref1]
    out = srv.run()
    np.testing.assert_array_equal(out[rid1], ref1)
    np.testing.assert_array_equal(out[holder["rid"]], ref2)
    assert srv.spec_stats()["proposed"] > 0


# ----------------------------------------------------------------------
# ingress: linger scaling (mid-flight adoption shrinks the window)
# ----------------------------------------------------------------------

class _Clock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def step(self, dt):
        self.t += dt


def _pending(clock, i, slo):
    from dml_tpu.ingress.router import PendingRequest

    return PendingRequest(
        id=f"r{i}", client="c", model="m", slo=slo, file="f.jpeg",
        payload=None, session=None, stream=False,
        arrival=clock.t, deadline=clock.t + slo.deadline_s,
    )


def test_linger_scale_shrinks_hungry_window():
    from dml_tpu.ingress.router import BatchFormer, SLOClass

    slo = SLOClass("interactive", deadline_s=2.0, linger_s=0.02)
    clock = _Clock()
    full = BatchFormer(lambda m: 8, lambda m, n: 0.01, now=clock)
    half = BatchFormer(
        lambda m: 8, lambda m, n: 0.01, now=clock, linger_scale=0.5
    )
    zero = BatchFormer(
        lambda m: 8, lambda m, n: 0.01, now=clock, linger_scale=0.0
    )
    for f in (full, half, zero):
        f.add(_pending(clock, 0, slo), None)
    # scale 0: an adopting backend merges at the next step boundary,
    # so a hungry pipeline dispatches immediately
    assert len(zero.due(hungry_models={"m"})) == 1
    clock.step(0.012)  # past 0.02 * 0.5, inside 0.02
    assert full.due(hungry_models={"m"}) == []
    assert len(half.due(hungry_models={"m"})) == 1
    clock.step(0.02)
    assert len(full.due(hungry_models={"m"})) == 1


def test_linger_scale_validation():
    from dml_tpu.ingress.router import BatchFormer

    for bad in (-0.1, 1.5):
        with pytest.raises(ValueError, match="linger_scale"):
            BatchFormer(
                lambda m: 4, lambda m, n: 0.01, linger_scale=bad
            )


# ----------------------------------------------------------------------
# loadgen: per-request TPOT summarized next to TTFT
# ----------------------------------------------------------------------

def test_summarize_tpot_percentiles_over_completions_only():
    from dml_tpu.ingress.loadgen import (
        TERMINAL_COMPLETED,
        TERMINAL_SHED,
        Outcome,
        summarize,
    )

    rows = [
        Outcome(slo="interactive", terminal=TERMINAL_COMPLETED,
                e2e_s=0.1, deadline_met=True, tpot_s=v)
        for v in (0.01, 0.02, 0.03)
    ]
    # a non-streaming completion and a shed request: both excluded
    rows.append(Outcome(slo="interactive", terminal=TERMINAL_COMPLETED,
                        e2e_s=0.1, deadline_met=True))
    rows.append(Outcome(slo="interactive", terminal=TERMINAL_SHED,
                        tpot_s=5.0))
    s = summarize(rows, 1.0)
    assert s["tpot_ms"]["p50"] == 20.0
    # linear interpolation over [10, 20, 30] ms: rank 0.95*2 = 1.9
    assert s["tpot_ms"]["p95"] == pytest.approx(29.0)
    assert s["tpot_ms"]["p99"] == pytest.approx(29.8)
    assert s["by_class"]["interactive"]["tpot_ms"]["p50"] == 20.0


def test_summarize_tpot_none_when_nothing_streamed():
    from dml_tpu.ingress.loadgen import (
        TERMINAL_COMPLETED,
        Outcome,
        summarize,
    )

    rows = [Outcome(slo="batch", terminal=TERMINAL_COMPLETED,
                    e2e_s=0.2, deadline_met=True)]
    s = summarize(rows, 1.0)
    assert s["tpot_ms"] == {"p50": None, "p95": None, "p99": None}
