"""A `layer_pattern` model (Mamba-2 state-space layers, attention without
rope, latent experts under a sigmoid router with a shared expert) on the
serving path (`LMBackend.from_spec` -> `LMServer` -> `LMDriver`), against
the benchmark's plain reference, loaded by its path as
`benchmark/harness/manifest.load_module` loads it. Small sizes, seeded
random weights, float32, on the CPU.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dml_tpu.inference import generate as G
from dml_tpu.inference import lm_server as LS
from dml_tpu.inference.kv_cache import KVPrefixCache
from dml_tpu.inference.lm_backend import LMBackend, lm_arch, lm_spec_parts
from dml_tpu.observability import METRICS
from dml_tpu.tracing import TRACER

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 97
TOL = 2e-4  # float32 programs of different shapes, logits of unit spread


def _reference():
    path = os.path.join(ROOT, "benchmark", "references",
                        "nemotron_h_latent_moe.py")
    spec = importlib.util.spec_from_file_location("ref_nemotron_h", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _spec(**over):
    return {
        "vocab_size": VOCAB, "d_model": 32, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "layer_pattern": "EME*M",
        "ssm": {"heads": 8, "head_dim": 8, "state": 16, "groups": 2,
                "conv_kernel": 4, "chunk": 8},
        "rope": "none", "norm_eps": 1e-5,
        "num_experts": 16, "experts_per_token": 3, "expert_d_ff": 24,
        "gated": False, "experts_held": [4, 8],
        "router": {"scoring": "sigmoid", "bias": True, "scale": 2.5},
        "expert_latent": 16, "shared_expert_d_ff": 40, "activation": "relu2",
        "dtype": "float32", "param_dtype": "float32",
        "max_len": 64, "max_slots": 4, "max_new_tokens": 10, "chunk": 4,
        "seed": 5, **over,
    }


def _parts(spec, seed=11):
    """(the REFERENCE's weights in the tree the program declares, cfg):
    the trees must agree leaf for leaf, or `tree.map` raises."""
    params, cfg = lm_spec_parts(spec)
    assert (jax.tree.map(lambda x: tuple(x.shape), params)
            == REF.param_shapes(spec))
    return jax.tree.map(lambda x, d: x.astype(d.dtype),
                        REF.make_params(spec, seed), params), cfg


def _backend(spec):
    params, cfg = _parts(spec)
    be = LMBackend.from_spec(spec)
    be.server.params = params
    return be, params, cfg


def _prompts(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, n).astype(np.int32) for n in lengths]


def _serve(be, prompts, budgets):
    return [[int(t) for t in ts]
            for ts in be.driver.serve(prompts, budgets)]


def _assert_the_references_choice(spec, params, prompt, budget, got):
    assert len(got) == budget
    g = REF.served_gaps(params, spec, prompt, got, pad_to=64, rows_pad=32)
    assert g["gap_max"] <= TOL, g


# ----------------------------------------------------------------------
# the layer mathematics, against the reference's one plain pass
# ----------------------------------------------------------------------


@pytest.mark.parametrize("pattern", ["EME*M", "M*E", "MEM", "E*ME"])
def test_prefill_then_decode_gives_the_references_logits_everywhere(pattern):
    """The chunked scan over a prompt (17 tokens: no multiple of the
    chunk of 8), then one recurrence step a token through the state:
    the logits at EVERY position are the reference's, which runs one
    plain `lax.scan` over the whole sequence."""
    spec = _spec(layer_pattern=pattern)
    params, cfg = _parts(spec)
    toks = _prompts([30], seed=3)[0]
    n_prompt = 17
    ref = REF.logits_rows(params, spec, toks, 0, 30, pad_to=30)
    run = jax.jit(lambda x, i: G.prefill(params, cfg, x, 64, logits_index=i))
    for i in range(n_prompt):  # every prompt position, the pad behind it
        logits, cache = run(jnp.asarray(toks[None, :n_prompt]), jnp.int32(i))
        np.testing.assert_allclose(logits[0], ref[i], atol=TOL)
    step = jax.jit(lambda c, t, p: G.batched_decode_step(params, cfg, c, t, p))
    for pos in range(n_prompt, 30):
        logits, cache = step(cache, jnp.asarray(toks[pos:pos + 1]),
                             jnp.asarray([pos], jnp.int32))
        np.testing.assert_allclose(logits[0], ref[pos], atol=TOL)


@pytest.mark.parametrize("length", [1, 2, 3, 5, 8, 9, 17])
def test_a_padded_rows_state_is_the_unpadded_runs(length):
    """A row padded to a bucket (with its last token, as the server pads)
    beside a longer row: with the rows' own lengths the prefill hands
    back the convolution window and the scan state of the UNPADDED
    prompt (a prompt shorter than the window has zeros on its left), and
    the same K/V rows."""
    spec = _spec()
    params, cfg = _parts(spec)
    short, longer = _prompts([length, 24], seed=length)
    padded = np.stack([np.pad(short, (0, 24 - length), mode="edge"), longer])
    _, alone = G.prefill(params, cfg, jnp.asarray(short[None]), 64)
    logits, both = G.prefill(
        params, cfg, jnp.asarray(padded), 64,
        logits_index=jnp.asarray([length - 1, 23], jnp.int32))
    for name, lay in alone.items():
        for key, leaf in lay.items():
            got = both[name][key][0]
            if key in ("k", "v"):  # rows past the prompt hold the pad's
                got, leaf = got[:, :length], leaf[:, :, :length]
            np.testing.assert_allclose(got, leaf[0], atol=1e-5,
                                       err_msg=f"{name}.{key}")
    ref = REF.logits_rows(params, spec, short, length - 1, 1, pad_to=24)
    np.testing.assert_allclose(logits[0], ref[0], atol=TOL)


@pytest.mark.parametrize("t", [1, 3, 127, 128, 300])
def test_the_chunked_scan_is_the_step_recurrence(t):
    """`ssm_scan_chunked` (chunk 128, from a given state) against
    `ssm_scan_step` applied t times: outputs and the final state."""
    b, h, p, g, n = 2, 4, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(t), 6)
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.5))
    bm = jax.random.normal(ks[3], (b, t, g, n))
    cm = jax.random.normal(ks[4], (b, t, g, n))
    h0 = jax.random.normal(ks[5], (b, h, p, n))
    y, h_end = jax.jit(G.ssm_scan_chunked, static_argnums=6)(
        x, dt, a, bm, cm, h0, 128)

    def step(hh, args):
        yy, hh = G.ssm_scan_step(*args[:2], a, *args[2:], hh)
        return hh, yy

    h_ref, y_ref = jax.lax.scan(step, h0, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, bm, cm)))
    np.testing.assert_allclose(y, jnp.moveaxis(y_ref, 0, 1),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(h_end, h_ref, atol=2e-4, rtol=2e-4)


def test_a_position_with_no_step_leaves_the_state_as_it_was():
    """dt = 0 is how a padded position is skipped: decay 1, input 0."""
    b, t, h, p, g, n = 1, 12, 4, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt = jnp.concatenate([jnp.full((b, 7, h), 0.3), jnp.zeros((b, 5, h))], 1)
    a = -jnp.ones(h)
    bm, cm = (jax.random.normal(k, (b, t, g, n)) for k in ks[1:3])
    h0 = jax.random.normal(ks[3], (b, h, p, n))
    _, whole = G.ssm_scan_chunked(x, dt, a, bm, cm, h0, 8)
    _, cut = G.ssm_scan_chunked(
        x[:, :7], dt[:, :7], a, bm[:, :7], cm[:, :7], h0, 8)
    np.testing.assert_allclose(whole, cut, atol=1e-6)


# ----------------------------------------------------------------------
# the expert layer: router, latent width, shared expert, shares
# ----------------------------------------------------------------------


def _moe_spec(held):
    # block_0 is the expert layer under test
    return _spec(layer_pattern="EM", num_experts=32, experts_per_token=6,
                 experts_held=held)


def _expert_layer(spec, params, y):
    cfg = lm_spec_parts(spec)[1]
    return G.expert_ffn(
        params["block_0"]["moe"], y, jnp.float32, cfg.experts_per_token,
        cfg.experts_first, scoring=cfg.router_scoring,
        scale=cfg.router_scale, activation=cfg.activation)


def test_the_shares_of_four_chips_add_up_to_the_uncut_layer():
    """model-configs section 4: four shares of a 32-expert latent layer,
    each computed by the program for the 8 experts it holds, with the
    shared expert (which every chip computes alike) counted once, add
    up to what the reference gives for the whole layer."""
    whole_spec = _moe_spec([0, 32])
    whole = REF.make_params(whole_spec, 3)
    moe = whole["block_0"]["moe"]
    y = jax.random.normal(jax.random.PRNGKey(1), (2, 9, 32))
    dims = REF._dims(whole_spec)
    want = REF.experts(y.reshape(-1, 32), moe, dims, "f32")
    shared = REF.experts(y.reshape(-1, 32), moe, dims, "f32") - REF.experts(
        y.reshape(-1, 32), moe, dims, "f32", shared=False)
    total = jnp.zeros_like(want)
    for first in (0, 8, 16, 24):
        spec = _moe_spec([first, 8])
        share = {"block_0": {"moe": {
            **moe, "w_up": moe["w_up"][first:first + 8],
            "w_down": moe["w_down"][first:first + 8]}}}
        out, counts = _expert_layer(spec, share, y)
        # every share routes over ALL 32 experts, and counts them so
        assert int(counts.sum()) == 2 * 9 * 6
        total = total + out.reshape(-1, 32) - shared
        # ... and is the reference's own share
        np.testing.assert_allclose(
            out.reshape(-1, 32),
            REF.experts(y.reshape(-1, 32), share["block_0"]["moe"],
                        REF._dims(spec), "f32"), atol=1e-4)
    np.testing.assert_allclose(total + shared, want, atol=2e-4)


@pytest.mark.parametrize("bias,scale", [(True, 2.5), (False, 1.0),
                                        (True, 5.0)])
def test_the_sigmoid_router_against_a_plain_top_k(bias, scale):
    """The k experts with the largest sigmoid + bias; gates the chosen
    sigmoids (WITHOUT the bias) renormalised, times the scale: by numpy,
    with the latent experts and the shared expert one by one."""
    spec = _spec(layer_pattern="EM", experts_held=[0, 16],
                 router={"scoring": "sigmoid", "bias": bias, "scale": scale})
    params = REF.make_params(spec, 9)
    moe = jax.tree.map(np.asarray, params["block_0"]["moe"])
    if bias:  # large enough to change choices, or the test shows nothing
        moe["router"]["bias"] = moe["router"]["bias"] * 10.0
    y = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (7, 32)))
    s = 1.0 / (1.0 + np.exp(-(y @ moe["router"]["kernel"])))
    pick = s + moe["router"]["bias"] if bias else s
    relu2 = lambda v: np.square(np.maximum(v, 0.0))
    want = relu2(y @ moe["shared_up"]["kernel"]) @ moe["shared_down"]["kernel"]
    u = y @ moe["latent_down"]["kernel"]
    changed = 0
    for t in range(7):
        top = np.argsort(-pick[t])[:3]
        changed += set(top) != set(np.argsort(-s[t])[:3])
        gates = scale * s[t, top] / s[t, top].sum()
        r = sum(g * relu2(u[t] @ moe["w_up"][e]) @ moe["w_down"][e]
                for g, e in zip(gates, top))
        want[t] += r @ moe["latent_up"]["kernel"]
    assert changed > 0 or not bias
    out, counts = _expert_layer(
        spec, {"block_0": {"moe": jax.tree.map(jnp.asarray, moe)}},
        jnp.asarray(y)[None])
    np.testing.assert_allclose(out[0], want, atol=2e-4)
    assert int(counts.sum()) == 7 * 3


def _windowed(monkeypatch, window, spec, params, y, **kw):
    """(out, counts, windows past the first or None) of the latent expert
    layer laid out over `window` rows a call (None: all its rows, the
    form without a loop; 0: the size its shapes give)."""
    if window != 0:
        monkeypatch.setattr(
            G, "moe_window",
            lambda n, k, e, held: n * k if window is None else window)
    cfg = lm_spec_parts(spec)[1]
    past = []
    out, counts = G.expert_ffn(
        params["block_0"]["moe"], y, jnp.float32, cfg.experts_per_token,
        cfg.experts_first, scoring=cfg.router_scoring,
        scale=cfg.router_scale, activation=cfg.activation, windows=past,
        **kw)
    monkeypatch.undo()
    return np.asarray(out), np.asarray(counts), (
        int(past[0]) if past else None)


def _float32_sums(got, want):
    # equal up to the order of float32 additions (PERF.md section 3)
    np.testing.assert_allclose(
        got, want, rtol=0, atol=4e-6 * max(1.0, np.abs(want).max()))


@pytest.mark.parametrize("live", [False, True])
@pytest.mark.parametrize("first,held", [(6, 2), (8, 8), (0, 32)])
def test_latent_experts_over_windows_give_the_one_windows_sums(
        monkeypatch, first, held, live):
    """Held shares 1/16, 1/4 and 1 of 32 ungated relu-squared experts
    top-6 in the latent width: windows of 24 rows against one window
    under the same loop, against the form without a loop over all 606
    rows, and against the size the shapes give."""
    whole = REF.make_params(_moe_spec([0, 32]), 3)["block_0"]["moe"]
    spec = _moe_spec([first, held])
    params = {"block_0": {"moe": {
        **whole, "w_up": whole["w_up"][first:first + held],
        "w_down": whole["w_down"][first:first + held]}}}
    y = jax.random.normal(jax.random.PRNGKey(3), (1, 101, 32))
    kw = {"live": jnp.asarray([False])} if live else {}
    want, counts, none = _windowed(monkeypatch, None, spec, params, y, **kw)
    assert none is None and int(counts.sum()) == (0 if live else 606)
    got, c, past = _windowed(monkeypatch, 24, spec, params, y, **kw)
    one, _, none = _windowed(monkeypatch, 605, spec, params, y, **kw)
    assert none == (held == 32)  # all 606 rows held: a second window of 1
    _float32_sums(got, one)
    _float32_sums(got, want)
    np.testing.assert_array_equal(c, counts)
    _, every, _ = _windowed(monkeypatch, None, spec, params, y)
    here = int(every[first:first + held].sum())
    assert past == max(-(-here // 24), 1) - 1 and past > 0
    by_shape, _, past = _windowed(monkeypatch, 0, spec, params, y, **kw)
    _float32_sums(by_shape, want)
    assert past == (None if held == 32 else 0)
    # ... and the reference's own share of the layer
    np.testing.assert_allclose(
        got[0], REF.experts(y[0], params["block_0"]["moe"],
                            REF._dims(spec), "f32"), atol=2e-4)


@pytest.mark.parametrize("push,windows", [(10.0, 3), (-10.0, 1)])
def test_latent_experts_drop_nothing_when_every_token_takes_the_held(
        monkeypatch, push, windows):
    """A selection bias on the 8 held experts of 32, top-6: all 606
    assignments are held rows where the shapes give windows of 256 (three
    windows, nothing dropped); the opposite bias leaves no held row, and
    the layer is its shared expert."""
    spec = _moe_spec([8, 8])
    moe = REF.make_params(spec, 3)["block_0"]["moe"]
    bias = np.zeros(32, np.float32)
    bias[8:16] = push
    moe = {**moe, "router": {**moe["router"], "bias": jnp.asarray(bias)}}
    params = {"block_0": {"moe": moe}}
    y = jax.random.normal(jax.random.PRNGKey(4), (1, 101, 32))
    assert G.moe_layout(101, 6, 32, 8) == (1, 101, 256)
    want, counts, _ = _windowed(monkeypatch, None, spec, params, y)
    got, c, past = _windowed(monkeypatch, 0, spec, params, y)
    assert int(counts[8:16].sum()) == (606 if push > 0 else 0)
    assert past == windows - 1
    _float32_sums(got, want)
    np.testing.assert_array_equal(c, counts)
    np.testing.assert_allclose(
        got[0], REF.experts(y[0], moe, REF._dims(spec), "f32"), atol=2e-4)
    if push < 0:
        np.testing.assert_allclose(
            got[0], REF.experts(y[0], moe, REF._dims(spec), "f32")
            - REF.experts(y[0], moe, REF._dims(spec), "f32", shared=False),
            atol=2e-4)


# ----------------------------------------------------------------------
# through the server: slots, placement, reuse
# ----------------------------------------------------------------------


def test_served_tokens_are_the_references_choice():
    """Prompts shorter than the convolution's window (1, 2), of no whole
    number of chunks, and longer than a chunk, more of them than slots:
    every served token is the reference's argmax given what came
    before it."""
    spec = _spec()
    be, params, _ = _backend(spec)
    prompts = _prompts([5, 2, 11, 17, 9, 1, 30])
    budgets = [10, 7, 9, 12, 4, 10, 20]
    try:
        results = _serve(be, prompts, budgets)
    finally:
        be.close()
    for prompt, budget, got in zip(prompts, budgets, results):
        _assert_the_references_choice(spec, params, prompt, budget, got)


def test_a_request_alone_equals_the_same_request_in_a_full_grid():
    spec = _spec()
    be, params, _ = _backend(spec)
    prompts = _prompts([9, 3, 14, 6], seed=4)
    try:
        alone = _serve(be, prompts[:1], [12])[0]
        full = _serve(be, prompts, [12, 5, 9, 12])
    finally:
        be.close()
    assert full[0] == alone
    for prompt, got in zip(prompts, full):
        _assert_the_references_choice(spec, params, prompt, len(got), got)


def test_a_slot_reused_after_a_longer_occupant_starts_clean():
    """One slot: a long request, then a short one in the same slot. The
    second's state is overwritten whole at placement (nothing of the
    first's scan state, window or rows is read)."""
    spec = _spec(max_slots=1)
    be, params, _ = _backend(spec)
    long_, short = _prompts([28, 4], seed=6)
    try:
        first = _serve(be, [long_], [30])[0]
        second = _serve(be, [short], [8])[0]
    finally:
        be.close()
    _assert_the_references_choice(spec, params, long_, 30, first)
    _assert_the_references_choice(spec, params, short, 8, second)


def test_joins_and_leaves_while_neighbours_decode():
    """Seven requests of spread budgets over two slots: each slot is
    left and joined at dispatches where the other is in mid-answer."""
    spec = _spec(max_slots=2, chunk=2)
    be, params, _ = _backend(spec)
    prompts = _prompts([7, 12, 3, 20, 5, 9, 2], seed=8)
    budgets = [21, 3, 9, 5, 14, 2, 11]
    try:
        results = _serve(be, prompts, budgets)
    finally:
        be.close()
    for prompt, budget, got in zip(prompts, budgets, results):
        _assert_the_references_choice(spec, params, prompt, budget, got)


def test_prefill_groups_keep_to_the_token_bound():
    lengths = [40] * 20 + [700] * 9 + [1500] * 5
    for bucket, rows, members in LS._prefill_groups(
            lengths, 4096, 64, max_tokens=4096):
        assert rows == 1 or rows * bucket <= 4096, (bucket, rows)
    free = LS._prefill_groups(lengths, 4096, 64)
    assert max(rows * bucket for bucket, rows, _ in free) > 4096
    assert sorted(m for _, _, ms in free for m in ms) == list(range(34))


def test_spans_and_counters_carry_the_state_and_the_routing():
    spec = _spec()
    be, _, _ = _backend(spec)
    held = METRICS.counter("moe_assignments_total")
    before = {w: held.value(where=w) for w in ("held", "absent")}
    n0 = len(TRACER.loop_spans("lm_step"))
    try:
        _serve(be, _prompts([6, 9]), [6, 6])
    finally:
        be.close()
    steps = [d for d in TRACER.loop_spans("lm_step")[n0:]
             if "state_slots" in d["lb"]]
    assert steps and all(
        d["lb"]["state_slots"] == d["lb"]["occupancy"] for d in steps)
    assert all(0 < d["lb"]["experts_touched_held"]
               <= min(8, d["lb"]["experts_touched"]) for d in steps)
    groups = [d for d in TRACER.loop_spans("lm_prefill_group")
              if "state_rows" in d["lb"]]
    assert groups and groups[-1]["lb"]["state_rows"] >= 1
    delta = {w: held.value(where=w) - before[w] for w in before}
    # 2 expert layers x top-3 x 2 occupied slots x the steps of the
    # dispatches that hold them: 5 tokens after the placement's first
    # are 2 dispatches of 4 steps (a slot is left between dispatches)
    assert delta["held"] + delta["absent"] == 2 * 3 * 2 * 8
    assert delta["held"] > 0 and delta["absent"] > 0
    state = METRICS.gauge("lm_server_state_bytes")
    s = spec["ssm"]
    assert state.value(kind="scan") == (
        2 * 4 * s["heads"] * s["head_dim"] * s["state"] * 4)
    assert state.value(kind="conv") == 2 * 4 * 3 * (64 + 2 * 2 * 16) * 4
    assert state.value(kind="kv") == 4 * 2 * 2 * 64 * 16 * 4


def test_windows_ride_the_readbacks_and_the_group_says_what_it_lays_out(
        monkeypatch):
    """Windows of 4 rows, so that a decode step (4 slots x top-3) loops as
    a prefill does: the steps' windows past the first are the routing's
    sixth number, the prefills' ride the next packed readback."""
    monkeypatch.setattr(G, "moe_window", lambda n, k, e, held: min(4, n * k))
    spec = _spec()
    be, params, _ = _backend(spec)
    ran = METRICS.counter("moe_windows_total")
    before = {w: ran.value(kind=w) for w in ("first", "further")}
    g0 = len(TRACER.loop_spans("lm_prefill_group"))
    n0 = len(TRACER.loop_spans("lm_step"))
    prompts = _prompts([6, 9])
    try:
        got = _serve(be, prompts, [6, 6])
        left = int(np.asarray(be.server._windows_dev)[0])
    finally:
        be.close()
    monkeypatch.undo()
    for p, toks in zip(prompts, got):
        _assert_the_references_choice(spec, params, p, 6, toks)
    groups = TRACER.loop_spans("lm_prefill_group")[g0:]
    steps = TRACER.loop_spans("lm_step")[n0:]
    assert groups and steps and left == 0
    for d in groups:  # top-3, one chunk a call, one window of 4 rows
        assert d["lb"]["moe_rows"] == d["lb"]["padded_tokens"] * 3
        assert d["lb"]["moe_rows_laid"] == 4
    first = ran.value(kind="first") - before["first"]
    assert first == 2 * len(groups) + 2 * 4 * len(steps)
    # 4 of 16 experts held: a quarter of the rows or so, 4 a window
    rows = sum(d["lb"]["moe_rows"] for d in groups)
    further = ran.value(kind="further") - before["further"]
    assert rows / 4 / 4 * 2 * 0.3 < further < rows / 4 / 4 * 2 * 3


# ----------------------------------------------------------------------
# what cannot hold the state refuses it
# ----------------------------------------------------------------------


def test_the_prefix_cache_refuses_the_state():
    be, _, _ = _backend(_spec())
    try:
        with pytest.raises(ValueError, match="cut by token"):
            be.server.enable_kv_cache(KVPrefixCache(1 << 20))
    finally:
        be.close()
    with pytest.raises(ValueError, match="cut by token"):
        LMBackend.from_spec(_spec(kv_cache_mb=1))


def test_submit_prefilled_refuses_the_state():
    be, _, cfg = _backend(_spec())
    try:
        with pytest.raises(ValueError, match="submit_prefilled"):
            be.server.submit_prefilled(
                np.arange(5, dtype=np.int32), {}, np.zeros(VOCAB), 4)
    finally:
        be.close()


def test_speculation_refuses_the_state():
    with pytest.raises(ValueError, match="speculative decoding"):
        LMBackend.from_spec(_spec(spec_k=2))


def test_diffusion_refuses_the_state():
    with pytest.raises(ValueError, match="block_causal"):
        lm_arch(_spec(attention_mask="block_causal", block_length=4,
                      denoising_steps=2, mask_token_id=96))
    params, cfg = _parts(_spec())
    masked = dataclasses.replace(
        cfg, attention_mask="block_causal", block_length=4)
    with pytest.raises(ValueError, match="block diffusion"):
        LS.LMServer(params, masked, max_slots=2, max_len=64,
                    diffusion=LS.BlockDiffusion(steps=2, mask_token_id=96))
    with pytest.raises(ValueError, match="roll a state-space"):
        G.batched_block_step(
            params, cfg, G.init_cache(cfg, 2, 64),
            jnp.zeros((2, 4), jnp.int32), jnp.zeros(2, jnp.int32))


def test_the_sharded_forms_refuse_the_state():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from dml_tpu.inference.lm_sharded import (
        LMPrefillBackend, PipelinedLMBackend,
    )

    params, cfg = _parts(_spec())
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    # a server finds its mesh on the tree it is handed
    placed = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
    with pytest.raises(ValueError, match="sharded forms"):
        LS.LMServer(placed, cfg, max_slots=2, max_len=64)
    with pytest.raises(ValueError, match="layer_pattern"):
        PipelinedLMBackend(
            _spec(), Mesh(np.asarray(jax.devices()[:2]), ("pp",)))
    with pytest.raises(ValueError, match="slab of K/V rows"):
        LMPrefillBackend(params, cfg)


@pytest.mark.parametrize("bad,match", [
    ({"layer_pattern": "EMX"}, "layer_pattern"),
    ({"layer_pattern": ""}, "layer_pattern"),
    ({"n_layers": 3}, "length"),
    ({"ssm": None}, "come together"),
    ({"layer_pattern": "E*E"}, "come together"),
    ({"ssm": {"heads": 8, "head_dim": 8}}, "heads, head_dim and state"),
    ({"ssm": {"heads": 8, "head_dim": 8, "state": 16, "dt_rank": 4}},
     "no other key"),
    ({"ssm": {"heads": 8, "head_dim": 8, "state": 16, "groups": 3}},
     "do not divide"),
    ({"ssm": {"heads": 8, "head_dim": 8, "state": 16, "conv_kernel": 1}},
     "state-space sizes"),
    ({"rope": "yarn"}, "unknown rope"),
    ({"norm_eps": 0.0}, "norm_eps"),
    ({"router": {"scoring": "tanh"}}, "router scoring"),
    ({"router": {"scoring": "softmax", "bias": True}}, "selection bias"),
    ({"router": {"scoring": "sigmoid", "scale": 0.0}}, "router scale"),
    ({"router": {"scoring": "sigmoid", "groups": 2}}, "router"),
    ({"activation": "gelu"}, "unknown activation"),
    ({"expert_latent": -1}, "below 0"),
    ({"num_experts": 0, "layer_pattern": "M*M"}, "without num_experts"),
    ({"layer_pattern": "M*M"}, "come together"),
])
def test_lm_arch_rejects_what_it_cannot_honour(bad, match):
    with pytest.raises(ValueError, match=match):
        lm_spec_parts(_spec(**bad))


def test_absent_keys_mean_what_the_tree_did_before():
    """A spec without the new keys declares the same config as before:
    rope on, eps 1e-6, a softmax router, SiLU, classic blocks."""
    _, cfg = lm_spec_parts({
        "vocab_size": 64, "d_model": 32, "n_heads": 4, "n_layers": 2,
        "num_experts": 4, "experts_per_token": 2, "dtype": "float32"})
    assert cfg.layer_pattern is None and cfg.ssm is None and cfg.rope
    assert cfg.norm_eps == G.RMS_EPS and cfg.activation == "silu"
    assert (cfg.router_scoring, cfg.router_scale) == ("softmax", 1.0)
    assert cfg.kinds == (None, None) and not cfg.has_state
