import asyncio

import numpy as np
import pytest

from dml_tpu.inference import InferenceEngine

from _tinynet import ensure_tinynet


@pytest.fixture(scope="module")
def engine():
    ensure_tinynet()
    eng = InferenceEngine()
    eng.load_model("TinyNet", batch_size=4)
    return eng


def test_load_and_cost_constants(engine):
    c = engine.cost_constants("TinyNet")
    assert c["batch_size"] == 4
    assert c["per_query"] > 0 and c["first_query"] > 0
    assert engine.loaded_models == ["TinyNet"]


def test_infer_arrays_pads_and_chunks(engine):
    imgs = np.random.default_rng(0).integers(0, 255, (5, 32, 32, 3), np.uint8)
    probs = engine.infer_arrays("TinyNet", imgs)
    assert probs.shape == (5, 1000)
    np.testing.assert_allclose(probs.sum(-1), 1.0, rtol=1e-4)
    # padded results must equal unpadded results image-for-image
    probs1 = engine.infer_arrays("TinyNet", imgs[:1])
    np.testing.assert_allclose(probs[:1], probs1, rtol=2e-4, atol=1e-6)
    assert engine.infer_arrays("TinyNet", imgs[:0]).shape == (0, 1000)


def test_infer_files_and_async(engine, tmp_path):
    from PIL import Image

    files = []
    rng = np.random.default_rng(1)
    for i in range(3):
        p = tmp_path / f"img{i}.jpeg"
        Image.fromarray(rng.integers(0, 255, (40, 40, 3), np.uint8)).save(p)
        files.append(str(p))
    res = engine.infer_files("TinyNet", files)
    assert res.files == files
    assert len(res.top5) == 3 and len(res.top5[0]) == 5
    d = res.to_json_dict()
    assert set(d) == set(files)
    assert {"wnid", "label", "score"} == set(d[files[0]][0])

    res2 = asyncio.run(engine.infer_files_async("TinyNet", files))
    assert res2.files == files


def test_set_batch_size(engine):
    engine.set_batch_size("TinyNet", 2)
    assert engine.cost_constants("TinyNet")["batch_size"] == 2
    imgs = np.zeros((3, 32, 32, 3), np.uint8)
    assert engine.infer_arrays("TinyNet", imgs).shape == (3, 1000)
    engine.set_batch_size("TinyNet", 4)


def test_unloaded_model_raises(engine):
    with pytest.raises(KeyError):
        engine.cost_constants("InceptionV3")


def test_unload_and_memory_stats(engine):
    stats = engine.memory_stats()
    assert "TinyNet" in stats and stats["TinyNet"]["param_mb"] > 0
    assert engine.unload_model("TinyNet")
    assert "TinyNet" not in engine.loaded_models
    assert not engine.unload_model("TinyNet")  # already gone
    # reload works after eviction
    engine.load_model("TinyNet", batch_size=4, warmup=False)
    assert engine.loaded_models == ["TinyNet"]


def test_evicted_explicit_weights_refuse_silent_reinit(engine):
    import jax

    # load explicit weights, evict, then a lazy load must refuse
    lm = engine.load_model("TinyNet", batch_size=4, warmup=False)
    explicit = jax.device_get(lm.variables)
    engine.load_model("TinyNet", variables=explicit, warmup=False)
    assert engine.unload_model("TinyNet")
    with pytest.raises(RuntimeError, match="explicit weights"):
        engine.load_model("TinyNet", warmup=False)
    # reloading explicit weights clears the guard
    engine.load_model("TinyNet", variables=explicit, warmup=False)
    assert engine.loaded_models == ["TinyNet"]


def test_reload_with_new_batch_size_keeps_explicit_weights(engine):
    import jax
    import numpy as np

    lm = engine.load_model("TinyNet", batch_size=4, warmup=False)
    explicit = jax.device_get(lm.variables)
    engine.load_model("TinyNet", variables=explicit, warmup=False)
    # reshape reload without passing weights: must keep the explicit ones
    lm2 = engine.load_model("TinyNet", batch_size=2, warmup=False)
    assert lm2.batch_size == 2 and lm2.explicit_weights
    a = jax.tree_util.tree_leaves(jax.device_get(lm2.variables))
    b = jax.tree_util.tree_leaves(explicit)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_infer_arrays_nowait_matches_sync(engine):
    """The dispatch-pipelining handle returns the same probs as the
    synchronous path, including padding/chunking and empty input; and
    several in-flight handles drain correctly in any order (the C4
    pipelined dispatch pattern)."""
    rng = np.random.RandomState(7)
    imgs = rng.randint(0, 255, (6, 32, 32, 3), dtype=np.uint8)
    sync = engine.infer_arrays("TinyNet", imgs)
    h = engine.infer_arrays_nowait("TinyNet", imgs)
    np.testing.assert_allclose(h(), sync, rtol=1e-6)
    assert engine.infer_arrays_nowait("TinyNet", imgs[:0])().shape == (0, 1000)
    # overlapping handles, drained LIFO
    batches = [rng.randint(0, 255, (3, 32, 32, 3), np.uint8) for _ in range(3)]
    handles = [engine.infer_arrays_nowait("TinyNet", b) for b in batches]
    for b, h in reversed(list(zip(batches, handles))):
        np.testing.assert_allclose(
            h(), engine.infer_arrays("TinyNet", b), rtol=1e-6
        )


def test_choose_dispatch_mode_picks_faster_both_ways(engine):
    """The adaptive dispatch selection (VERDICT r4 item 3) must pick
    whichever mode the measurement says is faster — exercised BOTH
    ways by steering the two paths' speed, plus the per-(model, bs)
    cache."""
    import time as _time

    sample = np.zeros((8, 32, 32, 3), np.uint8)
    orig_sync = engine.infer_arrays
    orig_nowait = engine.infer_arrays_nowait
    calls = {"sync": 0, "nowait": 0}

    def slow_sync(name, imgs):
        calls["sync"] += 1
        _time.sleep(0.01)
        return orig_sync(name, imgs)

    def slow_nowait(name, imgs):
        calls["nowait"] += 1
        h = orig_nowait(name, imgs)

        def wrapped():
            _time.sleep(0.01)
            return h()

        return wrapped

    round_spec = [("TinyNet", sample), ("TinyNet", sample)]
    try:
        # pipelined path slower -> engine must choose sync
        engine.infer_arrays_nowait = slow_nowait
        assert engine.choose_dispatch_mode(round_spec) == "sync"
        engine._dispatch_mode.clear()
        engine.infer_arrays_nowait = orig_nowait

        # sync path slower -> engine must choose pipelined
        engine.infer_arrays = slow_sync
        assert engine.choose_dispatch_mode(round_spec) == "pipelined"
        # cached: a second ask re-measures nothing
        n_sync = calls["sync"]
        assert engine.choose_dispatch_mode(round_spec) == "pipelined"
        assert calls["sync"] == n_sync
        # ... but the entry EXPIRES: host conditions drift, so a
        # long-lived server must re-measure (ttl_s=0 forces it)
        engine.infer_arrays = orig_sync
        engine.infer_arrays_nowait = slow_nowait
        assert (
            engine.choose_dispatch_mode(round_spec, ttl_s=0.0) == "sync"
        )
    finally:
        engine.infer_arrays = orig_sync
        engine.infer_arrays_nowait = orig_nowait
        engine._dispatch_mode.clear()
