"""Weight distribution through the replicated store: train -> publish
-> fetch -> serve (the checkpoint/resume story the reference lacks —
its only persistence is SDFS files on disk, SURVEY §5)."""

import jax.numpy as jnp
import numpy as np

from _tinynet import ensure_tinynet
from dml_tpu.models.params_io import (
    init_variables,
    variables_from_bytes,
    variables_to_bytes,
)

ensure_tinynet()


def test_variables_bytes_roundtrip():
    spec = ensure_tinynet()
    v = init_variables(spec, seed=3, dtype=jnp.float32)
    data = variables_to_bytes(v)
    assert isinstance(data, bytes) and len(data) > 1000
    like = init_variables(spec, seed=0, dtype=jnp.float32)
    back = variables_from_bytes(data, like)
    a = v["params"]["predictions"]["kernel"]
    b = back["params"]["predictions"]["kernel"]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


async def test_publish_fetch_through_cluster(tmp_path):
    from test_jobs_sim import cluster

    from dml_tpu.inference.weights import fetch_weights, publish_weights

    async with cluster(3, tmp_path, 24100) as sim:
        await sim.wait_converged()
        u = sim.by_name("H3")
        store = sim.stores[u]
        spec = ensure_tinynet()
        v1 = init_variables(spec, seed=1, dtype=jnp.float32)
        r = await publish_weights(store, "TinyNet", v1)
        assert r["version"] == 1

        # second publish -> version 2; fetch latest and pinned
        v2 = init_variables(spec, seed=2, dtype=jnp.float32)
        r2 = await publish_weights(store, "TinyNet", v2)
        assert r2["version"] == 2

        got2 = await fetch_weights(store, "TinyNet", dtype=jnp.float32)
        got1 = await fetch_weights(store, "TinyNet", version=1, dtype=jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(got2["params"]["predictions"]["kernel"]),
            np.asarray(v2["params"]["predictions"]["kernel"]),
        )
        np.testing.assert_array_equal(
            np.asarray(got1["params"]["predictions"]["kernel"]),
            np.asarray(v1["params"]["predictions"]["kernel"]),
        )

        # a different node serves the fetched weights
        other = sim.stores[sim.by_name("H1")]
        from dml_tpu.inference.engine import InferenceEngine

        got = await fetch_weights(other, "TinyNet", dtype=jnp.float32)
        eng = InferenceEngine(dtype=jnp.float32)
        eng.load_model("TinyNet", variables=got, batch_size=4, warmup=False)
        imgs = np.random.RandomState(0).randint(0, 255, (4, 32, 32, 3), np.uint8)
        probs = eng.infer_arrays("TinyNet", imgs)
        assert probs.shape == (4, 1000) and np.all(np.isfinite(probs))


def test_spans_and_jsonl_logging(tmp_path):
    import json
    import logging

    from dml_tpu.observability import jsonl_logging
    from dml_tpu.tracing import Tracer

    spans = Tracer()  # the one span recorder; `profile spans` prints this
    with spans.loop_span("store_op_put"):
        pass
    with spans.loop_span("store_op_put"):
        pass
    s = spans.summary()
    assert s["store_op_put"]["count"] == 2
    assert s["store_op_put"]["mean_s"] >= 0

    log_path = tmp_path / "node.jsonl"
    handler = jsonl_logging(str(log_path))
    try:
        logging.getLogger("dml_tpu.test").info("hello %s", "world")
        handler.flush()
        line = json.loads(log_path.read_text().strip().splitlines()[-1])
        assert line["msg"] == "hello world" and line["level"] == "INFO"
    finally:
        logging.getLogger().removeHandler(handler)
