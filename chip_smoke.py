#!/usr/bin/env python3
"""chip_smoke.py — the cluster serving path, once, on the chip.

    python chip_smoke.py [--seed N]          # one TPU chip
    python chip_smoke.py --multichip         # four chips: tp=4 serving only

One process drives the system's main path — ingress -> scheduler ->
worker -> engine -> store — through the entry points a user calls, at
the full width of the models the repo benchmarks, and checks what comes
out against the repo's own references:

- image: a 4-node localhost cluster (`cluster.chaos.LocalCluster`) whose
  JobServices share ONE `InferenceEngine` on the chip serves real
  ResNet50 (224x224, 1000 classes, bf16, batch 32): a `submit_job` of 64
  queries over seeded JPEGs in the replicated store, then per-request
  `ingress.request` calls. Top-5 of every file must equal a direct
  `engine.infer_files`; every probability row is finite and sums to 1.
- lm: the same cluster with `LMBackend.from_spec` at the benchmarked LM
  width (vocab 32,000, d_model 1,024, 16 heads, GQA-4, 12 layers, d_ff
  4,096, bf16, max_len 4,096, 8 slots): a job of 8 prompts x 32 new
  tokens, one streamed ingress request, then the same job again with
  `LMServer.enable_spec_decode` on (self-draft). Every served sequence
  must be a greedy decode of the repo's plain reference forward up to
  near-ties (`NEAR_TIE_MARGIN`), and how many equal
  `inference.generate.generate` token for token is reported.
- kernels: the decode cache-attention kernel (bf16 and int8 caches) at
  the LM's shapes and `fused_normalize` over a ragged row block against
  their jnp references — the Mosaic side of switches the LM config
  above does not take (grouped bf16 caches stay on the einsum).
- `--multichip` runs only `sharded_lm_backend` on a tp=4 mesh against
  the single-device `LMBackend`: same 8 prompts, both checked against
  the reference as above, and each device holding a quarter of the
  parameter and KV-cache bytes.

It refuses to run without a TPU, any phase that raises, hangs or fails
a comparison makes the exit non-zero, and the last stdout line — printed
only when every phase passed — is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
Weights, images and prompts are made from `--seed`; nothing is read
from outside the checkout. No rate is claimed from anything it prints.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import faulthandler
import json
import os
import sys
import tempfile
import time
from typing import Any, Dict, List, Sequence

import numpy as np

#: the smoke's LM: 198M parameters, grouped-query attention, bfloat16
LM_SPEC: Dict[str, Any] = {
    "name": "SmokeLM", "vocab_size": 32000, "d_model": 1024,
    "n_heads": 16, "n_kv_heads": 4, "n_layers": 12, "d_ff": 4096,
    "dtype": "bfloat16", "max_new_tokens": 32, "max_slots": 8,
    "max_len": 4096,  # "seed" comes from --seed
}
IMAGE_MODEL, IMAGE_BATCH = "ResNet50", 32
#: the whole run's own limit, inside the driver's 1200 s
RUN_LIMIT_S = 1100


def say(what: str, **fields: Any) -> None:
    print(json.dumps({"smoke": what, **fields}, default=str), flush=True)


class CompileMeter:
    """Seconds JAX spent compiling (or fetching from the persistent
    cache) and cache hits/misses, from JAX's own monitoring events."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event: str, secs: float, **_: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _on_event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> Dict[str, Any]:
        return {"compile_s": round(self.seconds, 2),
                "cache_hits": self.hits, "cache_misses": self.misses}


@contextlib.asynccontextmanager
async def smoke_cluster(root: str, base_port: int, make_jobs):
    """The 4-node localhost cluster the bench and chaos suites use
    (scaled-down SWIM timing), every node with its RequestRouter."""
    from dml_tpu.cluster.chaos import LocalCluster
    from dml_tpu.config import Timing

    cluster = LocalCluster(
        4, root, base_port,
        timing=Timing(ping_interval=0.2, ack_timeout=0.3,
                      cleanup_time=1.0, leader_rpc_timeout=10.0),
        make_jobs=make_jobs, with_ingress=True,
    )
    try:
        await cluster.start()
        await cluster.wait_for(
            cluster.converged, 20.0,
            f"smoke cluster convergence (stale process on ports "
            f"{base_port - 1}-{base_port + 3}?)",
        )
        yield cluster
    finally:
        await cluster.stop()


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ----------------------------------------------------------------------
# image phase
# ----------------------------------------------------------------------


async def image_phase(
    engine, model: str, batch: int, *, seed: int, root: str,
    base_port: int, n_files: int = 32, n_queries: int = 64,
    n_requests: int = 4, image_hw: int = 256,
) -> Dict[str, Any]:
    from PIL import Image

    from dml_tpu.jobs.service import JobService
    from dml_tpu.models.preprocess import load_images
    from dml_tpu.native.loader import native_available

    t0 = time.monotonic()
    lm = await asyncio.to_thread(
        engine.load_model, model, batch_size=batch, seed=seed
    )
    load_s = time.monotonic() - t0
    rng = np.random.RandomState(seed)
    names = [f"img_{i:02d}.jpeg" for i in range(n_files)]
    local = {}
    for name in names:
        local[name] = os.path.join(root, name)
        Image.fromarray(
            rng.randint(0, 255, (image_hw, image_hw, 3), np.uint8)
        ).save(local[name])

    def make_jobs(node, store):
        # one SHARED engine across the co-located services: one weights
        # copy, one compile, one owner of the chip
        return JobService(node, store, engine=engine)

    async with smoke_cluster(root, base_port, make_jobs) as cluster:
        client = cluster.client()
        for name in names:
            await client.store.put(local[name], name)
        await client.jobs.set_batch_size(model, batch)
        job_id = await client.jobs.submit_job(model, n_queries)
        done = await client.jobs.wait_job(job_id, timeout=300.0)
        _require(done["total_queries"] == n_queries,
                 f"job answered {done['total_queries']} of {n_queries}")
        merged = await client.jobs.get_output(
            job_id, os.path.join(root, "final.json")
        )
        asked = names[:n_requests]
        answered = {}
        for name in asked:
            term = await client.ingress.request(
                model, slo="batch", store_name=name, timeout=120.0
            )
            _require(bool(term.get("ok")), f"ingress {name}: {term}")
            answered[name] = term["result"]

    # the reference: the same engine, called directly, on the same files
    paths = [local[n] for n in names]
    direct = engine.infer_files(model, paths).to_json_dict()
    probs = engine.infer_arrays(model, load_images(paths, lm.spec.input_size))
    _require(probs.shape == (n_files, lm.num_classes),
             f"probability shape {probs.shape}")
    _require(bool(np.isfinite(probs).all()), "non-finite probabilities")
    sums = probs.astype(np.float64).sum(axis=1)
    _require(bool(np.allclose(sums, 1.0, atol=1e-2)),
             f"probability rows sum to [{sums.min()}, {sums.max()}]")
    _require(sorted(merged) == names,
             f"job output holds {len(merged)} files, wanted {n_files}")
    for name in names:
        _require(len(merged[name]) == 5, f"{name}: not a top-5")
        _require(merged[name] == direct[local[name]],
                 f"job top-5 of {name} differs from the direct engine: "
                 f"{merged[name]} vs {direct[local[name]]}")
    for name in asked:
        _require(answered[name] == direct[local[name]],
                 f"ingress top-5 of {name} differs from the direct "
                 f"engine: {answered[name]} vs {direct[local[name]]}")
    return {
        "model": model, "batch": batch, "load_and_compile_s": round(load_s, 2),
        "job_queries": n_queries, "files": n_files,
        "ingress_requests": len(asked),
        "answers_equal_direct_engine": True,
        "native_jpeg_loader": native_available(),
        "forward_has_tpu_custom_call": engine.forward_has_kernel(model),
    }


# ----------------------------------------------------------------------
# LM phase
# ----------------------------------------------------------------------


def make_prompts(
    seed: int, vocab: int, n: int, lengths: Sequence[int]
) -> List[np.ndarray]:
    """`n` seeded prompts whose lengths cycle through `lengths` — a few
    distinct lengths bound the reference's compilations (one `generate`
    program per length) while still crossing prefill buckets."""
    rng = np.random.RandomState(seed)
    return [
        rng.randint(0, vocab, lengths[i % len(lengths)]).astype(np.int32)
        for i in range(n)
    ]


#: How far below the reference's best logit a served token's reference
#: logit may sit. Greedy decoding is exact in logic — on the chip, float32
#: at HIGHEST matmul precision serves tokens identical to `generate` — but
#: bf16 (and TPU-default float32) matmuls round differently in programs of
#: different shapes (bucket-padded prefill, a 4,096-row cache, a 5-token
#: verify step), so where the reference itself hardly prefers one token
#: the argmax can fall either way. Measured at this width on a v5e (PR 22
#: chip run): every divergence from `generate` sat at a reference top-2
#: margin <= 0.006, against a median top-2 margin of 0.108 and logits of
#: unit spread; a wrong token would sit ~4 below.
NEAR_TIE_MARGIN = 0.05


class GreedyReference:
    """The repo's plain references for one LM: `generate` per prompt in
    isolation, and the training forward (`models.transformer`, full-matrix
    float32 attention, no cache, no kernel) that scores any served
    sequence position by position."""

    def __init__(self, params, cfg, prompts, new_tokens: int):
        import jax

        from dml_tpu.inference.generate import generate
        from dml_tpu.models.transformer import TransformerLM

        self.params, self.cfg, self.prompts = params, cfg, prompts
        model = TransformerLM(
            vocab_size=cfg.vocab_size, d_model=cfg.d_model,
            n_heads=cfg.n_heads, n_layers=cfg.n_layers, d_ff=cfg.d_ff,
            dtype=cfg.dtype, n_kv_heads=cfg.n_kv_heads,
        )
        self._forward = jax.jit(
            lambda p, tokens: model.apply({"params": p}, tokens))
        gen = jax.jit(generate, static_argnums=(1, 3))
        self.generated = [
            [int(t) for t in np.asarray(
                gen(params, cfg, p[None], new_tokens))[0]]
            for p in prompts
        ]

    def margin(self, i: int, tokens: Sequence[int]) -> float:
        """The most the reference prefers another token over any of
        `tokens`, served for prompt `i`, given the tokens before it —
        0.0 when every one is the reference's own argmax."""
        prompt = self.prompts[i]
        full = np.concatenate([prompt, np.asarray(tokens, np.int32)])
        logits = np.asarray(self._forward(self.params, full[None]))[0]
        rows = logits[prompt.size - 1:-1]  # row t scores token t + 1
        chosen = rows[np.arange(len(tokens)), np.asarray(tokens)]
        return float(np.max(rows.max(axis=-1) - chosen))

    def check(self, served: Sequence[Sequence[int]], what: str,
              first: int = 0) -> Dict[str, Any]:
        """Every served sequence is a greedy decode of the reference up
        to near-ties; reports how many equal `generate` token for
        token. `served[j]` answers prompt `first + j`."""
        bad, worst, equal = [], 0.0, 0
        for j, tokens in enumerate(served):
            i = first + j
            want = self.generated[i]
            equal += list(tokens) == want
            m = self.margin(i, tokens) if len(tokens) == len(want) else None
            if m is None or not m <= NEAR_TIE_MARGIN:
                bad.append(f"prompt {i}: served {list(tokens)} (reference "
                           f"margin {m}), generate gives {want}")
            else:
                worst = max(worst, m)
        _require(not bad, f"{what}: not a greedy decode of the reference "
                 f"within {NEAR_TIE_MARGIN} — " + "; ".join(bad))
        return {"sequences": len(served), "equal_generate": equal,
                "worst_reference_margin": round(worst, 5)}


async def lm_phase(
    lm_spec: Dict[str, Any], *, seed: int, root: str, base_port: int,
    n_prompts: int = 8, prompt_lengths: Sequence[int] = (12, 24, 40),
    spec_k: int = 4,
) -> Dict[str, Any]:
    from dml_tpu.inference.generate import uses_decode_kernel
    from dml_tpu.inference.lm_backend import LMBackend, write_prompt_file
    from dml_tpu.jobs.service import JobService

    name = lm_spec["name"]
    new_tokens = int(lm_spec["max_new_tokens"])
    t0 = time.monotonic()
    be = await asyncio.to_thread(
        LMBackend.from_spec, {**lm_spec, "seed": seed}
    )
    build_s = time.monotonic() - t0
    try:
        ref = await asyncio.to_thread(
            GreedyReference, be.server.params, be.cfg,
            make_prompts(seed, be.cfg.vocab_size, n_prompts, prompt_lengths),
            new_tokens,
        )
        files = [f"prompt_{i}.tokens.txt" for i in range(n_prompts)]

        def make_jobs(node, store):
            jobs = JobService(node, store)
            jobs.register_lm(name, backend=be.backend, cost=be.cost())
            return jobs

        async def job_tokens(client) -> List[List[int]]:
            job_id = await client.jobs.submit_job(name, n_prompts)
            done = await client.jobs.wait_job(job_id, timeout=600.0)
            _require(done["total_queries"] == n_prompts,
                     f"LM job answered {done['total_queries']}")
            merged = await client.jobs.get_output(
                job_id, os.path.join(root, f"lm_{job_id}.json")
            )
            _require(sorted(merged) == sorted(files),
                     f"LM job output holds {sorted(merged)}")
            return [merged[f]["tokens"] for f in files]

        async with smoke_cluster(root, base_port, make_jobs) as cluster:
            client = cluster.client()
            for f, p in zip(files, ref.prompts):
                path = os.path.join(root, f)
                write_prompt_file(path, p)
                await client.store.put(path, f)

            plain = await job_tokens(client)
            job = ref.check(plain, "LM job")

            rid = await client.ingress.submit(
                name, slo="batch", store_name=files[0], stream=True,
                timeout=30.0,
            )
            chunks = await client.ingress.stream_text(rid, timeout=300.0)
            term = await client.ingress.wait(rid, timeout=300.0)
            _require(bool(term.get("ok")), f"streamed request: {term}")
            streamed = [int(t) for t in "".join(chunks).split()]
            _require(streamed == term["result"]["tokens"],
                     f"the token stream {streamed} is not the terminal's "
                     f"result {term['result']['tokens']}")
            stream = ref.check([streamed], "streamed request")

            # speculative decoding with the target as its own draft:
            # a real device proposer, and every verify round commits
            # through PR 17's batched_verify_step
            be.server.enable_spec_decode(
                spec_k, draft_params=be.server.params, draft_cfg=be.cfg
            )
            spec_tokens = await job_tokens(client)
            spec_job = ref.check(
                spec_tokens, "LM job with speculative decoding")
            spec = be.spec_stats()
            _require(spec["enabled"] and spec["rounds"] > 0,
                     f"speculative decoding did not run: {spec}")
        return {
            "model": name, "build_s": round(build_s, 2),
            "prompts": n_prompts, "new_tokens_per_prompt": new_tokens,
            "tokens_generated": int(be.decode_tokens_total()),
            "near_tie_margin": NEAR_TIE_MARGIN,
            "job": job, "streamed_request": stream,
            "spec_job": {
                **spec_job,
                "equal_plain_job": sum(
                    a == b for a, b in zip(spec_tokens, plain)),
                **{k: spec[k] for k in ("k", "rounds", "proposed",
                                        "accepted", "accept_rate")},
            },
            "has_tpu_custom_call": be.server.kernel_report(),
            "decode_kernel_by_policy": uses_decode_kernel(),
        }
    finally:
        be.close()


# ----------------------------------------------------------------------
# kernels the LM config above does not reach
# ----------------------------------------------------------------------


def kernel_phase(seed: int, *, batch: int = 8, heads: int = 16,
                 kv_heads: int = 4, head_dim: int = 64,
                 context: int = 4096, image_hw: int = 299) -> Dict[str, Any]:
    """Compiled (not interpreted) kernels against their jnp references."""
    import jax
    import jax.numpy as jnp

    from dml_tpu.inference.generate import _kv_quantize
    from dml_tpu.models.preprocess import normalize_on_device
    from dml_tpu.ops.decode_attention import decode_attention
    from dml_tpu.ops.preprocess import fused_normalize

    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (batch, 1, heads, head_dim), jnp.float32)
    shape = (batch, kv_heads, context, head_dim)
    ck = jax.random.normal(ks[1], shape, jnp.float32).astype(jnp.bfloat16)
    cv = jax.random.normal(ks[2], shape, jnp.float32).astype(jnp.bfloat16)
    pos = jax.random.randint(ks[3], (batch,), 1, context).astype(jnp.int32)

    def einsum_ref(q, ck, cv, pos):
        grp = heads // kv_heads
        valid = jnp.arange(context)[None, :] <= pos[:, None]
        qg = q.reshape(batch, 1, kv_heads, grp, head_dim)
        hi = jax.lax.Precision.HIGHEST
        s = jnp.einsum("bqkgd,bktd->bkgqt", qg, ck.astype(jnp.float32),
                       precision=hi) * head_dim ** -0.5
        s = jnp.where(valid[:, None, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqt,bktd->bqkgd", p, cv.astype(jnp.float32),
                       precision=hi)
        return o.reshape(batch, 1, heads, head_dim)

    def max_err(a, b) -> float:
        return float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32))))

    out: Dict[str, Any] = {}
    err = max_err(jax.jit(decode_attention)(q, ck, cv, pos + 1),
                  jax.jit(einsum_ref)(q, ck, cv, pos))
    out["decode_attention_bf16_max_err"] = err
    _require(err < 0.05, f"decode kernel (bf16 cache) off by {err}")

    kq, ksc = _kv_quantize(ck)
    vq, vsc = _kv_quantize(cv)
    got = jax.jit(
        lambda q, k, s, v, t, p: decode_attention(
            q, k, v, p + 1, k_scale=s, v_scale=t)
    )(q, kq, jnp.swapaxes(ksc, 2, 3), vq, jnp.swapaxes(vsc, 2, 3), pos)
    err = max_err(got, jax.jit(einsum_ref)(
        q, kq.astype(jnp.float32) * ksc, vq.astype(jnp.float32) * vsc, pos))
    out["decode_attention_int8_max_err"] = err
    _require(err < 0.05, f"decode kernel (int8 cache) off by {err}")

    # 299 rows = one 256-row block + a ragged 43-row one
    img = jax.random.randint(
        ks[0], (batch, image_hw, image_hw, 3), 0, 256, jnp.int32
    ).astype(jnp.uint8)
    # bound: one bf16 step at the mode's largest magnitude
    for mode, ulp in (("tf", 2.0 ** -8), ("caffe", 1.0)):
        got = jax.jit(lambda x, m=mode: fused_normalize(x, m))(img)
        ref = jax.jit(
            lambda x, m=mode: normalize_on_device(x, m, jnp.bfloat16))(img)
        err = max_err(got, ref)
        out[f"fused_normalize_{mode}_max_err"] = err
        _require(err <= ulp,
                 f"fused_normalize {mode} differs from jnp by {err}")
    return out


# ----------------------------------------------------------------------
# four chips: tp-sharded weight-resident serving
# ----------------------------------------------------------------------


def _require_spread(tree, devices, what: str) -> Dict[str, Any]:
    """Every device holds about 1/len(devices) of `tree`'s bytes."""
    import jax

    leaves = jax.tree_util.tree_leaves(tree)
    total = sum(leaf.nbytes for leaf in leaves)
    held: Dict[int, int] = {}
    for leaf in leaves:
        for shard in leaf.addressable_shards:
            held[shard.device.id] = (
                held.get(shard.device.id, 0) + shard.data.nbytes
            )
    share = {d.id: held.get(d.id, 0) / total for d in devices}
    fair = 1.0 / len(devices)
    _require(
        all(0.8 * fair <= s <= 1.25 * fair for s in share.values()),
        f"{what} not spread over the devices: shares {share}",
    )
    return {"total_mb": round(total / 2**20, 1),
            "share_per_device": {k: round(v, 3) for k, v in share.items()}}


def multichip_phase(
    lm_spec: Dict[str, Any], *, seed: int, root: str, tp: int = 4,
    n_prompts: int = 8, prompt_lengths: Sequence[int] = (12, 24, 40),
) -> Dict[str, Any]:
    import jax

    from dml_tpu.config import MeshSpec
    from dml_tpu.inference.lm_backend import LMBackend, write_prompt_file
    from dml_tpu.inference.lm_sharded import sharded_lm_backend
    from dml_tpu.parallel.mesh import make_mesh

    devices = jax.devices()[:tp]
    _require(len(devices) == tp, f"--multichip needs {tp} devices")
    mesh = make_mesh(MeshSpec(dp=1, tp=tp), devices=devices)
    spec = {**lm_spec, "seed": seed}
    prompts = make_prompts(
        seed, int(spec["vocab_size"]), n_prompts, prompt_lengths)
    paths = []
    for i, p in enumerate(prompts):
        paths.append(os.path.join(root, f"prompt_{i}.tokens.txt"))
        write_prompt_file(paths[-1], p)

    sharded = sharded_lm_backend(spec, mesh)
    try:
        got, _, _ = sharded.serve_files(paths)
        params = _require_spread(
            sharded.server.params, devices, "tp-sharded parameters")
        cache = _require_spread(sharded.server.cache, devices, "KV cache")
        kernels = sharded.server.kernel_report()
    finally:
        sharded.close()
    single = LMBackend.from_spec(spec)
    try:
        want, _, _ = single.serve_files(paths)
        ref = GreedyReference(
            single.server.params, single.cfg, prompts,
            int(spec["max_new_tokens"]),
        )
        on_mesh = [got[p]["tokens"] for p in paths]
        on_one = [want[p]["tokens"] for p in paths]
        report = {
            f"tp{tp}": ref.check(on_mesh, f"tp={tp} serving"),
            "one_device": ref.check(on_one, "one-device serving"),
        }
    finally:
        single.close()
    return {
        "mesh": dict(mesh.shape), "prompts": n_prompts,
        "near_tie_margin": NEAR_TIE_MARGIN, **report,
        "equal_one_device": sum(a == b for a, b in zip(on_mesh, on_one)),
        "param_bytes": params, "kv_cache_bytes": cache,
        "has_tpu_custom_call": kernels,
    }


# ----------------------------------------------------------------------


def main(argv: Sequence[str] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--multichip", action="store_true",
                    help="run only tp=4 sharded LM serving (four chips)")
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    from dml_tpu.compile_cache import configure_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is {dev} "
              f"(platform {dev.platform!r})", file=sys.stderr)
        return 1
    cache_dir = configure_compile_cache()
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except (ImportError, AttributeError):
        libtpu_version = "unknown"
    say("start", jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu_version, device_kind=dev.device_kind,
        devices=len(jax.devices()), seed=args.seed,
        compile_cache_dir=cache_dir, multichip=args.multichip)

    # a hung run (a wedged chip, a lost datagram loop) must end with a
    # non-zero exit inside the driver's limit, whatever thread hangs
    faulthandler.dump_traceback_later(RUN_LIMIT_S, exit=True)
    meter = CompileMeter()

    def run(name: str, thunk) -> Dict[str, Any]:
        before, t0 = meter.snapshot(), time.monotonic()
        report = thunk()
        after = meter.snapshot()
        say(name, wall_s=round(time.monotonic() - t0, 2),
            **{k: round(after[k] - before[k], 2) for k in after}, **report)
        return report

    with tempfile.TemporaryDirectory(prefix="dml_tpu_smoke_") as root:
        def sub(name: str) -> str:
            path = os.path.join(root, name)
            os.makedirs(path)
            return path

        if args.multichip:
            run("multichip", lambda: multichip_phase(
                LM_SPEC, seed=args.seed, root=sub("multichip")))
        else:
            from dml_tpu.inference.engine import InferenceEngine

            engine = InferenceEngine()  # bfloat16, first visible device
            image = run("image", lambda: asyncio.run(image_phase(
                engine, IMAGE_MODEL, IMAGE_BATCH, seed=args.seed,
                root=sub("image"), base_port=29611)))
            engine.unload_model(IMAGE_MODEL)
            lm = run("lm", lambda: asyncio.run(lm_phase(
                LM_SPEC, seed=args.seed, root=sub("lm"), base_port=29631)))
            run("kernels", lambda: kernel_phase(args.seed))
            # the TPU side of every silent switch was the one taken
            _require(image["forward_has_tpu_custom_call"],
                     "the engine forward holds no Pallas kernel")
            _require(image["native_jpeg_loader"],
                     "JPEGs were decoded by PIL, not the native loader")
            _require(lm["has_tpu_custom_call"]["prefill"],
                     "the LM prefill holds no flash kernel")
            _require(lm["has_tpu_custom_call"]["decode"]
                     == lm["decode_kernel_by_policy"],
                     "the decode step's kernel is not what "
                     "generate.uses_decode_kernel says")

    peak = dev.memory_stats().get("peak_bytes_in_use")
    say("done", peak_bytes_in_use=peak, **meter.snapshot())
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
