"""The program's LM serving stack as the system under test.

Built as an operator builds it: `LMBackend.from_spec(lm_spec)`, the whole
of the configuration's `lm_spec` block with `seed` set to the run's. One
thing is swapped inside that call: where `from_spec` asks the program's
`lm_spec_parts` for the weights, it gets the benchmark's values
(`references/<reference>.make_params`, one jitted call from the seed) in
the tree and the storage types the program's own init declares (read
with `jax.eval_shape`, so nothing is made twice). The plain reference can
then hold the same numbers without taking anything the program made, and
how the weights are stored stays the program's choice: a PR that changes
`lm_spec_parts`' `param_dtype` changes what this cell serves. Every
`JobService` of the in-process cluster shares this one backend: one
weight copy, one slot grid, one owner of the chip.

What the harness reads from here: the tokens each served prompt got and
when (a tap on the backend's own `on_token` contract, which is how the
benchmark counts throughput itself), and snapshots of the program's
counters for the per-layer readers.
"""

from __future__ import annotations

import gc
import hashlib
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple
from unittest import mock

import numpy as np

#: the server's placement policy that warm-up has to cover (lm_server.py
#: `_place_waiting`): prompt lengths are bucketed to powers of two from
#: 16; a group of one bucket <= 256 is padded to max_slots rows, a longer
#: one to the next power of two of its size
_SMALL_BUCKET_MAX = 256

#: packed readbacks (see `readback_sequences`): every sequence that up to
#: this many requests placed between two decode dispatches can make is run
#: in every set-up, and up to this many is compiled into the persistent
#: cache by the first run in a checkout
_READBACKS_WARM, _READBACKS_KEPT = 4, 7


def _bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _powers_to(n: int) -> List[int]:
    return [r for r in (1, 2, 4, 8, 16, 32, 64, 128) if r <= n]


def readback_sequences(slots: int, small: bool, large: bool,
                       requests: int) -> List[Tuple[int, ...]]:
    """Every sequence of padded group sizes that up to `requests` requests,
    placed between two decode dispatches in any number of rounds, can leave
    for the next packed readback: a group of a small bucket is `slots` rows
    whatever it holds, a group of a long bucket the next power of two of
    its k prompts (so r rows stand for more than r / 2 prompts)."""
    steps = []  # (rows, fewest prompts such a group holds)
    if small:
        steps.append((slots, 1))
    if large:
        steps += [(r, r // 2 + 1) for r in _powers_to(slots)
                  if not (small and r == slots)]
    out: List[Tuple[int, ...]] = []

    def grow(seq: Tuple[int, ...], left: int) -> None:
        for rows, need in steps:
            if need <= left:
                out.append(seq + (rows,))
                grow(seq + (rows,), left - need)

    grow((), requests)
    return out


class TokenTap:
    """Per served prompt: when its first and last token were delivered
    to the host and how many, on the host's monotonic clock."""

    def __init__(self) -> None:
        self.total = 0
        self.served: List[Dict[str, Any]] = []
        self._lock = threading.Lock()

    def open(self, path: str) -> Dict[str, Any]:
        rec = {"path": path, "first": None, "last": None, "n": 0}
        with self._lock:
            self.served.append(rec)
        return rec

    def note(self, rec: Dict[str, Any]) -> None:
        now = time.monotonic()
        if rec["first"] is None:
            rec["first"] = now
        rec["last"] = now
        rec["n"] += 1
        self.total += 1


class System:
    def __init__(self, config: Dict[str, Any], reference, seed: int,
                 variant: Optional[str] = None):
        import jax

        from dml_tpu.inference import lm_backend as program

        spec = {**config["lm_spec"], "seed": int(seed) % (2 ** 31)}
        if variant == "bf16":
            # the control of a configuration that states float32
            spec["dtype"], variant = "bfloat16", None
        if variant not in (None, "int8w"):
            raise ValueError(f"unknown variant {variant!r}")
        self.spec = spec
        self.name = config["model_name"]
        self.tap = TokenTap()
        self.weights_s = 0.0
        program_parts = program.lm_spec_parts

        def parts(s):
            if s is not spec:  # a draft model's spec: the program's own
                return program_parts(s)
            t0 = time.monotonic()
            made = {}

            def declared():
                params, made["cfg"] = program_parts(s)
                return params

            like = jax.eval_shape(declared)
            params = jax.tree.map(lambda x, d: x.astype(d.dtype),
                                  reference.make_params(s, seed), like)
            if variant == "int8w":
                # the control: the program's own weight-only int8 path
                from dml_tpu.inference.quantize import quantize_lm_params

                params = quantize_lm_params(params)
            jax.block_until_ready(params)
            self.weights_s = time.monotonic() - t0
            return params, made["cfg"]

        t0 = time.monotonic()
        with mock.patch.object(program, "lm_spec_parts", parts):
            self.be = program.LMBackend.from_spec(spec)
        self.backend_s = time.monotonic() - t0 - self.weights_s
        self.chunk = self.be.server.chunk
        self.slots = self.be.server.max_slots

    # -- the cluster's view -------------------------------------------

    def make_jobs(self, node, store):
        from dml_tpu.jobs.service import JobService

        jobs = JobService(node, store)
        jobs.register_lm(self.name, backend=self.backend, cost=self.be.cost())
        return jobs

    async def backend(self, model: str, paths: Sequence[str],
                      on_dispatch=None, on_token=None):
        """`LMBackend.backend` with the token tap spliced into its
        `on_token` contract (`on_token(local_path, text)` per delivered
        token)."""
        import asyncio

        recs = {p: self.tap.open(p) for p in paths}
        note = self.tap.note

        def tapped(path: str, text: str) -> None:
            note(recs[path])
            if on_token is not None:
                on_token(path, text)

        return await asyncio.to_thread(
            self.be.serve_files, paths, on_dispatch, tapped)

    # -- set-up ---------------------------------------------------------

    def warm(self, sizes: Sequence[Dict[str, int]]) -> Dict[str, Any]:
        """Run every program the traffic can reach once, on the backend's
        own thread-safe entry: the decode chunk, and each (bucket, rows)
        prefill group with its insert, sample and merge programs; then the
        packed readbacks."""
        lens = [s["prompt_tokens"] for s in sizes]
        rng = np.random.RandomState(0)
        vocab = int(self.spec["vocab_size"])
        buckets = sorted({min(_bucket(n), self.be.server.max_len)
                          for n in lens})
        groups = []
        for b in buckets:
            # the shortest and the longest prompt of a bucket compile to
            # the same program; take a length inside the traffic's range
            length = max(min(lens), min(b, max(lens)))
            rows = [1] if b <= _SMALL_BUCKET_MAX else _powers_to(self.slots)
            for k in rows:
                groups.append((b, k, length))
        for b, k, length in groups:
            prompts = [rng.randint(0, vocab, length).astype(np.int32)
                       for _ in range(k)]
            self.be.driver.serve(prompts, [2] * k)
        return {"prefill_groups": len(groups), "buckets": buckets,
                **self._warm_packed_readbacks(buckets)}

    def _warm_packed_readbacks(self, buckets: Sequence[int]) -> Dict[str, Any]:
        """`LMServer._chunk_step` reads back one eager `jnp.concatenate` of
        the chunk's tokens and the first tokens of every group placed since
        the last dispatch: one tiny program per SEQUENCE of group sizes,
        about 60 ms each to compile in set-up (0.4 s inside a window) and
        45 ms to load from the persistent cache, and no set-up can hold
        them all (PERF.md, PR 24 findings 2 and 7). The first run in a
        checkout compiles every sequence that up to `_READBACKS_KEPT` placed
        requests can make into the persistent cache (a marker in the
        checkout's own `.jax_cache` says it is done; one at a time, the
        chip's compiler runs no two at once); every run then runs those of
        up to `_READBACKS_WARM` requests. What the window meets beyond these
        it loads, or compiles if more requests were placed at once than were
        kept: the harness bounds those seconds (`cell.TOLERATED_SHARE`) and
        every run prints them."""
        import jax
        import jax.numpy as jnp

        from dml_tpu.compile_cache import DEFAULT_CACHE_DIR

        small = any(b <= _SMALL_BUCKET_MAX for b in buckets)
        large = any(b > _SMALL_BUCKET_MAX for b in buckets)
        toks = jnp.zeros(self.chunk * self.slots, jnp.int32)
        firsts = {k: jnp.zeros(k, jnp.int32) for k in _powers_to(self.slots)}

        def run(seq: Tuple[int, ...]) -> None:
            jnp.concatenate([toks] + [firsts[k] for k in seq])

        out: Dict[str, Any] = {}
        kept = readback_sequences(self.slots, small, large, _READBACKS_KEPT)
        # the marker lives beside what it vouches for: the benchmark's cache
        # is the checkout's own `.jax_cache` (cell.configure_compile_cache)
        key = hashlib.sha256(repr((
            jax.__version__, jax.config.jax_compilation_cache_dir,
            self.chunk, self.slots, kept)).encode()).hexdigest()[:16]
        marker = os.path.join(DEFAULT_CACHE_DIR,
                              f"benchmark_readbacks_{key}.done")
        if not os.path.exists(marker):
            t0 = time.monotonic()
            for seq in kept:
                run(seq)
            os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
            with open(marker, "w") as f:
                f.write(f"{len(kept)} packed readbacks\n")
            out.update(readbacks_kept=len(kept),
                       readbacks_kept_s=time.monotonic() - t0)
        t0 = time.monotonic()
        warm = readback_sequences(self.slots, small, large, _READBACKS_WARM)
        for seq in warm:
            run(seq)
        out.update(packed_readbacks=len(warm),
                   packed_readbacks_s=time.monotonic() - t0)
        return out

    # -- counters ---------------------------------------------------------

    def counters(self) -> Dict[str, float]:
        """The program's counters the per-layer readers take deltas of."""
        from dml_tpu.observability import METRICS

        def hist(name: str):
            rows = METRICS.histogram(name).items()
            return (sum(v[0] for _, v in rows), sum(v[1] for _, v in rows))

        out: Dict[str, float] = {"t": time.monotonic(),
                                 "tap_tokens": self.tap.total}
        for key, name in (("step", "lm_server_step_seconds"),
                          ("queue_wait", "lm_server_queue_wait_seconds"),
                          ("occupancy", "lm_server_slot_occupancy")):
            out[key + "_count"], out[key + "_sum"] = hist(name)
        out["steps_total"] = METRICS.counter("lm_server_steps_total").value()
        out["tokens_delivered"] = float(self.be.decode_tokens_total())
        return out

    def free(self) -> None:
        """Stop the backend and drop every device buffer it holds, so
        that the reference has the chip to itself."""
        self.be.close()
        srv = self.be.server
        srv.params = None
        srv.cache = None
        srv._cur_dev = srv._pos_dev = None
        srv._pending_first = []
        self.be = None
        gc.collect()
