"""The program's LM serving stack as the system under test, for a
block-diffusion configuration: `backends/lm.py`'s `System` (built as an
operator builds it, `LMBackend.from_spec(lm_spec)`, with the benchmark's
weight VALUES in the tree the program declares) with what differs on this
serve loop:

- before any weight is made, the tree the program's `lm_spec_parts`
  declares for the configuration is set against the reference's. A
  program that does not know the architecture ignores the keys it does
  not know and declares another tree (a dense decoder): the run stops
  there, at once, instead of serving another model;
- warm-up runs each (bucket, rows) prefill group the traffic can form
  and the diffusion dispatch once. A group of a long bucket is padded to
  the next power of two of its prompts, and no placement round can hold
  more prompts of one bucket than the copies of the item pool in flight
  hold (the configuration's `warm_pool_copies`: a closed loop of two jobs
  keeps two); larger groups are not compiled (32 x 2048 tokens through
  128 experts is 3.7 GiB of transients that no window meets). There are
  no packed readbacks of varying arity to keep (the dispatch reads back
  ONE fixed shape, packed on the device);
- the counter snapshot adds the dispatch's own counters (forwards, tokens
  fixed, blocks committed) and the expert routing's.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

import numpy as np

from benchmark.harness import manifest as mf

_lm = mf.load_module("backends", "lm")


class UnknownArchitecture(RuntimeError):
    pass


def _declared_tree(spec: Dict[str, Any]):
    import jax

    from dml_tpu.inference import lm_backend as program

    return jax.eval_shape(lambda: program.lm_spec_parts(spec)[0])


class System(_lm.System):
    def __init__(self, config: Dict[str, Any], reference, seed: int,
                 variant: Optional[str] = None):
        import jax

        spec = config["lm_spec"]
        declared = jax.tree.map(lambda s: tuple(s.shape),
                                _declared_tree(spec))
        if declared != reference.param_shapes(spec):
            got = sorted(declared.get("block_0", {}))
            raise UnknownArchitecture(
                f"the program's lm_spec_parts declares another model for "
                f"{config['name']!r} (its first block holds {got}): it "
                f"does not know this architecture's lm_spec keys")
        super().__init__(config, reference, seed, variant=variant)
        self.pool_copies = int(config.get("warm_pool_copies", 1))
        if self.be.server.diffusion is None:
            raise UnknownArchitecture(
                f"{config['name']!r} was built without a block-diffusion "
                f"dispatch")

    def warm(self, sizes: Sequence[Dict[str, int]]) -> Dict[str, Any]:
        """Run every program the traffic can reach once, on the backend's
        own thread-safe entry: each (bucket, rows) prefill group with its
        inserts and merges, and the diffusion dispatch."""
        lens = [s["prompt_tokens"] for s in sizes]
        rng = np.random.RandomState(0)
        spec = self.spec
        vocab, mask_id = int(spec["vocab_size"]), int(spec["mask_token_id"])
        buckets = sorted({min(_lm._bucket(n), self.be.server.max_len)
                          for n in lens})
        groups = []
        for b in buckets:
            length = max(min(lens), min(b, max(lens)))
            most = min(self.slots, self.pool_copies * sum(
                1 for n in lens if _lm._bucket(n) == b))
            rows = ([1] if b <= _lm._SMALL_BUCKET_MAX else [
                k for k in _lm._powers_to(self.slots) if k < 2 * most])
            groups += [(b, k, length) for k in rows]
        for b, k, length in groups:
            prompts = []
            for _ in range(k):
                ids = rng.randint(0, vocab - 1, length).astype(np.int32)
                prompts.append(ids + (ids >= mask_id))
            self.be.driver.serve(prompts, [2] * k)
        return {"prefill_groups": len(groups), "buckets": buckets}

    def counters(self) -> Dict[str, float]:
        from dml_tpu.observability import METRICS

        out = super().counters()
        forwards = METRICS.counter("lm_server_forwards_total")
        out["forwards_denoise"] = forwards.value(kind="denoise")
        out["forwards_commit"] = forwards.value(kind="commit")
        out["forwards_total"] = (out["forwards_denoise"]
                                 + out["forwards_commit"])
        for key, name in (
                ("tokens_fixed", "lm_server_tokens_fixed_total"),
                ("blocks_committed", "lm_server_blocks_committed_total"),
                ("moe_assignments", "moe_assignments_total")):
            out[key] = METRICS.counter(name).value()
        for key, name in (("experts_touched", "moe_experts_touched"),
                          ("expert_load_max", "moe_expert_load_max")):
            rows = METRICS.histogram(name).items()
            out[key + "_count"] = sum(v[0] for _, v in rows)
            out[key + "_sum"] = sum(v[1] for _, v in rows)
        return out

    def free(self) -> None:
        self.be.server._blk_dev = None
        super().free()
