"""The program's LM serving stack as the system under test, for a
configuration whose layers are one mixer each by a `layer_pattern`
(state-space, attention, expert feed-forward): `backends/lm.py`'s
`System` (built as an operator builds it, `LMBackend.from_spec(lm_spec)`,
with the benchmark's weight VALUES in the tree the program declares)
with what differs on this serve loop:

- before any weight is made, the tree the program's `lm_spec_parts`
  declares for the configuration is set against the reference's. A
  program that does not know the architecture ignores the keys it does
  not know and declares another tree (a dense decoder, or experts in the
  hidden width): the run stops there, at once, instead of serving
  another model;
- warm-up runs each (bucket, rows) prefill group the traffic can form
  and the chunk dispatch once. A group is padded to the next power of
  two of its prompts (a prompt's bucket is the program's own
  `_prefill_bucket`), no placement round can hold more prompts of one
  bucket than the copies of the item pool in flight hold (the
  configuration's `warm_pool_copies`), and the program bounds the padded
  tokens of a group of several rows where a state-space layer's
  transients grow with them (`LMServer._group_tokens`). There are no
  packed readbacks of varying arity to keep: the dispatch reads back ONE
  fixed shape;
- the counter snapshot adds the expert routing's counters (over all the
  routed experts and over those held) and the slot state's bytes.
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
        if not getattr(self.be.server.cfg, "has_state", False):
            raise UnknownArchitecture(
                f"{config['name']!r} was built without a state-space "
                f"layer's state")

    def warm(self, sizes: Sequence[Dict[str, int]]) -> Dict[str, Any]:
        """Run every program the traffic can reach once, on the backend's
        own thread-safe entry: each (bucket, rows) prefill group with its
        inserts and merges, and the chunk dispatch."""
        from dml_tpu.inference.lm_server import _prefill_bucket

        srv = self.be.server
        lens = [s["prompt_tokens"] for s in sizes]
        rng = np.random.RandomState(0)
        vocab = int(self.spec["vocab_size"])
        own = [_prefill_bucket(n, srv.max_len) for n in lens]
        bound = srv._group_tokens
        groups = []
        for b in sorted(set(own)):
            length = max(min(lens), min(b, max(lens)))
            most = min(self.slots, self.pool_copies * own.count(b))
            if b <= _lm._SMALL_BUCKET_MAX:
                rows = [1]  # padded to the whole grid whatever it holds
            else:
                rows = [k for k in _lm._powers_to(self.slots)
                        if k < 2 * most
                        and (k == 1 or not bound or k * b <= bound)]
            groups += [(b, k, length) for k in rows]
        for b, k, length in groups:
            prompts = [rng.randint(0, vocab, length).astype(np.int32)
                       for _ in range(k)]
            self.be.driver.serve(prompts, [2] * k)
        return {"prefill_groups": len(groups),
                "groups": [[b, k] for b, k, _ in groups]}

    def counters(self) -> Dict[str, float]:
        from dml_tpu.observability import METRICS

        out = super().counters()
        assigned = METRICS.counter("moe_assignments_total")
        out["moe_assignments_held"] = assigned.value(where="held")
        out["moe_assignments_absent"] = assigned.value(where="absent")
        for key, name in (("experts_touched", "moe_experts_touched"),
                          ("experts_held_touched",
                           "moe_experts_touched_held"),
                          ("expert_load_max", "moe_expert_load_max")):
            rows = METRICS.histogram(name).items()
            out[key + "_count"] = sum(v[0] for _, v in rows)
            out[key + "_sum"] = sum(v[1] for _, v in rows)
        state = METRICS.gauge("lm_server_state_bytes")
        for kind in ("kv", "conv", "scan"):
            out["state_bytes_" + kind] = state.value(kind=kind)
        return out

    def free(self) -> None:
        self.be.server._firsts_dev = None
        super().free()
