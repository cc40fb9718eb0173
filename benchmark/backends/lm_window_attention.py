"""The program's LM serving stack as the system under test, for a
configuration whose attention layers go by type (full, or a window of W
positions whose rows a slot caches in a ring): `backends/lm_state_space.py`'s
`System` as it stands (the declared tree set against the reference's
before any weight is made, so that a program that does not know the
architecture's `lm_spec` keys stops at once; warm-up of each (bucket,
rows) prefill group the traffic can form under the program's bound on a
group's padded tokens, and of the chunk dispatch; the expert routing's
counters) with the one thing that differs: what a slot carries is K and V
rows by layer type, not a state-space layer's state, so the construction
asks for a window layer's ring and the counter snapshot adds the rings'
bytes and the decode steps' cache rows by layer type.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark.harness import manifest as mf

_lm = mf.load_module("backends", "lm")
_ss = mf.load_module("backends", "lm_state_space")
UnknownArchitecture = _ss.UnknownArchitecture


class System(_ss.System):
    def __init__(self, config: Dict[str, Any], reference, seed: int,
                 variant: Optional[str] = None):
        import jax

        spec = config["lm_spec"]
        declared = jax.tree.map(lambda s: tuple(s.shape),
                                _ss._declared_tree(spec))
        if declared != reference.param_shapes(spec):
            got = sorted(declared.get("block_0", {}))
            raise UnknownArchitecture(
                f"the program's lm_spec_parts declares another model for "
                f"{config['name']!r} (its first block holds {got}): it "
                f"does not know this architecture's lm_spec keys")
        _lm.System.__init__(self, config, reference, seed, variant=variant)
        self.pool_copies = int(config.get("warm_pool_copies", 1))
        if not getattr(self.be.server.cfg, "has_ring", False):
            raise UnknownArchitecture(
                f"{config['name']!r} was built without a window layer's "
                f"ring of rows")

    def counters(self) -> Dict[str, float]:
        from dml_tpu.observability import METRICS

        out = super().counters()
        out["state_bytes_kv_window"] = METRICS.gauge(
            "lm_server_state_bytes").value(kind="kv_window")
        rows = METRICS.counter("lm_server_decode_kv_rows_total")
        for layers in ("full", "window"):
            for kind in ("live", "read", "grid"):
                out[f"kv_rows_{layers}_{kind}"] = rows.value(
                    kind=kind, layers=layers)
        return out
