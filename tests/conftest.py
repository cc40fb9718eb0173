"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Must run before any jax import — pytest loads conftest first, so env
vars set here take effect for the whole test session. Multi-chip
sharding paths are validated on this virtual mesh; the chip is reached
only through `chip_smoke.py` / `benchmark/run.py`, one process at a
time.
"""

import os
import sys

# tests never touch an accelerator: JAX_PLATFORMS=cpu before the first
# `import jax` is all it takes
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import asyncio
import inspect

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from dml_tpu.compile_cache import configure_compile_cache

# XLA CPU compiles dominate test time; cache them across sessions
# under the one placement rule (dml_tpu/compile_cache.py)
configure_compile_cache()


def pytest_pyfunc_call(pyfuncitem):
    """Minimal async-test support (pytest-asyncio is not in the image)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            k: pyfuncitem.funcargs[k] for k in pyfuncitem._fixtureinfo.argnames
        }
        # slow-marked scenarios (chaos soaks) get headroom: this
        # sandbox's host can stall the whole process for minutes at a
        # time, and a recovery soak must be allowed to ride that out
        timeout = 300 if pyfuncitem.get_closest_marker("slow") else 120
        asyncio.run(asyncio.wait_for(fn(**kwargs), timeout=timeout))
        return True
    return None


def pytest_configure(config):
    # mirrors pytest.ini's marker registry (the canonical copy) so
    # running a test module outside the repo root stays warning-free
    config.addinivalue_line("markers", "asyncio: async test (run via asyncio.run)")
    config.addinivalue_line("markers", "slow: heavyweight test (keras builds, chaos soaks etc.)")
    config.addinivalue_line("markers", "chaos: fault-injection scenario driven by the chaos engine")
    config.addinivalue_line("markers", "adaptive: probe-adaptive depth-controller coverage (the tier-1 smoke keeps the controller path from silently rotting)")
    config.addinivalue_line("markers", "sharded: tensor-parallel worker-group serving coverage (group topology, sharded-vs-single-chip output equality)")
    config.addinivalue_line("markers", "disagg: prefill/decode-disaggregated LM serving coverage (KV-slab handoff over the data plane, role-split groups)")
    config.addinivalue_line("markers", "ingress: request front-door coverage (SLO admission/shedding, continuous batch formation, open-loop load, token streaming)")
    config.addinivalue_line("markers", "pp: pipeline-parallel LM serving coverage (layer-stack stage sharding over the pp mesh axis, microbatched stage handoff)")
    config.addinivalue_line("markers", "lint: static-analysis coverage (tools/dmllint.py rule fixtures and the tier-1 zero-unbaselined-findings enforcement)")
    config.addinivalue_line("markers", "tracing: distributed request-tracing coverage (span propagation, flight recorder, cluster trace collection, tail attribution)")
    config.addinivalue_line("markers", "scale: control-plane scale coverage (bounded delta gossip, relay metrics aggregation, O(100)-node sims, sustained churn)")
    config.addinivalue_line("markers", "kvcache: KV prefix-cache coverage (warm-start decode from resident slabs, suffix-only prefill, budgeted eviction, session affinity relay)")
    config.addinivalue_line("markers", "elastic: elastic-membership coverage (authenticated runtime join/leave, versioned universe, adaptive group re-formation, capacity-change chaos)")
    config.addinivalue_line("markers", "signal: SLO signal-plane coverage (windowed time-series, burn-rate monitors, straggler cross-checks, typed alert lifecycle)")
    config.addinivalue_line("markers", "autoscale: closed-loop autoscaler coverage (SLO-burn-driven scale-out/in, capacity reallocation, decision-ledger replay, controller-aimed chaos)")
    config.addinivalue_line("markers", "specdec: speculative-decoding coverage (draft propose + batched verify exactness, acceptance accounting and auto-disable, shipped-draft handoff, step-granular adoption races)")
    config.addinivalue_line("markers", "train: elastic data-parallel training coverage (TrainJob step ledger exactly-once accounting, elastic re-shard at step boundaries, checkpoint adoption after leader failover)")

