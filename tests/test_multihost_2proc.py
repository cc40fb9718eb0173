"""True multi-process jax.distributed smoke test.

Round-1 coverage only exercised single-process degeneracy
(`_initialized` stayed False everywhere); this spawns TWO real CPU
processes through `multihost.initialize_from_spec`, builds the global
mesh in each, assembles a cross-process global batch, and checks a
jitted global reduction (psum-equivalent) sees BOTH hosts' shards —
the coordinator-address/process-id wiring bugs this catches only
exist across real process boundaries."""

import os
import subprocess
import sys

import pytest

CHILD = """
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax

import numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from dml_tpu.config import ClusterSpec, MeshSpec
from dml_tpu.parallel import multihost

spec_path, idx = sys.argv[1], int(sys.argv[2])
spec = ClusterSpec.from_file(spec_path)
pid = multihost.initialize_from_spec(spec, spec.nodes[idx])
assert pid == idx, (pid, idx)
assert jax.process_count() == 2, jax.process_count()
assert multihost._initialized

mesh = multihost.global_mesh(MeshSpec(dp=-1))
assert mesh.shape["dp"] == jax.device_count()

# each process contributes a distinct shard; the global sum must see
# both (process 0 contributes 0s, process 1 contributes 1s)
per_host = jax.local_device_count()
local = np.full((4 * per_host, 2), float(pid), np.float32)
arr = multihost.global_batch(local, mesh, P("dp"))
assert arr.shape[0] == 8 * per_host  # global, not local

total = jax.jit(
    lambda x: jnp.sum(x), out_shardings=NamedSharding(mesh, P())
)(arr)
expected = 1.0 * 4 * per_host * 2  # process 1's ones
assert float(total) == expected, (float(total), expected)
print(f"MULTIHOST_OK pid={pid} total={float(total)}")
"""


@pytest.mark.slow
def test_two_process_global_psum(tmp_path):
    from dml_tpu.config import ClusterSpec

    # base_port chosen so base_port + JAX_COORD_PORT_OFFSET is free
    spec = ClusterSpec.localhost(2, base_port=18651, introducer_port=18650)
    spec_path = tmp_path / "spec.json"
    spec_path.write_text(spec.to_json())
    child_path = tmp_path / "child.py"
    child_path.write_text(CHILD)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # 1 CPU device per process
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + env.get("PYTHONPATH", "").split(os.pathsep)
    )
    procs = []
    try:
        for idx in (0, 1):
            procs.append(subprocess.Popen(
                [sys.executable, str(child_path), str(spec_path), str(idx)],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                env=env, text=True,
            ))
    except OSError as e:  # pragma: no cover - sandbox without spawn
        pytest.skip(f"cannot spawn subprocesses here: {e}")
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
    except subprocess.TimeoutExpired:  # pragma: no cover
        for p in procs:
            p.kill()
        pytest.fail("2-process jax.distributed run hung (coordinator "
                    "wiring?)\n" + "\n---\n".join(outs))
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"child failed:\n{out}"
        assert "MULTIHOST_OK" in out, out
