"""A continuous-batching slot grid for job-path tests, without JAX.

`StubGrid` stands where one `LMServer` stands behind every node of an
in-process cluster: `S` slots, one FIFO queue, requests that hold a slot
for a number of steps read from their file's name, slots freed and
refilled at step boundaries. Nothing in it sleeps or reads a clock: the
TEST steps it, so what it records (which calls entered in which order,
how many slots were occupied and how many requests waited at each step)
depends on the job path's rules alone.

`backend(joins=True)` is the `LMBackend` contract (it declares
`on_dispatch` and fires it once the call's requests are queued);
`backend(joins=False)` is a backend served batch after batch.
"""

from __future__ import annotations

import asyncio
import contextlib
import os
import re
import shutil
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional

MODEL = "GridLM"
PATTERNS = ("*.prompt.txt",)


def prompt_name(i: int, steps: int) -> str:
    """Store name of request `i`, which holds a slot for `steps` steps."""
    return f"p{i:03d}_len{steps:04d}.prompt.txt"


def _steps_of(path: str) -> int:
    return int(re.search(r"_len(\d+)", os.path.basename(path)).group(1))


def _index_of(path: str) -> int:
    return int(re.search(r"p(\d+)_len", os.path.basename(path)).group(1))


class _Call:
    """One backend call (a worker's batch) inside the grid."""

    def __init__(self, paths: List[str], fut: asyncio.Future):
        self.paths = list(paths)
        self.items = sorted(_index_of(p) for p in paths)
        self.fut = fut
        self.remaining = len(paths)
        self.entered_at_step = 0
        self.abandoned = False  # its worker task was cancelled

    @property
    def live(self) -> bool:
        return not self.fut.done() and not self.abandoned


class StubGrid:
    def __init__(self, slots: int):
        self.slots: List[Optional[List[Any]]] = [None] * slots
        self.queue: Deque[List[Any]] = deque()
        self.calls: List[_Call] = []  # in order of entry
        self.occupancy: List[int] = []  # occupied slots, per step
        self.waiting: List[int] = []  # queued without a slot, per step
        # fail(call) -> bool: raise instead of serving (failure tests)
        self.fail: Callable[[_Call], bool] = lambda call: False

    @property
    def size(self) -> int:
        return len(self.slots)

    def live_calls(self) -> List[_Call]:
        return [c for c in self.calls if c.live]

    def backend(self, joins: bool = True):
        grid = self

        if joins:
            async def backend(model: str, paths: List[str],
                              on_dispatch=None):
                return await grid._serve(paths, on_dispatch)
        else:
            async def backend(model: str, paths: List[str]):
                return await grid._serve(paths, None)
        return backend

    async def _serve(self, paths, on_dispatch):
        call = _Call(paths, asyncio.get_running_loop().create_future())
        call.entered_at_step = len(self.occupancy)
        self.calls.append(call)
        if self.fail(call):
            call.abandoned = True
            raise RuntimeError("injected grid failure")
        for p in paths:
            self.queue.append([call, p, _steps_of(p)])
        if on_dispatch is not None:
            on_dispatch()
        try:
            await call.fut
        except asyncio.CancelledError:
            # the requests stay in the grid, as an orphaned decode does
            call.abandoned = True
            raise
        steps = len(self.occupancy) - call.entered_at_step
        results: Dict[str, Any] = {
            p: {"tokens": [_index_of(p)] * _steps_of(p)} for p in paths}
        return results, float(steps), None

    def step(self) -> None:
        """One decode step: free slots take queued requests, every
        occupied slot advances, finished requests leave."""
        for i, r in enumerate(self.slots):
            if r is None and self.queue:
                self.slots[i] = self.queue.popleft()
        self.occupancy.append(sum(r is not None for r in self.slots))
        self.waiting.append(len(self.queue))
        for i, r in enumerate(self.slots):
            if r is None:
                continue
            r[2] -= 1
            if r[2] > 0:
                continue
            self.slots[i] = None
            call = r[0]
            call.remaining -= 1
            if call.remaining == 0 and not call.fut.done():
                call.fut.set_result(None)

    def has_work(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.slots)


@contextlib.asynccontextmanager
async def grid_cluster(n, base_port, tmp_path, grid, joins=True, batch=None):
    """An in-process `chaos.LocalCluster` of `n` nodes (leader, standby,
    n - 2 workers), every one with the grid's backend registered as
    `register_lm` asks: on every node, with the same arguments. A batch
    is a whole grid (as `LMBackend.cost()` has it) unless `batch` says
    otherwise."""
    from dml_tpu.cluster import chaos
    from dml_tpu.jobs.cost_model import ModelCost
    from dml_tpu.jobs.service import JobService

    root = str(tmp_path / f"grid_{base_port}")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)

    def make_jobs(node, store):
        js = JobService(node, store,
                        infer_backend=chaos.stub_backend(0.004))
        js.register_lm(
            MODEL, backend=grid.backend(joins), patterns=PATTERNS,
            cost=ModelCost(0.0, 0.0, 0.001, download_time=0.0,
                           batch_size=batch or grid.size))
        return js

    c = chaos.LocalCluster(n, root, base_port, make_jobs=make_jobs)
    try:
        await c.start()
        await c.wait_for(c.converged, 20.0, "initial convergence")
        yield c
    finally:
        await c.stop()


async def drain(grid: "StubGrid", waiter: "asyncio.Future",
                each_step=None) -> Any:
    """Step the grid until `waiter` is done, letting the job path move
    between steps; returns its result."""
    while not waiter.done():
        if each_step is not None:
            each_step()
        grid.step()
        await asyncio.sleep(0.002)
    return waiter.result()
