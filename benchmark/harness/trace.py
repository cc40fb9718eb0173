"""From a profiler trace (`.xplane.pb`) to numbers: how long the device
was busy, how long each compiled program (XLA module) ran, which device
operations took most time, and what the host was doing in the gaps.

Read with `jax.profiler.ProfileData`, nothing else. A TPU's plane is
named `/device:TPU:<n>`; its `XLA Modules` line has one event per
execution of a compiled program, named `<module>(<fingerprint>)`, and
its `XLA Ops` line one event per operation inside them. The host's plane
(`/host:CPU`) has one line per thread with the Python tracer's events
(`$file.py:LINE function`) and the runtime's own.

Busy time is the UNION of the device's operation intervals (a `while`
spans its body, so a sum would count twice), averaged over the chips
that ran anything. The program's own spans are not in this trace yet
(no `TraceAnnotation` in the program): a gap is named by the innermost
Python frame of the program's serve-loop files (the configuration's
`trace_host_frames`) that was open at its middle on any host thread,
and is `unattributed` where there is none.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
#: frames that mean "this thread is waiting", not "this is what the host did"
_WAITING = ("threading.py", "selectors.py", "queue.py", "asyncio/",
            "concurrent/futures", "sleep", "epoll", "acquire", "wait",
            "socket.py", "profiler.py", "<built-in method select")
#: attribute only this many of the longest gaps by name (each costs a
#: pass over the host's events); the rest are summed as `short_gaps`
_NAMED_GAPS = 200


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _union(intervals: np.ndarray) -> Tuple[float, np.ndarray]:
    """Total length of the union of [start, end) rows, and the merged
    intervals themselves."""
    if len(intervals) == 0:
        return 0.0, intervals
    iv = intervals[np.argsort(intervals[:, 0])]
    ends = np.maximum.accumulate(iv[:, 1])
    # a new merged interval starts where a start lies past every end so far
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    last = np.append(idx[1:] - 1, len(iv) - 1)
    merged = np.stack([starts, ends[last]], axis=1)
    return float((merged[:, 1] - merged[:, 0]).sum()), merged


def _module_name(event_name: str) -> str:
    return re.sub(r"\(\d+\)$", "", event_name)


def _events(line) -> Tuple[np.ndarray, List[str]]:
    rows, names = [], []
    for ev in line.events:
        rows.append((ev.start_ns, ev.start_ns + ev.duration_ns))
        names.append(ev.name)
    return np.asarray(rows, np.float64).reshape(-1, 2), names


def _op_name(hlo: str) -> str:
    """`%fusion.12 = bf16[...] fusion(...)` -> `fusion.12`."""
    return hlo.split(" = ", 1)[0].lstrip("%")[:80]


def reduce_trace(path: str, window_s: Optional[float] = None,
                 top: int = 10,
                 host_frames: Optional[Sequence[str]] = None) -> Dict[str, Any]:
    """The reduction. `window_s` is the traced window by the host's
    clock; without it the window is the span of everything recorded.
    `host_frames` are substrings of the Python frames that idle gaps may
    be named by (the program's serve-loop files); without them any frame
    that is not a wait will do."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, Dict[str, Any]] = {}
    host_lines: List[Tuple[np.ndarray, List[str]]] = []
    lo, hi = np.inf, -np.inf
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            dev: Dict[str, Any] = {}
            for line in plane.lines:
                if line.name in (MODULES_LINE, OPS_LINE):
                    dev[line.name] = _events(line)
            if dev:
                devices[plane.name] = dev
        elif plane.name.startswith("/host:") and "metadata" not in plane.name:
            for line in plane.lines:
                iv, names = _events(line)
                if not len(iv):
                    continue
                lo, hi = min(lo, iv[:, 0].min()), max(hi, iv[:, 1].max())
                keep = [i for i, n in enumerate(names) if n.startswith("$")
                        and (any(f in n for f in host_frames) if host_frames
                             else not any(w in n for w in _WAITING))]
                if keep:
                    host_lines.append((iv[keep], [names[i] for i in keep]))

    busy, modules, ops = [], {}, {}
    gaps: List[Tuple[float, float]] = []
    for dev in devices.values():
        iv, names = dev.get(OPS_LINE) or dev.get(MODULES_LINE)
        if not len(iv):
            continue
        lo, hi = min(lo, iv[:, 0].min()), max(hi, iv[:, 1].max())
        total, merged = _union(iv)
        busy.append(total)
        if not gaps:  # idle gaps of the first chip that ran anything
            gaps = [(float(a), float(b)) for a, b in
                    zip(merged[:-1, 1], merged[1:, 0])]
        if OPS_LINE in dev:
            oiv, onames = dev[OPS_LINE]
            for (a, b), n in zip(oiv, onames):
                ops[n] = ops.get(n, 0.0) + (b - a)
        if MODULES_LINE in dev:
            miv, mnames = dev[MODULES_LINE]
            for (a, b), n in zip(miv, mnames):
                m = modules.setdefault(_module_name(n), [])
                m.append((b - a) * 1e-9)

    chips = max(1, len(busy))
    span_s = (hi - lo) * 1e-9 if hi > lo else 0.0
    out: Dict[str, Any] = {
        "window_s": float(window_s if window_s else span_s),
        "span_s": span_s,
        "busy_s": float(sum(busy) / chips * 1e-9),
        "chips": len(busy),
        "modules": {k: {"count": len(v), "seconds": float(sum(v)),
                        "durations": v}
                    for k, v in modules.items()},
        "device_ops": [[_op_name(n), s * 1e-9 / chips] for n, s in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": _name_gaps(gaps, host_lines, top),
    }
    return out


def _name_gaps(gaps: Sequence[Tuple[float, float]],
               host_lines: Sequence[Tuple[np.ndarray, List[str]]],
               top: int) -> List[List[Any]]:
    if not gaps:
        return []
    by_len = sorted(gaps, key=lambda g: g[0] - g[1])
    named: Dict[str, float] = {}
    for a, b in by_len[:_NAMED_GAPS]:
        mid = 0.5 * (a + b)
        best, best_len = "unattributed", np.inf
        for iv, names in host_lines:
            # innermost: the shortest kept frame open at the gap's middle
            hit = np.flatnonzero((iv[:, 0] <= mid) & (iv[:, 1] > mid))
            if len(hit):
                i = hit[np.argmin(iv[hit, 1] - iv[hit, 0])]
                if iv[i, 1] - iv[i, 0] < best_len:
                    best, best_len = names[i].lstrip("$"), iv[i, 1] - iv[i, 0]
        named[best] = named.get(best, 0.0) + (b - a) * 1e-9
    rest = sum(b - a for a, b in by_len[_NAMED_GAPS:]) * 1e-9
    if rest > 0:
        named["short_gaps"] = rest
    return [[n, s] for n, s in sorted(
        named.items(), key=lambda kv: -kv[1])[:top]]


def module_durations(reduced: Dict[str, Any], pattern: str,
                     min_us: float = 0.0) -> List[float]:
    """Device seconds of each execution of the modules whose name matches
    `pattern`, leaving out executions shorter than `min_us`."""
    rx = re.compile(pattern)
    return [d for name, m in reduced["modules"].items() if rx.search(name)
            for d in m["durations"] if d * 1e6 >= min_us]


def module_seconds(reduced: Dict[str, Any], pattern: str,
                   min_us: float = 0.0) -> Tuple[int, float]:
    """(executions, device seconds) of those modules."""
    durs = module_durations(reduced, pattern, min_us)
    return len(durs), sum(durs)
