"""The program's own serve-loop spans (`dml_tpu.tracing.TRACER`'s loop
ring) inside a run's measured window, for the per-layer readers.

The cluster is in-process, so the process-global recorder holds what the
serving thread, the workers and the store said about themselves. The
window is `run["counters"]["start"]["t"]` to `["end"]["t"]`, which are
`time.monotonic()`; spans are on the wall clock, and `TRACER.wall_of` is
the program's own mapping between the two, so nothing is guessed. A span
belongs to the window when it ENDED inside it (a share clips to it
instead).

Every function returns None where there is nothing to read: a program
without loop spans (a parent commit), a window that never closed, or a
window in which no loop span of any name ended. The metric is then left
out of the result line.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple


def _window(run: Dict[str, Any]) -> Optional[Tuple[Any, float, float]]:
    """(recorder, window start, window end) on the spans' wall clock."""
    from dml_tpu.tracing import TRACER

    c = run.get("counters") or {}
    if not hasattr(TRACER, "loop_spans") or "end" not in c:
        return None
    return (TRACER, TRACER.wall_of(c["start"]["t"]),
            TRACER.wall_of(c["end"]["t"]))


def program_spans(run: Dict[str, Any], name: str,
                  under: Optional[str] = None) -> Optional[List[Dict[str, Any]]]:
    """The loop spans called `name` that ended inside the window, oldest
    first; with `under`, only those whose parent is a span of that name
    (a decode dispatch's phases under `lm_step`: not `_flush_firsts`'
    stray readback, not the placement a submit does). None where no loop
    span of any name ended in the window."""
    w = _window(run)
    if w is None:
        return None
    tracer, a, b = w
    ring = tracer.loop_spans()
    inside = [d for d in ring if a <= d["t1"] <= b]
    if not inside:
        return None
    parents = {d["sid"] for d in ring if d["name"] == under}
    return [d for d in inside if d["name"] == name
            and (under is None or d["par"] in parents)]


def mean_ms(run: Dict[str, Any], name: str,
            under: Optional[str] = None) -> Optional[float]:
    """Mean duration of those spans, in ms."""
    spans = program_spans(run, name, under)
    if not spans:
        return None
    return 1000.0 * sum(d["t1"] - d["t0"] for d in spans) / len(spans)


def share_pct(run: Dict[str, Any], name: str) -> Optional[float]:
    """The share of the window that spans called `name` cover, each
    clipped to the window, in per cent. 0.0 where other spans ended in
    the window and none of this name touched it."""
    w = _window(run)
    if w is None or program_spans(run, name) is None:
        return None
    tracer, a, b = w
    covered = sum(max(0.0, min(b, d["t1"]) - max(a, d["t0"]))
                  for d in tracer.loop_spans(name))
    return 100.0 * covered / (b - a)


def label_ratio_pct(run: Dict[str, Any], name: str, part: str,
                    whole: str) -> Optional[float]:
    """Sum of label `part` over sum of label `whole` over those spans,
    in per cent."""
    spans = program_spans(run, name)
    if not spans:
        return None
    total = sum(d["lb"][whole] for d in spans)
    return 100.0 * sum(d["lb"][part] for d in spans) / total if total else None


def event_gap_mean_ms(run: Dict[str, Any], name: str, first: str,
                      second: str) -> Optional[float]:
    """Mean time from event `first` to event `second` over those spans
    that carry both, in ms."""
    gaps = []
    for d in program_spans(run, name) or ():
        ev = dict(d.get("ev") or ())
        if first in ev and second in ev:
            gaps.append(ev[second] - ev[first])
    return 1000.0 * sum(gaps) / len(gaps) if gaps else None
