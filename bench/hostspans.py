"""Device idle time split by the program layer the host was in.

The program's `obs.trace` spans enter a `TraceMe` while the profiler
collects, so they lie on the host line that holds the benchmark's own
spans (`devtrace.Trace.host`), on the device trace's clock. Each idle
interval of the first chip in the window is split exactly over those
spans: every instant goes to the innermost program span covering it, and
the span's name (its first dotted component) names the layer. JAX's own
host events (`PjitFunction(...)`, `DevicePut`) are not program spans and
fall to the program span that encloses them; an instant under no program
span, or under one the table does not name, is booked to `other`.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from bench import devtrace
from bench.devtrace import Event, Trace

LAYERS = {"store": "store", "safs": "store",
          "pass": "passes", "ortho": "passes",
          "operator": "operator",
          "solve": "restart"}
OTHER = "other"
ROOT_SPAN = "solve"


def layer_of(name: str) -> Optional[str]:
    """The layer of a program span, or None for any other host event."""
    return LAYERS.get(name.split(".", 1)[0])


def segments(spans: List[Event]) -> List[Tuple[float, float, str]]:
    """Disjoint (start, end, layer) pieces of one thread's nested spans,
    each piece named by the innermost span over it."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Event] = []
    t = float("-inf")

    def emit(end: float) -> None:
        nonlocal t
        if stack and end > t:
            out.append((t, end, layer_of(stack[-1].name)))
        t = max(t, end)

    for s in sorted(spans, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1].end <= s.start:
            emit(stack[-1].end)
            stack.pop()
        emit(s.start)
        stack.append(s)
    while stack:
        emit(stack[-1].end)
        stack.pop()
    return out


def split(idle: List[Tuple[float, float]],
          pieces: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Idle time by layer: the overlap of the sorted, disjoint `idle`
    intervals with the sorted, disjoint `pieces`; the rest is `other`."""
    total = {layer: 0.0 for layer in set(LAYERS.values())}
    total[OTHER] = sum(e - s for s, e in idle)
    i = 0
    for s, e, layer in pieces:
        while i < len(idle) and idle[i][1] <= s:
            i += 1
        j = i
        while j < len(idle) and idle[j][0] < e:
            d = min(e, idle[j][1]) - max(s, idle[j][0])
            total[layer] += d
            total[OTHER] -= d
            j += 1
    return total


def idle_ns_by_layer(tr: Trace) -> Optional[Dict[str, float]]:
    """Idle nanoseconds of the first chip in the window by layer, `other`
    included, so that the values sum to the window's idle time; None
    where no host line holds a `solve` span in the window (a program
    without the spans)."""
    lo, hi = tr.window()
    for line in tr.host:
        mine = [e for e in line if layer_of(e.name) is not None
                and e.end > lo and e.start < hi]
        if any(e.name == ROOT_SPAN for e in mine):
            ops = next(iter(tr.device_ops.values()), [])
            idle = devtrace.gaps(devtrace.union(
                [(o.start, o.end) for o in ops], lo, hi), lo, hi)
            return split(idle, segments(mine))
    return None


def idle_ms_per_apply(run, layer: str) -> Optional[float]:
    """Device idle milliseconds in the traced window while the host was
    in `layer`, per operator apply of the window's solves."""
    if run.trace is None or not run.n_ops:
        return None
    by_layer = idle_ns_by_layer(run.trace)
    if by_layer is None:
        return None
    return by_layer[layer] * 1e-6 / run.n_ops
