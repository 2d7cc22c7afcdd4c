"""Reduction of a JAX profiler trace to what the metrics read.

`load(log_dir)` reads the `.xplane.pb` that `jax.profiler` wrote and
returns a `Trace`: the device operations of each accelerator plane (the
"XLA Ops" line, whose event names are the ops' HLO text, and the "XLA
Modules" line that says which program each op belongs to), the
benchmark's own `TraceAnnotation` spans (names that start with "bench.")
and the other host events on the same threads, all on the profiler's
clock in nanoseconds. On a v5e the device clock runs about 1 ms ahead of
the host's in this trace; over windows of seconds that shifts nothing
that is measured here.

The helpers below turn those into busy time (the union of operation
intervals), idle gaps named by what the host was doing, per-operation
totals, and the operand shapes of a kernel call.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_SHAPE = re.compile(r"\b(pred|[subf]\d+|bf16)\[([\d,]*)\]")
_INSTR = re.compile(r"%?([\w.-]+?)(\.\d+)?\s*=")
_OPCODE = re.compile(r"[\]}]\s+[a-z][\w-]*\(")


@dataclasses.dataclass
class Event:
    name: str
    start: float            # ns
    end: float              # ns


@dataclasses.dataclass
class Trace:
    device_ops: Dict[str, List[Event]]      # device plane -> ops by start
    modules: Dict[str, List[Event]]         # device plane -> programs
    spans: List[Event]                      # the benchmark's annotations
    host: List[List[Event]]                 # other events, one list a thread

    def window(self) -> Tuple[float, float]:
        w = [s for s in self.spans if s.name == WINDOW_SPAN]
        if not w:
            raise ValueError(f"trace holds no {WINDOW_SPAN} span")
        return w[0].start, w[0].end


def _events(line) -> List[Event]:
    return [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def from_profile(pd) -> Trace:
    """A Trace from a `jax.profiler.ProfileData`."""
    device_ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    host: List[List[Event]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = sorted(_events(line),
                                                    key=lambda e: e.start)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = sorted(_events(line),
                                                 key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = _events(line)
                mine = [e for e in evs if e.name.startswith(SPAN_PREFIX)]
                if mine:
                    spans += mine
                    host.append([e for e in evs
                                 if not e.name.startswith(SPAN_PREFIX)])
    device_ops = {k: v for k, v in device_ops.items() if v}
    return Trace(device_ops, modules, spans, host)


def load(log_dir: str) -> Trace:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, "
                         f"found {len(paths)}")
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(paths[0]))


# ------------------------------------------------------------ intervals
def union(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals clipped to [lo, hi]."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The complement of merged `busy` intervals within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def busy_ns(tr: Trace) -> float:
    """Device-busy nanoseconds in the window, averaged over the chips."""
    lo, hi = tr.window()
    per_chip = [sum(e - s for s, e in union(
        [(o.start, o.end) for o in ops], lo, hi))
        for ops in tr.device_ops.values()]
    if not per_chip:
        raise ValueError("trace holds no device operations")
    return sum(per_chip) / len(per_chip)


def innermost(events: List[Event], times: List[float]) -> List[Optional[str]]:
    """For each of the sorted `times`, the name of the shortest event of
    `events` (one thread's, so nested) that contains it, or None."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    out: List[Optional[str]] = []
    stack: List[Event] = []
    i = 0
    for t in times:
        while i < len(evs) and evs[i].start <= t:
            while stack and stack[-1].end <= evs[i].start:
                stack.pop()
            stack.append(evs[i])
            i += 1
        while stack and stack[-1].end <= t:
            stack.pop()
        out.append(stack[-1].name if stack else None)
    return out


def idle_gaps(tr: Trace, top: int = 10) -> List[list]:
    """Idle time of the first chip within the window, summed by what the
    host was doing at each gap's middle: the innermost benchmark span and
    the innermost other host event on its thread. Longest first."""
    lo, hi = tr.window()
    ops = next(iter(tr.device_ops.values()))
    idle = gaps(union([(o.start, o.end) for o in ops], lo, hi), lo, hi)
    mids = [0.5 * (g0 + g1) for g0, g1 in idle]
    span = innermost([s for s in tr.spans if s.name != WINDOW_SPAN], mids)
    what = [innermost(h, mids) for h in tr.host]
    total: Dict[str, float] = defaultdict(float)
    for k, (g0, g1) in enumerate(idle):
        doing = next((w[k] for w in what if w[k] is not None), None)
        name = (span[k] or WINDOW_SPAN) + (f":{doing}" if doing else "")
        total[name] += (g1 - g0) * 1e-9
    return sorted(([k, v] for k, v in total.items()),
                  key=lambda kv: -kv[1])[:top]


# --------------------------------------------------------------- op names
def instruction(e: Event) -> str:
    """The op's HLO instruction name without its instance number:
    "%copy-start.2 = (...) copy-start(...)" -> "copy-start"."""
    m = _INSTR.match(e.name)
    return m.group(1) if m else re.sub(r"\.\d+$", "", e.name)


def _module_of(modules: List[Event], starts: List[float], t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i].end >= t:
        return modules[i].name.split("(")[0]
    return ""


def device_op_totals(tr: Trace, top: int = 10) -> List[list]:
    """Seconds per "program/instruction" on the first chip in the window."""
    lo, hi = tr.window()
    plane = next(iter(tr.device_ops))
    mods = tr.modules.get(plane, [])
    starts = [m.start for m in mods]
    total: Dict[str, float] = defaultdict(float)
    for o in tr.device_ops[plane]:
        d = min(o.end, hi) - max(o.start, lo)
        if d > 0:
            mod = _module_of(mods, starts, o.start)
            total[f"{mod}/{instruction(o)}" if mod else instruction(o)] += (
                d * 1e-9)
    return sorted(([k, v] for k, v in total.items()),
                  key=lambda kv: -kv[1])[:top]


# --------------------------------------------------------------- kernels
def _call_start(text: str) -> int:
    """Index of the "(" that opens the call after the result's shape and
    layout (a layout such as {1,0:T(8,128)} has parentheses of its own)."""
    m = _OPCODE.search(text, text.find("="))
    return m.end() - 1 if m else len(text)


def _call_args(text: str) -> str:
    """The operand list of the instruction's call, "(...)" with nested
    brackets of layouts inside, without what follows it."""
    start = _call_start(text)
    depth = 0
    for i in range(start, len(text)):
        c = text[i]
        if c in "({[":
            depth += 1
        elif c in ")}]":
            depth -= 1
            if depth == 0:
                return text[start + 1:i]
    return text[start + 1:]


def shapes(text: str) -> Tuple[Optional[tuple], List[tuple]]:
    """(result, operands) of one HLO instruction's text as (dtype, dims)
    pairs, e.g. "%x = f32[8,4]{1,0} custom-call(s32[2]{0} %a, ...)"."""
    if "=" not in text:
        return None, []
    head = text[:_call_start(text)]

    def parse(part):
        return [(t, tuple(int(x) for x in d.split(",") if x))
                for t, d in _SHAPE.findall(part)]
    res = parse(head)
    return (res[-1] if res else None), parse(_call_args(text))


def kernel_events(tr: Trace, kernel: str) -> List[Event]:
    """Calls in the window, on every chip, of the Pallas kernel named
    `kernel` (`pallas_call(name=...)`): custom calls of that name."""
    lo, hi = tr.window()
    return [o for ops in tr.device_ops.values() for o in ops
            if o.start >= lo and o.end <= hi and instruction(o) == kernel
            and "custom-call(" in o.name]
