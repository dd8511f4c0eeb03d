"""Reduction of a profiler trace (``.xplane.pb``) to device time.

Read with ``jax.profiler.ProfileData`` and nothing else.  A device plane
(``/device:TPU:<n>``) holds a line of XLA operations and a line of XLA
modules (one event per program execution); host planes hold the
``TraceAnnotation`` spans the harness wrote, on the same clock.

Busy time is the union of the operation intervals inside the traced
window; idle share is one minus busy over the window.  An operation
belongs to the program whose module event encloses it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

__all__ = ["Event", "DeviceTrace", "load", "union_ns", "busy_ns",
           "module_of", "summarize"]

WINDOW_SPAN = "bench.window"
# operations whose interval encloses the operations of their body
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str             # an XLA operation's name is its HLO text
    start: float          # ns
    dur: float            # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class DeviceTrace:
    window: tuple[float, float]                  # ns, from the window span
    ops: dict[str, list[Event]]                  # device plane -> operations
    modules: dict[str, list[Event]]              # device plane -> programs
    host: list[Event]                            # harness spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def _line_kind(name: str) -> str | None:
    """The device plane's program line and its line of synchronous
    operations; the line of asynchronous operations (copies in flight
    beside the compute) does not make the device busy."""
    return {"XLA Modules": "modules", "XLA Ops": "ops"}.get(name)


def _events(line) -> list[Event]:
    return [Event(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def from_profile(pd) -> DeviceTrace:
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        if re.match(r"/device:TPU:\d+$", plane.name):
            for line in plane.lines:
                kind = _line_kind(line.name)
                if kind == "ops":
                    ops.setdefault(plane.name, []).extend(_events(line))
                elif kind == "modules":
                    modules.setdefault(plane.name, []).extend(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN or e.name.startswith("bench.") \
                            or e.name in ("engine.step", "trainer.step"):
                        host.append(Event(e.name, float(e.start_ns),
                                          float(e.duration_ns)))
    win = [e for e in host if e.name == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    if not ops:
        raise ValueError("the trace has no device operations")
    w = win[0]
    return DeviceTrace((w.start, w.end), ops, modules, host)


def load(trace_dir: str) -> DeviceTrace:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(paths)}")
    return from_profile(ProfileData.from_file(paths[0]))


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns(tr: DeviceTrace) -> float:
    """Busy time inside the window, averaged over the device planes."""
    lo, hi = tr.window
    vals = [union_ns(((e.start, e.end) for e in evs), lo, hi)
            for evs in tr.ops.values()]
    return sum(vals) / len(vals)


def module_of(ev: Event, modules: list[Event]) -> str | None:
    """Name of the program execution that encloses ``ev``."""
    for m in modules:
        if m.start <= ev.start and ev.end <= m.end:
            return m.name
    return None


def in_window(tr: DeviceTrace, ev: Event) -> bool:
    return tr.window[0] <= ev.start and ev.end <= tr.window[1]


def op_kind(name: str) -> str:
    """A short name for an operation: its HLO name without the instance
    number, its result type and its opcode (``closed_call bf16[16,5120]
    custom-call``)."""
    lhs, eq, rhs = name.partition(" = ")
    if not eq:
        return name[:80]
    base = re.sub(r"\.\d+$", "", lhs.lstrip("%"))
    if rhs.startswith("("):
        typ, rest = "tuple", rhs[rhs.find(") ") + 2:]
    else:
        typ, _, rest = rhs.partition(" ")
        typ = typ.split("{")[0]
    return f"{base} {typ} {rest.split('(')[0]}"


def top_ops(tr: DeviceTrace, n: int = 10) -> list[list]:
    """The device operations that took most time in the window, by kind."""
    tot: dict[str, float] = {}
    for evs in tr.ops.values():
        for e in evs:
            key = op_kind(e.name)
            if in_window(tr, e) and key.split()[-1] not in CONTAINERS:
                tot[key] = tot.get(key, 0.0) + e.dur / 1e9
    k = len(tr.ops)
    return [[name, s / k] for name, s in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: DeviceTrace, n: int = 10) -> list[list]:
    """The longest gaps between device operations in the window, each
    named by the harness span the host was in at the gap's middle."""
    lo, hi = tr.window
    plane = sorted(tr.ops)[0]
    evs = sorted((max(e.start, lo), min(e.end, hi)) for e in tr.ops[plane]
                 if e.end > lo and e.start < hi)
    gaps, cur = [], lo
    for s, e in evs:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    spans = [h for h in tr.host if h.name != WINDOW_SPAN]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        host = next((h.name for h in spans if h.start <= mid <= h.end),
                    "harness")
        out.append([host, (e - s) / 1e9])
    return out


def summarize(tr: DeviceTrace) -> dict:
    return {"busy_s": busy_ns(tr) / 1e9, "window_s": tr.window_s,
            "breakdown": {"device_ops": top_ops(tr),
                          "idle_gaps": idle_gaps(tr)}}
