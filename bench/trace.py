"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler.trace`` writes; it is
read with ``jax.profiler.ProfileData``.  Device planes are those named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per operation
run on the chip, named by its HLO text (``%id = shape opcode(...)``).  A
loop or call op holds the ops it runs; it counts towards the busy time
but not as an op of its own.  Host spans are the benchmark's own
``TraceAnnotation`` events, whose names start with ``bench.``, on the host
planes; the outermost, ``bench.window``, bounds the traced window.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
HLO = re.compile(r"^%?(?P<id>[^\s=]+) = .*? (?P<op>[a-z][a-z0-9-]*)\(")
TARGET = re.compile(r'custom_call_target="([^"]+)"')
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|ragged-all-to-all)")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(text: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...), kind=kLoop`` -> ``fusion.3
    fusion``; a custom call keeps its target."""
    m = HLO.match(text)
    if not m:
        return text[:120]
    name = f"{m['id']} {m['op']}"
    target = TARGET.search(text)
    return f"{name} {target[1]}" if target else name


def is_collective(text: str) -> bool:
    """An op that moves data between chips, by its own opcode or id (not
    by the names of its operands)."""
    m = HLO.match(text)
    return bool(m) and bool(COLLECTIVE.match(m["op"])
                            or COLLECTIVE.match(m["id"]))


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def leaves(events) -> list:
    """The events that hold no other event of the same line: a ``while``
    whose body ops are traced inside it is left out."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    parent = [False] * len(order)
    stack = []
    for i, (_, s, e) in enumerate(order):
        while stack and order[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent[stack[-1]] = True
        stack.append(i)
    return [ev for ev, p in zip(order, parent) if not p]


@dataclasses.dataclass
class Summary:
    """Seconds, on one clock, of what the traced window held."""

    window_s: float
    busy_s: float                   # device-busy union, mean over chips
    chips: int
    ops: dict                       # op text -> (seconds per chip, count)
    spans: list                     # (name, start_s, end_s) host spans
    gaps: list                      # (seconds, host span) idle gaps
    collective_s: float             # collective-op union, mean over chips

    def op_seconds(self, pattern: str) -> float | None:
        """Seconds per chip of every op whose text matches ``pattern``;
        None when no op does."""
        rx = re.compile(pattern)
        hits = [s for name, (s, _) in self.ops.items() if rx.search(name)]
        return sum(hits) if hits else None

    def breakdown(self) -> dict:
        """The ten ops that took most device time, and the idle time
        summed by what the host was doing during it, largest first."""
        by_name: dict = {}
        for text, (secs, _) in self.ops.items():
            name = short_name(text)
            by_name[name] = by_name.get(name, 0.0) + secs
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
        idle: dict = {}
        for secs, name in self.gaps:
            idle[name] = idle.get(name, 0.0) + secs
        gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9


def summarize(path: str, chips: int) -> Summary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans, devices = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans.extend((n, s, e) for n, s, e in _events(line)
                             if n.startswith(SPAN_PREFIX))
    windows = [(s, e) for n, s, e in spans if n == "bench.window"]
    if not windows:
        raise ValueError(f"{path}: no bench.window span")
    lo, hi = windows[0]
    devices = sorted(devices, key=lambda p: int(p.name.rsplit(":", 1)[1]))
    devices = devices[:chips]
    if not devices:
        raise ValueError(f"{path}: no device plane")
    busy, coll, ops = [], [], {}
    for plane in devices:
        evs = [ev for line in plane.lines if line.name == OPS_LINE
               for ev in _events(line)]
        busy.append(union(clip([(s, e) for _, s, e in evs], lo, hi)))
        coll.append(covered(clip([(s, e) for n, s, e in evs
                                  if is_collective(n)], lo, hi)))
        for n, s, e in leaves(evs):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                secs, count = ops.get(n, (0.0, 0))
                ops[n] = (secs + d / len(devices), count + 1)
    spans = [(n, s - lo, e - lo) for n, s, e in spans if n != "bench.window"]
    return Summary(
        window_s=hi - lo,
        busy_s=sum(covered(b) for b in busy) / len(busy),
        chips=len(devices), ops=ops, spans=spans,
        gaps=_gaps(busy[0], lo, hi, spans),
        collective_s=sum(coll) / len(coll))


def _gaps(busy, lo: float, hi: float, spans) -> list:
    """Idle gaps of one chip in the window, longest first, each named by
    the innermost host span that covers its middle ("none" if none)."""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    out = []
    for s, e in zip(edges[::2], edges[1::2]):
        if e <= s:
            continue
        mid = 0.5 * (s + e) - lo
        inside = [(b - a, n) for n, a, b in spans if a <= mid <= b]
        out.append((e - s, min(inside)[1] if inside else "none"))
    return sorted(out, reverse=True)
