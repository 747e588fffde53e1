"""Reduce a profiler trace to the numbers the per-layer metrics read.

Input is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``; the reduction itself works on plain
tuples, so a unit test can hand it intervals with known answers.

- Device ops: every event on a device plane (``/device:<kind>:<n>``),
  taken from its ``XLA Ops`` line where the plane has one, named by the
  HLO instruction's name. A control-flow op (``while``, ``conditional``,
  ``call``) encloses its body's ops: it counts toward busy time, and
  not toward the per-op totals or as compute that hides a collective.
- Host spans: events named ``chipbench.*`` on any host plane, which the
  benchmark records with ``jax.profiler.TraceAnnotation`` around its
  calls into each layer.

Within a window ``[lo, hi)`` (the ``chipbench.window`` span), per
device: busy time is the length of the union of op intervals; idle is
the rest; each op name's summed duration; collective time is the union
of collective ops' intervals, and its exposed part the share of that
union that no other op on the device overlaps. Idle gaps are the holes
in the busy union, each named by the innermost benchmark span open at
its midpoint.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

COLLECTIVE = re.compile(
    r"(collective-permute|all-gather|all-reduce|reduce-scatter|all-to-all"
    r"|ppermute|psum|send|recv)", re.IGNORECASE)
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
SPAN_PREFIX = "chipbench."
# Control-flow ops whose events enclose the ops of their bodies: counted
# in the busy union, left out of the per-op totals.
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")


def op_name(text: str) -> str:
    """The op's name from its trace event, which on a TPU is the whole
    HLO instruction (``%fusion.12 = bf16[...] fusion(...)``)."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


@dataclasses.dataclass
class Trace:
    """``ops[device] = [(name, start_ns, end_ns)]`` and
    ``spans = [(name, start_ns, end_ns)]``, on one clock."""

    ops: dict
    spans: list

    def window(self, name: str = SPAN_PREFIX + "window") -> tuple:
        found = [(s, e) for n, s, e in self.spans if n == name]
        if not found:
            raise ValueError(f"no {name} span in the trace")
        return min(s for s, _ in found), max(e for _, e in found)


def find_xplane(trace_dir) -> str:
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: dict = {}
    spans: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = list(plane.lines)
            named = [ln for ln in lines if ln.name == "XLA Ops"]
            events = ops.setdefault(plane.name, [])
            for ln in named or lines:
                for ev in ln.events:
                    start = float(ev.start_ns)
                    events.append((op_name(ev.name), start,
                                   start + float(ev.duration_ns)))
        else:
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        start = float(ev.start_ns)
                        spans.append((ev.name, start,
                                      start + float(ev.duration_ns)))
    return Trace(ops=ops, spans=spans)


def union(intervals) -> list:
    """Sorted, merged ``[(start, end)]`` of the given intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: float, hi: float) -> list:
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """Parts of the merged intervals ``a`` that the merged ``b`` leaves
    uncovered."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class DeviceSummary:
    busy_ns: float
    op_ns: dict  # op name -> summed duration inside the window
    collective_ns: float
    collective_exposed_ns: float
    busy: list  # merged busy intervals inside the window


def summarize(events, lo: float, hi: float) -> DeviceSummary:
    inside = [(n, s, e) for n, s, e in events if e > lo and s < hi]
    busy = union(clip([(s, e) for _, s, e in inside], lo, hi))
    op_ns: dict = {}
    for n, s, e in inside:
        if not CONTAINER.match(n):
            op_ns[n] = op_ns.get(n, 0.0) + min(e, hi) - max(s, lo)
    coll = union(clip([(s, e) for n, s, e in inside if COLLECTIVE.search(n)],
                      lo, hi))
    other = union(clip([(s, e) for n, s, e in inside
                        if not COLLECTIVE.search(n)
                        and not CONTAINER.match(n)], lo, hi))
    return DeviceSummary(
        busy_ns=length(busy),
        op_ns=op_ns,
        collective_ns=length(coll),
        collective_exposed_ns=length(subtract(coll, other)),
        busy=busy,
    )


def span_at(spans, t: float) -> str:
    """Innermost (shortest) benchmark span open at ``t``, or ``idle``."""
    best = None
    for n, s, e in spans:
        if s <= t < e and n != SPAN_PREFIX + "window":
            if best is None or e - s < best[1]:
                best = (n, e - s)
    return best[0] if best else "idle"


def gaps(busy, lo: float, hi: float) -> list:
    """Holes in the merged ``busy`` intervals within ``[lo, hi)``."""
    return subtract([(lo, hi)], busy)


@dataclasses.dataclass
class Summary:
    window_ns: float
    devices: dict  # device plane name -> DeviceSummary
    spans: list
    lo: float
    hi: float

    @property
    def busy_ns(self) -> float:
        """Busy time averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(d.busy_ns for d in self.devices.values()) / len(
            self.devices)

    def spans_named(self, name: str) -> list:
        return [(s, e) for n, s, e in self.spans
                if n == name and e > self.lo and s < self.hi]

    def top_ops(self, k: int = 10) -> list:
        """``[[name, seconds]]`` of the ops that took most device time,
        summed over devices and averaged per device."""
        tot: dict = {}
        for d in self.devices.values():
            for n, v in d.op_ns.items():
                tot[n] = tot.get(n, 0.0) + v
        per = len(self.devices) or 1
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v / per / 1e9] for n, v in ranked]

    def top_gaps(self, k: int = 10) -> list:
        """``[[span, seconds]]`` of the longest idle gaps on any device,
        each named by the host span open at its midpoint."""
        found = []
        for d in self.devices.values():
            for s, e in gaps(d.busy, self.lo, self.hi):
                found.append((e - s, span_at(self.spans, (s + e) / 2)))
        found.sort(key=lambda x: -x[0])
        return [[n, v / 1e9] for v, n in found[:k]]


def reduce(trace: Trace, window: tuple | None = None) -> Summary:
    lo, hi = window or trace.window()
    return Summary(
        window_ns=hi - lo,
        devices={name: summarize(ev, lo, hi)
                 for name, ev in sorted(trace.ops.items())},
        spans=trace.spans,
        lo=lo,
        hi=hi,
    )
