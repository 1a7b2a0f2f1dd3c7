"""Reduce a JAX profiler trace to the intervals the metrics read.

``Trace.load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes:
device operations from each ``/device:TPU:<n>`` plane (line "XLA Ops",
with the program that ran each under the ``hlo_module`` stat) and the
benchmark's own host spans (``jax.profiler.TraceAnnotation`` names
beginning ``bench.``) from the host plane.  Both are on the profiler's
clock, in nanoseconds.  Everything after loading is plain interval
arithmetic, so the metric readers can be checked on traces built by
hand.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench."


def module_name(event_name: str) -> str:
    """``jit_fused(123)`` -> ``jit_fused``: the program's name without
    the id the profiler appends."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``: the HLO
    instruction's name (TPU traces name an operation by its text)."""
    m = re.match(r"%?([\w.\-]+)", event_name)
    return m.group(1) if m else event_name


@dataclasses.dataclass
class Op:
    device: int
    name: str
    module: str
    start: float          # ns
    end: float            # ns
    long_name: str = ""   # the HLO instruction, with operand shapes


@dataclasses.dataclass
class Trace:
    ops: list             # [Op], every device
    spans: list           # [(name, start_ns, end_ns)], host spans
    devices: int

    @classmethod
    def load(cls, directory: str) -> "Trace":
        from jax.profiler import ProfileData

        paths = sorted(glob.glob(os.path.join(directory, "**",
                                              "*.xplane.pb"),
                                 recursive=True))
        if not paths:
            raise FileNotFoundError(f"no xplane.pb under {directory}")
        data = ProfileData.from_file(paths[-1])
        ops, spans, devices = [], [], set()
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:") and \
                    plane.name[len("/device:TPU:"):].isdigit():
                dev = int(plane.name[len("/device:TPU:"):])
                devices.add(dev)
                by_line = {line.name: line for line in plane.lines}
                modules = sorted(
                    (ev.start_ns, ev.start_ns + ev.duration_ns,
                     module_name(ev.name))
                    for ev in (by_line["XLA Modules"].events
                               if "XLA Modules" in by_line else ()))
                starts = [m[0] for m in modules]
                line = by_line.get("XLA Ops")
                for ev in (line.events if line is not None else ()):
                    start, end = ev.start_ns, ev.start_ns + ev.duration_ns
                    i = bisect.bisect_right(starts, start) - 1
                    module = (modules[i][2] if i >= 0
                              and modules[i][1] >= end else "")
                    ops.append(Op(dev, op_name(ev.name), module, start,
                                  end, ev.name))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            spans.append((ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns))
        return cls(ops=ops, spans=spans, devices=len(devices))

    # -- window ------------------------------------------------------------
    def window(self, open_name: str, close_name: str) -> tuple:
        """(start, end) ns: the end of the last ``open_name`` span to the
        start of the first ``close_name`` span after it."""
        opens = [e for n, s, e in self.spans if n == open_name]
        if not opens:
            raise ValueError(f"no {open_name!r} span in the trace")
        lo = max(opens)
        closes = [s for n, s, e in self.spans if n == close_name and s >= lo]
        if not closes:
            raise ValueError(f"no {close_name!r} span after the window "
                             f"opened")
        return lo, min(closes)

    def ops_in(self, lo: float, hi: float, device: int | None = None):
        return [o for o in self.ops if o.end > lo and o.start < hi
                and (device is None or o.device == device)]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def union(intervals):
    """Sorted, merged ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def gaps(intervals, lo: float, hi: float):
    """Idle ``(start, end)`` stretches of ``[lo, hi]`` not covered."""
    out, t = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def busy_share(trace: Trace, lo: float, hi: float, chips: int) -> float:
    """Nanoseconds of ``[lo, hi]`` in which some operation ran, averaged
    over devices ``0 .. chips - 1``."""
    chips = max(1, chips)
    return sum(covered(clip([(o.start, o.end) for o in trace.ops
                             if o.device == d], lo, hi))
               for d in range(chips)) / chips


def module_ops(trace: Trace, lo: float, hi: float, match) -> list:
    """Device-0 operations inside ``[lo, hi]`` whose program name
    ``match`` accepts."""
    return [o for o in trace.ops_in(lo, hi, 0) if match(o.module)]


def busy_ns(ops, lo: float, hi: float) -> float:
    return covered(clip([(o.start, o.end) for o in ops], lo, hi))
