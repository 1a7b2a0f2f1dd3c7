"""The traced run's ``device`` times and ``breakdown`` from its trace.

``busy_s`` is the union of the device operations' intervals inside the
traced window, averaged over the chips the cell uses; ``window_s`` is
the window's length on the profiler's clock.  ``device_ops`` lists the
(program, operation) pairs that took the most device time;
``idle_gaps`` the longest stretches with no operation on device 0, each
named by the benchmark's host span that overlaps it most (or "program
host work" where none does).
"""
from __future__ import annotations

import collections

import devtrace

TOP = 10


def summarize(ctx):
    t = ctx["trace"]
    lo, hi = ctx["trace_window"]
    ops = t.ops_in(lo, hi)
    busy = devtrace.busy_share(t, lo, hi, ctx["chips"])
    per = collections.Counter()
    for o in ops:
        s, e = max(o.start, lo), min(o.end, hi)
        per[f"{o.module}:{o.name}"] += e - s
    device_ops = [[name, ns * 1e-9] for name, ns in per.most_common(TOP)]
    idle = devtrace.gaps([(o.start, o.end) for o in ops if o.device == 0],
                         lo, hi)
    idle.sort(key=lambda g: g[0] - g[1])
    gaps = []
    for s, e in idle[:TOP]:
        best, label = 0.0, "program host work"
        for name, a, b in t.spans:
            ov = min(b, e) - max(a, s)
            if ov > best:
                best, label = ov, name
        gaps.append([label, (e - s) * 1e-9])
    return busy * 1e-9, (hi - lo) * 1e-9, {"device_ops": device_ops,
                                            "idle_gaps": gaps}
