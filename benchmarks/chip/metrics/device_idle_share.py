"""Share of the traced window, in %, in which no operation ran on the
device: 1 - (union of the device's operation intervals) / window,
averaged over the chips the cell uses."""
import devtrace


def read(ctx):
    t = ctx.get("trace")
    if t is None:
        return None
    lo, hi = ctx["trace_window"]
    busy = devtrace.busy_share(t, lo, hi, ctx["chips"])
    return 100.0 * (1.0 - busy / (hi - lo))
