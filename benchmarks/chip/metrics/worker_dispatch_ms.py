"""Median host time, in ms, of the worker's call that dispatches its
backward->wire program (the program's ``worker.grad`` span: the
dispatch, the batch's transfer and any blocking by the runtime), over
the window's gradients.

The window selection the other span readers load from here: the
window's gradients are the last ``grads`` the master applied, by apply
step.  A gradient is known by its ``(worker, seq)``; its step is the one
its ``worker.rpc`` span carries.  Spans are in ``stats["spans"]`` (the
program's trace-event format, times in us); without them every reader
returns None."""
import statistics


def spans(ctx, name):
    """The window call's complete spans named ``name``, or None."""
    got = (ctx.get("stats") or {}).get("spans")
    if not got or not ctx.get("grads"):
        return None
    return [e for e in got if e.get("ph") == "X" and e.get("name") == name]


def window_grads(ctx):
    """``{(worker, seq): step}`` of the window's gradients, or None."""
    rpc = spans(ctx, "worker.rpc")
    steps = {(e["args"]["worker"], e["args"]["seq"]): e["args"]["step"]
             for e in rpc or () if "step" in e.get("args", {})
             and e["args"]["seq"] >= 0}
    if not steps:
        return None
    first = max(steps.values()) - ctx["grads"] + 1
    return {g: s for g, s in steps.items() if s >= first}


def window_receives(ctx):
    """The window's ``master.apply`` spans: receives that applied one of
    the window's gradients, or None."""
    grads = window_grads(ctx)
    applies = spans(ctx, "master.apply")
    if not grads or not applies:
        return None
    first = min(grads.values())
    got = [e for e in applies
           if e["args"]["step"] + e["args"]["k"] - 1 >= first]
    return got or None


def median_ms(ctx, name):
    """Median duration, in ms, of the window's gradients' ``name`` spans."""
    grads = window_grads(ctx)
    durs = [e["dur"] for e in spans(ctx, name) or ()
            if (e["args"]["worker"], e["args"]["seq"]) in (grads or {})]
    return 1e-3 * statistics.median(durs) if durs else None


def read(ctx):
    return median_ms(ctx, "worker.grad")
