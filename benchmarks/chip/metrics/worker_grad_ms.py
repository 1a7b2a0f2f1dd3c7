"""Device time of the worker's backward->wire program per gradient, in
ms: the union of its operations' intervals inside the traced window,
over the gradients completed in the window."""
import devtrace

PROGRAM = "jit__lambda"     # runtime.flat_grad_program's jitted lambda


def read(ctx):
    t = ctx.get("trace")
    if t is None or not ctx.get("grads"):
        return None
    lo, hi = ctx["trace_window"]
    ops = devtrace.module_ops(t, lo, hi, lambda m: m.startswith(PROGRAM))
    if not ops:
        return None
    return 1e-6 * devtrace.busy_ns(ops, lo, hi) / ctx["grads"]
