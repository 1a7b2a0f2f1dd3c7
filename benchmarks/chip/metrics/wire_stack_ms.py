"""Device time of the master's wire stacking (``jnp.stack`` of the
drained gradients into one (k, R, 128) buffer, a dispatch of its own)
per gradient, in ms, inside the traced window.  At k = 1 the stack runs
as an eager reshape (program ``jit_reshape``, one full copy)."""
import devtrace

PROGRAMS = ("jit_stack", "jit_concatenate", "jit_reshape",
            "jit_expand_dims")


def read(ctx):
    t = ctx.get("trace")
    if t is None or not ctx.get("grads"):
        return None
    lo, hi = ctx["trace_window"]
    ops = devtrace.module_ops(t, lo, hi,
                              lambda m: m.startswith(PROGRAMS))
    if not ops:
        return None
    return 1e-6 * devtrace.busy_ns(ops, lo, hi) / ctx["grads"]
