"""The master's fused receive kernel's share of its HBM roofline, in %
(kernels/flat_update/kernel.py, found by the kernel's name):
the bytes the calls inside the traced window need, over the kernel's
device time there, over the HBM peak.  Each call is counted at its own
k (the messages it applies), read from its operands: the stacked
(k, R, 128) gradient is the kernel's last three-dimensional operand."""
import re

import devtrace

KERNEL = "flat_master_update_batch"   # the Pallas kernel's own name


def bytes_per_call(elements: int, workers: int, k: int) -> float:
    """DANA-Zero's receive of k messages over ``elements`` f32 values:
    theta and v0 read and written (4 copies), the N momentum slabs read
    and written (2N), k gradients read and k look-ahead views written
    (2k): 4 * elements * (2 * (2 + N) + 2 * k) bytes."""
    return 4.0 * elements * (2 * (2 + workers) + 2 * k)


def call_k(op) -> int | None:
    text = op.long_name
    at = text.find("custom-call(")
    if at < 0:
        return None
    shapes = re.findall(r"f32\[(\d+),\d+,128\]", text[at:])
    return int(shapes[-1]) if shapes else None


def kernel_ops(t, lo, hi):
    return [o for o in t.ops_in(lo, hi, 0) if o.name.startswith(KERNEL)]


def read(ctx):
    t = ctx.get("trace")
    if t is None or ctx.get("peaks") is None:
        return None
    lo, hi = ctx["trace_window"]
    ops = kernel_ops(t, lo, hi)
    ks = [call_k(o) for o in ops]
    if not ops or None in ks:
        return None
    need = sum(bytes_per_call(ctx["params"], ctx["traffic"]["workers"], k)
               for k in ks)
    busy_s = 1e-9 * devtrace.busy_ns(ops, lo, hi)
    return 100.0 * need / busy_s / ctx["peaks"]["hbm_bytes_per_s"]
