"""The master thread's host time per gradient, in ms: the durations of
the window's receives (the program's ``master.apply`` spans: stacking,
id and time transfers, the receive's dispatch, the replies) summed, over
the window's gradients."""
import bench

spans = bench.load_module(bench.HERE / "metrics" / "worker_dispatch_ms.py")


def read(ctx):
    got = spans.window_receives(ctx)
    if not got:
        return None
    return 1e-3 * sum(e["dur"] for e in got) / ctx["grads"]
