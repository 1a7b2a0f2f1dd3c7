"""Median time, in ms, from a worker's push into the mailbox to the
master's reply in its hand (the program's ``worker.rpc`` span: queueing,
the master's receive dispatch and reply), over the window's gradients."""
import bench

spans = bench.load_module(bench.HERE / "metrics" / "worker_dispatch_ms.py")


def read(ctx):
    return spans.median_ms(ctx, "worker.rpc")
