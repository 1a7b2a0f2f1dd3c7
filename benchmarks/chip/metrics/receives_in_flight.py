"""Mean number of earlier receives the device had not finished when the
master dispatched one of the window's receives (the ``in_flight`` the
program's ``master.apply`` span carries, read without a sync).  Each
unfinished receive holds its own state buffers, so this is how far
dispatch runs ahead of the device."""
import bench

spans = bench.load_module(bench.HERE / "metrics" / "worker_dispatch_ms.py")


def read(ctx):
    got = [e["args"]["in_flight"] for e in spans.window_receives(ctx) or ()
           if "in_flight" in e["args"]]
    return sum(got) / len(got) if got else None
