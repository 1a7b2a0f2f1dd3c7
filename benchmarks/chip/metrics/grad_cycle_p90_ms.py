"""90th percentile, over every gradient completed in the window on every
worker, of the time between a worker's consecutive requests for a batch:
batch, backward to wire, push, master apply and reply.  Asynchronous
cells only (the percentile is numpy's linear interpolation)."""
import numpy as np


def read(ctx):
    cycles = ctx.get("cycles_ms")
    if not cycles:
        return None
    return float(np.percentile(np.asarray(cycles, np.float64), 90))
