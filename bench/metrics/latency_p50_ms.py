"""Median request latency over every request of the window (host
clock). Closed loop: enqueue -> ``QueryFuture.result()`` returned, read
in enqueue order. Open loop: the request's due time -> the moment its
answer is ready, so a stall counts against every request due during it
and a fast answer is not timed behind a slow one."""
import numpy as np


def read(ctx):
    if not ctx.latencies_s:
        return None
    return 1e3 * float(np.percentile(ctx.latencies_s, 50))
