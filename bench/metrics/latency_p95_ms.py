"""95th percentile of request latency over every request of the window
(host clock; not a median of chunks). Closed loop: enqueue ->
``QueryFuture.result()`` returned, read in enqueue order. Open loop:
the request's due time -> the moment its answer is ready."""
import numpy as np


def read(ctx):
    if not ctx.latencies_s:
        return None
    return 1e3 * float(np.percentile(ctx.latencies_s, 95))
