"""95th percentile of enqueue -> ``QueryFuture.result()`` returned, over
every request of the window (host clock; not a median of chunks)."""
import numpy as np


def read(ctx):
    if not ctx.latencies_s:
        return None
    return 1e3 * float(np.percentile(ctx.latencies_s, 95))
