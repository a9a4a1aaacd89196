"""Median request queue wait in the window: the scheduler's ``queue_wait``
spans (enqueue -> launch start), one per request; the same observations
``engine_queue_wait_seconds`` buckets."""
import numpy as np


def read(ctx):
    waits = [s["dur"] for s in ctx.spans if s["name"] == "queue_wait"]
    return 1e-3 * float(np.median(waits)) if waits else None
