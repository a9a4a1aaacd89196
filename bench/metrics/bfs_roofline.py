"""BFS launches' share of the HBM roofline: the least bytes any BFS of
the window's launches must move (``bench/bytes/bfs.py``) at the chip's
peak bandwidth, over the kernel program's device time in the trace."""
from bench.metrics_roofline import roofline_pct


def read(ctx):
    return roofline_pct(ctx, "bfs")
