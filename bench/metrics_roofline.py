"""Roofline share shared by the ``<kernel>_roofline`` metric readers.

The least time the window's launches of a kernel could take is their
least bytes (``bench/bytes/<kernel>.py``, from the real V, E, launches
and sources) at the chip's peak HBM bandwidth (``bench/peaks.json``);
the share is that over the kernel program's device seconds in the
reduced trace. The kernels do no arithmetic worth a compute bound, so
memory bounds them. The launches and sources are those of the kernel's
class (``ctx.classes``): the window's ``launch`` spans of that kernel,
and the sources its answered requests carried.
"""
from __future__ import annotations


def roofline_pct(ctx, kernel: str):
    cls = ctx.classes.get(kernel)
    if cls is None or ctx.trace is None or not ctx.peaks:
        return None
    seconds = sum(p["seconds"] for name, p in ctx.trace["programs"].items()
                  if cls.program in name)
    if seconds <= 0 or not cls.launches:
        return None
    from bench.harness import load_module
    least = load_module(cls.bytes_model).least_bytes(
        ctx.num_vertices, ctx.num_edges, cls.launches, cls.sources)
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / seconds
