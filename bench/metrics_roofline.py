"""Roofline share shared by the ``<kernel>_roofline`` metric readers.

The least time the window's launches could take is their least bytes
(``bench/bytes/<kernel>.py``, from the real V, E, launches and sources)
at the chip's peak HBM bandwidth (``bench/peaks.json``); the share is
that over the kernel program's device seconds in the reduced trace. The
kernels do no arithmetic worth a compute bound, so memory bounds them.
"""
from __future__ import annotations


def roofline_pct(ctx, kernel: str):
    if (ctx.traffic["kernel"] != kernel or ctx.trace is None
            or not ctx.peaks):
        return None
    seconds = sum(p["seconds"] for name, p in ctx.trace["programs"].items()
                  if ctx.traffic["program"] in name)
    launches = ctx.counters.get("engine_launches_total", 0)
    if seconds <= 0 or not launches:
        return None
    from bench.harness import load_module
    least = load_module(ctx.bytes_model).least_bytes(
        ctx.num_vertices, ctx.num_edges, launches,
        ctx.counters.get("engine_sources_total", 0))
    return 100.0 * least / ctx.peaks["hbm_bytes_per_s"] / seconds
