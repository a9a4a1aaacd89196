"""Passes of the in-CSR segmented reduction per loop step in the window:
the backend's ``engine_segment_passes_total`` (each launch's passes per
step times its steps) over ``engine_kernel_steps_total``. The pass count
is ⌈log2⌉ of the graph's largest real in-degree."""


def read(ctx):
    passes = ctx.counters.get("engine_segment_passes_total")
    steps = ctx.counters.get("engine_kernel_steps_total")
    if passes is None or not steps:
        return None
    return passes / steps
