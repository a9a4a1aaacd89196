"""Share of the BFS levels in the window that pushed: the levels whose
frontier's out-arcs fit the push step's E/16 slots, so that they
scattered those arcs instead of gathering every in-arc. The backend's
``engine_push_steps_total`` over ``engine_kernel_steps_total``, both of
the ``bfs`` kernel (``ctx.kernel_counters``). A program without the push
step counts none, and the metric is left out."""


def read(ctx):
    counters = ctx.kernel_counters.get("bfs", {})
    pushes = counters.get("engine_push_steps_total")
    steps = counters.get("engine_kernel_steps_total")
    if pushes is None or not steps:
        return None
    return 100.0 * pushes / steps
