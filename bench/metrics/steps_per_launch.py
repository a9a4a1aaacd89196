"""Loop steps (BFS levels, Bellman-Ford rounds) per launch in the
window: the backend's ``engine_kernel_steps_total``, each launch's trip
count read back from its program, over ``engine_launches_total``."""


def read(ctx):
    steps = ctx.counters.get("engine_kernel_steps_total")
    launches = ctx.counters.get("engine_launches_total", 0)
    if steps is None or not launches:
        return None
    return steps / launches
