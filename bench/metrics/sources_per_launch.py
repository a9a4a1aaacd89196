"""Real (unpadded) sources per device launch in the window: the
backends' ``engine_sources_total`` over the scheduler's
``engine_launches_total``."""


def read(ctx):
    launches = ctx.counters.get("engine_launches_total", 0)
    if not launches:
        return None
    return ctx.counters.get("engine_sources_total", 0) / launches
