"""Compiles inside the measured window: the backend's executable-cache
misses (``engine_compile_cache_misses_total``) plus XLA backend compiles
that JAX reported while the window ran. The warm-up makes it 0."""


def read(ctx):
    return (ctx.counters.get("engine_compile_cache_misses_total", 0)
            + ctx.xla_compiles)
