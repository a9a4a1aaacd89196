"""Device time of the cell's kernel program per execution, from the
reduced profiler trace (the ``XLA Modules`` line, the traffic file's
``program``)."""


def read(ctx):
    if ctx.trace is None:
        return None
    prog = [p for name, p in ctx.trace["programs"].items()
            if ctx.traffic["program"] in name]
    count = sum(p["count"] for p in prog)
    if not count:
        return None
    return 1e3 * sum(p["seconds"] for p in prog) / count
