"""Device time of the cell's kernel program per execution, from the
reduced profiler trace (the ``XLA Modules`` line, the class's
``program``). A mix of classes has no one program and reads nothing."""


def read(ctx):
    if ctx.trace is None or len(ctx.classes) != 1:
        return None
    (cls,) = ctx.classes.values()
    prog = [p for name, p in ctx.trace["programs"].items()
            if cls.program in name]
    count = sum(p["count"] for p in prog)
    if not count:
        return None
    return 1e3 * sum(p["seconds"] for p in prog) / count
