"""Host seconds per launch that the scheduler spends handing the rows
out: its ``cache_fill`` span (each fresh row copied and put in the
result cache; none with the cache off) plus its ``slice_out`` span
(each request's rows stacked and its future resolved), over the
window's ``launch`` spans. A program that does not span its result path
(no ``d2h`` span) reads nothing: there an unspanned copy could not be
told from an empty one."""

NAMES = ("cache_fill", "slice_out")


def read(ctx):
    names = [s["name"] for s in ctx.spans]
    launches = names.count("launch")
    if not launches or "d2h" not in names:
        return None
    total = sum(s["dur"] for s in ctx.spans if s["name"] in NAMES)
    return 1e-3 * total / launches
