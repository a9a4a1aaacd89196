"""Mean ``unpermute`` span per launch in the window: the session's
gather of the launch's rows back to original vertex ids through the
reorder permutation, after the ``launch`` span has closed."""


def read(ctx):
    gathers = [s["dur"] for s in ctx.spans if s["name"] == "unpermute"]
    return 1e-3 * sum(gathers) / len(gathers) if gathers else None
