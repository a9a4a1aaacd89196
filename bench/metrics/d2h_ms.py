"""Mean ``d2h`` span per launch in the window: the session's
device-to-host copy of the launch's rows (``np.asarray``), a child of
``launch`` after its ``device_sync``."""


def read(ctx):
    copies = [s["dur"] for s in ctx.spans if s["name"] == "d2h"]
    return 1e-3 * sum(copies) / len(copies) if copies else None
