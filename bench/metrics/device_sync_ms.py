"""Mean ``device_sync`` span per launch in the window: the backend's
wait in ``block_until_ready`` for the launched program."""


def read(ctx):
    syncs = [s["dur"] for s in ctx.spans if s["name"] == "device_sync"]
    return 1e-3 * sum(syncs) / len(syncs) if syncs else None
