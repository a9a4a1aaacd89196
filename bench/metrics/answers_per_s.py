"""Requests answered in the window over the window's seconds (host clock).
The window is whole bursts: it ends when the burst in flight at
``--seconds`` completes."""


def read(ctx):
    return ctx.answered / ctx.window_s if ctx.window_s > 0 else None
