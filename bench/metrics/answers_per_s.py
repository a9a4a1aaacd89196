"""Requests answered in the window over the window's seconds (host clock).
Closed loop: the window is whole bursts, from the first enqueue until
the burst in flight at ``--seconds`` completes. Open loop: the window
runs from the first arrival's due time to the moment the last answer of
the arrivals due in ``[0, --seconds)`` is ready."""


def read(ctx):
    return ctx.answered / ctx.window_s if ctx.window_s > 0 else None
