"""Process start -> window start (host clock): graph generation, the
choice of roots, ``from_edges`` + ``register``, and the warm-up (with
its compiles, or their load from the cache): a closed loop's burst of
the cell's own shape, or, for each class of an open loop, sets of one,
two, ... requests served together, every launch size the scheduler can
coalesce its arrivals into."""


def read(ctx):
    return ctx.setup_s
