"""Process start -> window start (host clock): graph generation,
``from_edges`` + ``register``, the warm-up burst of the cell's own
shape (with its compiles, or their load from the cache)."""


def read(ctx):
    return ctx.setup_s
