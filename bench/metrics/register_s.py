"""Host seconds of ``from_edges`` + ``EngineSession.register``: CSR
build, probes, reorder, cache-model estimate and device upload."""


def read(ctx):
    return ctx.register_s
