"""XLA and Mosaic compilations inside the measured window
(``repro.sanitize.CompileEvents``)."""


def read(ctx):
    return ctx.compiles
