"""setup_s (s): process start to the first timed step: loading, weights
and batch pool, and the first three steps, compiling included."""


def read(ctx):
    return ctx.setup_s
