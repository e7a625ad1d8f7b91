"""Process start to the first timed dispatch: imports, service build,
compiles (or compile-cache reads) and the warm-up work."""


def read(ctx):
    return ctx["setup_s"]
