"""setup_s (s, host clock): process start to window start: JAX and chip
start-up, the generator's start, session allocation, the programs compiled
or loaded from the cache, and the warm-up serve."""


def read(ctx):
    return ctx["setup_s"]
