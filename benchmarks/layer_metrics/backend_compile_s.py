"""Backend compile seconds during warm-up (``jax.monitoring``): a read
of the persistent cache when the program is there."""


def read(ctx):
    return ctx["compile"]["backend_compile_s"]
