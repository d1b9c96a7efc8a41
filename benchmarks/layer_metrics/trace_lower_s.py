"""Python tracing plus lowering of the cell's programs during warm-up
(``jax.monitoring``): the part of set-up no compile cache removes."""


def read(ctx):
    return ctx["compile"]["trace_s"] + ctx["compile"]["lower_s"]
