"""Host clock around population generation, partition and placement."""


def read(ctx):
    return ctx["data_build_s"]
