"""(busiest chip's compute time - mean) / busiest over the traced part,
collectives left out: a chip that waits for a slower one waits inside
the all-reduce, so with them in every chip reads equally busy."""


def read(ctx):
    t = ctx["trace"]
    if t is None or ctx["chips"] < 2 or ctx["device"]["platform"] != "tpu":
        return None
    return 100.0 * t["chip_skew"]
