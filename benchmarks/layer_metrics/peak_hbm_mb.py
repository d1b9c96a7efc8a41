"""``peak_bytes_in_use + peak_bytes_reserved`` of the fullest chip after
the window, before the reference runs: the arrays that live on the chip
and the scratch its loaded programs hold (the result line's ``device``
gives the two apart)."""


def read(ctx):
    if ctx["device"]["platform"] != "tpu":
        return None
    return ctx["memory_peak_bytes"] / 1e6
