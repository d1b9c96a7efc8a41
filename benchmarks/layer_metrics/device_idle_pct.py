"""1 - busy / window of the steady traced part, mean over the chips."""


def read(ctx):
    t = ctx["trace"]
    if t is None or ctx["device"]["platform"] != "tpu":
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
