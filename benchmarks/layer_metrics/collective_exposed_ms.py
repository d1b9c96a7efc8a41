"""Per traced round: collective-op time during which no other op runs
on that chip, mean over the chips."""


def read(ctx):
    t = ctx["trace"]
    if t is None or ctx["chips"] < 2 or ctx["device"]["platform"] != "tpu":
        return None
    return 1e3 * t["collective_exposed_s"] / t["rounds"]
