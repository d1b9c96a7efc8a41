"""Per traced round: the steady window's length minus the device's busy
time in it — what the round loop spends with the chip idle."""


def read(ctx):
    t = ctx["trace"]
    if t is None or ctx["device"]["platform"] != "tpu":
        return None
    return 1e3 * (t["window_s"] - t["per_chip_busy_s"][0]) / t["rounds"]
