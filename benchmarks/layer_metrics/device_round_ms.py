"""Device-busy time of the compiled round program per traced round
(union of op intervals inside the round program's module events)."""


def read(ctx):
    t = ctx["trace"]
    if t is None or ctx["device"]["platform"] != "tpu":
        return None
    busy = t["round_program_busy_s"]
    if busy is None:
        return None
    return 1e3 * busy / t["rounds"]
