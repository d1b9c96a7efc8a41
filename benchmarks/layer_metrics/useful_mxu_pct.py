"""Matrix work the traced rounds needed over what the chips could do
while the round program kept them busy: FLOPs of one client step
(``lib/refnet.step_flops``) x the client steps those rounds really
executed / (peak FLOP/s x chips x round-program busy seconds)."""


def read(ctx):
    t = ctx["trace"]
    if t is None or ctx["peaks"] is None or not t["round_program_busy_s"]:
        return None
    # round_program_busy_s is chip 0's; every chip runs the same program
    denom = (ctx["peaks"]["flops_per_s"] * ctx["chips"]
             * t["round_program_busy_s"])
    return 100.0 * ctx["step_flops"] * ctx["client_steps"] / denom
