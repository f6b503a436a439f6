"""Percent of the chips' bf16 peak over whole rounds: the useful model
FLOPs of the traced rounds (the family's FLOP formula times the active
local steps) over their wall time, the chips and the peak."""


def read(ctx):
    if not ctx["n_steps"] or ctx["wall_s"] <= 0:
        return None
    flops = sum(int(s.sum()) for s in ctx["n_steps"]) * ctx["step_flops"]
    return 100.0 * flops / (ctx["wall_s"] * ctx["chips"]
                            * ctx["peaks"]["bf16_flops_per_s"])
