"""Percent of the chip's bf16 peak that the cohort trainer reaches on
useful work: the model FLOPs of the active local steps (the family's FLOP
formula) over the trainer program's device time in the trace times the
peak. The trainer is the jitted, vmapped local run, ``jit_run``."""
PROGRAM = "jit_run"


def read(ctx):
    t = ctx["trace"]["programs"].get(PROGRAM, 0.0)
    if t <= 0 or not ctx["n_steps"]:
        return None
    flops = sum(int(s.sum()) for s in ctx["n_steps"]) * ctx["step_flops"]
    return 100.0 * flops / (t * ctx["peaks"]["bf16_flops_per_s"])
