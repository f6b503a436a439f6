"""Percent of the cohort trainer's local steps that train: the clients'
active step counts over clients times the schedule's steps, summed over
the traced rounds (the arguments the trainer was given)."""


def read(ctx):
    steps = ctx["n_steps"]
    if not steps or not ctx["schedule_steps"]:
        return None
    slots = sum(len(s) for s in steps) * ctx["schedule_steps"]
    return 100.0 * sum(int(s.sum()) for s in steps) / slots
