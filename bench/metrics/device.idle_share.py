"""Percent of the traced window in which no operation ran on the device:
one minus the union of the device's operation intervals over the
window, from the profiler trace."""


def read(ctx):
    return 100.0 * ctx["trace"]["idle_share"]
