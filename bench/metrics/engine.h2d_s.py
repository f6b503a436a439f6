"""Seconds per round in the server's ``fl/h2d`` span: the copy of the
cohort's batches to the device, waited on while tracing, inside
``fl/broadcast`` (``fl/server.py``)."""
SPAN = "fl/h2d"


def read(ctx):
    got = ctx["spans"].get(SPAN)
    return sum(got) / ctx["rounds"] if got else None
