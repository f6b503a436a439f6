"""Seconds per round in the server's ``fl/aggregate`` span: the cohort
aggregate, waited on while tracing, so the span holds its device time
(``fl/server.py``)."""
SPAN = "fl/aggregate"


def read(ctx):
    got = ctx["spans"].get(SPAN)
    return sum(got) / ctx["rounds"] if got else None
