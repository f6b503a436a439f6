"""Seconds per round in the server's ``fl/slice`` spans: slicing each
client's trees out of the trained cohort, once per client, inside
``fl/pack`` (``fl/server.py``)."""
SPAN = "fl/slice"


def read(ctx):
    got = ctx["spans"].get(SPAN)
    return sum(got) / ctx["rounds"] if got else None
