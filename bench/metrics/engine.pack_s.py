"""Seconds per round in the server's ``fl/pack`` span: the per-client
loop that slices each trained tree out of the cohort and packs its
uplink (``fl/server.py``)."""
SPAN = "fl/pack"


def read(ctx):
    got = ctx["spans"].get(SPAN)
    return sum(got) / ctx["rounds"] if got else None
