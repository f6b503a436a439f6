"""Seconds per round in the server's ``fl/stage_batches`` span: stacking
(and padding) every client's batches on the host, inside ``fl/broadcast``
(``fl/server.py``)."""
SPAN = "fl/stage_batches"


def read(ctx):
    got = ctx["spans"].get(SPAN)
    return sum(got) / ctx["rounds"] if got else None
