"""Seconds per round in the server's ``fl/broadcast`` span: the broadcast
decode, the staging of every client's batches on the host and their copy
to the device (``fl/server.py``)."""
SPAN = "fl/broadcast"


def read(ctx):
    got = ctx["spans"].get(SPAN)
    return sum(got) / ctx["rounds"] if got else None
