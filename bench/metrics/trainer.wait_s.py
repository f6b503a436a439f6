"""Seconds per round in the server's ``fl/train_wait`` span: the host's
wait for the cohort trainer's losses and trained trees, inside
``fl/client_train`` (``fl/server.py``)."""
SPAN = "fl/train_wait"


def read(ctx):
    got = ctx["spans"].get(SPAN)
    return sum(got) / ctx["rounds"] if got else None
