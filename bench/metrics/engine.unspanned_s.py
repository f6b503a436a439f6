"""Seconds per round that no phase of the round names: the server's
``fl/round`` span less its five top-level phases (``fl/server.py``)."""
ROUND = "fl/round"
PHASES = ("fl/broadcast", "fl/client_train", "fl/pack", "fl/uplink",
          "fl/aggregate")


def read(ctx):
    spans = ctx["spans"]
    got = spans.get(ROUND)
    if not got:
        return None
    phases = sum(sum(spans.get(p, ())) for p in PHASES)
    return (sum(got) - phases) / ctx["rounds"]
