"""Seconds per round in the server's ``fl/encode`` spans: each client's
uplink encode (``core/flocora.client_uplink``, which packs the flat tree
through ``core/flat.pack_flat``), once per client, inside ``fl/pack``."""
SPAN = "fl/encode"


def read(ctx):
    got = ctx["spans"].get(SPAN)
    return sum(got) / ctx["rounds"] if got else None
