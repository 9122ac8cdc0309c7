"""Requests answered per panel executed in the window (engine counters
``served / applies``)."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("panels"):
        return None
    return c["served"] / c["panels"]
