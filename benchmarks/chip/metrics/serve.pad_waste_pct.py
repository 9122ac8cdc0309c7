"""Share of the panel slots the engine executed in the window that were
zero padding, in percent (engine counters ``pad_slots / panel_slots``)."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("panel_slots"):
        return None
    return 100.0 * c["pad_slots"] / c["panel_slots"]
