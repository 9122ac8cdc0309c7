"""Share of the device time in which an ``all-to-all`` runs and no other
operation runs on that chip, in percent, averaged over the chips. The
device time is the union of all device operations in the traced window:
the halo apply, its scatter and gather, and the column generator."""


def read(ctx):
    red = ctx["trace"]
    if red["busy_s"] <= 0 or red["collective_s"] <= 0:
        return None
    return 100.0 * red["exposed_collective_s"] / red["busy_s"]
