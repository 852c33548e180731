"""Device milliseconds per round in collective operations (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all), summed over
the cell's chips and divided by them.  Silent on a window with no
collective: one chip has none."""


def read(ctx):
    info = ctx["info"]
    if info["kind"] != "fedround" or not info["rounds"]:
        return None
    red = ctx["trace"]
    s = sum(red.collective_s(d) for d in red.devices) / ctx["chips"]
    if s <= 0:
        return None
    return 1e3 * s / info["rounds"]
