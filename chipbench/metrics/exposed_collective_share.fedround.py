"""Share of the traced window in which a collective runs on a chip and no
other operation does, averaged over the cell's chips: the communication
that compute does not hide.  Silent on a window with no collective.

The quantity is ``Reduced.exposed_collective_s``, computed in one sweep:
that method sorts every compute op of the chip once per collective, and a
four-chip round trace holds tens of thousands of collectives among more
than a million ops."""

import bisect

from chipbench import trace_reduce as TR


def exposed_s(red, dev) -> float:
    """Seconds of ``dev``'s collectives, clipped to the window, that no
    compute op on ``dev`` overlaps (overlapping collectives each count)."""
    ops = [o for o in red.devices[dev]["ops"]
           if o[1] + o[2] > red.lo and o[1] < red.hi]
    starts, ends, cum = [], [], [0]      # the compute ops' union, disjoint
    for s, e in sorted((o[1], o[1] + o[2]) for o in ops
                       if not TR.is_collective(o)):
        if starts and s <= ends[-1]:
            if e > ends[-1]:
                cum[-1] += e - ends[-1]
                ends[-1] = e
        else:
            starts.append(s)
            ends.append(e)
            cum.append(cum[-1] + e - s)

    def covered(x):                      # union length in (-inf, x]
        k = bisect.bisect_right(starts, x) - 1
        return 0 if k < 0 else cum[k] + min(x, ends[k]) - starts[k]

    total = 0
    for o in ops:
        if TR.is_collective(o):
            s, e = max(o[1], red.lo), min(o[1] + o[2], red.hi)
            if e > s:
                total += (e - s) - (covered(e) - covered(s))
    return total * 1e-9


def read(ctx):
    info = ctx["info"]
    if info["kind"] != "fedround" or not info["rounds"]:
        return None
    red = ctx["trace"]
    if not any(red.collective_s(d) > 0 for d in red.devices):
        return None
    exposed = sum(exposed_s(red, d) for d in red.devices)
    return 100.0 * exposed / len(red.devices) / red.window_s
