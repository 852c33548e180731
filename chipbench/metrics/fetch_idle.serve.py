"""Share of the traced window in which the first device ran nothing while
the host was inside the serving engine's completion ``fetch`` span
(``serving/engine.py`` ``_retire_finished``): the idle gaps' overlap with
the spans, not the span open at a gap's midpoint."""

import bisect

from chipbench import trace_reduce as TR

SPAN = "fetch"


def idle_under(red, name):
    """Seconds in the window in which the first device ran nothing while a
    host span called ``name`` was open; None without such a span."""
    spans = sorted((s, s + d) for n, s, d in red.host
                   if n == name and s + d > red.lo and s < red.hi)
    if not spans:
        return None
    ops = next(iter(red.devices.values()))["ops"]
    idle = TR.gaps([(o[1], o[1] + o[2]) for o in ops], red.lo, red.hi)
    starts, ends = [g[0] for g in idle], [g[1] for g in idle]
    total, done = 0.0, red.lo
    for s, e in spans:
        s = max(s, done)                  # overlapping spans count once
        if e > s:
            i, j = bisect.bisect_right(ends, s), bisect.bisect_left(starts, e)
            total += TR.union_length(idle[i:j], s, e)
        done = max(done, e)
    return total * 1e-9


def read(ctx):
    if ctx["info"]["kind"] != "serve":
        return None
    red = ctx["trace"]
    s = idle_under(red, SPAN)
    return None if s is None else 100.0 * s / red.window_s
