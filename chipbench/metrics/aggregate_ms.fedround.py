"""Device milliseconds per round of the operations under the round
program's ``fedround.aggregate`` scope (the registry's aggregation, Pallas
``dim_agg`` or jnp, with fault absorption; scope read from the compiled
round's HLO metadata; summed over chips, divided by them)."""

SCOPE = "fedround.aggregate"


def read(ctx):
    info = ctx["info"]
    if info["kind"] != "fedround" or not info["rounds"]:
        return None
    red = ctx["trace"]
    s = red.seconds_where(lambda r, op: SCOPE in r.scope(op)) / ctx["chips"]
    if s <= 0:
        return None
    return 1e3 * s / info["rounds"]
