"""Device milliseconds per round of the operations under the round
program's ``fedround.local_train`` scope (the compiled program's HLO
metadata names each op's scope; summed over chips, divided by them)."""

SCOPE = "fedround.local_train"


def read(ctx):
    info = ctx["info"]
    if info["kind"] != "fedround" or not info["rounds"]:
        return None
    red = ctx["trace"]
    s = red.seconds_where(lambda r, op: SCOPE in r.scope(op)) / ctx["chips"]
    if s <= 0:
        return None
    return 1e3 * s / info["rounds"]
