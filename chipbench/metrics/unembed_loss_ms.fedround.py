"""Device milliseconds per round of the operations under the
``unembed_loss`` scope (``models/transformer.py`` ``loss_fn``: the
unembedding, the f32 log-softmax and the NLL, forward and backward; scope
read from the compiled round's HLO metadata; summed over chips, divided by
them)."""

SCOPE = "unembed_loss"


def read(ctx):
    info = ctx["info"]
    if info["kind"] != "fedround" or not info["rounds"]:
        return None
    red = ctx["trace"]
    s = red.seconds_where(lambda r, op: SCOPE in r.scope(op)) / ctx["chips"]
    if s <= 0:
        return None
    return 1e3 * s / info["rounds"]
