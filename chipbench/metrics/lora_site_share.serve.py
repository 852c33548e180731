"""Share of the serving programs' busy device time spent in LoRA-carrying
projections: the device seconds of the ops under the ``lora_site`` scope
(``core/lora.py``: base matmul and adapter delta, whatever implements
them; the compiled programs' HLO metadata names each op's scope) over the
device's busy seconds, both clipped to the traced window and summed over
chips."""

SCOPE = "lora_site"


def read(ctx):
    if ctx["info"]["kind"] != "serve":
        return None
    red = ctx["trace"]
    s = red.seconds_where(lambda r, op: SCOPE in r.scope(op))
    busy = sum(red.busy_s(d) for d in red.devices)
    if s <= 0 or busy <= 0:
        return None
    return 100.0 * s / busy
