"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips (federated cells)."""


def read(ctx):
    if ctx["info"]["kind"] != "fedround":
        return None
    red = ctx["trace"]
    return 100.0 * (1.0 - red.mean_busy_s() / red.window_s)
