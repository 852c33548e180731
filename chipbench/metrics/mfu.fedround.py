"""Required operations of the rounds completed in the traced window (local
training forward and backward, ``flops.train_round``) over the window
times the chips' bf16 peak."""


def read(ctx):
    info = ctx["info"]
    if info["kind"] != "fedround" or not info["rounds"]:
        return None
    red = ctx["trace"]
    done = info["rounds"] * info["round_flops"]
    return 100.0 * done / (red.window_s * ctx["chips"]
                           * ctx["peaks"]["bf16_flops"])
