"""Required operations of every decode and prefill dispatch in the traced
window (``flops.serve_dispatch``) over the window times the chips' bf16
peak."""


def read(ctx):
    info = ctx["info"]
    if info["kind"] != "serve" or not info["dispatches"]:
        return None
    red = ctx["trace"]
    done = sum(d["flops"] for d in info["dispatches"])
    return 100.0 * done / (red.window_s * ctx["chips"]
                           * ctx["peaks"]["bf16_flops"])
