"""The decode program's share of its roofline: for each ``serve_step``
dispatch of the traced window the larger of its required operations over
the bf16 peak and its required bytes over HBM bandwidth (weights, live
cache rows at their real lengths, gathered adapters;
``flops.serve_dispatch``), summed, over the device time of the
``serve_step`` program runs."""


def read(ctx):
    info = ctx["info"]
    if info["kind"] != "serve":
        return None
    red, pk = ctx["trace"], ctx["peaks"]
    runs = red.module_runs(lambda m: m == "jit_serve_step")
    t = sum(d for _, d in runs) * 1e-9
    steps = [d for d in info["dispatches"] if d["kind"] == "serve_step"]
    if t <= 0 or not steps:
        return None
    bound = sum(max(d["flops"] / pk["bf16_flops"],
                    d["bytes"] / pk["hbm_bytes_per_s"]) for d in steps)
    return 100.0 * bound / t
