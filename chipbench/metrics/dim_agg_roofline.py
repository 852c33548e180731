"""The ``dim_agg`` kernel's share of its roofline: for each call the
larger of its required operations over the bf16 peak and its required
bytes over HBM bandwidth (``flops.dim_agg_round``), summed over the
rounds of the traced window, over the kernel's device time there."""

KERNEL = "dim_agg_pallas"   # the pallas_call's HLO instruction name


def read(ctx):
    info = ctx["info"]
    if info["kind"] != "fedround" or not info["rounds"]:
        return None
    red, pk = ctx["trace"], ctx["peaks"]
    t = red.kernel_seconds(KERNEL)
    if t <= 0:
        return None
    bound = sum(max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"])
                for f, b in info["dim_agg_calls"]) * info["rounds"]
    return 100.0 * bound * ctx["chips"] / t
