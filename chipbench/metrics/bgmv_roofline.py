"""The grouped LoRA (BGMV) kernel's share of its roofline: each call's
larger of required operations over the bf16 peak and required bytes over
HBM bandwidth (``flops.bgmv_call``: every row the call is given, its
adapters gathered once), summed over the calls of the traced window's
dispatches, over the kernel's device time."""

from chipbench import flops

KERNEL = "grouped_lora_matmul_pallas"   # its HLO instruction name


def read(ctx):
    info = ctx["info"]
    if info["kind"] != "serve" or not info["dispatches"]:
        return None
    red, pk = ctx["trace"], ctx["peaks"]
    t = red.kernel_seconds(KERNEL)
    if t <= 0:
        return None
    bound = 0.0
    for d in info["dispatches"]:
        for k, n in info["bgmv_shapes"]:
            f, b = flops.bgmv_call(d["rows"], k, n, info["bank_rank"],
                                   d["adapters"])
            bound += info["layers"] * max(f / pk["bf16_flops"],
                                          b / pk["hbm_bytes_per_s"])
    return 100.0 * bound / t
