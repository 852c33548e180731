"""The FLOP and byte functions against hand counts at a tiny size, and the
table of peaks."""

import pytest

from chipbench import flops, peaks

DM = {"layers": 2, "d": 8, "heads": 2, "kv_heads": 1, "head_dim": 4,
      "ff": 16, "vocab": 32, "bias": True, "eps": 1e-6, "theta": 1e4,
      "dtype": "bfloat16"}


def test_block_params():
    # wq 8x8, wk and wv 8x4, wo 8x8, w1 w3 8x16, w2 16x8
    assert flops.block_matmul_params(DM) == 64 + 32 + 32 + 64 + 3 * 128


def test_train_sequence():
    base = 2 * (2 * 576 + 8 * 32) * 4          # forward, 4 tokens
    lora = (6 * 2 * (8 + 8) + 6 * 2 * (8 + 4)) * 2 * 4
    attn = 6 * 4 * 4 * 4 * 2 * 2                # 6 S^2 hd h L
    assert flops.train_sequence(DM, seq=4, rank=2) == 2 * base + lora + attn
    assert flops.train_round(DM, clients=2, steps=3, batch=2, seq=4,
                             ranks=[2, 2]) == 12 * (2 * base + lora + attn)


def test_serve_tokens():
    assert flops.decode_token_flops(DM, context=3, rank=2) == \
        2 * (2 * 576 + 8 * 32) + 2 * (4 * 3 * 4 * 2 + 2 * 2 * 16 + 2 * 2 * 12)
    assert flops.prefill_token_flops(DM, position=2, rank=2) == \
        2 * (2 * 576 + 4 * 3 * 4 * 2 + 2 * 2 * 16 + 2 * 2 * 12)


def test_bytes():
    per_layer = 576 + 2 * 8 + (2 + 2) * 4      # matrices, norms, biases
    assert flops.weight_bytes(DM) == 2 * (2 * per_layer + 8 + 32 * 8)
    assert flops.weight_bytes(DM, unembed=False) == 2 * (2 * per_layer + 8)
    assert flops.kv_bytes_per_token(DM) == 2 * 2 * 2 * 1 * 4
    assert flops.adapter_bytes(DM, 2) == 4 * 2 * (2 * 16 + 2 * 12)


def test_serve_dispatch():
    fl, by = flops.serve_dispatch(DM, [(2, 1, 2), (0, 1, 2)], [2],
                                  decode=True)
    assert fl == (flops.decode_token_flops(DM, 3, 2)
                  + flops.decode_token_flops(DM, 1, 2))
    assert by == flops.weight_bytes(DM) + 32 * (3 + 1 + 2) \
        + flops.adapter_bytes(DM, 2)
    fl, by = flops.serve_dispatch(DM, [(0, 3, 2)], [2], decode=False)
    assert fl == sum(flops.prefill_token_flops(DM, p, 2) for p in range(3))
    assert by == flops.weight_bytes(DM, unembed=False) + 32 * 6 \
        + flops.adapter_bytes(DM, 2)


def test_kernels():
    calls = flops.dim_agg_round(DM, cohort=3, r_g=2)
    assert [c for c, _ in calls] == [24.0 * n for n in (8, 8, 8, 4)]
    assert [b for _, b in calls] == [64 * n + 24 for n in (8, 8, 8, 4)]
    assert flops.bgmv_call(3, 8, 4, 2, adapters=2) == (
        2 * 3 * 8 * 4 + 2 * 3 * 2 * 12, 2 * (24 + 32 + 12) + 4 * 2 * 2 * 12)


def test_peaks():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
