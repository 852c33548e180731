"""Operations and bytes that the algorithm requires, from shapes alone.

``dm`` is ``model.dims(config)``.  Matrix products count 2 operations per
multiply-add; elementwise work (norms, softmax, optimizer) is not counted.
Bytes count each operand read once and each result written once, at the
dtype it is stored in (weights bfloat16, adapters float32).
"""

from __future__ import annotations

BF16, F32 = 2, 4


def block_matmul_params(dm: dict) -> int:
    """Weights multiplied per token in one decoder layer."""
    d, h, kv, hd, ff = dm["d"], dm["heads"], dm["kv_heads"], dm["head_dim"], \
        dm["ff"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff


def lora_site_dims(dm: dict) -> list[tuple[int, int]]:
    """(in, out) of the adapted projections of one layer: query, value."""
    d, hd = dm["d"], dm["head_dim"]
    return [(d, dm["heads"] * hd), (d, dm["kv_heads"] * hd)]


def train_sequence(dm: dict, seq: int, rank: int) -> float:
    """One training sequence, forward and backward, frozen base: the base's
    matrix products forward and once more backward (input gradients only,
    for every layer and the tied unembedding); the LoRA products forward,
    for the input gradient, and for the A and B gradients; causal attention
    counted once (half of the S×S products), forward and twice backward.
    No recompute."""
    L, V, d = dm["layers"], dm["vocab"], dm["d"]
    base = 2 * (L * block_matmul_params(dm) + d * V) * seq
    lora = sum(6 * rank * (i + o) for i, o in lora_site_dims(dm)) * L * seq
    attn = 6 * seq * seq * dm["head_dim"] * dm["heads"] * L
    return 2 * base + lora + attn


def train_round(dm: dict, *, clients: int, steps: int, batch: int, seq: int,
                ranks) -> float:
    """A round's local training: each client's ``steps × batch`` sequences
    at its own rank (``ranks`` has one entry per cohort client)."""
    assert len(ranks) == clients, (ranks, clients)
    return sum(steps * batch * train_sequence(dm, seq, r) for r in ranks)


def dim_agg_round(dm: dict, *, cohort: int, r_g: int) -> list[tuple]:
    """(flops, bytes) of each ``dim_agg`` call of one FediLoRA aggregation:
    for each adapted site, the A stack [K, L, r, in] and the transposed B
    stack [K, L, r, out] reduced over the K clients, float32."""
    L = dm["layers"]
    out = []
    for i, o in lora_site_dims(dm):
        for n in (i, o):
            elems = cohort * L * r_g * n
            out.append((2.0 * elems,
                        F32 * (elems + L * r_g * n) + F32 * cohort * r_g))
    return out


def weight_bytes(dm: dict, unembed: bool = True) -> int:
    """The base as served: every layer's matrices, norms and biases, the
    final norm, and the tied embedding read whole as the unembedding
    (``unembed``; a prefill dispatch computes no logits)."""
    L, d, h, kv, hd = dm["layers"], dm["d"], dm["heads"], dm["kv_heads"], \
        dm["head_dim"]
    per_layer = block_matmul_params(dm) + 2 * d
    if dm["bias"]:
        per_layer += (h + 2 * kv) * hd
    return BF16 * (L * per_layer + d + (dm["vocab"] * d if unembed else 0))


def kv_bytes_per_token(dm: dict) -> int:
    return BF16 * 2 * dm["layers"] * dm["kv_heads"] * dm["head_dim"]


def adapter_bytes(dm: dict, rank: int) -> int:
    return F32 * dm["layers"] * sum(rank * (i + o)
                                    for i, o in lora_site_dims(dm))


def decode_token_flops(dm: dict, context: int, rank: int) -> float:
    """One decoded token attending to ``context`` positions (itself
    included), with its logits over the vocabulary."""
    L = dm["layers"]
    lora = sum(2 * rank * (i + o) for i, o in lora_site_dims(dm))
    return (2 * (L * block_matmul_params(dm) + dm["d"] * dm["vocab"])
            + L * (4 * context * dm["head_dim"] * dm["heads"] + lora))


def prefill_token_flops(dm: dict, position: int, rank: int) -> float:
    """One prompt position (0-based) written to the cache: no logits."""
    L = dm["layers"]
    lora = sum(2 * rank * (i + o) for i, o in lora_site_dims(dm))
    return L * (2 * block_matmul_params(dm)
                + 4 * (position + 1) * dm["head_dim"] * dm["heads"] + lora)


def serve_dispatch(dm: dict, rows: list[tuple[int, int, int]],
                   adapter_ranks: list[int], *, decode: bool) -> tuple:
    """(flops, bytes) required by one ``serve_step`` (``decode``) or
    ``prefill_step`` dispatch.  ``rows``: per active slot ``(first
    position, positions processed, rank)``; ``adapter_ranks``: ranks of
    the distinct adapters the dispatch gathers.  Bytes: the weights once,
    each row's live cache read and its new rows written, the adapters."""
    fl, kv_read, new = 0.0, 0, 0
    for pos, n, r in rows:
        for p in range(pos, pos + n):
            fl += (decode_token_flops(dm, p + 1, r) if decode
                   else prefill_token_flops(dm, p, r))
        kv_read += pos + n
        new += n
    by = (weight_bytes(dm, unembed=decode) + kv_bytes_per_token(dm) * (kv_read + new)
          + sum(adapter_bytes(dm, r) for r in adapter_ranks))
    return fl, by


def bgmv_call(m: int, k: int, n: int, r: int, adapters: int) -> tuple:
    """(flops, bytes) of one grouped LoRA projection over ``m`` rows:
    ``x W`` plus each row's rank-``r`` product, bfloat16 activations and
    weight, float32 adapters (``adapters`` distinct ones gathered)."""
    fl = 2.0 * m * k * n + 2.0 * m * r * (k + n)
    by = BF16 * (m * k + k * n + m * n) + F32 * adapters * r * (k + n)
    return fl, by
