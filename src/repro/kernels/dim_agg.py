"""Dimension-wise reweighted aggregation kernel (FediLoRA Eqs. 3-5).

Aggregates K stacked client LoRA-A matrices [K, L, r_g, n] with per-client,
per-rank-dimension weights w̃ [K, r_g] into the global [L, r_g, n]:

    out[l, d, :] = Σ_k  w̃[k, d] · A[k, l, d, :]

Kernel layout: grid over (L, n/bn); each program holds the full client axis
K and rank axis r_g in VMEM (K ≤ ~32 clients, r_g ≤ 64 — a [K, r_g, bn]
stack at bn=512 is ≈ 4 MB f32, inside the VMEM budget) and performs the
weighted reduction as a broadcast-multiply + sum over K on the VPU.  One HBM
pass over the client stack, one write of the aggregate — the reduction that
FedAvg-family servers run every communication round, fused.

The same kernel aggregates B matrices by passing them transposed to
[K, L, r_g, m] layout (ops.py handles the transpose).

An optional per-client ``scale`` [K, 1] operand multiplies the weight row of
each client inside the kernel — the FedBuff staleness discount
``(1+s_k)^-decay`` and the ``fedilora_clip`` update-norm clip factor
``min(1, clip/||u_k||)`` both ride the same VMEM-resident reduction instead
of materialising a discounted [K, r_g] weight matrix in HBM first (ops.py's
``fedbuff_aggregate_tree`` / ``fedilora_clip_tree`` are the callers).

``dim_agg_trimmed_pallas`` is the Byzantine-robust sibling: per scalar
element it computes each client's counting rank among the covering clients,
discards the ``t[d]`` smallest and largest contributions, and renormalises
the surviving weights — the dimension-wise trimmed mean, one HBM pass.  The
K×K comparison is unrolled over client pairs on 2-D ``[r, bn]`` tiles (the
index tie-break is static per pair), so VMEM holds O(K·r·bn), never a
[K, K, r, bn] block, and Mosaic sees no rank-5 broadcast.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w_ref, o_ref):
    x = x_ref[...]                    # [K, 1, r, bn]
    w = w_ref[...]                    # [K, r]
    acc = jnp.sum(x.astype(jnp.float32) * w[:, None, :, None].astype(jnp.float32),
                  axis=0)             # [1, r, bn]
    o_ref[...] = acc.astype(o_ref.dtype)


def _kernel_scaled(x_ref, w_ref, s_ref, o_ref):
    x = x_ref[...]                    # [K, 1, r, bn]
    w = w_ref[...].astype(jnp.float32) * s_ref[...].astype(jnp.float32)
    acc = jnp.sum(x.astype(jnp.float32) * w[:, None, :, None], axis=0)
    o_ref[...] = acc.astype(o_ref.dtype)


def _kernel_trimmed(x_ref, pw_ref, c_ref, t_ref, o_ref):
    """Per-element trimmed weighted mean over the client axis.

    x [K, r, bn]; pw [K, r, 1] client weight × coverage; c [K, r, 1]
    coverage (rank mask × participation); t [r, 1] per-dimension trim
    counts.  Client i's counting ranks ``lo``/``hi`` among the covering
    clients compare it against every other client j, ties broken by client
    index (so the trim set is deterministic under duplicates); a
    contribution ranked inside either ``t[d]``-tail is dropped and the
    survivors renormalised.  Each (i, j) pair is one 2-D [r, bn] compare —
    j < i counts a tie as below, j > i as above.
    """
    K = x_ref.shape[0]
    xs = [x_ref[k].astype(jnp.float32) for k in range(K)]       # [r, bn]
    cs = [c_ref[k].astype(jnp.float32) for k in range(K)]       # [r, 1]
    t = t_ref[...].astype(jnp.float32)                          # [r, 1]
    num = jnp.zeros_like(xs[0])
    den = jnp.zeros_like(xs[0])
    for i in range(K):
        lo = jnp.zeros_like(xs[0])
        hi = jnp.zeros_like(xs[0])
        for j in range(K):
            if j == i:
                continue
            below = xs[j] <= xs[i] if j < i else xs[j] < xs[i]
            above = xs[j] > xs[i] if j < i else xs[j] >= xs[i]
            lo = lo + jnp.where(below, cs[j], 0.0)
            hi = hi + jnp.where(above, cs[j], 0.0)
        w = jnp.where((lo >= t) & (hi >= t),
                      pw_ref[i].astype(jnp.float32), 0.0)       # [r, bn]
        num = num + w * xs[i]
        den = den + w
    o_ref[...] = (num / jnp.maximum(den, 1e-12)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def dim_agg_trimmed_pallas(stacked, p, cover, t, *, bn: int = 128,
                           interpret: bool = False):
    """stacked: [K, L, r, n]; p: [K] client weights; cover: [K, r] coverage
    mask; t: [r] per-dimension trim counts → [L, r, n].  Smaller default
    block than ``dim_agg_pallas``: the kernel keeps every client's
    [r, bn] tile live across the pairwise compare."""
    K, L, r, n = stacked.shape
    assert p.shape == (K,) and cover.shape == (K, r) and t.shape == (r,), (
        stacked.shape, p.shape, cover.shape, t.shape)
    bn = min(bn, n)
    assert n % bn == 0, (n, bn)
    pw = (p[:, None] * cover)[..., None]                        # [K, r, 1]
    return pl.pallas_call(
        _kernel_trimmed,
        grid=(L, n // bn),
        in_specs=[
            pl.BlockSpec((K, pl.Squeezed(), r, bn), lambda l, j: (0, l, 0, j)),
            pl.BlockSpec((K, r, 1), lambda l, j: (0, 0, 0)),
            pl.BlockSpec((K, r, 1), lambda l, j: (0, 0, 0)),
            pl.BlockSpec((r, 1), lambda l, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((pl.Squeezed(), r, bn), lambda l, j: (l, 0, j)),
        out_shape=jax.ShapeDtypeStruct((L, r, n), stacked.dtype),
        interpret=interpret,
        name="dim_agg_pallas",
    )(stacked, pw, cover[..., None], t.reshape(r, 1))


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def dim_agg_pallas(stacked, weights, scale=None, *, bn: int = 512,
                   interpret: bool = False):
    """stacked: [K, L, r, n]; weights: [K, r]; scale: optional [K, 1]
    per-client multiplier (FedBuff staleness discount) → [L, r, n]."""
    K, L, r, n = stacked.shape
    assert weights.shape == (K, r), (stacked.shape, weights.shape)
    bn = min(bn, n)
    assert n % bn == 0, (n, bn)

    in_specs = [
        pl.BlockSpec((K, 1, r, bn), lambda l, j: (0, l, 0, j)),
        pl.BlockSpec((K, r), lambda l, j: (0, 0)),
    ]
    operands = (stacked, weights)
    kernel = _kernel
    if scale is not None:
        assert scale.shape == (K, 1), scale.shape
        in_specs.append(pl.BlockSpec((K, 1), lambda l, j: (0, 0)))
        operands = operands + (scale,)
        kernel = _kernel_scaled

    return pl.pallas_call(
        kernel,
        grid=(L, n // bn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, r, bn), lambda l, j: (l, 0, j)),
        out_shape=jax.ShapeDtypeStruct((L, r, n), stacked.dtype),
        interpret=interpret,
        name="dim_agg_pallas",
    )(*operands)
