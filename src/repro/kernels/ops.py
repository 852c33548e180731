"""jit'd dispatch wrappers for the Pallas kernels.

The mode follows the backend, never a silent default: on a TPU backend the
kernels always lower to Mosaic (asking for interpret mode there raises); on
the CPU backend — the one the tests force — ``interpret=None`` runs the
Pallas body in interpret mode for correctness checks, and an explicit
``interpret=False`` lowers for a described TPU topology (the compile tests);
any other backend raises.  Inputs that don't tile exactly are zero-padded
to the block grid and the result is sliced back.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.dim_agg import dim_agg_pallas, dim_agg_trimmed_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lora_gather_matmul import (block_sizes,
                                              grouped_lora_matmul_pallas)
from repro.kernels.lora_matmul import lora_matmul_pallas


def _interpret(interpret: bool | None) -> bool:
    """Resolve a kernel call's interpret flag from the backend (see the
    module docstring)."""
    backend = jax.default_backend()
    if backend == "tpu":
        if interpret:
            raise ValueError("Pallas interpret mode requested on a TPU "
                             "backend; the kernels lower to Mosaic there")
        return False
    if backend != "cpu":
        raise RuntimeError(
            f"no Pallas kernel path for backend {backend!r}: the kernels "
            "lower to Mosaic on a TPU and interpret on the CPU backend only")
    return True if interpret is None else interpret


def _pad_to(x, axis: int, mult: int):
    rem = (-x.shape[axis]) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


def fused_lora_matmul(x, w, a, b, *, scale: float = 1.0, bm: int = 256,
                      bn: int = 256, bk: int = 512, interpret: bool | None = None):
    """y = x@W + scale·(x@Aᵀ)@Bᵀ with arbitrary leading batch dims on x."""
    interpret = _interpret(interpret)
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    bm_, bn_, bk_ = min(bm, M), min(bn, N), min(bk, K)
    xp = _pad_to(_pad_to(x2, 0, bm_), 1, bk_)
    wp = _pad_to(_pad_to(w, 0, bk_), 1, bn_)
    ap = _pad_to(a, 1, bk_)
    bp = _pad_to(b, 0, bn_)
    y = lora_matmul_pallas(xp, wp, ap, bp, scale=scale, bm=bm_, bn=bn_, bk=bk_,
                           interpret=interpret)
    return y[:M, :N].reshape(*lead, N)


def grouped_lora_matmul(x, w, a, b, idx, *, scale: float = 1.0,
                        bn: int | None = None, bk: int | None = None,
                        interpret: bool | None = None):
    """Multi-tenant LoRA projection: row ``m`` uses adapter ``idx[m]`` from
    the stacked bank (BGMV).  x: [..., K]; w: [K, N]; a: [G, r, K];
    b: [G, N, r]; idx: i32 over x's leading dims, broadcast over the rest —
    a per-batch [B] index against x [B, chunk, K] (the chunked-prefill
    shape) covers each slot's chunk, and those chunk rows form the kernel's
    row block.  ``bn``/``bk`` force N/K tiles; by default K and N are whole
    where they fit VMEM (``lora_gather_matmul.block_sizes``)."""
    interpret = _interpret(interpret)
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[1]
    idx = jnp.asarray(idx)
    idx_b = jnp.broadcast_to(idx, lead[:idx.ndim]).reshape(-1)
    x3 = x.reshape(idx_b.shape[0], -1, K)
    R = x3.shape[1]
    br, bn_, bk_ = block_sizes(R, K, N, a.shape[1], x.dtype, w.dtype,
                               a.dtype, bn, bk)
    xp = _pad_to(_pad_to(x3, 1, br), 2, bk_)
    wp = _pad_to(_pad_to(w, 0, bk_), 1, bn_)
    ap = _pad_to(a, 2, bk_)
    bp = _pad_to(b, 1, bn_)
    y = grouped_lora_matmul_pallas(xp, wp, ap, bp, idx_b, scale=scale,
                                   br=br, bn=bn_, bk=bk_, interpret=interpret)
    return y[:, :R, :N].reshape(*lead, N)


def dimension_wise_aggregate(stacked, weights, scale=None, *, bn: int = 512,
                             interpret: bool | None = None):
    """FediLoRA Eq. 5 over one stacked leaf [K, L, r, n] with w̃ [K, r];
    ``scale`` [K] optionally multiplies each client's weight row in-kernel
    (the FedBuff staleness discount)."""
    interpret = _interpret(interpret)
    n = stacked.shape[-1]
    bn_ = min(bn, n)
    sp = _pad_to(stacked, 3, bn_)
    if scale is not None:
        scale = scale.reshape(-1, 1).astype(weights.dtype)
    out = dim_agg_pallas(sp, weights, scale, bn=bn_, interpret=interpret)
    return out[..., :n]


def fedilora_aggregate_tree(stacked_tree, ranks, p, *, interpret: bool | None = None):
    """Kernel-backed FediLoRA aggregation over a stacked LoRA pytree —
    drop-in for ``repro.core.aggregation.fedilora`` (A rows / B cols)."""
    from repro.core.aggregation import dimension_wise_weights

    first = next(iter(stacked_tree.values()))
    r_g = first["A"].shape[2]
    w = dimension_wise_weights(ranks, p, r_g)     # [K, r_g]
    out = {}
    for name, entry in stacked_tree.items():
        a = dimension_wise_aggregate(entry["A"], w, interpret=interpret)
        bt = jnp.swapaxes(entry["B"], -1, -2)     # [K, L, r, m]
        b = dimension_wise_aggregate(bt, w, interpret=interpret)
        out[name] = {"A": a, "B": jnp.swapaxes(b, -1, -2)}
    return out


def discounted_aggregate_tree(stacked_tree, ranks, p, disc, anchor=None,
                              *, interpret: bool | None = None):
    """Kernel-backed discounted dimension-wise merge over a stacked LoRA
    pytree — the shared core of the FedBuff staleness merge and
    ``fedilora_clip``: the per-client discount ``disc`` [K] (staleness
    factor or clip factor) is fused as ``dim_agg``'s per-client ``scale``
    operand, and the per-dimension weight mass the discount forfeits is
    retained by ``anchor`` via a cheap [r_g]-vector epilogue."""
    from repro.core.aggregation import dimension_wise_weights

    first = next(iter(stacked_tree.values()))
    r_g = first["A"].shape[2]
    w = dimension_wise_weights(ranks, p, r_g)                 # [K, r_g]
    covered = (jnp.sum(w, axis=0) > 0).astype(w.dtype)        # [r_g]
    resid = covered * (1.0 - jnp.sum(w * disc[:, None], axis=0))

    out = {}
    for name, entry in stacked_tree.items():
        a = dimension_wise_aggregate(entry["A"], w, disc, interpret=interpret)
        bt = jnp.swapaxes(entry["B"], -1, -2)                 # [K, L, r, m]
        b = dimension_wise_aggregate(bt, w, disc, interpret=interpret)
        b = jnp.swapaxes(b, -1, -2)
        if anchor is not None:
            r = resid.astype(a.dtype)
            a = a + r[None, :, None] * anchor[name]["A"]
            b = b + r[None, None, :] * anchor[name]["B"]
        out[name] = {"A": a, "B": b}
    return out


def fedbuff_aggregate_tree(stacked_tree, ranks, p, staleness=None, anchor=None,
                           *, decay: float = 0.5,
                           interpret: bool | None = None):
    """Kernel-backed FedBuff merge over a stacked LoRA pytree — drop-in for
    ``repro.core.aggregation.fedbuff``: the staleness-discounted
    dimension-wise reduction runs in the ``dim_agg`` kernel (discount fused
    as the per-client ``scale`` operand); the residual anchor blend
    ``(1 - Σ_k ŵ_k^(d)) · anchor`` is a cheap [r_g]-vector epilogue."""
    from repro.core.aggregation import staleness_discount

    if staleness is None:
        disc = jnp.ones((p.shape[0],), p.dtype)
    else:
        disc = staleness_discount(staleness.astype(p.dtype), decay)
    return discounted_aggregate_tree(stacked_tree, ranks, p, disc, anchor,
                                     interpret=interpret)


def fedilora_clip_tree(stacked_tree, ranks, p, clip, anchor=None,
                       *, interpret: bool | None = None):
    """Kernel-backed ``fedilora_clip``: per-client update-norm clip factors
    ``min(1, clip/||u_k||)`` ride the ``dim_agg`` ``scale`` operand — no new
    HBM materialisation beyond the [K] norm reduction."""
    from repro.core.aggregation import client_update_norms

    norms = client_update_norms(stacked_tree)
    disc = jnp.minimum(1.0, clip / jnp.maximum(norms, 1e-12)).astype(p.dtype)
    return discounted_aggregate_tree(stacked_tree, ranks, p, disc, anchor,
                                     interpret=interpret)


def dimension_wise_trimmed(stacked, p, cover, t, *, bn: int = 128,
                           interpret: bool | None = None):
    """Per-element trimmed weighted mean over one stacked leaf [K, L, r, n]
    (see ``dim_agg_trimmed_pallas``); pads the feature axis to the block
    grid with zeros (padding is sliced off before it can influence real
    elements — each element trims independently)."""
    interpret = _interpret(interpret)
    n = stacked.shape[-1]
    bn_ = min(bn, n)
    sp = _pad_to(stacked, 3, bn_)
    out = dim_agg_trimmed_pallas(sp, p, cover, t, bn=bn_, interpret=interpret)
    return out[..., :n]


def fedilora_trimmed_tree(stacked_tree, ranks, p, trim,
                          *, interpret: bool | None = None):
    """Kernel-backed ``fedilora_trimmed`` over a stacked LoRA pytree — the
    dimension-wise trimmed mean runs in ``dim_agg_trimmed_pallas`` for both
    A (rank rows) and B (rank cols, via transpose)."""
    from repro.core.aggregation import (_client_masks,
                                        trimmed_dimension_counts)

    first = next(iter(stacked_tree.values()))
    r_g = first["A"].shape[2]
    cover = (_client_masks(ranks, r_g, p.dtype)
             * (p > 0).astype(p.dtype)[:, None])              # [K, r_g]
    t = trimmed_dimension_counts(cover, trim)
    out = {}
    for name, entry in stacked_tree.items():
        a = dimension_wise_trimmed(entry["A"], p, cover, t, interpret=interpret)
        bt = jnp.swapaxes(entry["B"], -1, -2)                 # [K, L, r, m]
        b = dimension_wise_trimmed(bt, p, cover, t, interpret=interpret)
        out[name] = {"A": a, "B": jnp.swapaxes(b, -1, -2)}
    return out


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 256, bk: int = 256,
                    interpret: bool | None = None):
    """q: [B,Sq,H,d]; k,v: [B,Sk,KV,d] (GQA) → [B,Sq,H,dv].  Folds heads
    into the batch grid dim, repeats KV heads for GQA, pads Sq/Sk to the
    tile grid and slices back."""
    interpret = _interpret(interpret)
    B, Sq, H, d = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Sk, dv)
    bq_, bk_ = min(bq, Sq), min(bk, Sk)
    qp = _pad_to(qf, 1, bq_)
    kp = _pad_to(kf, 1, bk_)
    vp = _pad_to(vf, 1, bk_)
    # padded KV rows sit at positions >= Sk; causal masking with q_pos < Sk
    # excludes them only if causal — guard non-causal via explicit Sk pad
    # handling: padded keys produce scores masked by the causal/window test
    # when q_pos < k_pos; for non-causal callers pad must be masked upstream.
    out = flash_attention_pallas(qp, kp, vp, causal=causal, window=window,
                                 bq=bq_, bk=bk_, interpret=interpret)
    return out[:, :Sq].reshape(B, H, Sq, dv).transpose(0, 2, 1, 3)


__all__ = ["fused_lora_matmul", "grouped_lora_matmul",
           "dimension_wise_aggregate", "dimension_wise_trimmed",
           "fedilora_aggregate_tree", "discounted_aggregate_tree",
           "fedbuff_aggregate_tree", "fedilora_clip_tree",
           "fedilora_trimmed_tree", "flash_attention", "ref"]
