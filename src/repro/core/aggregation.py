"""Federated LoRA aggregation strategies.

All strategies consume *stacked* client LoRA pytrees — every leaf carries a
leading client axis ``K`` (``A: [K, L, r_g, n]``, ``B: [K, L, m, r_g]``) plus a
static-shape rank vector ``ranks: i32[K]`` and base FedAvg weights
``p: f32[K]`` (normalised local data sizes, paper Eq. 1).  Stacking makes every
strategy a pure, jit-able tensor program; on the production mesh the client
axis lives on ``data`` so aggregation lowers to a weighted
reduce-scatter/all-reduce rather than a parameter-server gather (DESIGN.md §3).

Implemented:

* ``fedavg``     — plain weighted mean (homogeneous-rank baseline, FedIT-style).
* ``hetlora``    — zero-pad + sparsity(Frobenius-norm)-weighted mean, global
                   truncate-redistribute (Cho et al., 2024).
* ``flora``      — noise-free stacking: dW = sum_k p_k B_k A_k folded into a
                   dense accumulated delta; clients re-init LoRA each round
                   (Wang et al., 2024).
* ``fedilora``   — the paper's dimension-wise reweighting (Eqs. 3-5): row d of
                   the global A (col d of B) is averaged only over clients
                   whose rank covers d, with weights renormalised per-dimension.
* ``fedbuff``    — buffered *asynchronous* aggregation (Nguyen et al., 2022,
                   composed with FediLoRA's dimension-wise reweighting): each
                   buffered client delta carries a staleness s_k (server
                   versions elapsed since its global was snapshot) and is
                   discounted by ``(1+s_k)^-decay``; the per-dimension weight
                   mass lost to the discount stays on the *current* global
                   (the anchor), so the merge is a convex per-dimension blend.
                   At staleness 0 it is exactly ``fedilora``.

Byzantine-robust variants (Koo et al. 2410.22815; see ``federated/faults.py``
for the fault model they defend against):

* ``fedilora_clip``    — per-client update-norm clipping: a client whose
                   Frobenius norm exceeds ``clip`` is scaled down to it, the
                   forfeited per-dimension mass anchored on the current
                   global (same residual algebra as ``fedbuff``; in the
                   kernel path the clip factor rides the existing per-client
                   ``scale`` operand of ``dim_agg``).  Defends scaled
                   outliers; a sign flip preserves the norm and sails
                   through — that is ``fedilora_trimmed``'s job.
* ``fedilora_trimmed`` — dimension-wise trimmed mean: per scalar element the
                   ``t_d`` largest and smallest covering-client
                   contributions are discarded before the weighted mean
                   (``t_d = min(⌊trim·m_d⌋, ⌊(m_d-1)/2⌋)`` over the ``m_d``
                   clients covering rank dimension d).  Defends sign flips
                   and arbitrary Byzantine values up to the trim budget.

Both are *statically* gated: ``clip`` off / ``trim == 0`` takes the literal
``fedilora`` code path, so degradation is bitwise (tested).  Every
adapter-space strategy accepts ``fallback`` (the previous global): when the
whole cohort's weight is zero — every client dropped or non-finite — the
previous global is returned unchanged instead of an all-zero adapter.
"""

from __future__ import annotations

from typing import Callable, Mapping

import jax
import jax.numpy as jnp

from repro.core.lora import rank_mask

Pytree = object
_EPS = 1e-12


def _client_masks(ranks: jax.Array, r_g: int, dtype=jnp.float32) -> jax.Array:
    """[K, r_g] binary masks, mask[k, d] = 1[d < r_k] (paper Eq. 3)."""
    return jax.vmap(lambda r: rank_mask(r, r_g, dtype))(ranks)


def dimension_wise_weights(ranks: jax.Array, p: jax.Array, r_g: int) -> jax.Array:
    """Paper Eq. 4: p~_k^(d) = mask_k^(d) p_k / sum_j mask_j^(d) p_j  → [K, r_g].

    Rows (dimensions) covered by no client get all-zero weights.
    """
    masks = _client_masks(ranks, r_g, p.dtype)          # [K, r_g]
    num = masks * p[:, None]                            # [K, r_g]
    den = jnp.sum(num, axis=0, keepdims=True)           # [1, r_g]
    return num / jnp.maximum(den, _EPS)


def client_update_norms(stacked: Pytree) -> jax.Array:
    """Per-client Frobenius norm of the stacked update across all modules
    (``||A_k||² + ||B_k||²`` summed over leaves, f32) → [K].  Shared by the
    HetLoRA sparsity weighting and ``fedilora_clip``."""
    leaves = jax.tree_util.tree_leaves(stacked)
    sq = sum(jnp.sum(jnp.square(x.astype(jnp.float32)),
                     axis=tuple(range(1, x.ndim)))
             for x in leaves)  # [K]
    return jnp.sqrt(sq)


def _apply_fallback(out: Pytree, p: jax.Array, fallback: Pytree | None) -> Pytree:
    """Zero-survivor guard: if the cohort's total weight is zero (every
    client dropped / forfeited / non-finite) return ``fallback`` — the
    previous global — instead of the all-zero adapter the weighted sums
    produce.  When any weight survives this is a bitwise no-op."""
    if fallback is None:
        return out
    alive = jnp.sum(p) > 0
    return jax.tree_util.tree_map(
        lambda o, f: jnp.where(alive, o, f.astype(o.dtype)), out, fallback)


def _clip_active(clip) -> bool:
    """Static gate: clipping participates in the program only for a finite
    positive threshold — ``None``/``0``/``inf`` take the exact unclipped
    code path (bitwise degradation)."""
    return clip is not None and 0 < float(clip) < float("inf")


def _trim_active(trim) -> bool:
    return trim is not None and float(trim) > 0


# ---------------------------------------------------------------------------
# FedAvg (homogeneous baseline)
# ---------------------------------------------------------------------------

def fedavg(stacked: Pytree, ranks: jax.Array, p: jax.Array,
           fallback: Pytree | None = None) -> Pytree:
    """Plain data-size-weighted mean over the client axis (paper Eq. 1).

    With heterogeneous ranks this is exactly HetLoRA-style zero-pad averaging
    with uniform-in-k weights: padded rows dilute by sum over *all* K clients.
    """
    pn = p / jnp.maximum(jnp.sum(p), _EPS)

    def _agg(leaf):
        return jnp.einsum("k,k...->...", pn.astype(leaf.dtype), leaf)

    return _apply_fallback(jax.tree_util.tree_map(_agg, stacked), p, fallback)


# ---------------------------------------------------------------------------
# HetLoRA (Cho et al. 2024): zero-pad + sparsity-weighted aggregation
# ---------------------------------------------------------------------------

def hetlora_sparsity_weights(stacked: Pytree, p: jax.Array, beta: float = 1.0) -> jax.Array:
    """HetLoRA reweights clients by the Frobenius norm of their update
    (||B_k A_k||_F proxied by ||A_k||_F * ||B_k||_F over all modules), so
    'information-rich' clients count more.  Padded rows contribute zero norm.
    """
    norms = client_update_norms(stacked) ** beta
    w = p * norms
    return w / jnp.maximum(jnp.sum(w), _EPS)


def hetlora(stacked: Pytree, ranks: jax.Array, p: jax.Array, beta: float = 1.0,
            fallback: Pytree | None = None) -> Pytree:
    """Zero-padding aggregation with sparsity weighting.  Crucially the
    denominator is the *total* weight (all K clients), so dimensions only a few
    high-rank clients populate are diluted — the failure mode FediLoRA fixes
    and Fig. 5 (global adapter L2 collapse) measures.
    """
    w = hetlora_sparsity_weights(stacked, p, beta)

    def _agg(leaf):
        return jnp.einsum("k,k...->...", w.astype(leaf.dtype), leaf)

    return _apply_fallback(jax.tree_util.tree_map(_agg, stacked), p, fallback)


def hetlora_self_prune(entry: Mapping[str, jax.Array], rank: jax.Array, r_g: int,
                       gamma: float = 0.99) -> jax.Array:
    """HetLoRA rank self-pruning: drop trailing dimensions whose cumulative
    contribution (by |A row| * |B col| mass) is below a (1-gamma) tail.
    Returns the pruned rank (never grows)."""
    a_mass = jnp.sqrt(jnp.sum(jnp.square(entry["A"]), axis=(0, 2)))  # [r_g]
    b_mass = jnp.sqrt(jnp.sum(jnp.square(entry["B"]), axis=(0, 1)))  # [r_g]
    mass = a_mass * b_mass
    total = jnp.maximum(jnp.sum(mass), _EPS)
    cum = jnp.cumsum(mass) / total
    kept = jnp.sum((cum < gamma).astype(jnp.int32)) + 1
    return jnp.minimum(jnp.minimum(kept, rank), r_g)


# ---------------------------------------------------------------------------
# FLoRA (Wang et al. 2024): stacking-based, noise-free aggregation
# ---------------------------------------------------------------------------

def flora_delta(stacked: Pytree, ranks: jax.Array, p: jax.Array, scale: float) -> Pytree:
    """Noise-free global update: dW = sum_k p_k * scale * B_k A_k.

    Stacking [A_1; ...; A_K] row-wise and [B_1 ... B_K] col-wise and
    multiplying is *identical* to summing the per-client products — we compute
    the sum directly (the padded tail rows/cols are zero, so heterogeneous
    ranks need no special casing).  Returns dense deltas {name: [L, m, n]}.
    """
    p = p / jnp.maximum(jnp.sum(p), _EPS)

    def _delta(entry):
        d = jnp.einsum("k,klor,klri->loi", p.astype(entry["A"].dtype), entry["B"], entry["A"])
        return scale * d

    return {name: _delta(entry) for name, entry in stacked.items()}


# ---------------------------------------------------------------------------
# FediLoRA (the paper): dimension-wise reweighted aggregation
# ---------------------------------------------------------------------------

def fedilora(stacked: Pytree, ranks: jax.Array, p: jax.Array,
             fallback: Pytree | None = None) -> Pytree:
    """Paper Eqs. 3-5.  Row d of global A aggregates only clients with
    r_k >= d, with weights renormalised within that set; likewise col d of B.

    Degenerate cases: homogeneous ranks → exactly FedAvg;  a dimension covered
    by a single client → that client's row verbatim (no dilution).
    """
    r_g = None
    for entry in stacked.values():
        r_g = entry["A"].shape[2]  # [K, L, r_g, n]
        break
    assert r_g is not None, "empty LoRA tree"
    pt = dimension_wise_weights(ranks, p, r_g)  # [K, r_g]

    out = {}
    for name, entry in stacked.items():
        a, b = entry["A"], entry["B"]
        w = pt.astype(a.dtype)
        out[name] = {
            "A": jnp.einsum("kd,kldn->ldn", w, a),   # row-wise over rank dim
            "B": jnp.einsum("kd,klmd->lmd", w, b),   # col-wise over rank dim
        }
    return _apply_fallback(out, p, fallback)


# ---------------------------------------------------------------------------
# FedBuff (Nguyen et al. 2022) × FediLoRA: staleness-discounted buffered merge
# ---------------------------------------------------------------------------

def staleness_discount(staleness: jax.Array, decay: float) -> jax.Array:
    """FedBuff's polynomial staleness discount ``(1 + s)^-decay`` → [K].

    ``staleness[k]`` counts server versions elapsed between the global the
    client trained against and the global at merge time; ``decay=0`` (or
    all-zero staleness) disables the discount entirely.
    """
    return (1.0 + staleness) ** (-decay)


def _discounted_dimension_merge(stacked: Pytree, ranks: jax.Array,
                                p: jax.Array, disc: jax.Array,
                                anchor: Pytree | None = None) -> Pytree:
    """Shared core of ``fedbuff`` and ``fedilora_clip``: dimension-wise
    weights (Eq. 4) × a per-client discount ``disc`` [K] (staleness factor
    or clip factor), with the per-dimension weight mass the discount
    forfeits retained by ``anchor`` on covered dimensions."""
    r_g = None
    for entry in stacked.values():
        r_g = entry["A"].shape[2]
        break
    assert r_g is not None, "empty LoRA tree"
    pt = dimension_wise_weights(ranks, p, r_g)           # [K, r_g], Eq. 4
    w = pt * disc[:, None]                               # [K, r_g]
    covered = (jnp.sum(pt, axis=0) > 0).astype(pt.dtype)  # [r_g]
    resid = covered * (1.0 - jnp.sum(w, axis=0))          # [r_g]

    out = {}
    for name, entry in stacked.items():
        a, b = entry["A"], entry["B"]
        wk = w.astype(a.dtype)
        ga = jnp.einsum("kd,kldn->ldn", wk, a)
        gb = jnp.einsum("kd,klmd->lmd", wk, b)
        if anchor is not None:
            r = resid.astype(a.dtype)
            ga = ga + r[None, :, None] * anchor[name]["A"]
            gb = gb + r[None, None, :] * anchor[name]["B"]
        out[name] = {"A": ga, "B": gb}
    return out


def fedbuff(stacked: Pytree, ranks: jax.Array, p: jax.Array,
            staleness: jax.Array | None = None, anchor: Pytree | None = None,
            decay: float = 0.5, fallback: Pytree | None = None) -> Pytree:
    """Buffered-async merge of K stacked client adapters with per-delta
    staleness discounting, composed with the paper's dimension-wise
    reweighting (Eqs. 3-5).

    Per dimension d the effective client weight is

        ŵ_k^(d) = p~_k^(d) · (1+s_k)^-decay          (p~ = paper Eq. 4)

    i.e. the *undiscounted* dimension-wise normalisation, then the discount —
    so the weight mass a stale client forfeits is NOT renormalised over the
    buffer but retained by the current global (``anchor``):

        out^(d) = Σ_k ŵ_k^(d) A_k^(d) + (1 − Σ_k ŵ_k^(d)) · anchor^(d)

    on dimensions covered by ≥1 buffered client; uncovered dimensions stay
    zero exactly like :func:`fedilora`.  With ``staleness == 0`` every
    discount is 1, Σ ŵ = 1 on covered dimensions, and the merge is *exactly*
    :func:`fedilora` (tested).  ``anchor=None`` drops the residual term.

    Uncovered-dimension semantics are a deliberate choice: zeroing matches
    the synchronous counterpart in EVERY case (paper Eq. 4 zeroes dimensions
    no sampled client covers, every round, at any sample rate), which is
    what keeps the zero-staleness async timeline bitwise-equivalent to
    ``fedilora``.  The flip side: a small merge batch (``buffer_size`` ≪ K)
    containing only low-rank clients wipes the global's high dimensions
    until a covering delta arrives — if that matters for a deployment,
    size the buffer so merges span the rank distribution.
    """
    if staleness is None:
        disc = jnp.ones((p.shape[0],), p.dtype)
    else:
        disc = staleness_discount(staleness.astype(p.dtype), decay)
    out = _discounted_dimension_merge(stacked, ranks, p, disc, anchor)
    return _apply_fallback(out, p, fallback)


def fedbuff_kernel(stacked: Pytree, ranks: jax.Array, p: jax.Array,
                   staleness: jax.Array | None = None,
                   anchor: Pytree | None = None, decay: float = 0.5,
                   fallback: Pytree | None = None) -> Pytree:
    """Pallas path of :func:`fedbuff`: the staleness-scaled dimension-wise
    reduction lowers to the ``dim_agg`` kernel (weights × per-client scale
    fused in-kernel).  Numerically identical to :func:`fedbuff` (tested)."""
    from repro.kernels.ops import fedbuff_aggregate_tree

    out = fedbuff_aggregate_tree(stacked, ranks, p, staleness, anchor,
                                 decay=decay)
    return _apply_fallback(out, p, fallback)


def fedilora_kernel(stacked: Pytree, ranks: jax.Array, p: jax.Array,
                    fallback: Pytree | None = None) -> Pytree:
    """Pallas dimension-wise aggregation (repro/kernels/dim_agg.py) —
    numerically identical to :func:`fedilora` (tested); on a TPU backend
    the per-leaf reduction always lowers to a fused Mosaic kernel, and only
    the CPU test backend runs it in interpret mode (``kernels/ops.py``).
    Imported lazily to keep core free of a kernels dependency."""
    from repro.kernels.ops import fedilora_aggregate_tree

    return _apply_fallback(fedilora_aggregate_tree(stacked, ranks, p), p,
                           fallback)


# ---------------------------------------------------------------------------
# Byzantine-robust variants (Koo et al. 2410.22815 × FediLoRA Eqs. 3-5)
# ---------------------------------------------------------------------------

def fedilora_clip(stacked: Pytree, ranks: jax.Array, p: jax.Array,
                  clip: float | None = None, anchor: Pytree | None = None,
                  fallback: Pytree | None = None) -> Pytree:
    """Dimension-wise aggregation with per-client update-norm clipping.

    Each client's contribution is scaled by ``c_k = min(1, clip/||u_k||_F)``
    — the same per-client discount channel FedBuff uses for staleness, so
    the kernel path fuses it into ``dim_agg``'s existing ``scale`` operand
    with no new HBM materialisation.  The per-dimension mass clipping
    forfeits is anchored on the current global (``anchor``), keeping the
    merge a convex blend instead of shrinking the adapter toward zero.

    Statically gated: ``clip`` of ``None``/``0``/``inf`` takes the literal
    :func:`fedilora` path (bitwise-identical degradation, tested).  Clipping
    bounds the damage of *scaled* outliers; it is blind to sign flips
    (norm-preserving) — pair with :func:`fedilora_trimmed` for those.
    """
    if not _clip_active(clip):
        return _apply_fallback(fedilora(stacked, ranks, p), p, fallback)
    norms = client_update_norms(stacked)
    disc = jnp.minimum(1.0, clip / jnp.maximum(norms, _EPS)).astype(p.dtype)
    out = _discounted_dimension_merge(stacked, ranks, p, disc, anchor)
    return _apply_fallback(out, p, fallback)


def fedilora_clip_kernel(stacked: Pytree, ranks: jax.Array, p: jax.Array,
                         clip: float | None = None,
                         anchor: Pytree | None = None,
                         fallback: Pytree | None = None) -> Pytree:
    """Pallas path of :func:`fedilora_clip`: clip factors ride ``dim_agg``'s
    per-client ``scale`` operand (numerically identical, tested)."""
    if not _clip_active(clip):
        return _apply_fallback(fedilora_kernel(stacked, ranks, p), p, fallback)
    from repro.kernels.ops import fedilora_clip_tree

    out = fedilora_clip_tree(stacked, ranks, p, clip, anchor)
    return _apply_fallback(out, p, fallback)


def trimmed_dimension_counts(cover: jax.Array, trim: float) -> jax.Array:
    """Per-rank-dimension trim count ``t_d = min(⌊trim·m_d⌋, ⌊(m_d-1)/2⌋)``
    (clamped ≥ 0) over the coverage matrix ``cover`` [K, r_g] → f32 [r_g].
    The second bound guarantees at least one contribution survives whenever
    any client covers the dimension."""
    m = jnp.sum(cover, axis=0)                            # [r_g]
    t = jnp.minimum(jnp.floor(trim * m), jnp.floor((m - 1.0) / 2.0))
    return jnp.maximum(t, 0.0)


def _trimmed_merge(x: jax.Array, p: jax.Array, cover: jax.Array,
                   t: jax.Array) -> jax.Array:
    """Elementwise trimmed weighted mean over the client axis of ``x``
    [K, L, r, n]: per scalar element, the ``t[d]`` smallest and largest
    covering-client values are discarded (counting rank by value with index
    tie-break — deterministic under duplicates), then the survivors are
    combined with renormalised weights ``p``.  Uncovered elements → 0,
    matching :func:`fedilora`."""
    K = x.shape[0]
    xf = x.astype(jnp.float32)
    xi = xf[:, None]                                      # [K, 1, L, r, n]
    xj = xf[None, :]                                      # [1, K, L, r, n]
    ki = jnp.arange(K)[:, None, None, None, None]
    kj = jnp.arange(K)[None, :, None, None, None]
    cj = cover.astype(jnp.float32)[None, :, None, :, None]
    lo = jnp.sum(cj * ((xj < xi) | ((xj == xi) & (kj < ki))), axis=1)
    hi = jnp.sum(cj * ((xj > xi) | ((xj == xi) & (kj > ki))), axis=1)
    tb = t.astype(jnp.float32)[None, None, :, None]
    keep = (cover.astype(jnp.float32)[:, None, :, None]
            * (lo >= tb) * (hi >= tb))                    # [K, L, r, n]
    pw = p.astype(jnp.float32)[:, None, None, None]
    num = jnp.sum(keep * pw * xf, axis=0)
    den = jnp.sum(keep * pw, axis=0)
    return (num / jnp.maximum(den, _EPS)).astype(x.dtype)


def fedilora_trimmed(stacked: Pytree, ranks: jax.Array, p: jax.Array,
                     trim: float = 0.0,
                     fallback: Pytree | None = None) -> Pytree:
    """Dimension-wise *trimmed* mean: robust to arbitrary Byzantine values
    (sign flips, huge outliers, even NaN-adjacent garbage the caller zeroed)
    as long as fewer than ``trim·m_d`` of the ``m_d`` clients covering a
    dimension are corrupted.  Per scalar element the extreme tails are
    dropped and the surviving weights renormalised — the trimmed analogue
    of paper Eq. 4's per-dimension renormalisation.

    Statically gated: ``trim == 0`` takes the literal :func:`fedilora` path
    (bitwise-identical degradation, tested).
    """
    if not _trim_active(trim):
        return _apply_fallback(fedilora(stacked, ranks, p), p, fallback)
    r_g = None
    for entry in stacked.values():
        r_g = entry["A"].shape[2]
        break
    assert r_g is not None, "empty LoRA tree"
    cover = (_client_masks(ranks, r_g, p.dtype)
             * (p > 0).astype(p.dtype)[:, None])          # [K, r_g]
    t = trimmed_dimension_counts(cover, trim)
    out = {}
    for name, entry in stacked.items():
        a = _trimmed_merge(entry["A"], p, cover, t)
        bt = jnp.swapaxes(entry["B"], -1, -2)             # [K, L, r, m]
        b = _trimmed_merge(bt, p, cover, t)
        out[name] = {"A": a, "B": jnp.swapaxes(b, -1, -2)}
    return _apply_fallback(out, p, fallback)


def fedilora_trimmed_kernel(stacked: Pytree, ranks: jax.Array, p: jax.Array,
                            trim: float = 0.0,
                            fallback: Pytree | None = None) -> Pytree:
    """Pallas path of :func:`fedilora_trimmed`: the per-element counting
    ranks and trimmed reduction run inside ``dim_agg_trimmed_pallas``
    (numerically identical, tested)."""
    if not _trim_active(trim):
        return _apply_fallback(fedilora_kernel(stacked, ranks, p), p, fallback)
    from repro.kernels.ops import fedilora_trimmed_tree

    out = fedilora_trimmed_tree(stacked, ranks, p, trim)
    return _apply_fallback(out, p, fallback)


# ---------------------------------------------------------------------------
# registry — the single dispatch point for every round driver
# ---------------------------------------------------------------------------
#
# Every entry shares the normalised signature
#     fn(stacked, ranks, p, *, hetlora_beta, lora_scale, staleness, anchor,
#        staleness_decay, clip, trim, fallback) -> (global_lora, base_delta)
# where exactly one of the outputs is non-None: LoRA-space strategies return
# a new global adapter; FLoRA returns dense weight deltas for the caller to
# fold into the base parameters (and re-initialise the global adapter).
# The async keywords (staleness / anchor / staleness_decay) are consumed by
# the fedbuff entries, the robustness keywords (clip / anchor, trim) by the
# fedilora_clip / fedilora_trimmed entries, and fallback — the zero-survivor
# guard — by every adapter-space strategy; the rest ignore them.
# Both the host-driven reference loop (repro/federated/runtime.py) and the
# fused SPMD round + buffer merge (repro/launch/fedround.py) dispatch through
# here — there is deliberately no other if/elif chain over aggregator names.

AGGREGATORS: dict[str, Callable] = {
    "fedavg": lambda s, r, p, *, fallback=None, **kw: (
        fedavg(s, r, p, fallback=fallback), None),
    "hetlora": lambda s, r, p, *, hetlora_beta=1.0, fallback=None, **kw: (
        hetlora(s, r, p, hetlora_beta, fallback=fallback), None),
    "fedilora": lambda s, r, p, *, fallback=None, **kw: (
        fedilora(s, r, p, fallback=fallback), None),
    "fedilora_kernel": lambda s, r, p, *, fallback=None, **kw: (
        fedilora_kernel(s, r, p, fallback=fallback), None),
    "flora": lambda s, r, p, *, lora_scale=1.0, **kw: (
        None, flora_delta(s, r, p, lora_scale)),
    "fedbuff": lambda s, r, p, *, staleness=None, anchor=None,
    staleness_decay=0.5, fallback=None, **kw: (
        fedbuff(s, r, p, staleness, anchor, staleness_decay,
                fallback=fallback), None),
    "fedbuff_kernel": lambda s, r, p, *, staleness=None, anchor=None,
    staleness_decay=0.5, fallback=None, **kw: (
        fedbuff_kernel(s, r, p, staleness, anchor, staleness_decay,
                       fallback=fallback), None),
    "fedilora_clip": lambda s, r, p, *, clip=None, anchor=None,
    fallback=None, **kw: (
        fedilora_clip(s, r, p, clip, anchor, fallback=fallback), None),
    "fedilora_clip_kernel": lambda s, r, p, *, clip=None, anchor=None,
    fallback=None, **kw: (
        fedilora_clip_kernel(s, r, p, clip, anchor, fallback=fallback), None),
    "fedilora_trimmed": lambda s, r, p, *, trim=0.0, fallback=None, **kw: (
        fedilora_trimmed(s, r, p, trim, fallback=fallback), None),
    "fedilora_trimmed_kernel": lambda s, r, p, *, trim=0.0, fallback=None,
    **kw: (
        fedilora_trimmed_kernel(s, r, p, trim, fallback=fallback), None),
}


def aggregate(name: str, stacked: Pytree, ranks: jax.Array, p: jax.Array, *,
              hetlora_beta: float = 1.0, lora_scale: float = 1.0,
              staleness: jax.Array | None = None, anchor: Pytree | None = None,
              staleness_decay: float = 0.5, clip: float | None = None,
              trim: float = 0.0, fallback: Pytree | None = None
              ) -> tuple[Pytree | None, Pytree | None]:
    """Dispatch one server aggregation through :data:`AGGREGATORS`.

    Returns ``(global_lora, base_delta)``; see the registry comment above.
    Pure and jit-able for every strategy (the kernel entries lower to
    Mosaic on a TPU backend and interpret only on the CPU backend).
    """
    try:
        fn = AGGREGATORS[name]
    except KeyError:
        raise ValueError(
            f"unknown aggregator {name!r}; have {sorted(AGGREGATORS)}") from None
    return fn(stacked, ranks, p, hetlora_beta=hetlora_beta,
              lora_scale=lora_scale, staleness=staleness, anchor=anchor,
              staleness_decay=staleness_decay, clip=clip, trim=trim,
              fallback=fallback)
