"""Heterogeneous-rank LoRA state for federated fine-tuning.

The paper (FediLoRA, Sec. 2.1/3.1) gives client ``k`` a low-rank pair

    ``A_k in R^{r_k x n}``,  ``B_k in R^{m x r_k}``,   ``dW_k = B_k A_k``

with *heterogeneous* ranks ``r_k``.  Ragged ranks do not exist on SPMD
hardware, so every client's pair is materialised at the padded global rank
``r_g = max_k r_k`` together with a static per-client binary rank mask
``mask_k^(d) = 1[d <= r_k]`` (paper Eq. 3).  Rows of ``A`` / columns of ``B``
beyond ``r_k`` are zero, which makes the padded pair *exactly* equivalent to
the ragged pair: ``B_k A_k`` is unchanged by zero padding.

A model exposes its adapted weight families as :class:`LoRASpec` entries
(one per scanned weight stack, e.g. ``"attn/wq"`` with a leading layer dim).
LoRA parameters are a pytree::

    {spec.name: {"A": f32[L, r_g, in_dim], "B": f32[L, out_dim, r_g]}}

kept replicated across the mesh (they are <2% of model size and are the
objects the federated aggregation operates on).
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp

Pytree = object


@dataclasses.dataclass(frozen=True)
class LoRASpec:
    """One adapted weight family (a stacked scan of ``num_layers`` matrices)."""

    name: str        # e.g. "attn/wq"
    in_dim: int      # n in the paper
    out_dim: int     # m in the paper
    num_layers: int  # leading (scan) dimension L


@dataclasses.dataclass(frozen=True)
class LoRAConfig:
    rank: int                 # r_g, the padded/global rank
    alpha: float = 16.0       # LoRA scaling numerator
    targets: tuple = ("attn/wq", "attn/wv")
    dtype: str = "float32"

    @property
    def scale(self) -> float:
        return self.alpha / float(self.rank)


def rank_mask(r_k, r_g: int, dtype=jnp.float32) -> jax.Array:
    """mask^(d) = 1[d <= r_k] for d in 1..r_g (paper Eq. 3). ``r_k`` may be a tracer."""
    return (jnp.arange(r_g) < r_k).astype(dtype)


def init_lora_params(
    key: jax.Array,
    specs: Sequence[LoRASpec],
    cfg: LoRAConfig,
    client_rank: int | None = None,
) -> Pytree:
    """Standard LoRA init: A ~ N(0, 1/r), B = 0 (so dW starts at zero).

    If ``client_rank`` is given, rows of A beyond it are zeroed so the padded
    state equals the ragged client state.
    """
    params = {}
    dtype = jnp.dtype(cfg.dtype)
    for spec in specs:
        key, ka = jax.random.split(key)
        a = jax.random.normal(ka, (spec.num_layers, cfg.rank, spec.in_dim), dtype) / jnp.sqrt(
            jnp.asarray(max(cfg.rank, 1), dtype)
        )
        b = jnp.zeros((spec.num_layers, spec.out_dim, cfg.rank), dtype)
        if client_rank is not None:
            a = a * rank_mask(client_rank, cfg.rank, dtype)[None, :, None]
        params[spec.name] = {"A": a, "B": b}
    return params


def mask_lora_params(params: Pytree, r_k, r_g: int) -> Pytree:
    """Zero rows of A / cols of B beyond the client rank (projection onto the
    ragged subspace). Idempotent; keeps padded-vs-ragged equivalence exact."""

    def _mask(entry):
        m = rank_mask(r_k, r_g, entry["A"].dtype)
        return {"A": entry["A"] * m[None, :, None], "B": entry["B"] * m[None, None, :]}

    return {name: _mask(entry) for name, entry in params.items()}


def truncate_redistribute(global_params: Pytree, r_k, r_g: int) -> Pytree:
    """Server -> client redistribution used by HetLoRA & FediLoRA: the global
    rank-``r_g`` pair is truncated to the client's rank (zero the tail)."""
    return mask_lora_params(global_params, r_k, r_g)


def lora_delta(entry: Mapping[str, jax.Array], scale: float) -> jax.Array:
    """Materialise dW = scale * B A for one spec (per stacked layer)."""
    return scale * jnp.einsum("lor,lri->loi", entry["B"], entry["A"])


@jax.named_scope("lora_site")
def lora_matmul(x: jax.Array, w: jax.Array, lora: Mapping[str, jax.Array] | None,
                scale: float) -> jax.Array:
    """``y = x @ w + scale * (x @ A^T) @ B^T`` — the LoRA-adapted projection.

    ``x``: [..., in_dim]; ``w``: [in_dim, out_dim]; ``A``: [r, in]; ``B``: [out, r].
    Padded rank rows/cols are zero so they contribute nothing.  This and
    :func:`grouped_lora_matmul` run under the ``lora_site`` named scope, so
    a profile finds every LoRA-carrying projection whatever implements it.
    """
    y = x @ w
    if lora is not None:
        delta = scale * jnp.einsum(
            "...r,or->...o", jnp.einsum("...i,ri->...r", x, lora["A"]), lora["B"])
        y = y + delta.astype(y.dtype)
    return y


@jax.named_scope("lora_site")
def grouped_lora_matmul(x: jax.Array, w: jax.Array,
                        bank: Mapping[str, jax.Array] | None, idx: jax.Array,
                        scale: float, *, kernel: bool = False) -> jax.Array:
    """Per-row adapter-index LoRA projection (BGMV) — the multi-tenant
    variant of :func:`lora_matmul`: leading-batch row ``b`` of ``x`` applies
    adapter ``idx[b]`` from a stacked bank.

    ``x``: [B, ..., in]; ``w``: [in, out]; ``bank``: {"A": [G, r, in],
    "B": [G, out, r]} (``None`` → plain ``x @ w``); ``idx``: i32 [B],
    broadcast over the inner dims.  The default path gathers only the tiny
    per-row (A, B) pairs and contracts them row-wise (XLA fuses the gather
    into the contraction; the [in, out]-sized delta is never materialised).
    ``kernel=True`` dispatches the Pallas BGMV kernel
    (``kernels/lora_gather_matmul.py``): the per-row index becomes a
    scalar-prefetch operand steering the A/B DMA, so the gather happens in
    the memory system — no HBM-materialised per-row adapter copies at all.
    """
    if bank is None:
        return x @ w
    if kernel:
        from repro.kernels.ops import grouped_lora_matmul as _kernel_glm
        return _kernel_glm(x, w, bank["A"], bank["B"], idx, scale=scale)
    a = bank["A"][idx]                                   # [B, r, in]
    b = bank["B"][idx]                                   # [B, out, r]
    y = x @ w
    xa = jnp.einsum("b...i,bri->b...r", x, a)
    delta = scale * jnp.einsum("b...r,bor->b...o", xa, b)
    return y + delta.astype(y.dtype)


def num_lora_params(specs: Sequence[LoRASpec], rank: int) -> int:
    return sum(s.num_layers * rank * (s.in_dim + s.out_dim) for s in specs)


def flatten_modules(params: Pytree) -> list[tuple[str, int, Mapping[str, jax.Array]]]:
    """Enumerate editable LoRA modules as (spec_name, layer_idx, {"A","B"}).

    The paper edits per-LoRA-layer (one (A,B) pair per adapted weight per
    transformer block).  We keep the stacked representation and let editing
    index into the leading layer dim instead of materialising slices.
    """
    out = []
    for name in sorted(params.keys()):
        L = params[name]["A"].shape[0]
        for l in range(L):
            out.append((name, l, params[name]))
    return out


def tree_l2_norm(params: Pytree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(params)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))
