"""Grouped (multi-adapter) LoRA projection kernel for multi-tenant serving:

    y[m] = x[m] @ W + scale * (x[m] @ A[g_m]ᵀ) @ B[g_m]ᵀ,   g_m = idx[m]

One batch of decode rows, MANY adapters: every row carries the index of its
own LoRA pair in a stacked ``[G, ...]`` adapter bank (the BGMV formulation of
Punica / S-LoRA multi-tenant serving).  The base projection ``x @ W`` is
shared by all tenants; only the tiny low-rank path is gathered per row.

TPU-native design (rides next to ``lora_matmul.py``'s single-adapter path):

* the per-row adapter index is a **scalar-prefetch operand**
  (``PrefetchScalarGridSpec``): the index vector lands in SMEM before the
  kernel body runs, so the A/B ``BlockSpec`` index maps can steer each
  program's DMA to ``A[idx[i]]`` / ``B[idx[i]]`` — the gather happens in the
  memory system, never as an HBM-materialised ``[M, r, K]`` gathered copy;
* grid (M, N/bn, K/bk) with one row per program: decode batches are
  one-token-per-slot, so M is the slot count and the row tile is [1, bk] —
  the adapter gather is per-row exact while W tiles stay MXU-aligned.
  The row axis is carried as ``x[M, 1, K]`` / ``out[M, 1, N]`` with the M
  dim squeezed out of the block: Mosaic needs the last two block dims to
  be (8, 128)-divisible or whole, and a ``(1, bk)`` block of an ``[M, K]``
  array is neither, while ``(1, bk)`` of ``[M, 1, K]`` is whole in its
  second-minor dim;
  Chunked prefill reuses the same grid: the ``[B, chunk, d]`` block
  flattens to M = B·chunk rows whose idx entries repeat per slot
  (``ops.grouped_lora_matmul`` broadcasts a [B] index over the chunk
  axis), so consecutive programs re-request the same A/B tiles and the
  pipelined BlockSpec DMA coalesces them;
* K innermost: both accumulators (base [1, bn] and x@Aᵀ [1, r]) live in VMEM
  scratch across the K loop, one HBM pass over x and W, output written once;
* accumulation is f32 scratch regardless of input dtype; the low-rank path
  contracts in f32 (the activation row is upcast, so a bf16 activation
  meets an f32 adapter bank exactly as the jnp gather path's promotion
  does), and both adapter contractions are transposed-RHS ``dot_general``s
  (no in-kernel transpose).

Heterogeneous-rank note: adapters of different ranks are zero-padded to the
bank's shared r (rows of A / cols of B beyond the tenant's rank are zero),
so one kernel serves every rank mix — the same invariant
``kernels/lora_matmul.py`` exploits for the fused single-adapter path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NT = (((1,), (1,)), ((), ()))       # contract the last dims: x @ yᵀ


def _kernel(idx_ref, x_ref, w_ref, a_ref, b_ref, o_ref, acc_ref, xa_ref, *,
            scale: float, k_steps: int):
    """One (row, bn) output tile; innermost grid dim accumulates over K.
    ``idx_ref`` is consumed by the BlockSpec index maps (the A/B tiles
    arriving here already belong to this row's adapter)."""
    del idx_ref
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xa_ref[...] = jnp.zeros_like(xa_ref)

    x = x_ref[...]                                         # [1, bk]
    acc_ref[...] += jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
    # xa: [1, r] accumulated over the K loop — the A tile is [r, bk]
    xa_ref[...] += jax.lax.dot_general(
        x.astype(jnp.float32), a_ref[...].astype(jnp.float32), _NT,
        preferred_element_type=jnp.float32)

    @pl.when(kk == k_steps - 1)
    def _flush():
        delta = jax.lax.dot_general(                       # [1, bn]
            xa_ref[...], b_ref[...].astype(jnp.float32), _NT,
            preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + scale * delta).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bn", "bk", "interpret"))
def grouped_lora_matmul_pallas(x, w, a, b, idx, *, scale: float = 1.0,
                               bn: int = 256, bk: int = 512,
                               interpret: bool = False):
    """x: [M, K]; w: [K, N]; a: [G, r, K]; b: [G, N, r]; idx: i32[M] → [M, N].

    K and N must tile exactly (pad upstream; ops.py handles padding); M is
    the grid's row axis and needs no padding.
    """
    M, K = x.shape
    N = w.shape[1]
    G, r, _ = a.shape
    assert w.shape[0] == K and a.shape[2] == K and b.shape == (G, N, r), (
        x.shape, w.shape, a.shape, b.shape)
    assert idx.shape == (M,), (idx.shape, M)
    bn, bk = min(bn, N), min(bk, K)
    assert N % bn == 0 and K % bk == 0, (N, K, bn, bk)
    k_steps = K // bk
    row = pl.Squeezed()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((row, 1, bk), lambda i, j, k, idx: (i, 0, k)),  # x
            pl.BlockSpec((bk, bn), lambda i, j, k, idx: (k, j)),         # w
            pl.BlockSpec((row, r, bk), lambda i, j, k, idx: (idx[i], 0, k)),
            pl.BlockSpec((row, bn, r), lambda i, j, k, idx: (idx[i], j, 0)),
        ],
        out_specs=pl.BlockSpec((row, 1, bn), lambda i, j, k, idx: (i, 0, j)),
        scratch_shapes=[
            pltpu.VMEM((1, bn), jnp.float32),              # base accumulator
            pltpu.VMEM((1, r), jnp.float32),               # x@Aᵀ accumulator
        ],
    )
    y = pl.pallas_call(
        functools.partial(_kernel, scale=scale, k_steps=k_steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, 1, N), x.dtype),
        interpret=interpret,
        name="grouped_lora_matmul_pallas",
    )(idx.astype(jnp.int32), x.reshape(M, 1, K), w, a, b)
    return y.reshape(M, N)
