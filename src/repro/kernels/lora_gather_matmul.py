"""Grouped (multi-adapter) LoRA projection kernel for multi-tenant serving:

    y[b, t] = x[b, t] @ W + scale * (x[b, t] @ A[g_b]ᵀ) @ B[g_b]ᵀ,   g_b = idx[b]

One batch of slots, MANY adapters: slot ``b`` carries the index of its own
LoRA pair in a stacked ``[G, ...]`` adapter bank (the BGMV formulation of
Punica / S-LoRA multi-tenant serving).  The base projection ``x @ W`` is
shared by all tenants; only the tiny low-rank path is gathered per slot.

TPU-native design (rides next to ``lora_matmul.py``'s single-adapter path):

* the per-slot adapter index is a **scalar-prefetch operand**
  (``PrefetchScalarGridSpec``): the index vector lands in SMEM before the
  kernel body runs, so the A/B ``BlockSpec`` index maps steer each
  program's DMA to ``A[idx[b]]`` / ``B[idx[b]]`` — the gather happens in
  the memory system, never as an HBM-materialised ``[M, r, K]`` copy;
* the row block is the run of rows that share one index, read from the
  input's shape: ``x[B, R, K]`` with ``idx[B]`` gives grid
  ``(B, R/br, N/bn, K/bk)`` and a ``[br, bk]`` block of one slot, with
  ``br = R`` up to ``MAX_ROWS`` (then multiples of 8).  Chunked prefill
  (``[slots, chunk, d]``, R = chunk) thus multiplies an MXU-shaped
  ``[chunk, bk] @ [bk, bn]`` per program, and the low-rank path contracts
  the whole block against its slot's pair.  Decode (``[slots, 1, d]``) and
  a flat ``[M, K]`` with an ``[M]`` index have one row per index, so their
  block stays a single row: neighbouring decode rows belong to different
  slots and may carry different adapters, and only a sort of the rows by
  adapter (a segmented gather) could batch them — a different algorithm.
  With whole-extent K/N blocks (below) a decode call still reads W once.
  The slot axis is squeezed out of every block: Mosaic needs the last two
  block dims (8, 128)-divisible or whole, which ``(br, bk)`` of
  ``[B, R, K]`` is;
* K and N blocks are whole where the working set fits ``VMEM_BUDGET``
  (``block_sizes``): W's block index is then the same for every program,
  so W crosses HBM once per call, and no operand needs padding.  Widths
  that do not fit fall back to (bk, bn) = (512, 256) tiles;
* K innermost: both accumulators (base [br, bn] and x@Aᵀ [br, r]) live in
  VMEM scratch across the K loop, one HBM pass over x, output written once;
* accumulation is f32 scratch regardless of input dtype; the low-rank path
  contracts in f32 (the activation block is upcast, so a bf16 activation
  meets an f32 adapter bank exactly as the jnp gather path's promotion
  does), and both adapter contractions are transposed-RHS ``dot_general``s
  (no in-kernel transpose).

Why the row block is the slot's run (measured on a TPU v5e, qwen2-0.5b,
32 slots × chunk 128): with one row per program, a prefill ``wq`` call ran
4096 × 4 × 2 programs, each fetching a fresh 512×256 W tile, 11.5 GB of
DMA for 6.6 GFLOP — 20.7 ms a call, 85% of the serving chip's busy time.
The W block index changed on every program, so nothing was reused.

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
MAX_ROWS = 512                       # largest row block; larger runs tile
VMEM_BUDGET = 12 * 2 ** 20           # of the 16 MiB scoped VMEM on a v5e
TILE_N, TILE_K = 256, 512            # tiles where whole extents do not fit


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def _vmem_bytes(br, bk, bn, r, x_size, w_size, bank_size) -> int:
    """Double-buffered blocks plus the f32 scratch; minor dims padded to
    the 128 lanes they occupy."""
    blocks = (br * _lanes(bk) * x_size + bk * _lanes(bn) * w_size
              + r * _lanes(bk) * bank_size + bn * _lanes(r) * bank_size
              + br * _lanes(bn) * x_size)
    return 2 * blocks + 4 * br * (_lanes(bn) + _lanes(r))


def block_sizes(rows: int, k: int, n: int, r: int, x_dtype, w_dtype,
                bank_dtype, bn: int | None = None,
                bk: int | None = None) -> tuple[int, int, int]:
    """(br, bn, bk) for ``rows`` rows per index against a [k, n] weight:
    whole K and N where they fit ``VMEM_BUDGET`` (or the given tiles)."""
    br = min(rows, MAX_ROWS)
    if bn is None and bk is None:
        size = lambda d: jnp.dtype(d).itemsize
        if _vmem_bytes(br, k, n, r, size(x_dtype), size(w_dtype),
                       size(bank_dtype)) <= VMEM_BUDGET:
            return br, n, k
    return br, min(bn or TILE_N, n), min(bk or TILE_K, k)


def _kernel(idx_ref, x_ref, w_ref, a_ref, b_ref, o_ref, acc_ref, xa_ref, *,
            scale: float, k_steps: int):
    """One (slot, row block, bn) output tile; innermost grid dim
    accumulates over K.  ``idx_ref`` is consumed by the BlockSpec index
    maps (the A/B tiles arriving here already belong to this slot's
    adapter)."""
    del idx_ref
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xa_ref[...] = jnp.zeros_like(xa_ref)

    x = x_ref[...]                                         # [br, bk]
    acc_ref[...] += jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
    # xa: [br, r] accumulated over the K loop — the A tile is [r, bk]
    xa_ref[...] += jax.lax.dot_general(
        x.astype(jnp.float32), a_ref[...].astype(jnp.float32), _NT,
        preferred_element_type=jnp.float32)

    @pl.when(kk == k_steps - 1)
    def _flush():
        delta = jax.lax.dot_general(                       # [br, bn]
            xa_ref[...], b_ref[...].astype(jnp.float32), _NT,
            preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + scale * delta).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "br", "bn", "bk", "interpret"))
def grouped_lora_matmul_pallas(x, w, a, b, idx, *, scale: float = 1.0,
                               br: int, bn: int, bk: int,
                               interpret: bool = False):
    """x: [B, R, K]; w: [K, N]; a: [G, r, K]; b: [G, N, r]; idx: i32[B]
    → [B, R, N]: rows ``x[b]`` use adapter ``idx[b]``.

    R, K and N must tile exactly by (br, bk, bn) (pad upstream; ops.py
    handles padding); B is the grid's slot axis and needs no padding.
    """
    B, R, K = x.shape
    N = w.shape[1]
    G, r, _ = a.shape
    assert w.shape[0] == K and a.shape[2] == K and b.shape == (G, N, r), (
        x.shape, w.shape, a.shape, b.shape)
    assert idx.shape == (B,), (idx.shape, B)
    assert R % br == 0 and N % bn == 0 and K % bk == 0, (R, N, K, br, bn, bk)
    k_steps = K // bk
    slot = pl.Squeezed()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, R // br, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((slot, br, bk), lambda s, i, j, k, idx: (s, i, k)),
            pl.BlockSpec((bk, bn), lambda s, i, j, k, idx: (k, j)),
            pl.BlockSpec((slot, r, bk),
                         lambda s, i, j, k, idx: (idx[s], 0, k)),
            pl.BlockSpec((slot, bn, r),
                         lambda s, i, j, k, idx: (idx[s], j, 0)),
        ],
        out_specs=pl.BlockSpec((slot, br, bn),
                               lambda s, i, j, k, idx: (s, i, j)),
        scratch_shapes=[
            pltpu.VMEM((br, bn), jnp.float32),             # base accumulator
            pltpu.VMEM((br, r), jnp.float32),              # x@Aᵀ accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, k_steps=k_steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, R, N), x.dtype),
        interpret=interpret,
        name="grouped_lora_matmul_pallas",
    )(idx.astype(jnp.int32), x, w, a, b)
