"""Fused LoRA projection kernel: ``y = x @ W + scale * (x @ Aᵀ) @ Bᵀ``.

TPU-native design (DESIGN.md §3 hardware adaptation):

* grid (M/bm, N/bn, K/bk), K innermost, so both accumulators live in VMEM
  scratch across the K loop and the output tile is written once — a single
  HBM pass over x and W;
* the LoRA rank r ≤ 64 rides along the MXU-aligned tiles: the A tile
  [r, bk] and B tile [bn, r] are tiny and VMEM-resident, so the low-rank
  path adds two small matmuls per tile instead of two extra HBM round-trips
  (the unfused form writes+reads the [M, r] activation and the [M, N] delta);
* default tiles (bm=bn=256, bk=512) keep the working set
  bm·bk + bk·bn + bm·bn + r·(bk+bn) ≈ 0.5 MB at bf16 — far under the ~16 MB
  VMEM budget — with every matmul dim a multiple of the 128-wide MXU;
* accumulation is f32 scratch regardless of input dtype.

Heterogeneous-rank note: clients pad A/B with zero rows/cols
(repro.core.lora), and zeros contribute nothing — one kernel serves all ranks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, a_ref, b_ref, o_ref, acc_ref, xa_ref, *, scale: float,
            k_steps: int):
    """One (bm, bn) output tile; innermost grid dim accumulates over K."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xa_ref[...] = jnp.zeros_like(xa_ref)

    x = x_ref[...]
    acc_ref[...] += jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
    # xa: [bm, r] accumulated over the K loop — A tile is [r, bk]
    xa_ref[...] += jnp.dot(x, a_ref[...].T, preferred_element_type=jnp.float32)

    @pl.when(kk == k_steps - 1)
    def _flush():
        delta = jnp.dot(xa_ref[...], b_ref[...].T,
                        preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + scale * delta).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "bm", "bn", "bk", "interpret"))
def lora_matmul_pallas(x, w, a, b, *, scale: float = 1.0, bm: int = 256,
                       bn: int = 256, bk: int = 512, interpret: bool = False):
    """x: [M, K]; w: [K, N]; a: [r, K]; b: [N, r] → [M, N].

    Shapes must tile exactly (pad upstream; ops.py handles padding).
    """
    M, K = x.shape
    N = w.shape[1]
    r = a.shape[0]
    assert w.shape[0] == K and a.shape[1] == K and b.shape == (N, r), (
        x.shape, w.shape, a.shape, b.shape)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    k_steps = K // bk

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, k_steps=k_steps),
        grid=(M // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),    # x
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),    # w
            pl.BlockSpec((r, bk), lambda i, j, k: (0, k)),     # A
            pl.BlockSpec((bn, r), lambda i, j, k: (j, 0)),     # B
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),                 # base accumulator
            pltpu.VMEM((bm, r), jnp.float32),                  # x@Aᵀ accumulator
        ],
        interpret=interpret,
        name="lora_matmul_pallas",
    )(x, w, a, b)
