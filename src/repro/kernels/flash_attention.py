"""Pallas flash attention (forward): online-softmax over KV tiles in VMEM.

The prefill/train attention hot spot (§Roofline: 32k prefill spends up to
~50% of compute in attention for the dense archs).  TPU-native design:

* grid (B·H, Sq/bq, Sk/bk) with the KV dim innermost: the running max ``m``,
  normaliser ``l`` and the f32 output accumulator live in VMEM scratch
  across the KV loop — one HBM pass over K/V per query tile, no [Sq, Sk]
  score materialisation (the jnp reference scans with O(S·chunk) memory; the
  kernel keeps everything register/VMEM-resident per tile);
* causal + sliding-window masking computed from iota inside the tile, so
  MXU tiles stay dense (masked positions contribute exp(-inf)=0);
* tile defaults bq=bk=256: working set ≈ bq·d + 2·bk·d + bq·bk ≈ 0.6 MB
  at d=128 f32 — far under VMEM; all matmul dims multiples of 128.

Grid iterates KV-before-Q (innermost) so ``pl.when(kk == 0)`` re-initialises
the accumulators at each new query tile.  Heads are folded into the batch
grid dim (GQA handled by the ops.py wrapper via K/V head repetition).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, bq: int, bk: int,
            k_steps: int):
    qi = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                   # [bq, d]
    k = k_ref[0]                                   # [bk, d]
    v = v_ref[0]                                   # [bk, dv]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [bq, bk]

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    ok = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        ok &= q_pos >= k_pos
    if window > 0:
        ok &= (q_pos - k_pos) < window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                          # [bq, bk]
    corr = jnp.exp(m_prev - m_new)                  # [bq, 1]
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v.astype(jnp.float32), preferred_element_type=jnp.float32)

    @pl.when(kk == k_steps - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           bq: int = 256, bk: int = 256,
                           interpret: bool = False):
    """q: [BH, Sq, d]; k: [BH, Sk, d]; v: [BH, Sk, dv] → [BH, Sq, dv].

    Heads pre-folded into the leading dim; Sq % bq == 0 and Sk % bk == 0
    (ops.py pads).  Scale 1/sqrt(d) applied internally.
    """
    BH, Sq, d = q.shape
    Sk, dv = k.shape[1], v.shape[2]
    bq, bk = min(bq, Sq), min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    k_steps = Sk // bk

    return pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(d), causal=causal,
                          window=window, bq=bq, bk=bk, k_steps=k_steps),
        grid=(BH, Sq // bq, k_steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),   # q
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),   # k
            pl.BlockSpec((1, bk, dv), lambda b, i, j: (b, j, 0)),  # v
        ],
        out_specs=pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max m
            pltpu.VMEM((bq, 1), jnp.float32),    # normaliser l
            pltpu.VMEM((bq, dv), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        name="flash_attention_pallas",
    )(q, k, v)
