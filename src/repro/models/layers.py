"""Neural net layers shared by every architecture family.

All layers are pure functions ``apply(params, x, ...) -> y`` over explicit
parameter pytrees.  Conventions:

* weights are stored ``[in_dim, out_dim]`` so forward is ``x @ w``;
* LoRA adapters (``{"A": [r, in], "B": [out, r]}``) are threaded as optional
  per-weight entries and applied as ``y += scale * (x @ A^T) @ B^T``;
* sequence attention supports three execution paths: naive (short sequences),
  chunked online-softmax "flash" (long prefill, O(S·chunk) memory), and a
  single-token decode path over a KV cache.
"""

from __future__ import annotations

import math
from typing import Mapping

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.lora import grouped_lora_matmul, lora_matmul
from repro.models.config import MLAConfig, ModelConfig, MoEConfig, SSMConfig

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# basics
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def _rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, D] (or [..., H, D] with scalar-ish positions [...]),
    positions broadcastable to x's leading+seq dims."""
    d = x.shape[-1]
    freqs = _rope_freqs(d, theta)                       # [D/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, D/2]
    ang = ang[..., None, :]                              # add head axis
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _softcap(scores: jax.Array, cap: float) -> jax.Array:
    if cap and cap > 0:
        return cap * jnp.tanh(scores / cap)
    return scores


# ---------------------------------------------------------------------------
# dense attention (GQA, optional sliding window / softcap / LoRA on wq & wv)
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, cross: bool = False, n: int = 1,
                   kv_in: int | None = None):
    """Stacked (leading dim n) attention params."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    if kv_in is None:
        kv_in = (cfg.vision_dim or d) if cross else d
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    std = 1.0 / math.sqrt(d)
    p = {
        "wq": jax.random.normal(ks[0], (n, d, h * hd), dt) * std,
        "wk": jax.random.normal(ks[1], (n, kv_in, kv * hd), dt) * (1.0 / math.sqrt(kv_in)),
        "wv": jax.random.normal(ks[2], (n, kv_in, kv * hd), dt) * (1.0 / math.sqrt(kv_in)),
        "wo": jax.random.normal(ks[3], (n, h * hd, d), dt) * (1.0 / math.sqrt(h * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((n, h * hd), dt)
        p["bk"] = jnp.zeros((n, kv * hd), dt)
        p["bv"] = jnp.zeros((n, kv * hd), dt)
    if cross:
        p["gate"] = jnp.zeros((n,), dt)  # tanh-gated cross-attn (llama-3.2-v)
    return p


def _qkv(params, x, kv_src, cfg: ModelConfig, lora, lora_scale,
         lora_idx=None, lora_kernel: bool = False):
    """``lora_idx`` [B]: LoRA entries are stacked banks [G, ...] and row
    ``b`` applies adapter ``lora_idx[b]`` (multi-tenant BGMV;
    ``lora_kernel`` selects the Pallas gather kernel)."""
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    lq = lora.get("wq") if lora else None
    lv = lora.get("wv") if lora else None
    if lora_idx is None:
        q = lora_matmul(x, params["wq"], lq, lora_scale)
        v = lora_matmul(kv_src, params["wv"], lv, lora_scale)
    else:
        q = grouped_lora_matmul(x, params["wq"], lq, lora_idx, lora_scale,
                                kernel=lora_kernel)
        v = grouped_lora_matmul(kv_src, params["wv"], lv, lora_idx,
                                lora_scale, kernel=lora_kernel)
    k = kv_src @ params["wk"]
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    B = x.shape[0]
    q = q.reshape(B, -1, h, hd)
    k = k.reshape(B, -1, kv, hd)
    v = v.reshape(B, -1, kv, hd)
    return q, k, v


def _attn_mask(q_pos, k_pos, causal: bool, window: int):
    """[..., Sq, Sk] additive mask from position vectors."""
    diff = q_pos[..., :, None] - k_pos[..., None, :]
    ok = jnp.ones(diff.shape, bool)
    if causal:
        ok &= diff >= 0
    if window and window > 0:
        ok &= diff < window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


@jax.named_scope("attention")
def multihead_attention(q, k, v, *, causal: bool, window: int = 0, softcap: float = 0.0,
                        q_pos=None, k_pos=None, pad_mask=None, chunked: bool | None = None,
                        q_chunk: int = 512, kv_chunk: int = 1024):
    """q: [B,Sq,H,D]; k,v: [B,Sk,KV,D] (GQA).  Returns [B,Sq,H,D].

    ``chunked=None`` auto-selects the flash path for Sk > 2048.
    ``pad_mask``: [B, Sk] 1=valid.

    ``q_pos`` / ``k_pos`` may be *batched* ([B, Sq] / [B, Sk]) — each row
    attends at its own positions (the serving engine's ragged per-slot
    offsets).  The batched form flows through both the naive and the
    chunked online-softmax path; only the sliding-window chunk-skip
    shortcut is disabled for it (the skip assumes positions follow the
    array index layout, which ragged per-row offsets break).
    """
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // KV
    if q_pos is None:
        q_pos = jnp.arange(Sq)
    if k_pos is None:
        k_pos = jnp.arange(Sk)
    q_pos, k_pos = jnp.asarray(q_pos), jnp.asarray(k_pos)
    batched_pos = q_pos.ndim > 1 or k_pos.ndim > 1
    if batched_pos:
        q_pos = jnp.broadcast_to(q_pos, (B, Sq))
        k_pos = jnp.broadcast_to(k_pos, (B, Sk))
    scale = 1.0 / math.sqrt(D)
    if chunked is None:
        # chunk whenever the full score block would be large — the naive
        # path materialises [B,KV,G,Sq,Sk] f32 (found via §Perf H3: VLM
        # cross-attention with Sq=4096, Sk=1600 vision tokens cost ~1.7 GB
        # per layer in scores alone)
        chunked = Sk > 2048 or Sq * Sk > 2048 * 2048

    qg = q.reshape(B, Sq, KV, G, D)

    if not chunked:
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        scores = _softcap(scores, softcap)
        mask = _attn_mask(q_pos, k_pos, causal, window)  # [Sq,Sk] | [B,Sq,Sk]
        scores = scores + (mask[:, None, None] if batched_pos else mask)
        if pad_mask is not None:
            scores = scores + jnp.where(pad_mask, 0.0, NEG_INF)[:, None, None, None, :]
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
        return out.reshape(B, Sq, H, Dv)

    # ---- chunked online-softmax ("flash") path ----------------------------
    nq = -(-Sq // q_chunk)
    nk = -(-Sk // kv_chunk)
    Sq_pad, Sk_pad = nq * q_chunk, nk * kv_chunk

    def pad_to(x, n, axis):
        pad = [(0, 0)] * x.ndim
        pad[axis] = (0, n - x.shape[axis])
        return jnp.pad(x, pad)

    qg_p = pad_to(qg, Sq_pad, 1).reshape(B, nq, q_chunk, KV, G, D)
    k_p = pad_to(k, Sk_pad, 1).reshape(B, nk, kv_chunk, KV, D)
    v_p = pad_to(v, Sk_pad, 1).reshape(B, nk, kv_chunk, KV, Dv)
    if batched_pos:
        qpos_p = pad_to(q_pos, Sq_pad, 1).reshape(B, nq, q_chunk)
        kpos_p = pad_to(k_pos + 1, Sk_pad, 1).reshape(B, nk, kv_chunk) - 1
    else:
        qpos_p = pad_to(q_pos, Sq_pad, 0).reshape(nq, q_chunk)
        kpos_p = pad_to(k_pos + 1, Sk_pad, 0).reshape(nk, kv_chunk) - 1  # pads → -1 (invalid)
    if pad_mask is None:
        pad_mask = jnp.ones((B, Sk), bool)
    pm_p = pad_to(pad_mask.astype(bool), Sk_pad, 1).reshape(B, nk, kv_chunk)

    # sliding-window chunk skip (§Perf): with a causal window only
    # ceil((window + q_chunk)/kv_chunk) + 1 KV chunks can intersect a query
    # chunk — scan those (clamped dynamic indices, out-of-range steps fully
    # masked) instead of all nk. 8–32× less attention work for gemma3-style
    # local layers at 32k (reflected in analytic.py `window_skip`).
    # Disabled for batched positions: the chunk arithmetic assumes q/k
    # positions follow the array index layout.
    window_skip = bool(causal and window and window > 0) and not batched_pos
    nk_eff = min((window + q_chunk) // kv_chunk + 2, nk) if window_skip else nk

    def q_step(_, qi):
        qc = qg_p[:, qi]          # [B, qc, KV, G, D]
        qp = qpos_p[:, qi] if batched_pos else qpos_p[qi]

        def kv_step(carry, step):
            m, l, acc = carry
            if window_skip:
                # last relevant chunk is the one containing qi's chunk end
                ki_raw = qi + 1 - nk_eff + step if q_chunk == kv_chunk else \
                    (qi * q_chunk + q_chunk - 1) // kv_chunk + 1 - nk_eff + step
                in_range = (ki_raw >= 0) & (ki_raw < nk)
                ki = jnp.clip(ki_raw, 0, nk - 1)
            else:
                ki = step
                in_range = jnp.bool_(True)
            kc, vc = k_p[:, ki], v_p[:, ki]
            kp = kpos_p[:, ki] if batched_pos else kpos_p[ki]
            s = jnp.einsum("bqkgd,bskd->bkgqs", qc.astype(jnp.float32),
                           kc.astype(jnp.float32)) * scale
            s = _softcap(s, softcap)
            mask = _attn_mask(qp, kp, causal, window)
            if batched_pos:
                mask = jnp.where((kp >= 0)[:, None, :], mask, NEG_INF)
                s = s + mask[:, None, None]
            else:
                mask = jnp.where((kp >= 0)[None, :], mask, NEG_INF)
                s = s + mask
            s = s + jnp.where(pm_p[:, ki], 0.0, NEG_INF)[:, None, None, None, :]
            s = jnp.where(in_range, s, NEG_INF)   # clamped duplicates masked
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p, vc.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_chunk, Dv), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk_eff))
        out = acc / jnp.maximum(l, 1e-30)[..., None]             # [B,KV,G,qc,D]
        return None, out.transpose(0, 3, 1, 2, 4)                # [B,qc,KV,G,D]

    # remat each q-chunk: without this the backward pass keeps every
    # [B,KV,G,qc,kc] f32 score block as a residual (§Perf H3 iter 3 —
    # ~10 GB/device for the 4k×4k VLM train step); recompute instead.
    q_step = jax.checkpoint(q_step, prevent_cse=False)
    _, outs = lax.scan(q_step, None, jnp.arange(nq))             # [nq,B,qc,KV,G,Dv]
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq_pad, H, Dv)[:, :Sq]
    return out.astype(v.dtype)


def attention_forward(params, x, cfg: ModelConfig, *, kind: str, lora=None,
                      lora_scale: float = 1.0, positions=None, pad_mask=None,
                      kv_src=None):
    """Full-sequence attention sublayer (pre-norm residual handled by caller).

    kind: "attn" (global causal), "attn_local" (sliding window), "cross_attn".
    """
    cross = kind == "cross_attn"
    src = kv_src if cross else x
    q, k, v = _qkv(params, x, src, cfg, lora, lora_scale)
    B, S = x.shape[0], x.shape[1]
    if positions is None:
        positions = jnp.arange(S)
    if not cross:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        window = cfg.sliding_window if kind == "attn_local" else 0
        out = multihead_attention(q, k, v, causal=True, window=window,
                                  softcap=cfg.attn_logit_softcap,
                                  q_pos=positions, k_pos=positions, pad_mask=pad_mask)
    else:
        out = multihead_attention(q, k, v, causal=False, pad_mask=pad_mask)
    y = out.reshape(B, S, -1) @ params["wo"]
    if cross and "gate" in params:
        y = jnp.tanh(params["gate"]).astype(y.dtype) * y
    return y


def attention_decode(params, x, cache, cfg: ModelConfig, *, kind: str, pos,
                     lora=None, lora_scale: float = 1.0, seq_axis=None):
    """One-token decode.  x: [B, 1, d]; cache: {"k","v": [B, Smax, KV, D]}
    (for cross_attn the cache holds the precomputed vision K/V and is static).
    ``pos``: scalar current position.  Returns (y [B,1,d], new_cache)."""
    B = x.shape[0]
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    if kind == "cross_attn":
        q = lora_matmul(x, params["wq"], lora.get("wq") if lora else None, lora_scale)
        if "bq" in params:
            q = q + params["bq"]
        q = q.reshape(B, 1, h, hd)
        out = multihead_attention(q, cache["k"], cache["v"], causal=False,
                                  pad_mask=cache.get("mask"), chunked=False)
        y = out.reshape(B, 1, -1) @ params["wo"]
        if "gate" in params:
            y = jnp.tanh(params["gate"]).astype(y.dtype) * y
        return y, cache

    q, k_new, v_new = _qkv(params, x, x, cfg, lora, lora_scale)
    pos_arr = jnp.full((1,), pos, jnp.int32)
    q = apply_rope(q, pos_arr, cfg.rope_theta)
    k_new = apply_rope(k_new, pos_arr, cfg.rope_theta)
    Smax = cache["k"].shape[1]
    if kind == "attn_local" and cfg.sliding_window and Smax <= cfg.sliding_window:
        slot = jnp.mod(pos, Smax)           # rolling window cache
    else:
        slot = pos
    k = lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), slot, 1)
    v = lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), slot, 1)
    k_pos = jnp.arange(Smax)
    if kind == "attn_local" and cfg.sliding_window and Smax <= cfg.sliding_window:
        # positions of ring slots: slot i holds the latest pos ≡ i (mod Smax)
        k_pos = pos - jnp.mod(pos - k_pos, Smax)
    window = cfg.sliding_window if kind == "attn_local" else 0
    valid = (k_pos <= pos) & (k_pos >= 0)
    out = multihead_attention(q, k, v, causal=True, window=window,
                              softcap=cfg.attn_logit_softcap,
                              q_pos=pos_arr, k_pos=k_pos,
                              pad_mask=jnp.broadcast_to(valid, (B, Smax)),
                              chunked=False)
    y = out.reshape(B, 1, -1) @ params["wo"]
    return y, {"k": k, "v": v}


def attention_decode_batch(params, x, cache, cfg: ModelConfig, *, kind: str,
                           pos, valid=None, lora=None,
                           lora_scale: float = 1.0, lora_idx=None,
                           lora_kernel: bool = False,
                           chunked: bool | None = False):
    """Multi-token, per-row-position cache-write decode — the serving hot
    path (one-token multi-adapter decode and chunked prefill share it).

    ``x``: [B, C, d] (C = 1 for decode, C = prefill chunk); ``pos``: [B]
    per-row first position — row ``b`` processes positions
    ``pos[b] .. pos[b]+C-1``.  ``valid``: optional [B, C] ragged-tail mask;
    masked positions leave their cache rows untouched (the gather-then-set
    keeps the old row) and their outputs are garbage the caller discards.
    ``lora_idx`` [B] makes the LoRA entries stacked banks (BGMV, see
    ``_qkv``); ``chunked`` selects ``multihead_attention``'s online-softmax
    path for the intra-chunk causal attention (None = auto).

    Invariants the caller (ServingEngine / make_chunked_prefill_step)
    upholds: valid positions stay below the cache length; for ring caches
    C ≤ ring size (per-row scatter indices must not collide) AND, when
    C > 1, every valid position < ring size — a chunk writes all its K/V
    rows BEFORE attending, so a write at position p ≥ ring would overwrite
    the slot holding p−ring, which earlier queries of the same chunk still
    attend (p−ring always falls inside their window because ring ≤ window);
    ring-wrapping prompts must stream one position at a time instead
    (engine-gated).  Returns (y [B, C, d], new cache {"k","v":
    [B, Smax, KV, D]}).
    """
    if kind == "cross_attn":
        raise NotImplementedError("batched decode covers self-attention "
                                  "caches only (engine gates cross-attn)")
    B, C = x.shape[:2]
    q, k_new, v_new = _qkv(params, x, x, cfg, lora, lora_scale,
                           lora_idx=lora_idx, lora_kernel=lora_kernel)
    q_pos = pos[:, None] + jnp.arange(C)                       # [B, C]
    q = apply_rope(q, q_pos, cfg.rope_theta)
    k_new = apply_rope(k_new, q_pos, cfg.rope_theta)
    Smax = cache["k"].shape[1]
    ring = (kind == "attn_local" and cfg.sliding_window
            and Smax <= cfg.sliding_window)
    slots = jnp.mod(q_pos, Smax) if ring else jnp.clip(q_pos, 0, Smax - 1)
    rows = jnp.arange(B)[:, None]

    def upd(c, new):
        new = new.astype(c.dtype)
        if valid is not None:
            # masked positions write back the row they gathered — identity
            new = jnp.where(valid[..., None, None], new, c[rows, slots])
        return c.at[rows, slots].set(new)

    k = upd(cache["k"], k_new)
    v = upd(cache["v"], v_new)

    n_val = valid.sum(1) if valid is not None else jnp.full((B,), C, pos.dtype)
    cur = pos + n_val - 1                # last position actually written
    if ring:
        # ring slot t holds the latest written position ≡ t (mod Smax); cur
        # (not pos + C - 1) anchors it so masked tails keep advertising the
        # OLD positions their slots still hold
        t = jnp.arange(Smax)[None, :]
        k_pos = cur[:, None] - jnp.mod(cur[:, None] - t, Smax)
    else:
        k_pos = jnp.broadcast_to(jnp.arange(Smax), (B, Smax))
    window = cfg.sliding_window if kind == "attn_local" else 0
    ok = (k_pos >= 0) & (k_pos <= cur[:, None])
    out = multihead_attention(q, k, v, causal=True, window=window,
                              softcap=cfg.attn_logit_softcap,
                              q_pos=q_pos, k_pos=k_pos, pad_mask=ok,
                              chunked=chunked, q_chunk=max(C, 1),
                              kv_chunk=min(512, Smax))
    y = out.reshape(B, C, -1) @ params["wo"]
    return y, {"k": k, "v": v}


# ---------------------------------------------------------------------------
# MLA — DeepSeek-V2 multi-head latent attention (compressed KV cache)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig, n: int = 1):
    m: MLAConfig = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 6)
    p = {}
    if m.q_lora_rank:
        p["wdq"] = jax.random.normal(ks[0], (n, d, m.q_lora_rank), dt) / math.sqrt(d)
        p["wuq"] = jax.random.normal(ks[1], (n, m.q_lora_rank, h * qd), dt) / math.sqrt(m.q_lora_rank)
    else:
        p["wq"] = jax.random.normal(ks[0], (n, d, h * qd), dt) / math.sqrt(d)
    p["wkv_a"] = jax.random.normal(ks[2], (n, d, m.kv_lora_rank + m.qk_rope_head_dim), dt) / math.sqrt(d)
    p["wkv_b"] = jax.random.normal(
        ks[3], (n, m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim)), dt) / math.sqrt(m.kv_lora_rank)
    p["wo"] = jax.random.normal(ks[4], (n, h * m.v_head_dim, d), dt) / math.sqrt(h * m.v_head_dim)
    return p


def _mla_q(params, x, cfg: ModelConfig, lora, lora_scale):
    m, h = cfg.mla, cfg.num_heads
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    if "wq" in params:
        q = lora_matmul(x, params["wq"], lora.get("wq") if lora else None, lora_scale)
    else:
        cq = x @ params["wdq"]
        q = lora_matmul(cq, params["wuq"], lora.get("wuq") if lora else None, lora_scale)
    B, S = x.shape[:2]
    q = q.reshape(B, S, h, qd)
    return jnp.split(q, [m.qk_nope_head_dim], axis=-1)  # q_nope, q_rope


def _mla_effective_wkv_b(params, cfg: ModelConfig, lora, lora_scale):
    w = params["wkv_b"]
    if lora and "wkv_b" in lora:
        w = w + (lora_scale * jnp.einsum(
            "or,ri->io", lora["wkv_b"]["B"], lora["wkv_b"]["A"])).astype(w.dtype)
    return w


def mla_forward(params, x, cfg: ModelConfig, *, lora=None, lora_scale: float = 1.0,
                positions=None, pad_mask=None):
    """Full-sequence (training/prefill) MLA with expanded K/V."""
    m = cfg.mla
    h = cfg.num_heads
    B, S = x.shape[:2]
    if positions is None:
        positions = jnp.arange(S)
    q_nope, q_rope = _mla_q(params, x, cfg, lora, lora_scale)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv_kr = x @ params["wkv_a"]
    c_kv, k_rope = jnp.split(ckv_kr, [m.kv_lora_rank], axis=-1)   # [B,S,c], [B,S,rd]
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)  # 1 shared head
    wkv_b = _mla_effective_wkv_b(params, cfg, lora, lora_scale)
    kv = (c_kv @ wkv_b).reshape(B, S, h, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, h, m.qk_rope_head_dim))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    out = multihead_attention(q, k, v, causal=True, q_pos=positions, k_pos=positions,
                              pad_mask=pad_mask)
    return out.reshape(B, S, -1) @ params["wo"]


def mla_decode(params, x, cache, cfg: ModelConfig, *, pos, lora=None,
               lora_scale: float = 1.0, seq_axis=None):
    """Absorbed-weight decode over the *compressed* cache
    {"c_kv": [B,Smax,c], "k_rope": [B,Smax,rd]} — MLA's signature trick: the
    up-projection is folded into the query/context sides so per-step FLOPs
    scale with kv_lora_rank, not with H·head_dim."""
    m, h = cfg.mla, cfg.num_heads
    B = x.shape[0]
    pos_arr = jnp.full((1,), pos, jnp.int32)
    q_nope, q_rope = _mla_q(params, x, cfg, lora, lora_scale)     # [B,1,h,*]
    q_rope = apply_rope(q_rope, pos_arr, cfg.rope_theta)

    ckv_kr = x @ params["wkv_a"]
    c_new, kr_new = jnp.split(ckv_kr, [m.kv_lora_rank], axis=-1)
    kr_new = apply_rope(kr_new[:, :, None, :], pos_arr, cfg.rope_theta)[:, :, 0, :]
    c_kv = lax.dynamic_update_slice_in_dim(cache["c_kv"], c_new.astype(cache["c_kv"].dtype), pos, 1)
    k_rope = lax.dynamic_update_slice_in_dim(cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), pos, 1)

    wkv_b = _mla_effective_wkv_b(params, cfg, lora, lora_scale)
    wkv_b = wkv_b.reshape(m.kv_lora_rank, h, m.qk_nope_head_dim + m.v_head_dim)
    w_uk, w_uv = jnp.split(wkv_b, [m.qk_nope_head_dim], axis=-1)  # [c,h,nope],[c,h,v]

    q_abs = jnp.einsum("bshn,chn->bshc", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))                   # [B,1,h,c]
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    s = (jnp.einsum("bshc,btc->bhst", q_abs, c_kv.astype(jnp.float32))
         + jnp.einsum("bshr,btr->bhst", q_rope.astype(jnp.float32),
                      k_rope.astype(jnp.float32))) * scale         # [B,h,1,Smax]
    Smax = c_kv.shape[1]
    valid = jnp.arange(Smax) <= pos
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    if seq_axis is not None:
        # keep scores sequence-sharded through the softmax so the context
        # contraction reduces with a [B,h,c]-sized all-reduce instead of
        # all-gathering [B,h,S] scores (EXPERIMENTS.md §Perf H1 iter 3)
        from jax.sharding import PartitionSpec as _P
        s = jax.lax.with_sharding_constraint(s, _P(None, None, None, seq_axis))
    p = jax.nn.softmax(s, axis=-1)
    if seq_axis is not None:
        from jax.sharding import PartitionSpec as _P
        p = jax.lax.with_sharding_constraint(p, _P(None, None, None, seq_axis))
    ctx_c = jnp.einsum("bhst,btc->bshc", p, c_kv.astype(jnp.float32))   # [B,1,h,c]
    ctx_v = jnp.einsum("bshc,chv->bshv", ctx_c, w_uv.astype(jnp.float32))
    y = ctx_v.reshape(B, 1, -1).astype(x.dtype) @ params["wo"]
    return y, {"c_kv": c_kv, "k_rope": k_rope}


def mla_decode_batch(params, x, cache, cfg: ModelConfig, *, pos, valid=None,
                     lora=None, lora_scale: float = 1.0, lora_idx=None,
                     lora_kernel: bool = False):
    """Absorbed-weight MLA decode over ``x`` [B, C, d] at per-row positions
    ``pos`` [B] (the multi-adapter / chunked-prefill sibling of
    :func:`mla_decode`).  ``valid`` [B, C] masks ragged chunk tails.

    LoRA: the q-side projection goes through the grouped (BGMV) path like
    ``_qkv``; ``wkv_b``'s LoRA must fold into an effective weight for the
    absorption trick, so the banked case folds per BANK entry ([G, c, ·],
    G = bank slots, small) and gathers per row — the ``lora_kernel`` flag
    therefore steers the q side only."""
    m, h = cfg.mla, cfg.num_heads
    B, C = x.shape[:2]
    q_pos = pos[:, None] + jnp.arange(C)                        # [B, C]
    if lora_idx is None:
        q_nope, q_rope = _mla_q(params, x, cfg, lora, lora_scale)
    else:
        qd = m.qk_nope_head_dim + m.qk_rope_head_dim
        if "wq" in params:
            q = grouped_lora_matmul(x, params["wq"],
                                    lora.get("wq") if lora else None,
                                    lora_idx, lora_scale, kernel=lora_kernel)
        else:
            cq = x @ params["wdq"]
            q = grouped_lora_matmul(cq, params["wuq"],
                                    lora.get("wuq") if lora else None,
                                    lora_idx, lora_scale, kernel=lora_kernel)
        q = q.reshape(B, C, h, qd)
        q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, q_pos, cfg.rope_theta)

    ckv_kr = x @ params["wkv_a"]
    c_new, kr_new = jnp.split(ckv_kr, [m.kv_lora_rank], axis=-1)
    kr_new = apply_rope(kr_new[:, :, None, :], q_pos, cfg.rope_theta)[:, :, 0, :]
    Smax = cache["c_kv"].shape[1]
    slots = jnp.clip(q_pos, 0, Smax - 1)
    rows = jnp.arange(B)[:, None]

    def upd(c, new):
        new = new.astype(c.dtype)
        if valid is not None:
            new = jnp.where(valid[..., None], new, c[rows, slots])
        return c.at[rows, slots].set(new)

    c_kv = upd(cache["c_kv"], c_new)
    k_rope = upd(cache["k_rope"], kr_new)

    w = params["wkv_b"]
    entry = lora.get("wkv_b") if lora else None
    if entry is not None:
        delta = jnp.einsum("...or,...ri->...io", entry["B"], entry["A"])
        if lora_idx is None:
            w = w + (lora_scale * delta).astype(w.dtype)        # [c, hnv]
        else:
            w = (w + lora_scale * delta.astype(w.dtype))[lora_idx]  # [B, c, hnv]
    per_row_w = w.ndim == 3
    nv = m.qk_nope_head_dim + m.v_head_dim
    if per_row_w:
        w = w.reshape(B, m.kv_lora_rank, h, nv)
    else:
        w = w.reshape(m.kv_lora_rank, h, nv)
    w_uk, w_uv = jnp.split(w, [m.qk_nope_head_dim], axis=-1)

    if per_row_w:
        q_abs = jnp.einsum("bshn,bchn->bshc", q_nope.astype(jnp.float32),
                           w_uk.astype(jnp.float32))
    else:
        q_abs = jnp.einsum("bshn,chn->bshc", q_nope.astype(jnp.float32),
                           w_uk.astype(jnp.float32))            # [B,C,h,c]
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    s = (jnp.einsum("bshc,btc->bhst", q_abs, c_kv.astype(jnp.float32))
         + jnp.einsum("bshr,btr->bhst", q_rope.astype(jnp.float32),
                      k_rope.astype(jnp.float32))) * scale      # [B,h,C,Smax]
    ok = jnp.arange(Smax)[None, None, :] <= q_pos[:, :, None]   # [B,C,Smax]
    s = jnp.where(ok[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    ctx_c = jnp.einsum("bhst,btc->bshc", p, c_kv.astype(jnp.float32))
    if per_row_w:
        ctx_v = jnp.einsum("bshc,bchv->bshv", ctx_c, w_uv.astype(jnp.float32))
    else:
        ctx_v = jnp.einsum("bshc,chv->bshv", ctx_c, w_uv.astype(jnp.float32))
    y = ctx_v.reshape(B, C, -1).astype(x.dtype) @ params["wo"]
    return y, {"c_kv": c_kv, "k_rope": k_rope}


# ---------------------------------------------------------------------------
# feed-forward: dense SwiGLU and MoE (sort-based capacity dispatch)
# ---------------------------------------------------------------------------

def init_mlp(key, d: int, ff: int, dtype, n: int = 1):
    ks = jax.random.split(key, 3)
    dt = jnp.dtype(dtype)
    return {
        "w1": jax.random.normal(ks[0], (n, d, ff), dt) / math.sqrt(d),
        "w3": jax.random.normal(ks[1], (n, d, ff), dt) / math.sqrt(d),
        "w2": jax.random.normal(ks[2], (n, ff, d), dt) / math.sqrt(ff),
    }


@jax.named_scope("mlp")
def mlp_forward(params, x):
    h = jax.nn.silu(x @ params["w1"]) * (x @ params["w3"])
    return h @ params["w2"]


def init_moe(key, cfg: ModelConfig, n: int = 1):
    mo: MoEConfig = cfg.moe
    d = cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    p = {
        "router": jax.random.normal(ks[0], (n, d, mo.num_experts), jnp.float32) / math.sqrt(d),
        "w1": jax.random.normal(ks[1], (n, mo.num_experts, d, mo.d_ff_expert), dt) / math.sqrt(d),
        "w3": jax.random.normal(ks[2], (n, mo.num_experts, d, mo.d_ff_expert), dt) / math.sqrt(d),
        "w2": jax.random.normal(ks[3], (n, mo.num_experts, mo.d_ff_expert, d), dt) / math.sqrt(mo.d_ff_expert),
    }
    if mo.num_shared_experts:
        ffs = (mo.d_ff_shared or mo.d_ff_expert) * mo.num_shared_experts
        p["shared"] = init_mlp(ks[4], d, ffs, dt, n=n)
    return p


def moe_forward(params, x, cfg: ModelConfig, expert_spec=None):
    """GShard-style capacity dispatch implemented with sort + scatter (no
    [T,E,C] one-hot).  FLOPs scale with selected tokens: E·C ≈ k·T·cf.
    Returns (y, aux_loss).

    ``expert_spec``: optional PartitionSpec for the [E, C, d] dispatch
    buffers (e.g. P("data", None, "model")) — pinning the expert dim onto a
    mesh axis makes XLA move *tokens* (all-to-all) instead of all-gathering
    the expert weights: the expert-parallel hillclimb (EXPERIMENTS.md §Perf).
    """
    mo: MoEConfig = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, K = mo.num_experts, mo.experts_per_token
    C = max(int(math.ceil(K * T / E * mo.capacity_factor)), 1)

    xf = x.reshape(T, d)
    logits = (xf.astype(jnp.float32) @ params["router"])            # [T,E] f32
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = lax.top_k(probs, K)                                # [T,K]
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

    # ---- load-balance auxiliary loss (Switch/GShard form) -----------------
    me = jnp.mean(probs, axis=0)                                    # [E]
    ce = jnp.mean(jax.nn.one_hot(ids[:, 0], E, dtype=jnp.float32), axis=0)
    aux = mo.aux_loss_coef * E * jnp.sum(me * ce)

    # ---- sort-based dispatch ----------------------------------------------
    flat_e = ids.reshape(-1)                                        # [T*K]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")  # [E]
    pos_in_e = jnp.arange(T * K) - starts[sorted_e]
    tok_idx = order // K
    valid = pos_in_e < C
    pos_c = jnp.clip(pos_in_e, 0, C - 1)

    buf = jnp.zeros((E, C, d), x.dtype)
    buf = buf.at[sorted_e, pos_c].add(xf[tok_idx] * valid[:, None].astype(x.dtype))
    if expert_spec is not None:
        buf = jax.lax.with_sharding_constraint(buf, expert_spec)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["w1"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, params["w3"])
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["w2"])           # [E,C,d]
    if expert_spec is not None:
        out_buf = jax.lax.with_sharding_constraint(out_buf, expert_spec)

    y_sorted = out_buf[sorted_e, pos_c] * valid[:, None].astype(x.dtype)
    g_sorted = gates.reshape(-1)[order].astype(x.dtype)
    y = jnp.zeros((T, d), x.dtype).at[tok_idx].add(y_sorted * g_sorted[:, None])

    if "shared" in params:
        y = y + mlp_forward(params["shared"], xf)
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Mamba-2 (SSD — state space duality, arXiv:2405.21060), chunked scan
# ---------------------------------------------------------------------------

def init_mamba(key, cfg: ModelConfig, n: int = 1):
    s: SSMConfig = cfg.ssm
    d = cfg.d_model
    d_in = s.expand * d
    nheads = d_in // s.head_dim
    conv_ch = d_in + 2 * s.state_dim
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    proj_out = 2 * d_in + 2 * s.state_dim + nheads  # z, xBC, dt
    dt_init = jnp.exp(jax.random.uniform(ks[2], (n, nheads))
                      * (math.log(s.dt_max) - math.log(s.dt_min)) + math.log(s.dt_min))
    return {
        "in_proj": jax.random.normal(ks[0], (n, d, proj_out), dt) / math.sqrt(d),
        "conv_w": jax.random.normal(ks[1], (n, s.conv_width, conv_ch), dt) / math.sqrt(s.conv_width),
        "conv_b": jnp.zeros((n, conv_ch), dt),
        "A_log": jnp.log(jnp.broadcast_to(jnp.arange(1, nheads + 1, dtype=jnp.float32), (n, nheads))),
        "D": jnp.ones((n, nheads), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(dt_init)).astype(jnp.float32),
        "gate_norm": jnp.ones((n, d_in), dt),
        "out_proj": jax.random.normal(ks[3], (n, d_in, d), dt) / math.sqrt(d_in),
    }


def _causal_conv(x, w, b):
    """x: [B,S,C]; w: [W,C] depthwise; left-padded causal conv."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = lax.conv_general_dilated(
        xp.astype(jnp.float32),
        w.astype(jnp.float32)[:, None, :],            # [W, 1, C]
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=w.shape[1])
    return (out + b.astype(jnp.float32)).astype(x.dtype)


def _segsum(x):
    """x: [..., Q] → [..., Q, Q] with out[..., i, j] = sum_{j<t<=i} x_t (i>=j)."""
    Q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    return jnp.where(mask, seg, -jnp.inf)


def ssd_chunked(xh, dt, A, Bm, Cm, chunk: int):
    """Mamba-2 SSD forward, chunkwise (matmul-dominant, TPU-friendly).

    xh: [B,S,H,P]; dt: [B,S,H] (already softplus'd); A: [H] (negative);
    Bm, Cm: [B,S,N] (single group, broadcast over heads).
    Returns (y [B,S,H,P], final_state [B,H,P,N]).
    """
    Bsz, S, H, P = xh.shape
    N = Bm.shape[-1]
    nc = -(-S // chunk)
    pad = nc * chunk - S

    def padS(t):
        return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))

    xh, dt, Bm, Cm = padS(xh), padS(dt), padS(Bm), padS(Cm)
    xh = xh.reshape(Bsz, nc, chunk, H, P)
    dt = dt.reshape(Bsz, nc, chunk, H)
    Bm = Bm.reshape(Bsz, nc, chunk, N)
    Cm = Cm.reshape(Bsz, nc, chunk, N)

    dA = dt * A[None, None, None, :]                     # [B,nc,Q,H]
    dA_cs = jnp.cumsum(dA, axis=2)

    # intra-chunk (quadratic within chunk): Y_d = (C B^T ∘ L ∘ dt) X
    L = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))       # [B,nc,H,Q,Q]
    cb = jnp.einsum("bcqn,bckn->bcqk", Cm.astype(jnp.float32), Bm.astype(jnp.float32))
    M = cb[:, :, None] * L                                # [B,nc,H,Q,K]
    y_intra = jnp.einsum("bchqk,bckh,bckhp->bcqhp", M, dt.astype(jnp.float32),
                         xh.astype(jnp.float32))

    # per-chunk final states
    decay_to_end = jnp.exp(dA_cs[:, :, -1:, :] - dA_cs)   # [B,nc,Q,H]
    states = jnp.einsum("bcqn,bcqh,bcqhp->bchpn",
                        Bm.astype(jnp.float32), (dt * decay_to_end).astype(jnp.float32),
                        xh.astype(jnp.float32))           # [B,nc,H,P,N]

    # inter-chunk recurrence over nc
    chunk_decay = jnp.exp(dA_cs[:, :, -1, :])             # [B,nc,H]

    def scan_fn(h, inp):
        st, dec = inp
        h_new = dec[..., None, None] * h + st
        return h_new, h

    h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    hT, h_prevs = lax.scan(scan_fn, h0,
                           (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)            # [B,nc,H,P,N] state entering chunk

    # inter-chunk contribution
    in_decay = jnp.exp(dA_cs)                             # decay from chunk start to t
    y_inter = jnp.einsum("bcqn,bcqh,bchpn->bcqhp", Cm.astype(jnp.float32),
                         in_decay.astype(jnp.float32), h_prevs)

    y = (y_intra + y_inter).reshape(Bsz, nc * chunk, H, P)[:, :S]
    return y, hT


def mamba_forward(params, x, cfg: ModelConfig):
    """Full-sequence Mamba-2 block. x: [B,S,d] → [B,S,d]."""
    s: SSMConfig = cfg.ssm
    d_in = s.expand * cfg.d_model
    H = d_in // s.head_dim
    proj = x @ params["in_proj"]
    z, xBC, dt = jnp.split(proj, [d_in, 2 * d_in + 2 * s.state_dim], axis=-1)
    xBC = _causal_conv(xBC, params["conv_w"], params["conv_b"])
    xBC = jax.nn.silu(xBC)
    xs, Bm, Cm = jnp.split(xBC, [d_in, d_in + s.state_dim], axis=-1)
    B_, S_ = x.shape[:2]
    xh = xs.reshape(B_, S_, H, s.head_dim)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    y, _ = ssd_chunked(xh, dt, A, Bm, Cm, s.chunk_size)
    y = y + params["D"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(B_, S_, d_in).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), params["gate_norm"], cfg.norm_eps)  # gated norm
    return y @ params["out_proj"]


def mamba_decode(params, x, cache, cfg: ModelConfig, *, lora=None,
                 lora_scale: float = 1.0, lora_idx=None,
                 lora_kernel: bool = False):
    """One-token recurrent step.  cache: {"h": [B,H,P,N] f32,
    "conv": [B,W-1,C]}.  x: [B,1,d].

    Single-adapter callers fold LoRA into the projection weights upstream
    (cheap: r small) and pass ``lora=None``; the multi-tenant serving path
    instead passes banked ``in_proj`` / ``out_proj`` entries + ``lora_idx``
    so each row applies its own adapter via the grouped (BGMV) matmul."""
    s: SSMConfig = cfg.ssm
    d_in = s.expand * cfg.d_model
    H = d_in // s.head_dim
    B = x.shape[0]
    if lora_idx is not None:
        proj = grouped_lora_matmul(x, params["in_proj"],
                                   lora.get("in_proj") if lora else None,
                                   lora_idx, lora_scale,
                                   kernel=lora_kernel)[:, 0]
    else:
        proj = (x @ params["in_proj"])[:, 0]               # [B, proj_out]
    z, xBC, dt = jnp.split(proj, [d_in, 2 * d_in + 2 * s.state_dim], axis=-1)

    conv_buf = jnp.concatenate([cache["conv"], xBC[:, None, :]], axis=1)  # [B,W,C]
    xBC = jnp.einsum("bwc,wc->bc", conv_buf.astype(jnp.float32),
                     params["conv_w"].astype(jnp.float32)) + params["conv_b"].astype(jnp.float32)
    xBC = jax.nn.silu(xBC).astype(x.dtype)
    new_conv = conv_buf[:, 1:]

    xs, Bm, Cm = jnp.split(xBC, [d_in, d_in + s.state_dim], axis=-1)
    xh = xs.reshape(B, H, s.head_dim).astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])   # [B,H]
    A = -jnp.exp(params["A_log"])                                      # [H]
    dA = jnp.exp(dt * A[None, :])                                      # [B,H]
    dBx = jnp.einsum("bh,bn,bhp->bhpn", dt, Bm.astype(jnp.float32), xh)
    h = dA[..., None, None] * cache["h"] + dBx                         # [B,H,P,N]
    y = jnp.einsum("bn,bhpn->bhp", Cm.astype(jnp.float32), h)
    y = y + params["D"][None, :, None] * xh
    y = y.reshape(B, 1, d_in).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z[:, None, :]), params["gate_norm"], cfg.norm_eps)
    if lora_idx is not None:
        out = grouped_lora_matmul(y, params["out_proj"],
                                  lora.get("out_proj") if lora else None,
                                  lora_idx, lora_scale, kernel=lora_kernel)
    else:
        out = y @ params["out_proj"]
    return out, {"h": h, "conv": new_conv}
