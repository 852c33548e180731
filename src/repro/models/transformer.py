"""Unified transformer assembly for all architecture families.

A model is a stack of ``num_blocks`` identical *blocks*, each containing the
``cfg.pattern`` sublayers (period P).  Parameters of sub-position ``i`` are
stacked over blocks (leading dim ``num_blocks``) and the forward pass is a
``lax.scan`` over blocks with a static inner loop over the P sublayers —
compile time scales with P, not depth (DESIGN.md §2).

LoRA adapters are a flat tree ``{spec_name: {"A": [num_blocks, r, in],
"B": [num_blocks, out, r]}}`` with spec names ``s{i}.{sub}.{weight}`` — one
editable module per (transformer layer × adapted weight), matching the
paper's per-LoRA-layer editing granularity.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.lora import LoRASpec
from repro.models import layers as L
from repro.models.config import ModelConfig

Pytree = Any


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def _init_sublayer(key, cfg: ModelConfig, kind: str, layer_in_pattern: int, n: int):
    d = cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    p: dict = {"ln1": jnp.ones((n, d), dt)}
    if kind in ("attn", "attn_local"):
        if cfg.mla is not None:
            p["mla"] = L.init_mla(k1, cfg, n=n)
        else:
            p["attn"] = L.init_attention(k1, cfg, n=n)
    elif kind == "cross_attn":
        p["cross"] = L.init_attention(k1, cfg, cross=True, n=n)
    elif kind == "mamba":
        p["mamba"] = L.init_mamba(k1, cfg, n=n)
    else:
        raise ValueError(kind)
    if cfg.is_moe_layer(layer_in_pattern):
        p["ln2"] = jnp.ones((n, d), dt)
        p["moe"] = L.init_moe(k2, cfg, n=n)
    elif cfg.d_ff > 0 :
        p["ln2"] = jnp.ones((n, d), dt)
        p["ffn"] = L.init_mlp(k2, d, cfg.d_ff, cfg.dtype, n=n)
    return p


def init_params(key, cfg: ModelConfig) -> Pytree:
    d = cfg.d_model
    dt = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, cfg.period + 4)
    params: dict = {
        "embed": jax.random.normal(keys[-1], (cfg.vocab_size, d), dt) * 0.02,
        "final_ln": jnp.ones((d,), dt),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = jax.random.normal(keys[-2], (d, cfg.vocab_size), dt) / math.sqrt(d)
    params["blocks"] = {
        f"s{i}": _init_sublayer(keys[i], cfg, cfg.pattern[i], i, cfg.num_blocks)
        for i in range(cfg.period)
    }
    if cfg.family == "vlm" and cfg.vision_mode == "prefix":
        params["vision_proj"] = jax.random.normal(
            keys[-3], (cfg.vision_dim, d), dt) / math.sqrt(cfg.vision_dim)
    if cfg.family == "encdec":
        ke = jax.random.split(keys[-4], 3)
        params["encoder"] = {
            "in_proj": jax.random.normal(ke[0], (cfg.audio_dim, d), dt) / math.sqrt(cfg.audio_dim),
            "final_ln": jnp.ones((d,), dt),
            "blocks": {"s0": _init_sublayer(ke[1], cfg, "attn", 0, cfg.encoder_layers)},
        }
        # decoder cross-attention over encoder output (kv_in = d_model)
        for i in range(cfg.period):
            kc = jax.random.fold_in(ke[2], i)
            params["blocks"][f"s{i}"]["lnx"] = jnp.ones((cfg.num_blocks, d), dt)
            ca = L.init_attention(kc, cfg, cross=True, n=cfg.num_blocks, kv_in=d)
            ca.pop("gate", None)
            params["blocks"][f"s{i}"]["dec_cross"] = ca
    return params


# ---------------------------------------------------------------------------
# LoRA specs — which weights the paper's technique adapts, per family
# ---------------------------------------------------------------------------

def lora_specs(cfg: ModelConfig) -> list[LoRASpec]:
    """Paper: LoRA on attention query & value projections.  Family
    adaptations (DESIGN.md §4): MLA → q (or up-q) and kv up-projection;
    Mamba → in/out projections; cross-attn → its q & v; enc-dec → decoder
    self & cross q/v."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    n = cfg.num_blocks
    specs: list[LoRASpec] = []
    for i, kind in enumerate(cfg.pattern):
        pre = f"s{i}"
        if kind in ("attn", "attn_local"):
            if cfg.mla is not None:
                m = cfg.mla
                qd = m.qk_nope_head_dim + m.qk_rope_head_dim
                if m.q_lora_rank:
                    specs.append(LoRASpec(f"{pre}.mla.wuq", m.q_lora_rank, h * qd, n))
                else:
                    specs.append(LoRASpec(f"{pre}.mla.wq", d, h * qd, n))
                specs.append(LoRASpec(f"{pre}.mla.wkv_b", m.kv_lora_rank,
                                      h * (m.qk_nope_head_dim + m.v_head_dim), n))
            else:
                specs.append(LoRASpec(f"{pre}.attn.wq", d, h * hd, n))
                specs.append(LoRASpec(f"{pre}.attn.wv", d, kv * hd, n))
        elif kind == "cross_attn":
            specs.append(LoRASpec(f"{pre}.cross.wq", d, h * hd, n))
            specs.append(LoRASpec(f"{pre}.cross.wv", cfg.vision_dim, kv * hd, n))
        elif kind == "mamba":
            s = cfg.ssm
            d_in = s.expand * d
            proj_out = 2 * d_in + 2 * s.state_dim + d_in // s.head_dim
            specs.append(LoRASpec(f"{pre}.mamba.in_proj", d, proj_out, n))
            specs.append(LoRASpec(f"{pre}.mamba.out_proj", d_in, d, n))
        if cfg.family == "encdec":
            specs.append(LoRASpec(f"{pre}.dec_cross.wq", d, h * hd, n))
            specs.append(LoRASpec(f"{pre}.dec_cross.wv", d, kv * hd, n))
    if cfg.family == "encdec":
        specs.append(LoRASpec("enc.attn.wq", d, h * hd, cfg.encoder_layers))
        specs.append(LoRASpec("enc.attn.wv", d, kv * hd, cfg.encoder_layers))
    return specs


def _sub_lora(lora: Pytree | None, prefix: str) -> dict:
    """Extract {weight_name: {"A","B"}} for one sublayer from the flat tree."""
    if not lora:
        return {}
    out = {}
    plen = len(prefix) + 1
    for name, entry in lora.items():
        if name.startswith(prefix + "."):
            out[name[plen:]] = entry
    return out


def _split_key(name: str) -> tuple[str, str]:
    sub, weight = name.split(".", 1)
    return sub, weight


# ---------------------------------------------------------------------------
# forward (training / prefill)
# ---------------------------------------------------------------------------

def _apply_sublayer(cfg: ModelConfig, kind: str, bp, x, *, lora_tree, sub_idx,
                    lora_scale, positions, pad_mask, vision, enc_out, enc_mask,
                    moe_spec=None):
    """One pattern sublayer (+ its FFN) on [B,S,d]."""
    pre = f"s{sub_idx}"
    aux = jnp.zeros((), jnp.float32)
    h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
    if kind in ("attn", "attn_local"):
        if cfg.mla is not None:
            lo = _sub_lora(lora_tree, f"{pre}.mla")
            y = L.mla_forward(bp["mla"], h, cfg, lora=lo, lora_scale=lora_scale,
                              positions=positions, pad_mask=pad_mask)
        else:
            lo = _sub_lora(lora_tree, f"{pre}.attn")
            y = L.attention_forward(bp["attn"], h, cfg, kind=kind, lora=lo,
                                    lora_scale=lora_scale, positions=positions,
                                    pad_mask=pad_mask)
    elif kind == "cross_attn":
        lo = _sub_lora(lora_tree, f"{pre}.cross")
        y = L.attention_forward(bp["cross"], h, cfg, kind="cross_attn", lora=lo,
                                lora_scale=lora_scale, kv_src=vision)
    elif kind == "mamba":
        lo = _sub_lora(lora_tree, f"{pre}.mamba")
        mp = dict(bp["mamba"])
        # LoRA on mamba projections folds into the weights (cheap: r small)
        for w in ("in_proj", "out_proj"):
            if w in lo:
                mp[w] = mp[w] + lora_scale * jnp.einsum(
                    "or,ri->io", lo[w]["B"], lo[w]["A"]).astype(mp[w].dtype)
        y = L.mamba_forward(mp, h, cfg)
    else:
        raise ValueError(kind)
    x = x + y

    if cfg.family == "encdec" and "dec_cross" in bp:
        hx = L.rms_norm(x, bp["lnx"], cfg.norm_eps)
        lo = _sub_lora(lora_tree, f"{pre}.dec_cross")
        y = L.attention_forward(bp["dec_cross"], hx, cfg, kind="cross_attn",
                                lora=lo, lora_scale=lora_scale, kv_src=enc_out,
                                pad_mask=enc_mask)
        x = x + y

    if "moe" in bp:
        h2 = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
        y, aux = L.moe_forward(bp["moe"], h2, cfg, expert_spec=moe_spec)
        x = x + y
    elif "ffn" in bp:
        h2 = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
        x = x + L.mlp_forward(bp["ffn"], h2)
    return x, aux


def _run_blocks(cfg: ModelConfig, blocks, lora, x, *, lora_scale, positions,
                pad_mask, vision=None, enc_out=None, enc_mask=None,
                remat: bool = False, act_spec=None, moe_spec=None):
    """scan over num_blocks; returns (x, total_aux).

    ``act_spec``: optional PartitionSpec pinned onto the residual stream at
    every block boundary — the sequence-parallel hillclimb lever
    (EXPERIMENTS.md §Perf): sharding S over the "model" axis turns the
    Megatron activation all-reduces into 1/tp-sized reduce-scatters plus one
    all-gather at the attention boundary.
    """
    lora = lora or {}

    def body(carry, xs):
        h = carry
        if act_spec is not None:
            h = jax.lax.with_sharding_constraint(h, act_spec)
        bp, lt = xs
        aux_tot = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(cfg.pattern):
            h, aux = _apply_sublayer(cfg, kind, bp[f"s{i}"], h, lora_tree=lt,
                                     sub_idx=i, lora_scale=lora_scale,
                                     positions=positions, pad_mask=pad_mask,
                                     vision=vision, enc_out=enc_out,
                                     enc_mask=enc_mask, moe_spec=moe_spec)
            aux_tot = aux_tot + aux
        return h, aux_tot

    # only block-stacked lora entries ride the scan (enc.* handled elsewhere)
    lora_scan = {k: v for k, v in lora.items() if k.startswith("s")}
    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, auxs = lax.scan(body, x, (blocks, lora_scan))
    return x, jnp.sum(auxs)


def encode(cfg: ModelConfig, params, audio, lora=None, lora_scale: float = 1.0,
           audio_mask=None):
    """Enc-dec encoder: bidirectional self-attention over frame embeddings."""
    enc = params["encoder"]
    x = audio.astype(jnp.dtype(cfg.dtype)) @ enc["in_proj"]
    lora = lora or {}
    lo = {k[len("enc."):]: v for k, v in lora.items() if k.startswith("enc.")}

    def body(h, xs):
        bp, lt = xs
        hn = L.rms_norm(h, bp["ln1"], cfg.norm_eps)
        q, k, v = L._qkv(bp["attn"], hn, hn, cfg, lt, lora_scale)
        S = hn.shape[1]
        pos = jnp.arange(S)
        q = L.apply_rope(q, pos, cfg.rope_theta)
        k = L.apply_rope(k, pos, cfg.rope_theta)
        o = L.multihead_attention(q, k, v, causal=False, pad_mask=audio_mask)
        h = h + o.reshape(h.shape[0], S, -1) @ bp["attn"]["wo"]
        h2 = L.rms_norm(h, bp["ln2"], cfg.norm_eps)
        h = h + L.mlp_forward(bp["ffn"], h2)
        return h, None

    lo_scan = {k: v for k, v in
               {"wq": lo.get("attn.wq"), "wv": lo.get("attn.wv")}.items()
               if v is not None}
    x, _ = lax.scan(body, x, (enc["blocks"]["s0"], lo_scan))
    return L.rms_norm(x, enc["final_ln"], cfg.norm_eps)


def _final_hidden(cfg: ModelConfig, params, tokens, *, lora=None,
                  lora_scale: float = 1.0, vision=None, audio=None,
                  pad_mask=None, audio_mask=None, remat: bool = False,
                  last_only: bool = False, act_spec=None, moe_spec=None):
    """:func:`forward` up to the unembedding: (final-norm hidden states
    [B,S,d] or [B,1,d], aux_loss)."""
    x = params["embed"][tokens]
    B, S = tokens.shape
    positions = jnp.arange(S)

    n_prefix = 0
    if cfg.family == "vlm" and cfg.vision_mode == "prefix" and vision is not None:
        pre = vision.astype(x.dtype) @ params["vision_proj"]     # [B,P,d]
        x = jnp.concatenate([pre, x], axis=1)
        n_prefix = pre.shape[1]
        positions = jnp.arange(S + n_prefix)
        if pad_mask is not None:
            pad_mask = jnp.concatenate(
                [jnp.ones((B, n_prefix), pad_mask.dtype), pad_mask], axis=1)

    enc_out = enc_mask = None
    if cfg.family == "encdec":
        enc_out = encode(cfg, params, audio, lora, lora_scale, audio_mask)
        enc_mask = audio_mask

    x, aux = _run_blocks(cfg, params["blocks"], lora, x, lora_scale=lora_scale,
                         positions=positions, pad_mask=pad_mask,
                         vision=vision if cfg.vision_mode == "cross" else None,
                         enc_out=enc_out, enc_mask=enc_mask, remat=remat,
                         act_spec=act_spec, moe_spec=moe_spec)
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    if n_prefix:
        x = x[:, n_prefix:]
    if last_only:
        x = x[:, -1:]
    return x, aux


def _unembed(cfg: ModelConfig, params, x):
    if cfg.tie_embeddings:
        return x @ params["embed"].T
    return x @ params["unembed"]


def forward(cfg: ModelConfig, params, tokens, **kw):
    """Training / prefill forward.  Returns (logits, aux_loss); logits are
    [B,S,V], or [B,1,V] when ``last_only`` (prefill — avoids the full-seq
    unembed matmul).  Keywords as :func:`_final_hidden`."""
    x, aux = _final_hidden(cfg, params, tokens, **kw)
    return _unembed(cfg, params, x), aux


def loss_fn(cfg: ModelConfig, params, lora, batch, lora_scale: float = 1.0,
            remat: bool = False, act_spec=None, moe_spec=None):
    """Masked next-token cross-entropy (+ MoE aux).  batch keys: tokens,
    labels, loss_mask, optional image/audio + modality masks.  The
    unembedding, the f32 log-softmax and the NLL run under the
    ``unembed_loss`` named scope (HLO metadata, forward and backward)."""
    vision = batch.get("image")
    if vision is not None and "image_mask" in batch:
        vision = (vision * batch["image_mask"][:, None, None]).astype(vision.dtype)
    x, aux = _final_hidden(cfg, params, batch["tokens"], lora=lora,
                           lora_scale=lora_scale, vision=vision,
                           audio=batch.get("audio"), remat=remat,
                           act_spec=act_spec, moe_spec=moe_spec)
    with jax.named_scope("unembed_loss"):
        logits = _unembed(cfg, params, x).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)[..., 0]
        mask = batch["loss_mask"].astype(jnp.float32)
        loss = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        acc = jnp.sum((jnp.argmax(logits, -1) == batch["labels"]) * mask) / jnp.maximum(
            jnp.sum(mask), 1.0)
    return loss + aux, {"loss": loss, "aux": aux, "acc": acc}


# ---------------------------------------------------------------------------
# decode (serve_step)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, params, batch: int, max_len: int, *,
               vision=None, audio=None) -> Pytree:
    """Allocate the per-sublayer decode state, stacked over blocks."""
    dt = jnp.dtype(cfg.dtype)
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    n = cfg.num_blocks
    cache: dict = {}
    for i, kind in enumerate(cfg.pattern):
        key = f"s{i}"
        if kind in ("attn", "attn_local"):
            if cfg.mla is not None:
                m = cfg.mla
                cache[key] = {
                    "c_kv": jnp.zeros((n, batch, max_len, m.kv_lora_rank), dt),
                    "k_rope": jnp.zeros((n, batch, max_len, m.qk_rope_head_dim), dt),
                }
            else:
                S = max_len
                if kind == "attn_local" and cfg.sliding_window:
                    S = min(max_len, cfg.sliding_window)   # rolling window
                cache[key] = {"k": jnp.zeros((n, batch, S, kv, hd), dt),
                              "v": jnp.zeros((n, batch, S, kv, hd), dt)}
        elif kind == "cross_attn":
            # precompute vision K/V once (static across decode steps)
            def _kv(bp):
                k = vision.astype(dt) @ bp["wk"]
                v = vision.astype(dt) @ bp["wv"]
                P = vision.shape[1]
                return (k.reshape(batch, P, kv, hd), v.reshape(batch, P, kv, hd))
            ks, vs = jax.vmap(_kv)(params["blocks"][key]["cross"])
            cache[key] = {"k": ks, "v": vs}
        elif kind == "mamba":
            s = cfg.ssm
            d_in = s.expand * cfg.d_model
            H = d_in // s.head_dim
            conv_ch = d_in + 2 * s.state_dim
            cache[key] = {
                "h": jnp.zeros((n, batch, H, s.head_dim, s.state_dim), jnp.float32),
                "conv": jnp.zeros((n, batch, s.conv_width - 1, conv_ch), dt),
            }
    if cfg.family == "encdec":
        enc_out = encode(cfg, params, audio)
        for i in range(cfg.period):
            def _kv(bp):
                k = enc_out @ bp["wk"]
                v = enc_out @ bp["wv"]
                P = enc_out.shape[1]
                return (k.reshape(batch, P, kv, hd), v.reshape(batch, P, kv, hd))
            ks, vs = jax.vmap(_kv)(params["blocks"][f"s{i}"]["dec_cross"])
            cache[f"s{i}_dec_cross"] = {"k": ks, "v": vs}
    return cache


def decode_step(cfg: ModelConfig, params, cache, tokens, pos, *, lora=None,
                lora_scale: float = 1.0, moe_spec=None, seq_axis=None,
                embeds=None):
    """One-token decode.  tokens: i32[B]; pos: scalar i32 (current position).
    Returns (logits [B, V], new_cache).

    ``embeds``: optional [B, 1, d] input vector that replaces the token
    embedding — used to stream non-token positions (e.g. the VLM vision
    prefix) through the KV cache during cached prefill."""
    lora = lora or {}
    x = embeds if embeds is not None else params["embed"][tokens][:, None, :]
    lora_scan = {k: v for k, v in lora.items() if k.startswith("s")}

    def body(carry, xs):
        h = carry
        bp, lt, ci = xs
        new_ci = {}
        for i, kind in enumerate(cfg.pattern):
            pre = f"s{i}"
            hn = L.rms_norm(h, bp[pre]["ln1"], cfg.norm_eps)
            if kind in ("attn", "attn_local"):
                if cfg.mla is not None:
                    lo = _sub_lora(lt, f"{pre}.mla")
                    y, new_ci[pre] = L.mla_decode(bp[pre]["mla"], hn, ci[pre], cfg,
                                                  pos=pos, lora=lo,
                                                  lora_scale=lora_scale,
                                                  seq_axis=seq_axis)
                else:
                    lo = _sub_lora(lt, f"{pre}.attn")
                    y, new_ci[pre] = L.attention_decode(bp[pre]["attn"], hn, ci[pre],
                                                        cfg, kind=kind, pos=pos,
                                                        lora=lo, lora_scale=lora_scale)
            elif kind == "cross_attn":
                lo = _sub_lora(lt, f"{pre}.cross")
                y, new_ci[pre] = L.attention_decode(bp[pre]["cross"], hn, ci[pre],
                                                    cfg, kind="cross_attn", pos=pos,
                                                    lora=lo, lora_scale=lora_scale)
            elif kind == "mamba":
                lo = _sub_lora(lt, f"{pre}.mamba")
                mp = dict(bp[pre]["mamba"])
                for w in ("in_proj", "out_proj"):
                    if w in lo:
                        mp[w] = mp[w] + lora_scale * jnp.einsum(
                            "or,ri->io", lo[w]["B"], lo[w]["A"]).astype(mp[w].dtype)
                y, new_ci[pre] = L.mamba_decode(mp, hn, ci[pre], cfg)
            h = h + y
            if cfg.family == "encdec":
                hx = L.rms_norm(h, bp[pre]["lnx"], cfg.norm_eps)
                lo = _sub_lora(lt, f"{pre}.dec_cross")
                y, _ = L.attention_decode(bp[pre]["dec_cross"], hx,
                                          ci[f"{pre}_dec_cross"], cfg,
                                          kind="cross_attn", pos=pos,
                                          lora=lo, lora_scale=lora_scale)
                new_ci[f"{pre}_dec_cross"] = ci[f"{pre}_dec_cross"]
                h = h + y
            if "moe" in bp[pre]:
                h2 = L.rms_norm(h, bp[pre]["ln2"], cfg.norm_eps)
                y, _ = L.moe_forward(bp[pre]["moe"], h2, cfg,
                                     expert_spec=moe_spec)
                h = h + y
            elif "ffn" in bp[pre]:
                h2 = L.rms_norm(h, bp[pre]["ln2"], cfg.norm_eps)
                h = h + L.mlp_forward(bp[pre]["ffn"], h2)
        return h, new_ci

    x, new_cache = lax.scan(body, x, (params["blocks"], lora_scan, cache))
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    return _unembed(cfg, params, x[:, 0]).astype(jnp.float32), new_cache


def decode_chunk(cfg: ModelConfig, params, cache, embeds, pos, *,
                 adapters=None, adapter_idx=None, lora_scale: float = 1.0,
                 valid=None, lora_kernel: bool = False, logits: bool = True,
                 chunked: bool | None = False, moe_spec=None):
    """Batched multi-adapter decode over ``C`` positions per row — the
    serving hot path (``C = 1``: one-token decode; ``C = chunk``: chunked
    prefill), replacing the per-row vmap-of-``decode_step`` formulation.

    ``embeds``: [B, C, d] input vectors (the engine muxes token embeddings
    / vision-prefix vectors upstream); ``pos``: [B] per-row first position
    (ragged continuous-batching slots); ``valid``: optional [B, C] mask for
    ragged chunk tails (masked positions leave their cache rows untouched
    and produce discarded outputs).  ``adapters``: stacked LoRA bank with
    leaves [L, G, ...] — the bank (G) axis sits AFTER the block-scan (L)
    axis so the scan strips L exactly like the single-adapter tree (see
    ``make_multi_adapter_serve_step``); ``adapter_idx``: i32 [B] per-row
    bank index (BGMV).  LoRA deltas are computed per row from the gathered
    tiny (A, B) pairs, or — ``lora_kernel=True`` — by the Pallas
    scalar-prefetch gather kernel; a full per-row adapter-tree copy is
    never materialised.  ``logits=False`` skips the final norm + unembed
    entirely (prefill positions' logits are discarded anyway); it is also
    required when ``C > 1``.

    Caches are the ``init_cache`` layout (batch axis 1).  Supported
    sublayers: attn / attn_local (incl. ring) / MLA / mamba (``C = 1``
    only — a recurrent state cannot skip masked chunk tails); cross-attn
    and enc-dec are rejected, matching the ServingEngine's gate.

    Returns (logits [B, V] | None, new_cache).
    """
    lora_scan = adapters if adapters is not None else {}
    C = embeds.shape[1]
    if logits and C != 1:
        raise ValueError("logits=True needs C == 1 (prefill discards them)")
    if cfg.family == "encdec":
        raise NotImplementedError("enc-dec stacks are engine-gated")

    def body(carry, xs):
        h = carry
        bp, lt, ci = xs
        new_ci = {}
        for i, kind in enumerate(cfg.pattern):
            pre = f"s{i}"
            hn = L.rms_norm(h, bp[pre]["ln1"], cfg.norm_eps)
            if kind in ("attn", "attn_local"):
                if cfg.mla is not None:
                    lo = _sub_lora(lt, f"{pre}.mla")
                    y, new_ci[pre] = L.mla_decode_batch(
                        bp[pre]["mla"], hn, ci[pre], cfg, pos=pos,
                        valid=valid, lora=lo, lora_scale=lora_scale,
                        lora_idx=adapter_idx, lora_kernel=lora_kernel)
                else:
                    lo = _sub_lora(lt, f"{pre}.attn")
                    y, new_ci[pre] = L.attention_decode_batch(
                        bp[pre]["attn"], hn, ci[pre], cfg, kind=kind,
                        pos=pos, valid=valid, lora=lo, lora_scale=lora_scale,
                        lora_idx=adapter_idx, lora_kernel=lora_kernel,
                        chunked=chunked)
            elif kind == "mamba":
                if C != 1:
                    raise NotImplementedError(
                        "chunked prefill over a recurrent mamba state is "
                        "not supported (engine gates it)")
                lo = _sub_lora(lt, f"{pre}.mamba")
                y, new_ci[pre] = L.mamba_decode(
                    bp[pre]["mamba"], hn, ci[pre], cfg, lora=lo,
                    lora_scale=lora_scale, lora_idx=adapter_idx,
                    lora_kernel=lora_kernel)
            else:
                raise NotImplementedError(
                    f"batched decode does not support {kind!r}")
            h = h + y
            if "moe" in bp[pre]:
                h2 = L.rms_norm(h, bp[pre]["ln2"], cfg.norm_eps)
                y, _ = L.moe_forward(bp[pre]["moe"], h2, cfg,
                                     expert_spec=moe_spec)
                h = h + y
            elif "ffn" in bp[pre]:
                h2 = L.rms_norm(h, bp[pre]["ln2"], cfg.norm_eps)
                h = h + L.mlp_forward(bp[pre]["ffn"], h2)
        return h, new_ci

    x, new_cache = lax.scan(body, embeds, (params["blocks"], lora_scan, cache))
    if not logits:
        return None, new_cache
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    return _unembed(cfg, params, x[:, 0]).astype(jnp.float32), new_cache
