"""A configuration file as the program runs it, and its weights.

``load_config`` reads ``chipbench/configs/<name>.json`` (the published
config's keys, plus ``source``/``assumed``) and builds the program's
``ModelConfig`` from it.  ``init_weights`` makes the frozen base in the
program's parameter layout in ONE jitted call on the device, from the run's
seed, in the dtype the configuration serves in.  The plain reference
(``reference.py``) reads the same arrays: they are the benchmark's, not the
program's.
"""

from __future__ import annotations

import json
import math
import os

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))

# residual-stream scale of the tied embedding: logits = h · E with h at
# unit RMS after the final norm, so their spread is sqrt(d) · EMBED_STD —
# 3.0 at d = 896, 4.8 at d = 2304: a peaked next-token distribution, as a
# trained model has, rather than the near-uniform one of a 0.02 init
EMBED_STD = 0.1
NORM_JITTER = 0.1       # RMSNorm scales 1 + N(0, 0.1²)
BIAS_STD = 0.05


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def config_path(name: str, root: str = HERE) -> str:
    return os.path.join(root, "configs", f"{name}.json")


def load_config(name: str, root: str = HERE) -> dict:
    return read_json(config_path(name, root))


def model_config(raw: dict, name: str):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import ModelConfig

    heads = raw["num_attention_heads"]
    return ModelConfig(
        name=name, family="dense",
        num_layers=raw["num_hidden_layers"], d_model=raw["hidden_size"],
        num_heads=heads, num_kv_heads=raw["num_key_value_heads"],
        head_dim=raw.get("head_dim", raw["hidden_size"] // heads),
        d_ff=raw["intermediate_size"], vocab_size=raw["vocab_size"],
        qkv_bias=bool(raw.get("attention_bias", False)),
        tie_embeddings=bool(raw["tie_word_embeddings"]),
        rope_theta=float(raw["rope_theta"]), norm_eps=float(raw["rms_norm_eps"]),
        dtype=raw["torch_dtype"], source=raw["source"])


def dims(raw: dict) -> dict:
    """The sizes the FLOP/byte functions and the reference read."""
    heads = raw["num_attention_heads"]
    return {"layers": raw["num_hidden_layers"], "d": raw["hidden_size"],
            "heads": heads, "kv_heads": raw["num_key_value_heads"],
            "head_dim": raw.get("head_dim", raw["hidden_size"] // heads),
            "ff": raw["intermediate_size"], "vocab": raw["vocab_size"],
            "bias": bool(raw.get("attention_bias", False)),
            "eps": float(raw["rms_norm_eps"]),
            "theta": float(raw["rope_theta"]),
            "dtype": raw["torch_dtype"]}


def _weights(key, dm: dict):
    L, d, h, kv, hd = (dm["layers"], dm["d"], dm["heads"], dm["kv_heads"],
                       dm["head_dim"])
    ff, V, dt = dm["ff"], dm["vocab"], jnp.dtype(dm["dtype"])
    ks = iter(jax.random.split(key, 16))

    def mat(shape, fan_in):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dt)

    def norm(shape):
        return (1.0 + NORM_JITTER * jax.random.normal(next(ks), shape,
                                                      jnp.float32)).astype(dt)

    attn = {"wq": mat((L, d, h * hd), d), "wk": mat((L, d, kv * hd), d),
            "wv": mat((L, d, kv * hd), d), "wo": mat((L, h * hd, d), h * hd)}
    if dm["bias"]:
        for w, n in (("bq", h * hd), ("bk", kv * hd), ("bv", kv * hd)):
            attn[w] = (BIAS_STD * jax.random.normal(next(ks), (L, n))).astype(dt)
    block = {"ln1": norm((L, d)), "attn": attn, "ln2": norm((L, d)),
             "ffn": {"w1": mat((L, d, ff), d), "w3": mat((L, d, ff), d),
                     "w2": mat((L, ff, d), ff)}}
    embed = (EMBED_STD * jax.random.normal(next(ks), (V, d))).astype(dt)
    return {"embed": embed, "final_ln": norm((d,)), "blocks": {"s0": block}}


def init_weights(seed: int, dm: dict, sharding=None):
    """The frozen base, made on the device in one jitted call."""
    key = jax.random.key(seed)
    fn = jax.jit(lambda k: _weights(k, dm), out_shardings=sharding)
    return fn(key)


def weight_shapes(dm: dict):
    """``ShapeDtypeStruct``s of the base (for compiles without a chip)."""
    return jax.eval_shape(lambda k: _weights(k, dm), jax.random.key(0))


def lora_sites(dm: dict) -> dict:
    """LoRA sites of the program's dense block (query and value
    projections): name -> (in_dim, out_dim)."""
    d, hd = dm["d"], dm["head_dim"]
    return {"s0.attn.wq": (d, dm["heads"] * hd),
            "s0.attn.wv": (d, dm["kv_heads"] * hd)}


def _adapters(key, dm: dict, ranks, r_g: int, b_std: float):
    """Stacked adapters [n, L, r_g, in] / [n, L, out, r_g], f32, with rows
    of A and columns of B past each adapter's rank zero."""
    ranks = jnp.asarray(ranks, jnp.int32)
    n, L = ranks.shape[0], dm["layers"]
    mask = (jnp.arange(r_g)[None, :] < ranks[:, None]).astype(jnp.float32)
    out = {}
    for i, (name, (din, dout)) in enumerate(sorted(lora_sites(dm).items())):
        ka, kb = jax.random.split(jax.random.fold_in(key, i))
        a = jax.random.normal(ka, (n, L, r_g, din)) / math.sqrt(r_g)
        b = b_std * jax.random.normal(kb, (n, L, dout, r_g))
        out[name] = {"A": a * mask[:, None, :, None],
                     "B": b * mask[:, None, None, :]}
    return out


def init_adapters(seed: int, dm: dict, ranks, r_g: int, b_std: float):
    """``len(ranks)`` adapters in one jitted call (see ``_adapters``)."""
    key = jax.random.fold_in(jax.random.key(seed), 7)
    return jax.jit(lambda k: _adapters(k, dm, tuple(ranks), r_g, b_std))(key)
