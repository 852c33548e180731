"""MiniCPM's µP decoder, the program against the plain reference
(``reference_minicpm``) on seeded random weights, at the reduced size
(``repro.configs.minicpm_2b.REDUCED``, float32) on the CPU: the logits, the
masked loss and its LoRA gradients; chunked prefill and then decode through
``decode_chunk``; and the serving engine's greedy tokens.

Tolerances: program and reference both compute in float32 here and differ
only in the order of their sums (fused projections, the scan's layout,
the engine's per-slot batching), which moves a float32 result by a few
units in its last place times the depth of the sums: relative 2e-5 of the
largest magnitude.  Each µP scaling moves the logits by far more than that
(``test_each_scaling_is_seen``), so none can go missing unnoticed.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import model
from chipbench import reference_minicpm as RM
from chipbench.reference import Prec
from repro.configs.minicpm_2b import REDUCED
from repro.models import transformer as T

SEED = 2 ** 31 + 77
TOL = 2e-5          # relative to the largest magnitude (see module doc)
RANK = 8
SCALE = 2.0         # LoRA alpha / r_g


def dims(cfg) -> dict:
    """``model.dims`` of a program config, with its µP scalars."""
    return {"layers": cfg.num_layers, "d": cfg.d_model,
            "heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.resolved_head_dim, "ff": cfg.d_ff,
            "vocab": cfg.vocab_size, "bias": cfg.qkv_bias,
            "eps": cfg.norm_eps, "theta": cfg.rope_theta, "dtype": cfg.dtype,
            "scale_emb": cfg.scale_emb, "scale_depth": cfg.scale_depth,
            "dim_model_base": cfg.dim_model_base}


@pytest.fixture(scope="module")
def setup():
    cfg = REDUCED
    dm = dims(cfg)
    params = model.init_weights(SEED, dm)
    lora = jax.tree_util.tree_map(
        lambda x: x[0], model.init_adapters(SEED, dm, [RANK], RANK, 0.05))
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(4, cfg.vocab_size, (2, 24)), jnp.int32)
    mask = np.zeros((2, 24), np.float32)
    mask[:, 10:] = 1.0
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1),
             "loss_mask": jnp.asarray(mask)}
    return cfg, dm, params, lora, batch


def close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    return float(np.max(np.abs(got - want))) / scale <= tol


def test_scalars_are_minicpms():
    assert (REDUCED.scale_emb, REDUCED.scale_depth,
            REDUCED.dim_model_base) == (12.0, 1.4, 256)
    assert REDUCED.residual_scale == pytest.approx(1.4 / np.sqrt(2))
    assert REDUCED.logit_scale == pytest.approx(256 / 288)


def test_logits_match(setup):
    cfg, dm, params, lora, batch = setup
    got, _ = T.forward(cfg, params, batch["tokens"], lora=lora,
                       lora_scale=SCALE)
    want = RM.logits(Prec(), dm, params, lora, batch["tokens"], SCALE)
    assert close(got, want)


@pytest.mark.parametrize("field,neutral", [("scale_emb", 1.0),
                                           ("scale_depth", None),
                                           ("dim_model_base", None)])
def test_each_scaling_is_seen(setup, field, neutral):
    """A program without one of the scalings misses the reference by far
    more than the tolerance."""
    cfg, dm, params, lora, batch = setup
    got, _ = T.forward(dataclasses.replace(cfg, **{field: neutral}), params,
                       batch["tokens"], lora=lora, lora_scale=SCALE)
    want = RM.logits(Prec(), dm, params, lora, batch["tokens"], SCALE)
    assert not close(got, want, 100 * TOL)


def test_masked_loss_and_lora_gradients_match(setup):
    cfg, dm, params, lora, batch = setup

    def prog(lo):
        return T.loss_fn(cfg, params, lo, batch, SCALE)[0]

    def ref(lo):
        return RM.loss(Prec(), dm, params, lo, batch, SCALE)

    (lp, gp), (lr, gr) = (jax.value_and_grad(prog)(lora),
                          jax.value_and_grad(ref)(lora))
    assert close(lp, lr)
    for name in lora:
        for m in ("A", "B"):
            assert close(gp[name][m], gr[name][m]), (name, m)


def test_prefill_then_decode_matches_full_forward(setup):
    """Chunked prefill of the prompt, then one position at a time through
    the cache (``decode_chunk``, a one-adapter bank), against the
    reference's full forward at every decoded position."""
    cfg, dm, params, lora, batch = setup
    toks = batch["tokens"]
    B, S = toks.shape
    P = 16
    bank = jax.tree_util.tree_map(lambda x: x[:, None], lora)   # [L, G=1]
    idx = jnp.zeros((B,), jnp.int32)
    cache = T.init_cache(cfg, params, B, S)
    _, cache = T.decode_chunk(cfg, params, cache,
                              T.embed_tokens(cfg, params, toks[:, :P]),
                              jnp.zeros((B,), jnp.int32), adapters=bank,
                              adapter_idx=idx, lora_scale=SCALE, logits=False)
    got = []
    for t in range(P, S):
        lg, cache = T.decode_chunk(
            cfg, params, cache, T.embed_tokens(cfg, params, toks[:, t:t + 1]),
            jnp.full((B,), t, jnp.int32), adapters=bank, adapter_idx=idx,
            lora_scale=SCALE)
        got.append(lg)
    want = RM.logits(Prec(), dm, params, lora, toks, SCALE)[:, P:]
    assert close(jnp.stack(got, 1), want)


def test_served_tokens_are_the_references_best(setup):
    """The serving engine (its own embedding lookup, chunked prefill and
    decode steps): each greedy token's reference logit is the largest, to
    the tolerance."""
    from repro.serving import AdapterStore, ServingEngine
    from repro.serving.engine import Request

    cfg, dm, params, lora, batch = setup
    store = AdapterStore(slots=2, rank=RANK)
    store.register(0, jax.device_get(lora), RANK, validate=False)
    eng = ServingEngine(cfg, params, store, lora_scale=SCALE, max_slots=2,
                        max_prompt=24, max_gen=8, prefill_chunk=8)
    prompt = np.asarray(batch["tokens"][0, :13])
    (rec,) = eng.run([Request(adapter_id=0, prompt_tokens=prompt,
                              gen_len=8)])
    seq = jnp.asarray(np.concatenate([prompt, rec["tokens"]])[None])
    lg = RM.logits(Prec(), dm, params, lora, seq, SCALE)[0, len(prompt) - 1:-1]
    served = jnp.take_along_axis(lg, jnp.asarray(rec["tokens"])[:, None],
                                 -1)[:, 0]
    gap = float(jnp.max(jnp.max(lg, -1) - served))
    assert len(rec["tokens"]) == 8
    assert gap <= TOL * float(jnp.max(jnp.abs(lg)))
