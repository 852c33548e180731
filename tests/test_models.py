"""Per-architecture smoke tests (deliverable f): reduced variant of each
assigned family runs one forward + one LoRA train step on CPU; output shapes
asserted, no NaNs."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_reduced_config
from repro.core.lora import LoRAConfig, init_lora_params
from repro.models import transformer as T
from repro.optim import OptimizerConfig, adamw_init, adamw_update, make_optimizer

ASSIGNED = [a for a in ARCHS if not a.startswith("fedbench")]


def _batch(cfg, key, B=2, S=16):
    tokens = jax.random.randint(key, (B, S), 4, cfg.vocab_size)
    batch = dict(tokens=tokens, labels=tokens,
                 loss_mask=jnp.ones((B, S), jnp.float32))
    if cfg.family == "vlm":
        batch["image"] = jax.random.normal(
            key, (B, cfg.num_vision_tokens, cfg.vision_dim), jnp.float32)
        batch["image_mask"] = jnp.ones((B,), jnp.float32)
    if cfg.family == "encdec":
        batch["audio"] = jax.random.normal(key, (B, 8, cfg.audio_dim), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_forward_shapes_and_finiteness(arch):
    cfg = get_reduced_config(arch)
    assert cfg.d_model <= 512 and cfg.num_layers <= 2
    if cfg.moe:
        assert cfg.moe.num_experts <= 4
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    batch = _batch(cfg, key)
    logits, aux = T.forward(cfg, params, batch["tokens"],
                            vision=batch.get("image"), audio=batch.get("audio"))
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ASSIGNED)
def test_smoke_lora_train_step_reduces_loss_direction(arch):
    """One AdamW step on the LoRA adapters: finite grads, params move, and
    loss does not explode."""
    cfg = get_reduced_config(arch)
    key = jax.random.PRNGKey(1)
    params = T.init_params(key, cfg)
    specs = T.lora_specs(cfg)
    lora = init_lora_params(key, specs, LoRAConfig(rank=8))
    batch = _batch(cfg, key)

    def loss_of(lo):
        loss, _ = T.loss_fn(cfg, params, lo, batch, 0.5)
        return loss

    l0, grads = jax.value_and_grad(loss_of)(lora)
    gleaves = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in gleaves)
    assert any(float(jnp.abs(g).max()) > 0 for g in gleaves), "no gradient signal"
    ocfg = OptimizerConfig(peak_lr=1e-2, total_steps=10)
    _, upd = make_optimizer(ocfg)
    state = adamw_init(lora)
    lora1, _ = upd(lora, grads, state)
    l1 = loss_of(lora1)
    assert bool(jnp.isfinite(l1))
    moved = sum(float(jnp.abs(a - b).sum()) for a, b in
                zip(jax.tree_util.tree_leaves(lora),
                    jax.tree_util.tree_leaves(lora1)))
    assert moved > 0


@pytest.mark.parametrize("arch", ASSIGNED)
def test_full_config_matches_assignment(arch):
    """The full-scale config carries the exact assigned dimensions."""
    cfg = get_config(arch)
    expect = {
        "gemma3-12b": (48, 3840, 16, 8, 15360, 262144),
        "minicpm-2b": (40, 2304, 36, 36, 5760, 122753),
        "llama4-scout-17b-a16e": (48, 5120, 40, 8, 8192, 202048),
        "llama-3.2-vision-11b": (40, 4096, 32, 8, 14336, 128256),
        "mamba2-130m": (24, 768, None, None, 0, 50280),
        "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536),
        "seamless-m4t-medium": (12, 1024, 16, 16, 4096, 256206),
        "qwen2-72b": (80, 8192, 64, 8, 29568, 152064),
        "deepseek-v2-236b": (60, 5120, 128, None, None, 102400),
        "qwen2-0.5b": (24, 896, 14, 2, 4864, 151936),
    }[arch]
    L, d, h, kv, ff, v = expect
    assert cfg.num_layers == L and cfg.d_model == d and cfg.vocab_size == v
    if h is not None:
        assert cfg.num_heads == h
    if kv is not None:
        assert cfg.num_kv_heads == kv
    if ff is not None and ff > 0:
        if cfg.moe and cfg.name.startswith("llama4"):
            assert cfg.moe.d_ff_expert == ff
        else:
            assert cfg.d_ff == ff
    # family-specific structure
    if arch == "gemma3-12b":
        assert cfg.pattern.count("attn_local") == 5 and cfg.pattern.count("attn") == 1
    if arch == "jamba-v0.1-52b":
        assert cfg.pattern.count("mamba") == 7 and cfg.pattern.count("attn") == 1
        assert cfg.moe.num_experts == 16 and cfg.moe.experts_per_token == 2
    if arch == "deepseek-v2-236b":
        assert cfg.mla.kv_lora_rank == 512
        assert cfg.moe.num_experts == 160 and cfg.moe.experts_per_token == 6
        assert cfg.moe.num_shared_experts == 2 and cfg.moe.d_ff_expert == 1536
    if arch == "llama4-scout-17b-a16e":
        assert cfg.moe.num_experts == 16 and cfg.moe.experts_per_token == 1
    # µP scalings: MiniCPM's published scale_emb / scale_depth /
    # dim_model_base and RMSNorm ε; every other stack unscaled
    mup = {"minicpm-2b": (12.0, 1.4, 256)}.get(arch, (1.0, None, None))
    assert (cfg.scale_emb, cfg.scale_depth, cfg.dim_model_base) == mup
    if arch == "minicpm-2b":
        assert cfg.norm_eps == 1e-5
        assert cfg.residual_scale == pytest.approx(1.4 / 40 ** 0.5)
        assert cfg.logit_scale == pytest.approx(1 / 9)


def test_moe_aux_loss_and_capacity():
    cfg = get_reduced_config("llama4-scout-17b-a16e")
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    batch = _batch(cfg, key)
    _, aux = T.forward(cfg, params, batch["tokens"])
    assert float(aux) > 0.0  # load-balance loss active


def test_wsd_schedule_shape():
    from repro.optim import wsd_schedule
    lr = wsd_schedule(1.0, 100, warmup_steps=10)
    assert float(lr(0)) == 0.0
    assert abs(float(lr(50)) - 1.0) < 1e-6          # stable plateau
    assert float(lr(99)) < 0.2                       # decayed
