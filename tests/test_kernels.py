"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret mode — kernel bodies execute in Python on CPU)."""

import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro.kernels import ops
from repro.kernels.ops import (dimension_wise_aggregate,
                               dimension_wise_trimmed, fused_lora_matmul)
from repro.kernels.ref import dim_agg_ref, dim_agg_trimmed_ref, lora_matmul_ref

SHAPES = [
    (64, 128, 128, 4), (128, 256, 192, 8), (256, 512, 384, 16),
    (300, 512, 640, 16),   # non-tiling M → padding path
    (128, 384, 256, 32),
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lora_matmul_allclose(shape, dtype):
    M, K, N, r = shape
    key = jax.random.PRNGKey(hash(shape) % 2 ** 31)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (M, K), dtype)
    w = jax.random.normal(ks[1], (K, N), dtype) * 0.05
    a = jax.random.normal(ks[2], (r, K), dtype) * 0.1
    b = jax.random.normal(ks[3], (N, r), dtype) * 0.1
    y = fused_lora_matmul(x, w, a, b, scale=0.7, bm=64, bn=64, bk=128,
                          interpret=True)
    yr = lora_matmul_ref(x, w, a, b, scale=0.7)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=tol, rtol=tol)


def test_lora_matmul_batched_input():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 7, 128))       # leading batch dims
    w = jax.random.normal(key, (128, 256)) * 0.05
    a = jax.random.normal(key, (8, 128)) * 0.1
    b = jax.random.normal(key, (256, 8)) * 0.1
    y = fused_lora_matmul(x, w, a, b, scale=1.0, bm=64, bn=64, bk=64,
                          interpret=True)
    assert y.shape == (2, 7, 256)
    yr = lora_matmul_ref(x.reshape(-1, 128), w, a, b).reshape(2, 7, 256)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4)


def test_lora_matmul_zero_padded_rank_equivalence():
    """Padded rank rows contribute nothing — kernel serves every client rank."""
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (64, 128))
    w = jax.random.normal(key, (128, 128)) * 0.05
    a = jax.random.normal(key, (16, 128)) * 0.1
    b = jax.random.normal(key, (128, 16)) * 0.1
    mask = (jnp.arange(16) < 5).astype(x.dtype)
    am, bm_ = a * mask[:, None], b * mask[None, :]
    y_pad = fused_lora_matmul(x, w, am, bm_, scale=1.0, bm=64, bn=64, bk=64,
                              interpret=True)
    yr = lora_matmul_ref(x, w, am[:5], bm_[:, :5])
    np.testing.assert_allclose(np.asarray(y_pad), np.asarray(yr), atol=1e-4)


@settings(max_examples=12, deadline=None)
@given(st.integers(2, 8), st.integers(1, 4), st.sampled_from([4, 8, 16]),
       st.sampled_from([96, 128, 300]), st.integers(0, 2 ** 31 - 1))
def test_dim_agg_allclose_property(K, L, r, n, seed):
    key = jax.random.PRNGKey(seed)
    s = jax.random.normal(key, (K, L, r, n))
    w = jax.random.uniform(jax.random.fold_in(key, 1), (K, r))
    out = dimension_wise_aggregate(s, w, bn=128, interpret=True)
    ref = dim_agg_ref(s, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dim_agg_dtypes(dtype):
    key = jax.random.PRNGKey(7)
    s = jax.random.normal(key, (4, 2, 8, 256), dtype)
    w = jax.random.uniform(jax.random.fold_in(key, 1), (4, 8), jnp.float32)
    out = dimension_wise_aggregate(s, w, interpret=True)
    ref = dim_agg_ref(s, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@pytest.mark.parametrize("K", [2, 5, 8])
@pytest.mark.parametrize("trim", [0.2, 0.4])
def test_dim_agg_trimmed_allclose(K, trim):
    """The pairwise-unrolled trimmed kernel against the [K, K, ...] oracle,
    with duplicated clients (index tie-break) and partial coverage."""
    from repro.core.aggregation import trimmed_dimension_counts

    rng = np.random.default_rng(K)
    x = np.round(rng.normal(size=(K, 2, 16, 200)), 1).astype(np.float32)
    x[1] = x[0]
    p = rng.uniform(0.1, 1.0, K).astype(np.float32)
    cover = (rng.uniform(size=(K, 16)) > 0.3).astype(np.float32)
    t = trimmed_dimension_counts(jnp.asarray(cover), trim)
    out = dimension_wise_trimmed(jnp.asarray(x), jnp.asarray(p / p.sum()),
                                 jnp.asarray(cover), t, interpret=True)
    ref = dim_agg_trimmed_ref(jnp.asarray(x), jnp.asarray(p / p.sum()),
                              jnp.asarray(cover), t)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_trimmed_kernel_aggregator_matches_reference():
    from repro.core import aggregation as AG
    from repro.core.lora import LoRAConfig, LoRASpec, init_lora_params

    specs = [LoRASpec("s0.attn.wq", 24, 32, 2)]
    key = jax.random.PRNGKey(3)
    loras = [init_lora_params(jax.random.fold_in(key, i), specs,
                              LoRAConfig(rank=16), client_rank=r)
             for i, r in enumerate((4, 8, 8, 16, 16))]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *loras)
    ranks = jnp.asarray([4, 8, 8, 16, 16])
    p = jnp.asarray([0.1, 0.2, 0.2, 0.25, 0.25])
    ref, _ = AG.aggregate("fedilora_trimmed", stacked, ranks, p, trim=0.3)
    ker, _ = AG.aggregate("fedilora_trimmed_kernel", stacked, ranks, p,
                          trim=0.3)
    for n in ref:
        for m in ("A", "B"):
            np.testing.assert_allclose(np.asarray(ker[n][m]),
                                       np.asarray(ref[n][m]), atol=1e-6)


@pytest.mark.parametrize("backend,requested,expect", [
    ("tpu", None, False), ("tpu", False, False), ("tpu", True, ValueError),
    ("cpu", None, True), ("cpu", False, False), ("cpu", True, True),
    ("gpu", None, RuntimeError)])
def test_interpret_mode_follows_backend(monkeypatch, backend, requested,
                                        expect):
    """A TPU backend never interprets; interpret is the CPU backend's
    default; any other backend has no kernel path."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if isinstance(expect, bool):
        assert ops._interpret(requested) is expect
    else:
        with pytest.raises(expect):
            ops._interpret(requested)
