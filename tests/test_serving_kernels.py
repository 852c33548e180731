"""The grouped (multi-adapter) LoRA matmul kernel: a hypothesis property
sweep against the pure-jnp oracle, its equivalence at the serving engine's
shapes and qwen2-0.5b's widths, and which row block each shape gets.  The
deterministic exactness tests (vs per-row dense compute, heterogeneous-rank
zero padding) live in ``test_serving.py`` so they run even without
hypothesis; this module is conftest-gated like the other property tests."""

import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro.kernels.lora_gather_matmul import MAX_ROWS
from repro.kernels.ops import grouped_lora_matmul
from repro.kernels.ref import grouped_lora_matmul_ref

pytestmark = pytest.mark.serving


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 6), st.integers(1, 5), st.sampled_from([4, 8, 16]),
       st.sampled_from([64, 128, 200]), st.integers(0, 2 ** 31 - 1))
def test_grouped_lora_matmul_property(M, G, r, N, seed):
    K = 64
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (M, K))
    w = jax.random.normal(ks[1], (K, N)) * 0.05
    a = jax.random.normal(ks[2], (G, r, K)) * 0.1
    b = jax.random.normal(ks[3], (G, N, r)) * 0.1
    idx = jnp.asarray(np.random.default_rng(seed).integers(0, G, M), jnp.int32)
    y = grouped_lora_matmul(x, w, a, b, idx, scale=0.5, bn=64, bk=64,
                            interpret=True)
    yr = grouped_lora_matmul_ref(x, w, a, b, idx, scale=0.5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-5,
                               rtol=2e-5)


def _bank(K, N, ranks, r_pad, key):
    """A bank of ``len(ranks)`` adapters of the given true ranks, zero-padded
    to ``r_pad`` (A rows / B columns past each rank are zero), in f32."""
    ka, kb = jax.random.split(key)
    a = jax.random.normal(ka, (len(ranks), r_pad, K)) * 0.05
    b = jax.random.normal(kb, (len(ranks), N, r_pad)) * 0.05
    mask = jnp.stack([(jnp.arange(r_pad) < rk).astype(jnp.float32)
                      for rk in ranks])
    return a * mask[:, :, None], b * mask[:, None, :]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("K,N", [(896, 896), (896, 128)], ids=["wq", "wv"])
@pytest.mark.parametrize("R", [1, 8, 128])
def test_grouped_slot_blocks_match_ref(R, K, N, dtype):
    """x [B, R, K] with a [B] index (R = 1 decode, R = chunk prefill) at
    qwen2-0.5b's widths: heterogeneous ranks zero-padded to the bank's 32,
    repeated and distinct indices, activations and base weight in
    ``dtype`` against the f32 bank."""
    B = 4
    ks = jax.random.split(jax.random.PRNGKey(R * 7 + N), 3)
    x = jax.random.normal(ks[0], (B, R, K), dtype)
    w = (jax.random.normal(ks[1], (K, N)) * 0.03).astype(dtype)
    a, b = _bank(K, N, [4, 8, 16, 32, 12], 32, ks[2])
    idx = jnp.asarray([3, 0, 3, 1], jnp.int32)
    y = grouped_lora_matmul(x, w, a, b, idx, scale=0.5, interpret=True)
    assert y.shape == (B, R, N) and y.dtype == dtype
    yr = grouped_lora_matmul_ref(x.reshape(B * R, K), w, a, b,
                                 jnp.repeat(idx, R), scale=0.5)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(y, np.float32).reshape(B * R, N),
                               np.asarray(yr, np.float32), atol=tol,
                               rtol=tol)


def test_grouped_rows_past_max_rows_tile():
    """A run longer than ``MAX_ROWS`` is tiled in row blocks and padded;
    every row still uses its slot's adapter."""
    B, R, K, N = 2, MAX_ROWS + 8, 128, 128
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(ks[0], (B, R, K))
    w = jax.random.normal(ks[1], (K, N)) * 0.05
    a, b = _bank(K, N, [8, 16], 16, ks[2])
    idx = jnp.asarray([1, 0], jnp.int32)
    y = grouped_lora_matmul(x, w, a, b, idx, scale=0.5, interpret=True)
    yr = grouped_lora_matmul_ref(x.reshape(B * R, K), w, a, b,
                                 jnp.repeat(idx, R), scale=0.5)
    np.testing.assert_allclose(np.asarray(y).reshape(B * R, N),
                               np.asarray(yr), atol=2e-5, rtol=2e-5)


def _pallas_call(fn, *args):
    """The one ``pallas_call`` equation in ``fn``'s jaxpr."""
    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e
            for p in e.params.values():
                inner = getattr(p, "jaxpr", p)
                if hasattr(inner, "eqns"):
                    yield from walk(inner)
    found = list(walk(jax.make_jaxpr(fn)(*args).jaxpr))
    assert len(found) == 1, found
    return found[0]


def _rows(block_mapping):
    """The block's row count (its second-minor dim)."""
    return block_mapping.block_shape[-2].block_size


@pytest.mark.parametrize("lead,idx_shape,grid_rows,block_rows", [
    ((32, 128), (32,), 32, 128),      # chunked prefill: one block per slot
    ((32, 1), (32,), 32, 1),          # decode: one row per slot
    ((32,), (32,), 32, 1),            # flat rows, one index each
    ((4, 8), (4, 8), 32, 1),          # a per-row index: single rows
], ids=["prefill", "decode", "flat", "per-row-index"])
def test_grouped_row_block_follows_shared_index(lead, idx_shape, grid_rows,
                                                block_rows):
    """Rows that share one index form one row block: the grid's leading
    dim is the number of indices, and each x / output block holds that
    index's rows (a slot's whole chunk in prefill, one row in decode).
    Fixed at trace time, so the traced call is what the chip runs."""
    K = N = 896
    x = jnp.zeros(lead + (K,), jnp.bfloat16)
    w = jnp.zeros((K, N), jnp.bfloat16)
    a, b = jnp.zeros((8, 32, K)), jnp.zeros((8, N, 32))
    idx = jnp.zeros(idx_shape, jnp.int32)
    eqn = _pallas_call(
        lambda *z: grouped_lora_matmul(*z, interpret=True), x, w, a, b, idx)
    gm = eqn.params["grid_mapping"]
    assert gm.grid == (grid_rows, 1, 1, 1)       # whole K and N at 896
    x_map, *_, out_map = gm.block_mappings
    assert _rows(x_map) == _rows(out_map) == block_rows
