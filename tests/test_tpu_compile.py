"""The main path's Pallas kernels compile for a TPU v5e at qwen2-0.5b's
widths (d_model 896, 14 query / 2 KV heads of 64, ranks up to 32).

Interpret mode accepts block shapes and in-kernel ops that Mosaic refuses;
these tests run the TPU compiler against a *described* v5e:2x2 topology
(nothing executes) and assert the kernel reached the HLO as a Mosaic
``tpu_custom_call``.  The topology is described inside a module-scoped
fixture, never at import: only one process may hold the TPU library.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

D_MODEL, HEADS, KV_HEADS, HEAD_DIM = 896, 14, 2, 64   # qwen2-0.5b
LAYERS, RANK, COHORT, BANK = 24, 32, 4, 10


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one:
    # keep these programs out of the persistent cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _hlo(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n", [D_MODEL, KV_HEADS * HEAD_DIM])
@pytest.mark.parametrize("scaled", [False, True])
def test_dim_agg_compiles(one_chip, n, scaled):
    shapes = [((COHORT, LAYERS, RANK, n), jnp.float32),
              ((COHORT, RANK), jnp.float32)]
    if scaled:
        shapes.append(((COHORT,), jnp.float32))
    fn = lambda s, w, *c: ops.dimension_wise_aggregate(
        s, w, *c, interpret=False)
    assert "tpu_custom_call" in _hlo(fn, one_chip, *shapes)


@pytest.mark.parametrize("cohort", [COHORT, BANK])
def test_dim_agg_trimmed_compiles(one_chip, cohort):
    fn = lambda s, p, c, t: ops.dimension_wise_trimmed(
        s, p, c, t, interpret=False)
    assert "tpu_custom_call" in _hlo(
        fn, one_chip, ((cohort, LAYERS, RANK, D_MODEL), jnp.float32),
        ((cohort,), jnp.float32), ((cohort, RANK), jnp.float32),
        ((RANK,), jnp.float32))


@pytest.mark.parametrize("n", [HEADS * HEAD_DIM, KV_HEADS * HEAD_DIM])
@pytest.mark.parametrize("rows", [(8,), (8, 8), (32, 1), (32, 128)],
                         ids=["decode", "prefill", "engine-decode",
                              "engine-prefill"])
def test_grouped_lora_matmul_compiles(one_chip, rows, n):
    """BGMV at the serving engine's shapes: decode [slots] or [slots, 1]
    (one row per block), chunked prefill [slots, chunk] (one chunk-row
    block per slot; 32 slots × chunk 128 is the benchmark's engine);
    bf16 activations and base weights, the adapter bank in its own f32."""
    fn = lambda x, w, a, b, i: ops.grouped_lora_matmul(
        x, w, a, b, i, scale=0.5, interpret=False)
    assert "tpu_custom_call" in _hlo(
        fn, one_chip, (rows + (D_MODEL,), jnp.bfloat16),
        ((D_MODEL, n), jnp.bfloat16), ((BANK, RANK, D_MODEL), jnp.float32),
        ((BANK, n, RANK), jnp.float32), (rows[:1], jnp.int32))


@pytest.mark.parametrize("seq", [256, 1024])
def test_flash_attention_compiles(one_chip, seq):
    fn = lambda q, k, v: ops.flash_attention(q, k, v, interpret=False)
    assert "tpu_custom_call" in _hlo(
        fn, one_chip, ((1, seq, HEADS, HEAD_DIM), jnp.bfloat16),
        ((1, seq, KV_HEADS, HEAD_DIM), jnp.bfloat16),
        ((1, seq, KV_HEADS, HEAD_DIM), jnp.bfloat16))



_NAMED = [   # (id, the kernel's name, wrapper, operand shapes)
    ("dim_agg", "dim_agg_pallas",
     lambda s, w: ops.dimension_wise_aggregate(s, w, interpret=False),
     [((COHORT, LAYERS, RANK, D_MODEL), jnp.float32),
      ((COHORT, RANK), jnp.float32)]),
    ("dim_agg_trimmed", "dim_agg_pallas",
     lambda s, p, c, t: ops.dimension_wise_trimmed(s, p, c, t,
                                                   interpret=False),
     [((COHORT, LAYERS, RANK, D_MODEL), jnp.float32),
      ((COHORT,), jnp.float32), ((COHORT, RANK), jnp.float32),
      ((RANK,), jnp.float32)]),
    ("grouped_lora_matmul", "grouped_lora_matmul_pallas",
     lambda x, w, a, b, i: ops.grouped_lora_matmul(x, w, a, b, i,
                                                   interpret=False),
     [((8, D_MODEL), jnp.bfloat16), ((D_MODEL, D_MODEL), jnp.bfloat16),
      ((BANK, RANK, D_MODEL), jnp.float32),
      ((BANK, D_MODEL, RANK), jnp.float32), ((8,), jnp.int32)]),
    ("lora_matmul", "lora_matmul_pallas",
     lambda x, w, a, b: ops.fused_lora_matmul(x, w, a, b, interpret=False),
     [((256, D_MODEL), jnp.bfloat16), ((D_MODEL, D_MODEL), jnp.bfloat16),
      ((RANK, D_MODEL), jnp.bfloat16), ((D_MODEL, RANK), jnp.bfloat16)]),
    ("flash_attention", "flash_attention_pallas",
     lambda q, k, v: ops.flash_attention(q, k, v, interpret=False),
     [((1, 256, HEADS, HEAD_DIM), jnp.bfloat16),
      ((1, 256, KV_HEADS, HEAD_DIM), jnp.bfloat16),
      ((1, 256, KV_HEADS, HEAD_DIM), jnp.bfloat16)]),
]


@pytest.mark.parametrize("name,fn,shapes", [k[1:] for k in _NAMED],
                         ids=[k[0] for k in _NAMED])
def test_kernel_compiled_name(one_chip, name, fn, shapes):
    """Each kernel's Mosaic call carries the name its ``pallas_call`` gives
    it, the prefix by which a profile's reduction finds the kernel."""
    text = _hlo(fn, one_chip, *shapes)
    assert re.search(rf"%{name}(\.\d+)? = \S+ custom-call\(.*"
                     r'custom_call_target="tpu_custom_call"', text)
