"""The program's own instrumentation: the named scopes that the compiled
round and serving programs carry in their HLO ``op_name`` metadata (what a
profile attributes each device op to), and the serving engine's counters
of the prefill work it computes."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_reduced_config
from repro.core.editing import EditConfig
from repro.core.lora import LoRAConfig, init_lora_params, mask_lora_params
from repro.launch.fedround import make_round_engine
from repro.models import transformer as T
from repro.optim import OptimizerConfig
from repro.serving import AdapterStore, Request, ServingEngine
from repro.telemetry import Telemetry


def _scopes(hlo_text: str) -> str:
    """Every op_name of a compiled program, one per line."""
    return "\n".join(re.findall(r'op_name="([^"]*)"', hlo_text))


# ---------------------------------------------------------------------------
# the fused round
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("aggregator", ["fedilora", "fedilora_kernel"])
def test_round_program_carries_its_scopes(aggregator):
    """The compiled round at fedbench-tiny names its phases and the model's
    layers: gather, local training, aggregation, scatter; the unembedding
    loss, the LoRA projections, attention and the MLP."""
    cfg = get_config("fedbench-tiny")
    specs = T.lora_specs(cfg)
    r_g, K, n_s, steps, B, S, N = 8, 4, 2, 2, 2, 16, 6
    step = make_round_engine(
        cfg, OptimizerConfig(peak_lr=1e-3, total_steps=10), specs=specs,
        lora_scale=2.0, r_g=r_g, edit=EditConfig(enabled=True),
        aggregator=aggregator, n_sample=n_s)
    sds = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg))
    g = jax.eval_shape(lambda: init_lora_params(jax.random.PRNGKey(0), specs,
                                                LoRAConfig(rank=r_g)))
    stacked = jax.tree_util.tree_map(
        lambda x: sds((K,) + x.shape, x.dtype), g)
    data = {"tokens": sds((K, N, S), jnp.int32),
            "labels": sds((K, N, S), jnp.int32),
            "loss_mask": sds((K, N, S), jnp.float32)}
    i32 = lambda *s: sds(s, jnp.int32)
    text = jax.jit(step).lower(
        params, stacked, g, g, i32(K), sds((K,), jnp.float32), data,
        i32(n_s), i32(n_s), i32(n_s, steps, B), i32()).compile().as_text()
    names = _scopes(text)
    for scope in ("fedround.gather", "fedround.local_train",
                  "fedround.edit", "fedround.aggregate", "fedround.scatter",
                  "unembed_loss", "lora_site", "attention", "mlp"):
        assert scope in names, scope
    # the backward pass keeps the layer names (under JAX's ``transpose(``
    # wrappers, which a scan's body may also enclose)
    for scope in ("unembed_loss", "lora_site", "attention", "mlp"):
        assert re.search(rf"transpose\(.*{scope}", names), scope
    if aggregator == "fedilora_kernel":
        assert re.search(r"fedround\.aggregate/.*dim_agg_pallas", names)


# ---------------------------------------------------------------------------
# the serving engine
# ---------------------------------------------------------------------------

def _engine(lora_backend="gather", *, slots=2, chunk=4, telemetry=None):
    cfg = get_reduced_config("qwen2-0.5b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    specs = T.lora_specs(cfg)
    store = AdapterStore(slots=2, rank=8)
    for i, r in enumerate((4, 8, 2)):
        store.register(f"a{i}", mask_lora_params(init_lora_params(
            jax.random.PRNGKey(i), specs, LoRAConfig(rank=8)), r, 8), r)
    return ServingEngine(cfg, params, store, lora_scale=0.5, max_slots=slots,
                         max_prompt=16, max_gen=4, prefill_chunk=chunk,
                         lora_backend=lora_backend, telemetry=telemetry)


@pytest.mark.serving
@pytest.mark.parametrize("lora_backend", ["gather", "grouped"])
def test_serving_programs_carry_their_scopes(lora_backend):
    """Decode and chunked prefill name the LoRA projections and attention,
    whichever backend implements the projections."""
    eng = _engine(lora_backend)
    args = (eng.params, eng.store.scan_stack, eng._state, eng._cache)
    for fn in (eng._step_fn, eng._prefill_fn):
        names = _scopes(fn.lower(*args).compile().as_text())
        assert "lora_site" in names and "attention" in names
        if lora_backend == "grouped":
            assert re.search(r"lora_site/.*grouped_lora_matmul_pallas",
                             names)


@pytest.mark.serving
def test_prefill_counters_match_the_bursts():
    """``serving.prefill_tokens`` counts the prompt positions filled and
    ``serving.prefill_rows`` the rows every prefill dispatch computes, in
    the registry's snapshot and its Prometheus text."""
    slots, chunk = 2, 4
    tel = Telemetry(enabled=False)
    eng = _engine(slots=slots, chunk=chunk, telemetry=tel)
    lengths = (5, 9, 3, 12, 7)
    rng = np.random.default_rng(0)
    eng.run([Request(adapter_id=f"a{i % 3}",
                     prompt_tokens=rng.integers(1, 500, n), gen_len=3)
             for i, n in enumerate(lengths)])
    counters = tel.snapshot()["counters"]
    fills = [f for b in eng.prefill_bursts for f in b["fills"]]
    assert sorted(fills) == sorted(n - 1 for n in lengths)
    assert counters["serving.prefill_tokens"] == sum(fills)
    n_disp = eng.dispatch_count["serve_prefill"]
    assert n_disp == sum(b["dispatches"] for b in eng.prefill_bursts) > 0
    assert counters["serving.prefill_rows"] == n_disp * slots * chunk
    text = tel.prometheus()
    assert f"serving_prefill_tokens_total {sum(fills)}" in text
    assert f"serving_prefill_rows_total {n_disp * slots * chunk}" in text
