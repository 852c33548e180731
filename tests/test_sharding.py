"""Partition rules (repro/sharding.py): fit_spec divisibility/missing-axis
degradation, param_spec / lora_spec / cache_spec classification across every
model family in src/repro/configs/ (incl. mamba2's non-divisible 3352-wide
in_proj and MoE expert weights), and the round-mesh axis helpers.

The spec functions only read ``mesh.shape`` / ``mesh.axis_names``, so these
tests drive them with a duck-typed stand-in — no 256-device mesh (or any
device) is required, unlike the dry-run lowering tests that exercised them
only indirectly."""

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import sharding as SH
from repro.configs import ARCHS, get_config
from repro.launch.specs import abstract_cache, abstract_lora, abstract_params


@dataclasses.dataclass(frozen=True)
class FakeMesh:
    """Duck-types the mesh surface the spec rules consume."""

    axes: tuple            # ((name, size), ...)

    @property
    def shape(self):
        return dict(self.axes)

    @property
    def axis_names(self):
        return tuple(n for n, _ in self.axes)


PROD = FakeMesh((("data", 16), ("model", 16)))          # single-pod 16x16
POD = FakeMesh((("pod", 2), ("data", 16), ("model", 16)))
CLIENT_1D = FakeMesh((("clients", 4),))                  # round mesh, no TP
ROUND_2D = FakeMesh((("client", 4), ("model", 2)))


def _leaves(tree):
    return [(SH._path_names(p), leaf.shape) for p, leaf in
            jax.tree_util.tree_leaves_with_path(tree)]


# ---------------------------------------------------------------------------
# fit_spec: divisibility + missing-axis degradation
# ---------------------------------------------------------------------------

def test_fit_spec_drops_non_divisible_dims():
    assert SH.fit_spec(PROD, (32, 48), P("data", "model")) == P("data", "model")
    assert SH.fit_spec(PROD, (30, 48), P("data", "model")) == P(None, "model")
    assert SH.fit_spec(PROD, (32, 50), P("data", "model")) == P("data", None)
    # tuple axes: both components must divide jointly (2*16 = 32)
    assert SH.fit_spec(POD, (64, 8), P(("pod", "data"), None)) == \
        P(("pod", "data"), None)
    assert SH.fit_spec(POD, (48, 8), P(("pod", "data"), None)) == P(None, None)


def test_fit_spec_drops_axes_missing_from_mesh():
    """A rule naming an axis the mesh doesn't carry degrades to replication
    on that dim (round meshes have no "data"; 1-D serving meshes have no
    "model") instead of emitting an unconstructible spec."""
    assert SH.fit_spec(CLIENT_1D, (32, 48), P("data", "model")) == P(None, None)
    assert SH.fit_spec(ROUND_2D, (32, 48), P("data", "model")) == \
        P(None, "model")
    assert SH.fit_spec(CLIENT_1D, (32,), P("clients")) == P("clients")
    assert SH.fit_spec(POD, (64, 8), P(("pod", "missing"), None)) == P(None, None)


def test_fit_spec_pads_short_specs_with_replication():
    assert SH.fit_spec(PROD, (4, 32, 48), P(None, "model")) == \
        P(None, "model", None)


# ---------------------------------------------------------------------------
# param_spec classification across every registered architecture
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ARCHS)
def test_param_spec_invariants_all_archs(arch):
    """Every parameter of every architecture maps to a LEGAL spec on the
    production mesh: named axes exist, sharded dims divide, replicated
    names and vectors stay replicated, matmul weights are at most 2-D
    sharded (TP over "model", FSDP over "data")."""
    params = abstract_params(get_config(arch))
    for path, shape in _leaves(params):
        spec = SH.param_spec(path, shape, PROD)
        name = str(path[-1])
        assert len(spec) <= len(shape), (path, spec)
        for dim, ax in zip(shape, tuple(spec)):
            if ax is None:
                continue
            assert SH._axes_in_mesh(PROD, ax), (path, spec)
            assert dim % SH._axis_size(PROD, ax) == 0, (path, shape, spec)
        if name in SH._REPLICATED or len(shape) <= 1:
            assert spec == P(), (path, spec)
        used = [a for a in spec if a is not None]
        assert len(used) == len(set(used)), (path, spec)  # axis used once


def test_param_spec_up_down_classification():
    params = abstract_params(get_config("qwen2-72b"))
    for path, shape in _leaves(params):
        spec = SH.param_spec(path, shape, PROD)
        name = str(path[-1])
        if name in SH._UP_LIKE and len(shape) >= 2:
            # up-projections: TP on the output (last) dim when divisible
            if shape[-1] % 16 == 0:
                assert spec[-1] == "model", (path, spec)
        if name in SH._DOWN_LIKE and len(shape) >= 2:
            if shape[-2] % 16 == 0:
                assert tuple(spec)[-2] == "model", (path, spec)


def test_param_spec_mamba2_non_divisible_in_proj_degrades():
    """mamba2-130m's in_proj is 3352 wide — not divisible by the 16-way
    model axis, so exactly that dim degrades to replication while the
    input dim keeps its FSDP sharding."""
    params = abstract_params(get_config("mamba2-130m"))
    found = False
    for path, shape in _leaves(params):
        if str(path[-1]) != "in_proj":
            continue
        found = True
        assert shape[-1] == 3352, shape
        spec = SH.param_spec(path, shape, PROD)
        assert spec[-1] is None, (shape, spec)             # degraded
        assert tuple(spec)[-2] == "data", (shape, spec)    # FSDP survives
        # a mesh whose model axis divides 3352 (8 × 419) keeps the TP dim
        ok = FakeMesh((("data", 4), ("model", 8)))
        assert SH.param_spec(path, shape, ok)[-1] == "model"
    assert found, "mamba2 config lost its in_proj"


def test_param_spec_moe_expert_modes():
    """MoE expert weights [n, E, in, out]: baseline shards like dense
    matmuls; "ep" moves the expert dim onto "data" (llama4: E=16 divides;
    deepseek: E=160 divides 16 too)."""
    for arch in ("llama4-scout-17b-a16e", "deepseek-v2-236b"):
        params = abstract_params(get_config(arch))
        seen = 0
        for path, shape in _leaves(params):
            name = str(path[-1])
            if name not in SH._MOE_EXPERT_WEIGHTS or len(shape) != 4:
                continue
            seen += 1
            ep = SH.param_spec(path, shape, PROD, mode="ep")
            assert tuple(ep)[1] == "data", (arch, path, ep)
            if name == "w2":
                assert tuple(ep)[2] == "model", (arch, path, ep)
            else:
                assert ep[-1] == "model", (arch, path, ep)
            base = SH.param_spec(path, shape, PROD)
            assert tuple(base)[1] is None, (arch, path, base)
        assert seen > 0, f"{arch} has no expert weights"


def test_param_spec_degrades_on_round_meshes():
    """On a 1-D client mesh every base weight replicates (no model/data
    axes); on a 2-D (client, "model") mesh weights go pure-TP — never
    sharded over the client axis (clients must see identical weights)."""
    params = abstract_params(get_config("fedbench-tiny"))
    for path, shape in _leaves(params):
        spec1d = SH.param_spec(path, shape, CLIENT_1D)
        assert all(a is None for a in spec1d), (path, spec1d)
        spec = SH.param_spec(path, shape, ROUND_2D)
        assert "client" not in tuple(spec), (path, spec)
        assert "clients" not in tuple(spec), (path, spec)


def test_param_spec_tp_strips_the_data_axis():
    """param_spec_tp: frozen-weight placement for meshes whose "data" axis
    is a slot/client axis — the TP "model" component survives, every FSDP
    "data" component is stripped (data-sharded frozen weights would
    all-gather per use)."""
    serve_mesh = FakeMesh((("data", 2), ("model", 2)))
    params = abstract_params(get_config("fedbench-tiny"))
    for path, shape in _leaves(params):
        base = SH.param_spec(path, shape, serve_mesh)
        tp = SH.param_spec_tp(path, shape, serve_mesh)
        assert "data" not in tuple(tp), (path, tp)
        # the model component is preserved wherever baseline had it
        for ax_b, ax_t in zip(tuple(base), tuple(tp)):
            if ax_b == "model":
                assert ax_t == "model", (path, base, tp)
    # 1-D ("data",) serving mesh: everything replicates
    mesh1d = FakeMesh((("data", 2),))
    for path, shape in _leaves(params):
        assert all(a is None
                   for a in SH.param_spec_tp(path, shape, mesh1d)), path
    # a hypothetical tuple axis loses only its "data" component
    pod = FakeMesh((("pod", 2), ("data", 2), ("model", 2)))
    spec = SH.fit_spec(pod, (8, 16), P(("data", "model"), None))
    assert spec == P(("data", "model"), None)
    import repro.sharding as mod
    # exercise the tuple-strip path directly via a stub spec function
    orig = mod.param_spec
    try:
        mod.param_spec = lambda *a, **k: P(("data", "model"), "data")
        out = mod.param_spec_tp(("w",), (8, 16), pod)
        assert tuple(out) == ("model", None), out
    finally:
        mod.param_spec = orig


@pytest.mark.parametrize("arch", ARCHS)
def test_lora_spec_always_replicates(arch):
    """LoRA adapters are the cross-client aggregation objects — replicated
    on every mesh for every architecture."""
    lora = abstract_lora(get_config(arch), 16)
    for path, shape in _leaves(lora):
        for mesh in (PROD, POD, CLIENT_1D, ROUND_2D):
            assert SH.lora_spec(path, shape, mesh) == P(), (arch, path)


# ---------------------------------------------------------------------------
# cache_spec classification across cache families
# ---------------------------------------------------------------------------

def _cache_leaves(arch, batch, max_len):
    cfg = get_config(arch)
    cache = abstract_cache(cfg, abstract_params(cfg), batch, max_len)
    return _leaves(cache)


@pytest.mark.parametrize("arch,batch,max_len", [
    ("qwen2-0.5b", 32, 256),          # plain GQA KV
    ("gemma3-12b", 32, 256),          # ring (attn_local) + global KV
    ("deepseek-v2-236b", 32, 256),    # MLA latent c_kv / k_rope
    ("mamba2-130m", 32, 256),         # conv + SSD recurrent states
    ("jamba-v0.1-52b", 32, 256),      # hybrid attn + mamba
])
def test_cache_spec_baseline_batch_and_feature(arch, batch, max_len):
    """Baseline: batch axis (dim 1) over (pod, data) when divisible,
    trailing feature dim over "model" when divisible — and every emitted
    spec is legal on the mesh."""
    for path, shape in _cache_leaves(arch, batch, max_len):
        spec = SH.cache_spec(path, shape, PROD)
        for dim, ax in zip(shape, tuple(spec)):
            if ax is not None:
                assert dim % SH._axis_size(PROD, ax) == 0, (path, shape, spec)
        if len(shape) >= 2 and shape[1] == batch:
            assert tuple(spec)[1] == "data", (path, shape, spec)
        if shape[-1] % 16 == 0 and shape[-1] > 1:
            assert spec[-1] == "model", (path, shape, spec)


def test_cache_spec_seq_mode_moves_sequence_onto_model():
    """mode="seq": KV/latent caches shard their SEQUENCE dim over "model"
    (the per-step cache-all-gather fix) and drop the feature-dim TP."""
    for path, shape in _cache_leaves("deepseek-v2-236b", 32, 256):
        name = str(path[-1])
        spec = SH.cache_spec(path, shape, PROD, mode="seq")
        if name in SH._SEQ_CACHES and len(shape) >= 3:
            assert tuple(spec)[2] == "model", (path, shape, spec)
            assert spec[-1] != "model" or len(shape) == 3, (path, spec)


def test_cache_spec_long_context_batch1_seq_over_data():
    # [n_blocks, B=1, S, H, Dh]: batch can't shard; sequence goes to data
    spec = SH.cache_spec(("s0", "k"), (2, 1, 4096, 8, 128), PROD)
    assert tuple(spec)[1] is None and tuple(spec)[2] == "data", spec


def test_cache_spec_on_serving_mesh_without_model_axis():
    """A 1-D ("data",) serving mesh shards slot rows and degrades the
    feature-dim rule instead of erroring on the absent "model" axis."""
    mesh = FakeMesh((("data", 2),))
    spec = SH.cache_spec(("s0", "k"), (2, 4, 64, 8, 16), mesh)
    assert tuple(spec)[1] == "data" and spec[-1] is None, spec


# ---------------------------------------------------------------------------
# batch_spec + round-mesh helpers
# ---------------------------------------------------------------------------

def test_batch_spec_rules():
    assert SH.batch_spec((256, 128), PROD) == P("data", None)
    assert SH.batch_spec((256, 128), POD) == P(("pod", "data"), None)
    assert SH.batch_spec((10, 128), PROD) == P()               # non-divisible
    assert SH.batch_spec((1, 4096), PROD, seq_axis=1) == P(None, "data")
    assert SH.batch_spec((8,), CLIENT_1D) == P()               # no data axis


def test_round_mesh_axes_classification():
    assert SH.round_mesh_axes(CLIENT_1D) == ("clients", None)
    assert SH.round_mesh_axes(ROUND_2D) == ("client", "model")
    with pytest.raises(ValueError, match="round mesh"):
        SH.round_mesh_axes(FakeMesh((("model", 2), ("client", 2))))
    with pytest.raises(ValueError, match="round mesh"):
        SH.round_mesh_axes(POD)


def test_cohort_pad():
    from repro.launch.fedround import cohort_pad

    assert cohort_pad(4, None) == 4
    assert cohort_pad(4, ROUND_2D) == 4
    assert cohort_pad(3, ROUND_2D) == 4          # client axis 4
    assert cohort_pad(5, CLIENT_1D) == 8
    assert cohort_pad(1, FakeMesh((("c", 2), ("model", 1)))) == 2


@pytest.mark.parametrize("multiple", [2, 4])
def test_pad_vocab_changes_no_loss_or_gradient(multiple):
    """A vocabulary padded to a multiple of the model axis (MiniCPM's odd
    122,753, here 509) gives the same masked loss, accuracy and LoRA
    gradients as the unpadded one: the pad rows' logits sit at the lowest
    finite value, so they take no probability mass and never win the
    argmax.  The same float32 ops on the same values, the unembedding's
    contraction over V blocked differently: equal to rounding (1e-5 of the
    largest gradient)."""
    import jax.numpy as jnp

    from repro.configs.minicpm_2b import REDUCED
    from repro.core.lora import LoRAConfig, init_lora_params
    from repro.models import transformer as T

    cfg = dataclasses.replace(REDUCED, vocab_size=509)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    padded = SH.pad_vocab(params, multiple)
    assert padded["embed"].shape[0] == -(-509 // multiple) * multiple > 509
    np.testing.assert_array_equal(padded["embed"][:509], params["embed"])
    lora = init_lora_params(jax.random.PRNGKey(1), T.lora_specs(cfg),
                            LoRAConfig(rank=4))
    lora = {n: dict(e, B=0.05 * jax.random.normal(jax.random.PRNGKey(2),
                                                  e["B"].shape))
            for n, e in lora.items()}           # B ≠ 0: A gets gradients
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 509, (2, 12)), jnp.int32)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1),
             "loss_mask": jnp.ones((2, 12), jnp.float32)}

    def run(p):
        return jax.value_and_grad(
            lambda lo: T.loss_fn(cfg, p, lo, batch, 2.0), has_aux=True)(lora)

    (l0, m0), g0 = run(params)
    (l1, m1), g1 = run(padded)
    np.testing.assert_allclose(l1, l0, rtol=1e-6)
    assert float(m1["acc"]) == float(m0["acc"])
    for name in g0:           # a longer contraction over V re-blocks sums
        for m in ("A", "B"):
            want = np.asarray(g0[name][m])
            np.testing.assert_allclose(g1[name][m], want, rtol=1e-5,
                                       atol=1e-5 * np.abs(want).max())
    logits, _ = T.forward(cfg, padded, toks)
    assert logits.shape[-1] == padded["embed"].shape[0]
    assert bool(jnp.all(jnp.argmax(logits, -1) < 509))
    assert bool(jnp.all(jnp.isfinite(logits)))
