"""2-D (client × model) mesh: fused-round equivalence on forced-host
multi-device meshes, compiled-HLO collective structure (model-axis psums
present, frozen base never all-gathered), zero-weight cohort padding for
non-divisible sample counts, and slot-sharded multi-device serving.

Each heavy test runs in a subprocess because ``XLA_FLAGS``'s forced host
device count must be set before jax initialises (the pattern of the
existing eval-sweep / lowering tests)."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, ndev: int, timeout: int = 1200):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stdout + out.stderr
    return out.stdout


_MK = """
    import numpy as np, jax
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.core.editing import EditConfig
    from repro.data.synthetic import SyntheticTaskConfig, make_federated_datasets
    from repro.federated import FederatedConfig, FederatedTrainer
    from repro.optim import OptimizerConfig

    tcfg = SyntheticTaskConfig()
    clients, gtest = make_federated_datasets(tcfg, 2, np.array([24, 24]))

    def mk(aggregator, mesh=None, **kw):
        fcfg = FederatedConfig(num_clients=2, sample_rate=1.0, ranks=(4, 8),
                               local_steps=1, batch_size=4,
                               aggregator=aggregator,
                               edit=EditConfig(enabled=aggregator != "flora"),
                               **kw)
        return FederatedTrainer(get_config("fedbench-tiny"), fcfg,
                                OptimizerConfig(peak_lr=3e-3, total_steps=10),
                                clients, clients, gtest, seed=0, mesh=mesh)

    def tree_err(a, b):
        a, b = jax.device_get(a), jax.device_get(b)
        return max(float(np.max(np.abs(a[n][m] - b[n][m])))
                   for n in a for m in ("A", "B"))
"""


# ---------------------------------------------------------------------------
# tentpole: 2x2 round outputs == single-device engine, ONE dispatch per round
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_round_2x2_matches_single_device_all_aggregators():
    """On a forced-host 2×2 (client, model) mesh, two fused rounds of every
    aggregator family (fedavg / hetlora+prune / fedilora / the Pallas
    dim_agg kernel entry / flora) must reproduce the single-device engine
    (allclose — TP reassociates float sums), stay ONE jitted round_step
    dispatch per round and one compile, and the 2-D population eval must
    match the per-client loop exactly."""
    code = _MK + """
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("client", "model"))
    cases = [("fedavg", {}), ("hetlora", {"hetlora_prune_gamma": 0.9}),
             ("fedilora", {}), ("fedilora_kernel", {}), ("flora", {})]
    import collections
    compiles = collections.Counter()
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, fun_name=None, **_: compiles.update(
            [fun_name] if name.endswith("backend_compile_duration") else []))
    for agg, kw in cases:
        tm = mk(agg, mesh=mesh, **kw)
        ts = mk(agg, **kw)
        for _ in range(2):
            rm = tm.run_round()
            rs = ts.run_round()
            assert rm["sampled"] == rs["sampled"]
            assert rm["edited_layers"] == rs["edited_layers"]
            assert abs(rm["train_loss"] - rs["train_loss"]) < 1e-4
        assert list(tm.client_ranks) == list(ts.client_ranks)
        assert tree_err(tm.server.global_lora, ts.server.global_lora) < 5e-4
        assert tree_err(tm.stacked_lora, ts.stacked_lora) < 5e-4
        # ONE fused dispatch per round, nothing else
        assert tm.dispatch_count["round_step"] == 2
        assert set(tm.dispatch_count) == {"round_step"}, tm.dispatch_count
        # ... and ONE compile each (tm, ts): round 1 already sees the
        # placed shardings
        assert compiles.pop("jit(round_step)") == 2, compiles
        print("agg OK", agg)
    # population eval over the 2-D mesh == per-client loop (exact decode)
    tm = mk("fedilora", mesh=mesh)
    tm.run_round()
    ev = tm.evaluate_personalized(generate=True, n=4)
    el = tm.evaluate_personalized(generate=True, n=4, vmapped=False)
    assert ev["bleu"] == el["bleu"] and ev["rsum"] == el["rsum"]
    assert abs(ev["loss"] - el["loss"]) < 1e-5
    assert tm.dispatch_count["population_eval"] == 1
    print("ALL OK")
    """
    out = _run(code, 4)
    assert "ALL OK" in out


@pytest.mark.slow
def test_round_2d_hlo_model_collectives_no_base_gather():
    """Compiled-HLO structure of the fused round on a 1×2 (client, model)
    mesh — the client axis is trivial, so every collective belongs to the
    model axis: psum all-reduces from the tensor-parallel matmuls must be
    present, and NO all-gather may materialise a full frozen-base weight
    (they stay sharded; only activation-sized gathers are allowed)."""
    code = _MK + """
    import re, jax.numpy as jnp
    from repro.launch.hlo_analysis import COLLECTIVE_OPS, _shape_bytes

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("client", "model"))
    tr = mk("fedilora", mesh=mesh)
    tr.run_round()                       # compiles + runs the 2-D engine
    sampled, batch_idx = tr._build_round_inputs()
    lowered = tr._get_round_step().lower(
        tr.base_params, tr.stacked_lora, tr.server.global_lora,
        tr.server.prev_global, tr._ranks_dev, tr._sizes_dev,
        tr._stacked_data, jnp.asarray(sampled, jnp.int32),
        jnp.asarray(sampled, jnp.int32),
        jnp.asarray(batch_idx, jnp.int32),
        jnp.asarray(tr.server.round, jnp.int32))
    txt = lowered.compile().as_text()
    n_ar = len(re.findall(r"= \\S+ all-reduce(?:-start)?\\(", txt))
    assert n_ar > 0, "no model-axis psum in the tensor-parallel round"
    # frozen base weights stay sharded: the largest permissible all-gather
    # is strictly smaller than the smallest big base matmul weight
    base = jax.device_get(tr.base_params)
    big_leaves = [l.size * l.dtype.itemsize
                  for l in jax.tree_util.tree_leaves(base) if l.ndim >= 2]
    limit = max(big_leaves)
    ags = [_shape_bytes(m.group(1)) for m in re.finditer(
        r"= ([^\\n]*?) all-gather(?:-start)?\\(", txt)]
    assert all(b < limit for b in ags), (sorted(ags)[-3:], limit)
    print("HLO OK all_reduce=", n_ar, "all_gather_max=",
          max(ags) if ags else 0, "limit=", limit)
    """
    out = _run(code, 4)
    assert "HLO OK" in out


# ---------------------------------------------------------------------------
# satellite: zero-weight padding for non-divisible cohorts (no fallback)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_nondivisible_cohort_pads_instead_of_fallback():
    """n_sample=3 over a 2-device client mesh: the engine pads the cohort
    with zero-weight dummy clients (no warning, no single-device fallback)
    and reproduces the unmeshed round for BOTH the sync and async drivers."""
    code = """
    import warnings
    import numpy as np, jax
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.core.editing import EditConfig
    from repro.data.synthetic import SyntheticTaskConfig, make_federated_datasets
    from repro.federated import FederatedConfig, FederatedTrainer
    from repro.optim import OptimizerConfig

    tcfg = SyntheticTaskConfig()
    clients, gtest = make_federated_datasets(tcfg, 3, np.array([24, 30, 24]))

    def mk(aggregator="fedilora", mesh=None, **kw):
        fcfg = FederatedConfig(num_clients=3, sample_rate=1.0, ranks=(4, 8, 8),
                               local_steps=1, batch_size=4,
                               aggregator=aggregator,
                               edit=EditConfig(enabled=True), **kw)
        return FederatedTrainer(get_config("fedbench-tiny"), fcfg,
                                OptimizerConfig(peak_lr=3e-3, total_steps=10),
                                clients, clients, gtest, seed=0, mesh=mesh)

    def tree_err(a, b):
        a, b = jax.device_get(a), jax.device_get(b)
        return max(float(np.max(np.abs(a[n][m] - b[n][m])))
                   for n in a for m in ("A", "B"))

    mesh = Mesh(np.array(jax.devices()), ("clients",))
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # the old fallback warned here
        tf = mk(mesh=mesh)
        recs_f = [tf.run_round() for _ in range(2)]
    tr = mk()
    recs_r = [tr.run_round() for _ in range(2)]
    for rf, rr in zip(recs_f, recs_r):
        assert rf["sampled"] == rr["sampled"]
        assert len(rf["edited_layers"]) == 3     # metrics sliced to n_sample
        assert abs(rf["train_loss"] - rr["train_loss"]) < 1e-4
    assert tree_err(tf.server.global_lora, tr.server.global_lora) < 5e-4
    assert tree_err(tf.stacked_lora, tr.stacked_lora) < 5e-4

    ta = mk("fedbuff", mesh=mesh)
    tb = mk("fedbuff")
    for _ in range(2):
        ra = ta.run_round_async(); rb = tb.run_round_async()
        assert ra["sampled"] == rb["sampled"] and ra["merges"] == rb["merges"]
        assert abs(ra["train_loss"] - rb["train_loss"]) < 1e-4
    assert tree_err(ta.server.global_lora, tb.server.global_lora) < 5e-4
    print("PAD OK")
    """
    out = _run(code, 2)
    assert "PAD OK" in out


# ---------------------------------------------------------------------------
# satellite: multi-device serving — slot axis sharded over the mesh
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serving_slot_sharded_token_identical():
    """An engine whose decode cache / slot state / adapter bank shard their
    slot axis over a 2-device ("data",) mesh — and a 1×2 ("data", "model")
    TP engine — must serve exactly the unsharded engine's tokens, chunked
    prefill included."""
    code = """
    import numpy as np, jax
    from jax.sharding import Mesh
    from repro.configs import get_config
    from repro.data.synthetic import SyntheticTaskConfig, make_federated_datasets
    from repro.federated import FederatedConfig, FederatedTrainer
    from repro.optim import OptimizerConfig
    from repro.serving import AdapterStore, Request, ServingEngine

    tcfg = SyntheticTaskConfig(caption_len=8)
    clients, gtest = make_federated_datasets(tcfg, 3, np.array([40, 50, 60]))
    fcfg = FederatedConfig(num_clients=3, sample_rate=1.0, ranks=(4, 8, 16),
                           local_steps=1, batch_size=4, aggregator="fedilora")
    tr = FederatedTrainer(get_config("fedbench-tiny"), fcfg,
                          OptimizerConfig(peak_lr=3e-3, total_steps=50),
                          clients, clients, gtest, seed=0)
    tr.run_round()
    lm = np.asarray(clients[0]["loss_mask"])
    cap_start = int(np.argmax(lm[0] > 0))
    gen_len = int(lm[0].sum())

    def reqs():
        out = []
        for i in range(6):
            k = i % 3
            out.append(Request(
                adapter_id=f"client{k}",
                prompt_tokens=np.asarray(clients[k]["tokens"][i % 4][:cap_start + 1]),
                gen_len=gen_len if i % 2 else 3,
                vision=np.asarray(clients[k]["image"][i % 4])))
        return out

    def engine(mesh=None, **kw):
        store = AdapterStore.from_trainer(tr, slots=4, mesh=mesh)
        return ServingEngine(tr.mcfg, tr.base_params, store,
                             lora_scale=tr.lora_scale, max_slots=4,
                             max_prompt=8, max_gen=gen_len, mesh=mesh, **kw)

    def bags(done):
        # uids are globally monotonic, so sorting by uid aligns the runs
        # request-for-request regardless of completion order
        return [np.asarray(d["tokens"]).tolist()
                for d in sorted(done, key=lambda d: d["uid"])]

    base = bags(engine().run(reqs()))
    slot_mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    assert bags(engine(mesh=slot_mesh).run(reqs())) == base
    assert bags(engine(mesh=slot_mesh, prefill_chunk=3).run(reqs())) == base
    tp_mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2),
                   ("data", "model"))
    assert bags(engine(mesh=tp_mesh).run(reqs())) == base
    print("SERVE OK")
    """
    out = _run(code, 2, timeout=1800)
    assert "SERVE OK" in out


# ---------------------------------------------------------------------------
# cheap in-process validation (no multi-device requirement)
# ---------------------------------------------------------------------------

def test_trainer_rejects_both_mesh_kwargs():
    from repro.configs import get_config
    from repro.data.synthetic import (SyntheticTaskConfig,
                                      make_federated_datasets)
    from repro.federated import FederatedConfig, FederatedTrainer
    from repro.optim import OptimizerConfig
    import jax
    from jax.sharding import Mesh

    tcfg = SyntheticTaskConfig()
    clients, gtest = make_federated_datasets(tcfg, 2, np.array([24, 24]))
    fcfg = FederatedConfig(num_clients=2, sample_rate=1.0, ranks=(4, 8),
                           local_steps=1, batch_size=4)
    m = Mesh(np.asarray(jax.devices()[:1]), ("clients",))
    with pytest.raises(ValueError, match="not both"):
        FederatedTrainer(get_config("fedbench-tiny"), fcfg,
                         OptimizerConfig(), clients, clients, gtest,
                         mesh=m, client_mesh=m)


def test_serving_engine_mesh_validation():
    import jax
    from jax.sharding import Mesh

    from repro.configs import get_config
    from repro.serving import AdapterStore, ServingEngine

    tiny = get_config("fedbench-tiny")
    store = AdapterStore(slots=1, rank=4)
    bad = Mesh(np.asarray(jax.devices()[:1]), ("slots",))
    with pytest.raises(ValueError, match="'data' axis"):
        ServingEngine(tiny, None, store, lora_scale=1.0, mesh=bad)


def test_serving_engine_rejects_store_of_different_mesh():
    """A store committed to one mesh cannot feed an engine on another —
    mixed placements would crash the jitted decode, so construction fails
    loudly instead."""
    import jax
    from jax.sharding import Mesh

    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serving import AdapterStore, ServingEngine

    tiny = get_config("fedbench-tiny")
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    # jax interns Mesh objects, so two same-device same-axes meshes ARE the
    # same object (legal); a genuinely different mesh needs different
    # devices/axes — stand one in with a sentinel, the check is identity
    store = AdapterStore(slots=1, rank=4, mesh=object())
    params = T.init_params(jax.random.PRNGKey(0), tiny)
    with pytest.raises(ValueError, match="different mesh"):
        ServingEngine(tiny, params, store, lora_scale=1.0, max_slots=1,
                      mesh=mesh)
    # the symmetric hazard: a mesh-backed store feeding an UNSHARDED
    # engine must also fail loudly, not at the first jitted dispatch
    store2 = AdapterStore(slots=1, rank=4, mesh=mesh)
    with pytest.raises(ValueError, match="unsharded"):
        ServingEngine(tiny, params, store2, lora_scale=1.0, max_slots=1)


def test_store_set_mesh_replaces_materialised_bank():
    """Adopting a mesh after the bank materialised must re-place the stack
    (and invalidate the scan-major copy) instead of leaving it committed
    to the pre-mesh sharding."""
    import jax
    from jax.sharding import Mesh

    from repro.serving import AdapterStore

    store = AdapterStore(slots=2, rank=8)
    store.register("a", _store_adapter(), 4)
    _ = store.stack                       # materialise pre-mesh
    _ = store.scan_stack
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    store.set_mesh(mesh)
    leaf = jax.tree_util.tree_leaves(store.stack)[0]
    assert leaf.sharding.mesh.axis_names == ("data",)
    leaf = jax.tree_util.tree_leaves(store.scan_stack)[0]
    assert leaf.sharding.mesh.axis_names == ("data",)


def _store_adapter():
    import jax

    from repro.configs import get_config
    from repro.core.lora import LoRAConfig, init_lora_params, mask_lora_params
    from repro.models import transformer as T

    specs = T.lora_specs(get_config("fedbench-tiny"))[:1]
    return mask_lora_params(
        init_lora_params(jax.random.PRNGKey(0), specs, LoRAConfig(rank=8)),
        4, 8)


def test_mesh_reassignment_invalidates_compiled_engines():
    """Swapping the trainer's mesh must drop the cached round engines —
    their shard_map mesh and cohort padding are baked in at build time."""
    import jax
    from jax.sharding import Mesh

    from repro.configs import get_config
    from repro.data.synthetic import (SyntheticTaskConfig,
                                      make_federated_datasets)
    from repro.federated import FederatedConfig, FederatedTrainer
    from repro.optim import OptimizerConfig

    tcfg = SyntheticTaskConfig()
    clients, gtest = make_federated_datasets(tcfg, 2, np.array([24, 24]))
    fcfg = FederatedConfig(num_clients=2, sample_rate=1.0, ranks=(4, 8),
                           local_steps=1, batch_size=4)
    tr = FederatedTrainer(get_config("fedbench-tiny"), fcfg,
                          OptimizerConfig(), clients, clients, gtest)
    tr._get_round_step()
    assert tr._round_step is not None
    tr.mesh = Mesh(np.asarray(jax.devices()[:1]), ("clients",))
    assert tr._round_step is None         # stale engine dropped
    tr._get_round_step()
    tr.mesh = tr.mesh                     # same mesh: cache kept
    assert tr._round_step is not None


def test_make_round_mesh_rejects_missing_devices():
    """Both branches must fail loudly when devices are short — the 1-D
    branch used to silently truncate to however many devices exist."""
    import jax

    from repro.launch.mesh import make_round_mesh

    too_many = len(jax.devices()) + 1
    with pytest.raises(ValueError, match="needs"):
        make_round_mesh(too_many)
    with pytest.raises(ValueError, match="needs"):
        make_round_mesh(too_many, 2)


def test_launch_meshes_have_auto_axes():
    """``jax.make_mesh`` defaults to Explicit axes, which refuse the round
    engine's ``with_sharding_constraint`` pins; the launch helpers build
    Auto meshes (the 2x2 round failed to trace on a chip mesh otherwise)."""
    from jax.sharding import AxisType

    from repro.launch.mesh import _auto_mesh, make_debug_mesh

    for mesh in (_auto_mesh((1, 1), ("client", "model")),
                 make_debug_mesh(1, 1)):
        assert set(mesh.axis_types) == {AxisType.Auto}


def test_serving_params_never_fsdp_over_the_slot_axis():
    """The sharded engine's frozen base weights must be TP-only: the
    serving mesh's "data" axis is the SLOT axis, and FSDP'ing frozen
    weights over it would all-gather them every decode step."""
    import jax
    from jax.sharding import Mesh

    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serving import AdapterStore, ServingEngine

    tiny = get_config("fedbench-tiny")
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    store = AdapterStore(slots=2, rank=8)
    params = T.init_params(jax.random.PRNGKey(0), tiny)
    eng = ServingEngine(tiny, params, store, lora_scale=1.0, max_slots=2,
                        max_prompt=4, max_gen=4, mesh=mesh)
    for leaf in jax.tree_util.tree_leaves(eng.params):
        assert all(ax != "data" for ax in tuple(leaf.sharding.spec)), \
            leaf.sharding


def test_round_engine_mesh_requires_n_sample():
    """Passing a mesh without n_sample must fail loudly — the old code
    silently dropped to single-device execution."""
    import jax
    from jax.sharding import Mesh

    from repro.configs import get_config
    from repro.core.editing import EditConfig
    from repro.launch.fedround import make_round_engine
    from repro.models import transformer as T
    from repro.optim import OptimizerConfig

    cfg = get_config("fedbench-tiny")
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("clients",))
    with pytest.raises(ValueError, match="n_sample"):
        make_round_engine(cfg, OptimizerConfig(), specs=T.lora_specs(cfg),
                          lora_scale=1.0, r_g=8, edit=EditConfig(),
                          mesh=mesh)


def test_round_engine_rejects_malformed_mesh():
    import jax
    from jax.sharding import Mesh

    from repro.configs import get_config
    from repro.core.editing import EditConfig
    from repro.launch.fedround import make_round_engine
    from repro.models import transformer as T
    from repro.optim import OptimizerConfig

    cfg = get_config("fedbench-tiny")
    bad = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
               ("model", "client"))        # model must be LAST
    with pytest.raises(ValueError, match="round mesh"):
        make_round_engine(cfg, OptimizerConfig(), specs=T.lora_specs(cfg),
                          lora_scale=1.0, r_g=8, edit=EditConfig(),
                          mesh=bad, n_sample=2)
