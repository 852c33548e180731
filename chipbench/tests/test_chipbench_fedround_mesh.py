"""The mesh driver (``drivers/fedround_mesh.py``) without the chip, on a
tiny float32 µP configuration whose odd vocabulary (511) the model axis
has to pad: a sound run is correct on a (1, 1) mesh here and on a (2, 2)
mesh of four forced host devices; the fp8 control and the half-batch
fault each fail a limit; the reference gives the same rounds on one
device and spread over four.  And the two collective readers, on a
hand-built two-chip trace and on the recorded one-chip round trace."""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from chipbench import harness
from chipbench import trace_reduce as TR

DATA = os.path.join(os.path.dirname(__file__), "data")
ROOT = os.path.dirname(os.path.dirname(DATA))
SEED = 2 ** 31 + 202
# float32 on both sides, sums reordered (tensor-parallel partial sums, the
# fused round's layout): the tiny cell's own limits
LIMITS = {"loss": 1e-05, "update_norm": 0.0001, "update_leaf_diff": 0.001,
          "change_norm": 0.0001, "change_diff": 0.001}
MUP = {"scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 16}


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def mesh_root(tmp_path_factory):
    """A checkout root and a cell directory for two tiny µP cells, one per
    mesh, that report what the real mesh cell reports."""
    real = harness.load_bench()
    root = str(tmp_path_factory.mktemp("mesh"))
    with open(os.path.join(DATA, "configs", "tiny.json")) as f:
        cfg = dict(json.load(f), num_key_value_heads=4, attention_bias=False,
                   vocab_size=511, **MUP)
    with open(os.path.join(DATA, "traffic", "paper-tiny.json")) as f:
        mix = dict(json.load(f), kind="fedround_mesh")
    cells = {"fedround.tinymup.1x1": (1, [1, 1]),
             "fedround.tinymup.2x2": (4, [2, 2])}
    real_cell = "fedround.minicpm-2b.paper-2x2"
    _write(os.path.join(root, "configs", "tinymup.json"), cfg)
    workloads = []
    for name, (chips, mesh) in cells.items():
        _write(os.path.join(root, "traffic", f"{name}.json"),
               dict(mix, mesh=mesh))
        _write(os.path.join(root, "limits", f"{name}.json"), LIMITS)
        workloads.append({"name": name, "config": "tinymup",
                          "traffic": name, "chips": chips, "why": "test"})

    def remap(m):
        if real_cell not in m.get("workloads", [real_cell]):
            return None
        return dict(m, workloads=list(cells)) if "workloads" in m else m

    bench = dict(real, configs=[], workloads=workloads,
                 end_to_end=[m for m in map(remap, real["end_to_end"]) if m],
                 per_layer=[m for m in map(remap, real["per_layer"]) if m])
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


def test_sound_run_is_correct(mesh_root):
    out = harness.run_cell("fedround.tinymup.1x1", SEED, 1.0, False,
                           t_start=time.perf_counter(), require_chip=False,
                           root=mesh_root, here=mesh_root)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"round_s", "setup_s"}


def test_control_and_fault_fail_a_limit(mesh_root):
    """What ``control.py`` reads for a federated cell, through the mesh
    driver: the program within every limit, the fp8 reference and the
    half-batch reference each past one."""
    from chipbench import model, traffic

    raw = model.load_config("tinymup", mesh_root)
    mix = traffic.load_traffic("fedround.tinymup.1x1", mesh_root)
    run = harness.driver(mix["kind"]).Run(raw, mix, SEED, 1)
    run.setup()
    prog = run.program()
    run.free()
    ref = run.reference()
    assert all(v <= LIMITS[k] for k, v in run.compare(prog, ref).items()
               if k in LIMITS)
    for bad in (run.reference("fp8"), run.reference(half=True)):
        got = run.compare(bad, ref)
        assert any(got[k] > LIMITS[k] for k in LIMITS), got


def test_mesh_driver_takes_the_mup_scalars(mesh_root):
    from chipbench import model, traffic

    raw = model.load_config("tinymup", mesh_root)
    mix = traffic.load_traffic("fedround.tinymup.1x1", mesh_root)
    run = harness.driver(mix["kind"]).Run(raw, mix, SEED, 1)
    cfg = run.cfg
    assert (cfg.scale_emb, cfg.scale_depth, cfg.dim_model_base) == (12, 1.4, 16)
    assert cfg.logit_scale == pytest.approx(0.25)
    assert all(run.dm[k] == v for k, v in MUP.items())
    with pytest.raises(ValueError, match="mesh"):
        harness.driver(mix["kind"]).Run(raw, mix, SEED, 4)


def test_2x2_run_on_four_devices(mesh_root):
    """The (2, 2) cell on four forced host devices: correct, and the
    reference spread one client to a device gives the rounds it gives on
    one device."""
    code = f"""
    import sys, time
    sys.path[0:0] = [{ROOT!r}, {os.path.join(ROOT, "src")!r}]
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    import numpy as np
    from chipbench import harness, model, reference_minicpm, traffic

    root = {mesh_root!r}
    out = harness.run_cell("fedround.tinymup.2x2", {SEED}, 1.0, False,
                           t_start=time.perf_counter(), require_chip=False,
                           root=root, here=root)
    assert out["correct"], out["checks"]
    raw = model.load_config("tinymup", root)
    mix = traffic.load_traffic("fedround.tinymup.2x2", root)
    run = harness.driver(mix["kind"]).Run(raw, mix, {SEED}, 4)
    run.setup()
    emb = run.trainer.base_params["embed"]
    assert emb.shape[0] == 512 and emb.sharding.spec[0] == "model"
    run.free()
    spread = run.reference()
    one = reference_minicpm.fed_rounds(
        run.dm, run.hp, run.base, run.shards, run.g0, run.seed,
        mix["checked_rounds"], devices=jax.devices()[:1])
    assert spread["cohorts"] == one["cohorts"]
    assert spread["edited"] == one["edited"]
    np.testing.assert_allclose(spread["loss"], one["loss"], rtol=1e-6)
    for n, e in one["globals"][-1].items():
        for m in ("A", "B"):
            np.testing.assert_allclose(spread["global_"][n][m], e[m],
                                       rtol=1e-5, atol=1e-7)
    print("MESH OK")
    """
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "MESH OK" in out.stdout


# ------------------------------------------------------------ readers

# two chips: device 0 runs an all-reduce 30-50 under compute 0-40 (10
# exposed) and an all-gather 70-80 alone (10 exposed); device 1 an
# all-reduce 0-20 alone (20 exposed)
MESH = {
    "devices": {
        "0": {"ops": [["fusion.1", 0, 40, "jit_round_step", "fusion"],
                      ["all-reduce.2", 30, 20, "jit_round_step",
                       "all-reduce"],
                      ["all-gather.3", 70, 10, "jit_round_step",
                       "all-gather"]], "modules": []},
        "1": {"ops": [["all-reduce.2", 0, 20, "jit_round_step",
                       "all-reduce"],
                      ["fusion.1", 20, 60, "jit_round_step", "fusion"]],
              "modules": []}},
    "host": [], "scopes": {}}


def _read(metric, trace, info, chips, window=(0, 100)):
    red = TR.Reduced(trace, *window, devices=chips)
    return harness.load_reader(metric)({"trace": red, "info": info,
                                        "chips": chips})


@pytest.mark.parametrize("metric,want", [
    ("collective_ms.fedround", (20 + 10 + 20) / 2 / 2 * 1e-6),  # ÷ chips, rounds
    ("exposed_collective_share.fedround", 100.0 * (20 + 20) / 2 / 100),
])
def test_collective_readers_hand_worked(metric, want):
    info = {"kind": "fedround", "rounds": 2}
    assert _read(metric, MESH, info, 2) == pytest.approx(want)
    assert _read(metric, MESH, {"kind": "serve"}, 2) is None
    assert _read(metric, MESH, dict(info, rounds=0), 2) is None


@pytest.mark.parametrize("metric", ["collective_ms.fedround",
                                    "exposed_collective_share.fedround"])
def test_collective_readers_silent_on_one_chip(metric):
    """The recorded round trace is of one chip: no collective runs."""
    tr = TR.load(os.path.join(DATA, "trace_small.json.gz"))
    red = TR.Reduced(tr, *tr["window"])
    assert harness.load_reader(metric)(
        {"trace": red, "info": {"kind": "fedround", "rounds": 2},
         "chips": 1}) is None


def test_exposed_sweep_matches_reduced_method():
    """The reader's one-sweep exposure equals ``Reduced.
    exposed_collective_s`` on a random two-chip trace whose collectives
    overlap compute, each other and the window's edges."""
    import random

    rnd = random.Random(7)
    trace = {"devices": {}, "host": [], "scopes": {}}
    for d in ("0", "1"):
        ops, t = [], 0
        for i in range(400):
            dur = rnd.randint(1, 50)
            kind = "all-reduce" if rnd.random() < 0.3 else "fusion"
            ops.append([f"{kind}.{i}", t, dur, "jit_round_step", kind])
            t += rnd.randint(0, dur + 5)
        trace["devices"][d] = {"ops": ops, "modules": []}
    red = TR.Reduced(trace, 500, t - 500, devices=2)
    sweep = harness.load_reader("exposed_collective_share.fedround")
    for d in red.devices:
        want = red.exposed_collective_s(d)
        assert want > 0
        assert sweep.__globals__["exposed_s"](red, d) == pytest.approx(
            want, rel=1e-12)
