"""Shared benchmark scaffolding: builds paper-style federated experiments on
the synthetic multimodal task at CPU-tractable scale.

The paper's setting: 10 clients, sampling rate 0.4, heterogeneous ranks
4..32, LLaVA-1.5-7B, three datasets, 40%/60% missing.  Bench scale: the
``fedbench-tiny`` prefix-VLM proxy, 10 clients, three synthetic "datasets"
(different task seeds standing in for Recaps-118K / SAM-LLaVA /
Next-Preference), identical federated protocol.  Directional claims are the
reproduction target; absolute scores are task-specific (DESIGN.md §1).
"""

from __future__ import annotations

import datetime
import json
import os
import subprocess
import sys
import time

import numpy as np

from repro.configs import get_config
from repro.core.editing import EditConfig
from repro.data.missing import apply_missing_modality
from repro.data.partition import heterogeneous_sizes
from repro.data.synthetic import SyntheticTaskConfig, make_federated_datasets
from repro.federated import FaultConfig, FederatedConfig, FederatedTrainer
from repro.optim import OptimizerConfig

# synthetic stand-ins for the paper's three datasets
DATASETS = {"recaps118k": 11, "samllava": 29, "nextpref": 47}

# 14 rounds × 8 local steps trains past the caption-prefix-collapse regime
# where all methods tie (validated: at 6 rounds all aggregators emit the
# shared group prefix and Table-1 ordering is noise; at 14 the paper's
# ordering emerges — see EXPERIMENTS.md §Repro)
DEFAULT_ROUNDS = 14
NUM_CLIENTS = 10
RANKS = (4, 8, 8, 12, 12, 16, 16, 24, 32, 32)


def build_trainer(dataset: str = "samllava", *, aggregator: str = "fedilora",
                  missing: float = 0.6, edit: EditConfig | None = None,
                  ranks: tuple = RANKS, local_steps: int = 8,
                  sample_rate: float = 0.4, seed: int = 0,
                  examples: int = 700,
                  tcfg: SyntheticTaskConfig | None = None,
                  faults: FaultConfig | None = None,
                  clip_norm: float = 0.0,
                  trim_frac: float = 0.0,
                  model: str = "fedbench-tiny", base_params=None,
                  mesh=None) -> FederatedTrainer:
    """The paper protocol on the synthetic multimodal task (see the module
    docstring) over the registered config ``model``; ``base_params`` shares
    one frozen base between same-seed trainers, ``mesh`` is the round mesh
    (``FederatedTrainer(mesh=...)``)."""
    tseed = DATASETS[dataset]
    tcfg = tcfg or SyntheticTaskConfig(seed=tseed)
    sizes = heterogeneous_sizes(NUM_CLIENTS, examples, seed=tseed)
    clients, gtest = make_federated_datasets(tcfg, NUM_CLIENTS, sizes, seed=tseed)
    ctrain, ceval = [], []
    for k, d in enumerate(clients):
        n = d["tokens"].shape[0]
        ntr = max(int(n * 0.8), 1)
        tr = {kk: v[:ntr] for kk, v in d.items()}
        ev = {kk: v[ntr:] for kk, v in d.items()}
        if missing:
            tr = apply_missing_modality(tr, missing, tcfg.prompt_len,
                                        seed=tseed + k)
        ctrain.append(tr)
        ceval.append(ev)
    fcfg = FederatedConfig(
        num_clients=NUM_CLIENTS, sample_rate=sample_rate, ranks=ranks,
        local_steps=local_steps, batch_size=8, aggregator=aggregator,
        missing_ratio=missing, edit=edit or EditConfig(), seed=seed,
        faults=faults or FaultConfig(), clip_norm=clip_norm,
        trim_frac=trim_frac)
    ocfg = OptimizerConfig(peak_lr=3e-3, total_steps=600)
    return FederatedTrainer(get_config(model), fcfg, ocfg, ctrain, ceval,
                            gtest, base_params=base_params, seed=seed,
                            mesh=mesh)


def run_rounds(trainer: FederatedTrainer, rounds: int = DEFAULT_ROUNDS):
    t0 = time.perf_counter()
    for _ in range(rounds):
        trainer.run_round()
    return (time.perf_counter() - t0) / rounds


def csv_line(name: str, us_per_call: float, derived) -> str:
    return f"{name},{us_per_call:.1f},{derived}"


def measure(fn, tag: str, *, host_devices: int = 1,
            timeout: int = 2400) -> dict:
    """Run the module-level measurement function ``fn`` (returns a JSON-able
    dict) — the protocol shared by bench_fedround and bench_serving.

    On a TPU backend ``fn`` runs in THIS process: a chip belongs to the
    process that opened it, so a child could not reach it.  On the CPU
    backend it runs in a fresh interpreter with ``host_devices`` forced host
    devices (the XLA flag must be set before JAX initialises there) and the
    ``tag``-prefixed JSON line it prints is scraped.  Any other backend
    raises."""
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return fn()
    if backend != "cpu":
        raise RuntimeError(f"benchmarks measure on a TPU (or the CPU test "
                           f"backend), not on {backend!r}")
    env = dict(os.environ)
    if host_devices > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={host_devices}").strip()
    code = (f"import json; from {fn.__module__} import {fn.__name__} as f; "
            f"print({tag!r} + json.dumps(f()))")
    return run_measurement_subprocess(code, tag, env=env, timeout=timeout)


def run_measurement_subprocess(code: str, tag: str, *, env: dict | None = None,
                               timeout: int = 2400) -> dict:
    """Run ``code`` in a fresh python (clean jax init — XLA flags / device
    counts must be set before jax imports) and scrape the ``tag``-prefixed
    JSON line it prints.  CPU only: :func:`measure` is the entry point."""
    env = dict(os.environ) if env is None else env
    env.setdefault("PYTHONPATH", os.path.join(os.path.dirname(__file__), ".."))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(f"measurement subprocess failed:\n{proc.stdout}"
                           f"\n{proc.stderr}")
    payload = next(l for l in proc.stdout.splitlines() if l.startswith(tag))
    return json.loads(payload[len(tag):])


def append_history(res: dict, path: str) -> dict:
    """Merge ``res`` into a benchmark artifact: latest run at the top level,
    every run (including migrated pre-history artifacts) appended to a
    ``history`` list keyed by git SHA + timestamp — the shared scheme of
    BENCH_fedround.json and BENCH_serving.json."""
    history = []
    if os.path.exists(path):
        with open(path) as f:
            prev = json.load(f)
        history = prev.pop("history", [])
        if not history and prev:      # migrate a pre-history artifact
            history.append({"sha": None, "timestamp": None, "results": prev})
    try:
        sha = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True,
                             cwd=os.path.dirname(os.path.abspath(__file__)),
                             timeout=10).stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        sha = None
    ts = datetime.datetime.now(datetime.timezone.utc).isoformat(
        timespec="seconds")
    history.append({"sha": sha, "timestamp": ts, "results": res})
    doc = dict(res)
    doc["history"] = history
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
    return doc
