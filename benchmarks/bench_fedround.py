"""Fused federated round: rounds/sec (blocking vs pipelined vs async vs the
sequential host-loop baseline), per-phase breakdown, KV-cached vs uncached
evaluation decode, and the looped vs vmapped personalized-evaluation sweep.

The fused engine (``FederatedTrainer.run_round``) executes a whole round as
one jit dispatch; ``run_round_pipelined`` overlaps the next round's host-side
sampling/batch-index build with the previous round's device execution
(metrics one round stale); ``run_round_async`` is the buffered FedBuff-style
timeline (client-update dispatch + staleness-weighted buffer merge).  The
sequential baseline (``run_round_reference``) is the pre-fusion engine: one
jit dispatch plus a blocking ``float()`` sync per client.

On the CPU, measurements run in a subprocess so the client mesh can be
backed by forced host-platform devices (``XLA_FLAGS`` must be set before jax
initialises); on a TPU they run in the process that holds the chips, over
the devices ``jax.devices()`` lists (``benchmarks.common.measure``).
Results go to ``BENCH_fedround.json``: the latest run at the top level, plus
a ``history`` list (one entry per run, keyed by git SHA + timestamp) so the
perf trajectory is tracked across PRs instead of overwritten.

A ``mesh`` section measures the round engine per mesh shape — 1×1, N×1
(client-parallel), 1×N (tensor-parallel) and 2×2 (client × model) on forced
host devices — recording rounds/sec AND the compiled round's HLO collective
counts (model-axis psums appear on 1×N/2×2; the frozen base is never
all-gathered).  The 2-core-container caveat is recorded in-artifact: forced
host devices share two physical cores, so multi-device wall clocks measure
slower here and only the collective structure is meaningful.

``--quick`` skips all wall-clock timing and instead checks the *dispatch
counts* of every round driver and of the one-dispatch evaluation sweep — the
regression signal (extra host syncs per round) without timing flakiness.
The tier-2 smoke test (``pytest -m slow``) asserts on these counters.
``--quick-mesh`` runs the dispatch-count asserts for a 2×2 (client, model)
mesh round + padded cohort + population eval in-process (requires
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` — the CI mesh step).

A ``population`` section scales the HOSTED client count through the paged
``ClientStateStore`` (``FederatedConfig.paged``): K = 10^3 / 10^4 / 10^5
clients sharing a small pool of synthetic shards, cohort fixed at 8 —
recording rounds/sec, the device-bank bytes (constant in K) and the host-
tier bytes, page-in dispatches and the peak number of device-resident
client rows.  ``--quick-population`` asserts the paging invariants instead
of timing: the bank never holds more client rows than its cohort-sized
slot count, prefetch/write-back add ZERO ``round_step`` dispatches, and a
hosted K=10^5 population completes rounds in the container.

A ``robustness`` section fault-injects the federation: global-eval loss vs
the fraction of persistently sign-flipping (Byzantine) clients for the plain
``fedilora`` aggregation and its robust variants (``fedilora_clip``,
``fedilora_trimmed``), recording whether the dimension-wise trimmed mean
beats plain aggregation at >= 20% flipped clients, plus the rounds/sec
overhead of running the fused round with live fault operands (dropout +
straggler forfeits + wire corruption) versus the clean program.
``--quick-robust`` asserts the fault-mode invariants instead of timing: a
hostile round is still exactly ONE ``round_step`` dispatch for the plain
and robust aggregators (sync and pipelined), one ``client_update`` per
async tick, and every global that leaves a faulted round stays finite.

Scale: fedbench-tiny, K=10 clients, sampling rate 0.4 (the paper protocol),
swept over local_steps; decode at gen_len 17 (≥16).
"""

from __future__ import annotations

import argparse
import sys
import time

_JSON_TAG = "BENCH_FEDROUND_JSON:"
_MESH_JSON_TAG = "BENCH_FEDROUND_MESH_JSON:"
_POP_JSON_TAG = "BENCH_FEDROUND_POP_JSON:"
_ROBUST_JSON_TAG = "BENCH_FEDROUND_ROBUST_JSON:"
ROBUST_BYZ_FRACS = (0.0, 0.2, 0.4)      # sign-flipping fraction of clients
ROBUST_AGGS = ("fedilora", "fedilora_clip", "fedilora_trimmed")
ROBUST_ROUNDS = 14                      # past the prefix-collapse regime
ROBUST_SAMPLE_RATE = 0.8                # cohort 8: the trimmed mean needs
                                        # survivors on both sides of the trim
ROBUST_CLIP = 1.0                       # update-norm ceiling (clip variant)
ROBUST_TRIM = 0.3                       # trim fraction (trimmed variant)
POP_SIZES = (1_000, 10_000, 100_000)    # hosted clients (paged store)
POP_COHORT = 8                          # sampled clients per round
POP_TIMED_ROUNDS = 3
MESH_SHAPES = ((1, 1), (2, 1), (1, 2), (2, 2))   # (client, model)
MESH_TIMED_ROUNDS = 3
ROUND_STEPS = (2, 8)        # local_steps sweep; 8 = paper-protocol default
TIMED_ROUNDS = 6
DECODE_CAPTION_LEN = 16     # gen_len = caption_len + 1 = 17 >= 16
DECODE_N = 16
EVAL_SWEEP_N = 8            # generation rows per client in the eval sweep


def _min_time(fn, reps):
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return min(ts)


def _measure() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import NUM_CLIENTS, build_trainer
    from repro.data.synthetic import SyntheticTaskConfig

    mesh = None
    if jax.device_count() > 1:
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()), ("clients",))

    out: dict = {"config": {"model": "fedbench-tiny", "num_clients": NUM_CLIENTS,
                            "sample_rate": 0.4, "devices": jax.device_count(),
                            "timed_rounds": TIMED_ROUNDS},
                 "rounds": {}}

    # ---- rounds/sec: fused blocking vs pipelined vs sequential ------------
    for steps in ROUND_STEPS:
        fused = build_trainer("samllava", aggregator="fedilora",
                              local_steps=steps)
        fused.client_mesh = mesh
        seq = build_trainer("samllava", aggregator="fedilora",
                            local_steps=steps)
        fused.run_round()            # compile
        seq.run_round_reference()
        tf = _min_time(fused.run_round, TIMED_ROUNDS)
        # pipelined vs blocking: BOTH as sustained loops (total/N).  A
        # per-call min would undercount the pipeline (a call only pays
        # fetch(t-1) + enqueue(t); the device cost of t lands in the NEXT
        # call) and min-vs-mean would bias the comparison, so time N
        # blocking rounds and N pipelined rounds + tail flush identically.
        t0 = time.perf_counter()
        for _ in range(TIMED_ROUNDS):
            fused.run_round()
        tb = (time.perf_counter() - t0) / TIMED_ROUNDS
        # drain the entering round before the timer so the timed window
        # covers exactly N rounds of device work (N calls + tail flush)
        fused.run_round_pipelined()  # enter the pipeline (returns None)
        fused.flush_rounds()
        t0 = time.perf_counter()
        for _ in range(TIMED_ROUNDS):
            fused.run_round_pipelined()
        fused.flush_rounds()
        tp = (time.perf_counter() - t0) / TIMED_ROUNDS
        ts = _min_time(seq.run_round_reference, TIMED_ROUNDS)
        out["rounds"][str(steps)] = {
            "fused_s": tf, "blocking_sustained_s": tb, "pipelined_s": tp,
            "sequential_s": ts,
            "fused_rounds_per_sec": 1.0 / tf,
            "pipelined_rounds_per_sec": 1.0 / tp,
            "sequential_rounds_per_sec": 1.0 / ts,
            "speedup": ts / tf,
            "pipeline_speedup_vs_blocking": tb / tp,
        }
    out["speedup_default_protocol"] = out["rounds"]["8"]["speedup"]
    out["speedup"] = max(r["speedup"] for r in out["rounds"].values())

    # ---- buffered async (fedbuff) rounds/sec ------------------------------
    asy = build_trainer("samllava", aggregator="fedbuff", local_steps=8)
    asy.client_mesh = mesh           # cohort axis shard_map, like the fused
    asy.run_round_async()            # compile (update + merge)
    ta = _min_time(asy.run_round_async, TIMED_ROUNDS)
    out["async"] = {"async_s": ta, "async_rounds_per_sec": 1.0 / ta,
                    "buffer_size": asy._n_sample,
                    "staleness_decay": asy.fcfg.staleness_decay}

    # ---- per-phase breakdown at the default protocol ----------------------
    tr = build_trainer("samllava", aggregator="fedilora", local_steps=8)
    tr.client_mesh = mesh
    tr.run_round()
    sampled = tr._sample_clients()
    idx = jnp.asarray(sampled, jnp.int32)
    ranks_s = tr._ranks_dev[idx]
    lora_s = jax.tree_util.tree_map(lambda x: x[idx], tr.stacked_lora)
    batch_idx = jnp.asarray(
        np.stack([tr._batch_indices(tr.clients[k]) for k in sampled]), jnp.int32)
    batches = {k: v[idx[:, None, None], batch_idx]
               for k, v in tr._stacked_data.items()}

    from repro.core import aggregation as AG
    from repro.launch.fedround import (_make_local_train, _vmapped_edit)
    lt = _make_local_train(tr.mcfg, tr.ocfg, lora_scale=tr.lora_scale,
                           r_g=tr.lcfg.rank)
    if mesh is not None:
        # pre-shard the per-client inputs so the timed train phase runs
        # client-parallel like the fused engine's shard_map section
        from jax.sharding import NamedSharding, PartitionSpec as P
        shard = NamedSharding(mesh, P("clients"))
        lora_s, ranks_s, batches = jax.device_put(
            (lora_s, ranks_s, batches), shard)
    vtrain = jax.jit(lambda bp, lo, r, b: jax.vmap(
        lambda l, rr, bb: lt(bp, l, rr, bb))(lo, r, b))
    vedit = jax.jit(lambda lo, r, g: _vmapped_edit(
        lo, r, g, tr.fcfg.edit, tr.lcfg.rank))
    vagg = jax.jit(lambda lo, r, p: AG.aggregate(
        "fedilora", lo, r, p)[0])
    p = jnp.full((len(sampled),), 1.0 / len(sampled))

    def timed(fn, *args):
        o = fn(*args); jax.block_until_ready(o)      # compile
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            o = fn(*args); jax.block_until_ready(o)
            ts.append(time.perf_counter() - t0)
        return min(ts), o

    t_train, (lora1, _) = timed(vtrain, tr.base_params, lora_s, ranks_s, batches)
    t_edit, (lora1, _) = timed(vedit, lora1, ranks_s, tr.server.prev_global)
    t_agg, _ = timed(vagg, lora1, ranks_s, p)
    out["phase_ms"] = {"local_train": t_train * 1e3, "edit": t_edit * 1e3,
                       "aggregate": t_agg * 1e3}

    # ---- evaluation decode: KV-cached vs per-token full forward -----------
    tcfg = SyntheticTaskConfig(seed=29, caption_len=DECODE_CAPTION_LEN)
    dec = build_trainer("samllava", aggregator="fedilora", local_steps=2,
                        tcfg=tcfg)
    dec.run_round()
    lora = dec.server.global_lora
    gtest = dec.global_test
    dec.generation_scores(lora, gtest, n=DECODE_N, cached=True)    # compile
    dec.generation_scores(lora, gtest, n=DECODE_N, cached=False)
    tc = _min_time(lambda: dec.generation_scores(lora, gtest, n=DECODE_N,
                                                 cached=True), 3)
    tu = _min_time(lambda: dec.generation_scores(lora, gtest, n=DECODE_N,
                                                 cached=False), 3)
    out["decode"] = {"gen_len": DECODE_CAPTION_LEN + 1, "batch": DECODE_N,
                     "cached_s": tc, "uncached_s": tu, "speedup": tu / tc}
    out["phase_ms"]["eval_decode_cached"] = tc * 1e3

    # ---- personalized eval sweep: per-client loop vs ONE vmapped dispatch
    # (client axis sharded over a mesh whose size divides K — possibly
    # smaller than the round mesh, which only has to divide n_sample) ------
    emesh = mesh
    if mesh is not None and NUM_CLIENTS % mesh.devices.size != 0:
        from jax.sharding import Mesh
        ed = max(d for d in range(1, mesh.devices.size + 1)
                 if NUM_CLIENTS % d == 0)
        emesh = Mesh(np.array(jax.devices()[:ed]), ("clients",)) \
            if ed > 1 else None
    dec.client_mesh = emesh
    dec.evaluate_personalized(n=EVAL_SWEEP_N, vmapped=True)        # compile
    dec.evaluate_personalized(n=EVAL_SWEEP_N, vmapped=False)
    tv = _min_time(lambda: dec.evaluate_personalized(n=EVAL_SWEEP_N,
                                                     vmapped=True), 3)
    tl = _min_time(lambda: dec.evaluate_personalized(n=EVAL_SWEEP_N,
                                                     vmapped=False), 3)
    out["eval_sweep_s"] = {"clients": NUM_CLIENTS, "gen_rows": EVAL_SWEEP_N,
                           "looped_s": tl, "vmapped_s": tv,
                           "speedup": tl / tv}

    # ---- telemetry artifact: a faulted paged federation, tracing ON -------
    # proves the instrumented round exports a valid Perfetto timeline and a
    # metrics snapshot (pager hit rate, per-phase spans, round latency)
    # while its dispatch counts stay exactly the uninstrumented ones
    from repro.telemetry import Telemetry
    tel = Telemetry(enabled=True)
    trt = _build_faulted_paged_trainer(tel)
    for _ in range(3):
        trt.run_round()
    trace = tel.chrome_trace()
    out["telemetry"] = {
        "span_counts": {k: int(v) for k, v in tel.tracer.counts.items()},
        "trace_events": len(trace["traceEvents"]),
        "dropped_events": trace["otherData"]["dropped_events"],
        "snapshot": tel.snapshot(),
        "dispatch_vs_spans_ok": all(
            tel.tracer.counts.get(name, 0) == cnt
            for name, cnt in trt.dispatch_count.items()),
    }
    return out


def _build_faulted_paged_trainer(telemetry=None):
    """Tiny paged + fault-injected trainer — the telemetry end-to-end
    workload: one round exercises cohort sampling, fault draws, page-in
    scatters, the fused dispatch and the deferred metrics fetch."""
    import numpy as np

    from repro.configs import get_config
    from repro.core.editing import EditConfig
    from repro.data.synthetic import (SyntheticTaskConfig,
                                      make_federated_datasets)
    from repro.federated import (FaultConfig, FederatedConfig,
                                 FederatedTrainer)
    from repro.optim import OptimizerConfig

    tcfg = SyntheticTaskConfig(caption_len=8)
    clients, gtest = make_federated_datasets(tcfg, 5, np.array([24] * 5))
    fcfg = FederatedConfig(
        num_clients=5, sample_rate=0.8, ranks=(4, 8, 8, 16, 8),
        local_steps=1, batch_size=4, aggregator="fedilora",
        edit=EditConfig(enabled=False), paged=True, store_slots=4,
        faults=FaultConfig(enabled=True, dropout_rate=0.3,
                           straggler_rate=0.2, corrupt_rate=0.2,
                           byzantine_clients=(1,), seed=3))
    return FederatedTrainer(get_config("fedbench-tiny"), fcfg,
                            OptimizerConfig(peak_lr=3e-3, total_steps=20),
                            clients, clients, gtest, seed=0,
                            telemetry=telemetry)


def quick_telemetry_check() -> dict:
    """Telemetry invariants on a faulted PAGED federation (raises on any
    violation):

    * a trainer with DISABLED telemetry records zero spans and is bitwise-
      invisible — dispatch counts, health counters and the global adapter
      identical to a trainer built with no telemetry argument;
    * an ENABLED trainer still matches (instrumentation adds no dispatches
      and no syncs), its per-name span counts equal the dispatch counts
      (``round_step``/``page_in``), its Chrome trace is well-formed and
      its snapshot carries the pager hit rate + round-latency histogram.
    """
    import jax
    import numpy as np

    from repro.telemetry import Telemetry

    def _run(tel):
        tr = _build_faulted_paged_trainer(tel)
        for _ in range(3):
            tr.run_round()
        return tr

    tr0 = _run(None)                       # uninstrumented baseline
    tel_off = Telemetry(enabled=False)
    tr_off = _run(tel_off)
    if tel_off.tracer.n_recorded != 0 or tel_off.tracer.counts:
        raise RuntimeError("disabled telemetry recorded spans: "
                           f"{dict(tel_off.tracer.counts)}")
    if dict(tr_off.dispatch_count) != dict(tr0.dispatch_count):
        raise RuntimeError(
            "disabled telemetry changed dispatch counts: "
            f"{dict(tr_off.dispatch_count)} != {dict(tr0.dispatch_count)}")

    tel_on = Telemetry(enabled=True)
    tr_on = _run(tel_on)
    if dict(tr_on.dispatch_count) != dict(tr0.dispatch_count):
        raise RuntimeError(
            "enabled telemetry changed dispatch counts: "
            f"{dict(tr_on.dispatch_count)} != {dict(tr0.dispatch_count)}")
    if dict(tr_on.health) != dict(tr0.health):
        raise RuntimeError("enabled telemetry changed health counters")
    g0 = jax.device_get(tr0.server.global_lora)
    g1 = jax.device_get(tr_on.server.global_lora)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            raise RuntimeError("enabled telemetry perturbed the global "
                               "adapter (must be bitwise-invisible)")
    for name, cnt in tr_on.dispatch_count.items():
        if tel_on.tracer.counts.get(name, 0) != cnt:
            raise RuntimeError(
                f"span count for {name!r} = "
                f"{tel_on.tracer.counts.get(name, 0)} != dispatch count "
                f"{cnt}")
    trace = tel_on.chrome_trace()
    for ev in trace["traceEvents"]:
        if ev["ph"] == "X" and (ev["ts"] < 0 or ev["dur"] < 0):
            raise RuntimeError(f"malformed trace event: {ev}")
    if trace["otherData"]["dropped_events"] != 0:
        raise RuntimeError("quick workload overflowed the span ring")
    if tel_on.tracer.counts.get("round") != 3:
        raise RuntimeError("round spans missing from the timeline")
    snap = tel_on.snapshot()
    if "fed.clients.pager_hit_rate" not in snap["gauges"]:
        raise RuntimeError("pager hit-rate gauge missing from snapshot")
    if snap["histograms"]["fed.round_seconds"]["count"] != 3:
        raise RuntimeError("round-latency histogram recorded "
                           f"{snap['histograms']['fed.round_seconds']}")
    if "fed_round_seconds" not in tel_on.prometheus():
        raise RuntimeError("Prometheus exposition lacks the round summary")
    return {"disabled": dict(tr_off.dispatch_count),
            "enabled": dict(tr_on.dispatch_count),
            "spans": {k: int(v) for k, v in tel_on.tracer.counts.items()}}


def quick_check() -> dict:
    """Dispatch-count regression check — no wall clock, just the jit-call
    counters of every round driver and of the evaluation sweep on a tiny
    3-client setup.  An extra host sync / dispatch per round shows up here
    deterministically; the tier-2 smoke test asserts on the result."""
    import numpy as np

    from repro.configs import get_config
    from repro.core.editing import EditConfig
    from repro.data.synthetic import (SyntheticTaskConfig,
                                      make_federated_datasets)
    from repro.federated import FederatedConfig, FederatedTrainer
    from repro.optim import OptimizerConfig

    def mk(aggregator):
        tcfg = SyntheticTaskConfig(caption_len=8)
        clients, gtest = make_federated_datasets(tcfg, 3,
                                                 np.array([24, 24, 24]))
        fcfg = FederatedConfig(num_clients=3, sample_rate=1.0,
                               ranks=(4, 8, 16), local_steps=1, batch_size=4,
                               aggregator=aggregator,
                               edit=EditConfig(enabled=True))
        return FederatedTrainer(get_config("fedbench-tiny"), fcfg,
                                OptimizerConfig(peak_lr=3e-3, total_steps=20),
                                clients, clients, gtest, seed=0)

    out = {}
    tr = mk("fedilora")
    for _ in range(3):
        tr.run_round()
    tr.evaluate_personalized(generate=True, n=4)
    out["sync"] = dict(tr.dispatch_count)

    tp = mk("fedilora")
    for _ in range(3):
        tp.run_round_pipelined()
    tp.flush_rounds()
    out["pipelined"] = dict(tp.dispatch_count)

    ta = mk("fedbuff")
    for _ in range(3):
        ta.run_round_async()
    out["async"] = dict(ta.dispatch_count)
    return out


def _build_population_trainer(K: int, n_s: int, *, slots: int = 0,
                              rounds_budget: int = 20, seed: int = 0):
    """Paged trainer hosting K clients over a SHARED pool of synthetic
    shards (clients alias pool entries, so host corpus RAM is O(pool) not
    O(K); adapters materialise lazily, so only ever-sampled clients cost
    anything) — the K-scaling harness for the population section."""
    import numpy as np

    from repro.configs import get_config
    from repro.core.editing import EditConfig
    from repro.data.synthetic import (SyntheticTaskConfig,
                                      make_federated_datasets)
    from repro.federated import FederatedConfig, FederatedTrainer
    from repro.optim import OptimizerConfig

    tcfg = SyntheticTaskConfig(caption_len=8)
    pool, gtest = make_federated_datasets(tcfg, 4, np.array([24] * 4))
    data = [pool[k % len(pool)] for k in range(K)]
    fcfg = FederatedConfig(
        num_clients=K, sample_rate=n_s / K,
        ranks=tuple((4, 8, 8, 16)[k % 4] for k in range(K)),
        local_steps=1, batch_size=4, aggregator="fedilora",
        edit=EditConfig(enabled=True), paged=True, store_slots=slots)
    return FederatedTrainer(get_config("fedbench-tiny"), fcfg,
                            OptimizerConfig(peak_lr=3e-3,
                                            total_steps=rounds_budget),
                            data, data, gtest, seed=seed)


def _population_measure() -> dict:
    """Rounds/sec + memory footprint scaling the HOSTED client population
    (paged store, cohort fixed at POP_COHORT)."""
    out: dict = {"cohort": POP_COHORT, "timed_rounds": POP_TIMED_ROUNDS,
                 "sizes": {}}
    for K in POP_SIZES:
        tr = _build_population_trainer(K, POP_COHORT)
        tr.run_round()                      # compile + first page-in
        t = _min_time(tr.run_round, POP_TIMED_ROUNDS)
        out["sizes"][str(K)] = {
            "round_s": t, "rounds_per_sec": 1.0 / t,
            "device_bank_bytes": tr.store.device_bytes(),
            "host_tier_bytes": tr.store.host_bytes(),
            "peak_resident_rows": tr.store.peak_resident,
            "bank_slots": tr.store.slots,
            "page_ins": int(tr.dispatch_count["page_in"]),
            "materialized_clients": len(tr.store.materialized_ids),
        }
    out["caveat"] = (
        "2-core container: absolute rounds/sec is CPU-bound here; this "
        "section tracks the K-scaling SHAPE — device-bank bytes must stay "
        "constant in K (the store pages cohorts, never residents the "
        "population) and round time must stay ~flat as K grows 100x")
    return out


def quick_population_check() -> dict:
    """Paged-store invariant checks (CI, in-process, no timing): the device
    bank never holds more client rows than its cohort-sized slot count,
    pipelined prefetch/write-back add ZERO ``round_step`` dispatches beyond
    one per round, and a hosted K=10^5 population completes rounds in the
    container.  Raises on any violation."""
    import jax

    out = {}
    tr = _build_population_trainer(50, 4)
    for _ in range(3):
        tr.run_round()
    for _ in range(3):
        tr.run_round_pipelined()       # prefetch under the overlap window
    tr.flush_rounds()
    counts = dict(tr.dispatch_count)
    out["population"] = counts
    if counts.get("round_step") != 6:
        raise RuntimeError(
            f"paging changed the round dispatch count: {counts} "
            "(expected exactly one round_step per round; prefetch must "
            "ride the page_in counter)")
    S = tr.store.slots
    if S != tr._n_sample:
        raise RuntimeError(
            f"store defaulted to {S} slots for a {tr._n_sample}-cohort")
    if tr.store.peak_resident > S or len(tr.store.pager.slot_of) > S:
        raise RuntimeError(
            f"device bank resided {tr.store.peak_resident} client rows "
            f"(now {len(tr.store.pager.slot_of)}) > cohort size {S}")
    bad = [leaf.shape[0] for leaf in jax.tree_util.tree_leaves(
        (tr.store.lora_bank, tr.store.ranks_bank, tr.store.sizes_bank,
         tr.store.data_bank)) if leaf.shape[0] != S]
    if bad:
        raise RuntimeError(f"bank leading dims {bad} != slots {S}")

    big = _build_population_trainer(100_000, POP_COHORT)
    for _ in range(2):
        big.run_round()
    if big.store.peak_resident > big.store.slots:
        raise RuntimeError(
            f"100k population resided {big.store.peak_resident} rows > "
            f"bank {big.store.slots}")
    if len(big.store.materialized_ids) > 2 * POP_COHORT:
        raise RuntimeError(
            "lazy init materialised "
            f"{len(big.store.materialized_ids)} clients for two "
            f"{POP_COHORT}-cohorts — the population is not lazy")
    out["population_100k"] = dict(big.dispatch_count)
    return out


def _robustness_measure() -> dict:
    """Global-eval loss vs the sign-flipped (Byzantine) client fraction for
    the plain and robust aggregators, plus the fused round's fault-injection
    overhead (live fault operands vs the clean program)."""
    from benchmarks.common import NUM_CLIENTS, build_trainer
    from repro.federated import FaultConfig

    out: dict = {"rounds": ROBUST_ROUNDS, "cohort_rate": ROBUST_SAMPLE_RATE,
                 "clip_norm": ROBUST_CLIP, "trim_frac": ROBUST_TRIM,
                 "byz_fracs": list(ROBUST_BYZ_FRACS), "aggregators": {}}
    for agg in ROBUST_AGGS:
        per = {}
        for frac in ROBUST_BYZ_FRACS:
            n_byz = int(round(frac * NUM_CLIENTS))
            tr = build_trainer(
                "samllava", aggregator=agg, local_steps=8,
                sample_rate=ROBUST_SAMPLE_RATE,
                faults=FaultConfig(enabled=True,
                                   byzantine_clients=tuple(range(n_byz))),
                clip_norm=ROBUST_CLIP if agg == "fedilora_clip" else 0.0,
                trim_frac=ROBUST_TRIM if agg == "fedilora_trimmed" else 0.0)
            for _ in range(ROBUST_ROUNDS):
                tr.run_round()
            ev = tr.evaluate_global(generate=False)
            per[f"{frac:.1f}"] = {"eval_loss": ev["loss"],
                                  "eval_acc": ev["acc"],
                                  "n_byzantine": n_byz}
        out["aggregators"][agg] = per
    plain = out["aggregators"]["fedilora"]
    trimmed = out["aggregators"]["fedilora_trimmed"]
    out["trimmed_beats_plain_at_20pct"] = bool(
        trimmed["0.2"]["eval_loss"] < plain["0.2"]["eval_loss"])

    # fault-injection overhead: identical protocol, clean program vs live
    # dropout/straggler/corruption operands (still one dispatch per round)
    clean = build_trainer("samllava", aggregator="fedilora", local_steps=8)
    clean.run_round()
    tc = _min_time(clean.run_round, TIMED_ROUNDS)
    hostile = build_trainer(
        "samllava", aggregator="fedilora", local_steps=8,
        faults=FaultConfig(enabled=True, dropout_rate=0.25,
                           straggler_rate=0.25, corrupt_rate=0.3))
    hostile.run_round()
    tf = _min_time(hostile.run_round, TIMED_ROUNDS)
    out["overhead"] = {"clean_s": tc, "faulted_s": tf,
                       "overhead_pct": (tf / tc - 1.0) * 100.0,
                       "faulted_rounds_per_sec": 1.0 / tf,
                       "health": {k: float(v)
                                  for k, v in hostile.health.items()}}
    out["caveat"] = (
        "clip targets scaled-outlier corruption (a sign-flip keeps its "
        "norm), so fedilora_clip is expected to track plain fedilora on "
        "this sweep; the trimmed mean is the sign-flip defence")
    return out


def quick_robust_check() -> dict:
    """Fault-mode dispatch asserts (CI, in-process, no timing): a hostile
    round — mid-round dropout + straggler forfeits + NaN wire corruption +
    a persistent Byzantine client — is still exactly ONE ``round_step``
    dispatch per round for the plain AND robust aggregators (sync and
    pipelined), the async driver keeps one ``client_update`` per tick, and
    every global that leaves a faulted round is finite.  Raises on any
    violation."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.editing import EditConfig
    from repro.data.synthetic import (SyntheticTaskConfig,
                                      make_federated_datasets)
    from repro.federated import (FaultConfig, FederatedConfig,
                                 FederatedTrainer)
    from repro.optim import OptimizerConfig

    faults = FaultConfig(enabled=True, dropout_rate=0.3, straggler_rate=0.2,
                         corrupt_rate=0.3, corrupt_mode="nan",
                         byzantine_clients=(1,), seed=3)
    tcfg = SyntheticTaskConfig(caption_len=8)
    clients, gtest = make_federated_datasets(tcfg, 4, np.array([24] * 4))

    def mk(aggregator, **kw):
        fcfg = FederatedConfig(num_clients=4, sample_rate=1.0,
                               ranks=(4, 8, 8, 16), local_steps=1,
                               batch_size=4, aggregator=aggregator,
                               edit=EditConfig(enabled=True), faults=faults,
                               **kw)
        return FederatedTrainer(get_config("fedbench-tiny"), fcfg,
                                OptimizerConfig(peak_lr=3e-3, total_steps=20),
                                clients, clients, gtest, seed=0)

    def check_finite(tr, tag):
        for leaf in jax.tree_util.tree_leaves(
                jax.device_get(tr.server.global_lora)):
            if not np.isfinite(np.asarray(leaf)).all():
                raise RuntimeError(
                    f"{tag}: non-finite global left a faulted round")

    out = {}
    for agg, kw in (("fedilora", {}),
                    ("fedilora_clip", {"clip_norm": 0.5}),
                    ("fedilora_trimmed", {"trim_frac": 0.3})):
        tr = mk(agg, **kw)
        for _ in range(3):
            tr.run_round()
        check_finite(tr, agg)
        out[agg] = dict(tr.dispatch_count)
        if tr.dispatch_count["round_step"] != 3:
            raise RuntimeError(
                f"faulted {agg} round not fused: {tr.dispatch_count}")
        if tr.health.get("fault_rounds", 0) != 3:
            raise RuntimeError(
                f"{agg} fault health not tracked: {dict(tr.health)}")

    tp = mk("fedilora")
    for _ in range(3):
        tp.run_round_pipelined()
    tp.flush_rounds()
    check_finite(tp, "pipelined")
    out["pipelined"] = dict(tp.dispatch_count)
    if tp.dispatch_count["round_step"] != 3:
        raise RuntimeError(
            f"faulted pipelined round not fused: {tp.dispatch_count}")

    ta = mk("fedbuff", async_delays=(0, 1, 0, 2), buffer_size=2)
    recs = [ta.run_round_async() for _ in range(4)]
    check_finite(ta, "async")
    out["async"] = dict(ta.dispatch_count)
    # a tick dispatches one client_update IFF it found an idle cohort;
    # faults must not add dispatches beyond that
    expected = sum(1 for r in recs if r["sampled"])
    if expected < 1 or ta.dispatch_count["client_update"] != expected:
        raise RuntimeError(
            f"faulted async tick dispatch regressed: {ta.dispatch_count} "
            f"(expected {expected} cohort dispatches)")
    return out


def _mesh_measure() -> dict:
    """Rounds/sec + compiled-HLO collective counts per mesh shape (1×1,
    N×1, 1×N, 2×2) — runs in a subprocess with 4 forced host devices."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from benchmarks.common import build_trainer
    from repro.launch.hlo_analysis import collective_bytes

    out = {"devices": jax.device_count(), "timed_rounds": MESH_TIMED_ROUNDS,
           "shapes": {}}
    for nc, nm in MESH_SHAPES:
        if nc * nm > jax.device_count():
            continue
        mesh = None
        if nc * nm > 1:
            mesh = Mesh(np.array(jax.devices()[: nc * nm]).reshape(nc, nm),
                        ("client", "model"))
        tr = build_trainer("samllava", aggregator="fedilora", local_steps=2)
        tr.mesh = mesh
        tr.run_round()                  # compile + place
        t = _min_time(tr.run_round, MESH_TIMED_ROUNDS)
        sampled, batch_idx = tr._build_round_inputs()
        lowered = tr._get_round_step().lower(
            tr.base_params, tr.stacked_lora, tr.server.global_lora,
            tr.server.prev_global, tr._ranks_dev, tr._sizes_dev,
            tr._stacked_data, jnp.asarray(sampled, jnp.int32),
            jnp.asarray(sampled, jnp.int32),
            jnp.asarray(batch_idx, jnp.int32),
            jnp.asarray(tr.server.round, jnp.int32))
        cb = collective_bytes(lowered.compile().as_text())
        out["shapes"][f"{nc}x{nm}"] = {
            "round_s": t, "rounds_per_sec": 1.0 / t,
            "collective_counts": cb["counts"],
            "collective_bytes": cb["total_bytes"],
        }
    out["caveat"] = (
        "2-core container: the forced host devices share two physical "
        "cores, so multi-device shapes measure SLOWER than 1x1 here — this "
        "section tracks the collective structure (model-axis all-reduces "
        "on 1xN/2x2, no frozen-base all-gather; asserted by "
        "tests/test_mesh2d.py) and the per-shape trend across PRs; "
        "re-measure rounds/sec on real accelerator meshes")
    return out


def quick_mesh_check() -> dict:
    """Dispatch-count asserts for the 2-D mesh round, in-process (the CI
    forced-host mesh step): a 2×2 (client, model) round is still ONE fused
    dispatch per round, a padded (non-divisible) cohort adds none, and the
    population eval stays one dispatch.  Raises on any mismatch."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    if jax.device_count() < 4:
        raise RuntimeError(
            f"--quick-mesh needs >= 4 devices (got {jax.device_count()}); "
            "run with XLA_FLAGS=--xla_force_host_platform_device_count=4")

    from repro.configs import get_config
    from repro.core.editing import EditConfig
    from repro.data.synthetic import (SyntheticTaskConfig,
                                      make_federated_datasets)
    from repro.federated import FederatedConfig, FederatedTrainer
    from repro.optim import OptimizerConfig

    tcfg = SyntheticTaskConfig(caption_len=8)
    clients, gtest = make_federated_datasets(tcfg, 4,
                                             np.array([24, 24, 24, 24]))

    def mk(sample_rate):
        fcfg = FederatedConfig(num_clients=4, sample_rate=sample_rate,
                               ranks=(4, 8, 8, 16), local_steps=1,
                               batch_size=4, aggregator="fedilora",
                               edit=EditConfig(enabled=True))
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("client", "model"))
        return FederatedTrainer(get_config("fedbench-tiny"), fcfg,
                                OptimizerConfig(peak_lr=3e-3, total_steps=20),
                                clients, clients, gtest, seed=0, mesh=mesh)

    out = {}
    tr = mk(1.0)                        # n_sample 4 : divides the 2 groups
    for _ in range(2):
        tr.run_round()
    tr.evaluate_personalized(generate=True, n=4)
    out["mesh2x2"] = dict(tr.dispatch_count)
    if tr.dispatch_count["round_step"] != 2:
        raise RuntimeError(f"2-D round not fused: {tr.dispatch_count}")
    if tr.dispatch_count["population_eval"] != 1 or \
            tr.dispatch_count.get("eval_loss", 0):
        raise RuntimeError(f"population eval regressed: {tr.dispatch_count}")

    tp = mk(0.75)                       # n_sample 3 : padded to 4, no extras
    for _ in range(2):
        tp.run_round()
    out["mesh2x2_padded"] = dict(tp.dispatch_count)
    if dict(tp.dispatch_count) != {"round_step": 2}:
        raise RuntimeError(
            f"padded cohort changed dispatch counts: {tp.dispatch_count}")
    return out


def _append_history(res: dict, path: str = "BENCH_fedround.json") -> dict:
    """SHA-keyed history merge — shared with BENCH_serving.json (see
    ``benchmarks.common.append_history``)."""
    from benchmarks.common import append_history
    return append_history(res, path)


def main(argv: list[str] | None = None) -> list[str]:
    """Measure every section (see ``benchmarks.common.measure``: forced
    host devices in a subprocess on the CPU, in-process on a TPU), append
    to BENCH_fedround.json's history, return CSV lines.
    ``--quick``: dispatch-count check only, in-process, nothing written.
    ``argv=None`` (the ``benchmarks.run`` harness, which leaves the suite
    name in ``sys.argv``) means no flags — only ``__main__`` passes argv."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="dispatch-count check only (no timing, no JSON)")
    ap.add_argument("--quick-mesh", action="store_true",
                    help="2-D mesh dispatch-count asserts only (needs 4 "
                         "forced host devices; no timing, no JSON)")
    ap.add_argument("--quick-population", action="store_true",
                    help="paged-store invariant asserts only (bank bounded "
                         "by the cohort, no extra round dispatches, 100k "
                         "hosted clients; no timing, no JSON)")
    ap.add_argument("--quick-robust", action="store_true",
                    help="fault-mode dispatch asserts only (faulted rounds "
                         "stay one dispatch, globals stay finite; no "
                         "timing, no JSON)")
    ap.add_argument("--quick-telemetry", action="store_true",
                    help="telemetry invariants: disabled path is bitwise-"
                         "invisible, enabled span counts == dispatch "
                         "counts on a faulted paged round")
    args = ap.parse_args([] if argv is None else argv)

    if args.quick or args.quick_mesh or args.quick_population \
            or args.quick_robust or args.quick_telemetry:
        counts = (quick_mesh_check() if args.quick_mesh
                  else quick_population_check() if args.quick_population
                  else quick_robust_check() if args.quick_robust
                  else quick_telemetry_check() if args.quick_telemetry
                  else quick_check())
        prefix = "telemetry" if args.quick_telemetry else "dispatch"
        return [f"fedround/{prefix}/{mode}/{name},0.0,{cnt}"
                for mode, cc in sorted(counts.items())
                for name, cnt in sorted(cc.items())]

    from benchmarks.common import measure
    # forced host devices back the client mesh on the CPU; on a TPU every
    # section measures in this process over the chips jax.devices() lists
    res = measure(_measure, _JSON_TAG, host_devices=4)
    res["mesh"] = measure(_mesh_measure, _MESH_JSON_TAG, host_devices=4)
    res["population"] = measure(_population_measure, _POP_JSON_TAG)
    res["robustness"] = measure(_robustness_measure, _ROBUST_JSON_TAG,
                                timeout=3600)
    _append_history(res)

    lines = []
    for steps, r in sorted(res["rounds"].items()):
        lines.append(f"fedround/steps{steps}/fused,{r['fused_s'] * 1e6:.1f},"
                     f"{r['fused_rounds_per_sec']:.2f} rounds/s")
        lines.append(f"fedround/steps{steps}/pipelined,"
                     f"{r['pipelined_s'] * 1e6:.1f},"
                     f"{r['pipelined_rounds_per_sec']:.2f} rounds/s")
        lines.append(f"fedround/steps{steps}/sequential,"
                     f"{r['sequential_s'] * 1e6:.1f},"
                     f"{r['sequential_rounds_per_sec']:.2f} rounds/s")
        lines.append(f"fedround/steps{steps}/speedup,0.0,{r['speedup']:.2f}x")
    a = res["async"]
    lines.append(f"fedround/async,{a['async_s'] * 1e6:.1f},"
                 f"{a['async_rounds_per_sec']:.2f} rounds/s")
    for phase, ms in res["phase_ms"].items():
        lines.append(f"fedround/phase/{phase},{ms * 1e3:.1f},ms={ms:.2f}")
    d = res["decode"]
    lines.append(f"fedround/decode/cached,{d['cached_s'] * 1e6:.1f},"
                 f"gen_len={d['gen_len']}")
    lines.append(f"fedround/decode/uncached,{d['uncached_s'] * 1e6:.1f},"
                 f"gen_len={d['gen_len']}")
    lines.append(f"fedround/decode/speedup,0.0,{d['speedup']:.2f}x")
    e = res["eval_sweep_s"]
    lines.append(f"fedround/eval_sweep/looped,{e['looped_s'] * 1e6:.1f},"
                 f"K={e['clients']}")
    lines.append(f"fedround/eval_sweep/vmapped,{e['vmapped_s'] * 1e6:.1f},"
                 f"K={e['clients']}")
    lines.append(f"fedround/eval_sweep/speedup,0.0,{e['speedup']:.2f}x")
    for shape, r in sorted(res["mesh"]["shapes"].items()):
        cc = r["collective_counts"]
        lines.append(
            f"fedround/mesh/{shape},{r['round_s'] * 1e6:.1f},"
            f"{r['rounds_per_sec']:.2f} rounds/s "
            f"ar={cc['all-reduce']} ag={cc['all-gather']}")
    for K, r in sorted(res["population"]["sizes"].items(),
                       key=lambda kv: int(kv[0])):
        lines.append(
            f"fedround/population/K{K},{r['round_s'] * 1e6:.1f},"
            f"{r['rounds_per_sec']:.2f} rounds/s "
            f"dev={r['device_bank_bytes']}B host={r['host_tier_bytes']}B "
            f"resident<={r['peak_resident_rows']}")
    rb = res["robustness"]
    for agg, per in sorted(rb["aggregators"].items()):
        for frac, v in sorted(per.items()):
            lines.append(f"fedround/robust/{agg}/byz{frac},0.0,"
                         f"loss={v['eval_loss']:.4f}")
    lines.append("fedround/robust/trimmed_beats_plain_at_20pct,0.0,"
                 f"{rb['trimmed_beats_plain_at_20pct']}")
    o = rb["overhead"]
    lines.append(f"fedround/robust/overhead,{o['faulted_s'] * 1e6:.1f},"
                 f"+{o['overhead_pct']:.1f}% vs clean")
    lines.append(f"fedround/devices,0.0,{res['config']['devices']}")
    return lines


if __name__ == "__main__":
    print("\n".join(main(sys.argv[1:])))
