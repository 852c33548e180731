"""Chip smoke: the federated round and multi-tenant serving, end to end on a
TPU, at qwen2-0.5b's published widths (bf16, 24 layers, d_model 896,
vocab 151936; random weights from a seed).

    python chip_smoke.py          # one chip: train phase, then serve phase
    python chip_smoke.py --mesh   # four chips: 2x2 (client, model) mesh
                                  # rounds against one-device rounds, only

Train: the paper protocol (10 clients, ranks 4..32, sample rate 0.4, the
synthetic multimodal task with 60% missing modality, 4 local steps of batch
8, editing on) for two fused rounds with the Pallas ``dim_agg`` aggregator
(``fedilora_kernel``) and two with the jnp one (``fedilora``), same seeds.
Serve: the trained clients' adapters behind ``ServingEngine`` with the
Pallas BGMV backend (``grouped``) answer 16 greedy requests over 8
adapters, checked against the jnp ``gather`` backend.

Every phase is fatal: a failed check raises and the script exits non-zero.
It refuses to run anywhere but on a TPU.  Earlier lines report compile
seconds per program, steady seconds per round and per decode step, and
peak device bytes; the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import collections
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# bf16 keeps 8 significant bits: one rounding is worth u = 2**-8 ≈ 3.9e-3.
# The two sides of every comparison below run the same math through
# different reduction orders (an f32 VPU kernel against XLA's bf16-pass
# dots; a 2x2 tensor-parallel all-reduce against one device), so they may
# disagree by a few u once that passes through 24 bf16 layers and AdamW's
# normalised steps.  TOL = 2e-2 ≈ 5u bounds that; a wrong aggregation (a
# dropped or misweighted client, a mis-tiled block) moves the result by a
# sizeable fraction of its norm.  Checked relative errors: |a - b| / |b|
# per round's train loss, ||a - b|| / ||b|| over the whole global adapter
# tree, and max|a - b| / max|b| for the kernel on identical inputs.
TOL = 2e-2
# A served token may differ between the BGMV backends only as a bf16
# near-tie: at the first differing step both tokens must sit within
# NEAR_TIE of the best logit of a plain forward over the shared prefix.
NEAR_TIE = 5e-2

MODEL = "qwen2-0.5b"
AGGREGATORS = ("fedilora_kernel", "fedilora")
ROUNDS = 2
N_REQUESTS = 16
N_ADAPTERS = 8


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: check failed: {msg}")


def report(tag: str, **fields) -> None:
    print(f"{tag} {json.dumps(fields, default=float)}", flush=True)


def require_tpu(min_devices: int) -> dict:
    """The device check that precedes everything else."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    report("device", **dev)
    if dev["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (backend "
            f"{jax.default_backend()!r}, devices {devs}); this smoke runs "
            "on the chip only")
    if dev["count"] < min_devices:
        raise SystemExit(f"chip_smoke: needs {min_devices} TPU devices, "
                         f"JAX found {dev['count']}")
    return dev


class CompileClock:
    """Seconds of XLA compilation (or persistent-cache load) per program
    name, from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds: collections.Counter = collections.Counter()

        def listen(name, secs, fun_name=None, **_):
            if name == self.EVENT:
                self.seconds[fun_name] += secs

        jax.monitoring.register_event_duration_secs_listener(listen)

    def take(self) -> dict:
        out = {k: round(v, 3) for k, v in self.seconds.items() if v >= 0.5}
        self.seconds.clear()
        return out


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def tree_rel(a, b) -> float:
    """||a - b|| / ||b|| over every leaf of two adapter trees."""
    import jax
    import numpy as np

    la = [np.asarray(x, np.float64) for x in jax.tree_util.tree_leaves(a)]
    lb = [np.asarray(x, np.float64) for x in jax.tree_util.tree_leaves(b)]
    num = math.sqrt(sum(float(np.sum((x - y) ** 2)) for x, y in zip(la, lb)))
    den = math.sqrt(sum(float(np.sum(y ** 2)) for y in lb))
    return num / max(den, 1e-30)


def make_trainer(aggregator: str, *, base_params=None, mesh=None):
    from benchmarks.common import build_trainer

    return build_trainer("samllava", aggregator=aggregator, missing=0.6,
                         local_steps=4, sample_rate=0.4, seed=0,
                         model=MODEL, base_params=base_params, mesh=mesh)


def run_rounds(tr, clock: CompileClock, label: str) -> list[float]:
    import jax

    losses = []
    for r in range(ROUNDS):
        t0 = time.perf_counter()
        rec = tr.run_round()             # ends in the round's metrics fetch
        jax.block_until_ready(tr.server.global_lora)
        secs = time.perf_counter() - t0
        check(math.isfinite(rec["train_loss"]),
              f"{label} round {r + 1} loss {rec['train_loss']}")
        losses.append(rec["train_loss"])
        report("round", trainer=label, round=r + 1,
               train_loss=rec["train_loss"], seconds=secs,
               compile_s=clock.take(), edited_layers=rec["edited_layers"])
    return losses


def round_hlo(tr) -> str:
    """Compiled HLO text of the trainer's fused round (a persistent-cache
    hit after the first dispatch compiled it)."""
    import jax.numpy as jnp

    n_s, fc = tr._n_sample, tr.fcfg
    idx = jnp.zeros((n_s,), jnp.int32)
    lowered = tr._get_round_step().lower(
        tr.base_params, tr.stacked_lora, tr.server.global_lora,
        tr.server.prev_global, tr._ranks_dev, tr._sizes_dev,
        tr._stacked_data, idx, idx,
        jnp.zeros((n_s, fc.local_steps, fc.batch_size), jnp.int32),
        jnp.zeros((), jnp.int32))
    return lowered.compile().as_text()


def compare_trainers(a, b, la, lb, label: str) -> None:
    """Per-round loss and global-adapter agreement within TOL."""
    loss_rel = [abs(x - y) / abs(y) for x, y in zip(la, lb)]
    g_rel = tree_rel(a.server.global_lora, b.server.global_lora)
    report("agree", pair=label, loss_rel=loss_rel, global_rel=g_rel, tol=TOL)
    check(max(loss_rel) <= TOL, f"{label}: train_loss rel diff {loss_rel}")
    check(g_rel <= TOL, f"{label}: global adapter rel diff {g_rel}")


def train_phase(clock: CompileClock):
    import jax
    import jax.numpy as jnp

    from repro.core import aggregation as AG

    trainers, losses = {}, {}
    base = None
    for agg in AGGREGATORS:
        t0 = time.perf_counter()
        tr = make_trainer(agg, base_params=base)
        jax.block_until_ready(tr.base_params)
        base = tr.base_params            # one frozen base for both
        report("trainer", aggregator=agg, model=tr.mcfg.name,
               dtype=tr.mcfg.dtype, layers=tr.mcfg.num_layers,
               d_model=tr.mcfg.d_model, vocab=tr.mcfg.vocab_size,
               cohort=tr._n_sample, ranks=list(tr.fcfg.ranks),
               build_s=time.perf_counter() - t0)
        losses[agg] = run_rounds(tr, clock, agg)
        trainers[agg] = tr
    kern, ref = trainers["fedilora_kernel"], trainers["fedilora"]
    compare_trainers(kern, ref, losses["fedilora_kernel"], losses["fedilora"],
                     "fedilora_kernel~fedilora")

    t0 = time.perf_counter()
    hlo = round_hlo(kern)
    n_kernels = hlo.count("tpu_custom_call")
    report("round_hlo", aggregator="fedilora_kernel",
           tpu_custom_calls=n_kernels, seconds=time.perf_counter() - t0)
    check(n_kernels > 0, "no tpu_custom_call in the fedilora_kernel round")

    # the kernel against jnp on identical inputs: every client's adapter
    p = kern._sizes_dev / jnp.sum(kern._sizes_dev)
    agg = {name: jax.jit(lambda s, r, w, n=name: AG.aggregate(n, s, r, w)[0])
           for name in AGGREGATORS}
    outs = {n: f(kern.stacked_lora, kern._ranks_dev, p)
            for n, f in agg.items()}
    la = jax.tree_util.tree_leaves(outs["fedilora_kernel"])
    lb = jax.tree_util.tree_leaves(outs["fedilora"])
    err = max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(la, lb))
    scale = max(float(jnp.max(jnp.abs(y))) for y in lb)
    report("dim_agg_direct", clients=kern.fcfg.num_clients,
           max_abs_diff=err, max_abs=scale, rel=err / scale, tol=TOL)
    check(err <= TOL * scale, f"dim_agg kernel vs jnp: {err} vs {scale}")
    report("train_memory", peak_bytes_in_use=peak_bytes())
    return kern


def make_requests(tr):
    import numpy as np

    from repro.serving.engine import Request

    toks = np.asarray(tr.global_test["tokens"])
    reqs = []
    for q in range(N_REQUESTS):
        plen = 4 + q % 7                          # ragged prompts: 4..10
        reqs.append(Request(adapter_id=f"client{q % N_ADAPTERS}",
                            prompt_tokens=toks[q, :plen].astype(np.int32),
                            gen_len=6 + (5 * q) % 11))      # 6..16
    return reqs


def serve(tr, backend: str, clock: CompileClock, *, timed: bool):
    """Answer the request set through a fresh engine; returns tokens per
    request index (and, ``timed``, a second steady-state pass)."""
    import jax

    from repro.serving import AdapterStore, ServingEngine

    store = AdapterStore.from_trainer(tr)
    eng = ServingEngine(tr.mcfg, tr.base_params, store,
                        lora_scale=tr.lora_scale, max_slots=8,
                        max_prompt=16, max_gen=16, prefill_chunk=8,
                        lora_backend=backend)
    runs = 2 if timed else 1
    tokens = None
    for run in range(runs):
        eng.reset()
        reqs = make_requests(tr)
        order = {r.uid: q for q, r in enumerate(reqs)}
        t0 = time.perf_counter()
        done = eng.run(reqs)
        jax.block_until_ready(eng._state)
        secs = time.perf_counter() - t0
        check(len(done) == N_REQUESTS
              and all(d["status"] == "ok" for d in done),
              f"{backend}: {[(d['uid'], d['status']) for d in done]}")
        got = [None] * N_REQUESTS
        for d in done:
            got[order[d["uid"]]] = d["tokens"]
        steps = eng.steps
        report("serve", backend=backend, run=run + 1,
               warm=run > 0, requests=len(done),
               adapters=len({d["adapter_id"] for d in done}),
               serve_steps=steps, seconds=secs,
               seconds_per_step=secs / max(steps, 1),
               dispatch=dict(eng.dispatch_count), compile_s=clock.take())
        if tokens is not None:
            check(all((a == b).all() for a, b in zip(tokens, got)),
                  f"{backend}: second pass served different tokens")
        tokens = got
    return eng, tokens


def near_tie(tr, req, common, a: int, b: int) -> float:
    """Gap between the two tokens' logits, and each to the best, under a
    plain forward of the request's adapter over prompt + shared tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as T

    adapter = tr.export_adapters()[req.adapter_id][0]
    seq = np.concatenate([req.prompt_tokens, np.asarray(common, np.int32)])
    pad = np.zeros((32,), np.int32)
    pad[:len(seq)] = seq
    fwd = jax.jit(lambda p, lo, t: T.forward(
        tr.mcfg, p, t[None], lora=lo, lora_scale=tr.lora_scale)[0][0])
    logits = np.asarray(fwd(tr.base_params, adapter, jnp.asarray(pad))
                        [len(seq) - 1], np.float64)
    best = float(logits.max())
    return max(best - logits[a], best - logits[b])


def serve_phase(tr, clock: CompileClock) -> None:
    _, grouped = serve(tr, "grouped", clock, timed=True)
    _, gather = serve(tr, "gather", clock, timed=False)
    reqs = make_requests(tr)
    check(len({r.adapter_id for r in reqs}) >= 4, "fewer than 4 adapters")
    same, ties = 0, []
    for q, (a, b) in enumerate(zip(grouped, gather)):
        if (a == b).all():
            same += 1
            continue
        g = int((a != b).argmax())               # first differing step
        gap = near_tie(tr, reqs[q], a[:g], int(a[g]), int(b[g]))
        ties.append({"request": q, "step": g, "grouped": int(a[g]),
                     "gather": int(b[g]), "gap_to_best": gap})
    report("serve_agree", identical_requests=same, near_ties=ties,
           near_tie_tol=NEAR_TIE)
    for t in ties:
        check(t["gap_to_best"] <= NEAR_TIE,
              f"grouped vs gather token differs beyond a near-tie: {t}")
    report("serve_memory", peak_bytes_in_use=peak_bytes())


def mesh_phase(clock: CompileClock) -> None:
    """2x2 (client, model) mesh rounds against one-device rounds, per
    aggregator."""
    import jax

    from repro.launch.mesh import make_round_mesh

    mesh = make_round_mesh(2, 2)
    base = None
    for agg in ("fedilora", "fedilora_kernel"):
        one = make_trainer(agg, base_params=base)
        base = one.base_params
        l_one = run_rounds(one, clock, f"{agg}@1")
        two = make_trainer(agg, base_params=base, mesh=mesh)
        l_two = run_rounds(two, clock, f"{agg}@2x2")
        compare_trainers(two, one, l_two, l_one, f"{agg}: 2x2~1")
        if agg == "fedilora_kernel":
            n = round_hlo(two).count("tpu_custom_call")
            report("round_hlo", aggregator=agg, mesh="2x2",
                   tpu_custom_calls=n)
            check(n > 0, "no tpu_custom_call in the 2x2 kernel round")
    report("mesh_memory", peak_bytes_in_use=peak_bytes(),
           devices=[str(d) for d in jax.devices()[:4]])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: the 2x2 (client, model) mesh round "
                         "against one device, and nothing else")
    args = ap.parse_args()
    dev = require_tpu(4 if args.mesh else 1)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_compile_cache

    report("compile_cache", dir=use_compile_cache())
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.mesh:
        mesh_phase(clock)
    else:
        tr = train_phase(clock)
        serve_phase(tr, clock)
    report("wall", seconds=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
