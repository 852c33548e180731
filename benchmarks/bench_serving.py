"""Multi-tenant adapter serving: tokens/sec, request-latency percentiles and
continuous- vs static-batching throughput over heterogeneous-rank
personalized LoRAs.

The workload: a ``fedbench-tiny`` population is trained for one round so
every client owns a distinct personalized adapter (heterogeneous ranks
4..32), the adapters are registered in an ``AdapterStore`` and a mixed
request stream (every request a different tenant, heterogeneous generation
lengths) is served by the ``ServingEngine``:

* **continuous** batching admits a queued request into any slot the moment
  it frees — the decode batch never idles while work is queued;
* **static** batching (the baseline) admits a full batch and drains it —
  slots whose request finished early idle until the batch's longest request
  completes.

Both modes run the identical request set through identical engines, so the
step-count gap is pure scheduling: continuous ≥ static throughput by
construction whenever generation lengths vary.  CPU-container caveat: the
per-step wall clock here is dominated by the tiny model's dispatch overhead
on 2 cores, so the throughput ratio ≈ the step-count ratio; on a real
accelerator the per-step cost grows with batch occupancy and the continuous
win widens.

A third engine measures **chunked prefill** (``prefill_chunk``): admission
fills a P-position prompt's cache rows in ⌈P/chunk⌉ ``serve_prefill``
dispatches instead of streaming P positions through shared decode steps —
the ``prefill`` section records its steps/dispatches and time-to-first-token
percentiles next to the streamed engines' (TTFT is dispatch-clock: submit →
the step() call that emitted the request's first token).

Results go to ``BENCH_serving.json`` — latest run at the top level plus a
``history`` list keyed by git SHA + timestamp (the same scheme as
``BENCH_fedround.json``, shared ``benchmarks.common.append_history``;
``python -m benchmarks.run --trajectory`` tabulates both histories).

``--quick`` skips wall-clock timing and checks the *dispatch counts* of the
serving loop (exactly one ``serve_step`` per decode step, one
``serve_admit`` per request, exactly ``max_s ⌈P_s/chunk⌉`` shared
``serve_prefill`` dispatches per admission burst — strictly fewer than the
per-request ``Σ_s ⌈P_s/chunk⌉`` on this workload, paging bounded by the
bank size) plus the continuous-vs-static step-count ordering — the
deterministic regression signal the tier-2 smoke test asserts on.
``--quick-prefill`` runs the chunked-prefill dispatch check alone (the CI
fail-fast step); both modes raise on a burst-count mismatch or when shared
prefill fails to beat the per-request count.

The ``slo`` section drives the SAME workload through the
``repro.serving.scheduler.SLOScheduler`` under open-loop Poisson and
bursty arrival traces (``benchmarks/load.py``) at an offered rate past
slot capacity: goodput-under-SLO, shed/timeout counts and per-class p99
TTFT/latency (read back from the engine's telemetry histograms, which see
OK completions only).  ``--quick-slo`` is the deterministic CI flavour on
a virtual clock: cancellation must add ZERO dispatches, an overload burst
must admit exactly the slot-capacity prefix, and one faulted (NaN
adapter) row must not change the step count while every other tenant's
tokens stay bit-identical.
"""

from __future__ import annotations

import argparse
import sys
import time

_JSON_TAG = "BENCH_SERVING_JSON:"
N_REQUESTS = 24
MAX_SLOTS = 4
GEN_LENS = (4, 13, 7, 10)       # heterogeneous per-request generation lengths
TIMED_REPS = 5
PREFILL_CHUNK = 8               # timed mode: ⌈15/8⌉ = 2 dispatches per prompt
QUICK_PREFILL_CHUNK = 4         # quick mode: ⌈15/4⌉ = 4 (exercises the tail)


def _build(num_clients: int = 6, local_steps: int = 1):
    """Tiny trained population + its serving pieces + a mixed request set."""
    import numpy as np

    from repro.configs import get_config
    from repro.data.synthetic import (SyntheticTaskConfig,
                                      make_federated_datasets)
    from repro.federated import FederatedConfig, FederatedTrainer
    from repro.optim import OptimizerConfig
    from repro.serving import Request

    tcfg = SyntheticTaskConfig(caption_len=12)
    clients, gtest = make_federated_datasets(
        tcfg, num_clients, np.full((num_clients,), 40))
    ranks = (4, 8, 8, 16, 24, 32)[:num_clients]
    fcfg = FederatedConfig(num_clients=num_clients, sample_rate=1.0,
                           ranks=ranks, local_steps=local_steps, batch_size=4,
                           aggregator="fedilora")
    tr = FederatedTrainer(get_config("fedbench-tiny"), fcfg,
                          OptimizerConfig(peak_lr=3e-3, total_steps=50),
                          clients, clients, gtest, seed=0)
    tr.run_round()

    lm = np.asarray(clients[0]["loss_mask"])
    cap_start = int(np.argmax(lm[0] > 0))

    def requests():
        out = []
        for i in range(N_REQUESTS):
            k = i % num_clients
            out.append(Request(
                adapter_id=f"client{k}",
                prompt_tokens=np.asarray(clients[k]["tokens"][i % 8][:cap_start + 1]),
                gen_len=GEN_LENS[i % len(GEN_LENS)],
                vision=np.asarray(clients[k]["image"][i % 8])))
        return out

    return tr, requests


def _engine(tr, *, continuous: bool, slots: int = MAX_SLOTS, **kw):
    from repro.serving import AdapterStore, ServingEngine

    store = AdapterStore.from_trainer(tr, slots=slots)
    return ServingEngine(tr.mcfg, tr.base_params, store,
                         lora_scale=tr.lora_scale, max_slots=slots,
                         max_prompt=8, max_gen=max(GEN_LENS),
                         continuous=continuous, **kw)


def _pctl(xs, q):
    xs = sorted(xs)
    return xs[min(int(len(xs) * q), len(xs) - 1)]


def _timed_rep(eng, requests) -> dict:
    eng.reset()
    reqs = requests()
    t0 = time.perf_counter()
    done = eng.run(reqs)
    wall = time.perf_counter() - t0
    toks = sum(len(d["tokens"]) for d in done)
    return {
        "wall_s": wall, "steps": eng.steps, "requests": len(done),
        "generated_tokens": toks,
        "tokens_per_sec": toks / wall,
        "requests_per_sec": len(done) / wall,
        "p50_latency_s": _pctl([d["latency_s"] for d in done], 0.5),
        "p95_latency_s": _pctl([d["latency_s"] for d in done], 0.95),
        "p99_latency_s": _pctl([d["latency_s"] for d in done], 0.99),
        "p50_ttft_s": _pctl([d["ttft_s"] for d in done], 0.5),
        "p95_ttft_s": _pctl([d["ttft_s"] for d in done], 0.95),
        "p99_ttft_s": _pctl([d["ttft_s"] for d in done], 0.99),
        "p50_queue_wait_s": _pctl([d["queue_wait_s"] for d in done], 0.5),
        "p95_queue_wait_s": _pctl([d["queue_wait_s"] for d in done], 0.95),
        "p99_queue_wait_s": _pctl([d["queue_wait_s"] for d in done], 0.99),
        "dispatch": dict(eng.dispatch_count),
    }


def _measure() -> dict:
    import jax

    tr, requests = _build()
    out = {"config": {"model": "fedbench-tiny", "adapters": 6,
                      "adapter_ranks": [4, 8, 8, 16, 24, 32],
                      "max_slots": MAX_SLOTS, "requests": N_REQUESTS,
                      "gen_lens": list(GEN_LENS),
                      "prefill_chunk": PREFILL_CHUNK,
                      "devices": jax.device_count(),
                      "timed_reps": TIMED_REPS}}
    # ONE engine per mode for warmup + all reps (a fresh engine would re-jit
    # its step/admit closures, putting compilation inside the timed window;
    # reset() clears the workload but keeps the compiled functions), and the
    # modes' reps are INTERLEAVED so host-load drift on the shared CI
    # cores biases all equally instead of whichever mode ran last
    eng_c = _engine(tr, continuous=True)
    eng_s = _engine(tr, continuous=False)
    eng_p = _engine(tr, continuous=True, prefill_chunk=PREFILL_CHUNK)
    eng_c.run(requests())
    eng_s.run(requests())
    eng_p.run(requests())
    best_c = best_s = best_p = None
    for _ in range(TIMED_REPS):
        rc = _timed_rep(eng_c, requests)
        rs = _timed_rep(eng_s, requests)
        rp = _timed_rep(eng_p, requests)
        if best_c is None or rc["wall_s"] < best_c["wall_s"]:
            best_c = rc
        if best_s is None or rs["wall_s"] < best_s["wall_s"]:
            best_s = rs
        if best_p is None or rp["wall_s"] < best_p["wall_s"]:
            best_p = rp
    out["continuous"] = best_c
    out["static"] = best_s
    p_fill = eng_p._n_prefix + len(requests()[0].prompt_tokens) - 1
    per_request = N_REQUESTS * -(-p_fill // PREFILL_CHUNK)
    out["prefill"] = dict(
        best_p, chunk=PREFILL_CHUNK, prompt_fill_positions=p_fill,
        dispatches_per_prompt=-(-p_fill // PREFILL_CHUNK),
        streamed_positions_per_prompt=p_fill,
        # shared prefill: same-step admissions ride one max-⌈P/chunk⌉ burst
        per_request_serve_prefill=per_request,
        shared_serve_prefill=best_p["dispatch"].get("serve_prefill", 0))
    out["continuous_vs_static_throughput"] = (
        out["continuous"]["tokens_per_sec"] / out["static"]["tokens_per_sec"])
    out["continuous_vs_static_steps"] = (
        out["static"]["steps"] / out["continuous"]["steps"])
    out["chunked_vs_streamed_ttft_p50"] = (
        best_c["p50_ttft_s"] / best_p["p50_ttft_s"])
    out["chunked_vs_streamed_throughput"] = (
        best_p["tokens_per_sec"] / best_c["tokens_per_sec"])
    out["chunked_vs_streamed_steps"] = best_c["steps"] / best_p["steps"]
    if out["continuous_vs_static_throughput"] < 1.1:
        out["caveat"] = (
            "small margin on the 2-core CI container: per-step wall clock "
            "is dispatch-overhead-bound at this tiny scale, so the "
            "throughput ratio tracks the step-count ratio "
            f"({out['continuous_vs_static_steps']:.2f}x); re-measure on an "
            "accelerator host where step cost scales with occupancy")
    out["prefill_caveat"] = (
        "2-core container: a serve_prefill dispatch costs about one "
        "dispatch overhead like a serve_step, so TTFT/throughput gains "
        "track the dispatch-count reduction "
        f"(P={p_fill} positions -> {-(-p_fill // PREFILL_CHUNK)} prefill "
        "dispatches per prompt); on accelerators the chunk also turns P "
        "serial matvec steps into matmul-shaped work")
    # ---- telemetry artifact: one instrumented mixed-batch run -------------
    # a fourth engine with tracing ON exports the Chrome trace-event
    # timeline + metrics snapshot (incl. pager hit rate, p99 TTFT and
    # queue-wait) proving the instrumented path serves the same workload
    from repro.telemetry import Telemetry
    tel = Telemetry(enabled=True)
    eng_t = _engine(tr, continuous=True, telemetry=tel)
    eng_t.run(requests())
    trace = tel.chrome_trace()
    snap = tel.snapshot()
    out["telemetry"] = {
        "span_counts": {k: int(v) for k, v in tel.tracer.counts.items()},
        "trace_events": len(trace["traceEvents"]),
        "dropped_events": trace["otherData"]["dropped_events"],
        "snapshot": snap,
        "dispatch_vs_spans_ok": all(
            tel.tracer.counts.get(name, 0) == cnt
            for name, cnt in eng_t.dispatch_count.items()),
    }
    out["slo"] = _slo_measure(tr, requests)
    return out


def _slo_measure(tr, requests) -> dict:
    """Open-loop overload traces through the SLO scheduler: the offered
    rate deliberately exceeds what MAX_SLOTS can drain so backpressure,
    shedding and deadline timeouts actually fire.  p99s come from the
    engine's telemetry histograms (ok-status completions only — shed and
    timed-out requests are counted, never averaged in)."""
    from benchmarks.load import (TraceConfig, arrival_offsets,
                                 run_open_loop, slo_classes)
    from repro.serving import RetryPolicy, SchedulerConfig, SLOScheduler
    from repro.telemetry import Telemetry

    out = {}
    for kind in ("poisson", "bursty"):
        tel = Telemetry(enabled=False)   # metrics are always live
        eng = _engine(tr, continuous=True, telemetry=tel)
        sched = SLOScheduler(eng, SchedulerConfig(
            interactive_deadline_s=0.25, batch_deadline_s=10.0,
            queue_limit=4, shed_policy="reject",
            retry=RetryPolicy(max_attempts=2, backoff_s=0.02)))
        tcfg = TraceConfig(kind=kind, rate=300.0, n=N_REQUESTS, seed=0,
                           burst_size=8)
        offs = arrival_offsets(tcfg)
        classes = slo_classes(tcfg)
        reqs = requests()

        def make_request(i):
            reqs[i].slo = classes[i]
            return reqs[i]

        rep = run_open_loop(sched, make_request, offs)
        m = eng.telemetry.metrics
        snap = m.snapshot()["histograms"]
        per_class = {}
        for cls in ("interactive", "batch"):
            per_class[cls] = {
                "p99_ttft_s": snap.get(
                    f"serving.ttft_seconds.{cls}", {}).get("p99"),
                "p99_latency_s": snap.get(
                    f"serving.latency_seconds.{cls}", {}).get("p99"),
                **rep["per_class"][cls]}
        out[kind] = {
            "trace": {"rate": tcfg.rate, "n": tcfg.n,
                      "burst_size": (tcfg.burst_size
                                     if kind == "bursty" else None)},
            "wall_s": rep["wall_s"],
            "goodput_under_slo": rep["goodput_frac"],
            "goodput": rep["goodput"], "offered": rep["offered"],
            "shed": m.get("serving.shed").value,
            "timeout": m.get("serving.timeout").value,
            "errors": m.get("serving.request_errors").value,
            "p99_ttft_s": snap["serving.ttft_seconds"].get("p99"),
            "p99_latency_s": snap["serving.latency_seconds"].get("p99"),
            "per_class": per_class,
        }
    out["caveat"] = (
        "2-core CI container: wall-clock service rate is dispatch-"
        "overhead-bound, so goodput/shed/timeout counts reflect this "
        "host's capacity under the fixed offered rate, not an "
        "accelerator's; the dispatch-count invariants (--quick-slo) are "
        "the portable regression signal")
    return out


def _quick_prefill(tr, requests, streamed_steps: int | None = None) -> dict:
    """Chunked-prefill dispatch accounting: each admission burst must cost
    exactly ``max_s ⌈P_s/chunk⌉`` shared serve_prefill dispatches (raises
    on mismatch — the CI fail-fast), the total must STRICTLY beat the
    per-request ``Σ_s ⌈P_s/chunk⌉`` (this workload's first step admits a
    burst of 2), and serve_step stops walking prompt positions."""
    eng = _engine(tr, continuous=True, slots=2,
                  prefill_chunk=QUICK_PREFILL_CHUNK)
    reqs = requests()
    fills = [eng._n_prefix + len(r.prompt_tokens) - 1 for r in reqs]
    per_request = sum(-(-p // QUICK_PREFILL_CHUNK) for p in fills)
    done = eng.run(reqs)
    bursts = eng.prefill_bursts
    expected = sum(max(-(-f // QUICK_PREFILL_CHUNK) for f in b["fills"])
                   for b in bursts)
    rec = {"chunk": QUICK_PREFILL_CHUNK, "requests": len(done),
           "prompt_fill_positions": fills[0], "steps": eng.steps,
           "expected_serve_prefill": expected,
           "per_request_serve_prefill": per_request,
           "bursts": len(bursts),
           "dispatch": dict(eng.dispatch_count)}
    if streamed_steps is not None:
        rec["streamed_steps"] = streamed_steps
    got = rec["dispatch"].get("serve_prefill")
    if sum(len(b["fills"]) for b in bursts) != len(reqs):
        raise RuntimeError(
            f"prefill burst accounting lost admissions: "
            f"{sum(len(b['fills']) for b in bursts)} != {len(reqs)}")
    if got != expected:
        raise RuntimeError(
            f"chunked prefill dispatch regression: {got} serve_prefill "
            f"dispatches != sum over bursts of max ceil(P/chunk) = "
            f"{expected}")
    if got >= per_request:
        raise RuntimeError(
            f"shared prefill must strictly beat per-request admission: "
            f"{got} dispatches >= per-request {per_request}")
    return rec


def quick_check() -> dict:
    """Dispatch-count + step-count regression check (no wall clock): one
    serve_step per decode step, one admit per request, adapter paging
    bounded by the bank, continuous needs no more steps than static, and
    chunked prefill admits in exactly ⌈P/chunk⌉ dispatches."""
    tr, requests = _build(num_clients=3, local_steps=1)
    out = {}
    for mode in ("continuous", "static"):
        eng = _engine(tr, continuous=mode == "continuous", slots=2)
        done = eng.run(requests())
        out[mode] = {"steps": eng.steps, "requests": len(done),
                     "dispatch": dict(eng.dispatch_count)}
    out["prefill"] = _quick_prefill(tr, requests,
                                    out["continuous"]["steps"])
    return out


def quick_prefill_check() -> dict:
    """The chunked-prefill dispatch check alone (CI fail-fast step)."""
    tr, requests = _build(num_clients=3, local_steps=1)
    return {"prefill": _quick_prefill(tr, requests)}


def quick_telemetry_check() -> dict:
    """Telemetry invariants on the serving loop (raises on violation):

    * a DISABLED engine records zero spans and is bitwise-invisible —
      dispatch counts and generated tokens identical to an engine built
      with no telemetry argument at all;
    * an ENABLED engine still matches those dispatch counts and tokens
      (instrumentation adds no dispatches and perturbs nothing), its
      per-name span counts equal the dispatch counts, its Chrome trace is
      well-formed and its snapshot carries pager hit rate + p99 TTFT.
    """
    import numpy as np

    from repro.telemetry import Telemetry

    tr, requests = _build(num_clients=3, local_steps=1)

    def _run(tel):
        eng = _engine(tr, continuous=True, slots=2,
                      prefill_chunk=QUICK_PREFILL_CHUNK, telemetry=tel)
        done = eng.run(requests())
        toks = np.concatenate([np.asarray(d["tokens"]) for d in done])
        return eng, done, toks

    eng0, done0, toks0 = _run(None)          # uninstrumented baseline
    tel_off = Telemetry(enabled=False)
    eng_off, _, toks_off = _run(tel_off)
    if tel_off.tracer.n_recorded != 0 or tel_off.tracer.counts:
        raise RuntimeError("disabled telemetry recorded spans: "
                           f"{dict(tel_off.tracer.counts)}")
    if dict(eng_off.dispatch_count) != dict(eng0.dispatch_count):
        raise RuntimeError(
            "disabled telemetry changed dispatch counts: "
            f"{dict(eng_off.dispatch_count)} != {dict(eng0.dispatch_count)}")
    if not np.array_equal(toks_off, toks0):
        raise RuntimeError("disabled telemetry changed generated tokens")

    tel_on = Telemetry(enabled=True)
    eng_on, done_on, toks_on = _run(tel_on)
    if dict(eng_on.dispatch_count) != dict(eng0.dispatch_count):
        raise RuntimeError(
            "enabled telemetry changed dispatch counts: "
            f"{dict(eng_on.dispatch_count)} != {dict(eng0.dispatch_count)}")
    if not np.array_equal(toks_on, toks0):
        raise RuntimeError("enabled telemetry changed generated tokens")
    for name, cnt in eng_on.dispatch_count.items():
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
    snap = tel_on.snapshot()
    if "serving.adapters.pager_hit_rate" not in snap["gauges"]:
        raise RuntimeError("pager hit-rate gauge missing from snapshot")
    if not snap["histograms"]["serving.ttft_seconds"]["count"]:
        raise RuntimeError("TTFT histogram recorded nothing")
    if "queue_wait_s" not in done_on[0]:
        raise RuntimeError("completion records lack queue_wait_s")
    if "serving_ttft_seconds" not in tel_on.prometheus():
        raise RuntimeError("Prometheus exposition lacks TTFT summary")
    return {"disabled": dict(eng_off.dispatch_count),
            "enabled": dict(eng_on.dispatch_count),
            "spans": {k: int(v) for k, v in tel_on.tracer.counts.items()}}


def quick_slo_check() -> dict:
    """SLO-scheduler invariants on a virtual clock (raises on violation):

    * **cancellation adds zero dispatches** — timing out every in-flight
      request frees the slots with no extra serve_* dispatch and no
      completion fetch;
    * **a shed burst admits exactly the slot-capacity prefix** — with
      ``queue_limit=0`` and S slots, a burst of N > S submits sheds
      N - S and the engine admits the FIFO prefix of S;
    * **one faulted row doesn't change the step count** — a NaN adapter
      (injected past validation with ``register(validate=False)``) errors
      only its own request; every other tenant's tokens are bit-identical
      to the clean run and total steps match.
    """
    import numpy as np

    from repro.serving import (AdapterStore, ManualClock, SchedulerConfig,
                               ServingEngine, SLOScheduler)

    tr, requests = _build(num_clients=3, local_steps=1)
    out = {}

    # ---- 1) shed burst admits exactly the slot-capacity prefix ------------
    clock = ManualClock()
    eng = _engine(tr, continuous=True, slots=2)
    sched = SLOScheduler(eng, SchedulerConfig(queue_limit=0,
                                              shed_policy="reject"),
                         clock=clock)
    reqs = requests()[:8]
    for r in reqs:
        sched.submit(r)
    shed_uids = [rec["uid"] for rec in sched.results
                 if rec["status"] == "shed"]
    if len(shed_uids) != 6:
        raise RuntimeError(f"expected 6 shed of 8 at queue_limit=0 over 2 "
                           f"slots, got {len(shed_uids)}")
    while sched.pending or eng.queue or eng.busy_slots:
        sched.step()
        clock.advance(1e-4)
    dc = dict(eng.dispatch_count)
    if dc.get("serve_admit") != 2:
        raise RuntimeError(f"shed burst admitted {dc.get('serve_admit')} "
                           "requests, expected exactly the 2-slot prefix")
    ok_uids = {rec["uid"] for rec in sched.results
               if rec["status"] == "ok"}
    if ok_uids != {r.uid for r in reqs[:2]}:
        raise RuntimeError("shed burst did not admit the FIFO prefix: "
                           f"completed {ok_uids}")
    if set(shed_uids) & ok_uids:
        raise RuntimeError("a shed request completed — it occupied a slot")
    out["shed"] = {"steps": eng.steps, "shed": len(shed_uids),
                   "admitted": 2, "dispatch": dc}

    # ---- 2) cancellation adds zero dispatches -----------------------------
    clock = ManualClock()
    eng = _engine(tr, continuous=True, slots=2)
    sched = SLOScheduler(eng, SchedulerConfig(interactive_deadline_s=0.05),
                         clock=clock)
    for r in requests()[:4]:
        r.slo = "interactive"
        sched.submit(r)
    sched.step()                       # admits 2, one decode step
    steps_before = eng.steps
    clock.advance(1.0)                 # every deadline now blown
    sched.step()                       # cancels in-flight, expires pending
    dc = dict(eng.dispatch_count)
    timeouts = sum(1 for rec in sched.results
                   if rec["status"] == "timeout")
    if timeouts != 4:
        raise RuntimeError(f"expected all 4 requests timed out, got "
                           f"{timeouts}")
    if eng.busy_slots or sched.pending:
        raise RuntimeError("timed-out requests still occupy slots/pending")
    if dc.get("fetch", 0) != 0:
        raise RuntimeError(f"cancellation fetched {dc['fetch']} times — it "
                           "must add zero dispatches")
    if dc.get("serve_step", 0) != eng.steps or eng.steps != steps_before:
        raise RuntimeError(
            f"cancellation changed dispatch accounting: serve_step="
            f"{dc.get('serve_step')}, steps={eng.steps}")
    if not set(dc) <= {"serve_step", "serve_admit", "adapter_load"}:
        raise RuntimeError(f"cancellation added dispatch kinds: {dc}")
    out["cancel"] = {"steps": eng.steps, "timeouts": timeouts,
                     "dispatch": dc}

    # ---- 3) one faulted row doesn't change the step count -----------------
    def _run(poison: bool):
        store = AdapterStore.from_trainer(tr)
        if poison:
            lora, rank = tr.export_adapters()["client1"]
            bad = {name: {"A": np.asarray(e["A"]) * np.nan,
                          "B": np.asarray(e["B"])}
                   for name, e in lora.items()}
            # past validation on purpose: forces non-finite logits through
            # the decode path (the quarantine path is tested separately)
            store.register("client1", bad, rank, validate=False)
        eng = ServingEngine(tr.mcfg, tr.base_params, store,
                            lora_scale=tr.lora_scale, max_slots=3,
                            max_prompt=8, max_gen=max(GEN_LENS),
                            continuous=True)
        done = eng.run(requests()[:3])     # one request per tenant
        return eng, {d["adapter_id"]: d for d in done}

    eng_clean, by_clean = _run(poison=False)
    eng_bad, by_bad = _run(poison=True)
    if eng_bad.steps != eng_clean.steps:
        raise RuntimeError(
            f"one faulted row changed the step count: {eng_bad.steps} != "
            f"{eng_clean.steps}")
    if dict(eng_bad.dispatch_count) != dict(eng_clean.dispatch_count):
        raise RuntimeError(
            "one faulted row changed dispatch counts: "
            f"{dict(eng_bad.dispatch_count)} != "
            f"{dict(eng_clean.dispatch_count)}")
    if by_bad["client1"]["status"] != "error":
        raise RuntimeError("faulted request did not complete with "
                           f"status=error: {by_bad['client1']['status']}")
    for cid in ("client0", "client2"):
        if by_bad[cid]["status"] != "ok":
            raise RuntimeError(f"{cid} was not ok next to a faulted row")
        if not np.array_equal(by_bad[cid]["tokens"],
                              by_clean[cid]["tokens"]):
            raise RuntimeError(
                f"{cid} tokens diverged next to a faulted row")
    out["fault"] = {"steps": eng_bad.steps,
                    "faulted": 1, "unaffected": 2,
                    "dispatch": dict(eng_bad.dispatch_count)}
    return out


def main(argv: list[str] | None = None) -> list[str]:
    """Measure (in a subprocess on the CPU, in this process on a TPU — see
    ``benchmarks.common.measure``), append to BENCH_serving.json's
    history, return CSV lines.  ``--quick``: dispatch-count check only,
    in-process, nothing written."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="dispatch-count check only (no timing, no JSON)")
    ap.add_argument("--quick-prefill", action="store_true",
                    help="chunked-prefill dispatch-count check only")
    ap.add_argument("--quick-telemetry", action="store_true",
                    help="telemetry invariants: disabled path is bitwise-"
                         "invisible, enabled span counts == dispatch counts")
    ap.add_argument("--quick-slo", action="store_true",
                    help="SLO-scheduler invariants: zero-dispatch "
                         "cancellation, slot-capacity shed prefix, fault "
                         "containment step parity")
    args = ap.parse_args([] if argv is None else argv)

    if args.quick_telemetry:
        counts = quick_telemetry_check()
        return [f"serving/telemetry/{mode}/{name},0.0,{cnt}"
                for mode, cc in sorted(counts.items())
                for name, cnt in sorted(cc.items())]

    if args.quick_slo:
        counts = quick_slo_check()
        lines = []
        for mode, rec in sorted(counts.items()):
            for name, val in sorted(rec.items()):
                if name == "dispatch":
                    for k, v in sorted(val.items()):
                        lines.append(f"serving/slo/{mode}/{k},0.0,{v}")
                else:
                    lines.append(f"serving/slo/{mode}/{name},0.0,{val}")
        return lines

    if args.quick or args.quick_prefill:
        counts = quick_prefill_check() if args.quick_prefill else \
            quick_check()
        lines = []
        for mode, rec in sorted(counts.items()):
            lines.append(f"serving/dispatch/{mode}/steps,0.0,{rec['steps']}")
            for name, cnt in sorted(rec["dispatch"].items()):
                lines.append(f"serving/dispatch/{mode}/{name},0.0,{cnt}")
            if "expected_serve_prefill" in rec:
                lines.append(f"serving/dispatch/{mode}/expected_serve_"
                             f"prefill,0.0,{rec['expected_serve_prefill']}")
        return lines

    from benchmarks.common import append_history, measure
    res = measure(_measure, _JSON_TAG)
    append_history(res, "BENCH_serving.json")

    lines = []
    for mode in ("continuous", "static", "prefill"):
        r = res[mode]
        lines.append(f"serving/{mode}/tokens_per_sec,"
                     f"{r['wall_s'] / max(r['steps'], 1) * 1e6:.1f},"
                     f"{r['tokens_per_sec']:.1f} tok/s")
        lines.append(f"serving/{mode}/p50_latency,"
                     f"{r['p50_latency_s'] * 1e6:.1f},"
                     f"p95={r['p95_latency_s'] * 1e3:.1f}ms")
        lines.append(f"serving/{mode}/p50_ttft,"
                     f"{r['p50_ttft_s'] * 1e6:.1f},"
                     f"p95={r['p95_ttft_s'] * 1e3:.1f}ms")
        lines.append(f"serving/{mode}/steps,0.0,{r['steps']}")
    lines.append(f"serving/continuous_vs_static,0.0,"
                 f"{res['continuous_vs_static_throughput']:.2f}x")
    lines.append(f"serving/chunked_vs_streamed_ttft_p50,0.0,"
                 f"{res['chunked_vs_streamed_ttft_p50']:.2f}x")
    lines.append(f"serving/chunked_vs_streamed_throughput,0.0,"
                 f"{res['chunked_vs_streamed_throughput']:.2f}x")
    for kind in ("poisson", "bursty"):
        s = res["slo"][kind]
        lines.append(f"serving/slo/{kind}/goodput_under_slo,0.0,"
                     f"{s['goodput_under_slo']:.2f} "
                     f"({s['goodput']}/{s['offered']})")
        lines.append(f"serving/slo/{kind}/shed,0.0,{s['shed']:.0f}")
        lines.append(f"serving/slo/{kind}/timeout,0.0,{s['timeout']:.0f}")
    return lines


if __name__ == "__main__":
    print("\n".join(main(sys.argv[1:])))
