"""Tier-2 smoke: the benchmark's --quick dispatch-count check.

Runs ``benchmarks.bench_fedround.quick_check()`` and asserts the jit-call
counters of every round driver — a regression here means an extra host sync
or dispatch crept into the round/eval hot path.  Counting dispatches is
deterministic, unlike wall-clock timing, so this can gate CI.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


@pytest.mark.slow
def test_bench_quick_dispatch_counts():
    from benchmarks.bench_fedround import quick_check

    counts = quick_check()

    # synchronous driver: one fused dispatch per round; the K-client
    # personalized evaluation is ONE population dispatch, never the
    # per-client eval-loss/generate loop
    assert counts["sync"]["round_step"] == 3
    assert counts["sync"]["population_eval"] == 1
    assert counts["sync"].get("eval_loss", 0) == 0
    assert counts["sync"].get("generate", 0) == 0
    assert counts["sync"].get("next_logits", 0) == 0

    # pipelined driver: same single dispatch per round (the pipeline only
    # reorders the metrics fetch, it must not add dispatches)
    assert counts["pipelined"]["round_step"] == 3
    assert counts["pipelined"].get("eval_loss", 0) == 0

    # buffered async: one client-update and (zero delay, M = cohort) one
    # buffer merge per tick — nothing else
    assert counts["async"]["client_update"] == 3
    assert counts["async"]["buffer_merge"] == 3
    assert counts["async"].get("round_step", 0) == 0


def test_bench_quick_cli_lines(monkeypatch):
    """--quick CSV formatting (quick_check stubbed — no compile cost)."""
    import benchmarks.bench_fedround as B

    monkeypatch.setattr(B, "quick_check", lambda: {
        "sync": {"round_step": 3, "population_eval": 1}})
    lines = B.main(["--quick"])
    assert "fedround/dispatch/sync/round_step,0.0,3" in lines
    assert "fedround/dispatch/sync/population_eval,0.0,1" in lines


def test_bench_quick_robust_cli_lines(monkeypatch):
    """--quick-robust CSV formatting (quick_robust_check stubbed — the real
    fault-mode asserts run in tests/test_faults.py and the CI bench step)."""
    import benchmarks.bench_fedround as B

    monkeypatch.setattr(B, "quick_robust_check", lambda: {
        "fedilora": {"round_step": 3},
        "fedilora_trimmed": {"round_step": 3},
        "async": {"client_update": 2, "buffer_merge": 2}})
    lines = B.main(["--quick-robust"])
    assert "fedround/dispatch/fedilora/round_step,0.0,3" in lines
    assert "fedround/dispatch/fedilora_trimmed/round_step,0.0,3" in lines
    assert "fedround/dispatch/async/client_update,0.0,2" in lines


def test_bench_quick_telemetry_cli_lines(monkeypatch):
    """--quick-telemetry CSV formatting (quick_telemetry_check stubbed —
    the real invariants run in tests/test_telemetry.py and the CI step)."""
    import benchmarks.bench_fedround as B

    monkeypatch.setattr(B, "quick_telemetry_check", lambda: {
        "disabled": {"round_step": 3, "page_in": 3},
        "enabled": {"round_step": 3, "page_in": 3},
        "spans": {"round": 3, "round_step": 3, "page_in": 3}})
    lines = B.main(["--quick-telemetry"])
    assert "fedround/telemetry/disabled/round_step,0.0,3" in lines
    assert "fedround/telemetry/enabled/page_in,0.0,3" in lines
    assert "fedround/telemetry/spans/round,0.0,3" in lines


@pytest.mark.slow
def test_bench_serving_quick_dispatch_counts():
    """Serving loop dispatch accounting: exactly one serve_step per decode
    step, one admit per request, paging + fetches bounded, continuous
    batching never needs more steps than static — and chunked prefill
    admits a P-position prompt in exactly ⌈P/chunk⌉ serve_prefill
    dispatches while serve_step stops walking prompt positions."""
    from benchmarks.bench_serving import N_REQUESTS, quick_check

    counts = quick_check()
    for mode in ("continuous", "static"):
        rec = counts[mode]
        assert rec["requests"] == N_REQUESTS
        assert rec["dispatch"]["serve_step"] == rec["steps"]
        assert rec["dispatch"]["serve_admit"] == N_REQUESTS
        assert rec["dispatch"]["fetch"] <= N_REQUESTS
        assert set(rec["dispatch"]) <= {"serve_step", "serve_admit",
                                        "adapter_load", "fetch"}
    assert counts["continuous"]["steps"] < counts["static"]["steps"]

    pre = counts["prefill"]
    assert pre["requests"] == N_REQUESTS
    # admission dispatches: max ⌈P/chunk⌉ per burst, exactly — and shared
    # bursts STRICTLY beat per-request Σ ⌈P/chunk⌉ (the first step admits
    # both slots together)
    per_prompt = -(-pre["prompt_fill_positions"] // pre["chunk"])
    assert pre["per_request_serve_prefill"] == N_REQUESTS * per_prompt
    assert pre["dispatch"]["serve_prefill"] == pre["expected_serve_prefill"]
    assert pre["dispatch"]["serve_prefill"] < pre["per_request_serve_prefill"]
    assert pre["bursts"] < N_REQUESTS          # >=1 multi-admission burst
    assert pre["dispatch"]["serve_step"] == pre["steps"]
    # serve_step no longer advances through prompt positions: every decode
    # step emits a token, so the same workload needs strictly fewer steps
    assert pre["steps"] < pre["streamed_steps"]
    assert set(pre["dispatch"]) <= {"serve_step", "serve_prefill",
                                    "serve_admit", "adapter_load", "fetch"}


def test_bench_serving_quick_cli_lines(monkeypatch):
    """--quick CSV formatting (quick_check stubbed — no compile cost)."""
    import benchmarks.bench_serving as B

    monkeypatch.setattr(B, "quick_check", lambda: {
        "continuous": {"steps": 5, "requests": 2,
                       "dispatch": {"serve_step": 5, "serve_admit": 2}}})
    lines = B.main(["--quick"])
    assert "serving/dispatch/continuous/steps,0.0,5" in lines
    assert "serving/dispatch/continuous/serve_step,0.0,5" in lines
    assert "serving/dispatch/continuous/serve_admit,0.0,2" in lines


def test_bench_serving_quick_prefill_cli_lines(monkeypatch):
    """--quick-prefill CSV formatting (stubbed — no compile cost)."""
    import benchmarks.bench_serving as B

    monkeypatch.setattr(B, "quick_prefill_check", lambda: {
        "prefill": {"steps": 4, "requests": 2, "chunk": 4,
                    "prompt_fill_positions": 15,
                    "expected_serve_prefill": 8,
                    "per_request_serve_prefill": 8, "bursts": 2,
                    "dispatch": {"serve_step": 4, "serve_prefill": 8}}})
    lines = B.main(["--quick-prefill"])
    assert "serving/dispatch/prefill/steps,0.0,4" in lines
    assert "serving/dispatch/prefill/serve_prefill,0.0,8" in lines
    assert "serving/dispatch/prefill/expected_serve_prefill,0.0,8" in lines


@pytest.mark.slow
def test_bench_serving_quick_slo_invariants():
    """SLO-scheduler CI invariants: quick_slo_check raises on violation;
    here we additionally pin the headline numbers so a silent relaxation
    of the checks themselves would show up."""
    from benchmarks.bench_serving import quick_slo_check

    counts = quick_slo_check()
    # shed burst: 8 arrivals, 2 slots, queue_limit=0 → exactly 6 shed
    assert counts["shed"]["shed"] == 6
    assert counts["shed"]["dispatch"]["serve_admit"] == 2
    # cancellation: all 4 timed out, zero completion fetches
    assert counts["cancel"]["timeouts"] == 4
    assert counts["cancel"]["dispatch"].get("fetch", 0) == 0
    # fault containment: clean/poisoned step parity was asserted inside
    assert counts["fault"]["faulted"] == 1
    assert counts["fault"]["unaffected"] == 2


def test_bench_serving_quick_slo_cli_lines(monkeypatch):
    """--quick-slo CSV formatting (quick_slo_check stubbed — the real
    invariants run in the slow test above and the CI bench step)."""
    import benchmarks.bench_serving as B

    monkeypatch.setattr(B, "quick_slo_check", lambda: {
        "shed": {"steps": 20, "shed": 6, "admitted": 2,
                 "dispatch": {"serve_step": 20, "serve_admit": 2}},
        "cancel": {"steps": 1, "timeouts": 4,
                   "dispatch": {"serve_step": 1, "serve_admit": 2}},
        "fault": {"steps": 26, "faulted": 1, "unaffected": 2,
                  "dispatch": {"serve_step": 26}}})
    lines = B.main(["--quick-slo"])
    assert "serving/slo/shed/shed,0.0,6" in lines
    assert "serving/slo/shed/serve_admit,0.0,2" in lines
    assert "serving/slo/cancel/timeouts,0.0,4" in lines
    assert "serving/slo/fault/steps,0.0,26" in lines


def test_bench_serving_quick_telemetry_cli_lines(monkeypatch):
    """--quick-telemetry CSV formatting (quick_telemetry_check stubbed)."""
    import benchmarks.bench_serving as B

    monkeypatch.setattr(B, "quick_telemetry_check", lambda: {
        "disabled": {"serve_step": 9, "serve_admit": 4},
        "enabled": {"serve_step": 9, "serve_admit": 4},
        "spans": {"serve_step": 9, "serve_admit": 4, "admit_burst": 3}})
    lines = B.main(["--quick-telemetry"])
    assert "serving/telemetry/disabled/serve_step,0.0,9" in lines
    assert "serving/telemetry/enabled/serve_admit,0.0,4" in lines
    assert "serving/telemetry/spans/admit_burst,0.0,3" in lines


def test_trajectory_cross_pr_table(tmp_path):
    """run.py --trajectory surfaces every artifact's SHA-keyed history as
    table rows (missing artifacts and pre-metric runs degrade gracefully)."""
    import json

    from benchmarks.run import trajectory

    with open(tmp_path / "BENCH_serving.json", "w") as f:
        json.dump({"history": [
            {"sha": "abc1234", "timestamp": "2026-07-28T00:00:00+00:00",
             "results": {"continuous": {"tokens_per_sec": 100.0,
                                        "p50_latency_s": 0.01,
                                        "p50_ttft_s": 0.005},
                         "continuous_vs_static_throughput": 1.2,
                         "chunked_vs_streamed_ttft_p50": 3.0}},
            {"sha": None, "timestamp": None, "results": {}},
        ]}, f)
    text = "\n".join(trajectory(root=str(tmp_path)))
    assert "abc1234" in text
    assert "100.00" in text and "3.00" in text and "5.00" in text  # ms scale
    assert "(missing" in text            # fedround artifact absent here


def test_bench_history_appends(tmp_path, monkeypatch):
    """BENCH_fedround.json accumulates a history entry per run (and
    migrates a pre-history artifact) instead of overwriting."""
    import json

    from benchmarks.bench_fedround import _append_history

    path = str(tmp_path / "BENCH_fedround.json")
    with open(path, "w") as f:
        json.dump({"speedup": 1.5, "rounds": {}}, f)   # pre-history artifact
    doc1 = _append_history({"speedup": 1.7}, path)
    assert doc1["speedup"] == 1.7
    assert len(doc1["history"]) == 2                   # migrated + new
    assert doc1["history"][0]["results"]["speedup"] == 1.5
    doc2 = _append_history({"speedup": 1.9}, path)
    assert len(doc2["history"]) == 3
    assert doc2["history"][-1]["results"]["speedup"] == 1.9
    assert doc2["history"][-1]["timestamp"] is not None


def test_run_exits_nonzero_when_a_suite_fails(monkeypatch, capsys):
    """A failing suite is recorded as an ERROR row, the other suites still
    run, and the harness's exit code says something failed."""
    import benchmarks.run as R

    def boom():
        raise ValueError("boom")

    monkeypatch.setattr(R, "SUITES", {"bad": boom,
                                      "good": lambda: ["good/x,1.0,ok"]})
    monkeypatch.setattr(sys, "argv", ["run", "bad", "good"])
    import repro.launch.compile_cache as CC
    monkeypatch.setattr(CC, "use_compile_cache", lambda: None)
    assert R.main() == 1
    out = capsys.readouterr().out
    assert "bad/ERROR,0.0,ValueError: boom" in out
    assert "good/x,1.0,ok" in out
    monkeypatch.setattr(sys, "argv", ["run", "good"])
    assert R.main() == 0


@pytest.mark.parametrize("backend", ["tpu", "gpu"])
def test_measure_runs_in_process_on_tpu_only(monkeypatch, backend):
    """On a TPU the measurement runs in the process that holds the chip
    (no child process); a backend that is neither TPU nor the CPU test
    backend is an error, not a default."""
    import jax

    import benchmarks.common as C

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(C, "run_measurement_subprocess",
                        lambda *a, **k: pytest.fail("spawned a child"))
    if backend == "tpu":
        assert C.measure(lambda: {"ran": "here"}, "TAG:") == {"ran": "here"}
    else:
        with pytest.raises(RuntimeError, match="gpu"):
            C.measure(lambda: {}, "TAG:")


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without it
    the cache goes to the checkout's fixed .jax_cache/.  Nothing compiles
    between setting and restoring the option, so nothing is written."""
    import jax

    import repro.launch.compile_cache as CC

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert CC.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = CC.use_compile_cache()
        assert path == os.path.join(CC.CHECKOUT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert os.path.isfile(os.path.join(CC.CHECKOUT, "chip_smoke.py"))
