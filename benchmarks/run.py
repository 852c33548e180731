"""Benchmark harness entry point — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV.  Usage:

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run table1 fig5
  PYTHONPATH=src python -m benchmarks.run --trajectory   # cross-PR table

``--trajectory`` aggregates the SHA-keyed ``history`` lists that
``BENCH_fedround.json`` and ``BENCH_serving.json`` accumulate (one entry
per benchmark run, appended by ``benchmarks.common.append_history``) into
one printed cross-PR perf table — the repo's perf story over time.
"""

from __future__ import annotations

import json
import os
import sys
import time

from benchmarks import (bench_fedround, bench_fig1, bench_fig4, bench_fig5,
                        bench_fig6, bench_kernels, bench_serving,
                        bench_table1, bench_table2, bench_table3,
                        bench_table4, bench_table5, roofline)

SUITES = {
    "fedround": bench_fedround.main,
    "serving": bench_serving.main,
    "table1": bench_table1.main,
    "table2": bench_table2.main,
    "table3": bench_table3.main,
    "table4": bench_table4.main,
    "table5": bench_table5.main,
    "fig1": bench_fig1.main,
    "fig4": bench_fig4.main,
    "fig5": bench_fig5.main,
    "fig6": bench_fig6.main,
    "kernels": bench_kernels.main,
    "roofline": roofline.main,
}

# (column header, dotted path into a history entry's ``results``, scale)
TRAJECTORY_METRICS = {
    "BENCH_fedround.json": [
        ("fused_vs_seq", "speedup", 1.0),
        ("pipeline", "rounds.8.pipeline_speedup_vs_blocking", 1.0),
        ("cached_decode", "decode.speedup", 1.0),
        ("eval_sweep", "eval_sweep_s.speedup", 1.0),
        ("async_rps", "async.async_rounds_per_sec", 1.0),
    ],
    "BENCH_serving.json": [
        ("tok_per_s", "continuous.tokens_per_sec", 1.0),
        ("p50_lat_ms", "continuous.p50_latency_s", 1e3),
        ("p50_ttft_ms", "continuous.p50_ttft_s", 1e3),
        ("cont_vs_static", "continuous_vs_static_throughput", 1.0),
        ("ttft_speedup", "chunked_vs_streamed_ttft_p50", 1.0),
    ],
}


def _dig(tree, path: str):
    for part in path.split("."):
        if not isinstance(tree, dict) or part not in tree:
            return None
        tree = tree[part]
    return tree


def trajectory(root: str | None = None) -> list[str]:
    """One cross-PR perf table from both artifacts' ``history`` lists:
    a row per recorded run (git SHA + timestamp), a column per headline
    metric; runs predating a metric show ``-``."""
    root = root or os.path.join(os.path.dirname(__file__), "..")
    lines = ["== cross-PR perf trajectory =="]
    for fname, metrics in TRAJECTORY_METRICS.items():
        path = os.path.join(root, fname)
        lines.append(fname)
        if not os.path.exists(path):
            lines.append("  (missing — run the benchmark to create it)")
            continue
        with open(path) as f:
            history = json.load(f).get("history", [])
        if not history:
            lines.append("  (no history recorded)")
            continue
        widths = [max(len(h), 8) for h, _, _ in metrics]
        header = "  " + "sha".ljust(9) + "timestamp".ljust(21) + "  ".join(
            h.rjust(w) for (h, _, _), w in zip(metrics, widths))
        lines.append(header)
        for entry in history:
            sha = (entry.get("sha") or "-")[:8]
            ts = (entry.get("timestamp") or "-")[:19]
            cells = []
            for (_, mpath, scale), w in zip(metrics, widths):
                v = _dig(entry.get("results", {}), mpath)
                cells.append(("-" if v is None else
                              f"{float(v) * scale:.2f}").rjust(w))
            lines.append("  " + sha.ljust(9) + ts.ljust(21)
                         + "  ".join(cells))
    return lines


def main() -> int:
    """Run the named suites (all by default); returns the exit code — 1 if
    any suite raised.  A failing suite is recorded as an ``ERROR`` row and
    the remaining suites still run."""
    args = sys.argv[1:]
    if "--trajectory" in args:
        print("\n".join(trajectory()))
        return 0
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    wanted = args or list(SUITES)
    failed = []
    print("name,us_per_call,derived")
    for name in wanted:
        t0 = time.perf_counter()
        try:
            for line in SUITES[name]():
                print(line, flush=True)
        except Exception as e:  # keep the harness going; record the failure
            failed.append(name)
            print(f"{name}/ERROR,0.0,{type(e).__name__}: {e}", flush=True)
        print(f"{name}/_suite_wall,{(time.perf_counter()-t0)*1e6:.0f},"
              f"{'failed' if name in failed else 'done'}", flush=True)
    if failed:
        print(f"failed suites: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
