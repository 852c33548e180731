"""One run of one cell: set-up, the measured window, the per-layer readings
(traced runs), and the check against the plain reference.

Everything that belongs to one cell is found by name: the cell in
``BENCHMARK.json``, its configuration ``configs/<config>.json``, its mix
``traffic/<traffic>.json`` (whose ``kind`` names the module
``drivers/<kind>.py``), its limits ``limits/<cell>.json``, and each
per-layer metric's reader ``metrics/<metric>.py``.
"""

from __future__ import annotations

import collections
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

from chipbench import model, peaks, trace_reduce, traffic

HERE = model.HERE
ROOT = os.path.dirname(HERE)


def load_bench(root: str = ROOT) -> dict:
    return model.read_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have "
                   f"{[c['name'] for c in bench['workloads']]}")


def load_limits(workload: str, here: str = HERE) -> dict:
    return model.read_json(os.path.join(here, "limits", f"{workload}.json"))


def load_reader(metric: str, here: str = HERE):
    path = os.path.join(here, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def driver(kind: str):
    return importlib.import_module(f"chipbench.drivers.{kind}")


def device_check(chips: int) -> dict:
    """The platform must be a TPU with at least ``chips`` devices."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device platform={info['platform']} device_kind={info['kind']} "
          f"count={info['count']}", file=sys.stderr, flush=True)
    if info["platform"] != "tpu":
        raise SystemExit(f"chipbench: JAX found no TPU (backend "
                         f"{jax.default_backend()!r}); the benchmark runs on "
                         "the chip only")
    if info["count"] < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} TPU chips, JAX "
                         f"found {info['count']}")
    return info


class CompileClock:
    """Compilations (or persistent-cache loads), from JAX's own events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.events: list[tuple[str, float]] = []

        def listen(name, secs, fun_name=None, **_):
            if name == self.EVENT:
                self.events.append((str(fun_name), secs))

        jax.monitoring.register_event_duration_secs_listener(listen)


def use_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache at the fixed path
    ``<checkout>/.jax_cache``, every program in it however short its
    compile: the second run of a cell in a checkout compiles nothing."""
    import jax

    path = os.path.join(root, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def peak_bytes(chips: int) -> int:
    import jax

    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.devices()[:chips]]
    return int(max(vals))


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True, root: str = ROOT,
             here: str = HERE) -> dict:
    """One run; returns the result line's object.  ``require_chip=False``
    (tests only) skips the device check; ``root``/``here`` (tests only)
    point at another BENCHMARK.json and its cells' files."""
    import jax

    bench = load_bench(root)
    cell = find_cell(bench, workload)
    if require_chip:
        dev = device_check(cell["chips"])
    else:
        d0 = jax.devices()[0]
        dev = {"platform": d0.platform, "kind": d0.device_kind,
               "count": len(jax.devices())}
    use_cache()
    raw = model.load_config(cell["config"], here)
    mix = traffic.load_traffic(cell["traffic"], here)
    limits = load_limits(workload, here)
    run = driver(mix["kind"]).Run(raw, mix, seed, cell["chips"],
                                  traced=trace)
    clock = CompileClock()
    run.setup()
    setup_s = time.perf_counter() - t_start
    n0 = len(clock.events)
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # annotations only, no Python frames
        jax.profiler.start_trace(tdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        res = run.window(seconds)
    if trace:
        jax.profiler.stop_trace()
    in_window = clock.events[n0:]
    mem = peak_bytes(cell["chips"])
    info = run.layer_info()
    run.free()
    gc.collect()
    numbers = run.check()
    checks = collections.OrderedDict(
        (name, {"value": numbers[name], "limit": lim})
        for name, lim in limits.items())
    checks["compiles_in_window"] = {"value": len(in_window), "limit": 0}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"]}
    dev = dict(dev, memory_peak_bytes=mem)
    if trace:
        metrics, breakdown, busy, win = per_layer(
            bench, workload, info, tdir, cell["chips"], dev["kind"])
        shutil.rmtree(tdir, ignore_errors=True)
        out["metrics"] = metrics
        dev.update(busy_s=busy, window_s=win)
        out["breakdown"] = breakdown
    else:
        out["metrics"] = {k: {"value": v, "unit": u}
                          for k, (v, u) in res["metrics"].items()}
        out["metrics"]["setup_s"] = {"value": setup_s, "unit": "s"}
    out["device"] = dev
    if in_window:
        out["compiled_in_window"] = [n for n, _ in in_window]
    if "generator" in info:
        out["generator"] = info["generator"]
    diagnostics = dict(getattr(run, "diagnostics", {}))
    diagnostics.update({n: v for n, v in numbers.items() if n not in limits})
    if diagnostics:
        out["diagnostics"] = diagnostics
    out["checks"] = checks
    return out


def per_layer(bench: dict, workload: str, info: dict, tdir: str, chips: int,
              kind: str):
    """Reduce the traced window and ask each per-layer metric's reader."""
    import glob

    files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    tr = trace_reduce.read_xplane(max(files, key=os.path.getmtime),
                                  info.get("hlo_texts", ()))
    span = [h for h in tr["host"] if h[0] == "chipbench.window"]
    lo, hi = ((span[0][1], span[0][1] + span[0][2]) if span else
              (min(o[1] for d in tr["devices"].values() for o in d["ops"]),
               max(o[1] + o[2] for d in tr["devices"].values()
                   for o in d["ops"])))
    red = trace_reduce.Reduced(tr, lo, hi, devices=chips)
    ctx = {"trace": red, "info": info, "peaks": peaks.peaks(kind),
           "chips": chips, "workload": workload}
    metrics = {}
    for m in bench["per_layer"]:
        if workload not in m.get("workloads", [workload]):
            continue
        val = load_reader(m["name"])(ctx)
        if val is not None:
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    breakdown = {"device_ops": red.top_ops(), "idle_gaps": red.idle_gaps()}
    return metrics, breakdown, red.mean_busy_s(), red.window_s


def print_result(out: dict) -> None:
    """Compared numbers beside their limits, last on stderr; the result
    line last on stdout."""
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
