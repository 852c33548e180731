"""The benchmark's CPU tests: nothing here names a device metric.  The
program is imported from ``src/`` and the benchmark as the ``chipbench``
package of the checkout."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


import jax  # noqa: E402
import pytest  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """The CPU runs here compile tiny programs: keep them out of the
    checkout's persistent compilation cache."""
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """A checkout root whose BENCHMARK.json holds the tiny cells of
    ``data/BENCHMARK.json`` and the real file's metrics: each tiny cell
    reports what the real cell it ``stands_for`` reports."""
    from chipbench import harness

    real, tiny = harness.load_bench(), harness.load_bench(DATA)
    of = {c["stands_for"]: c["name"] for c in tiny["workloads"]}

    def remap(m):
        if "workloads" not in m:
            return m
        return dict(m, workloads=[of[w] for w in m["workloads"] if w in of])

    bench = dict(real, configs=tiny["configs"], workloads=tiny["workloads"],
                 end_to_end=[remap(m) for m in real["end_to_end"]],
                 per_layer=[remap(m) for m in real["per_layer"]])
    root = tmp_path_factory.mktemp("tiny")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)
