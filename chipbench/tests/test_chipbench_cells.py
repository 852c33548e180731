"""Every cell of BENCHMARK.json loads by name, and each traffic generator
is seeded: the same seed gives the same inputs, and every seed the same
sizes."""

import json
import os
import re

import numpy as np
import pytest

from chipbench import harness, model, traffic

BENCH = harness.load_bench()
CELLS = [c["name"] for c in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", CELLS)
def test_cell_loads(workload):
    cell = harness.find_cell(BENCH, workload)
    raw = model.load_config(cell["config"])
    model.model_config(raw, cell["config"])          # the program's config
    mix = traffic.load_traffic(cell["traffic"])
    assert hasattr(harness.driver(mix["kind"]), "Run")
    limits = harness.load_limits(workload)
    assert all(v > 0 for v in limits.values())
    reported = [m["name"] for m in BENCH["end_to_end"]
                if workload in m.get("workloads", [workload])]
    assert "setup_s" in reported and len(reported) >= 2
    layer = [m for m in BENCH["per_layer"]
             if workload in m.get("workloads", [workload])]
    assert layer and all(m["moves"] in reported for m in layer)


def test_benchmark_file():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(names)) == len(names)
    for m in BENCH["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for c in BENCH["configs"]:
        assert os.path.exists(os.path.join(harness.ROOT, c["file"]))
    for n in names + CELLS + [c["name"] for c in BENCH["configs"]]:
        assert NAME.match(n), n
    assert all(0.01 <= m["bound"] <= 0.25 for m in BENCH["end_to_end"])
    for entry in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"]), m
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(harness.HERE, "traffic"))))
def test_traffic_seeded(name):
    mix = traffic.load_traffic(name)
    big = 2 ** 31 + 12345
    if mix["kind"] == "fedround":
        a = traffic.fedround_shards(mix, 1000, big)
        b = traffic.fedround_shards(mix, 1000, big)
        c = traffic.fedround_shards(mix, 1000, big + 1)
        for x, y in zip(a, b):
            for k in x:
                np.testing.assert_array_equal(x[k], y[k])
        assert sorted(s["tokens"].shape[0] for s in a) == \
            sorted(s["tokens"].shape[0] for s in c)
        assert not np.array_equal(a[0]["tokens"][:8], c[0]["tokens"][:8])
        for s in a:
            assert s["tokens"].shape[1] == mix["seq_len"]
            assert (s["loss_mask"].sum(1) > 0).all()
    else:
        a = traffic.serve_requests(mix, 1000, big, 5.0)
        b = traffic.serve_requests(mix, 1000, big, 5.0)
        c = traffic.serve_requests(mix, 1000, big + 1, 5.0)
        backlog = mix.get("backlog", 0)
        assert len(a) == len(c) == backlog + round(mix["rate"] * 5.0)
        assert all(r["due"] == 0.0 for r in a[:backlog + 1])
        assert a[backlog + 1]["due"] > 0.0
        for x, y in zip(a, b):
            assert x["due"] == y["due"] and x["tenant"] == y["tenant"]
            np.testing.assert_array_equal(x["prompt"], y["prompt"])
        # sizes and arrivals: one schedule for every seed; the seed draws
        # the tenants and the tokens
        for size in (lambda r: r["gen_len"], lambda r: len(r["prompt"]),
                     lambda r: r["due"]):
            assert list(map(size, a)) == list(map(size, c))
        assert [r["tenant"] for r in a] != [r["tenant"] for r in c]
        assert [r["due"] for r in a] == sorted(r["due"] for r in a)
        assert not all(np.array_equal(x["prompt"], y["prompt"])
                       for x, y in zip(a, c))
        assert all(0 <= r["due"] < 5.0 for r in a)
        assert all(mix["prompt"]["min"] <= len(r["prompt"])
                   <= mix["prompt"]["max"] for r in a)
        hist = traffic.bank_history(mix, big)
        assert hist == traffic.bank_history(mix, big)
        assert len(set(hist)) == mix["bank_slots"]
        assert set(hist) <= set(range(mix["tenants"]))


def test_stratified_blocks():
    """Every full block of STRATA consecutive values holds one value of
    each of STRATA equal bands, whatever the seed."""
    vals = np.arange(100)
    band = {int(v): i for i, b in enumerate(np.array_split(vals,
                                                            traffic.STRATA))
            for v in b}
    k = traffic.STRATA
    orders = [traffic._stratified(vals, np.random.default_rng(s))
              for s in (1, 2 ** 31 + 5)]
    assert not np.array_equal(*orders)
    for out in orders:
        assert sorted(out) == list(vals)
        for i in range(0, len(vals) - k + 1, k):
            assert sorted(band[int(v)] for v in out[i:i + k]) == list(range(k))


@pytest.mark.parametrize("step", [1, 3, 5])
def test_interleaved_blocks(step):
    """The serving sizes' fixed order: a permutation of the values in which
    every full block of STRATA holds one value of each band, and every
    prefix of whole blocks spans each band evenly."""
    vals = np.arange(203)
    bands = np.array_split(vals, traffic.STRATA)
    band = {int(v): i for i, b in enumerate(bands) for v in b}
    k = traffic.STRATA
    out = traffic._interleaved(vals, step)
    assert sorted(out) == list(vals)
    for i in range(0, len(vals) - k + 1, k):
        assert sorted(band[int(v)] for v in out[i:i + k]) == list(range(k))
    # the first four blocks take members from across each band, not its
    # low end: each band's first four picks span over half of it
    for j, b in enumerate(bands):
        picks = [int(v) for v in out[:4 * k] if band[int(v)] == j]
        assert max(picks) - min(picks) > len(b) // 2
    # differing steps pair the bands differently within a block
    other = traffic._interleaved(vals, step + 2)
    assert [band[int(v)] for v in out[:k]] != [band[int(v)] for v in other[:k]]
