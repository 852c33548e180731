"""The reduction from a profiler trace to per-layer numbers: on a small
synthetic trace with hand-worked answers, and on a small trace recorded on
the chip and checked in (``data/trace_small.json.gz``)."""

import os

import pytest

from chipbench import trace_reduce as TR

DATA = os.path.join(os.path.dirname(__file__), "data")

# device ops [name, start, dur, module, hlo op]; host spans [name, start, dur]
SYNTH = {
    "devices": {
        "0": {"ops": [["fusion.1", 0, 40, "jit_f", "fusion"],
                      ["dim_agg_pallas.2", 30, 20, "jit_f", "custom-call"],
                      ["all-reduce.1", 60, 20, "jit_f", "all-reduce"],
                      ["fusion.3", 70, 5, "jit_f", "fusion"]],
              "modules": [["jit_f", 0, 80]]},
        "1": {"ops": [["fusion.1", 0, 100, "jit_f", "fusion"]],
              "modules": [["jit_f", 0, 100]]},
    },
    "host": [["round", 0, 100], ["metrics_fetch", 50, 15],
             ["$array.py _value", 52, 10], ["sample", 85, 10]],
    "scopes": {"jit_f:fusion.1": "jit(f)/fedround.local_train/dot_general"},
}


def test_union_and_gaps():
    assert TR.union_length([(0, 40), (30, 50), (60, 80), (70, 75)], 0, 100) == 70
    assert TR.union_length([(0, 40)], 10, 20) == 10
    assert TR.gaps([(0, 40), (30, 50), (60, 80)], 0, 100) == [(50, 60), (80, 100)]


def test_reduced_synthetic():
    red = TR.Reduced(SYNTH, 0, 100)
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s("0") == pytest.approx(70e-9)
    assert red.busy_s("1") == pytest.approx(100e-9)
    assert red.mean_busy_s() == pytest.approx(85e-9)
    assert red.seconds_where(lambda r, o: "local_train" in r.scope(o)) == \
        pytest.approx(140e-9)
    assert red.seconds_where(lambda r, o: o[0].startswith("dim_agg"), "0") \
        == pytest.approx(20e-9)
    # the all-reduce runs 60-80 with an add at 70-75: 15 of 20 exposed
    assert red.collective_s("0") == pytest.approx(20e-9)
    assert red.exposed_collective_s("0") == pytest.approx(15e-9)
    # gaps on device 0: 50-60 in metrics_fetch waiting on a value, 80-100
    # under sample at its midpoint 90
    assert red.idle_gaps("0") == [
        ["sample", pytest.approx(20e-9)],
        ["metrics_fetch / $array.py _value", pytest.approx(10e-9)]]
    assert red.module_runs(lambda m: m == "jit_f", "1") == [(0, 100)]
    assert red.top_ops(1)[0][0] == \
        "jit_f:fusion.1 fedround.local_train/dot_general"


def test_window_clips():
    red = TR.Reduced(SYNTH, 20, 60, devices=1)
    assert list(red.devices) == ["0"]
    assert red.busy_s("0") == pytest.approx(30e-9)      # 20-50
    assert red.top_ops(1) == [["jit_f:fusion.1 fedround.local_train/dot_general",
                               pytest.approx(20e-9)]]


def test_parse_hlo_text():
    assert TR.parse_op("%fusion.149 = (f32[2]{0}, f32[3]{0}) fusion(f32[2]{0} "
                       "%x), kind=kOutput") == ("fusion.149", "fusion")
    assert TR.parse_op("%all-reduce.7 = bf16[8]{0} all-reduce(bf16[8]{0} %y)")\
        == ("all-reduce.7", "all-reduce")
    text = ('HloModule jit_round_step, is_scheduled=true\n\n'
            '  %fusion.3 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, '
            'metadata={op_name="jit(round_step)/fedround.local_train/mul"}')
    assert TR.scope_map(text) == (
        {"jit_round_step:fusion.3": "jit(round_step)/fedround.local_train/mul"},
        {"jit_round_step:fusion.3": ["p"]})


def test_compact_device_drops_loops():
    dev = TR.compact_device(
        [("%while.1 = (s32[]) while((s32[]) %a)", 10, 50),
         ("%fusion.2 = f32[1]{0} fusion(f32[1]{0} %b)", 12, 10),
         ("%dim_agg_pallas.4 = f32[1]{0} custom-call(f32[1]{0} %c)", 70, 5)],
        [("jit_round_step(123)", 0, 100)])
    assert dev["ops"] == [["fusion.2", 12, 10, "jit_round_step", "fusion"],
                          ["dim_agg_pallas.4", 70, 5, "jit_round_step",
                           "custom-call"]]


def test_kernel_with_its_neighbours():
    trace = {"devices": {"0": {"ops": [
        ["pad.1", 0, 10, "jit_g", "pad"],
        ["dim_agg_pallas.2", 10, 5, "jit_g", "custom-call"],
        ["slice.3", 15, 4, "jit_g", "slice"],
        ["fusion.4", 19, 30, "jit_g", "fusion"]], "modules": []}},
        "host": [],
        "edges": {"jit_g:pad.1": ["param.0"],
                  "jit_g:dim_agg_pallas.2": ["pad.1", "w.5"],
                  "jit_g:slice.3": ["dim_agg_pallas.2"],
                  "jit_g:fusion.4": ["slice.3"]}}
    red = TR.Reduced(trace, 0, 100)
    assert red.kernel_seconds("dim_agg_pallas") == pytest.approx(19e-9)


def test_recorded_round_trace():
    """Two steady rounds of ``fedround.qwen2-0.5b.paper`` recorded on one
    TPU v5e (the compact form ``read_xplane`` writes)."""
    tr = TR.load(os.path.join(DATA, "trace_small.json.gz"))
    lo, hi = tr["window"]
    red = TR.Reduced(tr, lo, hi)
    runs = red.module_runs(lambda m: m == "jit_round_step")
    assert len(runs) == 2
    in_runs = sum(d for _, d in runs) * 1e-9
    busy = red.busy_s("0")
    # the round program keeps the chip busy; nothing runs outside programs
    assert 0.95 * in_runs <= busy <= in_runs + 1e-4
    assert all(o[3] for o in red._in["0"])
    kernels = [o for o in red._in["0"] if o[0].startswith("dim_agg_pallas")]
    assert len(kernels) == 8                 # A and Bᵀ of wq and wv, 2 rounds
    idle = sum(v for _, v in red.idle_gaps(top=1000))
    assert idle == pytest.approx(red.window_s - busy, rel=1e-6)
    assert all(name.startswith("jit_round_step:")
               for name, _ in red.top_ops())
