"""The per-layer readers that read the program's own scopes and spans: on
hand-built traces with hand-worked answers, and on the recorded round trace
(``data/trace_small.json.gz``), which predates the scopes."""

import os

import pytest

from chipbench import harness
from chipbench import trace_reduce as TR

DATA = os.path.join(os.path.dirname(__file__), "data")

# device ops [name, start, dur, module, hlo op]; host spans [name, start, dur]
SERVE = {
    "devices": {"0": {"ops": [
        ["grouped_lora_matmul_pallas.1", 0, 30, "jit_prefill_step",
         "custom-call"],
        ["fusion.2", 30, 20, "jit_prefill_step", "fusion"],
        ["fusion.3", 60, 10, "jit_serve_step", "fusion"],
        ["fusion.4", 90, 5, "jit_serve_step", "fusion"]], "modules": []}},
    # fetch spans: 45-65 (idle 50-60), a nested one inside it counted
    # once, 80-110 (idle 80-90 and 95-100 in the window), one after it
    "host": [["serve_step", 0, 100], ["fetch", 45, 20], ["fetch", 50, 5],
             ["fetch", 80, 30], ["fetch", 120, 10], ["serve_prefill", 70, 8]],
    "scopes": {
        "jit_prefill_step:grouped_lora_matmul_pallas.1":
            "jit(prefill_step)/lora_site/jit(grouped_lora_matmul_pallas)/"
            "grouped_lora_matmul_pallas/pallas_call",
        "jit_prefill_step:fusion.2": "jit(prefill_step)/attention/dot_general",
        "jit_serve_step:fusion.3": "jit(serve_step)/while/body/lora_site/dot_general"},
}

FEDROUND = {
    "devices": {
        "0": {"ops": [
            ["fusion.1", 0, 40, "jit_round_step", "fusion"],
            ["fusion.2", 40, 10, "jit_round_step", "fusion"],
            ["dim_agg_pallas.3", 50, 4, "jit_round_step", "custom-call"],
            ["fusion.4", 54, 6, "jit_round_step", "fusion"],
            ["fusion.1", 70, 20, "jit_round_step", "fusion"],
            ["dim_agg_pallas.3", 90, 4, "jit_round_step", "custom-call"]],
            "modules": []},
        "1": {"ops": [
            ["fusion.1", 0, 40, "jit_round_step", "fusion"],
            ["dim_agg_pallas.3", 50, 4, "jit_round_step", "custom-call"]],
            "modules": []}},
    # idle on device 0 under metrics_fetch: 60-70 and 94-100
    "host": [["round", 0, 100], ["metrics_fetch", 55, 20],
             ["metrics_fetch", 92, 20]],
    "scopes": {
        "jit_round_step:fusion.1": "jit(round_step)/fedround.local_train/"
                                   "jvp(unembed_loss)/dot_general",
        "jit_round_step:fusion.2": "jit(round_step)/fedround.local_train/"
                                   "transpose(jvp(unembed_loss))/dot_general",
        "jit_round_step:dim_agg_pallas.3":
            "jit(round_step)/fedround.aggregate/jit(dim_agg_pallas)/"
            "dim_agg_pallas/pallas_call",
        "jit_round_step:fusion.4": "jit(round_step)/fedround.scatter/scatter"},
}


def _read(metric, trace, info, chips=1, window=(0, 100)):
    red = TR.Reduced(trace, *window, devices=chips)
    return harness.load_reader(metric)({"trace": red, "info": info,
                                        "chips": chips})


@pytest.mark.parametrize("metric,want", [
    ("lora_site_share.serve", 100.0 * 40 / 65),    # (30 + 10) of 65 busy
    ("fetch_idle.serve", 25.0),                     # 10 + 10 + 5 of 100
])
def test_serve_readers_hand_worked(metric, want):
    assert _read(metric, SERVE, {"kind": "serve"}) == pytest.approx(want)
    assert _read(metric, SERVE, {"kind": "fedround", "rounds": 2}) is None


@pytest.mark.parametrize("metric,want_ns", [
    ("unembed_loss_ms.fedround", (40 + 10 + 20 + 40) / 2 / 2),   # ÷ chips, rounds
    ("aggregate_ms.fedround", (4 + 4 + 4) / 2 / 2),
    ("fetch_idle.fedround", (10 + 6) / 2),                    # device 0 only
])
def test_fedround_readers_hand_worked(metric, want_ns):
    info = {"kind": "fedround", "rounds": 2}
    assert _read(metric, FEDROUND, info, chips=2) == \
        pytest.approx(want_ns * 1e-6)                         # ns -> ms
    assert _read(metric, FEDROUND, {"kind": "serve"}, chips=2) is None
    assert _read(metric, FEDROUND, dict(info, rounds=0), chips=2) is None


def test_span_readers_clip_to_the_window():
    """A window of 40-90: the fetch spans' idle there is 50-60 and 80-90."""
    assert _read("fetch_idle.serve", SERVE, {"kind": "serve"},
                 window=(40, 90)) == pytest.approx(100.0 * 20 / 50)


@pytest.mark.parametrize("metric,kind", [
    ("lora_site_share.serve", "serve"),
    ("fetch_idle.serve", "serve"),
    ("unembed_loss_ms.fedround", "fedround"),
    ("aggregate_ms.fedround", "fedround"),
])
def test_readers_silent_without_their_scope_or_span(metric, kind):
    """The recorded round trace carries no scope and no ``fetch`` span."""
    tr = TR.load(os.path.join(DATA, "trace_small.json.gz"))
    red = TR.Reduced(tr, *tr["window"])
    info = {"kind": kind, "rounds": 2}
    assert harness.load_reader(metric)(
        {"trace": red, "info": info, "chips": 1}) is None


def test_metrics_fetch_idle_on_the_recorded_trace():
    """The recorded round trace does carry ``metrics_fetch`` spans: the idle
    under them is part of the window's idle, and at least what the gap
    midpoints attribute to the host's value fetch inside them."""
    tr = TR.load(os.path.join(DATA, "trace_small.json.gz"))
    red = TR.Reduced(tr, *tr["window"])
    ms = harness.load_reader("fetch_idle.fedround")(
        {"trace": red, "info": {"kind": "fedround", "rounds": 2},
         "chips": 1})
    idle = red.window_s - red.busy_s("0")
    by_mid = sum(v for n, v in red.idle_gaps(top=1000)
                 if n.startswith("np.asarray"))
    assert by_mid <= 2 * ms * 1e-3 <= idle
