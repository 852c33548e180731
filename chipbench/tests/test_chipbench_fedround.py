"""A federated run without the chip, at a tiny float32 size: sound, it is
correct; with the timed path broken underneath (a round that returns its
state unchanged, a local step over half of its batch) ``correct`` comes
out false; and the control, the reference in fp8, fails a limit."""

import os
import time

import pytest

from chipbench import control, harness

DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "fedround.tiny.paper"
SEED = 2 ** 31 + 101


def run(root):
    return harness.run_cell(CELL, SEED, 1.0, False, t_start=time.perf_counter(),
                            require_chip=False, root=root, here=DATA)


def test_sound_run_is_correct(tiny_root):
    out = run(tiny_root)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"round_s", "setup_s"}
    assert list(out)[-1] == "checks"


def test_state_left_unchanged(monkeypatch, tiny_root):
    from repro.federated import runtime

    real = runtime.make_round_engine

    def engine(*a, **k):
        step = real(*a, **k)

        def stuck(base, stacked, glob, prev, *rest):
            out = step(base, stacked, glob, prev, *rest)
            return dict(out, global_lora=glob)
        return stuck

    monkeypatch.setattr(runtime, "make_round_engine", engine)
    out = run(tiny_root)
    assert not out["correct"]
    assert out["checks"]["change_norm"]["value"] > 0.5


def test_half_batch(monkeypatch, tiny_root):
    from repro.models import transformer as T

    real = T.loss_fn

    def half(cfg, params, lora, batch, *a, **k):
        n = batch["tokens"].shape[0] // 2
        return real(cfg, params, lora, {key: v[:n] for key, v in
                                        batch.items()}, *a, **k)

    monkeypatch.setattr(T, "loss_fn", half)
    assert not run(tiny_root)["correct"]


def test_control_fails_a_limit(tiny_root):
    limits = harness.load_limits(CELL, DATA)
    (row,) = control.readings(CELL, [SEED], 1.0, root=tiny_root, here=DATA,
                              require_chip=False)
    assert all(row["program"][k] <= limits[k] for k in limits)
    for bad in ("control", "half_batch"):
        assert any(row[bad][k] > limits[k] for k in limits), row[bad]
