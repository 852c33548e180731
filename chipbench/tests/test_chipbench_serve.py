"""A serving run without the chip, at a tiny float32 size: sound, it is
correct and reports its end-to-end metrics; with a served token altered
where the engine produces it ``correct`` comes out false; and the
control, the reference in fp8, fails the limit."""

import os
import time

from chipbench import control, harness

DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "serve.tiny.zipf"
SEED = 2 ** 31 + 202


def run(root):
    return harness.run_cell(CELL, SEED, 2.0, False, t_start=time.perf_counter(),
                            require_chip=False, root=root, here=DATA)


def test_sound_run_is_correct(tiny_root):
    out = run(tiny_root)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"out_tokens_per_s", "setup_s"}
    assert out["metrics"]["out_tokens_per_s"]["value"] > 0
    assert out["checks"]["compiles_in_window"]["value"] == 0


def test_altered_token(monkeypatch, tiny_root):
    from repro.serving.engine import ServingEngine

    real = ServingEngine._retire_finished

    def altered(self):
        out = real(self)
        for rec in out:
            rec["tokens"] = rec["tokens"].copy()
            rec["tokens"][-1] = (rec["tokens"][-1] + 1) % self.cfg.vocab_size
        return out

    monkeypatch.setattr(ServingEngine, "_retire_finished", altered)
    out = run(tiny_root)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > 1e-3


def test_control_fails_the_limit(tiny_root):
    limit = harness.load_limits(CELL, DATA)["logit_gap"]
    (row,) = control.readings(CELL, [SEED], 2.0, root=tiny_root, here=DATA,
                              require_chip=False)
    assert row["program"]["logit_gap"] <= limit
    assert row["control"]["logit_gap"] > limit
