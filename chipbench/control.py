"""Readings that set a cell's limits, on the chip at the cell's own size.

    python3 chipbench/control.py --workload <cell> --seeds <n> [<n> ...] \
        [--seconds <s>] [--controls <k>]

For each seed, in one process, it prints one JSON line with the cell's
compared numbers for:

* ``program`` — the timed path against the float32 reference (the lower
  reading: sound runs of the program);
* ``control`` — the reference computed in fp8 (e4m3, per-tensor scales)
  in the program's place, the precision below the configuration's
  bfloat16 (the upper reading);
* federated cells also ``half_batch`` — the reference with each local step
  taking the mean over half of its batch (a planted fault).

``--controls k`` reads the control and the fault on the first ``k`` seeds
only, the program on all of them.

Federated cells need no measured window: set-up drives the checked rounds.
Serving cells serve a ``--seconds`` window at the cell's load, and the
control reads, at each position of the same prompts and served tokens,
the gap of the token the fp8 reference ranks first.  The benchmark's own
runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(workload: str, seeds, seconds: float, *, root: str = ROOT,
             here=None, require_chip: bool = True, controls=None):
    """Yield one row of readings per seed (see the module docstring)."""
    from chipbench import harness, model, traffic

    here = here or harness.HERE
    cell = harness.find_cell(harness.load_bench(root), workload)
    if require_chip:
        harness.device_check(cell["chips"])
    harness.use_cache()
    raw = model.load_config(cell["config"], here)
    mix = traffic.load_traffic(cell["traffic"], here)
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        with_control = controls is None or i < controls
        run = harness.driver(mix["kind"]).Run(raw, mix, seed, cell["chips"])
        run.setup()
        row = {"workload": workload, "seed": seed}
        if mix["kind"] == "fedround":
            prog = run.program()
            run.free()
            gc.collect()
            ref = run.reference()
            row["program"] = run.compare(prog, ref)
            if with_control:
                row["control"] = run.compare(run.reference("fp8"), ref)
                row["half_batch"] = run.compare(run.reference(half=True),
                                                ref)
            row["loss"] = {"program": prog["loss"], "reference": ref["loss"]}
            row["edited"] = {"program": prog["edited"],
                             "reference": ref["edited"]}
        else:
            res = run.window(seconds)
            run.free()
            gc.collect()
            row["served"] = len(run.served)
            row["attempted"] = res["attempted"]
            row["program"] = run.check()
            if with_control:
                row["control"] = run.check("fp8")
        row["seconds"] = time.perf_counter() - t0
        yield row


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--controls", type=int, default=None)
    args = ap.parse_args()
    sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
    for row in readings(args.workload, args.seeds, args.seconds,
                        controls=args.controls):
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
