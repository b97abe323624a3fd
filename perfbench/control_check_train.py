#!/usr/bin/env python3
"""The controls of a TRAINING cell's output check, put in the program's
place at the cell's own sizes: where the readings behind a
``train_job`` cell's ``limits`` come from (PERF.md, section 2).
``perfbench/control_check.py`` does the same for a serving cell.

    python3 perfbench/control_check_train.py --workload <name> \
        --seed <n> [--controls fp8,no_window]

The cell's reference follows the cell's first ``check_steps`` steps
from the seeded weights once as it is and once for each control
(``CONTROLS`` of ``perfbench/reference/<family>.py``: the reference
altered one way, ``follow(control=...)``), and each control's losses,
first gradient and parameters' change go through the comparison
``train_job`` makes of the program's, against the traffic file's
``limits``. Prints the checks of each control and, as the last line,
one JSON object ``{control: {"correct", "checks"}}``. Exits 1 if a
control came out ``correct``: the limits are then too wide to tell it
from the program. Needs the cell's chips, like ``run.py``; on the CPU,
at the tiny presets, ``tests/test_mellum_training.py`` drives ``run``.
"""
import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def run(cell, seed, device, controls=None):
    """{control: the checks and ``correct``}."""
    from perfbench import harness
    from perfbench.generators import train_job

    cfg, job = cell.config, cell.traffic
    ref = importlib.import_module("perfbench.reference." + cfg["family"])
    rows = job["per_chip_batch"] * job.get("plan", {}).get("dp", 1)
    batches = ref.make_batches(cfg, job, seed, job["check_steps"], rows)
    w0 = ref.make_weights(cfg, seed, device)
    follow = lambda **kw: ref.follow(  # noqa: E731
        cfg, job, w0, batches, job["optimizer"],
        job["reference_block_rows"], **kw)
    want = follow()
    out = {}
    for control in controls or ref.CONTROLS:
        t0 = time.perf_counter()
        got = follow(control=control)
        checks = harness.Checks()
        train_job.compare_with_reference(checks, job["limits"], got, want)
        harness.say("control", name=control, seed=seed,
                    reference_s=round(time.perf_counter() - t0, 2),
                    losses=[round(v, 5) for v in got["losses"]])
        checks.print()
        out[control] = {"correct": checks.ok, "checks": checks.as_dict()}
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--controls", default="",
                    help="comma-separated; default: every one")
    args = ap.parse_args()

    from perfbench import harness

    cell = harness.Cell(args.workload)
    devices = harness.require_tpu(cell.chips)
    harness.enable_compile_cache()
    out = run(cell, args.seed, devices[0],
              [c for c in args.controls.split(",") if c] or None)
    print(json.dumps(out), flush=True)
    raise SystemExit(int(any(r["correct"] for r in out.values())))


if __name__ == "__main__":
    main()
