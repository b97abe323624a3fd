#!/usr/bin/env python3
"""The controls of a serving cell's output check, put in the program's
place at the cell's own sizes: where the readings behind a cell's
``limits`` come from (PERF.md, section 2).

    python3 perfbench/control_check.py --workload <name> --seed <n> \
        [--controls fp8,no_rope_term]

For each control of the cell's reference (``CONTROLS`` of
``perfbench/reference/<family>.py``: the reference altered one way),
the reference's ``served_token_gaps(control=...)`` runs on the shapes of
the sessions that finish in the cell's window (``schedule.closed_loop``:
their contexts and what they have left, tokens drawn from ``--seed``),
and its gaps go through the comparison ``serving.check_outputs`` makes
of a run's, against the traffic file's ``limits``. Prints the checks of
each control and, as the last line, one JSON object ``{control:
{"correct", "mean_gap", "widest_gap", ...}}``. Exits 1 if a control
came out ``correct``: the limits are then too wide to tell it from the
program. Needs the cell's chips, like ``run.py``; on the CPU, at the
tiny presets, ``tests/test_mla_serving.py`` drives ``run`` below.
"""
import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def finishing_sequences(cfg, traffic, seed):
    """``(context ids, ids of what is left)`` of each session the
    window finishes, at the lengths the cell's schedule gives them."""
    import numpy as np

    from perfbench import schedule

    rng = np.random.default_rng(seed)
    draw = lambda n: rng.integers(  # noqa: E731
        0, cfg["vocab_size"], n).astype(np.int32)
    return [(draw(s["context"]), draw(s["remaining"]))
            for s in schedule.closed_loop(traffic)["initial"][
                :traffic["finishing"]]]


def run(cell, seed, device, controls=None):
    """{control: the gaps' summary, the checks and ``correct``}."""
    import numpy as np

    from perfbench import harness

    cfg, traffic = cell.config, cell.traffic
    ref = importlib.import_module("perfbench.reference." + cfg["family"])
    seqs = finishing_sequences(cfg, traffic, seed)
    limits, out = traffic["limits"], {}
    for control in controls or ref.CONTROLS:
        t0 = time.perf_counter()
        flat = np.concatenate(ref.served_token_gaps(
            cfg, seed, seqs, device, control=control))
        checks = harness.Checks()
        # serving.check_outputs' three, on the control's gaps
        checks.at_least("checked_served_tokens", int(flat.size),
                        limits["min_checked_tokens"])
        checks.at_most("mean_served_logit_gap", float(flat.mean()),
                       limits["mean_logit_gap"])
        checks.at_most("widest_served_logit_gap", float(flat.max()),
                       limits["widest_logit_gap"])
        harness.say("control", name=control, seed=seed,
                    requests=len(seqs),
                    reference_s=round(time.perf_counter() - t0, 2))
        checks.print()
        out[control] = {"correct": checks.ok,
                        "mean_gap": float(flat.mean()),
                        "widest_gap": float(flat.max()),
                        "mismatch_share": float((flat > 0).mean()),
                        "served_tokens": int(flat.size),
                        "checks": checks.as_dict()}
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
