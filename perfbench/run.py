#!/usr/bin/env python3
"""The benchmark's entry point.

    python3 perfbench/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on
and prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` with ``--trace 1``). With ``--trace 0`` the metrics are
the cell's end-to-end metrics, with ``--trace 1`` its per-layer ones.
Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result (the CPU rehearsal is
``perfbench/rehearse.py``).
"""
import time

_T_START = time.perf_counter()      # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from perfbench import harness

    cell = harness.Cell(args.workload)
    devices = harness.require_tpu(cell.chips)
    cache = harness.enable_compile_cache()
    harness.say("run", workload=cell.name, seed=args.seed,
                seconds=args.seconds, trace=args.trace,
                device=devices[0].device_kind, chips=len(devices),
                compile_cache=cache)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), _T_START, devices)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
