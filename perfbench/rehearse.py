#!/usr/bin/env python3
"""CPU rehearsal: every cell of ``BENCHMARK.json`` end to end at its
tiny presets, Pallas kernels interpreted, four-chip cells on four
virtual devices. The first cells' presets are in
``perfbench/rehearsal.json`` by configuration and traffic; a cell added
since brings a file ``perfbench/rehearsal.<any name>.json`` of its own
that names it (``"workload"``, ``"config"``, ``"traffic"``).

    python3 perfbench/rehearse.py [--workload <name>] [--seconds <s>]

It finds wrong paths, arguments and control flow before any chip time
is spent. It prints counts only (steps, requests, tokens, checks) and
never a device metric: a time taken here says how fast the CPU backend
is. The real entry, ``perfbench/run.py``, refuses to run here.
"""
import glob
import os
import sys
import time

_T_START = time.perf_counter()

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
for _k in ("FLASH", "NORM", "CE", "DECODE", "MOE", "SCAN"):
    os.environ.setdefault(f"MXNET_TPU_{_k}_INTERPRET", "1")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def merge(base, over):
    """``over`` laid over ``base``, nested objects merged key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def tiny_cell(name, benchmark=None):
    """The cell ``name`` with the tiny presets laid over its files."""
    from perfbench import harness

    cell = harness.Cell(name, benchmark)
    own = next((p for p in map(harness.load_json, sorted(glob.glob(
        os.path.join(harness.HERE, "rehearsal.*.json"))))
        if p.get("workload") == name), None)
    if own:
        config, traffic = own["config"], own["traffic"]
    else:
        tiny = harness.load_json(harness.HERE, "rehearsal.json")
        config = tiny["configs"][cell.config_name]
        traffic = tiny["traffic"][cell.traffic_name]
    cell.config = merge(cell.config, config)
    cell.traffic = merge(cell.traffic, traffic)
    return cell


def run_tiny(cell, seed, seconds):
    """One rehearsal run of a (tiny) cell on the CPU's virtual devices;
    returns the result object (no device metric in it)."""
    import jax

    from perfbench import harness

    return harness.run_cell(cell, seed, seconds, False,
                            time.perf_counter(),
                            jax.devices()[:cell.chips], on_chip=False)


def main():
    import argparse

    import jax
    jax.config.update("jax_platforms", "cpu")

    from perfbench import harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 12345)
    args = ap.parse_args()

    bm = harness.load_json(harness.ROOT, "BENCHMARK.json")
    names = [args.workload] if args.workload else \
        [w["name"] for w in bm["workloads"]]
    failed = []
    for name in names:
        result = run_tiny(tiny_cell(name, bm), args.seed, args.seconds)
        print(f"[rehearsal] {name}: correct={result['correct']} "
              f"attempted={result['attempted']} "
              f"failed={result['failed']} (counts only; no device "
              "metric comes from a CPU)", flush=True)
        if not result["correct"]:
            failed.append(name)
    if failed:
        raise SystemExit(f"rehearsal failed: {failed}")


if __name__ == "__main__":
    main()
