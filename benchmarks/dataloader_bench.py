"""Input-pipeline throughput: images/sec through gluon.data.DataLoader
(decode-free synthetic CIFAR-like records, full augmentation stack,
C++ host-engine prefetch workers). Reference analogue: the fork's
ImageRecordIter tuning runs — the input pipeline must outrun the
accelerator or everything else is moot.

The pipeline is host-side work, so a CPU run measures the thing
itself. One JSON line under a BudgetGuard like every other benchmark
here.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np

# the fork's pipeline target is to keep ResNet-50 fed at the headline
# rate — same baseline constant as the training benchmark
from bench import REFERENCE_IMG_PER_SEC, BudgetGuard

#: shared with the exception handler: best-so-far survives a crash
_guard = None


def _mirror_to_telemetry(guard, prefix):
    """Publish the BudgetGuard headline numbers through the telemetry
    registry and write the full snapshot JSON next to the bench's JSON
    line (every bench emits through telemetry.dump_json too)."""
    from mxnet_tpu import telemetry
    if not telemetry.enabled():
        telemetry.enable()
    for k, v in guard.best.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            telemetry.set_gauge(f"bench_{k}", float(v), bench=prefix)
    path = os.environ.get("BENCH_TELEMETRY_JSON",
                          f"/tmp/{prefix}_telemetry.json")
    guard.best["telemetry_json"] = telemetry.dump_json(path)
    guard.emit()


def main():
    global _guard
    _guard = guard = BudgetGuard("dataloader_images_per_sec",
                                 "images/sec").install()
    import jax

    jax.config.update("jax_platforms", "cpu")  # host-side bench

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
    from mxnet_tpu.gluon.data.vision import transforms as T

    n = int(os.environ.get("BENCH_DL_N", "2048"))
    batch = int(os.environ.get("BENCH_DL_BATCH", "64"))
    workers = int(os.environ.get("BENCH_DL_WORKERS", "2"))

    rs = np.random.RandomState(0)
    imgs = rs.randint(0, 256, (n, 32, 32, 3)).astype(np.uint8)
    labels = rs.randint(0, 10, (n,)).astype(np.int32)

    tf = T.Compose([
        T.RandomFlipLeftRight(),
        T.RandomColorJitter(0.4, 0.4, 0.4, 0.2),
        T.RandomLighting(0.1),
        T.ToTensor(layout="NHWC"),
        T.Normalize([0.485, 0.456, 0.406], [0.229, 0.224, 0.225],
                    layout="NHWC"),
    ])
    ds = ArrayDataset(imgs, labels).transform_first(tf)

    def one_epoch(num_workers, worker_type="thread"):
        dl = DataLoader(ds, batch_size=batch, shuffle=True,
                        num_workers=num_workers,
                        worker_type=worker_type)
        t0 = time.perf_counter()
        seen = 0
        for x, y in dl:
            seen += x.shape[0]
        return seen / (time.perf_counter() - t0)

    one_epoch(0)  # warm the jit-free path / allocators
    ips_serial = one_epoch(0)
    guard.best.update({
        "value": round(ips_serial, 1),
        "vs_baseline": round(ips_serial / REFERENCE_IMG_PER_SEC, 3),
        "phase": "serial", "batch": batch, "n": n,
        "images_per_sec_serial": round(ips_serial, 1),
    })
    guard.emit()

    if guard.remaining() > 20.0:
        ips_workers = one_epoch(workers)
        guard.best.update({
            "value": round(max(ips_serial, ips_workers), 1),
            "vs_baseline": round(max(ips_serial, ips_workers)
                                 / REFERENCE_IMG_PER_SEC, 3),
            "phase": "prefetch", "workers": workers,
            "images_per_sec_prefetch": round(ips_workers, 1),
        })
        guard.emit()

    # thread-vs-process scaling table (round-4 verdict item 6). On a
    # 1-core host the table is expected flat (the MEASURED caveat in
    # PERF.md); on a real multi-core TPU host the process column is
    # the one that escapes the GIL for PIL-style transforms.
    table = {"serial_0": round(ips_serial, 1)}
    best = ips_serial
    for wt in ("thread", "process"):
        if guard.remaining() < 25.0:
            break
        for nw in (2, 4):
            if guard.remaining() < 25.0:
                break
            try:
                ips = one_epoch(nw, worker_type=wt)
            except Exception as e:
                table[f"{wt}_{nw}"] = f"failed: {type(e).__name__}"
                continue
            table[f"{wt}_{nw}"] = round(ips, 1)
            best = max(best, ips)
    guard.best.update({
        "value": round(best, 1),
        "vs_baseline": round(best / REFERENCE_IMG_PER_SEC, 3),
        "phase": "worker_table",
        "worker_table": table,
    })
    guard.emit()

    # one instrumented epoch feeds the dataloader telemetry (data-wait
    # histogram, queue depth, worker wait) before the snapshot dump
    from mxnet_tpu import telemetry
    telemetry.enable()
    telemetry.reset()
    if guard.remaining() > 15.0:
        one_epoch(workers)
    _mirror_to_telemetry(guard, "dataloader_bench")


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # always emit a JSON line; rc stays 0.
        import traceback

        traceback.print_exc()
        if _guard is not None:  # keep best-so-far (e.g. the serial
            _guard.best["error"] = \
                f"{type(e).__name__}: {e}"[:300]  # phase's number)
            _guard.emit()
        else:
            print(json.dumps({"metric": "dataloader_images_per_sec",
                              "value": 0.0, "unit": "images/sec",
                              "vs_baseline": 0.0,
                              "error": f"{type(e).__name__}: {e}"[:300]}))
