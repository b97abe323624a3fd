"""KVStore collective bandwidth (SURVEY §6: GB/s).

Default leg: standalone wrapper over bench.py's `_allreduce_phase`
(psum over the dp mesh axis inside one jitted step; single chip: the
fused add/identity path; multi-chip: ICI collective bandwidth). It
measures on the chip or not at all. bench.py also folds this metric
into its headline JSON as `allreduce_gbps`.

`--collective all_gather` / `--collective ppermute` legs benchmark the
round-13 quantized collectives (parallel/compression.py): each scheme
(fp32 baseline, block-scaled int8, fp8-e4m3) runs the same jitted
shard_map collective, and the leg emits a logical-vs-wire byte table,
per-scheme step-time A/B, `bench_collective_*` telemetry gauges, and a
BudgetGuard JSON line. These legs take whatever platform JAX gives the
process: on a CPU mesh the byte table is the result (a count, valid
anywhere) and the ms column only says what the quantize/dequantize math
costs the host — there is no ICI whose saved bytes could pay for it.

Every leg runs in the calling process and exits non-zero on failure.
"""
import argparse
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import (BudgetGuard, REFERENCE_ALLREDUCE_GBPS,
                   _allreduce_phase, _best, _guard)

SCHEMES = (None, "int8", "fp8")


def _collective_phase(guard, which):
    """Quantized all_gather / ppermute A/B over every wire scheme."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu import telemetry as _tm
    from jax import shard_map
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.compression import (
        DEFAULT_BLOCK, quantized_all_gather, quantized_ppermute,
        wire_nbytes)

    n = len(jax.devices())
    mesh = make_mesh([n], ["dp"])
    on_tpu = jax.devices()[0].platform not in ("cpu",)
    mb = int(os.environ.get("BENCH_MB", 64 if on_tpu else 4))
    size = max(n * DEFAULT_BLOCK, mb * 1024 * 1024 // 4)
    size -= size % (n * DEFAULT_BLOCK)  # whole blocks per shard
    per = size // n
    reps = int(os.environ.get("BENCH_REPS", 10))
    perm = tuple((i, (i + 1) % n) for i in range(n))

    x = jax.device_put(jnp.linspace(-3.0, 3.0, size, dtype=jnp.float32),
                       NamedSharding(mesh, P("dp")))

    def make_fn(scheme):
        if which == "all_gather":
            def body(v):
                if scheme is None:
                    full = jax.lax.all_gather(v, "dp", axis=0,
                                              tiled=True)
                else:
                    full = quantized_all_gather(v, "dp", scheme,
                                                DEFAULT_BLOCK)
                # fold back to shard size so reps can chain (keeps the
                # timed loop dispatch-dependent, like the psum leg)
                i = jax.lax.axis_index("dp")
                return jax.lax.dynamic_slice(full, (i * per,), (per,))
        else:
            def body(v):
                if scheme is None:
                    return jax.lax.ppermute(v, "dp", perm)
                return quantized_ppermute(v, "dp", perm, scheme,
                                          DEFAULT_BLOCK)
        return jax.jit(shard_map(body, mesh=mesh, in_specs=P("dp"),
                                 out_specs=P("dp"), check_vma=False))

    # wire bytes one device RECEIVES per rep (the kvstore accounting
    # convention): all_gather receives every shard, ppermute one
    logical_per = per * 4 * (n if which == "all_gather" else 1)
    rows, fields = [], {}
    base_ms = None
    for scheme in SCHEMES:
        f = make_fn(scheme)
        jax.block_until_ready(f(x))  # compile + warm
        times = []
        for _ in range(max(3, reps // 3)):
            y = x
            t0 = time.perf_counter()
            for _ in range(reps):
                y = f(y)
            jax.block_until_ready(y)
            times.append((time.perf_counter() - t0) / reps * 1e3)
        ms = statistics.median(times)
        wire_per = logical_per if scheme is None else \
            wire_nbytes(per, scheme, DEFAULT_BLOCK) * \
            (n if which == "all_gather" else 1)
        cut = logical_per / wire_per
        tag = scheme or "fp32"
        if scheme is None:
            base_ms = ms
        rows.append((tag, logical_per, wire_per, cut, ms,
                     ms / base_ms))
        fields[f"{tag}_ms"] = round(ms, 3)
        fields[f"{tag}_wire_cut"] = round(cut, 3)
        _tm.set_gauge("bench_collective_wire_cut", cut,
                      collective=which, scheme=tag)
        _tm.set_gauge("bench_collective_ms", ms,
                      collective=which, scheme=tag)
        guard.best["value"] = fields.get("int8_wire_cut", 0.0)
        guard.best.update(fields)
        guard.best["phase"] = f"{which}:{tag}"
        if guard.remaining() < 10.0:
            break

    print(f"# {which} over {n} devices, {size} fp32 elements "
          f"({reps} reps)", file=sys.stderr)
    print(f"# {'scheme':>6} {'logical':>12} {'wire':>12} {'cut':>7} "
          f"{'ms/op':>9} {'vs fp32':>8}", file=sys.stderr)
    for tag, lg, wr, cut, ms, rel in rows:
        print(f"# {tag:>6} {lg:>12,} {wr:>12,} {cut:>6.2f}x "
              f"{ms:>9.3f} {rel:>7.2f}x", file=sys.stderr)
    guard.best.update({
        "devices": n, "elements": size,
        # the ideal block-128 cut is 3.879x; vs_baseline reports how
        # close this shape got to it
        "vs_baseline": round(fields.get("int8_wire_cut", 0.0) / 3.879,
                             3),
        "phase": which,
    })
    guard.emit()


def main():
    import jax

    from mxnet_tpu import tracing

    ap = argparse.ArgumentParser()
    ap.add_argument("--collective", default="allreduce",
                    choices=("allreduce", "all_gather", "ppermute"))
    args = ap.parse_args()
    backend = jax.default_backend()
    tracing.enable_compile_cache()
    if args.collective == "allreduce":
        if backend != "tpu":
            raise SystemExit("the allreduce leg measures on the chip: "
                             f"jax.default_backend() is {backend!r}")
        _guard.best.update({"metric": "kvstore_allreduce_gbps",
                            "unit": "GB/s"})
        _guard.install()
        _best.update({"backend": backend, "phase": "backend_acquired"})
        gbps = _allreduce_phase(backend)
        _best.update({
            "value": round(gbps, 2),
            "vs_baseline": round(gbps / REFERENCE_ALLREDUCE_GBPS, 3),
            "phase": "allreduce",
        })
        _guard.emit()
        return
    guard = BudgetGuard(f"bench_collective_{args.collective}_wire_cut",
                        "x")
    guard.install()
    guard.best.update({"backend": backend, "phase": "backend_acquired"})
    _collective_phase(guard, args.collective)


if __name__ == "__main__":
    main()
