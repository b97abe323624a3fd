"""Tracing / graph-dump subsystem (SURVEY §2 aux: jaxpr/HLO dump,
compile-cache stats).

The reference exposes its graph through ``symbol.json`` exports and env
switches like ``MXNET_EXEC_*``/graph-pass dumps; the XLA-native
equivalents are the jaxpr (front-end trace) and StableHLO (compiler
input). This module records every HybridBlock compilation, serves
cache-hit statistics (the CachedOp hit-rate analogue), and — when
``MXNET_TPU_DUMP_HLO=<dir>`` is set — writes each freshly compiled
graph's StableHLO to that directory as it is built.

API:
    enable_compile_cache() — JAX's persistent cache at a fixed place
    cache_stats() / reset_cache_stats()
    lower_text(entry)  — StableHLO of a compiled _CacheEntry
    jaxpr_text(entry)  — jaxpr of the same
    dump_dir()         — active MXNET_TPU_DUMP_HLO directory or None
"""
from __future__ import annotations

import os
import threading
from typing import Optional

import jax

__all__ = ["enable_compile_cache", "cache_stats", "reset_cache_stats",
           "record_hit",
           "record_compile", "record_compile_seconds", "lower_text",
           "jaxpr_text", "dump_dir", "maybe_dump"]

def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and
    no directory is set in code, so whoever runs the program decides
    where compiled code survives. Otherwise the cache lives at
    ``<checkout>/.jax_cache`` (git-ignored): a fixed path, because the
    path is part of what a later process must reproduce to hit — never
    one built from a temporary name, a pid or the time. Entry points
    call this (chip_smoke.py, perfbench/run.py, the kernel tuner, the
    serving worker); importing the package does not."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir",
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), ".jax_cache"))
    # cache every executable: the eager per-op programs of a cold start
    # are small and quick to compile, and there are hundreds of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


_lock = threading.Lock()
_stats = {"compiles": 0, "hits": 0}
#: per-block breakdown: {block_name: {"compiles": n, "hits": n,
#: "compile_seconds": s}} — the telemetry snapshot surfaces this via
#: cache_stats()["per_block"]
_per_block: dict = {}
_compile_seconds = 0.0


def cache_stats() -> dict:
    """Compile-cache statistics across all HybridBlocks: `compiles` =
    distinct (shape, dtype, mode) entries built, `hits` = calls served
    from cache, `hit_rate` in [0, 1]. The global keys keep their
    original shape; `compile_seconds` (wall time spent building fresh
    entries) and `per_block` ({name: {compiles, hits,
    compile_seconds}}) ride along."""
    with _lock:
        total = _stats["compiles"] + _stats["hits"]
        return {**_stats,
                "hit_rate": (_stats["hits"] / total) if total else 0.0,
                "compile_seconds": _compile_seconds,
                "per_block": {k: dict(v) for k, v in _per_block.items()}}


def reset_cache_stats():
    global _compile_seconds
    with _lock:
        _stats["compiles"] = 0
        _stats["hits"] = 0
        _per_block.clear()
        _compile_seconds = 0.0


def _block_slot(name):
    ent = _per_block.get(name)
    if ent is None:
        ent = _per_block[name] = {"compiles": 0, "hits": 0,
                                  "compile_seconds": 0.0}
    return ent


def record_hit(name: Optional[str] = None):
    with _lock:
        _stats["hits"] += 1
        if name is not None:
            _block_slot(name)["hits"] += 1


def record_compile_seconds(name: str, seconds: float):
    """Wall time one fresh cache entry took to trace+compile+first-run;
    feeds the global and per-block accumulators plus the
    `compile_seconds_total`/`compiles_total` telemetry metrics."""
    global _compile_seconds
    with _lock:
        _compile_seconds += seconds
        _block_slot(name)["compile_seconds"] += seconds
    from . import telemetry as _tm
    if _tm._ENABLED:
        _tm.observe("compile_seconds", seconds, block=name)
    from . import flight as _fl
    if _fl._ENABLED:
        _fl.record("compile", name, seconds=seconds)
    from . import goodput as _gp
    if _gp._ENABLED:
        _gp.note_compile(seconds)


def record_compile(name: str, entry) -> None:
    with _lock:
        _stats["compiles"] += 1
        _block_slot(name)["compiles"] += 1
        n = _stats["compiles"]
    from . import telemetry as _tm
    if _tm._ENABLED:
        _tm.inc("compiles_total", 1, block=name)
    d = dump_dir()
    if d:
        try:
            text = lower_text(entry)
            os.makedirs(d, exist_ok=True)
            with open(os.path.join(d, f"{name}-{n:03d}.stablehlo.mlir"),
                      "w") as f:
                f.write(text)
        except Exception as e:  # dumping must never break training
            import warnings
            warnings.warn(f"MXNET_TPU_DUMP_HLO failed for {name}: {e}")


def dump_dir() -> Optional[str]:
    return os.environ.get("MXNET_TPU_DUMP_HLO") or None


def _abstract_args(entry):
    if getattr(entry, "_example_avals", None) is None:
        raise RuntimeError("block has not been called yet — no example "
                           "shapes recorded to lower with")
    return entry._example_avals


def lower_text(entry) -> str:
    """StableHLO text for a compiled _CacheEntry (what XLA compiles)."""
    avals = _abstract_args(entry)
    return entry.jit_fn.lower(*avals).as_text()


def jaxpr_text(entry) -> str:
    """jaxpr for a compiled _CacheEntry (the functional trace)."""
    avals = _abstract_args(entry)
    return str(jax.make_jaxpr(entry.raw_fn)(*avals))


def maybe_dump(name: str, text: str, suffix: str = "txt"):
    d = dump_dir()
    if d:
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, f"{name}.{suffix}"), "w") as f:
            f.write(text)
