"""Profiler (reference: mxnet/profiler.py + src/profiler/).

Wraps jax.profiler for device traces plus host-side scoped timers; dumps a
chrome-trace-compatible JSON like the reference's profile_output.
"""
from __future__ import annotations

import contextlib
import json
import time
from typing import Dict, List, Optional

import jax

from . import telemetry as _tm

__all__ = ["set_config", "set_state", "scope", "Timer", "dump",
           "start_device_trace", "stop_device_trace", "summary",
           "register_memory_provider", "unregister_memory_provider",
           "resident_bytes"]

_CONFIG = {"filename": "profile.json", "aggregate_stats": True}
_STATE = {"running": False}
_EVENTS: List[dict] = []
_AGG: Dict[str, List[float]] = {}

# -- resident-bytes accounting (ZeRO memory claims are asserted, not
# hand-computed): training components (Trainer's multi-tensor updater,
# FusedTrainStep) register a provider that reports CURRENT per-replica
# resident bytes by category. Providers return None to drop themselves
# (the usual pattern is a closure over a weakref to the owner).
_MEM_PROVIDERS: Dict[str, object] = {}

MEM_CATEGORIES = ("weights", "grads", "opt_state", "transient")


def register_memory_provider(name: str, fn):
    """Register `fn() -> {"weights": int, "grads": int, "opt_state": int,
    "transient": int} | None` reporting per-replica resident bytes.
    Returning None unregisters the provider (dead weakref)."""
    _MEM_PROVIDERS[name] = fn


def unregister_memory_provider(name: str):
    _MEM_PROVIDERS.pop(name, None)


def resident_bytes() -> Dict[str, Dict[str, int]]:
    """Per-provider snapshot of per-replica resident training bytes,
    plus a cross-provider "total" entry. Sharded buffers count as
    global_bytes / num_shards; replicated buffers count full size."""
    out: Dict[str, Dict[str, int]] = {}
    total = {k: 0 for k in MEM_CATEGORIES}
    for name in list(_MEM_PROVIDERS):
        try:
            rep = _MEM_PROVIDERS[name]()
        except Exception:
            rep = None
        if rep is None:
            _MEM_PROVIDERS.pop(name, None)
            continue
        row = {k: int(rep.get(k, 0)) for k in MEM_CATEGORIES}
        row["total"] = sum(row.values())
        out[name] = row
        for k in MEM_CATEGORIES:
            total[k] += row[k]
    total_row = dict(total)
    total_row["total"] = sum(total.values())
    out["total"] = total_row
    return out


def set_config(**kwargs):
    _CONFIG.update(kwargs)


def set_state(state="run"):
    _STATE["running"] = state in ("run", True)


@contextlib.contextmanager
def scope(name: str, sync: bool = False):
    """Host-side scoped timer; sync=True blocks on device (accurate op
    timing under async dispatch, like the reference's engine profiling)."""
    if not _STATE["running"]:
        yield
        return
    t0 = time.perf_counter()
    yield
    if sync:
        from .ndarray import waitall
        waitall()
    dt = (time.perf_counter() - t0) * 1e6
    _EVENTS.append({"name": name, "ph": "X", "ts": t0 * 1e6, "dur": dt,
                    "pid": 0, "tid": 0})
    _AGG.setdefault(name, []).append(dt)
    if _tm._ENABLED:
        _tm.observe("profiler_scope_seconds", dt / 1e6, scope=name)


class Timer:
    def __init__(self, name):
        self.name = name
        self._cm = None

    def __enter__(self):
        self._cm = scope(self.name, sync=True)
        return self._cm.__enter__()

    def __exit__(self, *exc):
        return self._cm.__exit__(*exc)


def start_device_trace(logdir="/tmp/jax-trace"):
    """Open a `jax.profiler` session writing `.xplane.pb` under
    `logdir`. The program's own spans (`telemetry.phase` / `span`,
    named `mx.*`) are TraceAnnotations, so that one file holds host
    spans and device operations on one clock; read it with
    `jax.profiler.ProfileData`. Nothing is merged into
    `telemetry.export_chrome_trace`, which stays on the host clock."""
    jax.profiler.start_trace(logdir)


def stop_device_trace():
    jax.profiler.stop_trace()


def dump(finished=True):
    """Write the chrome-trace JSON to _CONFIG["filename"].

    Honors the config + its own argument (reference semantics):
    `aggregate_stats` (set_config) adds the per-scope aggregate table
    and the resident-bytes snapshot to the dumped JSON; `finished=True`
    stops the profiling session, `finished=False` leaves it running for
    further dumps. Collected events/aggregates stay readable either way
    (summary()/dumps()); `dumps(reset=True)` clears them."""
    payload: dict = {"traceEvents": list(_EVENTS)}
    if _CONFIG.get("aggregate_stats"):
        payload["aggregateStats"] = {
            name: {"calls": len(durs),
                   "mean_us": sum(durs) / len(durs),
                   "total_us": sum(durs)}
            for name, durs in sorted(_AGG.items())}
        payload["residentBytes"] = resident_bytes()
    with open(_CONFIG["filename"], "w") as f:
        json.dump(payload, f)
    if finished:
        set_state("stop")
    return _CONFIG["filename"]


def summary() -> str:
    lines = [f"{'scope':<40}{'calls':>8}{'mean_us':>12}{'total_us':>14}"]
    for name, durs in sorted(_AGG.items()):
        lines.append(f"{name:<40}{len(durs):>8}"
                     f"{sum(durs) / len(durs):>12.1f}{sum(durs):>14.1f}")
    from .kernels.dispatch import fallback_counts
    fb = fallback_counts()
    if fb:
        lines.append("kernel fallbacks: " + ", ".join(
            f"{k}={v}" for k, v in sorted(fb.items())))
    mem = resident_bytes()
    if len(mem) > 1:  # more than the always-present "total" row
        lines.append(f"{'resident bytes/replica':<28}"
                     + "".join(f"{c:>12}" for c in MEM_CATEGORIES)
                     + f"{'total':>12}")
        for name, row in sorted(mem.items()):
            if name == "total" and len(mem) == 2:
                continue  # single provider: total row is redundant
            lines.append(f"{name:<28}"
                         + "".join(f"{row[c]:>12}" for c in MEM_CATEGORIES)
                         + f"{row['total']:>12}")
    return "\n".join(lines)


def dumps(reset=False):
    s = summary()
    if reset:
        _AGG.clear()
        _EVENTS.clear()
    return s
