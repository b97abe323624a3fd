"""Multi-host (TPU pod / multi-process) wiring.

TPU-first replacement for the reference's distributed launch plumbing
(kvstore dist_* modes: parameter-server `ps-lite` bootstrap + NCCL
communicators). On TPU there is no rendezvous server to run: every host
calls :func:`initialize` once, JAX's coordination service forms the
global device view, and from then on *the same* SPMD program (psum /
all_gather over a Mesh) spans all hosts — the DCN hops are just slower
mesh axes.

Design notes (scaling-book recipe):
- ICI axes (within a pod slice) carry the high-traffic collectives
  (tensor-parallel all_gather/psum); DCN (between slices) should only
  carry low-frequency traffic (data-parallel gradient reduce).
- ``hybrid_device_mesh`` therefore puts the DCN axis *outermost* and the
  ICI axes innermost, via ``mesh_utils.create_hybrid_device_mesh``.
- Checkpointing and logging are gated on :func:`is_primary` (process 0),
  matching the reference's "rank 0 saves" convention.
"""
from __future__ import annotations

import os
from typing import Optional, Sequence

import numpy as _np

import jax

__all__ = [
    "initialize", "is_initialized", "is_primary", "process_index",
    "process_count", "local_devices", "hybrid_device_mesh",
    "sync_global_devices", "broadcast_from_primary",
    "kv_set", "kv_get", "kv_delete", "kv_dir_get", "client_barrier",
]

_initialized = False


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None, **kwargs):
    """Join the multi-host job: wrap ``jax.distributed.initialize``.

    All arguments default to auto-detection (TPU metadata / env vars
    ``MXNET_TPU_COORDINATOR``, ``MXNET_TPU_NUM_PROCS``,
    ``MXNET_TPU_PROC_ID``), so single-host runs may simply never call
    this. Safe to call twice (second call is a no-op). Replaces the
    reference's ``DMLC_PS_ROOT_URI``/scheduler bootstrap.
    """
    global _initialized
    if _initialized:
        return
    from .. import faults as _ft
    if os.environ.get("MXNET_TPU_BREAK_MULTIHOST") or \
            (_ft._ACTIVE and _ft.fire("multihost.break") is not None):
        # fault injection (faults.py site "multihost.break"; the env
        # var is the pre-injector spelling, kept for compat): lets the
        # dryrun's 2-process legs prove that a broken multihost path
        # turns the dryrun red instead of being swallowed as "skipped"
        raise RuntimeError("multihost.initialize deliberately broken "
                           "(MXNET_TPU_BREAK_MULTIHOST set)")
    coordinator_address = coordinator_address or os.environ.get(
        "MXNET_TPU_COORDINATOR")
    if num_processes is None and "MXNET_TPU_NUM_PROCS" in os.environ:
        num_processes = int(os.environ["MXNET_TPU_NUM_PROCS"])
    if process_id is None and "MXNET_TPU_PROC_ID" in os.environ:
        process_id = int(os.environ["MXNET_TPU_PROC_ID"])
    # CPU-backend multi-process jobs (CI dryruns, tests) need a real
    # collectives implementation — without this every cross-process
    # computation dies with "Multiprocess computations aren't
    # implemented on the CPU backend". Checked via the platforms
    # CONFIG string so we don't force backend init before
    # jax.distributed.initialize.
    plats = (jax.config.jax_platforms or "")
    if "cpu" in plats.split(","):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id, **kwargs)
    _initialized = True


def is_initialized() -> bool:
    return _initialized


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_primary() -> bool:
    """True on process 0 — gate checkpoint writes / logging on this."""
    return jax.process_index() == 0


def local_devices():
    return jax.local_devices()


def hybrid_device_mesh(ici_shape: Sequence[int],
                       dcn_shape: Sequence[int],
                       axis_names: Sequence[str],
                       devices=None) -> "jax.sharding.Mesh":
    """DCN×ICI hybrid mesh: ``dcn_shape`` axes span pod slices (slow
    network, put dp here), ``ici_shape`` axes span chips within a slice
    (fast ICI, put tp/sp here). Axis ``i`` has total size
    ``dcn_shape[i] * ici_shape[i]``.

    Example for 2 slices × 16 chips, dp over DCN and tp over ICI::

        mesh = hybrid_device_mesh(ici_shape=[2, 8], dcn_shape=[2, 1],
                                  axis_names=["dp", "tp"])
    """
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh
    n = int(_np.prod(ici_shape)) * int(_np.prod(dcn_shape))
    devices = list(devices if devices is not None else jax.devices())[:n]
    if int(_np.prod(dcn_shape)) == 1:
        arr = mesh_utils.create_device_mesh(tuple(ici_shape),
                                            devices=devices)
    else:
        arr = mesh_utils.create_hybrid_device_mesh(
            tuple(ici_shape), tuple(dcn_shape), devices=devices)
    return Mesh(arr, tuple(axis_names))


def sync_global_devices(name: str = "barrier"):
    """Cross-host barrier (reference: ``kv.barrier()``)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(name)


def broadcast_from_primary(tree):
    """Broadcast host-local values from process 0 to all processes
    (reference: PS init broadcast of fresh weights)."""
    if jax.process_count() <= 1:
        return tree
    from jax.experimental import multihost_utils
    return multihost_utils.broadcast_one_to_all(tree)


# -- coordination-service side channel --------------------------------------
#
# The jax.distributed coordination service carries a string KV store
# and a host-level barrier that involve NO device collective — safe to
# use from arbitrary host threads (the /metrics scrape thread, signal
# handlers' aftermath) and under the gloo CPU backend. telemetry's
# cross-process aggregation and checkpoint's orbax CPU patch both ride
# this channel.

def _client():
    """The coordination-service client, or None when this process never
    joined a multi-process job."""
    if not _initialized:
        return None
    from jax._src import distributed as _dist
    return _dist.global_state.client


def kv_set(key: str, value: str) -> bool:
    """Publish `key` -> `value` in the coordination-service KV store
    (last write wins). False when there is no service to publish to."""
    c = _client()
    if c is None:
        return False
    c.key_value_set(key, value, allow_overwrite=True)
    return True


def kv_get(key: str, timeout_ms: int = 2000) -> Optional[str]:
    """Read `key` from the KV store, waiting up to `timeout_ms` for it
    to appear. None on timeout or when no service is up."""
    c = _client()
    if c is None:
        return None
    try:
        return c.blocking_key_value_get(key, int(timeout_ms))
    except Exception:
        return None


def kv_delete(key: str) -> bool:
    """Delete `key` (and, per the service's semantics, any keys under
    the directory `key/`) from the KV store. False when no service."""
    c = _client()
    if c is None:
        return False
    try:
        c.key_value_delete(key)
    except Exception:
        return False
    return True


def kv_dir_get(prefix: str) -> list:
    """Non-blocking prefix scan: every ``(key, value)`` currently under
    `prefix` (the coordination service treats keys as paths, so use a
    trailing ``/`` to scan a directory). Empty list when nothing is
    there yet or no service is up. This is the polling primitive the
    serving fleet's result channel rides — unlike :func:`kv_get` it
    never blocks waiting for a key to appear."""
    c = _client()
    if c is None:
        return []
    try:
        return [(k, v) for k, v in c.key_value_dir_get(prefix)]
    except Exception:
        return []


def client_barrier(name: str, timeout_ms: int = 60_000):
    """Host-level barrier through the coordination service — unlike
    :func:`sync_global_devices` this never launches a device collective,
    so it is gloo-safe and usable while a computation is in flight on
    another thread. No-op (True) single-process; True once every
    process arrived; raises on timeout."""
    c = _client()
    if c is None:
        return True
    c.wait_at_barrier(name, int(timeout_ms))
    return True
