"""Device contexts: mx.cpu() / mx.tpu().

Reference parity: mxnet/context.py (Context class, with-stack semantics,
mx.gpu()). TPU-first: a Context resolves to a jax.Device; `gpu` is an alias
for `tpu` so reference scripts run with only the context string changed
(BASELINE.json north star). tpu(i) resolves to the i-th host device ONLY
when the process has pinned JAX to the CPU (JAX_PLATFORMS=cpu, or
jax.config.update("jax_platforms", "cpu") — what tests/conftest.py and
every example's --cpu flag do). Not pinned and no TPU found is an error:
a run that was meant for the chip must never pass on the host instead.
"""
from __future__ import annotations

import threading

import jax

_CTX_STACK = threading.local()


class Context:
    """A device context. devtype: 'cpu' | 'tpu' ('gpu' aliases 'tpu')."""

    def __init__(self, device_type: str, device_id: int = 0):
        if device_type == "gpu":  # reference scripts use mx.gpu(); map to tpu
            device_type = "tpu"
        if device_type not in ("cpu", "tpu"):
            raise ValueError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = device_id

    # -- jax resolution -----------------------------------------------------
    @property
    def jax_device(self) -> jax.Device:
        # local (addressable) devices only: in a multi-process job,
        # jax.devices() lists every host's chips and eager placement on
        # a non-addressable device is invalid
        if self.device_type == "tpu":
            devs = _local_tpus()
            if not devs:
                _require_cpu_pin()
                devs = jax.local_devices()  # pinned: tpu ids on host devices
            return devs[self.device_id % len(devs)]
        cpus = jax.local_devices(backend="cpu")
        return cpus[self.device_id % len(cpus)]

    # -- context-manager stack ---------------------------------------------
    def __enter__(self):
        stack = getattr(_CTX_STACK, "stack", None)
        if stack is None:
            stack = _CTX_STACK.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        _CTX_STACK.stack.pop()
        return False

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias so unmodified reference scripts map onto TPU chips."""
    return Context("tpu", device_id)


def current_context() -> Context:
    stack = getattr(_CTX_STACK, "stack", None)
    if stack:
        return stack[-1]
    return _default_context()


def _local_tpus():
    return [d for d in jax.local_devices() if d.platform == "tpu"]


def _require_cpu_pin():
    """No TPU was found: legal only in a process that pinned JAX to the
    CPU on purpose."""
    if jax.config.jax_platforms != "cpu":
        raise RuntimeError(
            "no TPU device found (jax sees "
            f"{[d.platform for d in jax.local_devices()]}) and JAX is "
            "not pinned to the CPU. To run on the host on purpose, set "
            "JAX_PLATFORMS=cpu (or jax.config.update('jax_platforms', "
            "'cpu')) before the first jax call.")


def _default_context() -> Context:
    if _local_tpus():
        return Context("tpu", 0)
    _require_cpu_pin()
    return Context("cpu", 0)


def num_tpus() -> int:
    """Local (this host's) TPU count, like the reference's num_gpus."""
    return len(_local_tpus())


def num_gpus() -> int:  # reference API parity (mx.context.num_gpus)
    return num_tpus()
