"""Where the choice of kernel is made. Whether a call runs its Mosaic
kernel, the Pallas interpreter or its jax.numpy twin is ONE decision,
`kernel_mode` (the family's switch in the environment, the backend,
where a concrete operand lives; a kernel file adds only its own shape
preconditions), followed by ONE pattern, `KernelFallback.run`: try the
kernel; a failure RAISES on a TPU backend — a kernel that does not
trace, lower or compile on the chip is a bug, and a silent return to
the jnp twin would let a chip run pass without the kernel. Off the
chip (the Pallas interpreter under tests) it is a warn-once, counted
fallback that MXNET_TPU_STRICT_KERNELS=1 (or the family's
MXNET_TPU_STRICT_<family>=1) makes fatal.

The families and their switches (docs/MIGRATION.md has the table):
FLASH, NORM, CE, MOE, SCAN -> MXNET_TPU_<family>_INTERPRET=1 runs the
family's kernels under the Pallas interpreter on any backend."""
from __future__ import annotations

import os
import warnings

__all__ = ["KernelFallback", "fallback_counts", "kernel_mode",
           "operand_on_cpu", "pick_rows", "pad_rows", "per_shard",
           "float0_like"]


def _on(name: str) -> bool:
    return os.environ.get(name, "0") == "1"


def operand_on_cpu(x) -> bool:
    """True when a CONCRETE array lives wholly on CPU devices.

    Kernel gating by `jax.default_backend()` alone is wrong for eager
    calls on CPU-committed arrays while a TPU backend exists (e.g.
    model init under `with mx.context.cpu():`): Mosaic lowering would
    run against CPU operands and fail. Tracers have no devices — this
    returns False for them and the backend gate decides."""
    try:
        devs = x.devices()
        return bool(devs) and all(d.platform == "cpu" for d in devs)
    except Exception:
        return False


def kernel_mode(family, operand=None, *, ok=True, ok_compiled=True):
    """None (the jnp twin), "interpret" or "compiled" for one call of a
    kernel of `family`. `ok`: the kernel's own preconditions in either
    mode; `ok_compiled`: those Mosaic alone has (the interpreter wins
    over them). Compiled needs a backend that is not the CPU and an
    `operand` that, where concrete, is not committed to CPU devices."""
    if not ok:
        return None
    if _on(f"MXNET_TPU_{family}_INTERPRET"):
        return "interpret"
    import jax

    if jax.default_backend() != "cpu" and ok_compiled \
            and not operand_on_cpu(operand):    # False for None too
        return "compiled"
    return None


def per_shard(fn, args, in_specs, out_like=0):
    """`fn(*args)` with every device working on its own shard; the one
    output is laid out like argument `out_like`.

    GSPMD cannot partition a Mosaic kernel: under a multi-device jit
    the lowering refuses ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map"). This is that
    wrap, at the one seam every kernel family dispatches through. It
    applies while tracing under `mesh.current_mesh()` (FusedTrainStep /
    ShardedForward bind it) when the mesh parallelizes something and no
    enclosing shard_map has made the axes manual already — the ZeRO and
    pipeline steps trace their kernels on per-device views and come
    straight through, as does everything on one device.

    Specs name the repo's conventional axes ("dp" splits the batch,
    "tp" the heads). An axis is used only if the mesh has it and it
    divides that dim of EVERY argument naming it — q heads split over
    tp while indivisible kv heads stay whole would pair the wrong
    heads — else it is dropped everywhere: replicated, which is
    correct if redundant."""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec

    from ..parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is None or mesh.size == 1 \
            or jax.sharding.get_abstract_mesh().manual_axes \
            or not any(isinstance(a, jax.core.Tracer) for a in args):
        return fn(*args)

    named = [(ax, dim) for sp, a in zip(in_specs, args)
             for ax, dim in zip(sp, a.shape) if ax is not None]
    usable = {ax for ax, _ in named if ax in mesh.axis_names
              and all(dim % mesh.shape[ax] == 0
                      for ax2, dim in named if ax2 == ax)}
    ins = tuple(PartitionSpec(*[ax if ax in usable else None
                                for ax in sp]) for sp in in_specs)
    return shard_map(fn, mesh=mesh, in_specs=ins,
                     out_specs=ins[out_like], check_vma=False)(*args)


def float0_like(a):
    """The cotangent a `custom_vjp` hands back for an integer or bool
    operand (jax's convention: zeros of dtype float0)."""
    import jax
    import numpy as np

    return np.zeros(a.shape, jax.dtypes.float0)


def pick_rows(n, row_bytes, want, budget_bytes):
    """Rows per block for a kernel whose VMEM working set costs
    `row_bytes` a row (its blocks double-buffered in their own dtype
    plus its fp32 temporaries — the caller's sum): bounded by the byte
    budget, power of two, MINIMUM 8 — Mosaic requires the sublane
    (second-to-last) block dim be a multiple of 8 (callers pad the row
    count up to a multiple, see pad_rows)."""
    budget = max(8, budget_bytes // max(row_bytes, 1))
    n_cap = 8
    while n_cap < n:
        n_cap *= 2
    b = max(8, min(want, budget, n_cap))
    p = 8
    while p * 2 <= b:
        p *= 2
    return p


def pad_rows(a, rows, fill=0):
    """Pad axis 0 up to a multiple of `rows` (callers slice the kernel
    outputs back to the original row count)."""
    import jax.numpy as jnp

    pad = (-a.shape[0]) % rows
    if pad:
        a = jnp.concatenate(
            [a, jnp.full((pad,) + a.shape[1:], fill, a.dtype)], axis=0)
    return a

#: every KernelFallback registers itself here so the profiler can report
#: per-family fallback counts (kernel regressions are never invisible)
_REGISTRY = {}


def fallback_counts():
    """{kernel_name: fallback count} across all kernel families."""
    return {name: fb.count for name, fb in _REGISTRY.items()}


class KernelFallback:
    def __init__(self, kernel_name: str, family: str):
        self.kernel_name = kernel_name
        self.family = family
        self.count = 0
        self._warned = False
        _REGISTRY[kernel_name] = self

    def strict(self) -> bool:
        import jax

        return jax.default_backend() == "tpu" \
            or _on(f"MXNET_TPU_STRICT_{self.family}") \
            or _on("MXNET_TPU_STRICT_KERNELS")

    def run(self, mode, kernel, twin):
        """`kernel(interpret)` where `mode` (kernel_mode's answer) is
        not None; `twin()` without a mode or after a failure that
        `note` let pass."""
        if mode is not None:
            try:
                return kernel(mode == "interpret")
            except Exception as e:
                self.note(e)
        return twin()

    def note(self, e: BaseException):
        """Record a fallback; re-raises first on a TPU backend and in
        strict mode."""
        if self.strict():
            raise e
        self.count += 1
        if not self._warned:
            self._warned = True
            # note <- run <- the kernel's entry <- its caller <- the
            # caller's: the model's line, not the op wrapper's
            warnings.warn(
                f"Pallas {self.kernel_name} kernel failed; falling back "
                f"to the jnp path: {type(e).__name__}: {e}",
                RuntimeWarning, stacklevel=5)
