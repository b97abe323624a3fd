"""NDArray: the imperative tensor, backed by jax.Array.

Reference parity: mxnet/ndarray/ndarray.py + src/ndarray/ndarray.cc. The
reference pushes every op onto a C++ dependency engine for async execution;
here jax's async dispatch IS that engine — every op returns immediately with
a future-like jax.Array, and `wait_to_read()` / `asnumpy()` synchronize.
Autograd hooks capture jax.vjp closures at dispatch (see autograd.py).
"""
from __future__ import annotations

import operator
from typing import Any, List, Optional, Sequence, Tuple

import numpy as _np

import jax
import jax.numpy as jnp
from jax import lax
from jax import typeof as _typeof

from . import autograd
from .base import resolve_dtype, dtype_name
from .context import Context, current_context

__all__ = ["NDArray", "array", "zeros", "ones", "full", "empty", "arange",
           "eye", "linspace", "zeros_like", "ones_like", "full_like",
           "from_numpy", "concat", "stack", "waitall"]


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _wrap_outputs(node: Optional[autograd.Node], raw_outs: List[Any],
                  multi: bool, ctx: Optional[Context] = None):
    outs = []
    for r in raw_outs:
        nd = NDArray(r, ctx=ctx)
        nd._node = node
        outs.append(nd)
    if node is not None:
        node.outputs = outs
        node.out_avals = [_typeof(r) for r in raw_outs]
    return tuple(outs) if multi else outs[0]


def invoke(fn, args: Sequence[Any], kwargs: Optional[dict] = None,
           n_out: int = 1):
    """Dispatch a pure jax function over NDArray/raw args.

    Records a tape node when autograd is recording and any input is in the
    graph. This is the single chokepoint every mx.nd op goes through —
    the analogue of MXImperativeInvoke in the reference C API.
    """
    kwargs = kwargs or {}
    raw = [a._data if isinstance(a, NDArray) else a for a in args]
    ctx = None
    for a in args:
        if isinstance(a, NDArray):
            ctx = a._ctx
            break

    grad_positions = []
    if autograd.is_recording():
        for i, a in enumerate(args):
            # inexact = floating OR complex: fft chains (spectral
            # losses) are differentiable through jax.vjp too
            if isinstance(a, NDArray) and a._in_graph \
                    and jnp.issubdtype(jnp.result_type(raw[i]),
                                       jnp.inexact):
                grad_positions.append(i)

    if grad_positions:
        def closed(*diff_args):
            buf = list(raw)
            for j, i in enumerate(grad_positions):
                buf[i] = diff_args[j]
            return fn(*buf, **kwargs)

        prim = tuple(raw[i] for i in grad_positions)
        out, vjp_fn = jax.vjp(closed, *prim)

        def bwd_fn(primals, cots, _closed=closed, _multi=n_out > 1):
            _, vjp = jax.vjp(_closed, *primals)
            return vjp(tuple(cots) if _multi else cots[0])

        node = autograd.Node(vjp_fn, [args[i] for i in grad_positions],
                             n_out, bwd_fn=bwd_fn, primals=prim)
    else:
        out = fn(*raw, **kwargs)
        node = None

    multi = n_out > 1
    raw_outs = list(out) if multi else [out]
    return _wrap_outputs(node, raw_outs, multi, ctx=ctx)


class NDArray:
    """Imperative tensor. Thin, immutable-data wrapper over jax.Array;
    in-place ops rebind `_data` (XLA arrays are functional) which keeps the
    autograd tape sound without the reference's write-dependency engine."""

    __slots__ = ("_data", "_ctx", "_node", "_grad", "_grad_req", "_stype",
                 "_grad_hook", "__weakref__")

    def __init__(self, data, ctx: Optional[Context] = None, _place=False):
        if isinstance(data, NDArray):
            data = data._data
        self._data = data
        self._ctx = ctx or current_context()
        if _place and not _is_tracer(data):
            self._data = jax.device_put(data, self._ctx.jax_device)
        self._node = None
        self._grad = None
        self._grad_req = "write"
        self._stype = "default"
        # ZeRO-2: backward() offers this leaf's cotangent to the hook the
        # moment its last consumer node has run; a hook returning True
        # consumes it (the full-size grad buffer is never written)
        self._grad_hook = None

    # -- autograd wiring ----------------------------------------------------
    @property
    def _in_graph(self) -> bool:
        return self._node is not None or (
            self._grad is not None and self._grad_req != "null")

    def attach_grad(self, grad_req: str = "write", stype=None):
        self._grad = NDArray(jnp.zeros(self.shape, self._data.dtype),
                             ctx=self._ctx)
        self._grad_req = grad_req
        self._node = None  # becomes a fresh leaf (reference semantics)

    @property
    def grad(self):
        return self._grad

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph)

    def detach(self) -> "NDArray":
        out = NDArray(self._data, ctx=self._ctx)
        return out

    def zero_grad(self):
        if self._grad is not None:
            self._grad._data = jnp.zeros_like(self._grad._data)

    # -- metadata -----------------------------------------------------------
    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return _np.dtype(dtype_name(self._data.dtype)) \
            if self._data.dtype != jnp.bfloat16 else jnp.bfloat16

    @property
    def size(self) -> int:
        return int(_np.prod(self.shape)) if self.shape else 1

    @property
    def ndim(self) -> int:
        return self._data.ndim

    @property
    def context(self) -> Context:
        return self._ctx

    ctx = context

    @property
    def stype(self) -> str:
        return self._stype

    @property
    def T(self) -> "NDArray":
        return self.transpose()

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of 0-d NDArray")
        return self.shape[0]

    def __repr__(self):
        if _is_tracer(self._data):
            return f"\n<NDArray tracer {self.shape} @{self._ctx}>"
        return f"\n{_np.asarray(self._data)}\n<NDArray {'x'.join(map(str, self.shape))} @{self._ctx}>"

    # -- synchronization (engine semantics) ---------------------------------
    def wait_to_read(self):
        if not _is_tracer(self._data):
            self._data.block_until_ready()

    def asnumpy(self) -> _np.ndarray:
        return _np.asarray(self._data)

    # -- DLPack interop (reference: ndarray.to_dlpack_for_read /
    # from_dlpack in python/mxnet/dlpack.py) --------------------------------
    def __dlpack__(self, stream=None):
        return self._data.__dlpack__(stream=stream)

    def __dlpack_device__(self):
        return self._data.__dlpack_device__()

    def to_dlpack_for_read(self):
        """A DLPack capsule sharing this array's device buffer (the
        reference's read-only variant; XLA arrays are immutable, so
        the write variant is identical)."""
        return self._data.__dlpack__()

    to_dlpack_for_write = to_dlpack_for_read

    def asscalar(self):
        if self.size != 1:
            raise ValueError("asscalar on non-scalar")
        return self.asnumpy().reshape(()).item()

    def item(self):
        return self.asscalar()

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    def __bool__(self):
        if self.size != 1:
            raise ValueError("truth value of multi-element NDArray is "
                             "ambiguous")
        return bool(self.asnumpy().reshape(()).item())

    def __int__(self):
        return int(self.asscalar())

    def __float__(self):
        return float(self.asscalar())

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # -- placement / casting ------------------------------------------------
    def as_in_context(self, ctx: Context) -> "NDArray":
        if ctx == self._ctx:
            return self
        return NDArray(self._data, ctx=ctx, _place=True)

    as_in_ctx = as_in_context

    def copyto(self, other):
        if isinstance(other, Context):
            return NDArray(self._data, ctx=other, _place=True)
        other._data = jax.device_put(self._data, other._ctx.jax_device)
        return other

    def copy(self) -> "NDArray":
        return NDArray(self._data, ctx=self._ctx)

    def astype(self, dtype, copy=True) -> "NDArray":
        dt = resolve_dtype(dtype)
        if not copy and self._data.dtype == dt:
            return self
        return invoke(lambda x: x.astype(dt), [self])

    def tostype(self, stype: str):
        from . import sparse
        if stype == "default":
            return self
        if stype == "row_sparse":
            return sparse.RowSparseNDArray.from_dense(self)
        if stype == "csr":
            return sparse.CSRNDArray.from_dense(self)
        raise ValueError(stype)

    # -- shape manipulation -------------------------------------------------
    def reshape(self, *shape):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        # MXNet magic numbers: -1 infer, 0 copy-from-input, -2.. unsupported
        inshape = self.shape
        out = []
        for i, s in enumerate(shape):
            out.append(inshape[i] if s == 0 else s)
        return invoke(lambda x: jnp.reshape(x, tuple(out)), [self])

    def reshape_like(self, other):
        return invoke(lambda x, y: jnp.reshape(x, y.shape), [self, other])

    def transpose(self, axes=None):
        return invoke(lambda x: jnp.transpose(x, axes), [self])

    def swapaxes(self, a1, a2):
        return invoke(lambda x: jnp.swapaxes(x, a1, a2), [self])

    def flatten(self):
        n = self.shape[0] if self.ndim else 1
        return invoke(lambda x: jnp.reshape(x, (n, -1)), [self])

    def expand_dims(self, axis):
        return invoke(lambda x: jnp.expand_dims(x, axis), [self])

    def squeeze(self, axis=None):
        return invoke(lambda x: jnp.squeeze(x, axis), [self])

    def broadcast_to(self, shape):
        return invoke(lambda x: jnp.broadcast_to(x, tuple(shape)), [self])

    def broadcast_like(self, other):
        return invoke(lambda x, y: jnp.broadcast_to(x, y.shape),
                      [self, other])

    def tile(self, reps):
        return invoke(lambda x: jnp.tile(x, reps), [self])

    def repeat(self, repeats, axis=None):
        return invoke(lambda x: jnp.repeat(x, repeats, axis), [self])

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        from . import nd
        return nd.split(self, num_outputs, axis=axis,
                        squeeze_axis=squeeze_axis)

    def slice(self, begin, end, step=None):
        from . import nd
        return nd.slice(self, begin, end, step)

    def slice_axis(self, axis, begin, end):
        from . import nd
        return nd.slice_axis(self, axis=axis, begin=begin, end=end)

    def take(self, indices, axis=0, mode="clip"):
        from . import nd
        return nd.take(self, indices, axis=axis, mode=mode)

    def pick(self, index, axis=-1, keepdims=False):
        from . import nd
        return nd.pick(self, index, axis=axis, keepdims=keepdims)

    def flip(self, axis):
        return invoke(lambda x: jnp.flip(x, axis), [self])

    def diag(self, k=0):
        return invoke(lambda x: jnp.diag(x, k), [self])

    # -- indexing -----------------------------------------------------------
    def __getitem__(self, key):
        if isinstance(key, NDArray):
            key = key._data if _is_tracer(key._data) else _np.asarray(key._data)
            if not _np.issubdtype(_np.asarray(key).dtype, _np.integer) \
                    and not hasattr(key, "aval"):
                key = _np.asarray(key).astype(_np.int64)
        k = key
        return invoke(lambda x: x[k], [self])

    def __setitem__(self, key, value):
        if isinstance(key, NDArray):
            key = _np.asarray(key._data)
        if isinstance(value, NDArray):
            value = value._data
        if isinstance(key, slice) and key == slice(None):
            self._data = jnp.broadcast_to(jnp.asarray(
                value, dtype=self._data.dtype), self.shape)
        else:
            self._data = self._data.at[key].set(
                jnp.asarray(value, dtype=self._data.dtype)
                if not isinstance(value, jax.Array) else value)
        self._node = None  # mutation invalidates any taped producer

    # -- reductions (methods mirror reference NDArray methods) -------------
    def _reduce(self, fn, axis=None, keepdims=False):
        return invoke(lambda x: fn(x, axis=axis, keepdims=keepdims), [self])

    def sum(self, axis=None, keepdims=False):
        return self._reduce(jnp.sum, axis, keepdims)

    def mean(self, axis=None, keepdims=False):
        return self._reduce(jnp.mean, axis, keepdims)

    def max(self, axis=None, keepdims=False):
        return self._reduce(jnp.max, axis, keepdims)

    def min(self, axis=None, keepdims=False):
        return self._reduce(jnp.min, axis, keepdims)

    def prod(self, axis=None, keepdims=False):
        return self._reduce(jnp.prod, axis, keepdims)

    def argmax(self, axis=None, keepdims=False):
        return invoke(lambda x: jnp.argmax(x, axis=axis,
                                           keepdims=keepdims).astype(jnp.float32),
                      [self])

    def argmin(self, axis=None, keepdims=False):
        return invoke(lambda x: jnp.argmin(x, axis=axis,
                                           keepdims=keepdims).astype(jnp.float32),
                      [self])

    def norm(self, ord=2, axis=None, keepdims=False):
        return invoke(lambda x: jnp.linalg.norm(
            x.reshape(-1) if axis is None else x, ord=ord,
            axis=axis, keepdims=keepdims), [self])

    def clip(self, a_min=None, a_max=None):
        return invoke(lambda x: jnp.clip(x, a_min, a_max), [self])

    # -- elementwise method forms -------------------------------------------
    def abs(self):
        return invoke(jnp.abs, [self])

    def exp(self):
        return invoke(jnp.exp, [self])

    def log(self):
        return invoke(jnp.log, [self])

    def sqrt(self):
        return invoke(jnp.sqrt, [self])

    def square(self):
        return invoke(jnp.square, [self])

    def sign(self):
        return invoke(jnp.sign, [self])

    def round(self):
        return invoke(jnp.round, [self])

    def floor(self):
        return invoke(jnp.floor, [self])

    def ceil(self):
        return invoke(jnp.ceil, [self])

    def sigmoid(self):
        return invoke(jax.nn.sigmoid, [self])

    def tanh(self):
        return invoke(jnp.tanh, [self])

    def relu(self):
        return invoke(jax.nn.relu, [self])

    def softmax(self, axis=-1):
        return invoke(lambda x: jax.nn.softmax(x, axis=axis), [self])

    def log_softmax(self, axis=-1):
        return invoke(lambda x: jax.nn.log_softmax(x, axis=axis), [self])

    def one_hot(self, depth, on_value=1.0, off_value=0.0):
        from . import nd
        return nd.one_hot(self, depth, on_value, off_value)

    def dot(self, other):
        from . import nd
        return nd.dot(self, other)

    # -- binary arithmetic ---------------------------------------------------
    def _binary(self, other, fn, reverse=False):
        if isinstance(other, NDArray):
            a, b = (other, self) if reverse else (self, other)
            return invoke(fn, [a, b])
        if reverse:
            return invoke(lambda x: fn(other, x), [self])
        return invoke(lambda x: fn(x, other), [self])

    def __add__(self, o):
        return self._binary(o, operator.add)

    def __radd__(self, o):
        return self._binary(o, operator.add, True)

    def __sub__(self, o):
        return self._binary(o, operator.sub)

    def __rsub__(self, o):
        return self._binary(o, operator.sub, True)

    def __mul__(self, o):
        return self._binary(o, operator.mul)

    def __rmul__(self, o):
        return self._binary(o, operator.mul, True)

    def __truediv__(self, o):
        return self._binary(o, operator.truediv)

    def __rtruediv__(self, o):
        return self._binary(o, operator.truediv, True)

    def __floordiv__(self, o):
        return self._binary(o, operator.floordiv)

    def __mod__(self, o):
        return self._binary(o, operator.mod)

    def __pow__(self, o):
        return self._binary(o, operator.pow)

    def __rpow__(self, o):
        return self._binary(o, operator.pow, True)

    def __matmul__(self, o):
        return self._binary(o, jnp.matmul)

    def __neg__(self):
        return invoke(operator.neg, [self])

    def __abs__(self):
        return self.abs()

    # in-place: rebind _data (functional under the hood)
    def _inplace(self, other, fn):
        res = self._binary(other, fn)
        self._data, self._node = res._data, res._node
        if res._node is not None:
            res._node.outputs = [self]
        return self

    def __iadd__(self, o):
        return self._inplace(o, operator.add)

    def __isub__(self, o):
        return self._inplace(o, operator.sub)

    def __imul__(self, o):
        return self._inplace(o, operator.mul)

    def __itruediv__(self, o):
        return self._inplace(o, operator.truediv)

    # comparisons (non-differentiable; emit float32 masks like the reference)
    def _compare(self, other, fn):
        if isinstance(other, NDArray):
            other = other._data
        with autograd.pause():
            return invoke(lambda x: fn(x, other).astype(jnp.float32), [self])

    def __eq__(self, o):
        return self._compare(o, operator.eq)

    def __ne__(self, o):
        return self._compare(o, operator.ne)

    def __lt__(self, o):
        return self._compare(o, operator.lt)

    def __le__(self, o):
        return self._compare(o, operator.le)

    def __gt__(self, o):
        return self._compare(o, operator.gt)

    def __ge__(self, o):
        return self._compare(o, operator.ge)

    def __hash__(self):
        return id(self)


# -- creation ---------------------------------------------------------------
def _make(raw, ctx):
    ctx = ctx or current_context()
    return NDArray(raw, ctx=ctx, _place=True)


def from_dlpack(ext, ctx=None) -> NDArray:
    """NDArray from any DLPack-exporting object — a legacy capsule, or
    an object with __dlpack__ (torch tensor, numpy array, jax array,
    or another NDArray). Zero-copy when the producer's buffer is
    already on a compatible device (reference: python/mxnet/dlpack.py
    from_dlpack)."""
    import jax

    if type(ext).__name__ == "PyCapsule":
        # modern jax only consumes the __dlpack__ protocol; adapt the
        # reference's capsule form (capsules carry no device info —
        # the legacy contract was host memory)
        class _CapsuleHolder:
            def __init__(self, cap):
                self._cap = cap

            def __dlpack__(self, stream=None, **kw):
                return self._cap

            def __dlpack_device__(self):
                return (1, 0)  # kDLCPU

        ext = _CapsuleHolder(ext)
    raw = jax.dlpack.from_dlpack(ext)
    return NDArray(raw, ctx=ctx)


def array(source, ctx=None, dtype=None) -> NDArray:
    if isinstance(source, NDArray):
        raw = source._data
        if dtype is not None:
            raw = raw.astype(resolve_dtype(dtype))
        return _make(raw, ctx)
    if dtype is None:
        is_np = isinstance(source, _np.ndarray)
        src = _np.asarray(source)
        if not is_np and not hasattr(source, "dtype"):
            dtype = _np.float32  # python lists default to f32 (reference)
        elif src.dtype == _np.float64:
            dtype = _np.float32
        elif src.dtype == _np.int64 and not jax.config.jax_enable_x64:
            dtype = _np.int32
        else:
            dtype = src.dtype
        raw = jnp.asarray(src, dtype=dtype)
    else:
        raw = jnp.asarray(_np.asarray(source), dtype=resolve_dtype(dtype))
    return _make(raw, ctx)


def from_numpy(a, zero_copy=False):
    return array(a)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx, dtype)


def zeros(shape, ctx=None, dtype=None):
    if isinstance(shape, int):
        shape = (shape,)
    return _make(jnp.zeros(shape, resolve_dtype(dtype)), ctx)


def ones(shape, ctx=None, dtype=None):
    if isinstance(shape, int):
        shape = (shape,)
    return _make(jnp.ones(shape, resolve_dtype(dtype)), ctx)


def full(shape, val, ctx=None, dtype=None):
    if isinstance(shape, int):
        shape = (shape,)
    return _make(jnp.full(shape, val, resolve_dtype(dtype)), ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    r = jnp.arange(start, stop, step, dtype=resolve_dtype(dtype))
    if repeat > 1:
        r = jnp.repeat(r, repeat)
    return _make(r, ctx)


def eye(N, M=None, k=0, ctx=None, dtype=None):
    return _make(jnp.eye(N, M, k, dtype=resolve_dtype(dtype)), ctx)


def linspace(start, stop, num, endpoint=True, ctx=None, dtype=None):
    return _make(jnp.linspace(start, stop, num, endpoint=endpoint,
                              dtype=resolve_dtype(dtype)), ctx)


def zeros_like(a):
    return invoke(jnp.zeros_like, [a])


def ones_like(a):
    return invoke(jnp.ones_like, [a])


def full_like(a, fill_value):
    return invoke(lambda x: jnp.full_like(x, fill_value), [a])


def concat(*arys, dim=1, axis=None):
    if len(arys) == 1 and isinstance(arys[0], (list, tuple)):
        arys = tuple(arys[0])
    ax = dim if axis is None else axis
    return invoke(lambda *xs: jnp.concatenate(xs, axis=ax), list(arys))


def stack(*arys, axis=0):
    if len(arys) == 1 and isinstance(arys[0], (list, tuple)):
        arys = tuple(arys[0])
    return invoke(lambda *xs: jnp.stack(xs, axis=axis), list(arys))


def waitall():
    """Block until all dispatched work completes (reference: mx.nd.waitall)."""
    (jax.device_put(0.0) + 0).block_until_ready()
    try:
        jax.effects_barrier()
    except Exception:
        pass
