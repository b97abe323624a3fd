"""Shared helpers: dtype handling, shape utilities.

Reference parity: mxnet/base.py (ctypes plumbing in the reference; here the
"C API" boundary is jax, so this file only keeps dtype/shape conventions).
"""
from __future__ import annotations

import numpy as _np

import jax.numpy as jnp

# MXNet dtype names -> jnp dtypes (reference: mshadow type enum).
_DTYPE_ALIASES = {
    "float32": jnp.float32,
    "float64": jnp.float64,
    "float16": jnp.float16,
    "bfloat16": jnp.bfloat16,
    "uint8": jnp.uint8,
    "int8": jnp.int8,
    "int16": jnp.int16,
    "int32": jnp.int32,
    "int64": jnp.int64,
    "bool": jnp.bool_,
}


def resolve_dtype(dtype):
    """Accept strings, numpy dtypes, jnp dtypes; return a canonical jnp dtype."""
    if dtype is None:
        return jnp.float32
    if isinstance(dtype, str):
        if dtype in _DTYPE_ALIASES:
            return _DTYPE_ALIASES[dtype]
        return jnp.dtype(dtype)
    return jnp.dtype(dtype) if not hasattr(dtype, "dtype") else dtype


def dtype_name(dtype) -> str:
    d = jnp.dtype(dtype)
    if d == jnp.bfloat16:
        return "bfloat16"
    return d.name


def normalize_axis(axis, ndim):
    if axis is None:
        return None
    if isinstance(axis, (list, tuple)):
        return tuple(a % ndim if a is not None else None for a in axis)
    return axis % ndim


def as_tuple(x, n=None):
    """Int -> (x,)*n ; tuple passthrough (kernel/stride/pad normalization)."""
    if isinstance(x, (tuple, list)):
        return tuple(x)
    if n is None:
        return (x,)
    return (x,) * n


def numpy_asarray(x):
    return _np.asarray(x)
