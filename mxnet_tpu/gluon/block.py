"""Block / HybridBlock (reference: mxnet/gluon/block.py).

TPU-first core: `hybridize()` does what the reference's CachedOp + NNVM
graph passes do, but through XLA — the block's imperative `forward` is traced
once per (input-signature, train-mode) into a pure function
`fn(trainable_params, aux_params, rng_key, *inputs) -> (outputs, new_aux)`
and jit-compiled. Parameter binding happens by temporarily swapping each
Parameter's backing jax array for a tracer, so user code is identical in
eager and compiled mode (BatchNorm's running-stat mutation surfaces as the
functional `new_aux` output). Under autograd.record the whole compiled graph
becomes ONE tape node via jax.vjp — the CachedOp-backward analogue.
"""
from __future__ import annotations

import contextlib
import time as _time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import numpy as _np

import jax
import jax.numpy as jnp
from jax import typeof as _typeof

from .. import autograd
from .. import random as _random
from ..ndarray import NDArray
from .parameter import Parameter, ParameterDict, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "Sequential", "HybridSequential",
           "SymbolBlock", "Lambda", "HybridLambda", "Identity"]


def _flatten_nd(obj):
    """Flatten a nested structure of NDArrays -> (leaves, treedef)."""
    leaves, treedef = jax.tree_util.tree_flatten(
        obj, is_leaf=lambda x: isinstance(x, NDArray))
    return leaves, treedef


class Block:
    """Imperative building block (reference: gluon.Block)."""

    def __init__(self, prefix=None, params=None):
        self._prefix = prefix or ""
        self._children: "OrderedDict[str, Block]" = OrderedDict()
        self._reg_params: Dict[str, Parameter] = {}
        self._forward_hooks: List = []
        self._forward_pre_hooks: List = []

    # -- attribute registration (reference: Block.__setattr__) -------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            self.__dict__.setdefault("_children", OrderedDict())
            self._children[name] = value
        elif isinstance(value, Parameter):
            self.__dict__.setdefault("_reg_params", {})
            self._reg_params[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        name = name or str(len(self._children))
        self._children[name] = block

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._prefix.rstrip("_") or type(self).__name__.lower()

    @contextlib.contextmanager
    def name_scope(self):
        """Reference-API compat; naming is attribute-path based here
        (matching the reference's save_parameters convention)."""
        yield self

    @property
    def params(self) -> ParameterDict:
        d = ParameterDict()
        for n, p in self._reg_params.items():
            d._params[n] = p
        return d

    def collect_params(self, select=None) -> ParameterDict:
        """Attribute-path-keyed parameters (reference:
        _collect_params_with_prefix, the save_parameters naming)."""
        import re
        out = ParameterDict()

        def walk(block, path):
            for n, p in block._reg_params.items():
                key = f"{path}{n}" if not path else f"{path}.{n}"
                if key not in out._params:
                    p.name = p.name if p.name and p.name != "param" else key
                    out._params[key] = p
            for cn, c in block._children.items():
                walk(c, f"{path}.{cn}" if path else cn)

        walk(self, "")
        if select:
            pat = re.compile(select)
            filtered = ParameterDict()
            for k, v in out.items():
                if pat.match(k):
                    filtered._params[k] = v
            return filtered
        return out

    # -- lifecycle ----------------------------------------------------------
    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        self.collect_params().initialize(init=init, ctx=ctx,
                                         force_reinit=force_reinit)
        return self

    def cast(self, dtype):
        for p in self.collect_params().values():
            p.cast(dtype)
        for c in self._children.values():
            pass  # params already covered by collect_params
        return self

    def apply(self, fn):
        for c in self._children.values():
            c.apply(fn)
        fn(self)
        return self

    # -- hooks ---------------------------------------------------------------
    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)
        return hook

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return hook

    # -- io ------------------------------------------------------------------
    def save_parameters(self, filename, deduplicate=False):
        """Flat .params file keyed by attribute path (reference format
        semantics; container is npz)."""
        self.collect_params().save(filename)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False):
        self.collect_params().load(filename, ctx=ctx,
                                   allow_missing=allow_missing,
                                   ignore_extra=ignore_extra)

    save_params = save_parameters
    load_params = load_parameters

    # -- execution -----------------------------------------------------------
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def summary(self, *inputs):
        params = self.collect_params()
        total = 0
        lines = [f"{'Parameter':<60}{'Shape':<24}{'#':>12}"]
        for k, p in params.items():
            n = int(_np.prod(p.shape)) if p.shape else 0
            total += n
            lines.append(f"{k:<60}{str(p.shape):<24}{n:>12}")
        lines.append(f"{'TOTAL':<84}{total:>12}")
        print("\n".join(lines))
        return total

    def __repr__(self):
        mods = "\n".join(f"  ({n}): {type(c).__name__}"
                         for n, c in self._children.items())
        return f"{type(self).__name__}(\n{mods}\n)"


class _CacheEntry:
    __slots__ = ("jit_fn", "raw_fn", "tr_names", "aux_names", "tensor_pos",
                 "out_treedef", "n_out", "_example_avals")

    def __init__(self, jit_fn, tr_names, aux_names, tensor_pos):
        self.jit_fn = jit_fn
        self.raw_fn = None  # unjitted fn for composition (fused train step)
        self.tr_names = tr_names
        self.aux_names = aux_names
        self.tensor_pos = tensor_pos
        self.out_treedef = None
        self.n_out = None
        self._example_avals = None  # recorded on first call (tracing.py)


class HybridBlock(Block):
    """Block that can compile to a single XLA executable via hybridize()."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)
        self.__dict__["_active"] = False
        self.__dict__["_jit_cache"] = {}
        self.__dict__["_cached_params"] = None

    def hybridize(self, active=True, **kwargs):
        self._active = active
        self._jit_cache = {}
        self._cached_params = None
        for c in self._children.values():
            if isinstance(c, HybridBlock):
                # children stay eager; the top-level trace subsumes them,
                # but mark for API parity
                c._active = False
        return self

    def infer_shape(self, *args):
        """Run a shape-inference forward (completes deferred params)."""
        with autograd.pause():
            self.forward(*args)

    def optimize_for(self, *args, backend=None, **kwargs):
        self.hybridize(True)
        if args:
            self(*args)
        return self

    def export(self, path, epoch=0, platforms=None):
        """Dump the compiled graph + params — the tracing/EXPORT
        subsystem (reference: HybridBlock.export to symbol.json/params,
        ONNX export role). Writes:

        - `{path}-symbol.txt`: human-readable StableHLO (inspection)
        - `{path}-{epoch:04d}.params`: flat parameter file
        - `{path}-module.bin` + `{path}-module.json`: a SERIALIZED
          serving artifact (jax.export) + manifest — reloadable with
          `SymbolBlock.imports` in a fresh process WITHOUT the Python
          model class. The serving trace is the predict-mode entry
          when one exists (RNG baked: dropout is off in predict mode);
          `platforms` (e.g. ["cpu", "tpu"]) makes the artifact
          portable across backends at export-time cost.
        """
        if not self._jit_cache:
            raise RuntimeError("call the hybridized block once before "
                               "export()")
        import json as _json
        import os as _os

        from .. import tracing as _tracing

        first = next(iter(self._jit_cache.values()))
        with open(f"{path}-symbol.txt", "w") as f:
            f.write(_tracing.lower_text(first))
        params_file = f"{path}-{epoch:04d}.params"
        self.save_parameters(params_file)

        # serving artifact: prefer a predict-mode trace (cache key[0]
        # is the training flag)
        serve_entry = None
        for key, e in self._jit_cache.items():
            if key[0] is False:
                serve_entry = e
                break
        if serve_entry is None:
            import warnings

            warnings.warn(
                "export(): no predict-mode trace in the jit cache — "
                "the serving artifact will bake the TRAINING trace "
                "(active dropout with a fixed mask, batch-stat "
                "norm). Run one forward under "
                "autograd.predict_mode() before export().",
                RuntimeWarning, stacklevel=2)
        serve_entry = serve_entry or first
        avals = getattr(serve_entry, "_example_avals", None)
        if avals is not None:
            from jax import export as _jax_export

            tr_sds, aux_sds, _rng_sds, *in_sds = avals
            # constant key, NOT _random.next_key(): consuming the
            # global stream here would shift every later random draw,
            # making training runs irreproducible just because they
            # exported (the key is unused in a predict-mode trace)
            fixed_key = jax.random.PRNGKey(0)
            tr_names = list(serve_entry.tr_names)
            aux_names = list(serve_entry.aux_names)

            def serve(tr_list, aux_list, *inputs):
                tr = dict(zip(tr_names, tr_list))
                aux = dict(zip(aux_names, aux_list))
                flat, _ = serve_entry.raw_fn(tr, aux, fixed_key,
                                             *inputs)
                return flat

            if isinstance(platforms, str):
                platforms = [platforms]
            exp = _jax_export.export(
                jax.jit(serve),
                platforms=list(platforms) if platforms else None)(
                    [tr_sds[n] for n in tr_names],
                    [aux_sds[n] for n in aux_names], *in_sds)
            with open(f"{path}-module.bin", "wb") as f:
                f.write(exp.serialize())
            with open(f"{path}-module.json", "w") as f:
                _json.dump({
                    "format": "mxnet_tpu-module-v1",
                    "tr_names": tr_names,
                    "aux_names": aux_names,
                    "n_inputs": len(in_sds),
                    "out_tree": _encode_treedef(serve_entry.out_treedef),
                    "params_file": _os.path.basename(params_file),
                }, f, indent=1)
        return f"{path}-symbol.txt"

    # -- compiled call path --------------------------------------------------
    def __call__(self, *args, **kwargs):
        if not self._active or kwargs:
            return super().__call__(*args, **kwargs)
        return self._call_cached(*args)

    def _get_params(self):
        if self._cached_params is None:
            self._cached_params = self.collect_params()
        return self._cached_params

    def _call_cached(self, *args):
        params = self._get_params()
        # deferred init → one eager forward infers shapes
        for p in params.values():
            if p._data is None:
                if p._deferred is None:
                    raise RuntimeError(f"{p.name} not initialized")
                return super().__call__(*args)
        training = autograd.is_training()
        key_parts = [training]
        tensor_pos = []
        for i, a in enumerate(args):
            if isinstance(a, NDArray):
                tensor_pos.append(i)
                key_parts.append((a.shape, str(a._data.dtype)))
            else:
                key_parts.append(("static", repr(a)))
        cache_key = tuple(key_parts)
        entry = self._jit_cache.get(cache_key)
        fresh = entry is None
        if fresh:
            # the fresh-call wall time IS the compile cost for this
            # shape signature: trace + XLA build + first run all happen
            # inside this call (jit compiles lazily on first execution)
            t0_compile = _time.perf_counter()
            entry = self._build(tuple(tensor_pos), args, training, params)
            self._jit_cache[cache_key] = entry

        tr = {n: params[n].data()._data for n in entry.tr_names}
        aux = {n: params[n].data()._data for n in entry.aux_names}
        rng = _random.next_key()
        tensor_raw = [args[i]._data for i in entry.tensor_pos]

        from .. import tracing as _tracing
        if fresh:
            sds = lambda t: jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
            entry._example_avals = (sds(tr), sds(aux), sds(rng),
                                    *[sds(t) for t in tensor_raw])
            _tracing.record_compile(self.name or type(self).__name__,
                                    entry)
        else:
            _tracing.record_hit(self.name or type(self).__name__)

        if autograd.is_recording():
            f = lambda tr_, *ins: entry.jit_fn(tr_, aux, rng, *ins)
            out_flat, vjp_fn, new_aux = jax.vjp(f, tr, *tensor_raw,
                                                has_aux=True)
            parents = [params[n].data() for n in entry.tr_names] + \
                [args[i] for i in entry.tensor_pos]
            tr_names = entry.tr_names

            def node_vjp(cots):
                cot_in = cots if entry.n_out > 1 else (cots,)
                g_tr, *g_inputs = vjp_fn(tuple(cot_in))
                return tuple(g_tr[n] for n in tr_names) + tuple(g_inputs)

            def node_bwd(primals, cots, _entry=entry, _aux=aux, _rng=rng,
                         _names=tr_names):
                # differentiable replay for grad(create_graph=True):
                # re-derive the vjp from the primals so the backward is
                # itself jax-traceable (autograd._backward_on_tape)
                ntr = len(_names)
                tr_ = dict(zip(_names, primals[:ntr]))
                _, vjp, _ = jax.vjp(
                    lambda t, *i: _entry.jit_fn(t, _aux, _rng, *i),
                    tr_, *primals[ntr:], has_aux=True)
                g_tr, *g_inputs = vjp(tuple(cots))
                return tuple(g_tr[n] for n in _names) + tuple(g_inputs)

            node = autograd.Node(
                node_vjp, parents, entry.n_out, bwd_fn=node_bwd,
                primals=tuple(tr[n] for n in tr_names) + tuple(tensor_raw))
        else:
            out_flat, new_aux = entry.jit_fn(tr, aux, rng, *tensor_raw)
            node = None

        for n in entry.aux_names:
            params[n].data()._data = new_aux[n]

        outs = []
        for r in out_flat:
            o = NDArray(r)
            o._node = node
            outs.append(o)
        if node is not None:
            node.outputs = outs
            node.out_avals = [_typeof(r) for r in out_flat]
        if fresh:
            _tracing.record_compile_seconds(
                self.name or type(self).__name__,
                _time.perf_counter() - t0_compile)
        return jax.tree_util.tree_unflatten(entry.out_treedef, outs)

    def _build(self, tensor_pos, proto_args, training, params):
        tr_names = [n for n, p in params.items() if p.grad_req != "null"]
        aux_names = [n for n, p in params.items() if p.grad_req == "null"]
        static_args = {i: a for i, a in enumerate(proto_args)
                       if i not in tensor_pos}
        n_args = len(proto_args)
        block = self
        entry = _CacheEntry(None, tr_names, aux_names, list(tensor_pos))

        def fn(tr, aux, rng_key, *tensor_args):
            saved = {n: params[n]._data._data for n in tr_names + aux_names}
            try:
                for n in tr_names:
                    params[n]._data._data = tr[n]
                for n in aux_names:
                    params[n]._data._data = aux[n]
                call_args = []
                ti = 0
                for i in range(n_args):
                    if i in static_args:
                        call_args.append(static_args[i])
                    else:
                        call_args.append(NDArray(tensor_args[ti]))
                        ti += 1
                with autograd._mode(False, training), \
                        _random.trace_key(rng_key):
                    out = Block.__call__(block, *call_args)
                leaves, treedef = _flatten_nd(out)
                entry.out_treedef = treedef
                entry.n_out = len(leaves)
                new_aux = {n: params[n]._data._data for n in aux_names}
                return tuple(l._data if isinstance(l, NDArray) else l
                             for l in leaves), new_aux
            finally:
                for n, v in saved.items():
                    params[n]._data._data = v

        entry.raw_fn = fn
        entry.jit_fn = jax.jit(fn)
        return entry

    def trace_entry(self, proto_args, training=True):
        """Public composition hook: returns a _CacheEntry whose raw_fn
        (tr_params, aux_params, rng_key, *tensors) -> (flat_outs, new_aux)
        is unjitted — the fused train step (parallel/) differentiates and
        shards it inside a single larger jit."""
        params = self._get_params()
        if any(p._data is None for p in params.values()):
            # materialize deferred shapes with one eager forward, like
            # _call_cached does, so raw_fn never sees uninitialized params
            with autograd.pause():
                Block.__call__(self, *proto_args)
            self._cached_params = None
            params = self._get_params()
            still = [n for n, p in params.items() if p._data is None]
            if still:
                raise RuntimeError(
                    f"parameters not initialized before trace_entry: "
                    f"{still}; call net.initialize() first")
        tensor_pos = tuple(i for i, a in enumerate(proto_args)
                           if isinstance(a, NDArray))
        return self._build(tensor_pos, proto_args, training, params)


class Sequential(Block):
    """reference: gluon.nn.Sequential."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix, params)

    def add(self, *blocks):
        for b in blocks:
            self.register_child(b)
        return self

    def forward(self, x, *args):
        for b in self._children.values():
            x = b(x, *args)
            args = ()
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            s = type(self)()
            for b in list(self._children.values())[idx]:
                s.add(b)
            return s
        return list(self._children.values())[idx]

    def __iter__(self):
        return iter(self._children.values())


class HybridSequential(HybridBlock, Sequential):
    """reference: gluon.nn.HybridSequential."""

    def __init__(self, prefix=None, params=None):
        HybridBlock.__init__(self, prefix, params)

    def pipeline_stages(self, pp, sample, cost_model="flops"):
        """Cut this chain of shape-preserving blocks into `pp` balanced
        pipeline stages (parallel.pipeline.pipeline_stages): the
        returned StagedPipeline carries stage-stacked params and a
        stage_fn for the gpipe/one_f_one_b schedules and for
        FusedTrainStep(pipeline=M)."""
        from ..parallel.pipeline import pipeline_stages
        return pipeline_stages(self, pp, sample=sample,
                               cost_model=cost_model)


class Lambda(Block):
    def __init__(self, function):
        super().__init__()
        self._fn = function

    def forward(self, *args):
        return self._fn(*args)


class HybridLambda(HybridBlock):
    def __init__(self, function):
        super().__init__()
        self._fn = function

    def forward(self, *args):
        return self._fn(*args)


class Identity(HybridBlock):
    def forward(self, x):
        return x


def _encode_treedef(treedef):
    """JSON-encodable skeleton of an output pytree (tuple/list/dict
    containers, integer leaf indices). Exotic container types fall
    back to None → the importer returns the flat leaf list."""
    try:
        skel = jax.tree_util.tree_unflatten(
            treedef, list(range(treedef.num_leaves)))

        def enc(x):
            if isinstance(x, tuple):
                if hasattr(x, "_fields"):  # namedtuple: a plain-tuple
                    raise TypeError(type(x))  # round trip would lose
                return {"t": [enc(v) for v in x]}  # .field access
            if isinstance(x, list):
                return {"l": [enc(v) for v in x]}
            if isinstance(x, dict):
                if any(not isinstance(k, str) for k in x):
                    raise TypeError("non-str dict key")  # json would
                return {"d": {k: enc(v) for k, v in x.items()}}  # cast
            if isinstance(x, int):
                return x
            raise TypeError(type(x))

        return enc(skel)
    except Exception:
        return None


def _decode_treedef(node, leaves):
    if isinstance(node, int):
        return leaves[node]
    if "t" in node:
        return tuple(_decode_treedef(v, leaves) for v in node["t"])
    if "l" in node:
        return [_decode_treedef(v, leaves) for v in node["l"]]
    return {k: _decode_treedef(v, leaves)
            for k, v in node["d"].items()}


class SymbolBlock(Block):
    """Reference: gluon.SymbolBlock — both upstream forms:

    1. `SymbolBlock(outputs, inputs, params=...)` wraps an `mx.sym`
       graph as a Gluon block: free variables become Parameters (so
       autograd/Trainer work), inputs bind positionally.
    2. `SymbolBlock.imports(...)` reloads a `HybridBlock.export`
       artifact — a serialized jax.export module
       (`{prefix}-module.bin` + `.json` manifest) plus the flat
       .params file — and serves inference WITHOUT the original model
       class (upstream: imports(symbol.json, ['data'], params))."""

    def __init__(self, outputs=None, inputs=None, params=None, *,
                 _artifact=None):
        super().__init__()
        if _artifact is not None:
            exported, manifest, raw = _artifact
            self._exp = exported
            self._manifest = manifest
            self._tr = [jnp.asarray(raw[n])
                        for n in manifest["tr_names"]]
            self._aux = [jnp.asarray(raw[n])
                         for n in manifest["aux_names"]]
            self._symbolic = None
            return
        if outputs is None or inputs is None:
            raise ValueError(
                "SymbolBlock(outputs, inputs, params=...) wraps a "
                "symbol; SymbolBlock.imports(...) reloads an exported "
                "artifact")
        from .. import symbol as _symbol

        if isinstance(outputs, (list, tuple)):
            outputs = _symbol.Group(list(outputs))
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        in_names = [s.name if hasattr(s, "name") else str(s)
                    for s in inputs]
        self._symbolic = (outputs, in_names)
        params = dict(params.items()) if hasattr(params, "items") \
            else dict(params or {})
        # arguments become trainable Parameters; auxiliary-state names
        # (moving_mean/...) become grad_req='null' ones — upstream
        # SymbolBlock's split exactly
        free = [(n, "write") for n in outputs.list_arguments()
                if n not in in_names]
        free += [(n, "null") for n in outputs.list_auxiliary_states()
                 if n not in in_names]
        unknown = set(params) - {n for n, _ in free}
        if unknown:  # a typo'd name would otherwise surface later as
            raise ValueError(  # an unrelated deferred-init error
                f"params entries {sorted(unknown)} match no free "
                f"variable of the symbol (free: "
                f"{sorted(n for n, _ in free)})")
        from .. import initializer as _initializer

        for name, grad_req in free:
            p = Parameter(name, grad_req=grad_req,
                          allow_deferred_init=True)
            if name in params:
                v = params[name]
                if isinstance(v, Parameter):
                    v = v.data()  # SymbolBlock(..., net.collect_params())
                raw = v._data if isinstance(v, NDArray) \
                    else jnp.asarray(v)
                p.shape = tuple(raw.shape)
                p.dtype = raw.dtype  # keep set_data from upcasting a
                #                      non-fp32 param to the default
                # copy: aliasing the caller's array would let a
                # Trainer step on this block mutate it (and fused
                # steps donate buffers) — same rule as set_data
                p._data = NDArray(jnp.array(raw, copy=True))
                if p._grad_req != "null":  # same wiring as _init_impl:
                    p._data.attach_grad(p._grad_req)  # autograd sees it
            else:
                # stage a deferred init so the documented recipe —
                # collect_params()[name].set_data(...) before forward —
                # actually works (set_data finishes the deferred init
                # once the value's shape is known)
                p._deferred = (_initializer.Zero(), None)
            self._reg_params[name] = p

    @staticmethod
    def imports(symbol_file, input_names=None, param_file=None,
                ctx=None):
        """Load `{prefix}-module.bin` (accepts the `-symbol.txt` path
        too and resolves the sibling artifact). `input_names` is kept
        for reference-signature compatibility; inputs are positional.
        """
        import json as _json
        import os as _os

        from jax import export as _jax_export

        base = str(symbol_file)
        if base.endswith("-symbol.txt"):
            base = base[:-len("-symbol.txt")] + "-module.bin"
        with open(base, "rb") as f:
            blob = f.read()
        with open(base[:-len(".bin")] + ".json") as f:
            manifest = _json.load(f)
        if manifest.get("format") != "mxnet_tpu-module-v1":
            raise ValueError(f"not an exported module: {base}")
        if param_file is None:
            param_file = _os.path.join(_os.path.dirname(base) or ".",
                                       manifest["params_file"])
        with _np.load(param_file, allow_pickle=False) as z:
            params = {k: z[k] for k in z.files}
        return SymbolBlock(_artifact=(
            _jax_export.deserialize(bytearray(blob)), manifest, params))

    def forward(self, *inputs):
        if getattr(self, "_symbolic", None) is not None:
            outputs, in_names = self._symbolic
            if len(inputs) != len(in_names):
                raise ValueError(f"expected {len(in_names)} inputs "
                                 f"({in_names}), got {len(inputs)}")
            env = dict(zip(in_names, inputs))
            for name, p in self._reg_params.items():
                env[name] = p.data()
            # _eval directly: Symbol.eval(ctx=None, **bindings) would
            # swallow a variable literally named "ctx"
            out = outputs._eval(env, {})

            def _leaves(o):  # a multi-output op inside a Group yields
                if isinstance(o, tuple):  # nested tuples: flatten like
                    for v in o:  # upstream (each output separately,
                        yield from _leaves(v)  # never stacked)
                else:
                    yield o

            outs = [o if isinstance(o, NDArray)
                    else NDArray(jnp.asarray(o)) for o in _leaves(out)]
            return outs[0] if len(outs) == 1 else outs
        n = self._manifest["n_inputs"]
        if len(inputs) != n:
            raise ValueError(f"expected {n} inputs, got {len(inputs)}")
        raw = [x._data if isinstance(x, NDArray) else jnp.asarray(x)
               for x in inputs]
        flat = self._exp.call(self._tr, self._aux, *raw)
        outs = [NDArray(o) for o in flat]
        tree = self._manifest.get("out_tree")
        if tree is not None:  # restore the model's output structure
            return _decode_treedef(tree, outs)
        return outs[0] if len(outs) == 1 else outs
