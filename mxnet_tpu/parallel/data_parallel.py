"""Fused training step + data parallelism over the device mesh.

This is the TPU-first replacement for the reference's hot training loop
(CachedOp forward → engine backward → NCCL allreduce → fused SGD kernel;
src/imperative + src/kvstore/kvstore_nccl.cc): ONE jit compiles
forward + backward + gradient allreduce + optimizer update, with buffers
donated, so a training step is a single XLA executable. Data parallelism is
sharding, not message passing — the batch carries PartitionSpec('dp', ...)
and XLA inserts the gradient AllReduce over ICI during the backward pass.
"""
from __future__ import annotations

import collections
import time as _time
from typing import Callable, Optional, Sequence

import numpy as _np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import autograd
from .. import faults as _ft
from .. import flight as _fl
from .. import goodput as _gp
from .. import random as _random
from .. import telemetry as _tm
from .. import tracing as _tracing
from ..ndarray import NDArray
from .mesh import current_mesh, use_mesh

__all__ = ["FusedTrainStep", "ShardedForward", "split_batch_spec"]


def _normalize_wire_cfg(cfg, direction):
    """Validate/normalize one weights/activations wire-compression entry
    of the widened ``compression={"weights":..., "activations":...,
    "grads":...}`` config. Accepts a scheme string or a dict; returns
    ``{"type", "block", "residual"}``. 2-bit is rejected outright: it
    needs error-feedback state to converge, which the stateless
    per-step gather/permute transport cannot carry for non-owned
    slices."""
    if cfg is None:
        return None
    from .compression import DEFAULT_BLOCK, WIRE_SCHEMES
    if isinstance(cfg, str):
        cfg = {"type": cfg}
    cfg = dict(cfg)
    ctype = cfg.get("type", "int8")
    if ctype not in WIRE_SCHEMES:
        raise ValueError(
            f"{direction} wire compression supports {WIRE_SCHEMES}; "
            f"got {ctype!r} (the 2-bit scheme is gradient-only: it "
            "relies on error feedback, which per-step weight/"
            "activation transport cannot carry)")
    return {"type": ctype,
            "block": int(cfg.get("block", DEFAULT_BLOCK)),
            "residual": bool(cfg.get("residual", False))}


def split_batch_spec(ndim: int, axis: int = 0, dp_axis: str = "dp"):
    spec = [None] * ndim
    spec[axis] = dp_axis
    return P(*spec)


def _global_put(v, sh):
    """device_put that works on multi-process meshes: a committed
    process-local array cannot be resharded onto a global mesh (jax
    raises on the cross-host transfer), but its VALUE is identical on
    every process (replicated init / host numpy), so round-trip through
    the host and let device_put write only the addressable shards."""
    try:
        return jax.device_put(v, sh)
    except ValueError:
        return jax.device_put(_np.asarray(v), sh)


def _unshard(v):
    """Gather a (possibly mesh-sharded) array to one replicated value."""
    if not hasattr(v, "sharding") or len(v.sharding.device_set) <= 1:
        return v
    if v.sharding.is_fully_replicated:
        # one shard already holds the full value — no host copy
        return v.addressable_shards[0].data
    if not v.is_fully_addressable:  # multi-host (TPU pod) case
        from jax.experimental import multihost_utils
        return jnp.asarray(
            multihost_utils.process_allgather(v, tiled=True))
    return jnp.asarray(_np.asarray(v))  # gather sharded dims


def _shards_nothing(spec, mesh):
    """True when `spec` names only axes `mesh` lacks or has at size 1:
    an annotation that shards nothing here. The Llama blocks carry
    P("tp", ...) whatever plan they run under, and a dp-only plan must
    not read that as tensor parallelism."""
    from .mesh import axis_size
    names = [a for part in spec if part is not None
             for a in (part if isinstance(part, tuple) else (part,))]
    return all(axis_size(mesh, a) == 1 for a in names)


def _param_shardings(params, names, mesh):
    """NamedSharding per parameter: its Parameter.sharding spec, else
    replicated."""
    return {n: NamedSharding(mesh, params[n].sharding
                             if params[n].sharding is not None else P())
            for n in names}


def _batch_shardings(args, mesh, dp_axis):
    """Batch args sharded over `dp_axis` on dim 0 (replicated when the
    mesh has no such axis, e.g. a tp-only mesh)."""
    dp = dp_axis if dp_axis in mesh.axis_names else None
    return tuple(
        NamedSharding(mesh, split_batch_spec(
            _np.ndim(a._data if isinstance(a, NDArray) else a), 0, dp))
        for a in args)


class ShardedForward:
    """Mesh-sharded inference: jit the traced forward with parameter
    shardings (Parameter.sharding, replicated otherwise) and the batch
    split over `dp_axis`. The inference twin of FusedTrainStep — tensor-
    parallel layers' sharding constraints only bind inside this compiled
    region."""

    def __init__(self, net, mesh: Optional[Mesh] = None,
                 dp_axis: str = "dp", training: bool = False):
        self.net = net
        self.mesh = mesh if mesh is not None else current_mesh()
        if self.mesh is None:
            raise ValueError(
                "ShardedForward needs an active mesh (pass mesh= or "
                "parallel.set_mesh(...)); for single-device inference "
                "just call the net (hybridized) directly")
        self.dp_axis = dp_axis
        self.training = training
        self._compiled = None
        self._entry = None
        self._seen = {}  # param name -> host array last placed

    def _build(self, args):
        mesh = self.mesh
        params = self.net.collect_params()
        if any(p._data is None for p in params.values()):
            with autograd.pause():
                self.net(*args)
            params = self.net.collect_params()
        with use_mesh(mesh):
            entry = self.net.trace_entry(list(args),
                                         training=self.training)
        self._entry = entry
        tr_sh = _param_shardings(params, entry.tr_names, mesh)
        aux_sh = _param_shardings(params, entry.aux_names, mesh)
        batch_sh = _batch_shardings(args, mesh, self.dp_axis)
        repl = NamedSharding(mesh, P())

        def fwd(tr, aux, key, *batch):
            flat, _ = entry.raw_fn(tr, aux, key, *batch)
            return flat

        self._compiled = jax.jit(
            fwd, in_shardings=(tr_sh, aux_sh, repl, *batch_sh))
        self._params = params
        self._tr_sh, self._aux_sh = tr_sh, aux_sh
        self._tr, self._aux = {}, {}
        self._refresh()
        self._batch_sh = batch_sh

    def _refresh(self):
        """(Re-)place any parameter whose host array changed since the
        last call (e.g. set_data / load_parameters between evals)."""
        for names, store, shs in ((self._entry.tr_names, self._tr,
                                   self._tr_sh),
                                  (self._entry.aux_names, self._aux,
                                   self._aux_sh)):
            for n in names:
                v = self._params[n].data()._data
                if self._seen.get(n) is not v:
                    store[n] = jax.device_put(v, shs[n])
                    self._seen[n] = v

    def __call__(self, *args):
        if self._compiled is None:
            self._build(args)
        else:
            self._refresh()
        key = _random.next_key()
        raw = [jax.device_put(
            a._data if isinstance(a, NDArray) else jnp.asarray(a), sh)
            for a, sh in zip(args, self._batch_sh)]
        with use_mesh(self.mesh):
            flat = self._compiled(self._tr, self._aux, key, *raw)
        out = jax.tree_util.tree_unflatten(
            self._entry.out_treedef, [NDArray(f) for f in flat])
        return out


class FusedTrainStep:
    """Compile net+loss+optimizer into one XLA executable.

    Usage:
        step = FusedTrainStep(net, loss_fn, trainer, mesh=mesh)
        loss = step(x, y)          # one fused device step
        step.sync_to_params()      # write weights back for checkpointing

    `trainer` may be a gluon.Trainer or a raw mx.optimizer.Optimizer.
    With a mesh, batch args are sharded over `dp_axis` and parameters are
    replicated (pure DP); parameters whose Parameter.sharding is set keep
    their own PartitionSpec (tensor parallelism composes — see
    tensor_parallel.py).
    """

    def __init__(self, net, loss_fn, trainer, mesh: Optional[Mesh] = None,
                 dp_axis: str = "dp", donate: bool = True,
                 n_model_inputs: int = 1, grad_accum: int = 1,
                 compression=None, zero1: bool = False, zero=None,
                 pipeline=None, pp_axis: str = "pp", plan=None,
                 virtual: int = 1, counts=None):
        from ..gluon.trainer import Trainer
        self.net = net
        self.loss_fn = loss_fn
        # counts=(names...): the net's LAST output is a small int32
        # vector the step hands back beside the loss (`loss_fn` never
        # sees it). A step's counts are read once its arrays are ready
        # — never by waiting on the step just launched — and ride the
        # `mx.train_step` span of the call that found them
        self._count_names = tuple(counts or ())
        self._counts_pending = collections.deque()
        # plan mode: a validated ParallelPlan drives the composition —
        # the legacy warn-once degrade matrices below are BYPASSED
        # (the plan already rejected every unfusable combination loudly)
        # and the plan's extra axes (tp/ep manual modes, interleaved
        # virtual stages, real pp x zero=3) unlock in the builders
        self._plan = plan
        self.virtual = max(1, int(virtual))
        if isinstance(trainer, Trainer):
            self.optimizer = trainer._optimizer
            self._trainer = trainer
            if compression is None:
                compression = trainer._compression_params
            if zero is None and trainer._zero_req:
                zero = trainer._zero_req
            if pipeline is None:
                pipeline = trainer._pipeline_req
        else:
            self.optimizer = trainer
            self._trainer = None
        self.mesh = mesh if mesh is not None else current_mesh()
        self.dp_axis = dp_axis
        self.donate = donate
        self.n_model_inputs = n_model_inputs
        self.grad_accum = grad_accum
        # compression config, two accepted shapes:
        #   legacy flat {"type": "2bit"|"int8", "threshold": float} —
        #     gradient compression only (quantized allreduce with error
        #     feedback; reference: src/kvstore/gradient_compression.cc)
        #   widened {"grads": {...}, "weights": {...},
        #            "activations": {...}} — per-direction wire
        #     compression: grads keep the legacy semantics; weights
        #     quantize the ZeRO weight all-gathers (block-scaled
        #     int8/fp8, parallel/compression.quantized_all_gather);
        #     activations quantize the pipeline's per-tick ppermute
        #     hops + last-stage broadcast (quantized_ppermute)
        comp = dict(compression) if compression else None
        self._wire_weights = None
        self._wire_acts = None
        if comp is not None and ({"weights", "activations", "grads"}
                                 & comp.keys()):
            g = comp.get("grads")
            self.compression = ({"type": g} if isinstance(g, str)
                                else dict(g)) if g else None
            self._wire_weights = _normalize_wire_cfg(
                comp.get("weights"), "weights")
            self._wire_acts = _normalize_wire_cfg(
                comp.get("activations"), "activations")
        else:
            self.compression = comp
        # ZeRO weight-update sharding (arXiv:2004.13336), all inside the
        # one compiled step so XLA schedules the collectives into the
        # backward. zero=1: grads reduce-scatter per flat bucket, each
        # replica updates its 1/N shard with shard-sized optimizer
        # state, weights all-gather back. zero=2 additionally carries
        # only SHARD-sized gradient accumulators through the grad_accum
        # scan (each microbatch psum_scatters immediately — the comm
        # overlaps the next microbatch's compute and the full-size grad
        # sum never exists). zero=3 additionally keeps the weights as
        # sharded flat buckets; the step all-gathers them transiently at
        # entry and emits updated SHARDS, so full-size weights exist
        # only inside the executable. zero1=True is the zero=1 alias.
        stage = 0 if zero in (None, False) else int(zero)
        if stage not in (0, 1, 2, 3):
            raise ValueError(f"zero must be one of False/0/1/2/3; "
                             f"got {zero!r}")
        if zero1 and stage == 0:
            stage = 1
        self.zero_stage = stage
        self.zero1 = stage >= 1
        # pipeline-parallel: pipeline=M runs the 1F1B microbatch
        # schedule (M microbatches, O(num_stages) activation stash,
        # recompute-vjp) over the mesh's `pp_axis` inside the one
        # compiled step; the net is auto-staged with
        # parallel.pipeline.pipeline_stages. No pp axis → sequential
        # semantics with a one-time warning (degrade matrix like ZeRO).
        if pipeline is not None and int(pipeline) < 1:
            raise ValueError(f"pipeline must be a positive microbatch "
                             f"count; got {pipeline!r}")
        self.pipeline = int(pipeline) if pipeline is not None else None
        self.pp_axis = pp_axis
        if self._count_names and (self.zero_stage or self.pipeline
                                  or compression or grad_accum > 1):
            raise ValueError(
                "counts= rides the plain fused step only (no zero, "
                "pipeline, compression or grad_accum)")
        # degrade matrix for the widened wire-compression config: each
        # unfusable combination warns ONCE (at construction) and runs
        # without the requested compression rather than failing the run.
        # The warnings diagnose; the REQUEST itself is kept — builders
        # resolve it against what each build actually puts on the wire,
        # so a config forwarded through Trainer(pipeline=M) cannot be
        # silently dropped before the pipeline builder ever sees it.
        # Under plan mode the ParallelPlan already rejected these.
        import warnings as _warnings
        if plan is None:
            if self._wire_weights is not None and self.zero_stage == 0:
                _warnings.warn(
                    "compression={'weights': ...} requested without ZeRO "
                    "(zero=0): there is no weight all-gather on the wire "
                    "to compress — training with uncompressed weights",
                    RuntimeWarning, stacklevel=2)
                self._wire_weights = None
            if self._wire_weights is not None and \
                    self._wire_weights["residual"] and \
                    self.zero_stage != 3:
                _warnings.warn(
                    "weight-compression residual mode applies to zero=3 "
                    "(resident shards re-gathered every step); under "
                    f"zero={self.zero_stage} the gather source is already "
                    "the exact post-update shard — ignoring residual=True",
                    RuntimeWarning, stacklevel=2)
                self._wire_weights = dict(self._wire_weights,
                                          residual=False)
            if self._wire_acts is not None and self.pipeline is None:
                _warnings.warn(
                    "compression={'activations': ...} requested without "
                    "pipeline=M: there are no activation ppermute hops "
                    "to compress — ignoring the activations entry",
                    RuntimeWarning, stacklevel=2)
                self._wire_acts = None
        # static per-step (logical, wire) byte totals for the quantized
        # gather/permute directions — filled by the builders, flushed
        # to the comm_bytes_{gathered,permuted} counters per step
        self._wire_gathered = None
        self._wire_permuted = None
        self._pp_staged = None
        self._pp_mask = None
        self._pp_flat_meta = None   # pp x zero=3: {name: (numel, padded, ssz)}
        self._pp_full_shapes = None  # pp x zero=3: {name: stacked shape}
        self._pp_total_ticks = None  # interleaved schedule length
        self._compiled = None
        self._params = None
        self._tr = None
        self._aux = None
        self._states = None
        self._resid = None
        self._step_count = 0
        self._zero3 = False  # _build_zero1 flips: _tr holds flat shards
        self._zero1_groups = None
        # whole-loop compilation (run_steps): per-(K, batch-shape)
        # lax.scan executables over the SAME step body _build lowered;
        # _loop_body is the uniform per-tick closure each builder
        # stashes, _loop_streak carries the consecutive-nonfinite-skip
        # count across K boundaries
        self._loop_body = None
        self._loop_cache = {}
        self._loop_streak = 0
        self._loop_warned = False
        # run_steps double buffer: the NEXT window's device-resident
        # (ids, raw, stacked) staged while the current window runs
        self._feed_staged = None
        import weakref
        from .. import profiler as _prof
        ref = weakref.ref(self)
        _prof.register_memory_provider(
            f"fused_step_{id(self):x}",
            lambda ref=ref: (lambda s: s.fused_resident_bytes()
                             if s is not None else None)(ref()))

    # -- state pull/push ----------------------------------------------------
    def _init_state(self, args):
        params = self.net.collect_params()
        # materialize deferred params with one eager forward
        needs_init = any(p._data is None for p in params.values())
        if needs_init:
            with autograd.pause():
                self.net(*args[:self.n_model_inputs])
            params = self.net.collect_params()
        self._params = params
        self._tr_names = [n for n, p in params.items()
                         if p.grad_req != "null"]
        self._aux_names = [n for n, p in params.items()
                          if p.grad_req == "null"]
        self._tr = {n: params[n].data()._data for n in self._tr_names}
        self._aux = {n: params[n].data()._data for n in self._aux_names}
        self._states = {n: self.optimizer.create_state(i, params[n].data())
                        for i, n in enumerate(self._tr_names)}
        for i, n in enumerate(self._tr_names):
            self.optimizer.idx2name[i] = n
        if getattr(self, "_pending_restore", None) is not None:
            # checkpoint.Checkpointer.restore ran before the first step
            slots, step_count = self._pending_restore
            if slots is not None:
                self._states = jax.tree_util.tree_map(jnp.asarray, slots)
            if step_count is not None:
                self._step_count = step_count
            self._pending_restore = None

    def sync_to_params(self):
        """Write device weights back into the Parameters (checkpointing /
        eval through the normal Gluon path). Mesh-sharded weights are
        gathered to a single replicated array so eager code can use them;
        ZeRO-3 flat weight shards gather and unflatten per bucket — the
        checkpoint is full-size and replica-count portable."""
        if self._pp_staged is not None:
            if self._pp_flat_meta is not None:
                # pp x zero=3: residents are flat padded per-stage
                # shards — unpad and reshape to the stacked layout
                full = {}
                for n in self._pp_staged.param_names:
                    numel = self._pp_flat_meta[n][0]
                    flat = _unshard(self._tr[n])
                    full[n] = flat[:, :numel].reshape(
                        self._pp_full_shapes[n])
                self._pp_staged.unstack_into_net(full)
            else:
                self._pp_staged.unstack_into_net(
                    {n: _unshard(self._tr[n])
                     for n in self._pp_staged.param_names})
            return
        if self._zero3:
            from .. import multi_tensor as _mt
            for gi, g in enumerate(self._zero1_groups):
                fulls = [_unshard(self._tr[f"__zero3__{gi}_{j}"])
                         for j in range(len(g.plans))]
                for n, w in zip(g.names, _mt.unflatten_buckets(
                        fulls, g.plans, len(g.names))):
                    self._params[n].data()._data = w
        else:
            for n in self._tr_names:
                self._params[n].data()._data = _unshard(self._tr[n])
        for n in self._aux_names:
            self._params[n].data()._data = _unshard(self._aux[n])

    def refresh_weights(self):
        """Re-import weights from the net's Parameters into the step's
        device buffers (after set_data / checkpoint restore). Inverse of
        sync_to_params; under ZeRO-3 the full-size parameters flatten
        back into sharded flat buckets."""
        params = self._params if self._params is not None \
            else self.net.collect_params()
        if self._pp_staged is not None:
            restacked = self._pp_staged.restack()
            if self._pp_flat_meta is not None:
                new_tr = {}
                for n in self._pp_staged.param_names:
                    numel, padded, _ssz = self._pp_flat_meta[n]
                    flat = restacked[n].reshape(
                        restacked[n].shape[0], -1)
                    if padded > numel:
                        flat = jnp.pad(flat,
                                       ((0, 0), (0, padded - numel)))
                    new_tr[n] = _global_put(flat, self._tr_sh[n])
                self._tr = new_tr
            else:
                self._tr = {n: _global_put(restacked[n],
                                           self._tr_sh[n])
                            for n in self._pp_staged.param_names}
            return
        if self._zero3:
            from .. import multi_tensor as _mt
            new_tr = {}
            for gi, g in enumerate(self._zero1_groups):
                w_bks = _mt.pad_buckets(_mt.flatten_buckets(
                    [params[n].data()._data for n in g.names], g.plans),
                    g.plans, g.padded)
                for j, b in enumerate(w_bks):
                    k = f"__zero3__{gi}_{j}"
                    new_tr[k] = _global_put(b, self._tr_sh[k])
            self._tr = new_tr
        else:
            self._tr = {n: params[n].data()._data
                        for n in self._tr_names}
            if self.mesh is not None and self._compiled is not None:
                self._tr = {n: _global_put(v, self._tr_sh[n])
                            for n, v in self._tr.items()}

    def export_states(self):
        """Optimizer slot state in per-name full-size form. Under
        zero>=1 the resident `__zero1__<g>_<j>` buckets are gathered,
        de-padded and unflattened back to one tree per parameter — the
        padded bucket layout depends on the dp shard count, so this is
        what makes a checkpoint replica-count portable (restoring
        re-buckets for whatever mesh the new run compiled)."""
        st = self._states
        if st is None or self._zero1_groups is None or \
                not any(str(k).startswith("__zero1__") for k in st):
            return st
        from .. import multi_tensor as _mt
        out = {}
        for gi, g in enumerate(self._zero1_groups):
            buckets = [st[f"__zero1__{gi}_{j}"]
                       for j in range(len(g.plans))]
            flat0, treedef = jax.tree_util.tree_flatten(buckets[0])
            leaves = [jax.tree_util.tree_leaves(b) for b in buckets]
            per_name = [[] for _ in g.names]
            for L in range(len(flat0)):
                fulls = [_unshard(leaves[j][L])
                         for j in range(len(g.plans))]
                for m, a in enumerate(_mt.unflatten_buckets(
                        fulls, g.plans, len(g.names))):
                    per_name[m].append(a)
            for m, n in enumerate(g.names):
                out[n] = jax.tree_util.tree_unflatten(
                    treedef, per_name[m])
        return out

    def _bucket_states(self, per_name):
        """Inverse of export_states: flatten restored per-name slot
        trees into this step's compiled `__zero1__` bucket layout
        (padded for THIS mesh's dp shard count)."""
        from .. import multi_tensor as _mt
        shard = NamedSharding(self.mesh, P(self.dp_axis))
        new_states = {}
        for gi, g in enumerate(self._zero1_groups):
            member = [jax.tree_util.tree_flatten(per_name[n])
                      for n in g.names]
            treedef = member[0][1]
            nleaf = len(member[0][0])
            per_leaf = []
            for L in range(nleaf):
                bks = _mt.pad_buckets(_mt.flatten_buckets(
                    [member[m][0][L] for m in range(len(g.names))],
                    g.plans), g.plans, g.padded)
                per_leaf.append([_global_put(b, shard) for b in bks])
            for j in range(len(g.plans)):
                new_states[f"__zero1__{gi}_{j}"] = \
                    jax.tree_util.tree_unflatten(
                        treedef, [per_leaf[L][j]
                                  for L in range(nleaf)])
        return new_states

    # -- compilation ---------------------------------------------------------
    def _build(self, args):
        if self.pipeline is not None:
            from .mesh import has_axis
            if has_axis(self.mesh, self.pp_axis):
                self._build_pipeline(args)
                return
            import warnings
            warnings.warn(
                f"pipeline={self.pipeline} requested but the mesh has "
                f"no {self.pp_axis!r} axis of size > 1 — running the "
                "plain fused step (sequential semantics); build a "
                "hybrid_mesh(dp=..., pp=...) to pipeline",
                RuntimeWarning, stacklevel=3)
            if self._wire_acts is not None:
                # diagnose only — the REQUEST survives, so a later
                # rebuild on a pp mesh still compresses its hops
                warnings.warn(
                    "activation wire compression requested but the "
                    "pipeline fell back to the plain step — no "
                    "inter-stage hops exist; ignoring the "
                    "'activations' entry", RuntimeWarning, stacklevel=3)
        with use_mesh(self.mesh):
            entry = self.net.trace_entry(
                list(args[:self.n_model_inputs]), training=True)
        tr_names = entry.tr_names
        aux_names = entry.aux_names
        opt = self.optimizer
        loss_fn = self.loss_fn
        n_in = self.n_model_inputs
        treedef_box = entry

        accum = self.grad_accum
        counted = bool(self._count_names)

        def loss_of(tr_, aux_, key_, batch_):
            flat, new_aux = entry.raw_fn(tr_, aux_, key_,
                                         *batch_[:n_in])
            outs = jax.tree_util.tree_unflatten(
                treedef_box.out_treedef,
                [NDArray(f) for f in flat])
            if counted:
                # the counts ride out as aux data beside new_aux
                *outs, cnt = outs
                outs = outs[0] if len(outs) == 1 else tuple(outs)
                new_aux = (new_aux, cnt._data)
            with autograd._mode(False, True), _random.trace_key(
                    jax.random.fold_in(key_, 7)):
                labels = [NDArray(b) for b in batch_[n_in:]]
                l = loss_fn(outs, *labels) if not isinstance(
                    outs, tuple) else loss_fn(*outs, *labels)
                l = l.mean()
            return l._data.astype(jnp.float32), new_aux

        def local_grads(tr, aux, key, batch):
            if accum <= 1:
                (loss, new_aux), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(tr, aux, key, batch)
                return loss, new_aux, grads
            # microbatch scan: split the batch dim by `accum`,
            # accumulate grads in fp32, one optimizer update at the
            # end — the remat-friendly way to grow effective batch
            # without growing activation memory
            micro = tuple(
                b.reshape(accum, b.shape[0] // accum, *b.shape[1:])
                for b in batch)
            keys = jax.random.split(key, accum)

            def body(carry, xs):
                aux_c, gacc, lacc = carry
                key_i, mb = xs
                (l, new_aux_c), g = jax.value_and_grad(
                    loss_of, has_aux=True)(tr, aux_c, key_i, mb)
                gacc = jax.tree_util.tree_map(
                    lambda a, b_: a + b_.astype(a.dtype), gacc, g)
                return (new_aux_c, gacc, lacc + l), None

            g0 = jax.tree_util.tree_map(
                lambda w: jnp.zeros(w.shape, jnp.float32), tr)
            (new_aux, gsum, lsum), _ = lax.scan(
                body, (aux, g0, jnp.float32(0.0)), (keys, micro))
            grads = jax.tree_util.tree_map(lambda g_: g_ / accum, gsum)
            return lsum / accum, new_aux, grads

        def step(tr, aux, states, hyper, key, *batch):
            loss, new_aux, grads = local_grads(tr, aux, key, batch)
            new_tr, new_states = {}, {}
            for n in tr_names:
                new_tr[n], new_states[n] = opt._step(
                    tr[n], grads[n], states[n], hyper)
            if counted:
                new_aux, cnt = new_aux
                return loss, new_tr, new_aux, new_states, cnt
            return loss, new_tr, new_aux, new_states

        # run_steps scans this same body; the extra global grad-norm
        # feeds the stacked per-step telemetry and the in-scan
        # nonfinite-skip predicate (unused outputs DCE away)
        def loop_body(tr, aux, states, resid, hyper, key, batch):
            loss, new_aux, grads = local_grads(tr, aux, key, batch)
            gn2 = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree_util.tree_leaves(grads))
            new_tr, new_states = {}, {}
            for n in tr_names:
                new_tr[n], new_states[n] = opt._step(
                    tr[n], grads[n], states[n], hyper)
            return (loss, jnp.sqrt(gn2), new_tr, new_aux, new_states,
                    resid)

        if self.zero1:
            if self.mesh is not None and \
                    self.dp_axis in self.mesh.axis_names and \
                    self.mesh.shape[self.dp_axis] > 1:
                self._build_zero1(args, local_grads, tr_names,
                                  aux_names, loss_of=loss_of)
                return
            import warnings
            warnings.warn(
                "zero1=True requested but there is no mesh with a "
                f"{self.dp_axis!r} axis of size > 1 — nothing to shard "
                "the update over; running unsharded",
                RuntimeWarning, stacklevel=3)
            if self._wire_weights is not None:
                warnings.warn(
                    "weight wire compression requested but the ZeRO "
                    "build fell back to unsharded — no weight "
                    "all-gather exists; ignoring the 'weights' entry",
                    RuntimeWarning, stacklevel=3)
                self._wire_weights = None
        if self.compression is not None:
            if self.mesh is not None and \
                    self.dp_axis in self.mesh.axis_names:
                self._build_compressed(args, local_grads, tr_names,
                                       aux_names)
                return
            import warnings
            warnings.warn(
                "gradient compression requested but there is no mesh "
                f"with a {self.dp_axis!r} axis — training uncompressed",
                RuntimeWarning, stacklevel=3)
        if self.mesh is not None:
            mesh = self.mesh
            repl = NamedSharding(mesh, P())
            tr_sh = _param_shardings(self._params, tr_names, mesh)
            aux_sh = _param_shardings(self._params, aux_names, mesh)
            # state shards mirror their weight's sharding
            st_sh = {n: jax.tree_util.tree_map(
                lambda _, sh=tr_sh[n]: sh,
                self._states[n]) for n in tr_names}
            batch_sh = _batch_shardings(args, mesh, self.dp_axis)
            hyper_sh = {k: repl for k in ("lr", "wd", "t", "rescale")}
            self._compiled = jax.jit(
                step,
                in_shardings=(tr_sh, aux_sh, st_sh, hyper_sh, repl,
                              *batch_sh),
                out_shardings=(repl, tr_sh, aux_sh, st_sh)
                + ((repl,) if counted else ()),
                donate_argnums=(0, 2) if self.donate else ())
            # place initial state on the mesh (args arrive single-device)
            self._tr = {n: _global_put(v, tr_sh[n])
                        for n, v in self._tr.items()}
            self._aux = {n: _global_put(v, aux_sh[n])
                         for n, v in self._aux.items()}
            self._states = jax.tree_util.tree_map(_global_put,
                                                  self._states, st_sh)
            self._batch_sh = batch_sh
            self._tr_sh, self._aux_sh, self._st_sh = tr_sh, aux_sh, st_sh
        else:
            self._compiled = jax.jit(
                step, donate_argnums=(0, 2) if self.donate else ())
        self._tr_names = tr_names
        self._aux_names = aux_names
        self._loop_body = loop_body
        self._loop_mode = "gspmd" if self.mesh is not None else "plain"

    def _build_compressed(self, args, local_grads, tr_names, aux_names):
        """Quantized-allreduce variant: the step runs inside shard_map
        over the dp axis so the gradient sync is an *explicit* collective
        we can quantize (psum of int codes + error feedback) instead of
        the implicit fp32 AllReduce XLA inserts in the backward. Pure
        data parallelism only — parameters must be unsharded."""
        from jax import shard_map
        from .compression import compressed_psum_tree
        from ..gluon.contrib import SyncBatchNorm

        for n in tr_names:
            sh = self._params[n].sharding
            if sh is not None and not _shards_nothing(sh, self.mesh):
                raise ValueError(
                    "gradient compression supports pure data parallelism; "
                    f"parameter {n!r} carries a TP sharding")

        def _blocks(b):
            yield b
            for c in getattr(b, "_children", {}).values():
                yield from _blocks(c)

        # inside shard_map each shard normalizes over its OWN batch
        # slice (upstream multi-device BatchNorm parity; running stats
        # are pmean'd below). SyncBatchNorm's contract is GLOBAL batch
        # statistics, which only the GSPMD jit path provides — refuse
        # loudly rather than silently train with per-shard stats.
        if any(isinstance(b, SyncBatchNorm) for b in _blocks(self.net)):
            raise ValueError(
                "SyncBatchNorm cannot run under gradient compression: "
                "the compressed step runs inside shard_map, where batch "
                "statistics are per-shard. Drop compression= (GSPMD "
                "syncs BN stats globally) or use plain BatchNorm "
                "(per-shard stats, upstream parity)")
        mesh = self.mesh
        dp = self.dp_axis
        ndp = mesh.shape[dp]
        scheme = self.compression.get("type", "2bit")
        threshold = float(self.compression.get("threshold", 0.5))
        # optional bucketed collective: O(num_buckets) psums instead of
        # O(num_tensors) (compression={"bucket_bytes": 4 << 20})
        bucket_bytes = self.compression.get("bucket_bytes")
        opt = self.optimizer

        def step(tr, aux, states, hyper, key, resid, *batch):
            # distinct dropout keys per dp shard
            key = jax.random.fold_in(key, lax.axis_index(dp))
            resid = jax.tree_util.tree_map(lambda r: r[0], resid)
            loss, new_aux, grads = local_grads(tr, aux, key, batch)
            grads, new_resid = compressed_psum_tree(
                grads, resid, dp, scheme, threshold,
                bucket_bytes=bucket_bytes)
            # effective (decompressed, dp-mean) grad norm — replicated
            gn2 = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree_util.tree_leaves(grads))
            loss = lax.pmean(loss, dp)
            # aux (e.g. BatchNorm running stats) computed on the local
            # shard: average across replicas like the fp32 path would
            new_aux = {n: lax.pmean(v, dp)
                       if jnp.issubdtype(v.dtype, jnp.inexact)
                       else lax.pmax(v, dp) for n, v in new_aux.items()}
            new_tr, new_states = {}, {}
            for n in tr_names:
                new_tr[n], new_states[n] = opt._step(
                    tr[n], grads[n], states[n], hyper)
            return (loss, jnp.sqrt(gn2), new_tr, new_aux, new_states,
                    jax.tree_util.tree_map(lambda r: r[None], new_resid))

        def fn_step(tr, aux, states, hyper, key, resid, *batch):
            out = step(tr, aux, states, hyper, key, resid, *batch)
            return (out[0],) + out[2:]  # single path drops the gnorm

        batch_specs = tuple(split_batch_spec(
            _np.ndim(a._data if isinstance(a, NDArray) else a), 0, dp)
            for a in args)
        in_specs = (P(), P(), P(), P(), P(), P(dp), *batch_specs)
        # check_vma=False: local_grads must yield each shard's OWN
        # gradient for the quantized psum below. With the varying-axes
        # checker on, differentiating w.r.t. the replicated weights
        # psums the cotangent implicitly and the explicit collective
        # then sums it a second time (ndp-fold gradients).
        fn = shard_map(
            fn_step, mesh=mesh, in_specs=in_specs,
            out_specs=(P(), P(), P(), P(), P(dp)), check_vma=False)
        self._compiled = jax.jit(
            fn, donate_argnums=(0, 2, 5) if self.donate else ())
        fn_loop = shard_map(
            step, mesh=mesh, in_specs=in_specs,
            out_specs=(P(), P(), P(), P(), P(), P(dp)), check_vma=False)

        def loop_body(tr, aux, states, resid, hyper, key, batch):
            return fn_loop(tr, aux, states, hyper, key, resid, *batch)

        self._loop_body = loop_body
        self._loop_mode = "shardmap"
        repl = NamedSharding(mesh, P())
        self._tr = {n: _global_put(v, repl)
                    for n, v in self._tr.items()}
        self._aux = {n: _global_put(v, repl)
                     for n, v in self._aux.items()}
        self._states = jax.tree_util.tree_map(
            lambda v: _global_put(v, repl), self._states)
        self._resid = {
            n: jax.device_put(
                jnp.zeros((ndp,) + tuple(self._tr[n].shape), jnp.float32),
                NamedSharding(mesh, P(dp)))
            for n in tr_names}
        self._batch_sh = tuple(
            NamedSharding(mesh, spec) for spec in batch_specs)
        # checkpoint restore reads these to re-place restored state
        self._tr_sh = {n: repl for n in tr_names}
        self._aux_sh = {n: repl for n in aux_names}
        self._st_sh = {n: jax.tree_util.tree_map(lambda _: repl,
                                                 self._states[n])
                       for n in tr_names}
        self._tr_names = tr_names
        self._aux_names = aux_names

    def _build_zero1(self, args, local_grads, tr_names, aux_names,
                     loss_of=None):
        """ZeRO variant (stages 1-3): the step runs inside shard_map
        over the dp axis; grads flatten into contiguous buckets and
        reduce-scatter (psum_scatter), each replica runs the fused
        optimizer math on its 1/N contiguous shard with SHARD-SIZED
        optimizer state. Stage 1/2: the updated weight shards all-gather
        back into full weights (optimizer state memory drops N-fold; the
        wire cost equals one allreduce). Stage 2 additionally replaces
        the grad_accum scan's full-size fp32 accumulators with
        shard-sized ones (per-microbatch reduce-scatter overlapped with
        compute). Stage 3 keeps the weights sharded across steps:
        transient in-step all-gathers materialize them, and the update
        emits shards — weight memory drops N-fold too. Composes with
        gradient compression: codes ride the reduce-scatter, error
        feedback keeps the full local residual. Pure data parallelism
        only."""
        from jax import shard_map
        from .. import multi_tensor as _mt
        from .compression import compressed_psum_scatter
        from ..gluon.contrib import SyncBatchNorm

        plan = self._plan
        ep_on = plan is not None and getattr(plan, "ep", 1) > 1
        # ep(MoE) sharing the dp axis: expert parameters (leading dim
        # sharded over dp) stay OUT of the flat buckets — each rank
        # holds its own experts' weights, grads and optimizer state
        # locally, and the forward does the token exchange explicitly
        # (MoEMLP manual mode). Everything else buckets as usual.
        ep_names = set()
        for n in tr_names:
            sh = self._params[n].sharding
            if sh is None or _shards_nothing(sh, self.mesh):
                continue
            if ep_on and len(sh) >= 1 and sh[0] == self.dp_axis:
                ep_names.add(n)
                continue
            raise ValueError(
                "zero1 shards the weight update over flat dp "
                f"buckets; parameter {n!r} carries a TP sharding. "
                "Drop zero1= or the tensor-parallel spec "
                "(expert parallelism composes through "
                "ParallelPlan(ep=..., zero=1) with the expert axis "
                "on the dp mesh axis)")

        def _blocks(b):
            yield b
            for c in getattr(b, "_children", {}).values():
                yield from _blocks(c)

        # same per-shard batch-statistics caveat as _build_compressed
        if any(isinstance(b, SyncBatchNorm) for b in _blocks(self.net)):
            raise ValueError(
                "SyncBatchNorm cannot run under zero1: the sharded step "
                "runs inside shard_map, where batch statistics are "
                "per-shard. Drop zero1= (GSPMD syncs BN stats globally) "
                "or use plain BatchNorm")
        mesh = self.mesh
        dp = self.dp_axis
        ndp = mesh.shape[dp]
        opt = self.optimizer
        scheme = threshold = None
        if self.compression is not None:
            scheme = self.compression.get("type", "2bit")
            threshold = float(self.compression.get("threshold", 0.5))
        # weight wire compression: the post-update (zero=1/2) or
        # in-step (zero=3) weight all-gather moves block-scaled
        # int8/fp8 codes + fp32 scales instead of fp32 shards
        wcfg = self._wire_weights
        wscheme = wcfg["type"] if wcfg is not None else None
        wblock = wcfg["block"] if wcfg is not None else None
        wres = bool(wcfg is not None and wcfg["residual"]
                    and self.zero_stage >= 3)
        # one flag drives the resid-carrying step signature: grad
        # error-feedback residuals and weight-gather residuals ride the
        # same shard-sharded dict (grad keys `__zero1__…`, weight keys
        # `__wres__…`), independently present
        has_resid = (scheme is not None) or wres
        if wscheme is not None:
            from .compression import (quantized_all_gather,
                                      quantized_all_gather_ef,
                                      wire_nbytes)

        def _wgather(v):
            if wscheme is not None:
                return quantized_all_gather(v, dp, wscheme, wblock)
            return lax.all_gather(v, dp, axis=0, tiled=True)

        # group trainables by (weight dtype, optimizer-state structure)
        # so every bucket flattens homogeneous leaves; the state probe
        # runs under eval_shape (no allocation) and is independent of
        # self._states, so grouping is deterministic across checkpoint
        # save/restore
        groups, order = {}, []
        for i, n in enumerate(tr_names):
            if n in ep_names:
                continue
            w = self._tr[n]
            probe = jax.eval_shape(
                lambda i=i, w=w: opt.create_state(
                    i, _mt._FlatWeight(jax.ShapeDtypeStruct(
                        w.shape, jnp.dtype(w.dtype)))))
            leaves, treedef = jax.tree_util.tree_flatten(probe)
            gk = (str(jnp.dtype(w.dtype)), str(treedef),
                  tuple(str(l.dtype) for l in leaves))
            if gk not in groups:
                groups[gk] = []
                order.append(gk)
            groups[gk].append(n)

        shard = NamedSharding(mesh, P(dp))
        repl = NamedSharding(mesh, P())
        if ep_names:
            # the plan already restricted ep x zero to stage 1 with an
            # elementwise optimizer and no grad compression; the local
            # expert update additionally needs E % dp == 0 and weight-
            # shaped state leaves (sharded along the expert dim)
            if self.zero_stage >= 2 or scheme is not None:
                raise ValueError(
                    "expert parallelism under ZeRO supports zero=1 "
                    "without gradient compression")
            for n in sorted(ep_names):
                E = self._tr[n].shape[0]
                if E % ndp:
                    raise ValueError(
                        f"expert parameter {n!r} has {E} experts, not "
                        f"divisible by the dp/ep axis size {ndp}")
                probe = jax.eval_shape(
                    lambda n=n: opt.create_state(
                        0, _mt._FlatWeight(jax.ShapeDtypeStruct(
                            self._tr[n].shape,
                            jnp.dtype(self._tr[n].dtype)))))
                for leaf in jax.tree_util.tree_leaves(probe):
                    if tuple(leaf.shape) != tuple(self._tr[n].shape):
                        raise ValueError(
                            f"optimizer state for expert parameter "
                            f"{n!r} is not weight-shaped "
                            f"({leaf.shape}); expert-local updates "
                            "need an elementwise optimizer")
        ep_shard_specs = {}
        for n in sorted(ep_names):
            ep_shard_specs[n] = P(dp, *([None] *
                                        (self._tr[n].ndim - 1)))

        class _Grp:
            __slots__ = ("names", "plans", "padded", "segs", "treedef")

        elementwise = _mt.is_elementwise_rule(self.optimizer)
        grp_list = []
        for gk in order:
            g = _Grp()
            g.names = groups[gk]
            shapes = [tuple(self._tr[n].shape) for n in g.names]
            dts = [self._tr[n].dtype for n in g.names]
            g.plans = _mt.plan_buckets(shapes, dts)
            g.padded = _mt.zero1_padded_sizes(g.plans, ndp)
            # static segment ids (flat element -> group-local tensor
            # index, pad id = n) close over the body as constants; the
            # per-shard slice is taken by rank inside the step. Only
            # the norm-based rules (LAMB/LARS) read them, and at four
            # bytes a parameter they are not free: an elementwise rule
            # builds none.
            g.segs = None if elementwise else [
                jnp.asarray(s) for s in _mt.bucket_segments(
                    g.plans, g.padded, len(g.names))]
            grp_list.append(g)

        def _skey(gi, j):
            return f"__zero1__{gi}_{j}"

        # bucket-sharded optimizer state: import per-name trees (fresh
        # from _init_state or a restored checkpoint) by flattening each
        # leaf position across the group into padded buckets; a
        # checkpoint saved FROM a zero1 step is already in bucket form
        # and only needs re-placing
        if any(str(k).startswith("__zero1__") for k in self._states):
            new_states = jax.tree_util.tree_map(
                lambda v: _global_put(v, shard), self._states)
        else:
            # flatten on the HOST: the per-name state sits full-size on
            # the device the net was built on, and a second full-size
            # flat copy beside it (10 bytes a parameter with Adam) is
            # what runs a 16 GB chip out of memory at ~0.7B parameters.
            # device_put then writes each device only its own shard.
            host = jax.local_devices(backend="cpu")[0]
            new_states = {}
            for gi, g in enumerate(grp_list):
                member = [jax.tree_util.tree_flatten(self._states[n])
                          for n in g.names]
                treedef = member[0][1]
                nleaf = len(member[0][0])
                per_leaf = []
                for L in range(nleaf):
                    with jax.default_device(host):
                        bks = _mt.pad_buckets(_mt.flatten_buckets(
                            [_np.asarray(member[m][0][L])
                             for m in range(len(g.names))],
                            g.plans), g.plans, g.padded)
                    per_leaf.append([_global_put(b, shard) for b in bks])
                for j in range(len(g.plans)):
                    new_states[_skey(gi, j)] = \
                        jax.tree_util.tree_unflatten(
                            treedef, [per_leaf[L][j]
                                      for L in range(nleaf)])
            for n in sorted(ep_names):
                # expert state shards along the expert (dp) dim —
                # weight-shaped leaves, so the P(dp) prefix applies
                new_states[n] = jax.tree_util.tree_map(
                    lambda v: _global_put(v, shard), self._states[n])
        self._states = new_states
        state_keys = [_skey(gi, j) for gi, g in enumerate(grp_list)
                      for j in range(len(g.plans))]

        z3 = self.zero_stage >= 3

        def _sk3(gi, j):
            return f"__zero3__{gi}_{j}"

        def _reduce_shards(grads, resid):
            """Flatten local grads into buckets and reduce-scatter each:
            every rank keeps only its 1/N shard of the reduced grads."""
            red, new_resid = {}, {}
            for gi, g in enumerate(grp_list):
                g_bks = _mt.pad_buckets(_mt.flatten_buckets(
                    [grads[n] for n in g.names], g.plans),
                    g.plans, g.padded)
                for j, gb in enumerate(g_bks):
                    sk = _skey(gi, j)
                    if scheme is not None:
                        red[sk], nres = compressed_psum_scatter(
                            gb, resid[sk][0], dp, scheme, threshold)
                        new_resid[sk] = nres[None]
                    else:
                        red[sk] = lax.psum_scatter(
                            gb, dp, scatter_dimension=0,
                            tiled=True) / ndp
            return red, new_resid

        # zero>=2 + grad_accum: the scan carries SHARD-sized gradient
        # accumulators — each microbatch reduce-scatters immediately
        # (the collective overlaps the next microbatch's compute) and
        # the full-size grad sum never exists. Compression keeps the
        # accumulate-then-quantize path: its error-feedback residual is
        # full-size resident anyway, and quantizing every microbatch
        # would break parity with the unsharded compressed step.
        accum = self.grad_accum
        shard_carry = self.zero_stage >= 2 and accum > 1 \
            and scheme is None

        def sharded_accum_grads(tr, aux, key, batch):
            micro = tuple(
                b.reshape(accum, b.shape[0] // accum, *b.shape[1:])
                for b in batch)
            keys = jax.random.split(key, accum)

            def body(carry, xs):
                aux_c, racc, lacc = carry
                key_i, mb = xs
                (l, new_aux_c), g = jax.value_and_grad(
                    loss_of, has_aux=True)(tr, aux_c, key_i, mb)
                red, _ = _reduce_shards(g, None)
                racc = {k: a + red[k].astype(a.dtype)
                        for k, a in racc.items()}
                return (new_aux_c, racc, lacc + l), None

            r0 = {_skey(gi, j): jnp.zeros((g.padded[j] // ndp,),
                                          jnp.float32)
                  for gi, g in enumerate(grp_list)
                  for j in range(len(g.plans))}
            (new_aux, rsum, lsum), _ = lax.scan(
                body, (aux, r0, jnp.float32(0.0)), (keys, micro))
            return (lsum / accum, new_aux,
                    {k: v / accum for k, v in rsum.items()})

        def _wkey(gi, j):
            return f"__wres__{gi}_{j}"

        def step(tr, aux, states, hyper, key, resid, *batch):
            # distinct dropout keys per dp shard
            key = jax.random.fold_in(key, lax.axis_index(dp))
            rank = lax.axis_index(dp)
            new_wres = {}
            if z3:
                # transient gather: full-size weights exist only inside
                # the executable (XLA frees each bucket's gather after
                # its last use); the resident weights are the shards.
                # Under weight wire compression the gather moves int8/
                # fp8 codes + per-block fp32 scales; residual mode
                # additionally carries per-shard error feedback so the
                # transmitted view is drift-free across steps
                wsh = tr
                tr = {}
                for gi, g in enumerate(grp_list):
                    fulls = []
                    for j in range(len(g.plans)):
                        if wres:
                            fb, nr = quantized_all_gather_ef(
                                wsh[_sk3(gi, j)],
                                resid[_wkey(gi, j)][0],
                                dp, wscheme, wblock)
                            new_wres[_wkey(gi, j)] = nr[None]
                        else:
                            fb = _wgather(wsh[_sk3(gi, j)])
                        fulls.append(fb)
                    for n, w in zip(g.names, _mt.unflatten_buckets(
                            fulls, g.plans, len(g.names))):
                        tr[n] = w
            if shard_carry:
                loss, new_aux, red = sharded_accum_grads(
                    tr, aux, key, batch)
                new_resid = {}
            elif ep_names:
                # manual-ep region: MoE layers see their LOCAL expert
                # shards and exchange tokens with explicit all_gathers;
                # the all_gather VJP (psum) already sums each expert's
                # grad over every rank's loss shard, so expert grads
                # only need the 1/N loss-mean scale, no reduce
                from .mesh import manual_axes as _ma
                with _ma({"ep": dp}):
                    loss, new_aux, grads = local_grads(tr, aux, key,
                                                       batch)
                red, new_resid = _reduce_shards(grads, resid)
            else:
                loss, new_aux, grads = local_grads(tr, aux, key, batch)
                red, new_resid = _reduce_shards(grads, resid)
            # global grad norm from the reduced shards (each rank holds
            # a distinct 1/N slice; pad lanes are zero)
            gn2 = sum(jnp.sum(jnp.square(v.astype(jnp.float32)))
                      for v in red.values())
            if ep_names:
                gn2 = gn2 + sum(
                    jnp.sum(jnp.square(
                        (grads[n] / ndp).astype(jnp.float32)))
                    for n in sorted(ep_names))
            gnorm = jnp.sqrt(lax.psum(gn2, dp))
            loss = lax.pmean(loss, dp)
            new_aux = {n: lax.pmean(v, dp)
                       if jnp.issubdtype(v.dtype, jnp.inexact)
                       else lax.pmax(v, dp) for n, v in new_aux.items()}
            new_tr, new_states = {}, {}
            for gi, g in enumerate(grp_list):
                if not z3:
                    w_bks = _mt.pad_buckets(_mt.flatten_buckets(
                        [tr[n] for n in g.names], g.plans),
                        g.plans, g.padded)
                full = []
                for j in range(len(g.plans)):
                    sk = _skey(gi, j)
                    ssz = g.padded[j] // ndp
                    if z3:
                        # the shard_map local view IS this rank's slice
                        w_sh = wsh[_sk3(gi, j)]
                    else:
                        w_sh = lax.dynamic_slice(
                            w_bks[j], (rank * ssz,), (ssz,))
                    seg = None if g.segs is None else \
                        lax.dynamic_slice(g.segs[j], (rank * ssz,),
                                          (ssz,))
                    nw, nst = _mt.zero1_update_shard(
                        opt, w_sh, red[sk], states[sk], hyper, seg,
                        len(g.names) + 1, dp)
                    new_states[sk] = nst
                    if z3:
                        # the update's output IS the new resident
                        # shard — updated weights never all-gather
                        new_tr[_sk3(gi, j)] = nw
                    else:
                        full.append(_wgather(nw))
                if not z3:
                    for n, w in zip(g.names, _mt.unflatten_buckets(
                            full, g.plans, len(g.names))):
                        new_tr[n] = w
            for n in sorted(ep_names):
                # expert-local update: this rank's experts, complete
                # grads (see above), shard-resident state — never
                # gathered
                nw, nst = opt._step(tr[n], grads[n] / ndp, states[n],
                                    hyper)
                new_tr[n] = nw
                new_states[n] = nst
            out = (loss, gnorm, new_tr, new_aux, new_states)
            if has_resid:
                return out + ({**new_resid, **new_wres},)
            return out

        batch_specs = tuple(split_batch_spec(
            _np.ndim(a._data if isinstance(a, NDArray) else a), 0, dp)
            for a in args)
        st_spec = {k: P(dp) for k in state_keys}
        st_spec.update({n: ep_shard_specs[n] for n in sorted(ep_names)})
        state_keys = state_keys + sorted(ep_names)
        z3_keys = [_sk3(gi, j) for gi, g in enumerate(grp_list)
                   for j in range(len(g.plans))]
        if z3:
            tr_spec = {k: P(dp) for k in z3_keys}
        elif ep_names:
            tr_spec = {n: ep_shard_specs.get(n, P())
                       for n in tr_names}
        else:
            tr_spec = P()
        in_specs = (tr_spec, P(), st_spec, P(), P())
        out_specs = (P(), tr_spec, P(), st_spec)
        loop_out_specs = (P(), P()) + out_specs[1:]
        resid_spec = {}
        if scheme is not None:
            resid_spec.update({k: P(dp) for k in state_keys})
        if wres:
            resid_spec.update(
                {_wkey(gi, j): P(dp)
                 for gi, g in enumerate(grp_list)
                 for j in range(len(g.plans))})
        if has_resid:
            in_specs = in_specs + (resid_spec,)
            out_specs = out_specs + (resid_spec,)
            loop_out_specs = loop_out_specs + (resid_spec,)

            def fn_step(tr, aux, states, hyper, key, resid, *batch):
                out = step(tr, aux, states, hyper, key, resid, *batch)
                return (out[0],) + out[2:]

            def fn_stats(tr, aux, states, hyper, key, resid, *batch):
                return step(tr, aux, states, hyper, key, resid, *batch)
        else:
            def fn_step(tr, aux, states, hyper, key, *batch):
                out = step(tr, aux, states, hyper, key, None, *batch)
                return (out[0],) + out[2:]

            def fn_stats(tr, aux, states, hyper, key, *batch):
                return step(tr, aux, states, hyper, key, None, *batch)
        # check_vma=False: all_gather'd weights ARE identical on every
        # replica but shard_map's static replication checker cannot
        # prove it, so P() outputs need the check off
        fn = shard_map(
            fn_step, mesh=mesh, in_specs=in_specs + batch_specs,
            out_specs=out_specs, check_vma=False)
        if has_resid:
            donate = (0, 2, 5)
        else:
            donate = (0, 2)
        self._compiled = jax.jit(
            fn, donate_argnums=donate if self.donate else ())
        fn_loop = shard_map(
            fn_stats, mesh=mesh, in_specs=in_specs + batch_specs,
            out_specs=loop_out_specs, check_vma=False)
        if has_resid:
            def loop_body(tr, aux, states, resid, hyper, key, batch):
                return fn_loop(tr, aux, states, hyper, key, resid,
                               *batch)
        else:
            def loop_body(tr, aux, states, resid, hyper, key, batch):
                loss, gnorm, ntr, naux, nst = fn_loop(
                    tr, aux, states, hyper, key, *batch)
                return loss, gnorm, ntr, naux, nst, resid
        self._loop_body = loop_body
        self._loop_mode = "shardmap"
        if z3:
            # weights live as 1/N flat bucket shards from here on;
            # full-size arrays exist only transiently inside the step
            # (and in sync_to_params gathers)
            new_tr = {}
            for gi, g in enumerate(grp_list):
                w_bks = _mt.pad_buckets(_mt.flatten_buckets(
                    [self._tr[n] for n in g.names], g.plans),
                    g.plans, g.padded)
                for j, b in enumerate(w_bks):
                    new_tr[_sk3(gi, j)] = _global_put(b, shard)
            self._tr = new_tr
        else:
            self._tr = {n: _global_put(v, shard if n in ep_names
                                       else repl)
                        for n, v in self._tr.items()}
        self._aux = {n: _global_put(v, repl)
                     for n, v in self._aux.items()}
        if has_resid:
            self._resid = {}
            if scheme is not None:
                self._resid.update({
                    _skey(gi, j): jax.device_put(
                        jnp.zeros((ndp, g.padded[j]), jnp.float32),
                        shard)
                    for gi, g in enumerate(grp_list)
                    for j in range(len(g.plans))})
            if wres:
                # weight-gather error feedback: one fp32 residual per
                # rank per bucket SHARD (not per full bucket — feedback
                # covers only what this rank transmits)
                self._resid.update({
                    _wkey(gi, j): jax.device_put(
                        jnp.zeros((ndp, g.padded[j] // ndp),
                                  jnp.float32), shard)
                    for gi, g in enumerate(grp_list)
                    for j in range(len(g.plans))})
        # static per-step byte totals for /metrics: every bucket is
        # gathered exactly once per step (z3 at entry, z1/2 post-
        # update). Logical = the fp32 value every rank receives; wire =
        # the payloads that actually travel (quantized shard codes +
        # scales, or the fp32 shards when uncompressed) — counted for
        # BOTH modes so the byte cut is A/B-provable from /metrics
        lg = wr = 0
        for g in grp_list:
            for pj in g.padded:
                lg += pj * 4
                if wscheme is not None:
                    wr += ndp * wire_nbytes(pj // ndp, wscheme, wblock)
                else:
                    wr += pj * 4
        self._wire_gathered = (lg, wr)
        self._batch_sh = tuple(
            NamedSharding(mesh, spec) for spec in batch_specs)
        # checkpoint restore reads these to re-place restored state;
        # zero1 state keys (and zero3 weight keys) are bucket ids,
        # sharded over dp
        self._tr_sh = ({k: shard for k in z3_keys} if z3
                       else {n: shard if n in ep_names else repl
                             for n in tr_names})
        self._aux_sh = {n: repl for n in aux_names}
        self._st_sh = {k: jax.tree_util.tree_map(lambda _: shard,
                                                 self._states[k])
                       for k in state_keys}
        self._tr_names = tr_names
        self._aux_names = aux_names
        self._zero1_groups = grp_list
        self._zero3 = z3

    def _build_pipeline(self, args):
        """Pipeline-parallel variant: the net is auto-staged over the
        mesh's pp axis (parallel.pipeline.pipeline_stages — balanced
        contiguous block runs, identity-padded to a uniform slot count)
        and ONE shard_map'd executable runs the full 1F1B microbatch
        schedule: M microbatches tick through the stages via ppermute,
        each stage stashes only O(num_stages) activations and
        recomputes its forward from the stashed input during the
        backward half (recompute-vjp). Gradients come out stage-stacked
        and feed the same fused optimizer rules:

          * plain dp: per-leaf pmean over dp, per-slot vmap'd _step (so
            norm-based rules like LAMB keep exact per-block norms);
          * zero=1|2: each stage's dp group reduce-scatters its FLAT
            stacked grads, updates a 1/ndp shard with SHARD-SIZED
            state, all-gathers weights (elementwise rules only —
            norm-based rules degrade to unsharded with a warning);
            zero=2 + grad_accum carries shard-sized accumulators;
            zero=3 clamps to 2 (stacked weights must stay resident for
            restacking);
          * compression: 2-bit/int8 codes ride the dp collective with
            per-(stage, rank) error-feedback residuals.

        Degrade matrix mirrors ZeRO's: no pp axis → _build warned and
        ran the sequential-semantics plain step; no dp axis → single
        data shard, dp collectives dropped."""
        from jax import shard_map
        from .. import multi_tensor as _mt
        from . import pipeline as _pl
        from .compression import (compressed_psum_scatter,
                                  compressed_psum_tree)
        from .mesh import axis_size
        import warnings

        mesh = self.mesh
        dp = self.dp_axis
        ppx = self.pp_axis
        npp = axis_size(mesh, ppx)
        ndp = axis_size(mesh, dp)
        M = int(self.pipeline)
        accum = self.grad_accum
        opt = self.optimizer
        loss_fn = self.loss_fn
        plan = self._plan
        virt = self.virtual
        tpx = getattr(plan, "tp_axis", "tp")
        manual_tp = plan is not None and getattr(plan, "tp", 1) > 1
        ntp = axis_size(mesh, tpx) if manual_tp else 1

        if self.n_model_inputs != 1 or len(args) != 2:
            raise ValueError(
                "pipeline=M needs exactly (x, y) batches with one "
                f"model input; got n_model_inputs={self.n_model_inputs}"
                f", {len(args)} args")
        for n in self._tr_names:
            sh = self._params[n].sharding
            if sh is None:
                continue
            if not manual_tp:
                raise ValueError(
                    "pipeline stages shard over the pp axis; parameter "
                    f"{n!r} carries a TP sharding — drop one of them "
                    "(pp x tp composes through ParallelPlan(pp=..., "
                    "tp=...))")
            axes = set()
            for e in sh:
                if isinstance(e, str):
                    axes.add(e)
                elif e is not None:
                    axes.update(e)
            if axes - {tpx}:
                raise ValueError(
                    f"ParallelPlan pipeline: parameter {n!r} sharding "
                    f"{sh} mentions axes {sorted(axes - {tpx})} beyond "
                    f"the plan's tp axis {tpx!r}")
        if self._aux_names:
            raise ValueError(
                "pipeline=M requires a stateless net (no aux params "
                f"like BatchNorm running stats); got {self._aux_names}")

        x0 = args[0]
        x0 = x0 if isinstance(x0, NDArray) else NDArray(jnp.asarray(x0))
        with use_mesh(None):
            staged = _pl.pipeline_stages(self.net, npp, sample=x0,
                                         virtual=virt)
        self._pp_staged = staged
        names = staged.param_names
        s = staged.num_slots
        # interleaved virtual stages: one host-precomputed tick table
        # drives the whole schedule (chunk index stays traced — one
        # executable per plan signature)
        sched = _pl.interleaved_schedule(npp, virt, M) if virt > 1 \
            else None
        # per-canonical-name TP sharding (manual mode): every block
        # carries the same Parameter specs by the identical-structure
        # staging contract
        tp_sharding = {}
        if manual_tp:
            for k in names:
                shs = {tuple(bp[k].sharding) if bp[k].sharding
                       is not None else None
                       for bp in staged._block_params}
                if len(shs) != 1:
                    raise ValueError(
                        f"ParallelPlan pipeline: parameter {k!r} has "
                        f"inconsistent TP shardings across blocks: "
                        f"{shs}")
                spec = shs.pop()
                if spec is not None and any(e is not None for e in spec):
                    tp_sharding[k] = spec
        xr = x0._data
        yr = args[1]._data if isinstance(args[1], NDArray) \
            else jnp.asarray(args[1])
        B = xr.shape[0]
        if B % (ndp * accum * M) != 0:
            raise ValueError(
                f"pipeline batch: global batch {B} must divide by "
                f"dp({ndp}) x grad_accum({accum}) x microbatches({M})")
        mbsz = B // (ndp * accum * M)

        stage = self.zero_stage
        if stage >= 3 and plan is None:
            # legacy path keeps the historical clamp; a ParallelPlan
            # runs REAL pp x zero=3 — the stage weights live as flat
            # (pp, dp)-sharded buckets, gathered transiently at step
            # entry and emitted as shards after the update
            warnings.warn(
                "pipeline + zero=3 is clamped to zero=2: stage-stacked "
                "weights must stay resident for checkpoint restacking; "
                "grads and optimizer state still shard over dp "
                "(ParallelPlan(pp=..., zero=3) runs the real thing)",
                RuntimeWarning, stacklevel=3)
            stage = 2
        if stage >= 1 and not _mt.is_elementwise_rule(opt):
            warnings.warn(
                f"pipeline + zero={stage} needs an elementwise update "
                f"rule; {type(opt).__name__} uses per-tensor norms — "
                "running the update unsharded (per-slot vmap keeps its "
                "norms exact)", RuntimeWarning, stacklevel=3)
            stage = 0
        if (stage >= 1 or self.compression is not None) and ndp <= 1:
            if stage >= 1:
                warnings.warn(
                    f"pipeline + zero={stage} requested but the mesh "
                    f"has no {dp!r} axis of size > 1 — nothing to "
                    "shard over; running unsharded",
                    RuntimeWarning, stacklevel=3)
            if self.compression is not None:
                warnings.warn(
                    "gradient compression requested but the mesh has "
                    f"no {dp!r} axis of size > 1 — training "
                    "uncompressed", RuntimeWarning, stacklevel=3)
            stage = 0
            self.compression = None
        scheme = threshold = None
        if self.compression is not None:
            scheme = self.compression.get("type", "2bit")
            threshold = float(self.compression.get("threshold", 0.5))

        # weight/activation wire compression: resolve the widened
        # config against what THIS build actually has on the wire
        wcfg = self._wire_weights
        if wcfg is not None and wcfg["residual"]:
            warnings.warn(
                "weight wire compression residual mode needs zero=3 "
                "and the pipeline clamps to zero<=2 — running the "
                "stateless gather (the exact-self patch keeps each "
                "owner's slice exact)", RuntimeWarning, stacklevel=3)
        if wcfg is not None and (stage < 1 or ndp <= 1):
            warnings.warn(
                "weight wire compression requested but this pipeline "
                "build runs zero=0 (or has no dp group) — no weight "
                "all-gather exists to compress; ignoring the "
                "'weights' entry", RuntimeWarning, stacklevel=3)
            wcfg = None
        wscheme = wcfg["type"] if wcfg is not None else None
        wblock = wcfg["block"] if wcfg is not None else None
        acfg = self._wire_acts
        if acfg is not None and npp <= 1:
            warnings.warn(
                f"activation wire compression requested but the "
                f"{ppx!r} axis has size 1 — no inter-stage hops to "
                "compress; ignoring the 'activations' entry",
                RuntimeWarning, stacklevel=3)
            acfg = None
        ascheme = acfg["type"] if acfg is not None else None
        ablock = acfg["block"] if acfg is not None else None
        awire = (ascheme, ablock) if ascheme is not None else None
        z3 = stage >= 3
        if wscheme is not None or ascheme is not None or z3:
            from .compression import quantized_all_gather, wire_nbytes

        # loss dtype probe (the 1F1B accumulator matches it — bf16
        # pipelines don't silently upcast)
        def _mb_loss(key_):
            def mb_loss(out_raw, y_raw):
                with autograd._mode(False, True), _random.trace_key(
                        jax.random.fold_in(key_, 7)):
                    l = loss_fn(NDArray(out_raw), NDArray(y_raw))
                    l = l.mean()
                return l._data
            return mb_loss

        mb_x = jax.ShapeDtypeStruct((mbsz,) + xr.shape[1:], xr.dtype)
        mb_y = jax.ShapeDtypeStruct((mbsz,) + yr.shape[1:], yr.dtype)
        ld = jax.eval_shape(_mb_loss(jax.random.PRNGKey(0)),
                            mb_x, mb_y).dtype

        stacked = {n: staged.params[n] for n in names}
        mask = staged.params["__mask__"]

        # optimizer state. zero=0: full stacked state sharded over pp,
        # updated with a per-slot vmap. zero>=1: per-name FLAT padded
        # buckets (pad to ndp x 128 lanes) sharded (pp, dp) — only the
        # 1/ndp shard of each stage's state is ever resident
        pad_q = ndp * _mt.ZERO1_LANE
        flat_meta = {}  # name -> (numel, padded, ssz)
        for n in names:
            numel = int(_np.prod(stacked[n].shape[1:]))  # s * prod(shape)
            padded = -(-numel // pad_q) * pad_q
            flat_meta[n] = (numel, padded, padded // ndp)

        states = {}
        if stage == 0:
            for i, n in enumerate(names):
                states[n] = opt.create_state(i, NDArray(stacked[n]))
                opt.idx2name[i] = n
        else:
            for i, n in enumerate(names):
                numel, padded, ssz = flat_meta[n]
                probe = jax.eval_shape(
                    lambda i=i, n=n, ssz=ssz: opt.create_state(
                        i, _mt._FlatWeight(jax.ShapeDtypeStruct(
                            (ssz,), jnp.dtype(stacked[n].dtype)))))
                leaves, treedef = jax.tree_util.tree_flatten(probe)
                states[n] = jax.tree_util.tree_unflatten(
                    treedef, [jnp.zeros((npp, ndp * l.shape[0]),
                                        l.dtype) for l in leaves])
                opt.idx2name[i] = n
        # a checkpoint saved FROM a pipeline step restored before the
        # first call already carries stage-stacked (or flat-sharded)
        # state under the canonical names — keep it instead of zeros
        if set(self._states.keys()) == set(names) and all(
                jax.tree_util.tree_structure(self._states[n]) ==
                jax.tree_util.tree_structure(states[n]) and all(
                    tuple(a.shape) == tuple(b.shape)
                    for a, b in zip(
                        jax.tree_util.tree_leaves(self._states[n]),
                        jax.tree_util.tree_leaves(states[n])))
                for n in names):
            states = {n: jax.tree_util.tree_map(
                jnp.asarray, self._states[n]) for n in names}

        def _pad_flat(v, padded):
            f = v.reshape(-1)
            return jnp.pad(f, (0, padded - f.shape[0])) \
                if padded > f.shape[0] else f

        def _reduce_dp(grads, resid):
            """dp gradient sync in the requested flavor. Returns
            (update-ready grads, new residuals): full stacked leaves
            for stage 0, 1/ndp flat shards for zero>=1."""
            new_resid = {}
            if stage == 0:
                if scheme is not None:
                    # local resid view under P(dp, ppx) is (1, 1, ...)
                    grads, new_resid = compressed_psum_tree(
                        grads, {n: resid[n][0, 0] for n in names}, dp,
                        scheme, threshold)
                    new_resid = {n: v[None, None] for n, v in
                                 new_resid.items()}
                elif ndp > 1:
                    grads = {n: lax.pmean(g, dp)
                             for n, g in grads.items()}
                return grads, new_resid
            red = {}
            for n in names:
                numel, padded, ssz = flat_meta[n]
                gf = _pad_flat(grads[n], padded)
                if scheme is not None:
                    red[n], nres = compressed_psum_scatter(
                        gf, resid[n][0, 0], dp, scheme, threshold)
                    new_resid[n] = nres[None, None]
                else:
                    red[n] = lax.psum_scatter(
                        gf, dp, scatter_dimension=0, tiled=True) / ndp
            return red, new_resid

        shard_accum = stage >= 2 and accum > 1 and scheme is None

        def body(tr, mask_l, states_l, hyper, key, resid, xb, yb):
            # local views: tr leaves (1, s, *shape) -> (s, *shape);
            # zero states (1, ssz) -> (ssz,); mask (1, s) -> (s,)
            rank = lax.axis_index(dp) if ndp > 1 else 0
            if z3:
                # transient gather: resident (1, ssz) flat shards
                # become full stage weights only inside the executable
                params = {}
                for n in names:
                    w_sh = tr[n][0]
                    if wscheme is not None:
                        wf = quantized_all_gather(w_sh, dp, wscheme,
                                                  wblock)
                    else:
                        wf = lax.all_gather(w_sh, dp, axis=0,
                                            tiled=True)
                    params[n] = wf[:flat_meta[n][0]].reshape(
                        stacked[n].shape[1:])
            else:
                params = {n: tr[n][0] for n in names}
            params["__mask__"] = mask_l[0]
            states_ = {n: jax.tree_util.tree_map(lambda v: v[0],
                                                 states_l[n])
                       for n in names}
            if ndp > 1:
                key = jax.random.fold_in(key, lax.axis_index(dp))
            key = jax.random.fold_in(key, lax.axis_index(ppx))
            stage_fn = staged.make_stage_fn(jax.random.fold_in(key, 1))
            if manual_tp:
                # manual-TP context: the blocks' forwards re-execute at
                # trace time, see the flag, and issue local matmuls +
                # explicit psum(tp) instead of GSPMD constraints
                from .mesh import manual_axes as _manual_axes
                base_fn = stage_fn
                if virt > 1:
                    def stage_fn(p, c, h):
                        with _manual_axes({"tp": tpx}):
                            return base_fn(p, c, h)
                else:
                    def stage_fn(p, h):
                        with _manual_axes({"tp": tpx}):
                            return base_fn(p, h)
            mb_loss = _mb_loss(key)

            def run_pipe(xc, yc):
                """One 1F1B sweep over M microbatches; returns the mean
                microbatch loss and the mean local grads (stacked)."""
                mbs = xc.reshape(M, mbsz, *xc.shape[1:])
                ybs = yc.reshape(M, mbsz, *yc.shape[1:])
                if sched is not None:
                    loss_sum, grads = _pl._1f1b_interleaved_local(
                        params, mbs, ybs, stage_fn, mb_loss, ppx,
                        sched, loss_dtype=ld, wire=awire)
                else:
                    loss_sum, grads = _pl._1f1b_local(
                        params, mbs, ybs, stage_fn, mb_loss, ppx,
                        loss_dtype=ld, wire=awire)
                loss_sum = lax.psum(loss_sum, ppx)  # lives on last stage
                grads = {n: grads[n] / M for n in names}
                return loss_sum / M, grads

            if accum <= 1:
                loss, grads = run_pipe(xb, yb)
                red, new_resid = _reduce_dp(grads, resid)
            else:
                xm = xb.reshape(accum, xb.shape[0] // accum,
                                *xb.shape[1:])
                ym = yb.reshape(accum, yb.shape[0] // accum,
                                *yb.shape[1:])

                def acc_body(carry, xs):
                    gacc, lacc = carry
                    xc, yc = xs
                    l, g = run_pipe(xc, yc)
                    if shard_accum:
                        # reduce-scatter every chunk immediately: the
                        # carry is 1/ndp-sized and the full grad sum
                        # never exists (ZeRO-2 semantics)
                        g, _ = _reduce_dp(g, None)
                    gacc = jax.tree_util.tree_map(
                        lambda a, b: a + b.astype(a.dtype), gacc, g)
                    return (gacc, lacc + l.astype(jnp.float32)), None

                if shard_accum:
                    g0 = {n: jnp.zeros((flat_meta[n][2],), jnp.float32)
                          for n in names}
                else:
                    g0 = {n: jnp.zeros(stacked[n].shape[1:],
                                       jnp.float32) for n in names}
                (gsum, lsum), _ = lax.scan(
                    acc_body, (g0, jnp.float32(0.0)), (xm, ym))
                loss = (lsum / accum).astype(ld)
                grads = {n: v / accum for n, v in gsum.items()}
                if shard_accum:
                    red, new_resid = grads, {}
                else:
                    red, new_resid = _reduce_dp(grads, resid)

            if ndp > 1:
                loss = lax.pmean(loss, dp)

            # global grad norm: each pp rank holds its stage's slice of
            # `red` (full stacked for stage 0, 1/ndp flat shards under
            # zero) — sum locally, psum across the axes that partition
            if manual_tp and tp_sharding:
                gn2 = sum(jnp.sum(jnp.square(
                    red[n].astype(jnp.float32)))
                    for n in names if n not in tp_sharding)
                gn2 = gn2 + lax.psum(sum(
                    jnp.sum(jnp.square(red[n].astype(jnp.float32)))
                    for n in tp_sharding), tpx)
            else:
                gn2 = sum(jnp.sum(jnp.square(v.astype(jnp.float32)))
                          for v in red.values())
            gn2 = lax.psum(gn2, ppx)
            if stage >= 1:
                gn2 = lax.psum(gn2, dp)
            gnorm = jnp.sqrt(gn2)

            new_tr, new_states = {}, {}
            if stage == 0:
                # per-slot vmap: norm-based rules see each block's own
                # tensor, exactly like the unpipelined per-name loop
                # (interleaved runs fold the virtual dim into it)
                def upd(w, g, st):
                    return opt._step(w, g, st, hyper)
                for n in names:
                    w, g, st = params[n], red[n], states_[n]
                    if virt > 1:
                        nw, nst = jax.vmap(upd)(
                            w.reshape((-1,) + w.shape[2:]),
                            g.reshape((-1,) + g.shape[2:]),
                            jax.tree_util.tree_map(
                                lambda v: v.reshape((-1,) + v.shape[2:]),
                                st))
                        nw = nw.reshape(w.shape)
                        nst = jax.tree_util.tree_map(
                            lambda v, o: v.reshape(o.shape), nst, st)
                    else:
                        nw, nst = jax.vmap(upd)(w, g, st)
                    new_tr[n] = nw[None]
                    new_states[n] = jax.tree_util.tree_map(
                        lambda v: v[None], nst)
            else:
                for n in names:
                    numel, padded, ssz = flat_meta[n]
                    if z3:
                        w_sh = tr[n][0]
                    else:
                        wf = _pad_flat(params[n], padded)
                        w_sh = lax.dynamic_slice(wf, (rank * ssz,),
                                                 (ssz,))
                    nw, nst = opt._step(w_sh, red[n], states_[n],
                                        hyper)
                    if z3:
                        # ZeRO-3: the updated SHARD is the resident
                        # form — no post-update gather; the next step
                        # re-gathers at entry
                        new_tr[n] = nw[None]
                    else:
                        if wscheme is not None:
                            full = quantized_all_gather(nw, dp, wscheme,
                                                        wblock)
                        else:
                            full = lax.all_gather(nw, dp, axis=0,
                                                  tiled=True)
                        new_tr[n] = full[:numel].reshape(
                            stacked[n].shape[1:])[None]
                    new_states[n] = jax.tree_util.tree_map(
                        lambda v: v[None], nst)
            out = (loss.astype(jnp.float32), gnorm, new_tr, new_states)
            return out + ((new_resid,) if scheme is not None else ())

        def _wspec(n):
            """Stacked-weight spec: pp on the stage dim; a manual-TP
            parameter keeps its own axes on the trailing dims; ZeRO-3
            residents are flat (pp, dp) buckets instead."""
            if z3:
                return P(ppx, dp)
            lead = 1 + (1 if virt > 1 else 0)  # [virtual,] slots
            if n in tp_sharding:
                return P(ppx, *([None] * lead), *tp_sharding[n])
            return P(ppx, *([None] * (stacked[n].ndim - 1)))

        pspec = {n: _wspec(n) for n in names}
        st_spec = {n: jax.tree_util.tree_map(
            lambda _: P(ppx) if stage == 0 else P(ppx, dp), states[n])
            for n in names}
        # stage-0 state leaves mirror the stacked weight's rank (and
        # its manual-TP axes — momentum shards live beside the weight)
        if stage == 0:
            st_spec = {n: jax.tree_util.tree_map(
                lambda v, n=n: _wspec(n)
                if v.ndim == stacked[n].ndim
                else P(ppx, *([None] * (v.ndim - 1))), states[n])
                for n in names}
        dpn = dp if ndp > 1 else None
        batch_specs = (split_batch_spec(xr.ndim, 0, dpn),
                       split_batch_spec(yr.ndim, 0, dpn))
        in_specs = (pspec, P(ppx), st_spec, P(), P())
        out_specs = (P(), pspec, st_spec)
        loop_out_specs = (P(), P(), pspec, st_spec)
        resid_spec = None
        if scheme is not None:
            if stage == 0:
                resid_spec = {n: P(dp, ppx,
                                   *([None] * (stacked[n].ndim - 1)))
                              for n in names}
            else:
                resid_spec = {n: P(dp, ppx) for n in names}
            in_specs = in_specs + (resid_spec,)
            out_specs = out_specs + (resid_spec,)
            loop_out_specs = loop_out_specs + (resid_spec,)

            def fn_step(tr, mask_l, states_l, hyper, key, resid,
                        *batch):
                out = body(tr, mask_l, states_l, hyper, key, resid,
                           *batch)
                return (out[0],) + out[2:]

            def fn_stats(tr, mask_l, states_l, hyper, key, resid,
                         *batch):
                return body(tr, mask_l, states_l, hyper, key, resid,
                            *batch)
        else:
            def fn_step(tr, mask_l, states_l, hyper, key, *batch):
                out = body(tr, mask_l, states_l, hyper, key, None,
                           *batch)
                return (out[0],) + out[2:]

            def fn_stats(tr, mask_l, states_l, hyper, key, *batch):
                return body(tr, mask_l, states_l, hyper, key, None,
                            *batch)

        # check_vma=False: the dead-tick lax.cond branches and the
        # ppermute broadcast produce values the static replication
        # checker cannot type, and the loss/weights ARE replicated
        # where the specs say so
        fn = shard_map(fn_step, mesh=mesh,
                       in_specs=in_specs + batch_specs,
                       out_specs=out_specs, check_vma=False)
        donate = (0, 2, 5) if scheme is not None else (0, 2)
        self._compiled = jax.jit(
            fn, donate_argnums=donate if self.donate else ())
        fn_loop = shard_map(fn_stats, mesh=mesh,
                            in_specs=in_specs + batch_specs,
                            out_specs=loop_out_specs, check_vma=False)
        if scheme is not None:
            def loop_body(tr, mask_l, states_l, resid, hyper, key,
                          batch):
                loss, gnorm, ntr, nst, nres = fn_loop(
                    tr, mask_l, states_l, hyper, key, resid, *batch)
                return loss, gnorm, ntr, mask_l, nst, nres
        else:
            def loop_body(tr, mask_l, states_l, resid, hyper, key,
                          batch):
                loss, gnorm, ntr, nst = fn_loop(
                    tr, mask_l, states_l, hyper, key, *batch)
                return loss, gnorm, ntr, mask_l, nst, resid
        self._loop_body = loop_body
        self._loop_mode = "shardmap"

        def _nsh(spec):
            return NamedSharding(mesh, spec)

        if z3:
            self._tr = {}
            for n in names:
                numel, padded, _ssz = flat_meta[n]
                flat = stacked[n].reshape(npp, -1)
                if padded > numel:
                    flat = jnp.pad(flat, ((0, 0), (0, padded - numel)))
                self._tr[n] = _global_put(flat, _nsh(pspec[n]))
        else:
            self._tr = {n: _global_put(stacked[n], _nsh(pspec[n]))
                        for n in names}
        self._pp_mask = _global_put(mask, _nsh(P(ppx)))
        self._states = {
            n: jax.tree_util.tree_map(
                lambda v, sp: _global_put(v, _nsh(sp)),
                states[n], st_spec[n]) for n in names}
        if scheme is not None:
            self._resid = {}
            for n in names:
                if stage == 0:
                    shape = (ndp,) + tuple(stacked[n].shape)
                else:
                    shape = (ndp, npp, flat_meta[n][1])
                self._resid[n] = jax.device_put(
                    jnp.zeros(shape, jnp.float32),
                    _nsh(resid_spec[n]))
        self._batch_sh = tuple(_nsh(sp) for sp in batch_specs)
        self._tr_sh = {n: _nsh(pspec[n]) for n in names}
        self._aux_sh = {}
        self._st_sh = {n: jax.tree_util.tree_map(
            lambda sp: _nsh(sp), st_spec[n],
            is_leaf=lambda v: isinstance(v, P)) for n in names}
        self._tr_names = names
        self._aux_names = []
        self._aux = {}
        self.zero_stage = stage
        self._pp_nstages = npp
        self._pp_virtual = virt
        self._pp_total_ticks = sched.total_ticks if sched is not None \
            else None
        self._pp_flat_meta = flat_meta if z3 else None
        self._pp_full_shapes = {n: tuple(stacked[n].shape)
                                for n in names} if z3 else None
        _gp.set_plan_axes(dp=ndp, tp=ntp, pp=npp,
                          ep=getattr(plan, "ep", 1)
                          if plan is not None else 1)

        # static wire-vs-logical byte accounting per step, one rank's
        # perspective (mirrors the kvstore counters): the dp weight
        # gather of each stage's flat shards, and the 1F1B activation/
        # cotangent ppermute hops across all the schedule's ticks
        if stage >= 1 and ndp > 1:
            lg = wr = 0
            for n in names:
                isz = jnp.dtype(stacked[n].dtype).itemsize
                padded, ssz = flat_meta[n][1], flat_meta[n][2]
                lg += padded * isz
                wr += ndp * wire_nbytes(ssz, wscheme, wblock) \
                    if wscheme is not None else padded * isz
            self._wire_gathered = (lg, wr)
        if npp > 1:
            act_elems = mbsz * int(_np.prod(xr.shape[1:]))
            isz = jnp.dtype(xr.dtype).itemsize
            if sched is not None:
                # interleaved: both full rings (npp edges) shift every
                # one of the schedule's measured ticks
                hops = sched.total_ticks * 2 * npp * accum
            else:
                hops = (M + 2 * (npp - 1)) * 2 * (npp - 1) * accum
            lg = hops * act_elems * isz
            wr = hops * wire_nbytes(act_elems, ascheme, ablock) \
                if ascheme is not None else lg
            self._wire_permuted = (lg, wr)

    def zero1_state_nbytes(self):
        """(total, per_replica) optimizer-state bytes after _build —
        per_replica is total/N, the ZeRO-1 memory claim."""
        tot = sum(l.nbytes for l in jax.tree_util.tree_leaves(
            self._states))
        ndp = self.mesh.shape[self.dp_axis]
        return tot, tot // ndp

    def fused_resident_bytes(self):
        """Per-replica resident training bytes by category (profiler
        memory-provider contract). Sharded buffers count global/N;
        replicated buffers count full size. Grads are transient inside
        the executable (0 resident); the compression residual, the only
        grad-shaped state that survives the step, counts as grads."""
        ndp = self.mesh.shape.get(self.dp_axis, 1) \
            if self.mesh is not None else 1

        def per_replica(v):
            sh = getattr(v, "sharding", None)
            if sh is None or getattr(sh, "is_fully_replicated", True):
                return v.nbytes
            try:
                # exact per-device residency regardless of WHICH axes
                # shard the array (dp flat buckets, pp stage stacks,
                # dp x pp state): one shard's bytes
                return max(s.data.nbytes for s in v.addressable_shards)
            except Exception:
                return v.nbytes // ndp

        out = {"weights": 0, "grads": 0, "opt_state": 0, "transient": 0}
        for store, cat in ((self._tr, "weights"), (self._aux, "weights"),
                           (self._states, "opt_state"),
                           (self._resid, "grads")):
            if store is None:
                continue
            for leaf in jax.tree_util.tree_leaves(store):
                if hasattr(leaf, "nbytes"):
                    out[cat] += per_replica(leaf)
        return out

    # -- execution ------------------------------------------------------------
    def __call__(self, *args) -> NDArray:
        # in a profiler trace: one `mx.train_step` a call, holding the
        # `mx.data` phase and `mx.train_dispatch` (the compiled call)
        with _tm.span("train_step", **self._ready_counts()):
            return self._step(args)

    def _ready_counts(self):
        """{name: sum} over the launched steps whose counts have
        arrived since the last call (and `counted_steps`, how many).
        Empty for a step that counts nothing."""
        got = {}
        pending = self._counts_pending
        while pending and pending[0].is_ready():
            for n, v in zip(self._count_names + ("counted_steps",),
                            list(_np.asarray(pending.popleft())) + [1]):
                got[n] = got.get(n, 0) + int(v)
        return got

    def _step(self, args) -> NDArray:
        if self._params is None:
            self._init_state(args)
        if self._compiled is None:
            self._build(args)
        if _ft._ACTIVE:
            # preemption / straggler injection: the kill lands mid-run
            # with the previous step's state committed but this step's
            # not — exactly what the checkpoint resume harness needs
            _ft.kill_point("step.kill")
            _ft.delay_point("host.slow")
            if self._wire_gathered is not None or \
                    self._wire_permuted is not None:
                # the weight-gather / activation-permute collectives
                # run inside the executable; this host choke point is
                # where an armed collective.timeout simulates their
                # hang (kvstore.pushpull covers the eager direction)
                _ft.timeout_point("collective.timeout")
        self._step_count += 1
        self.optimizer.num_update = self._step_count
        hyper = self._hyper()
        key = _random.next_key()
        raw = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
               for a in args]
        with _tm.phase("data"):
            if self.mesh is not None:
                raw = [_global_put(r, sh)
                       for r, sh in zip(raw, self._batch_sh)]
        # one executable = fwd + bwd + grad psum + optimizer: the
        # internal phases are fused away by XLA, so telemetry records
        # the synced whole-step device span (pid 1 in the chrome trace)
        timed = _tm._ENABLED
        if timed:
            t0 = _time.perf_counter()
        fl_on = _fl._ENABLED and (self._wire_gathered is not None
                                  or self._wire_permuted is not None)
        if fl_on:
            # same event shape as KVStore.pushpull so post-mortems see
            # weight-gather / activation-hop stalls alongside the eager
            # collectives; bytes = wire payload per step (static)
            import time as _ftm
            t0f = _ftm.monotonic()
            if self._wire_gathered is not None:
                _fl.record("collective", "fused.all_gather",
                           key="__weights__", store="fused",
                           bytes=int(self._wire_gathered[1]))
            if self._wire_permuted is not None:
                _fl.record("collective", "fused.ppermute",
                           key="__activations__", store="fused",
                           bytes=int(self._wire_permuted[1]))
        # compile accounting, as serving's Program does it: the jit
        # cache grew during the call = this call traced and compiled
        n_exe = self._compiled._cache_size()
        t_call = _time.perf_counter()
        with _tm.span("train_dispatch"), \
                use_mesh(self.mesh if self.mesh is not None
                         else current_mesh()):
            if self._pp_mask is not None:
                cargs = (self._tr, self._pp_mask, self._states, hyper,
                         key)
                if self._resid is not None:
                    (loss, self._tr, self._states,
                     self._resid) = self._compiled(
                        *cargs, self._resid, *raw)
                else:
                    loss, self._tr, self._states = self._compiled(
                        *cargs, *raw)
            elif self._resid is not None:
                (loss, self._tr, self._aux, self._states,
                 self._resid) = self._compiled(
                    self._tr, self._aux, self._states, hyper, key,
                    self._resid, *raw)
            else:
                loss, self._tr, self._aux, self._states, *cnt = \
                    self._compiled(self._tr, self._aux, self._states,
                                   hyper, key, *raw)
                if cnt:
                    cnt[0].copy_to_host_async()
                    self._counts_pending.append(cnt[0])
        if self._compiled._cache_size() > n_exe:
            _tracing.record_compile("fused_step", None)
            _tracing.record_compile_seconds(
                "fused_step", _time.perf_counter() - t_call)
        else:
            _tracing.record_hit("fused_step")
        if timed:
            # everything before this point is host work: argument prep
            # plus the async dispatch (the compiled call returns before
            # the device finishes) — this is the overhead TrainLoop's
            # k="auto" amortizes across the fused window
            t_disp = _time.perf_counter()
        if fl_on:
            dtf = _ftm.monotonic() - t0f
            if self._wire_gathered is not None:
                _fl.record("collective_done", "fused.all_gather",
                           key="__weights__", dur_s=dtf)
            if self._wire_permuted is not None:
                _fl.record("collective_done", "fused.ppermute",
                           key="__activations__", dur_s=dtf)
        if timed:
            _tm.set_gauge("train_dispatch_overhead_ms_per_step",
                          (t_disp - t0) * 1e3)
            jax.block_until_ready(loss)
            dt = _time.perf_counter() - t0
            if _gp._ENABLED:
                # claim the host dispatch window first so the fused
                # device span's clipped remainder lands as productive
                _gp.charge_span("dispatch_overhead", t_disp - t0,
                                end=t_disp)
            _tm.mark_phase("fused_step", dt, t0=t0, device=True)
            if self._pp_staged is not None:
                # attribute the device span to fill/steady/drain and
                # publish the measured bubble_ratio gauge
                _tm.record_pipeline_step(
                    self._pp_nstages, self.pipeline, dt, t0=t0,
                    virtual=getattr(self, "_pp_virtual", 1),
                    total_ticks=self._pp_total_ticks)
            # host-side view of the same span: the eager phases land on
            # pid 0, so the fused step needs a host event there too for
            # a complete per-step host timeline
            _tm.mark_phase("fused_step_host", dt, t0=t0)
            nb = raw[0].shape[0] if raw and getattr(
                raw[0], "ndim", 0) else None
            _tm.step_done(nb)
            self._count_wire_bytes(1)
            if _gp._ENABLED:
                tok = None
                if nb:
                    shp = raw[0].shape
                    tok = int(nb) * (int(shp[1])
                                     if len(shp) > 1 else 1)
                if tok:
                    _gp.note_tokens("train", tok, self._n_chips())
                if self._pp_mask is not None:
                    gargs = (self._tr, self._pp_mask, self._states,
                             hyper, key)
                else:
                    gargs = (self._tr, self._aux, self._states,
                             hyper, key)
                if self._resid is not None:
                    gargs += (self._resid,)
                self._goodput_step(dt, tok, gargs + tuple(raw))
        return NDArray(loss)

    def _hyper(self):
        """The traced hyperparameter operands of one step."""
        opt = self.optimizer
        return {"lr": jnp.asarray(opt.learning_rate, jnp.float32),
                "wd": jnp.asarray(opt.wd, jnp.float32),
                "t": jnp.asarray(self._step_count, jnp.int32),
                "rescale": jnp.asarray(opt.rescale_grad, jnp.float32)}

    def lower(self, *args):
        """The ``jax.stages.Lowered`` of the single-step executable for
        this batch against the step's live state (built first if this
        is its first use; nothing runs, the RNG stream is untouched).
        ``.as_text()`` names the Pallas kernels in the module (each
        custom call carries its ``kernel_name``); ``.compile()
        .as_text()`` shows the shapes each device works on after
        partitioning."""
        if self._params is None:
            self._init_state(args)
        if self._compiled is None:
            self._build(args)
        raw = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
               for a in args]
        if self.mesh is not None:
            raw = [_global_put(r, sh)
                   for r, sh in zip(raw, self._batch_sh)]
        cargs = (self._tr,
                 self._aux if self._pp_mask is None else self._pp_mask,
                 self._states, self._hyper(), jax.random.PRNGKey(0))
        if self._resid is not None:
            cargs += (self._resid,)
        with use_mesh(self.mesh if self.mesh is not None
                      else current_mesh()):
            return self._compiled.lower(*cargs, *raw)

    #: goodput efficiency caches, filled by the first timed step
    _gp_nparams = None
    _gp_hw_flops = None

    def _goodput_step(self, step_s, tokens, call_args=None):
        """Feed the MFU/HFU gauges for one (per-)step: analytic
        6·N·tokens model FLOPs, plus traced ``cost_analysis()`` FLOPs
        once per build when *call_args* is given (a one-time AOT
        lower/compile — acceptable, goodput is an opt-in observer)."""
        if not _gp._ENABLED:
            return
        if self._gp_nparams is None:
            self._gp_nparams = sum(
                int(getattr(leaf, "size", 0) or 0)
                for leaf in jax.tree_util.tree_leaves(self._tr))
        model = 6.0 * self._gp_nparams * tokens if tokens else None
        if self._gp_hw_flops is None and call_args is not None:
            try:
                cost = self._compiled.lower(
                    *call_args).compile().cost_analysis()
                self._gp_hw_flops = float((cost or {}).get("flops",
                                                           0.0))
            except Exception:
                self._gp_hw_flops = 0.0
        _gp.note_train_step(step_s, model_flops=model,
                            hw_flops=self._gp_hw_flops or None,
                            chips=self._n_chips())

    def _n_chips(self) -> int:
        """Devices this step's executable spans (its mesh, else one)."""
        return 1 if self.mesh is None else int(self.mesh.devices.size)

    def _count_wire_bytes(self, k):
        """Feed the `comm_bytes_{gathered,permuted}` counter families
        for the in-executable weight all-gathers / activation ppermute
        hops (labels mirror ``KVStore._count_bytes``; store="fused").
        The byte totals are static per build — computed once at trace
        time and multiplied by the step count here, so the /metrics
        wire-vs-logical ratio proves the quantized-collective cut
        without touching the hot path."""
        if not _tm._ENABLED:
            return
        for op, stats in (("gathered", self._wire_gathered),
                          ("permuted", self._wire_permuted)):
            if stats is None:
                continue
            fam = _tm.counter(
                f"comm_bytes_{op}",
                "bytes moved by kvstore collectives (logical vs wire)")
            fam.labels(store="fused", kind="logical").inc(stats[0] * k)
            fam.labels(store="fused", kind="wire").inc(stats[1] * k)

    # -- whole-loop compilation (K steps per dispatch) -----------------------
    def _loop_fallback_reason(self):
        """Why run_steps must degrade to K=1 single dispatches, or None
        when the whole-loop path is usable (the degrade matrix in
        docs/compiled_loop.md)."""
        opt = self.optimizer
        if not getattr(opt, "supports_fused", True):
            return (f"{type(opt).__name__}.supports_fused is False "
                    "(host-side state or randomness in the update)")
        sched = getattr(opt, "lr_scheduler", None)
        if sched is not None and \
                getattr(sched, "as_traced", lambda: None)() is None:
            return (f"{type(sched).__name__} has no traced form "
                    "(as_traced() is None — it mutates host state per "
                    "call), so the in-scan step counter cannot "
                    "reproduce it")
        tr = self._trainer
        if tr is not None and getattr(tr, "_kvstore", None) is not None \
                and getattr(tr, "_update_on_kvstore", False):
            return ("update_on_kvstore routes every update through the "
                    "host kvstore")
        if self._loop_body is None:
            return "this build variant does not expose a scan body"
        return None

    def _build_loop(self, k, scaler, skip_on, unroll=1):
        """jit one lax.scan executable running `k` ticks of the SAME
        step body `_build` lowered for the single-dispatch path. The
        carry is (weights, aux, opt state, residuals, step counter,
        loss-scale state, skip streak); per-tick xs are the RNG key and
        the (K, ...)-stacked batch slices. LR schedule, AMP loss-scale
        and nonfinite-skip all run as traced functions of the in-carry
        counter, so nothing retraces across K boundaries."""
        body = self._loop_body
        opt = self.optimizer
        sched = getattr(opt, "lr_scheduler", None)
        lr_fn = getattr(sched, "as_traced", lambda: None)() \
            if sched is not None else None
        amp_on = scaler is not None
        traced_scale = scaler.traced_update_scale if amp_on else None

        def loop(tr, aux, states, resid, hyper0, carry0, keys, *sbatch):
            def tick(c, xs):
                tr, aux, states, resid, t, ls, unsk, streak = c
                key, batch = xs[0], xs[1:]
                t1 = t + 1
                lr = lr_fn(t1) if lr_fn is not None else hyper0["lr"]
                rescale = hyper0["rescale_unit"] / ls if amp_on \
                    else hyper0["rescale"]
                hyper = {"lr": jnp.asarray(lr, jnp.float32),
                         "wd": hyper0["wd"], "t": t1,
                         "rescale": jnp.asarray(rescale, jnp.float32)}
                loss, gnorm, ntr, naux, nst, nres = body(
                    tr, aux, states, resid, hyper, key, batch)
                skipped = jnp.int32(0)
                if not (skip_on or amp_on):
                    # drop the grad-norm output so XLA dead-code
                    # eliminates its reduction: a second consumer of
                    # every grad tensor breaks the grad->optimizer
                    # fusion and materializes the full grad set per
                    # tick — measurably slower for big nets on CPU
                    gnorm = jnp.zeros_like(loss)
                if skip_on or amp_on:
                    ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
                    if skip_on:
                        def sel(new, old):
                            return jax.tree_util.tree_map(
                                lambda a, b: jnp.where(ok, a, b),
                                new, old)
                        ntr, naux = sel(ntr, tr), sel(naux, aux)
                        nst, nres = sel(nst, states), sel(nres, resid)
                        streak = jnp.where(ok, 0, streak + 1)
                        skipped = (~ok).astype(jnp.int32)
                    if amp_on:
                        ls, unsk = traced_scale(ok, ls, unsk)
                return ((ntr, naux, nst, nres, t1, ls, unsk, streak),
                        (loss, gnorm, skipped))

            c0 = (tr, aux, states, resid, carry0["t"], carry0["scale"],
                  carry0["unskipped"], carry0["streak"])
            c, ys = lax.scan(tick, c0, (keys,) + sbatch, unroll=unroll)
            ntr, naux, nst, nres = c[:4]
            losses, gnorms, skips = ys
            return (losses, gnorms, skips, ntr, naux, nst, nres,
                    {"scale": c[5], "unskipped": c[6], "streak": c[7]})

        donate = (0, 2, 3) if self.donate else ()
        if self._loop_mode == "gspmd":
            # pin carry-out shardings to the carry-in ones so dispatch
            # N+1 sees identical argument shardings (no recompile)
            mesh = self.mesh
            repl = NamedSharding(mesh, P())
            hyper0_sh = {kk: repl for kk in
                         ("lr", "wd", "rescale", "rescale_unit")}
            carry0_sh = {kk: repl for kk in
                         ("t", "scale", "unskipped", "streak")}
            sb_sh = tuple(NamedSharding(mesh, P(None, *sh.spec))
                          for sh in self._batch_sh)
            fn = jax.jit(
                loop,
                in_shardings=(self._tr_sh, self._aux_sh, self._st_sh,
                              {}, hyper0_sh, carry0_sh, repl, *sb_sh),
                out_shardings=(repl, repl, repl, self._tr_sh,
                               self._aux_sh, self._st_sh, {},
                               {kk: repl for kk in
                                ("scale", "unskipped", "streak")}),
                donate_argnums=donate)
        else:
            fn = jax.jit(loop, donate_argnums=donate)
        return {"fn": fn, "fresh": True}

    def _stack_window(self, raw):
        """Host-stack one K-window to (K, ...) per argument and place
        it on the mesh (batch dim sharded per `self._batch_sh`)."""
        stacked = []
        for j in range(len(raw[0])):
            s = jnp.stack([raw[i][j] for i in range(len(raw))])
            if self.mesh is not None:
                s = _global_put(s, NamedSharding(
                    self.mesh, P(None, *self._batch_sh[j].spec)))
            stacked.append(s)
        return stacked

    def run_steps(self, batches, skip_nonfinite=None,
                  unroll=None, next_batches=None) -> NDArray:
        """Run ``len(batches)`` fused steps as ONE ``lax.scan``
        dispatch and return the stacked (K,) per-step losses.

        `batches` is a sequence of K per-step argument tuples (what
        ``__call__`` takes); they are stacked to (K, ...) on the host
        and sliced per scan tick on device, so the executable runs K
        full steps — forward, backward, gradient sync, optimizer —
        without returning to Python. Numerics match K single dispatches
        exactly: each tick consumes the same `random.next_key()` the
        single path would have drawn, and the LR schedule / weight
        decay / loss-scale are traced functions of the in-carry step
        counter (host LR or loss-scale changes between dispatches never
        retrace). One executable is compiled and cached per (K, batch
        shape) — a ragged final window simply compiles a second, K'-
        sized entry.

        With a Trainer carrying an AMP ``DynamicLossScaler`` and/or a
        ``GradSanitizer`` (or ``skip_nonfinite=True``), each tick also
        checks grad finiteness in-scan: nonfinite ticks skip the update
        (weights/state carried unchanged), the loss scale backs off /
        grows by the host scaler's own law, and the stacked skip flags
        are flushed to telemetry at the K boundary — where a sanitizer
        budget overrun raises ``FloatingPointError`` like the eager
        path. Host-visible per-step telemetry (stacked loss, grad norm,
        skip flags) lands in ``self.last_loop_metrics``.

        Unfusable configs — host-stateful LR schedulers,
        ``supports_fused=False`` rules, update_on_kvstore — degrade
        loudly to K single dispatches (one RuntimeWarning). Checkpoint
        saves, fault-injection sites and the PreemptionHandler drain
        all align to K boundaries: sites fire once per dispatch, and
        ``_step_count`` only ever advances by K between dispatches."""
        batches = [tuple(b) if isinstance(b, (tuple, list)) else (b,)
                   for b in batches]
        k = len(batches)
        if k == 0:
            raise ValueError("run_steps needs at least one batch")
        if self._count_names:
            raise ValueError("counts= rides single dispatches only: a "
                             "scanned window hands none back")
        if self._params is None:
            self._init_state(batches[0])
        if self._compiled is None:
            self._build(batches[0])
        trainer = self._trainer
        scaler = getattr(trainer, "_amp_scaler", None) \
            if trainer is not None else None
        sanitizer = getattr(trainer, "_sanitizer", None) \
            if trainer is not None else None
        amp_on = scaler is not None
        skip_on = bool(skip_nonfinite) if skip_nonfinite is not None \
            else (sanitizer is not None or amp_on)
        reason = self._loop_fallback_reason()
        # K=1 with no in-scan skip/loss-scale semantics is exactly a
        # single dispatch — skip the scan wrapper; skip_on/amp_on still
        # go through the (K=1) scan so the streak/scale law is uniform
        if reason is not None or (k == 1 and not (skip_on or amp_on)):
            if reason is not None and k > 1 and not self._loop_warned:
                import warnings
                warnings.warn(
                    f"run_steps(K={k}) degrading to K=1 single "
                    f"dispatches: {reason}", RuntimeWarning,
                    stacklevel=2)
                self._loop_warned = True
            losses = [self(*b)._data for b in batches]
            return NDArray(jnp.stack(losses))
        # one `mx.train_step` a dispatched window of `k` steps,
        # holding `mx.data` and `mx.train_dispatch`
        with _tm.span("train_step"):
            return self._run_window(batches, k, next_batches, unroll,
                                    scaler, sanitizer, amp_on, skip_on)

    def _run_window(self, batches, k, next_batches, unroll, scaler,
                    sanitizer, amp_on, skip_on) -> NDArray:
        """The scan path of `run_steps`: K steps in one dispatch."""
        opt = self.optimizer
        # double-buffer feed: if the previous dispatch staged THIS
        # window (run_steps(..., next_batches=window)) while the device
        # was busy, consume the device-resident copy instead of paying
        # the host stack + device_put on the critical path. Identity of
        # the original batch objects keys the hand-off.
        staged, self._feed_staged = self._feed_staged, None
        ids = tuple(id(a) for b in batches for a in b)
        pre_stacked = None
        if staged is not None and staged[0] == ids:
            raw, pre_stacked = staged[1], staged[2]
            if _tm._ENABLED:
                _tm.inc("train_feed_window_hits_total")
        else:
            raw = [[a._data if isinstance(a, NDArray)
                    else jnp.asarray(a) for a in b] for b in batches]
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in raw[0])
        # unroll=k flattens the scan into straight-line code: same
        # single dispatch, but no while-loop boundary, so XLA keeps the
        # single-step executable's layouts/fusions (on CPU the loop
        # carry otherwise pays per-tick weight-layout copies that can
        # swamp the dispatch saving for conv-heavy nets). Costs ~k x
        # compile time; default 1 (rolled), settable per call or via
        # `self.loop_unroll`.
        if unroll is None:
            unroll = getattr(self, "loop_unroll", 1)
        unroll = k if unroll is True else min(int(unroll), k)
        name = f"train_loop_k{k}"
        ck = (k, sig, amp_on, skip_on, unroll)
        entry = self._loop_cache.get(ck)
        if entry is None:
            entry = self._build_loop(k, scaler if amp_on else None,
                                     skip_on, unroll=max(1, unroll))
            self._loop_cache[ck] = entry
        else:
            _tracing.record_hit(name)

        if _ft._ACTIVE:
            # one fire per dispatch: fault sites land on K boundaries,
            # with the previous window fully committed
            _ft.kill_point("step.kill")
            _ft.delay_point("host.slow")
            if self._wire_gathered is not None or \
                    self._wire_permuted is not None:
                _ft.timeout_point("collective.timeout")

        # K host key draws — the exact key sequence K single dispatches
        # would consume, so dropout/RNG parity is bitwise
        keys = jnp.stack([_random.next_key() for _ in range(k)])
        with _tm.phase("data"):
            stacked = pre_stacked if pre_stacked is not None \
                else self._stack_window(raw)

        hyper0 = {
            "lr": jnp.asarray(opt.lr, jnp.float32),
            "wd": jnp.asarray(opt.wd, jnp.float32),
            "rescale": jnp.asarray(opt.rescale_grad, jnp.float32),
            "rescale_unit": jnp.asarray(
                opt.rescale_grad * (scaler.loss_scale if amp_on
                                    else 1.0), jnp.float32)}
        if amp_on:
            ls0, unsk0 = scaler.as_carry()
        else:
            ls0, unsk0 = jnp.float32(1.0), jnp.int32(0)
        carry0 = {"t": jnp.asarray(self._step_count, jnp.int32),
                  "scale": ls0, "unskipped": unsk0,
                  "streak": jnp.asarray(self._loop_streak, jnp.int32)}
        aux_in = self._pp_mask if self._pp_mask is not None \
            else self._aux
        resid_in = self._resid if self._resid is not None else {}

        timed = _tm._ENABLED
        fresh = entry.pop("fresh", False)
        if timed or fresh:
            t_start = _time.perf_counter()
        fl_on = _fl._ENABLED and (self._wire_gathered is not None
                                  or self._wire_permuted is not None)
        if fl_on:
            t0f = _time.monotonic()
            if self._wire_gathered is not None:
                _fl.record("collective", "fused.all_gather",
                           key="__weights__", store="fused",
                           bytes=int(self._wire_gathered[1]) * k)
            if self._wire_permuted is not None:
                _fl.record("collective", "fused.ppermute",
                           key="__activations__", store="fused",
                           bytes=int(self._wire_permuted[1]) * k)
        with _tm.span("train_dispatch"), \
                use_mesh(self.mesh if self.mesh is not None
                         else current_mesh()):
            (losses, gnorms, skips, self._tr, aux_out, self._states,
             resid_out, carry_out) = entry["fn"](
                self._tr, aux_in, self._states, resid_in, hyper0,
                carry0, keys, *stacked)
        if timed:
            # host prep + async dispatch for the whole K-window; the
            # per-step share (divided by k below) feeds k="auto"
            t_disp = _time.perf_counter()
        if fl_on:
            dtf = _time.monotonic() - t0f
            if self._wire_gathered is not None:
                _fl.record("collective_done", "fused.all_gather",
                           key="__weights__", dur_s=dtf)
            if self._wire_permuted is not None:
                _fl.record("collective_done", "fused.ppermute",
                           key="__activations__", dur_s=dtf)
        if next_batches is not None:
            # stage window i+1 while window i runs: the dispatch above
            # is async, so this host stack + device_put overlaps the
            # device scan. Dropping the previous staged refs here is
            # the donation — XLA reuses the freed buffers.
            t_feed = _time.perf_counter()
            nxt = [tuple(b) if isinstance(b, (tuple, list)) else (b,)
                   for b in next_batches]
            nraw = [[a._data if isinstance(a, NDArray)
                     else jnp.asarray(a) for a in b] for b in nxt]
            self._feed_staged = (
                tuple(id(a) for b in nxt for a in b), nraw,
                self._stack_window(nraw))
            if _tm._ENABLED:
                _tm.set_gauge("train_feed_overlap_ms",
                              (_time.perf_counter() - t_feed) * 1e3)
                _tm.inc("train_feed_windows_staged_total")
        if fresh:
            jax.block_until_ready(losses)
            _tracing.record_compile(name, None)
            _tracing.record_compile_seconds(
                name, _time.perf_counter() - t_start)
        if self._pp_mask is not None:
            self._pp_mask = aux_out
        else:
            self._aux = aux_out
        if self._resid is not None:
            self._resid = resid_out
        self._step_count += k
        opt.num_update = self._step_count

        if amp_on:
            scaler.sync_from_carry(carry_out["scale"],
                                   carry_out["unskipped"])
        if skip_on:
            self._loop_streak = int(carry_out["streak"])
            nskip = int(jnp.sum(skips))
            if nskip and _tm._ENABLED:
                _tm.inc("steps_skipped_nonfinite_total", nskip)
            if nskip and _fl._ENABLED:
                _fl.record("sanitizer_skip", "run_steps",
                           skipped=nskip, streak=self._loop_streak,
                           step=self._step_count)
            if sanitizer is not None:
                sanitizer.consecutive_skips = self._loop_streak
                cap = sanitizer.max_consecutive_skips
                if self._loop_streak > cap:
                    if _fl._ENABLED:
                        _fl.record("abort", "grad_sanitizer",
                                   consecutive=self._loop_streak,
                                   max=cap, step=self._step_count)
                        _fl.dump(reason="sanitizer_abort")
                    raise FloatingPointError(
                        f"gradients nonfinite for {self._loop_streak} "
                        f"consecutive steps (> max_consecutive_skips="
                        f"{cap}) — the run has diverged; lower the lr "
                        "or check the data pipeline")
        self.last_loop_metrics = {"loss": NDArray(losses),
                                  "grad_norm": NDArray(gnorms),
                                  "skipped": NDArray(skips)}

        if timed:
            jax.block_until_ready(losses)
            dt = _time.perf_counter() - t_start
            per = dt / k
            if _gp._ENABLED:
                # whole-window host dispatch claimed before the
                # synthesized per-step device spans land as productive
                _gp.charge_span("dispatch_overhead",
                                t_disp - t_start, end=t_disp)
            # per-step device spans are synthesized by even split: the
            # K steps ran back-to-back inside one executable, so the
            # per-step timeline shows K contiguous spans with the
            # per-dispatch host gap gone
            for i in range(k):
                _tm.mark_phase("fused_step", per, t0=t_start + i * per,
                               device=True)
            if self._pp_staged is not None:
                _tm.record_pipeline_step(
                    self._pp_nstages, self.pipeline, dt, t0=t_start,
                    virtual=getattr(self, "_pp_virtual", 1),
                    total_ticks=self._pp_total_ticks)
            _tm.mark_phase("fused_loop_host", dt, t0=t_start)
            nb = raw[0][0].shape[0] if raw[0] and getattr(
                raw[0][0], "ndim", 0) else None
            _tm.step_done(nb * k if nb else None, steps=k)
            _tm.set_gauge("train_loop_k", k)
            _tm.set_gauge("train_dispatch_overhead_ms_per_step",
                          (t_disp - t_start) / k * 1e3)
            _tm.inc("train_loop_dispatches_total")
            self._count_wire_bytes(k)
            if _gp._ENABLED:
                tok = None
                if nb:
                    shp = raw[0][0].shape
                    tok = int(nb) * (int(shp[1])
                                     if len(shp) > 1 else 1)
                if tok:
                    _gp.note_tokens("train", tok * k, self._n_chips())
                # no AOT re-lower of the scan executable: the fused
                # window would recompile; MFU rides the analytic flops
                self._goodput_step(per, tok)
        return NDArray(losses)
