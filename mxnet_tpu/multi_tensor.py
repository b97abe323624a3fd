"""Multi-tensor fused optimizer step for the eager Trainer path.

Reference parity: the fork's multi_mp_sgd / multi_lars / multi_sum_sq
kernels — ONE kernel launch updates every tensor of a group instead of
O(num_params) tiny launches. TPU-first redesign: the whole eager
optimizer step becomes one (or a few, dtype-grouped) XLA executables.

Per group of parameters sharing (weight dtype, multi-precision mode,
optimizer-state structure):

  1. gradients are flattened into ~4 MB buckets (`plan_buckets` /
     `flatten_buckets`) so the cross-replica sync is one collective per
     bucket instead of one per tensor — which is also what makes
     quantized allreduce pay off (EQuARX, arXiv:2506.17615: 2-bit codes
     + error feedback ride the wire per-bucket);
  2. a single jitted, state-donating function rescales, clips, runs the
     optimizer's `_step` math over every tensor in the group (so
     SGD/NAG/Adam/AdamW/LAMB/LARS all fuse for free, including
     multi-precision fp32 master weights), and returns new weights +
     states;
  3. executables are cached per (shapes, dtypes, state-structure) key —
     the Trainer-side analogue of `HybridBlock._jit_cache` — so repeated
     same-shape steps never retrace.

Per-tensor hyperparameters (lr, wd, step count) enter as traced vectors
and the global rescale as a traced scalar, so LR schedules, lr_mult /
wd_mult and loss-scale changes never trigger recompiles. The math is the
SAME `Optimizer._step` the per-parameter loop jits, applied in the same
order with the same 0-d hyper values, so the fused path is numerically
identical to the loop it replaces.
"""
from __future__ import annotations

import time
from collections import OrderedDict
from typing import Dict, List, Sequence, Tuple

import numpy as _np

import jax
import jax.numpy as jnp

from . import faults as _ft
from . import flight as _fl
from . import telemetry as _tm

__all__ = ["MultiTensorUpdater", "plan_buckets", "flatten_buckets",
           "unflatten_buckets", "DEFAULT_BUCKET_BYTES",
           "zero1_padded_sizes", "bucket_segments", "zero1_update_shard",
           "is_elementwise_rule"]

#: bucket size for flattened-gradient collectives (~4 MB, the sweet spot
#: between per-tensor launch overhead and collective latency hiding)
DEFAULT_BUCKET_BYTES = 4 << 20

#: shard granularity for ZeRO-1 bucket padding: every shard is a whole
#: number of TPU lanes so the per-replica slice keeps the (8, 128)
#: layout tileable
ZERO1_LANE = 128

#: mesh axis name for the eager updater's weight-update shards
ZERO1_AXIS = "z1"


# -- bucketing (pure shape arithmetic; traceable flatten/unflatten) --------

def plan_buckets(shapes: Sequence[Tuple[int, ...]], dtypes: Sequence,
                 bucket_bytes: int = DEFAULT_BUCKET_BYTES):
    """Partition tensors into contiguous flat buckets of <= bucket_bytes
    (a tensor larger than the budget gets a bucket of its own).

    Returns a list of buckets; each bucket is a list of
    (tensor_index, offset, size, shape) with static offsets so slicing
    stays free inside jit.
    """
    plans, cur, cur_bytes, off = [], [], 0, 0
    for k, (shape, dtype) in enumerate(zip(shapes, dtypes)):
        size = int(_np.prod(shape)) if len(shape) else 1
        nbytes = size * jnp.dtype(dtype).itemsize
        if cur and cur_bytes + nbytes > bucket_bytes:
            plans.append(cur)
            cur, cur_bytes, off = [], 0, 0
        cur.append((k, off, size, tuple(shape)))
        off += size
        cur_bytes += nbytes
    if cur:
        plans.append(cur)
    return plans


def flatten_buckets(leaves: Sequence, plans, dtype=None) -> List:
    """Concatenate raveled tensors per bucket (jit-traceable)."""
    out = []
    for plan in plans:
        parts = [leaves[k].reshape(-1) for (k, _, _, _) in plan]
        if dtype is not None:
            parts = [p.astype(dtype) for p in parts]
        out.append(parts[0] if len(parts) == 1
                   else jnp.concatenate(parts))
    return out


def unflatten_buckets(buckets: Sequence, plans, n: int) -> List:
    """Inverse of flatten_buckets: static slices back to tensor shapes.
    Tolerates trailing padding in the buckets (offsets are static, so a
    ZeRO-1 padded bucket unflattens with the same plan)."""
    leaves = [None] * n
    for b, plan in zip(buckets, plans):
        for (k, off, size, shape) in plan:
            leaves[k] = jax.lax.slice(b, (off,), (off + size,)) \
                .reshape(shape)
    return leaves


# -- ZeRO-1 sharding helpers (arXiv:2004.13336) -----------------------------

def zero1_padded_sizes(plans, num_shards: int,
                       lane: int = ZERO1_LANE) -> List[int]:
    """Padded total size per bucket: the smallest multiple of
    num_shards*lane covering the bucket, so every replica owns an equal,
    lane-aligned contiguous shard."""
    quantum = num_shards * lane
    out = []
    for plan in plans:
        used = plan[-1][1] + plan[-1][2]
        out.append(max(quantum, -(-used // quantum) * quantum))
    return out


def pad_buckets(buckets: Sequence, plans, padded: Sequence[int]) -> List:
    """Zero-pad flat buckets to their ZeRO-1 padded sizes (traceable)."""
    out = []
    for b, plan, tot in zip(buckets, plans, padded):
        used = plan[-1][1] + plan[-1][2]
        if tot > used:
            b = jnp.concatenate([b, jnp.zeros((tot - used,), b.dtype)])
        out.append(b)
    return out


def bucket_segments(plans, padded: Sequence[int], n: int) -> List:
    """Per-bucket int32 segment ids mapping each flat element to its
    group-local tensor index; padding elements get the out-of-range id
    `n` so they pick up the harmless pad entry of the hyper vectors and
    form their own (all-zero) norm segment."""
    segs = []
    for plan, tot in zip(plans, padded):
        s = _np.full((tot,), n, _np.int32)
        for (k, off, size, _) in plan:
            s[off:off + size] = k
        segs.append(s)
    return segs


def _tensorwise_norm(seg, num_segments: int, axis_name):
    """Build `norm(x)` for Optimizer._zero1_step: per-element broadcast
    of each tensor's GLOBAL L2 norm, computed as segment partial sums on
    the local shard + a cross-shard psum."""
    def norm(x):
        part = jax.ops.segment_sum(jnp.square(x.astype(jnp.float32)), seg,
                                   num_segments=num_segments,
                                   indices_are_sorted=True)
        if axis_name is not None:
            part = jax.lax.psum(part, axis_name)
        return jnp.sqrt(part)[seg]
    return norm


def zero1_update_shard(opt, w, g, state, hyper, seg, num_segments: int,
                       axis_name):
    """Run one fused optimizer update on a 1/N contiguous shard of a
    flattened bucket. `hyper` values may be scalars (FusedTrainStep) or
    per-element vectors (eager updater); norm-based rules (LAMB/LARS)
    get exact global per-tensor norms through the seg/psum helper."""
    return opt._zero1_step(w, g, state, hyper,
                           _tensorwise_norm(seg, num_segments, axis_name))


def is_elementwise_rule(opt) -> bool:
    """True when `opt`'s update math is purely elementwise — i.e. it did
    NOT override Optimizer._zero1_step to consume per-tensor norms
    (LAMB/LARS do). Elementwise rules can run on arbitrary contiguous
    slices of flattened/stacked weights with no norm bookkeeping, which
    is what the pipeline ZeRO path (flat per-stage shards, no segment
    ids) requires."""
    from .optimizer import Optimizer
    return type(opt)._zero1_step is Optimizer._zero1_step


class _FlatWeight:
    """Minimal weight stand-in for Optimizer.create_state on a flat
    bucket (works under jax.eval_shape, so probing a state's structure
    and dtypes never allocates bucket-sized buffers)."""

    __slots__ = ("_data",)

    def __init__(self, data):
        self._data = data

    @property
    def shape(self):
        return self._data.shape

    @property
    def dtype(self):
        return self._data.dtype


# -- the fused updater ------------------------------------------------------

class _GroupExec:
    """Compiled artifacts for one parameter group: the fused update
    executable, the (optional) gradient flatten executable and its
    bucket plan."""

    __slots__ = ("update_fn", "flatten_fn", "plans")

    def __init__(self, update_fn, flatten_fn=None, plans=None):
        self.update_fn = update_fn
        self.flatten_fn = flatten_fn
        self.plans = plans


class _ZeroGroup:
    """One ZeRO parameter group: compiled executables plus the RESIDENT
    sharded optimizer state. Unlike the unsharded path (state lives
    per-parameter in Trainer._states), the authoritative state here is
    one tree per flat bucket, laid out P(z1) across the update mesh so
    each device holds 1/N of every moment/master buffer. Stage 2 adds
    resident 1/N GRAD shards (filled by autograd hooks as backward
    produces each bucket); stage 3 makes the sharded WEIGHT buckets
    authoritative, with just-in-time gathers on access."""

    __slots__ = ("idxs", "mp", "plans", "padded", "segs", "shard",
                 "flatten_fn", "flatpad_fn", "pad_fn", "wpad_fn",
                 "update_fn", "unflatten_fn", "states", "masters",
                 "wshards", "wrote", "home", "params", "reqs", "gdtype",
                 "flat1_fns", "pad1_fns", "flatpad1_fns", "unflat1_fns",
                 "pending", "gshards", "gfresh", "baccum", "k2bucket",
                 "inflight", "wq1_fns", "wdq1_fns", "wire_bytes")

    def __init__(self, idxs, mp, plans, padded, segs, shard, flatten_fn,
                 flatpad_fn, pad_fn, wpad_fn, update_fn, unflatten_fn,
                 states, masters, home):
        self.idxs = idxs
        self.mp = mp
        self.plans = plans
        self.padded = padded
        self.segs = segs
        self.shard = shard        # NamedSharding(mesh, P(z1))
        self.flatten_fn = flatten_fn
        self.flatpad_fn = flatpad_fn
        self.pad_fn = pad_fn
        self.wpad_fn = wpad_fn
        self.update_fn = update_fn
        self.unflatten_fn = unflatten_fn
        self.states = states      # per bucket: sharded state tree
        self.masters = masters    # per bucket: sharded fp32 flat (mp)
        self.home = home          # SingleDeviceSharding: gather target
        #: resident P(z1) weight buckets — stage <= 2: an optimization
        #: (skip the re-upload while `wrote` matches); stage 3: THE
        #: authoritative weights (low-precision copy under mp)
        self.wshards = None
        #: the per-tensor arrays written back last step, for the
        #: identity staleness check (set_data() breaks the match and
        #:  forces a re-import)
        self.wrote = None
        #: group-local Parameter list / grad_req snapshot (hook + stage-3
        #: paths address members by local index k)
        self.params = None
        self.reqs = None
        self.gdtype = None
        #: per-bucket single-bucket executables (hook flush / JIT gather)
        self.flat1_fns = None
        self.pad1_fns = None
        self.flatpad1_fns = None
        self.unflat1_fns = None
        #: stage-2 collector: per-bucket {local k -> cotangent} awaiting
        #: members, the resident 1/N grad shards, and per-bucket
        #: freshness (a fresh shard already holds this round's reduction)
        self.pending = None
        self.gshards = None
        self.gfresh = None
        #: per-bucket: True when every member has grad_req == "add" (the
        #: shard then ACCUMULATES across backward passes / microbatches)
        self.baccum = None
        self.k2bucket = None
        #: stage-3 prefetch: bucket index -> in-flight gathered flat
        #: bucket (dispatched async one bucket ahead of use)
        self.inflight = None


class MultiTensorUpdater:
    """Applies one optimizer step to many parameters as a handful of
    fused XLA executables (one per dtype/state-structure group)."""

    def __init__(self, optimizer, bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                 zero1: bool = False, num_shards: int = None,
                 stage: int = None, weight_compression=None):
        self.optimizer = optimizer
        self.bucket_bytes = bucket_bytes
        #: weights-direction wire compression for the ZeRO gathers
        #: (block-scaled int8/fp8, parallel/compression.py): the shard
        #: quantizes before the shard->home transfer, dequantizes on
        #: arrival. The eager chain is drift-free without residuals —
        #: zg.wshards (the authoritative copy) is never quantized, only
        #: the transient materialized replicas are.
        from .parallel.data_parallel import _normalize_wire_cfg
        wc = _normalize_wire_cfg(weight_compression, "weights")
        if wc is not None:
            import warnings
            if wc.pop("residual", False):
                warnings.warn(
                    "weight_compression residual mode is a fused-step "
                    "(FusedTrainStep zero=3) concern; the eager "
                    "updater's authoritative sharded weights are never "
                    "quantized, so gathers are drift-free without it — "
                    "ignored")
            if (int(stage) if stage is not None
                    else (1 if zero1 else 0)) < 1:
                warnings.warn(
                    "weight_compression requires a ZeRO stage (the "
                    "unsharded fused path gathers no weights); ignored")
                wc = None
        self._wcomp = wc
        self._cache: Dict = {}
        #: trace count — cache misses; steady state adds zero
        self.compiles = 0
        #: ZeRO weight-update sharding (arXiv:2004.13336): stage 1
        #: shards optimizer state, stage 2 additionally persists only
        #: 1/N grad shards (reduce-scattered by autograd hooks during
        #: backward), stage 3 additionally keeps the weights sharded
        #: with just-in-time gathers. `zero1=True` is the stage-1 alias.
        self.stage = int(stage) if stage is not None else (1 if zero1 else 0)
        self.zero1 = self.stage >= 1
        self._num_shards = num_shards
        self._zmesh = None
        self._zgroups: Dict = {}
        # stage >= 2 hook state: the registered fused param set, its
        # live states dict / kvstore, and the lazily-(re)built map from
        # param index -> (group, gid, bucket j, local k)
        self._hook_params = None
        self._hook_states = None
        self._hook_kvstore = None
        self._hook_map = None
        self._hook_sig = None
        #: observability: bucket flushes fired DURING backward (overlap)
        #: vs. flushed lazily at step()
        self.hook_flushes = 0
        self.step_flushes = 0
        if self.stage >= 1:
            import weakref
            from . import profiler as _prof
            ref = weakref.ref(self)
            _prof.register_memory_provider(
                f"zero{self.stage}_updater_{id(self):x}",
                lambda: (lambda u: None if u is None
                         else u.zero_resident_bytes())(ref()))

    @property
    def cache_size(self) -> int:
        return len(self._cache)

    @staticmethod
    def supports(optimizer) -> bool:
        """A rule fuses iff it uses the stock update() driver around a
        pure `_step` (SGLD draws eager RNG and opts out via
        `supports_fused = False`)."""
        from .optimizer import Optimizer
        cls = type(optimizer)
        return (getattr(cls, "supports_fused", True)
                and cls.update is Optimizer.update
                and cls._step is not Optimizer._step)

    # -- grouping ----------------------------------------------------------
    def _mp_active(self, p, state) -> bool:
        opt = self.optimizer
        return (opt._use_mp(p._data) and isinstance(state, tuple)
                and len(state) == 2 and isinstance(state[0], jax.Array))

    def _group_members(self, indexed_params, states: Dict):
        """Partition params into fused groups. Shared by step() and the
        stage-2 hook path so bucket/group ids (and therefore compression
        residual keys) always agree between the two."""
        opt = self.optimizer
        groups: "OrderedDict" = OrderedDict()
        for i, p in indexed_params:
            if self.zero1 and i not in states:
                # state lives shard-sized inside a _ZeroGroup (or is yet
                # to be created there) — group by weight dtype + mp only
                mp = opt._use_mp(p._data)
                skey = ("__zero1__", mp)
                state = None
            else:
                state = states.get(i)
                mp = self._mp_active(p, state)
                skey = jax.tree_util.tree_structure(state)
            # p._data._data may be a stage-3 ShapeDtypeStruct placeholder
            # (released weights); .dtype works on both, and crucially the
            # grouping never forces a materializing p.data() call
            key = (str(p._data._data.dtype), mp, skey)
            groups.setdefault(key, []).append((i, p, state))
        return groups

    def step(self, indexed_params, states: Dict, kvstore=None):
        """One fused optimizer step over `indexed_params`
        ([(index, Parameter), ...]). Mutates parameter data in place and
        rebinds `states[index]`, exactly like the per-param loop."""
        opt = self.optimizer
        groups = self._group_members(indexed_params, states)
        # bump every update count first; identical to the interleaved
        # loop because all counts advance in lockstep (num_update is the
        # running max, reached at the first parameter either way)
        for i, _ in indexed_params:
            opt._update_count(i)
        for gid, members in enumerate(groups.values()):
            if self.zero1:
                self._apply_group_zero(gid, members, states, kvstore)
            else:
                self._apply_group(gid, members, states, kvstore)

    # -- per-group fused executables ---------------------------------------
    def _apply_group(self, gid, members, states, kvstore):
        opt = self.optimizer
        _, p0, s0 = members[0]
        mp = self._mp_active(p0, s0)
        wdtype = p0.data()._data.dtype
        if mp:
            ws = [st[0] for (_, _, st) in members]       # fp32 masters
            states_in = [st[1] for (_, _, st) in members]
        else:
            ws = [p.data()._data for (_, p, _) in members]
            states_in = [st for (_, _, st) in members]
        gs = [p.grad()._data for (_, p, _) in members]
        idxs = [i for (i, _, _) in members]
        lrs, wds, ts, rescale = opt._fused_hyper_vectors(idxs)

        bucketed = kvstore is not None
        cache_key = (type(opt), gid, mp, str(wdtype), bucketed,
                     tuple((tuple(g.shape), str(g.dtype)) for g in gs),
                     jax.tree_util.tree_structure(states_in))
        exe = self._cache.get(cache_key)
        if exe is None:
            exe = self._build(members, mp, wdtype, bucketed, gs)
            self._cache[cache_key] = exe
            self.compiles += 1

        if bucketed:
            buckets = exe.flatten_fn(gs)
            with _tm.phase("grad_comm"):
                gs = self._sync_buckets(kvstore, gid, buckets)

        if mp:
            with _tm.phase("optimizer"):
                new_ws, new_states, low_ws = exe.update_fn(
                    states_in, ws, gs, lrs, wds, ts, rescale)
            for k, (i, p, _) in enumerate(members):
                p.data()._data = low_ws[k]
                states[i] = (new_ws[k], new_states[k])
        else:
            with _tm.phase("optimizer"):
                new_ws, new_states = exe.update_fn(
                    states_in, ws, gs, lrs, wds, ts, rescale)
            for k, (i, p, _) in enumerate(members):
                p.data()._data = new_ws[k]
                states[i] = new_states[k]

    def _sync_buckets(self, kvstore, gid, buckets):
        """One pushpull (psum / compressed allreduce) per flat bucket —
        the O(num_params) -> O(num_buckets) collective reduction."""
        from .ndarray import NDArray
        nds = [NDArray(b) for b in buckets]
        kvstore.pushpull_buckets(gid, nds)
        return [nd._data for nd in nds]

    def _build(self, members, mp, wdtype, bucketed, gs) -> _GroupExec:
        opt = self.optimizer
        n = len(members)
        plans = flatten_fn = None
        if bucketed:
            plans = plan_buckets([g.shape for g in gs],
                                 [g.dtype for g in gs], self.bucket_bytes)
            _plans = plans

            def _flatten(grads):
                return flatten_buckets(grads, _plans)

            flatten_fn = jax.jit(_flatten)

        def run(states_in, ws, grads, lrs, wds, ts, rescale):
            if bucketed:
                grads = unflatten_buckets(grads, plans, n)
            new_ws, new_states, low_ws = [], [], []
            for k in range(n):
                hyper = {"lr": lrs[k], "wd": wds[k], "t": ts[k],
                         "rescale": rescale}
                g = grads[k]
                if mp:
                    g = g.astype(jnp.float32)
                nw, ns = opt._step(ws[k], g, states_in[k], hyper)
                new_ws.append(nw)
                new_states.append(ns)
                if mp:
                    low_ws.append(nw.astype(wdtype))
            if mp:
                return new_ws, new_states, low_ws
            return new_ws, new_states

        # donate the optimizer state (and, under multi-precision, the
        # fp32 masters — argnum 1 is the master list then): both are
        # owned exclusively by the Trainer and rebound after the call.
        # Weights are NOT donated on the non-mp path: the autograd tape
        # and user views may still alias those buffers.
        donate = (0, 1) if mp else (0,)
        return _GroupExec(jax.jit(run, donate_argnums=donate),
                          flatten_fn, plans)

    # -- ZeRO-1 weight-update sharding (arXiv:2004.13336) ------------------
    def _zero1_mesh(self):
        if self._zmesh is None:
            devs = jax.devices()
            n = self._num_shards or len(devs)
            n = max(1, min(int(n), len(devs)))
            self._zmesh = jax.sharding.Mesh(_np.asarray(devs[:n]),
                                            (ZERO1_AXIS,))
        return self._zmesh

    @property
    def num_shards(self) -> int:
        return int(self._zero1_mesh().devices.size)

    def _zero_group_for(self, gid, members, states):
        """Find (or build, spilling any overlapping stale group) the
        resident _ZeroGroup for this member set. Shared by step() and
        the stage-2 hook path."""
        opt = self.optimizer
        idxs = tuple(i for (i, _, _) in members)
        _, p0, s0 = members[0]
        wdtype = p0._data._data.dtype
        mp = (self._mp_active(p0, s0) if s0 is not None
              else opt._use_mp(p0._data))
        # keyed on weight metadata, not grads: under stage >= 2 the
        # full-size grad buffers no longer exist (attach_grad contract:
        # grads share the weight's shape and dtype)
        cache_key = (type(opt), mp, str(wdtype), idxs,
                     tuple((tuple(p._data._data.shape),
                            str(p._data._data.dtype))
                           for (_, p, _) in members))
        zg = self._zgroups.get(cache_key)
        if zg is None:
            # group composition changed (e.g. a grad_req toggled):
            # spill any overlapping group's sharded state back to
            # per-param form so the rebuild imports live values
            for k2 in [k for k, g2 in self._zgroups.items()
                       if set(g2.idxs) & set(idxs)]:
                self._export_group(self._zgroups.pop(k2), states)
            self._hook_map = None  # bucket layout changed
            zg = self._build_zero1(members, mp, wdtype, states)
            self._zgroups[cache_key] = zg
            self.compiles += 1
        return zg

    def _apply_group_zero(self, gid, members, states, kvstore):
        """ZeRO analogue of _apply_group: reduce(-scatter) the grad
        buckets, update only this replica's 1/N shard of every bucket
        (state resident sharded on the update mesh). Stage <= 2 gathers
        the new weights back to full per-tensor form; stage 3 keeps them
        sharded and releases the full-size parameter arrays."""
        opt = self.optimizer
        stage = self.stage
        idxs = tuple(i for (i, _, _) in members)
        zg = self._zero_group_for(gid, members, states)
        mp = zg.mp

        lrs, wds, ts, rescale = opt._fused_hyper_vectors(list(idxs))
        # entry n is the padding segment's hyper: lr/wd 0, t=1 (keeps
        # Adam's bias correction away from 1-beta**0 == 0)
        lrs = jnp.concatenate([lrs, jnp.zeros((1,), lrs.dtype)])
        wds = jnp.concatenate([wds, jnp.zeros((1,), wds.dtype)])
        ts = jnp.concatenate([ts, jnp.ones((1,), ts.dtype)])
        extras = opt._zero1_hyper_extras(lrs, wds, ts)

        if stage >= 2:
            # grads were reduce-scattered bucket-by-bucket as backward
            # produced them (autograd hooks); consume the resident
            # shards, force-flushing any bucket the hooks did not finish
            # (manual grad writes, partial backward)
            g_bks = self._collect_grad_shards(zg, gid, kvstore)
        else:
            gs = [p.grad()._data for (_, p, _) in members]
            with _tm.phase("grad_comm"):
                if kvstore is not None:
                    buckets = self._reduce_scatter(kvstore, gid,
                                                   zg.flatten_fn(gs))
                    pads = zg.pad_fn(buckets)
                else:
                    pads = zg.flatpad_fn(gs)
                # THE scatter: pad on the source device, then place each
                # grad bucket P(z1) so every replica receives exactly its
                # 1/N slice (params/grads may be committed to a single
                # device — explicit device_put is the one legal path onto
                # the update mesh)
                g_bks = jax.device_put(pads, [zg.shard] * len(pads))
        if mp:
            with _tm.phase("optimizer"):
                zg.states, zg.masters, w_bks = zg.update_fn(
                    zg.states, zg.masters, g_bks, zg.segs,
                    lrs, wds, ts, rescale, extras)
        else:
            if self._weights_clean(zg):
                # weights unchanged since our last write-back (or still
                # released, stage 3): reuse the resident sharded
                # buckets, skip the re-upload
                w_in = zg.wshards
            else:
                ws = [p.data()._data for (_, p, _) in members]
                w_in = jax.device_put(zg.wpad_fn(ws),
                                      [zg.shard] * len(zg.padded))
            with _tm.phase("optimizer"):
                zg.states, w_bks = zg.update_fn(
                    zg.states, w_in, g_bks, zg.segs, lrs, wds, ts,
                    rescale, extras)
        # resident sharded weights: stage 3's authoritative copy (the
        # low-precision one under mp); stage <= 2 keeps them only on the
        # non-mp path as a re-upload-skipping optimization
        zg.wshards = w_bks if (stage >= 3 or not mp) else None
        if stage >= 3:
            # no gather: the sharded buckets ARE the weights now. Full
            # arrays rematerialize lazily (Parameter.data() -> one
            # transient per-bucket gather with one-bucket lookahead).
            self._release_group(zg)
            return
        # the all-gather: one device_put per bucket back to the home
        # device (single-process gather — no host bounce). The arrays
        # land committed there, which matches where eager NDArray data
        # already lives; explicit device_put remains the path back onto
        # any mesh.
        if _ft._ACTIVE:
            _ft.timeout_point("collective.timeout")
        fl_on = _fl._ENABLED
        if fl_on:
            t0 = time.monotonic()
            _fl.record("collective", "zero.weight_gather",
                       store=f"zero{stage}",
                       bytes=sum(w for (_, w) in zg.wire_bytes))
        with _tm.phase("weight_gather"):
            if self._wcomp is not None:
                futs = [self._gather_dispatch(zg, j, b)
                        for j, b in enumerate(w_bks)]
                homed = [self._gather_finish(zg, j, f)
                         for j, f in enumerate(futs)]
            else:
                homed = jax.device_put(w_bks, [zg.home] * len(w_bks))
            new_ws = zg.unflatten_fn(homed)
            for k, (i, p, _) in enumerate(members):
                p.data()._data = new_ws[k]
        self._count_gather_bytes(zg, range(len(w_bks)))
        if fl_on:
            _fl.record("collective_done", "zero.weight_gather",
                       dur_s=time.monotonic() - t0)
        zg.wrote = list(new_ws)

    def _weights_clean(self, zg) -> bool:
        """True when the resident sharded weight buckets still reflect
        the parameters' live values: every member either carries the
        exact array we wrote back (identity check — set_data() breaks
        it) or is still released (stage-3 placeholder)."""
        if zg.wshards is None or zg.mp:
            # mp: fp32 masters are authoritative from the first build on
            return zg.wshards is not None and zg.mp
        if zg.wrote is None:
            return False
        for k, p in enumerate(zg.params):
            d = p._data._data
            if isinstance(d, jax.Array) and zg.wrote[k] is not d:
                return False
        return True

    # -- ZeRO-2: hook-driven grad bucket reduce-scatter --------------------
    def register_grad_hooks(self, indexed_params, states: Dict,
                            kvstore=None):
        """Install per-parameter autograd hooks (stage >= 2): each hook
        consumes its leaf's cotangent the moment backward finishes with
        it; when a bucket's last member lands, the bucket reduce-scatters
        immediately — overlapping comm with the rest of the backward
        walk — and only the 1/N shard stays resident. The full-size grad
        buffers are replaced by 0-size placeholders."""
        if self.stage < 2:
            return
        self._hook_params = list(indexed_params)
        self._hook_states = states
        self._hook_kvstore = kvstore
        self._hook_map = None
        self._hook_sig = None
        for i, p in self._hook_params:
            # registration must NOT clear existing grad buffers: the
            # trainer installs hooks lazily on the first step(), which
            # runs AFTER the first backward already wrote real grads
            # there. Buffers are freed the first time a hook consumes a
            # cotangent instead (_hook_fire).
            p._data._grad_hook = self._make_hook(i)

    def _make_hook(self, i):
        def hook(arr, g):
            return self._hook_fire(i, arr, g)
        return hook

    def _hook_signature(self):
        return tuple((i, id(p._data), p.grad_req)
                     for i, p in self._hook_params)

    def _ensure_hook_map(self):
        """(Re)build param index -> (group, gid, bucket, local k) using
        the SAME grouping as step(), so hook-time reduce-scatters use
        identical bucket tags (and compression residual keys) as the
        step-time path."""
        sig = self._hook_signature()
        if self._hook_map is not None and sig == self._hook_sig:
            return
        self._hook_sig = None
        live = [(i, p) for i, p in self._hook_params
                if p.grad_req != "null"]
        groups = self._group_members(live, self._hook_states)
        # build into a local dict: _zero_group_for nukes self._hook_map
        # when it (re)builds a group (e.g. after zero1_reset), which
        # would otherwise happen mid-loop
        hmap = {}
        for gid, members in enumerate(groups.values()):
            zg = self._zero_group_for(gid, members, self._hook_states)
            for k, (i, _, _) in enumerate(members):
                hmap[i] = (zg, gid, zg.k2bucket[k], k)
        self._hook_map = hmap
        self._hook_sig = sig

    def _hook_fire(self, i, arr, g) -> bool:
        """Autograd delivered leaf i's finalized cotangent. Stash it in
        its bucket's pending set; flush (reduce-scatter + accumulate
        into the resident shard) once the bucket is complete. Returns
        True when consumed."""
        if self.stage < 2 or self._hook_params is None:
            return False
        self._ensure_hook_map()
        ent = self._hook_map.get(i)
        if ent is None:
            return False
        zg, gid, j, k = ent
        buf = zg.pending[j]
        if k in buf:
            # same leaf contributed twice between flushes (e.g. two
            # backward passes): combine by its grad_req semantics
            buf[k] = buf[k] + g if zg.reqs[k] == "add" else g
        else:
            buf[k] = g
        gb = arr._grad
        if gb is not None and gb._data.size:
            # first consumption: free the full-size grad buffer — from
            # here on this leaf's resident grad state is the 1/N shard.
            # Under "add" the buffer may hold grads accumulated before
            # the hook was installed; fold them in first.
            if zg.reqs[k] == "add" and \
                    tuple(gb._data.shape) == tuple(g.shape):
                buf[k] = buf[k] + gb._data
            gb._data = jnp.zeros((0,), gb._data.dtype)
        if len(buf) == len(zg.plans[j]):
            self._flush_bucket(zg, gid, j)
            self.hook_flushes += 1
        return True

    def _flush_bucket(self, zg, gid, j, force=False):
        """Reduce-scatter one grad bucket into its resident 1/N shard.
        `force` fills members the hooks never saw from their grad
        buffers (manual writes) or zeros (partial backward)."""
        plan = zg.plans[j]
        buf = zg.pending[j]
        if not force and len(buf) < len(plan):
            return
        if force and not buf and zg.gfresh[j]:
            return  # nothing new since the last flush
        # keyed by the member's GROUP index k: the per-bucket jitted
        # fns index leaves[k] through the plan, and for any bucket past
        # the first k is not bucket-local (a dict is a pytree, so the
        # jit signature stays stable per bucket)
        leaves = {}
        for (k, off, size, shape) in plan:
            g = buf.get(k)
            if g is None:
                gb = zg.params[k]._data._grad
                d = gb._data if gb is not None else None
                if d is not None and tuple(d.shape) == shape:
                    g = d  # manually written full grad
                else:
                    g = jnp.zeros(shape, zg.gdtype)
            leaves[k] = g
        buf.clear()
        t0 = time.perf_counter() if _tm._ENABLED else 0.0
        kv = self._hook_kvstore
        if kv is not None and kv.supports_flat_pushpull():
            # same __flat__/{gid}/{j} key as the allreduce path: the
            # compression error-feedback residuals stay bit-identical
            from .ndarray import NDArray
            nd = NDArray(zg.flat1_fns[j](leaves))
            kv.reduce_scatter_bucket(gid, j, nd)
            flat = zg.pad1_fns[j](nd._data)
        else:
            flat = zg.flatpad1_fns[j](leaves)
        shard_flat = jax.device_put(flat, zg.shard)
        if _tm._ENABLED:
            _tm.mark_phase("grad_comm", time.perf_counter() - t0, t0=t0)
        if zg.gfresh[j] and zg.baccum[j] and zg.gshards[j] is not None:
            # grad_accum: accumulate IN THE SHARD — the full-size sum
            # never exists (slice-then-add == add-then-slice, elementwise
            # exact, so microbatch accumulation stays bit-identical to
            # the unsharded sum)
            zg.gshards[j] = zg.gshards[j] + shard_flat
        else:
            zg.gshards[j] = shard_flat
        zg.gfresh[j] = True

    def grad_shard_arrays(self):
        """Every live stage>=2 gradient array this updater holds: the
        resident reduce-scattered 1/N flat shards plus any cotangents
        still pending in partially-filled hook buckets. The trainer's
        GradSanitizer folds these into the global finiteness check —
        under ZeRO-2 the full-size grad buffers are already freed, so
        p.grad() alone would miss every hooked parameter."""
        out = []
        for zg in self._zgroups.values():
            if zg.gshards is not None:
                out.extend(a for a in zg.gshards if a is not None)
            if zg.pending is not None:
                for buf in zg.pending:
                    out.extend(buf.values())
        return out

    def discard_grads(self):
        """Drop every resident grad shard and pending hook cotangent
        (stage >= 2). Called when a step is SKIPPED (non-finite grads):
        the poisoned shards must not survive into the next round's
        accumulation."""
        for zg in self._zgroups.values():
            if zg.plans is None:
                continue
            nbk = len(zg.plans)
            if zg.gshards is not None:
                zg.gshards = [None] * nbk
            if zg.gfresh is not None:
                zg.gfresh = [False] * nbk
            if zg.pending is not None:
                for buf in zg.pending:
                    buf.clear()

    def _collect_grad_shards(self, zg, gid, kvstore):
        """Step-time consumption of the resident grad shards; buckets
        the hooks did not complete are force-flushed here (falling back
        to grad buffers / zeros)."""
        if self._hook_kvstore is None and kvstore is not None:
            self._hook_kvstore = kvstore
        nbk = len(zg.plans)
        for j in range(nbk):
            if zg.pending[j] or not zg.gfresh[j]:
                self._flush_bucket(zg, gid, j, force=True)
                self.step_flushes += 1
        out = zg.gshards
        # hand the shards to the (donating) update executable and reset
        # the collector for the next round
        zg.gshards = [None] * nbk
        zg.gfresh = [False] * nbk
        return out

    # -- weights-direction wire (gathers): quantize/count/finish -----------
    def _gather_dispatch(self, zg, j, bucket):
        """Dispatch bucket j's shard->home transfer. With weight wire
        compression the sharded bucket quantizes first, so the 1-byte
        codes + per-block fp32 scales are what travels; otherwise the
        flat bucket moves at its logical size."""
        if self._wcomp is None:
            return jax.device_put(bucket, zg.home)
        return jax.device_put(zg.wq1_fns[j](bucket), zg.home)

    def _gather_finish(self, zg, j, fut):
        """Resolve a dispatched transfer to the full-precision flat
        bucket at home (dequantizing when compressed)."""
        if self._wcomp is None:
            return fut
        return zg.wdq1_fns[j](*fut)

    def _count_gather_bytes(self, zg, js):
        if not _tm._ENABLED:
            return
        fam = _tm.counter(
            "comm_bytes_gathered",
            "bytes moved by kvstore collectives (logical vs wire)")
        store = f"zero{self.stage}"
        fam.labels(store=store, kind="logical").inc(
            sum(zg.wire_bytes[j][0] for j in js))
        fam.labels(store=store, kind="wire").inc(
            sum(zg.wire_bytes[j][1] for j in js))

    # -- ZeRO-3: sharded weights with just-in-time gathers -----------------
    def _release_group(self, zg):
        """Drop every member's full-size weight array, leaving a
        ShapeDtypeStruct placeholder plus a lazy fetch that gathers the
        parameter's bucket on first access (Parameter.data())."""
        if zg.wrote is None or len(zg.wrote) != len(zg.params):
            zg.wrote = [None] * len(zg.params)
        for k, p in enumerate(zg.params):
            d = p._data._data
            p._data._data = jax.ShapeDtypeStruct(tuple(d.shape), d.dtype)
            p._lazy_fetch = self._make_fetch(zg, k)
            zg.wrote[k] = None
        zg.inflight.clear()

    def _make_fetch(self, zg, k):
        def fetch(param):
            self._materialize_bucket(zg, zg.k2bucket[k])
        return fetch

    def _materialize_bucket(self, zg, j):
        """Gather bucket j's weights back to the home device and fill in
        its members' arrays; dispatch the NEXT bucket's gather async
        (one-bucket lookahead) so sequential layer access — fwd or bwd —
        hides the gather latency."""
        if _ft._ACTIVE:
            _ft.timeout_point("collective.timeout")
        fl_on = _fl._ENABLED
        if fl_on:
            t0 = time.monotonic()
            _fl.record("collective", "zero3.gather", bucket=j,
                       store=f"zero{self.stage}",
                       bytes=zg.wire_bytes[j][1])
        fut = zg.inflight.pop(j, None)
        if fut is None:
            fut = self._gather_dispatch(zg, j, zg.wshards[j])
        jn = j + 1
        if jn < len(zg.plans) and jn not in zg.inflight and any(
                not isinstance(zg.params[k]._data._data, jax.Array)
                for (k, _, _, _) in zg.plans[jn]):
            zg.inflight[jn] = self._gather_dispatch(zg, jn,
                                                    zg.wshards[jn])
        leaves = zg.unflat1_fns[j](self._gather_finish(zg, j, fut))
        self._count_gather_bytes(zg, (j,))
        if fl_on:
            _fl.record("collective_done", "zero3.gather", bucket=j,
                       dur_s=time.monotonic() - t0)
        for arr, (k, _, _, _) in zip(leaves, zg.plans[j]):
            p = zg.params[k]
            if not isinstance(p._data._data, jax.Array):
                p._data._data = arr
                p._lazy_fetch = None
                zg.wrote[k] = arr

    # -- resident-bytes accounting (profiler memory provider) --------------
    def zero_resident_bytes(self):
        """Per-replica resident training bytes by category. Sharded
        buffers count global/N; replicated (full-size) buffers count
        full. Stage-3 transiently materialized weights and in-flight
        gathers count as 'transient'."""
        n = max(1, self.num_shards)
        w = g = o = t = 0
        for zg in self._zgroups.values():
            for st in zg.states:
                for leaf in jax.tree_util.tree_leaves(st):
                    o += leaf.nbytes // n
            if zg.mp and zg.masters:
                for m in zg.masters:
                    o += m.nbytes // n
            if zg.wshards is not None:
                for b in zg.wshards:
                    if b is not None:
                        w += b.nbytes // n
            for p in (zg.params or []):
                d = p._data._data
                if isinstance(d, jax.Array):
                    if self.stage >= 3:
                        t += d.nbytes  # transient gather, freed on step
                    else:
                        w += d.nbytes
                gb = p._data._grad
                if gb is not None and isinstance(gb._data, jax.Array):
                    g += gb._data.nbytes
            for sh in (zg.gshards or []):
                if sh is not None:
                    g += sh.nbytes // n
            for buf in (zg.pending or []):
                for ga in buf.values():
                    t += ga.nbytes
            for fut in (zg.inflight or {}).values():
                # compressed prefetches are (codes, scales) pairs
                t += sum(x.nbytes
                         for x in jax.tree_util.tree_leaves(fut))
        return {"weights": w, "grads": g, "opt_state": o, "transient": t}

    def _reduce_scatter(self, kvstore, gid, buckets):
        """Cross-replica reduction of the UNPADDED grad buckets (keeps
        compression residuals bit-identical to the allreduce path); the
        scatter placement is done by the sharded executable's specs."""
        from .ndarray import NDArray
        nds = [NDArray(b) for b in buckets]
        if kvstore.supports_reduce_scatter():
            kvstore.reduce_scatter_buckets(gid, nds)
        else:
            # a zero>=2 request already degraded (with its own warning)
            # to ZeRO-1 on this store: plain bucket allreduce, skipping
            # the store's redundant reduce-scatter fallback warning
            kvstore.pushpull_buckets(gid, nds)
        return [nd._data for nd in nds]

    def _build_zero1(self, members, mp, wdtype, states) -> _ZeroGroup:
        opt = self.optimizer
        mesh = self._zero1_mesh()
        nsh = int(mesh.devices.size)
        n = len(members)
        idxs = [i for (i, _, _) in members]
        P = jax.sharding.PartitionSpec
        shard = jax.sharding.NamedSharding(mesh, P(ZERO1_AXIS))
        # plan on weight metadata (== grad metadata by the attach_grad
        # contract): under stage >= 2 the full grad buffers do not
        # exist, and under stage 3 the weights may be released
        wmeta = [p._data._data for (_, p, _) in members]
        plans = plan_buckets([tuple(w.shape) for w in wmeta],
                             [w.dtype for w in wmeta], self.bucket_bytes)
        padded = zero1_padded_sizes(plans, nsh)
        segs = [jax.device_put(jnp.asarray(s), shard)
                for s in bucket_segments(plans, padded, n)]

        missing = [i for i in idxs if i not in states]
        if len(missing) == n:
            bucket_states, masters = self._fresh_zero1_state(
                members, mp, wdtype, plans, padded, shard)
        else:
            member_states = []
            for (i, p, _) in members:
                st = states.pop(i) if i in states else \
                    opt.create_state_multi_precision(i, p.data())
                member_states.append(st)
            bucket_states, masters = self._import_zero1_state(
                member_states, mp, plans, padded, shard)

        nbk = len(plans)
        from jax import shard_map

        def body(st_bks, m_or_w_bks, g_bks, seg_bks, lrs, wds, ts,
                 rescale, extras):
            new_st, new_w, low_w = [], [], []
            for j in range(nbk):
                seg = seg_bks[j]
                hyper = {"lr": lrs[seg], "wd": wds[seg], "t": ts[seg],
                         "rescale": rescale}
                for k2, vec in extras.items():
                    hyper[k2] = vec[seg]
                g = g_bks[j]
                if mp:
                    g = g.astype(jnp.float32)
                nw, ns = zero1_update_shard(opt, m_or_w_bks[j], g,
                                            st_bks[j], hyper, seg,
                                            n + 1, ZERO1_AXIS)
                new_st.append(ns)
                new_w.append(nw)
                if mp:
                    low_w.append(nw.astype(wdtype))
            if mp:
                return new_st, new_w, low_w
            return new_st, new_w

        Pz, Pr = P(ZERO1_AXIS), P()
        run = shard_map(
            body, mesh=mesh,
            in_specs=(Pz, Pz, Pz, Pz, Pr, Pr, Pr, Pr, Pr),
            out_specs=(Pz, Pz, Pz) if mp else (Pz, Pz),
            check_vma=False)

        # donate the resident sharded state, the masters (mp) or
        # resident weight buckets, and the scattered grad buckets —
        # nothing user-visible aliases them
        update_fn = jax.jit(run, donate_argnums=(0, 1, 2))
        flatten_fn = jax.jit(lambda gs_: flatten_buckets(gs_, plans))
        pad_fn = jax.jit(lambda bks: pad_buckets(bks, plans, padded))
        flatpad_fn = jax.jit(lambda gs_: pad_buckets(
            flatten_buckets(gs_, plans), plans, padded))
        wpad_fn = flatpad_fn
        unflatten_fn = jax.jit(
            lambda bks: unflatten_buckets(bks, plans, n))
        ws0 = members[0][1].data()._data
        home = jax.sharding.SingleDeviceSharding(
            next(iter(ws0.devices())))
        zg = _ZeroGroup(idxs, mp, plans, padded, segs, shard,
                        flatten_fn, flatpad_fn, pad_fn, wpad_fn,
                        update_fn, unflatten_fn, bucket_states,
                        masters, home)
        zg.params = [p for (_, p, _) in members]
        zg.reqs = [p.grad_req for (_, p, _) in members]
        zg.gdtype = wmeta[0].dtype
        nbk = len(plans)
        # single-bucket executables: the stage-2 hook flush works one
        # bucket at a time (that IS the overlap), and the stage-3 lazy
        # gather rebuilds one bucket's tensors at a time
        zg.flat1_fns, zg.pad1_fns, zg.flatpad1_fns, zg.unflat1_fns = \
            [], [], [], []
        for plan, tot in zip(plans, padded):
            zg.flat1_fns.append(jax.jit(
                lambda ls, plan=plan: flatten_buckets(ls, [plan])[0]))
            zg.pad1_fns.append(jax.jit(
                lambda b, plan=plan, tot=tot:
                pad_buckets([b], [plan], [tot])[0]))
            zg.flatpad1_fns.append(jax.jit(
                lambda ls, plan=plan, tot=tot: pad_buckets(
                    flatten_buckets(ls, [plan]), [plan], [tot])[0]))
            zg.unflat1_fns.append(jax.jit(
                lambda b, plan=plan:
                [jax.lax.slice(b, (off,), (off + size,)).reshape(shape)
                 for (_, off, size, shape) in plan]))
        # weights-direction wire compression: per-bucket quantize (runs
        # on the sharded bucket BEFORE the shard->home transfer, so the
        # 1-byte codes + per-block fp32 scales are what travels) and
        # dequantize (at home, on arrival) executables; plus the
        # per-bucket (logical, wire) gathered-byte stats either way so
        # the A/B accounting always has both sides
        bdt = wdtype if mp else wmeta[0].dtype
        isz = jnp.dtype(bdt).itemsize
        wc = self._wcomp
        if wc is not None:
            from .parallel.compression import (block_dequantize,
                                               block_quantize,
                                               wire_nbytes)
            zg.wq1_fns, zg.wdq1_fns = [], []
            for tot in padded:
                zg.wq1_fns.append(jax.jit(
                    lambda b, sch=wc["type"], blk=wc["block"]:
                    block_quantize(b, sch, blk)))
                zg.wdq1_fns.append(jax.jit(
                    lambda c, s, tot=tot, dt=bdt:
                    block_dequantize(c, s, n=tot, dtype=dt)))
            zg.wire_bytes = [
                (tot * isz, wire_nbytes(tot, wc["type"], wc["block"]))
                for tot in padded]
        else:
            zg.wire_bytes = [(tot * isz, tot * isz) for tot in padded]
        zg.pending = [dict() for _ in range(nbk)]
        zg.gshards = [None] * nbk
        zg.gfresh = [False] * nbk
        zg.baccum = [all(zg.reqs[k] == "add" for (k, _, _, _) in plan)
                     for plan in plans]
        zg.k2bucket = {k: j for j, plan in enumerate(plans)
                       for (k, _, _, _) in plan}
        zg.inflight = {}
        return zg

    def _fresh_zero1_state(self, members, mp, wdtype, plans, padded,
                           shard):
        """Shard-sized state allocation from init: structure/dtypes come
        from an eval_shape probe of create_state on the flat bucket (no
        full-size buffer is ever materialized); fp32 masters are the
        flattened weights, laid out P(z1) per bucket."""
        opt = self.optimizer
        i0 = members[0][0]
        sdtype = jnp.float32 if mp else wdtype
        ws = [p.data()._data for (_, p, _) in members]
        bucket_states, masters = [], []
        for plan, tot in zip(plans, padded):
            probe = jax.eval_shape(
                lambda tot=tot: opt.create_state(
                    i0, _FlatWeight(jax.ShapeDtypeStruct((tot,),
                                                         sdtype))))
            bucket_states.append(jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype, device=shard),
                probe))
            if mp:
                flat = pad_buckets(
                    flatten_buckets(ws, [plan], dtype=jnp.float32),
                    [plan], [tot])[0]
                masters.append(jax.device_put(flat, shard))
        return bucket_states, (masters if mp else None)

    def _import_zero1_state(self, member_states, mp, plans, padded,
                            shard):
        """Flatten existing per-parameter state trees (e.g. from
        load_states) into the resident sharded bucket form."""
        if mp:
            m_list = [st[0] for st in member_states]
            inners = [st[1] for st in member_states]
        else:
            m_list, inners = None, list(member_states)
        tdef = jax.tree_util.tree_structure(inners[0])
        leaves = [jax.tree_util.tree_flatten(t)[0] for t in inners]
        nleaves = len(leaves[0])
        bucket_states, masters = [], []
        for plan, tot in zip(plans, padded):
            bl = []
            for j in range(nleaves):
                flat = pad_buckets(
                    flatten_buckets([l[j] for l in leaves], [plan]),
                    [plan], [tot])[0]
                bl.append(jax.device_put(flat, shard))
            bucket_states.append(jax.tree_util.tree_unflatten(tdef, bl))
            if mp:
                flat = pad_buckets(flatten_buckets(m_list, [plan]),
                                   [plan], [tot])[0]
                masters.append(jax.device_put(flat, shard))
        return bucket_states, (masters if mp else None)

    def _export_group(self, zg, states):
        """Gather one group's sharded state back to per-parameter trees
        (host gather + static slices) into `states`, keyed by parameter
        index — the save-side of replica-count-portable checkpoints."""
        for bi, plan in enumerate(zg.plans):
            leaves, tdef = jax.tree_util.tree_flatten(zg.states[bi])
            leaves_h = [_np.asarray(a) for a in leaves]
            m_h = _np.asarray(zg.masters[bi]) if zg.mp else None
            for (k, off, size, shape) in plan:
                inner = jax.tree_util.tree_unflatten(
                    tdef, [jnp.asarray(lh[off:off + size].reshape(shape))
                           for lh in leaves_h])
                i = zg.idxs[k]
                if zg.mp:
                    states[i] = (jnp.asarray(
                        m_h[off:off + size].reshape(shape)), inner)
                else:
                    states[i] = inner

    def zero1_export_states(self, states: Dict):
        """Materialize every resident group's optimizer state into
        per-parameter entries of `states` (gather-on-save: checkpoints
        stay replica-count-portable). Groups keep running sharded."""
        for zg in self._zgroups.values():
            self._export_group(zg, states)

    def zero1_reset(self):
        """Drop resident sharded state; the next step() re-imports from
        the per-parameter states dict (used by Trainer.load_states).
        Stage 3 materializes weights first so no parameter is left
        pointing at a dropped group's shards."""
        if self.stage >= 3:
            for zg in self._zgroups.values():
                for p in (zg.params or []):
                    if not isinstance(p._data._data, jax.Array):
                        p.data()  # lazy fetch -> full array
        self._zgroups.clear()
        self._hook_map = None
        self._hook_sig = None

    def zero1_state_nbytes(self) -> Tuple[int, int]:
        """(total_bytes, per_replica_bytes) of resident optimizer state
        (moments + fp32 masters); per-replica is total/N by layout."""
        total = 0
        for zg in self._zgroups.values():
            for st in zg.states:
                for leaf in jax.tree_util.tree_leaves(st):
                    total += leaf.nbytes
            if zg.mp:
                for m in zg.masters:
                    total += m.nbytes
        return total, total // max(1, self.num_shards)
