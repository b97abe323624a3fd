"""Pipeline parallelism — GPipe / 1F1B microbatch schedules over a `pp`
mesh axis, plus auto-staging of a HybridSequential into balanced stages.

Reference parity: MXNet's model-parallel examples place layer groups on
different GPUs and rely on the dependency engine to overlap them
(example/model-parallel; ctx lists in Gluon). The TPU rebuild runs the
schedule *inside* one XLA program: stage parameters are stacked on a
leading dimension sharded over `pp`, a `lax.scan` ticks the pipeline,
and `lax.ppermute` shifts activations to the next stage over ICI. The
whole pipeline — bubbles, steady state, drain — is a single compiled
loop XLA can overlap with collectives.

Constraints (classic GPipe):
  * every stage maps (mb, ...) -> (mb, ...) with the same shape/dtype
    (transformer blocks satisfy this);
  * all stages share one parameter treedef (stacked leading dim = pp).

`gpipe(...)` is differentiable — reverse-mode flows back through the
scan/ppermute schedule. `one_f_one_b(...)` computes loss AND grads in
one pass with an O(num_stages) activation stash; `pipeline_stages(...)`
cuts a HybridSequential into balanced stages that drop straight into
either schedule (and into `FusedTrainStep(pipeline=M)`).
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence

import numpy as _np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .mesh import current_mesh

__all__ = ["stack_stage_params", "gpipe", "sequential_apply",
           "one_f_one_b", "pipeline_stages", "StagedPipeline",
           "bubble_ratio", "stash_slots", "InterleavedSchedule",
           "interleaved_schedule", "interleaved_bubble_ratio"]


def bubble_ratio(num_stages: int, num_microbatches: int) -> float:
    """Fraction of schedule ticks lost to fill+drain bubbles:
    (n-1)/(M+n-1) — the classic GPipe/1F1B pipeline inefficiency."""
    n, M = int(num_stages), int(num_microbatches)
    return (n - 1) / (M + n - 1) if M + n - 1 > 0 else 0.0


def interleaved_bubble_ratio(total_ticks: int, num_microbatches: int,
                             virtual: int) -> float:
    """MEASURED bubble fraction of an interleaved schedule: the fill+
    drain half-ticks as a fraction of the schedule's actual length.
    Each rank owes 2*M*v half-ticks of work (M*v forward chunk-ops and
    M*v backward chunk-ops); everything beyond that in `total_ticks`
    is bubble. At the Megatron-LM optimum total_ticks = 2*M*v + 2(n-1),
    giving (n-1)/(M*v + n-1) — the classic ratio shrunk ~1/v."""
    T, M, v = int(total_ticks), int(num_microbatches), int(virtual)
    return (T - 2 * M * v) / T if T > 0 else 0.0


def stash_slots(num_stages: int) -> int:
    """Activation-stash slots per stage under the 1F1B schedule:
    2n-1, bounded by the STAGE count — independent of the microbatch
    count M (GPipe under plain AD stashes all M)."""
    return 2 * int(num_stages) - 1


class InterleavedSchedule:
    """Host-precomputed tick tables for the interleaved virtual-stage
    1F1B schedule (Megatron-LM arXiv:2104.04473 §2.2).

    Virtual stage s = c*n + r places model chunk c on pp rank r = s % n,
    so activations walk rank 0..n-1 for chunk 0, wrap around the ring,
    walk it again for chunk 1, and so on. One schedule tick is ONE
    chunk-op per rank (a forward OR a backward half — half the
    granularity of the non-interleaved machine's fused fwd+bwd tick),
    which is what lets a rank slot another chunk's forward into what
    would otherwise be a fill/drain bubble.

    The per-rank op order is Megatron's constructive schedule
    (num_warmup = min(2*(n-r-1) + (v-1)*n, M*v) warmup forwards, then
    strict 1F1B alternation, then drain backwards); tick placement
    comes from an event-driven simulation with a 1-tick wire latency:
    fwd(m, s) needs fwd(m, s-1) at a strictly earlier tick, bwd(m, s)
    needs bwd(m, s+1) (or, for the last virtual stage, its own forward)
    strictly earlier. The resulting `total_ticks` is the MEASURED
    schedule length that feeds `interleaved_bubble_ratio` — no
    analytic formula is trusted.

    The emitted tables drive `_1f1b_interleaved_local`, one int32 row
    per (tick, rank):

      op_kind (0 idle / 1 fwd / 2 bwd), op_m, op_c  — what runs;
      feed                 — fwd input comes from the microbatch feed
                             (virtual stage 0) instead of the queue;
      fq_r / fq_w          — forward-activation FIFO slot to read for
                             this tick's fwd / to write this tick's
                             up-ring arrival into (-1 = discard);
      bq_r / bq_w          — same for the cotangent FIFO on the down
                             ring;
      stash_w / stash_r    — recompute-stash slot for the fwd's INPUT
                             and the bwd's readback;
      loss_op / dout_w     — this fwd is the last virtual stage:
                             compute the loss and park its cotangent;
      use_dout / dout_r    — this bwd seeds from the parked loss
                             cotangent instead of the down ring.

    Slot indices are allocated host-side with exact lifetimes, so
    `fq_size`/`bq_size`/`stash_size`/`dout_size` are the true peak
    buffer occupancies (SPMD: maxed over ranks).
    """

    #: table column layout (see class docstring)
    FIELDS = ("op_kind", "op_m", "op_c", "feed", "fq_r", "fq_w",
              "bq_r", "bq_w", "stash_w", "stash_r", "loss_op",
              "use_dout", "dout_w", "dout_r")

    def __init__(self, num_stages: int, virtual: int,
                 num_microbatches: int):
        n, v, M = int(num_stages), int(virtual), int(num_microbatches)
        if n < 2 or v < 1 or M < 1:
            raise ValueError(
                f"InterleavedSchedule: need pp >= 2, virtual >= 1, "
                f"microbatches >= 1 (got pp={n}, virtual={v}, M={M})")
        if M % n != 0:
            raise ValueError(
                f"InterleavedSchedule: the interleaved 1F1B order "
                f"needs num_microbatches divisible by pp (got M={M}, "
                f"pp={n}) — pad or regroup the microbatches")
        self.n, self.v, self.M = n, v, M
        L = n * v  # virtual stages

        def _mc(k, back):
            c = (k // n) % v
            if back:
                c = v - 1 - c
            return n * (k // (n * v)) + (k % n), c

        # Megatron per-rank op order: warmup fwds, 1F1B, drain bwds
        ops = []
        for r in range(n):
            warm = min((n - r - 1) * 2 + (v - 1) * n, M * v)
            seq, fi, bi = [], 0, 0
            for _ in range(warm):
                m, c = _mc(fi, False)
                seq.append(("f", m, c))
                fi += 1
            while fi < M * v:
                m, c = _mc(fi, False)
                seq.append(("f", m, c))
                fi += 1
                m, c = _mc(bi, True)
                seq.append(("b", m, c))
                bi += 1
            while bi < M * v:
                m, c = _mc(bi, True)
                seq.append(("b", m, c))
                bi += 1
            ops.append(seq)

        # event-driven tick placement (1-tick wire latency)
        done = {}
        ptr = [0] * n
        rows = []
        limit = 4 * M * v + 4 * n + 16
        while any(ptr[r] < len(ops[r]) for r in range(n)):
            t = len(rows)
            if t > limit:
                raise RuntimeError(
                    f"InterleavedSchedule: no valid placement within "
                    f"{limit} ticks for pp={n}, virtual={v}, M={M} — "
                    "the per-rank op order deadlocked")
            row = [None] * n
            for r in range(n):
                if ptr[r] >= len(ops[r]):
                    continue
                kind, m, c = ops[r][ptr[r]]
                s = c * n + r
                if kind == "f":
                    ok = s == 0 or done.get(("f", m, s - 1), t) < t
                elif s == L - 1:
                    ok = done.get(("f", m, s), t) < t
                else:
                    ok = done.get(("b", m, s + 1), t) < t
                if ok:
                    row[r] = (kind, m, c, s)
            if all(e is None for e in row):
                raise RuntimeError(
                    f"InterleavedSchedule: schedule stalled at tick "
                    f"{t} for pp={n}, virtual={v}, M={M}")
            for r, e in enumerate(row):
                if e is not None:
                    done[(e[0], e[1], e[3])] = t
                    ptr[r] += 1
            rows.append(row)
        T = len(rows)
        assert len(done) == 2 * M * L, (len(done), 2 * M * L)
        self.total_ticks = T

        # slot bookkeeping: exact-lifetime allocators per rank
        def _alloc(pool):
            if pool["free"]:
                return pool["free"].pop(0)
            slot = pool["next"]
            pool["next"] = slot + 1
            return slot

        fpool = [{"free": [], "next": 0} for _ in range(n)]
        bpool = [{"free": [], "next": 0} for _ in range(n)]
        spool = [{"free": [], "next": 0} for _ in range(n)]
        dpool = [{"free": [], "next": 0} for _ in range(n)]
        freed = {"f": {}, "b": {}, "s": {}, "d": {}}
        pend_f, pend_b, pend_s, pend_d = {}, {}, {}, {}

        tab = _np.zeros((T, n, len(self.FIELDS)), _np.int32)
        tab[:, :, 5] = -1  # fq_w: default = discard the arrival
        tab[:, :, 7] = -1  # bq_w
        col = {f: i for i, f in enumerate(self.FIELDS)}

        for t in range(T):
            for key, pools in (("f", fpool), ("b", bpool),
                               ("s", spool), ("d", dpool)):
                for r, slot in freed[key].pop(t, ()):
                    pools[r]["free"].append(slot)
            # arrivals: payloads shifted at the END of tick t-1 land
            # now, BEFORE this tick's reads (write-then-read order in
            # the traced tick)
            if t >= 1:
                for r, e in enumerate(rows[t - 1]):
                    if e is None:
                        continue
                    kind, m, _c, s = e
                    if kind == "f" and s < L - 1:
                        r2 = (r + 1) % n
                        slot = _alloc(fpool[r2])
                        tab[t, r2, col["fq_w"]] = slot
                        pend_f[(m, s + 1)] = slot
                    elif kind == "b" and s > 0:
                        r2 = (r - 1) % n
                        slot = _alloc(bpool[r2])
                        tab[t, r2, col["bq_w"]] = slot
                        pend_b[(m, s - 1)] = slot
            for r, e in enumerate(rows[t]):
                if e is None:
                    continue
                kind, m, c, s = e
                tab[t, r, col["op_kind"]] = 1 if kind == "f" else 2
                tab[t, r, col["op_m"]] = m
                tab[t, r, col["op_c"]] = c
                if kind == "f":
                    if s == 0:
                        tab[t, r, col["feed"]] = 1
                    else:
                        slot = pend_f.pop((m, s))
                        tab[t, r, col["fq_r"]] = slot
                        freed["f"].setdefault(t + 1, []).append((r, slot))
                    slot = _alloc(spool[r])
                    tab[t, r, col["stash_w"]] = slot
                    pend_s[(m, s)] = slot
                    if s == L - 1:
                        tab[t, r, col["loss_op"]] = 1
                        slot = _alloc(dpool[r])
                        tab[t, r, col["dout_w"]] = slot
                        pend_d[m] = slot
                else:
                    slot = pend_s.pop((m, s))
                    tab[t, r, col["stash_r"]] = slot
                    freed["s"].setdefault(t + 1, []).append((r, slot))
                    if s == L - 1:
                        tab[t, r, col["use_dout"]] = 1
                        slot = pend_d.pop(m)
                        tab[t, r, col["dout_r"]] = slot
                        freed["d"].setdefault(t + 1, []).append((r, slot))
                    else:
                        slot = pend_b.pop((m, s))
                        tab[t, r, col["bq_r"]] = slot
                        freed["b"].setdefault(t + 1, []).append((r, slot))
        assert not pend_f and not pend_b and not pend_s and not pend_d
        self.table = tab
        self.fq_size = max(1, max(p["next"] for p in fpool))
        self.bq_size = max(1, max(p["next"] for p in bpool))
        self.stash_size = max(1, max(p["next"] for p in spool))
        self.dout_size = max(1, max(p["next"] for p in dpool))

    def bubble_ratio(self) -> float:
        return interleaved_bubble_ratio(self.total_ticks, self.M,
                                        self.v)


def interleaved_schedule(num_stages: int, virtual: int,
                         num_microbatches: int) -> InterleavedSchedule:
    """Build (and cache) the interleaved 1F1B tick tables for
    pp=num_stages ranks running `virtual` model chunks each over
    `num_microbatches` microbatches."""
    key = (int(num_stages), int(virtual), int(num_microbatches))
    hit = _SCHED_CACHE.get(key)
    if hit is None:
        hit = _SCHED_CACHE[key] = InterleavedSchedule(*key)
    return hit


_SCHED_CACHE: dict = {}


def stack_stage_params(params_list):
    """Stack per-stage parameter pytrees (identical treedefs) into one
    pytree whose leaves carry a leading `pp` dimension.

    Raises a ValueError naming the first mismatched stage when the
    per-stage treedefs or leaf shapes/dtypes differ (instead of the
    cryptic tree_map arity error jax would produce)."""
    if not params_list:
        raise ValueError("stack_stage_params: empty stage list")
    ref_leaves, ref_treedef = jax.tree_util.tree_flatten(params_list[0])
    for i, p in enumerate(params_list[1:], start=1):
        leaves, treedef = jax.tree_util.tree_flatten(p)
        if treedef != ref_treedef:
            raise ValueError(
                f"stack_stage_params: stage {i} parameter tree "
                f"structure {treedef} does not match stage 0's "
                f"{ref_treedef}; every stage must share one treedef "
                "so leaves can stack on a leading pp dimension")
        for k, (a, b) in enumerate(zip(ref_leaves, leaves)):
            if jnp.shape(a) != jnp.shape(b) or \
                    jnp.asarray(a).dtype != jnp.asarray(b).dtype:
                raise ValueError(
                    f"stack_stage_params: stage {i} leaf {k} has "
                    f"shape/dtype {jnp.shape(b)}/"
                    f"{jnp.asarray(b).dtype} but stage 0 has "
                    f"{jnp.shape(a)}/{jnp.asarray(a).dtype}; stages "
                    "must be structurally identical to stack")
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *params_list)


def sequential_apply(stage_fn, stacked_params, x):
    """Reference semantics: run the stages one after another (no mesh).
    Used as the single-device fallback and in tests."""
    n = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]

    def body(h, i):
        p_i = jax.tree_util.tree_map(lambda a: a[i], stacked_params)
        return stage_fn(p_i, h), ()

    out, _ = jax.lax.scan(body, x, jnp.arange(n))
    return out


def _vary(x, axis_name):
    try:
        return jax.lax.pcast(x, (axis_name,), to="varying")
    except ValueError:
        return x  # already varying over axis_name


def _shift_fn(axis_name, wire):
    """The activation/cotangent hop: plain `lax.ppermute`, or the
    block-scaled quantized hop (1-byte codes + per-block fp32 scales on
    the wire) when `wire=(scheme, block)` is set."""
    if wire is None:
        return lambda v, perm: jax.lax.ppermute(v, axis_name, perm)
    from .compression import quantized_ppermute
    scheme, block = wire
    return lambda v, perm: quantized_ppermute(v, axis_name, perm,
                                              scheme, block)


def _qbcast_impl(x, axis_name, n, scheme, block):
    from .compression import block_dequantize, block_quantize
    idx = jax.lax.axis_index(axis_name)
    codes, scales = block_quantize(x, scheme, block)
    span = 1
    while span < n:
        pairs = [(s, s - span) for s in range(n - span, n)
                 if s - span >= 0]
        rc = jax.lax.ppermute(codes, axis_name, pairs)
        rs = jax.lax.ppermute(scales, axis_name, pairs)
        newly = jnp.logical_and(idx >= n - 2 * span, idx < n - span)
        codes = jnp.where(newly, rc, codes)
        scales = jnp.where(newly, rs, scales)
        span *= 2
    deq = block_dequantize(codes, scales, shape=x.shape, dtype=x.dtype)
    # quantize ONCE at the source and forward the codes through every
    # doubling round (no requantize-per-hop error compounding); the
    # source stage keeps its exact value — only wire hops are lossy,
    # mirroring quantized_all_gather's exact-self patch
    return jnp.where(idx == n - 1, x, deq)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _quantized_bcast_from_last(x, axis_name, n, scheme, block):
    return _qbcast_impl(x, axis_name, n, scheme, block)


def _qbcast_fwd(x, axis_name, n, scheme, block):
    return _qbcast_impl(x, axis_name, n, scheme, block), None


def _qbcast_bwd(axis_name, n, scheme, block, _, ct):
    # transpose of broadcast-from-last: the source stage absorbs every
    # stage's cotangent (straight-through the quantizer — the standard
    # STE treatment), all other stages contribute nothing
    idx = jax.lax.axis_index(axis_name)
    s = jax.lax.psum(ct, axis_name)
    return (jnp.where(idx == n - 1, s, jnp.zeros_like(ct)),)


_quantized_bcast_from_last.defvjp(_qbcast_fwd, _qbcast_bwd)


def _bcast_from_last(x, axis_name, n, wire=None):
    """Broadcast the LAST stage's value to every pp shard with a
    recursive-doubling ppermute chain (ceil(log2 n) hops), replacing the
    old full-size psum: no fake zero-contributions ride the wire and no
    reduction work is spent adding them. jax requires unique ppermute
    sources, so the multicast is staged — after round r the suffix of
    min(2^r, n) stages holds the value. With `wire=(scheme, block)` the
    value travels quantized (codes + scales take the same doubling
    route; one quantize at the source, one dequantize at the end)."""
    if n <= 1:
        return x
    if wire is not None:
        return _quantized_bcast_from_last(x, axis_name, int(n),
                                          wire[0], int(wire[1]))
    idx = jax.lax.axis_index(axis_name)
    span = 1
    while span < n:
        pairs = [(s, s - span) for s in range(n - span, n)
                 if s - span >= 0]
        recv = jax.lax.ppermute(x, axis_name, pairs)
        newly = jnp.logical_and(idx >= n - 2 * span, idx < n - span)
        x = jnp.where(newly, recv, x)
        span *= 2
    return x


def _gpipe_local(params, mbatches, stage_fn, axis_name, wire=None):
    """Per-device schedule body (runs inside shard_map).

    params: this stage's parameters (leading pp dim already split away).
    mbatches: (M, mb, ...) full microbatched input, replicated; only
    stage 0 reads it. Returns (M, mb, ...) outputs, broadcast from the
    last stage with a ppermute chain (see _bcast_from_last).

    Dead ticks — a stage before its first microbatch arrives (fill) or
    after its last has left (drain) — skip the stage compute through a
    lax.cond, so XLA executes nothing for them instead of computing a
    garbage activation that a select then throws away.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    M = mbatches.shape[0]
    perm = [(i, i + 1) for i in range(n - 1)]  # no wraparound
    shift = _shift_fn(axis_name, wire)

    state0 = _vary(jnp.zeros(mbatches.shape[1:], mbatches.dtype),
                   axis_name)
    out0 = _vary(jnp.zeros_like(mbatches), axis_name)

    def tick(carry, t):
        state, outputs = carry
        m = t - idx  # the microbatch this stage works on this tick
        live = jnp.logical_and(m >= 0, m < M)
        feed = jax.lax.dynamic_index_in_dim(
            mbatches, jnp.clip(t, 0, M - 1), 0, keepdims=False)
        inp = jnp.where(idx == 0, feed, state)
        out = jax.lax.cond(live, lambda i: stage_fn(params, i),
                           jnp.zeros_like, inp)
        j = jnp.clip(t - (n - 1), 0, M - 1)
        upd = jax.lax.dynamic_update_index_in_dim(outputs, out, j, 0)
        take = jnp.logical_and(idx == n - 1, t >= n - 1)
        outputs = jnp.where(take, upd, outputs)
        state = shift(out, perm)
        return (state, outputs), ()

    (_, outputs), _ = jax.lax.scan(
        tick, (state0, out0), jnp.arange(M + n - 1))
    # ship the last stage's results to every pp shard (ppermute chain,
    # not a psum of mostly-zeros)
    return _bcast_from_last(outputs, axis_name, n, wire)


def _1f1b_local(params, mbatches, ybatches, stage_fn, loss_fn,
                axis_name, loss_dtype=None, wire=None):
    """Per-device 1F1B schedule body (runs inside shard_map).

    One scan tick = one forward micro-step AND one backward micro-step
    per stage (interleaved steady state). Stage `idx` forwards
    microbatch m at tick m + idx and backprops it at tick
    m + 2(n-1) - idx, so at most 2(n-1-idx)+1 <= 2n-1 activations are
    ever stashed per stage — bounded by the *stage count*, independent
    of the microbatch count M. (GPipe under jax.grad stashes all M.)
    The backward recomputes each stage forward from the stashed INPUT
    (recompute-vjp), the standard trade on TPU where HBM, not FLOPs,
    is the binding constraint.

    Dead half-ticks (a stage with no forward microbatch in range, or no
    backward cotangent yet) skip their compute through lax.cond —
    during fill/drain XLA executes the cheap zero branch instead of a
    masked-out stage forward or vjp.

    Loss accumulates in `loss_dtype` (default: whatever `loss_fn`
    returns — probed by the caller), NOT hardcoded fp32, and the
    loss-seeded cotangent is cast to the activation dtype ONCE where it
    is created, so bf16-activation pipelines keep a bf16 steady state.

    Returns (loss_sum, grad_acc): loss summed over microbatches on the
    last stage (zeros elsewhere), grads for this stage's params.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    M = mbatches.shape[0]
    S = 2 * n - 1  # stash slots: max in-flight microbatches per stage
    perm_up = [(i, i + 1) for i in range(n - 1)]
    perm_down = [(i + 1, i) for i in range(n - 1)]
    shift = _shift_fn(axis_name, wire)

    mb_shape = mbatches.shape[1:]
    act_dtype = mbatches.dtype
    if loss_dtype is None:
        loss_dtype = jax.eval_shape(
            loss_fn, jax.ShapeDtypeStruct(mb_shape, act_dtype),
            jax.ShapeDtypeStruct(ybatches.shape[1:],
                                 ybatches.dtype)).dtype
    state0 = _vary(jnp.zeros(mb_shape, act_dtype), axis_name)
    cot0 = _vary(jnp.zeros(mb_shape, act_dtype), axis_name)
    stash0 = _vary(jnp.zeros((S,) + mb_shape, act_dtype), axis_name)
    grad0 = jax.tree_util.tree_map(
        lambda p: _vary(jnp.zeros_like(p), axis_name), params)

    is_last = idx == n - 1

    def tick(carry, t):
        state, cot_in, stash, grads, loss_acc = carry

        # ---- forward half: stage idx forwards microbatch m_f = t - idx
        m_f = t - idx
        valid_f = jnp.logical_and(m_f >= 0, m_f < M)
        m_f_c = jnp.clip(m_f, 0, M - 1)
        feed = jax.lax.dynamic_index_in_dim(mbatches, m_f_c, 0,
                                            keepdims=False)
        inp = jnp.where(idx == 0, feed, state)
        out = jax.lax.cond(valid_f, lambda i: stage_fn(params, i),
                           jnp.zeros_like, inp)
        # stash the stage INPUT for recompute in the backward half
        upd = jax.lax.dynamic_update_index_in_dim(
            stash, inp, m_f_c % S, 0)
        stash = jnp.where(valid_f, upd, stash)

        # last stage: loss + its cotangent for the just-forwarded mb.
        # Other stages (and dead ticks) take the free branch.
        y_f = jax.lax.dynamic_index_in_dim(ybatches, m_f_c, 0,
                                           keepdims=False)

        def loss_half(oy):
            o, y = oy
            lval, dout = jax.value_and_grad(loss_fn)(o, y)
            # single cast point: the loss cotangent joins the pipeline
            # in the ACTIVATION dtype (bf16 stays bf16 downstream)
            return lval.astype(loss_dtype), dout.astype(act_dtype)

        lval, dout_loss = jax.lax.cond(
            jnp.logical_and(is_last, valid_f), loss_half,
            lambda oy: (jnp.zeros((), loss_dtype),
                        jnp.zeros_like(oy[0])), (out, y_f))
        loss_acc = loss_acc + lval

        # ---- backward half: stage idx backprops m_b = t - 2(n-1) + idx
        m_b = t - 2 * (n - 1) + idx
        valid_b = jnp.logical_and(m_b >= 0, m_b < M)
        m_b_c = jnp.clip(m_b, 0, M - 1)
        inp_b = jax.lax.dynamic_index_in_dim(stash, m_b_c % S, 0,
                                             keepdims=False)
        # cotangent: from the loss (last stage, same-tick mb) or from
        # the next stage via the previous tick's ppermute
        cot = jnp.where(is_last, dout_loss, cot_in)

        def bwd_half(ic):
            i, c = ic
            _, vjp = jax.vjp(stage_fn, params, i)
            return vjp(c)

        dparams, dinp = jax.lax.cond(
            valid_b, bwd_half,
            lambda ic: (jax.tree_util.tree_map(jnp.zeros_like, params),
                        jnp.zeros_like(ic[0])), (inp_b, cot))
        grads = jax.tree_util.tree_map(
            lambda g, d: g + d, grads, dparams)

        # shift: activations up, cotangents down (both quantized under
        # wire compression — EQuARX covers forward AND backward hops)
        state = shift(out, perm_up)
        cot_out = shift(dinp, perm_down)
        return (state, cot_out, stash, grads, loss_acc), ()

    total_ticks = M + 2 * (n - 1)
    init = (state0, cot0, stash0, grad0,
            _vary(jnp.zeros((), loss_dtype), axis_name))
    (_, _, _, grads, loss_acc), _ = jax.lax.scan(
        tick, init, jnp.arange(total_ticks))
    return loss_acc, grads


def _1f1b_interleaved_local(params, mbatches, ybatches, stage_fn,
                            loss_fn, axis_name, sched,
                            loss_dtype=None, wire=None):
    """Per-device interleaved 1F1B body (runs inside shard_map).

    `params` is this rank's full chunk set (leaves lead with the
    virtual dim); `stage_fn(params, c, h)` runs chunk `c` — the chunk
    index stays TRACED (it arrives from the tick table), so the whole
    interleaved schedule is ONE scan body and one executable per plan
    signature, never a per-chunk recompile.

    One tick = ONE op per rank (idle / fwd / bwd), driven by the
    host-precomputed `sched` tables (see InterleavedSchedule). Both
    rings permute every tick — forward activations up the full ring
    [(i, (i+1)%n)] (the wraparound hop IS the chunk transition),
    cotangents down the reversed ring — and receivers file arrivals
    into FIFO queues at table-assigned slots (-1 = discard: the last
    virtual stage's output and virtual stage 0's input cotangent).
    Backward recomputes from the stashed stage INPUT (recompute-vjp)
    exactly like the non-interleaved machine; the vjp runs against the
    FULL chunk set, yielding zeros outside chunk c, so gradients
    accumulate in microbatch order per chunk — bit-identical to the
    non-interleaved accumulation per (chunk, leaf).

    Returns (loss_sum, grads): loss summed over microbatches on the
    rank owning the last virtual stage (zeros elsewhere).
    """
    n = sched.n
    assert sched.M == mbatches.shape[0], \
        f"schedule built for M={sched.M}, got {mbatches.shape[0]}"
    rank = jax.lax.axis_index(axis_name)
    M = mbatches.shape[0]
    mb_shape = mbatches.shape[1:]
    act_dtype = mbatches.dtype
    if loss_dtype is None:
        loss_dtype = jax.eval_shape(
            loss_fn, jax.ShapeDtypeStruct(mb_shape, act_dtype),
            jax.ShapeDtypeStruct(ybatches.shape[1:],
                                 ybatches.dtype)).dtype
    perm_up = [(i, (i + 1) % n) for i in range(n)]
    perm_down = [((i + 1) % n, i) for i in range(n)]
    shift = _shift_fn(axis_name, wire)

    def _z(shape):
        return _vary(jnp.zeros(shape, act_dtype), axis_name)

    fq0 = _z((sched.fq_size,) + mb_shape)
    bq0 = _z((sched.bq_size,) + mb_shape)
    stash0 = _z((sched.stash_size,) + mb_shape)
    dout0 = _z((sched.dout_size,) + mb_shape)
    grad0 = jax.tree_util.tree_map(
        lambda p: _vary(jnp.zeros_like(p), axis_name), params)
    col = {f: i for i, f in enumerate(InterleavedSchedule.FIELDS)}
    rows = jnp.asarray(sched.table)  # (T, n, F)

    def tick(carry, row):
        fq, bq, stash, dout_st, grads, loss_acc, up_in, down_in = carry
        tr = row[rank]  # this rank's (F,) table row, traced

        # 1. file the ring arrivals shifted at the end of last tick
        fq_upd = jax.lax.dynamic_update_index_in_dim(
            fq, up_in, jnp.clip(tr[col["fq_w"]], 0, sched.fq_size - 1),
            0)
        fq = jnp.where(tr[col["fq_w"]] >= 0, fq_upd, fq)
        bq_upd = jax.lax.dynamic_update_index_in_dim(
            bq, down_in,
            jnp.clip(tr[col["bq_w"]], 0, sched.bq_size - 1), 0)
        bq = jnp.where(tr[col["bq_w"]] >= 0, bq_upd, bq)

        m_c = jnp.clip(tr[col["op_m"]], 0, M - 1)
        c_op = tr[col["op_c"]]

        # 2. forward op (or the free zero branch)
        feed = jax.lax.dynamic_index_in_dim(mbatches, m_c, 0,
                                            keepdims=False)
        q_in = jax.lax.dynamic_index_in_dim(fq, tr[col["fq_r"]], 0,
                                            keepdims=False)
        inp = jnp.where(tr[col["feed"]] == 1, feed, q_in)
        y_f = jax.lax.dynamic_index_in_dim(ybatches, m_c, 0,
                                           keepdims=False)
        is_loss = tr[col["loss_op"]] == 1

        def fwd_op(operand):
            i_, y_, c_ = operand
            out = stage_fn(params, c_, i_)

            def loss_half(oy):
                lval, dval = jax.value_and_grad(loss_fn)(oy[0], oy[1])
                return lval.astype(loss_dtype), dval.astype(act_dtype)

            lval, dval = jax.lax.cond(
                is_loss, loss_half,
                lambda oy: (jnp.zeros((), loss_dtype),
                            jnp.zeros_like(oy[0])), (out, y_))
            return out, lval, dval

        out, lval, dout_val = jax.lax.cond(
            tr[col["op_kind"]] == 1, fwd_op,
            lambda o: (jnp.zeros(mb_shape, act_dtype),
                       jnp.zeros((), loss_dtype),
                       jnp.zeros(mb_shape, act_dtype)), (inp, y_f, c_op))
        loss_acc = loss_acc + lval
        st_upd = jax.lax.dynamic_update_index_in_dim(
            stash, inp, tr[col["stash_w"]], 0)
        stash = jnp.where(tr[col["op_kind"]] == 1, st_upd, stash)
        d_upd = jax.lax.dynamic_update_index_in_dim(
            dout_st, dout_val, tr[col["dout_w"]], 0)
        dout_st = jnp.where(is_loss, d_upd, dout_st)

        # 3. backward op: recompute-vjp against the FULL chunk set
        inp_b = jax.lax.dynamic_index_in_dim(
            stash, tr[col["stash_r"]], 0, keepdims=False)
        cot_q = jax.lax.dynamic_index_in_dim(bq, tr[col["bq_r"]], 0,
                                             keepdims=False)
        cot_d = jax.lax.dynamic_index_in_dim(
            dout_st, tr[col["dout_r"]], 0, keepdims=False)
        cot = jnp.where(tr[col["use_dout"]] == 1, cot_d, cot_q)

        def bwd_op(operand):
            i_, ct_, c_ = operand
            _, vjp = jax.vjp(lambda pr, h: stage_fn(pr, c_, h),
                             params, i_)
            return vjp(ct_)

        dparams, dinp = jax.lax.cond(
            tr[col["op_kind"]] == 2, bwd_op,
            lambda o: (jax.tree_util.tree_map(jnp.zeros_like, params),
                       jnp.zeros_like(o[0])), (inp_b, cot, c_op))
        grads = jax.tree_util.tree_map(lambda g, d: g + d, grads,
                                       dparams)

        # 4. both rings shift every tick (quantized under wire
        # compression — every pp hop rides the compressed transport)
        up_out = shift(out, perm_up)
        down_out = shift(dinp, perm_down)
        return (fq, bq, stash, dout_st, grads, loss_acc, up_out,
                down_out), ()

    init = (fq0, bq0, stash0, dout0, grad0,
            _vary(jnp.zeros((), loss_dtype), axis_name),
            _z(mb_shape), _z(mb_shape))
    (_, _, _, _, grads, loss_acc, _, _), _ = jax.lax.scan(
        tick, init, rows)
    return loss_acc, grads


def one_f_one_b(stage_fn, stacked_params, x, y, loss_fn,
                num_microbatches, mesh=None, pp_axis="pp", wire=None,
                virtual=1):
    """1F1B pipeline schedule: fused forward+backward with interleaved
    microbatch backprop and an O(num_stages) activation stash.

    Unlike `gpipe` (forward-only, differentiable via jax AD — which
    stashes every microbatch's activations), this computes the loss AND
    the parameter gradients in one pass:

        loss, grads = one_f_one_b(stage_fn, params, x, y, loss_fn, M)

    stage_fn: (stage_params, h) -> h, shape/dtype-preserving.
    loss_fn: (out_mb, y_mb) -> scalar mean loss for one microbatch.
    Returns (mean microbatch loss, grads pytree stacked like
    `stacked_params` with the leading pp dim). The loss accumulates in
    the dtype `loss_fn` actually returns (probed with eval_shape), so a
    bf16 loss pipeline never silently upcasts.

    Reference analogue: upstream MXNet has no pipeline engine — this is
    the TPU-first design the SURVEY §2 checklist promises (bubble ratio
    (n-1)/(M+n-1), steady state 1 fwd + 1 bwd per tick per stage).

    Without a mesh (or without a `pp` axis) it computes the same
    quantities sequentially (exact reference semantics for tests).

    `wire=(scheme, block)` (scheme "int8" | "fp8") sends the per-tick
    activation/cotangent hops block-scale-quantized over the wire —
    ~3.9x fewer inter-stage bytes at block=128. Ignored by the
    sequential fallback (nothing crosses a wire there).

    `virtual=v` (v > 1) switches to the interleaved virtual-stage
    schedule: `stacked_params` leaves lead with (pp, v, ...) — chunk c
    of rank r is virtual stage c*pp + r — and `stage_fn` takes
    (rank_params, c, h) with a TRACED chunk index. Requires
    num_microbatches % pp == 0.
    """
    mesh = mesh if mesh is not None else current_mesh()
    B = x.shape[0]
    assert B % num_microbatches == 0, (B, num_microbatches)
    mb = B // num_microbatches
    mbatches = x.reshape(num_microbatches, mb, *x.shape[1:])
    ybatches = y.reshape(num_microbatches, mb, *y.shape[1:])
    loss_dtype = jax.eval_shape(
        loss_fn, jax.ShapeDtypeStruct(mbatches.shape[1:], mbatches.dtype),
        jax.ShapeDtypeStruct(ybatches.shape[1:], ybatches.dtype)).dtype
    virtual = int(virtual)

    if mesh is None or pp_axis not in mesh.axis_names:
        n_st = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]

        def total(params):
            def body(acc, mby):
                mbx, mby_ = mby
                if virtual > 1:
                    h = mbx
                    for s in range(n_st * virtual):
                        p_r = jax.tree_util.tree_map(
                            lambda a: a[s % n_st], params)
                        h = stage_fn(p_r, s // n_st, h)
                    out = h
                else:
                    out = sequential_apply(stage_fn, params, mbx)
                return acc + loss_fn(out, mby_), ()
            acc, _ = jax.lax.scan(body, jnp.zeros((), loss_dtype),
                                  (mbatches, ybatches))
            return acc / num_microbatches
        loss, grads = jax.value_and_grad(total)(stacked_params)
        return loss, grads

    n = mesh.shape[pp_axis]
    leaves = jax.tree_util.tree_leaves(stacked_params)
    assert leaves[0].shape[0] == n, \
        f"{leaves[0].shape[0]} stages vs pp={n} shards"
    sched = interleaved_schedule(n, virtual, num_microbatches) \
        if virtual > 1 else None

    param_specs = jax.tree_util.tree_map(
        lambda a: P(pp_axis, *([None] * (a.ndim - 1))), stacked_params)

    def body(params, mbs, ybs):
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        if sched is not None:
            loss_sum, grads = _1f1b_interleaved_local(
                params, mbs, ybs, stage_fn, loss_fn, pp_axis, sched,
                loss_dtype=loss_dtype, wire=wire)
        else:
            loss_sum, grads = _1f1b_local(
                params, mbs, ybs, stage_fn, loss_fn, pp_axis,
                loss_dtype=loss_dtype, wire=wire)
        # loss lives on the last stage only; share it with every shard
        loss_sum = jax.lax.psum(loss_sum, pp_axis)
        grads = jax.tree_util.tree_map(lambda g: g[None], grads)
        return loss_sum, grads

    fn = shard_map(body, mesh=mesh,
                   in_specs=(param_specs, P(), P()),
                   out_specs=(P(), param_specs), check_vma=False)
    loss_sum, grads = fn(stacked_params, mbatches, ybatches)
    # per-microbatch cotangents were seeded unscaled; match the
    # sequential reference's mean-over-microbatches loss
    grads = jax.tree_util.tree_map(lambda g: g / num_microbatches, grads)
    return loss_sum / num_microbatches, grads


def gpipe(stage_fn, stacked_params, x, num_microbatches, mesh=None,
          pp_axis="pp", wire=None):
    """Run `x` through the staged pipeline.

    stage_fn: (stage_params, h) -> h, shape-preserving.
    stacked_params: pytree with leading dim = num_stages (sharded over
        `pp_axis` when a mesh is active).
    x: (B, ...) batch; B % num_microbatches == 0.
    wire: optional (scheme, block) — quantize the inter-stage hops and
        the final last-stage broadcast (block-scaled int8/fp8 on the
        wire; differentiable via a straight-through custom_vjp).

    Without a mesh (or without a `pp` axis) this degrades to the exact
    sequential computation (`wire` ignored — nothing crosses a wire).
    """
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None or pp_axis not in mesh.axis_names:
        return sequential_apply(stage_fn, stacked_params, x)
    n = mesh.shape[pp_axis]
    leaves = jax.tree_util.tree_leaves(stacked_params)
    assert leaves[0].shape[0] == n, \
        f"{leaves[0].shape[0]} stages vs pp={n} shards"
    B = x.shape[0]
    assert B % num_microbatches == 0, (B, num_microbatches)
    mb = B // num_microbatches
    mbatches = x.reshape(num_microbatches, mb, *x.shape[1:])

    param_specs = jax.tree_util.tree_map(
        lambda a: P(pp_axis, *([None] * (a.ndim - 1))), stacked_params)
    # strip the (now size-1) stage dim inside the body
    def body(params, mbs):
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        return _gpipe_local(params, mbs, stage_fn, pp_axis, wire)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(param_specs, P()), out_specs=P(),
                   check_vma=False)
    out = fn(stacked_params, mbatches)
    return out.reshape(B, *out.shape[2:])


# -- auto-staging a HybridSequential ---------------------------------------

def _balanced_partition(costs: Sequence[float], k: int) -> List[List[int]]:
    """Contiguous split of `costs` into k non-empty runs minimizing the
    max run cost (dynamic program; block counts are small)."""
    L = len(costs)
    prefix = [0.0]
    for c in costs:
        prefix.append(prefix[-1] + float(c))
    INF = float("inf")
    best = [[INF] * (L + 1) for _ in range(k + 1)]
    cut = [[0] * (L + 1) for _ in range(k + 1)]
    best[0][0] = 0.0
    for st in range(1, k + 1):
        for i in range(st, L - (k - st) + 1):
            for j in range(st - 1, i):
                c = max(best[st - 1][j], prefix[i] - prefix[j])
                if c < best[st][i]:
                    best[st][i] = c
                    cut[st][i] = j
    bounds = [L]
    i = L
    for st in range(k, 0, -1):
        i = cut[st][i]
        bounds.append(i)
    bounds.reverse()
    return [list(range(bounds[s], bounds[s + 1])) for s in range(k)]


class StagedPipeline:
    """A HybridSequential cut into `pp` balanced stages, ready for the
    pipeline schedules.

    Attributes:
      num_stages, num_slots: pp and the per-stage block-slot count
        (max stage length; shorter stages are identity-padded).
      assignment: list of block-index runs, one per stage.
      param_names: canonical per-block parameter names (block 0's).
      params: stacked trainable params + the `__mask__` leaf — pytree
        with leading dim pp, drop-in for gpipe/one_f_one_b. Slot j of
        stage i computes block assignment[i][j]; padded slots carry a
        COPY of the stage's last real block's params and a 0 mask, so
        they compute something well-defined whose output a select
        discards — the schedule stays uniform across stages and their
        grads are exactly zero.
      stage_fn: (stage_params, h) -> h built from the blocks'
        hybridized (traced) forms; `make_stage_fn(key)` rebinds the
        dropout key (folded per slot).
      costs: the per-block cost-model values the partition balanced.
    """

    def __init__(self, net, blocks, assignment, entry, param_names,
                 block_params, costs, sample_aval, virtual=1):
        self.net = net
        self.blocks = blocks
        self.assignment = assignment
        self.virtual = int(virtual)
        # runs are in MODEL order: virtual stage s = c*pp + r lives in
        # assignment[s]; with virtual == 1 this is the plain stage list
        self.num_stages = len(assignment) // self.virtual
        self.num_slots = max(len(a) for a in assignment)
        self._entry = entry
        self.param_names = list(param_names)
        self._block_params = block_params  # per block: {name: Parameter}
        self.costs = list(costs)
        self.sample_aval = sample_aval
        # (virtual stage, slot) -> block index for REAL slots
        self.slot_map = {}
        for i, run in enumerate(assignment):
            for j, b in enumerate(run):
                self.slot_map[(i, j)] = b
        if self.virtual == 1:
            self.mask = jnp.asarray(
                [[1.0 if (i, j) in self.slot_map else 0.0
                  for j in range(self.num_slots)]
                 for i in range(self.num_stages)], jnp.float32)
        else:
            pp = self.num_stages
            self.mask = jnp.asarray(
                [[[1.0 if (c * pp + r, j) in self.slot_map else 0.0
                   for j in range(self.num_slots)]
                  for c in range(self.virtual)]
                 for r in range(pp)], jnp.float32)
        self.params = self.restack()

    # -- param shuttling ---------------------------------------------------
    def _slot_block(self, i, j):
        """Block index backing slot (i, j): the real block, or — for an
        identity-padded slot — the stage's last real block (its params
        are copied so the padded compute is well-defined; the mask
        discards its output and zeroes its grads)."""
        return self.slot_map.get((i, j), self.assignment[i][-1])

    def restack(self):
        """(Re-)read the net's Parameters into the stacked pytree
        (leading dims [pp, num_slots] — or [pp, virtual, num_slots]
        under interleaving) including the `__mask__` leaf."""
        stacked = {}
        pp, v = self.num_stages, self.virtual
        for k in self.param_names:
            if v == 1:
                stacked[k] = jnp.stack([
                    jnp.stack([
                        self._block_params[self._slot_block(i, j)][k]
                        .data()._data
                        for j in range(self.num_slots)], axis=0)
                    for i in range(pp)], axis=0)
            else:
                stacked[k] = jnp.stack([
                    jnp.stack([
                        jnp.stack([
                            self._block_params[
                                self._slot_block(c * pp + r, j)][k]
                            .data()._data
                            for j in range(self.num_slots)], axis=0)
                        for c in range(v)], axis=0)
                    for r in range(pp)], axis=0)
        stacked["__mask__"] = self.mask
        return stacked

    def unstack_into_net(self, stacked):
        """Write stacked weights back into the net's Parameters (only
        real slots; padded copies are dropped)."""
        pp = self.num_stages
        for (i, j), b in self.slot_map.items():
            for k in self.param_names:
                arr = jnp.asarray(stacked[k])
                if self.virtual == 1:
                    self._block_params[b][k].data()._data = arr[i, j]
                else:
                    self._block_params[b][k].data()._data = \
                        arr[i % pp, i // pp, j]

    # -- the stage function ------------------------------------------------
    def make_stage_fn(self, key=None):
        """stage_fn(stage_params, h) running this stage's block slots in
        order through block 0's traced form; `key` seeds per-slot
        dropout (folded by slot index). Padded slots run but their
        output is discarded by the `__mask__` select.

        Under interleaving (virtual > 1) the signature becomes
        stage_fn(rank_params, c, h): `rank_params` leaves lead with the
        virtual dim and `c` is the (possibly TRACED) chunk index —
        selected with dynamic_index_in_dim so one traced body serves
        every chunk (one executable, no per-chunk recompiles)."""
        entry = self._entry
        names = self.param_names
        s = self.num_slots
        if key is None:
            key = jax.random.PRNGKey(0)

        if self.virtual > 1:
            def stage_fn(p, c, h):
                m = jax.lax.dynamic_index_in_dim(p["__mask__"], c, 0,
                                                 keepdims=False)
                kc = jax.random.fold_in(key, c)
                for j in range(s):
                    pj = {k: jax.lax.dynamic_index_in_dim(
                        p[k], c, 0, keepdims=False)[j] for k in names}
                    flat, _ = entry.raw_fn(
                        pj, {}, jax.random.fold_in(kc, j), h)
                    h = jnp.where(m[j] != 0, flat[0], h)
                return h
            return stage_fn

        def stage_fn(p, h):
            m = p["__mask__"]
            for j in range(s):
                pj = {k: p[k][j] for k in names}
                flat, _ = entry.raw_fn(pj, {},
                                       jax.random.fold_in(key, j), h)
                h = jnp.where(m[j] != 0, flat[0], h)
            return h
        return stage_fn

    @property
    def stage_fn(self):
        return self.make_stage_fn()

    def param_bytes(self):
        return sum(int(_np.prod(v.shape)) * v.dtype.itemsize
                   for k, v in self.params.items() if k != "__mask__")


def pipeline_stages(net, pp: int, sample=None, cost_model: str = "flops",
                    virtual: int = 1):
    """Cut a HybridSequential of shape-preserving blocks into `pp`
    balanced stages and return a StagedPipeline.

    `virtual=v` (v > 1) cuts pp*v balanced runs instead and assigns
    rank r the NON-CONTIGUOUS chunks {c*pp + r : c < v} — Megatron's
    interleaved placement, which the interleaved 1F1B schedule walks
    to shrink the pipeline bubble ~1/v (see interleaved_schedule).

    Balancing uses a per-block cost model: `cost_model="flops"` traces
    block 0 and reads XLA's FLOPs estimate (all stackable blocks share
    one traced form, hence one estimate); when the backend reports no
    FLOPs it falls back to per-block parameter bytes. The partition is
    the contiguous split minimizing the max stage cost; stages shorter
    than the longest are identity-padded (see StagedPipeline.params).

    Requirements (clear errors otherwise): at least `pp` blocks, all of
    one class with identical parameter names/shapes/dtypes (so stage
    params stack), no aux params (BatchNorm running stats), and each
    block must map (mb, ...) -> (mb, ...) preserving shape and dtype.
    `sample` (an example input batch) is required to trace the blocks
    and finish any deferred parameter initialization.
    """
    from ..gluon.block import HybridBlock, Sequential
    from ..ndarray import NDArray
    from .. import autograd

    if isinstance(net, Sequential) or hasattr(net, "_children"):
        blocks = list(net._children.values())
    else:
        blocks = list(net)
    L = len(blocks)
    virtual = int(virtual)
    if virtual < 1:
        raise ValueError(f"pipeline_stages: virtual={virtual} must "
                         "be >= 1")
    if pp < 1 or L < pp * virtual:
        raise ValueError(
            f"pipeline_stages: need at least pp*virtual="
            f"{pp * virtual} blocks to cut into {pp} stages x "
            f"{virtual} virtual chunks; the net has {L}")
    if sample is None:
        raise ValueError(
            "pipeline_stages needs a sample input batch to trace the "
            "blocks (pass sample=x)")
    if not isinstance(sample, NDArray):
        sample = NDArray(jnp.asarray(sample))
    for b in blocks:
        if not isinstance(b, HybridBlock):
            raise ValueError(
                f"pipeline_stages: block {type(b).__name__} is not a "
                "HybridBlock — stages are built from hybridized "
                "(traced) forms")
        if type(b) is not type(blocks[0]):
            raise ValueError(
                f"pipeline_stages: mixed block classes "
                f"{type(blocks[0]).__name__} vs {type(b).__name__}; "
                "stage params stack across blocks, so all blocks must "
                "share one class/config (wrap heterogeneous layers "
                "into one repeated block)")

    # finish deferred init with one eager forward through the chain
    all_params = net.collect_params() if hasattr(net, "collect_params") \
        else None
    if all_params is not None and any(
            p._data is None for p in all_params.values()):
        with autograd.pause():
            h = sample
            for b in blocks:
                h = b(h)

    block_params = []
    names0 = None
    for bi, b in enumerate(blocks):
        bp = dict(b.collect_params().items())
        for k, p in bp.items():
            if p.grad_req == "null":
                raise ValueError(
                    f"pipeline_stages: block {bi} has aux parameter "
                    f"{k!r} (grad_req='null', e.g. BatchNorm running "
                    "stats) — pipeline stages must be stateless; use "
                    "LayerNorm-style blocks")
            if p._data is None:
                raise ValueError(
                    f"pipeline_stages: block {bi} parameter {k!r} is "
                    "uninitialized; call net.initialize() and pass a "
                    "sample input")
        keys = sorted(bp)
        if names0 is None:
            names0 = keys
            shapes0 = {k: (tuple(bp[k].data()._data.shape),
                           bp[k].data()._data.dtype) for k in keys}
        else:
            if keys != names0:
                raise ValueError(
                    f"pipeline_stages: block {bi} parameters {keys} "
                    f"do not match block 0's {names0}; blocks must be "
                    "structurally identical to stack")
            for k in keys:
                got = (tuple(bp[k].data()._data.shape),
                       bp[k].data()._data.dtype)
                if got != shapes0[k]:
                    raise ValueError(
                        f"pipeline_stages: block {bi} parameter {k!r} "
                        f"has shape/dtype {got} but block 0 has "
                        f"{shapes0[k]}")
        block_params.append(bp)

    entry = blocks[0].trace_entry([sample], training=True)
    if entry.aux_names:
        raise ValueError(
            f"pipeline_stages: block 0 traces with aux params "
            f"{entry.aux_names}; pipeline stages must be stateless")
    raw = sample._data
    out_sds = jax.eval_shape(
        lambda tr, h: entry.raw_fn(tr, {}, jax.random.PRNGKey(0), h)[0],
        {k: block_params[0][k].data()._data for k in names0}, raw)
    if len(out_sds) != 1 or out_sds[0].shape != raw.shape or \
            out_sds[0].dtype != raw.dtype:
        raise ValueError(
            f"pipeline_stages: blocks must be shape/dtype-preserving "
            f"(got {[(o.shape, str(o.dtype)) for o in out_sds]} for "
            f"input {raw.shape}/{raw.dtype}) — classic GPipe "
            "constraint, satisfied by transformer blocks")

    costs = _block_costs(blocks, block_params, entry, raw, cost_model)
    assignment = _balanced_partition(costs, pp * virtual)
    return StagedPipeline(net, blocks, assignment, entry, names0,
                          block_params, costs,
                          jax.ShapeDtypeStruct(raw.shape, raw.dtype),
                          virtual=virtual)


def _block_costs(blocks, block_params, entry, raw, cost_model):
    """Per-block partition weights. "flops": XLA's traced-FLOPs
    estimate of the block executable (identical-by-construction blocks
    share one trace); fallback — and `cost_model="bytes"` — is each
    block's parameter bytes."""
    bytes_costs = [
        max(1.0, sum(
            float(_np.prod(p.data()._data.shape)) *
            p.data()._data.dtype.itemsize
            for p in bp.values()))
        for bp in block_params]
    if cost_model != "flops":
        return bytes_costs
    try:
        names = sorted(block_params[0])
        tr0 = {k: block_params[0][k].data()._data for k in names}
        lowered = jax.jit(
            lambda tr, h: entry.raw_fn(tr, {}, jax.random.PRNGKey(0),
                                       h)[0]).lower(tr0, raw)
        cost = lowered.compile().cost_analysis()
        flops = float(cost.get("flops", 0.0)) if cost else 0.0
        if flops > 0:
            return [flops] * len(blocks)
    except Exception:
        pass
    return bytes_costs
