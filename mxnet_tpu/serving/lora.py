"""Batched multi-LoRA serving + tenant QoS primitives.

Production traffic is thousands of fine-tunes and tenants multiplexed
over ONE base model. This module holds the pieces that make that a
zero-recompile serving workload:

- :class:`AdapterPool` — a fixed-capacity, device-resident table of
  stacked low-rank ``(A, B)`` factors per target matmul per layer.
  Per-slot adapter *indices* enter the decode/prefill/verify
  executables as traced ``(B,)`` values and the factors are gathered
  INSIDE the executable (``h += (x @ A[idx]) @ B[idx]``) — the same
  trick that made temperature/top_k per-request traced values — so
  arbitrary adapter mixes, hot-loads, and evictions never add a
  compile. Index 0 is the reserved all-zero identity adapter: base
  rows compute an exact ``+0.0`` and stay bit-identical to a server
  without LoRA. Hot-load/evict is refcounted (the prefix-cache
  allocator is the pattern) and swaps the table functionally
  (``refresh_params()``-style): in-flight ticks keep the old arrays.
- :class:`WeightedFairScheduler` — stride scheduling over tenant
  names: each tenant owns a virtual ``pass``; picking takes the
  minimum, charging advances by ``amount / weight``. The server uses
  it for admission order, chunked-prefill budget split, and decode
  token accounting, so one flooding tenant cannot starve another.
- :class:`TenantSpec` / :class:`TenantObjective` — per-tenant QoS:
  weight + priority class + queue bound (shed policy), and an SLO
  objective that samples ONLY that tenant's ``tenant=``-labeled
  telemetry children.
- :func:`train_adapter` / :func:`merged_weights` — the
  train-a-LoRA → hot-load → parity-vs-merged-weights loop
  (examples/llama_serve.py drives it end to end).

Telemetry rides the bounded ``tenant=`` label through the module-level
``_note_*`` hooks below — they gate on ``telemetry._ENABLED`` (the
observability cost contract, enforced by tests/test_telemetry_lint.py).
"""
from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import telemetry as _tm
from .. import slo as _slo
from ..models import llama_math

__all__ = ["AdapterPool", "WeightedFairScheduler", "TenantSpec",
           "TenantObjective", "train_adapter", "merged_weights",
           "PRIORITY_RANK", "priority_rank"]

#: priority classes, low to high — shedding evicts the lowest rank
#: first; unknown classes rank as "standard"
PRIORITY_RANK = {"batch": 0, "standard": 1, "interactive": 2,
                 "realtime": 3}


def priority_rank(priority: Optional[str]) -> int:
    """Numeric rank of a priority class (higher = more protected)."""
    return PRIORITY_RANK.get(priority or "standard", 1)


# -- telemetry hooks ---------------------------------------------------------
# Each gates on the module flag per the cost contract.

def _note_adapter(event: str, name: str):
    """Adapter lifecycle counter: event in {load, evict, update}."""
    if _tm._ENABLED:
        _tm.inc("serving_adapter_%ss_total" % event)


def _note_shed(tenant: Optional[str], priority: Optional[str]):
    """A request shed at the server (per-tenant queue bound)."""
    if _tm._ENABLED:
        _tm.inc("serve_shed_total")
        _tm.inc("serve_shed_total",
                **{"class": priority or "standard"})


def _note_ttft(tenant: str, seconds: float):
    if _tm._ENABLED:
        _tm.observe("serving_ttft_seconds", seconds, tenant=tenant)


def _note_tpot(tenant: str, seconds: float, spec: str):
    if _tm._ENABLED:
        _tm.observe("serving_tpot_seconds", seconds, spec=spec,
                    tenant=tenant)


def _note_finish(tenant: str, status: str):
    if _tm._ENABLED:
        _tm.inc("serving_tenant_requests_total", tenant=tenant,
                status=status)


def _note_tokens(tenant: str, n: int):
    if _tm._ENABLED:
        _tm.inc("serving_tenant_tokens_total", n, tenant=tenant)


def _note_tenant_gauges(counts: Dict[str, Tuple[int, int]]):
    """Per-tenant (queued, active) gauges, bounded by the server's
    tenant-label cap."""
    if _tm._ENABLED:
        for t, (q, a) in counts.items():
            _tm.set_gauge("serving_tenant_queue_depth", q, tenant=t)
            _tm.set_gauge("serving_tenant_active_slots", a, tenant=t)


# -- the adapter table -------------------------------------------------------

class AdapterPool:
    """Fixed-capacity device-resident table of stacked LoRA factors.

    Layout: per layer, per target matmul ``t`` in `targets`, two
    stacked arrays ``a (capacity, din, rank)`` / ``b (capacity, rank,
    dout)`` in the model dtype (``din``/``dout`` read off the net's
    own weights, Dense convention W ``(dout, din)``). Row 0 is the
    reserved identity adapter (all zeros — an exact 0.0 delta), so
    `capacity` bounds LOADED adapters at ``capacity - 1``.

    The scale (``alpha / rank``) is folded into ``b`` at load time, so
    the executable math is always the unscaled two-matmul gather.

    Hot-load under traffic is safe by construction: the table swap is
    functional (``.at[idx].set`` builds new arrays, the pool rebinds
    ``self.tables``), the server passes ``pool.tables`` afresh into
    every tick, and eviction refuses while any live request holds the
    adapter (refcounts acquired at submit, released at terminate).
    """

    def __init__(self, net, *, capacity: int = 8, rank: int = 8,
                 targets: Tuple[str, ...] = ("wq", "wv"),
                 dtype=None):
        from ..models.llama_infer import _params_device, _params_tree
        capacity = int(capacity)
        rank = int(rank)
        if capacity < 2:
            raise ValueError("capacity must be >= 2 (row 0 is the "
                             "reserved identity adapter)")
        if rank < 1:
            raise ValueError("rank must be >= 1")
        allowed = ("wq", "wk", "wv", "wo")
        targets = tuple(targets)
        for t in targets:
            if t not in allowed:
                raise ValueError(f"unknown LoRA target {t!r} "
                                 f"(targets are among {allowed})")
        if not targets:
            raise ValueError("need at least one LoRA target")
        params = _params_tree(net)
        self.capacity = capacity
        self.rank = rank
        self.targets = targets
        dt = params["embed"].dtype if dtype is None else jnp.dtype(dtype)
        dev = _params_device(params)
        tables = []
        self._dims = []                 # per-layer {t: (din, dout)}
        for lp in params["layers"]:
            layer = {}
            dims = {}
            for t in targets:
                dout, din = lp[t].shape
                dims[t] = (din, dout)
                layer[t] = {"a": jnp.zeros((capacity, din, rank), dt),
                            "b": jnp.zeros((capacity, rank, dout), dt)}
            tables.append(layer)
            self._dims.append(dims)
        # device_put-committed so the executables' first call presents
        # the same sharding signature as steady-state calls
        self.tables = jax.device_put(tables, dev)
        self._idx: Dict[str, int] = {}      # name -> table row
        self._refs: Dict[str, int] = {}     # name -> live requests
        self._lru: List[str] = []           # load/use order (old first)
        self.loads = 0
        self.evictions = 0

    def signature(self) -> tuple:
        """The STATIC part of the executable build key — table shape
        only, never contents, so loads/evictions never re-key."""
        return (self.capacity, self.rank, self.targets)

    def loaded(self) -> List[str]:
        return sorted(self._idx)

    def free_rows(self) -> int:
        return self.capacity - 1 - len(self._idx)

    def index(self, name: str) -> int:
        """Table row of a loaded adapter (KeyError when unknown)."""
        return self._idx[name]

    def refcount(self, name: str) -> int:
        return self._refs.get(name, 0)

    def _validate(self, factors):
        if len(factors) != len(self._dims):
            raise ValueError(
                f"adapter has {len(factors)} layers, net has "
                f"{len(self._dims)}")
        for li, (lf, dims) in enumerate(zip(factors, self._dims)):
            if set(lf) != set(self.targets):
                raise ValueError(
                    f"layer {li} targets {sorted(lf)} != pool targets "
                    f"{sorted(self.targets)}")
            for t, (a, b) in lf.items():
                din, dout = dims[t]
                a = np.asarray(a)
                b = np.asarray(b)
                if a.shape != (din, self.rank) \
                        or b.shape != (self.rank, dout):
                    raise ValueError(
                        f"layer {li} target {t}: got A{a.shape} "
                        f"B{b.shape}, pool wants A({din}, {self.rank}) "
                        f"B({self.rank}, {dout})")

    def load(self, name: str, adapter, scale: Optional[float] = None
             ) -> int:
        """Hot-load (or update in place) adapter `name`. `adapter` is
        the dict :func:`train_adapter` returns, or a bare per-layer
        factors list ``[{target: (A, B)}, ...]``. When the table is
        full, the least-recently-loaded refcount-0 adapter is evicted;
        with every row pinned by live traffic this raises. Returns the
        table row."""
        if isinstance(adapter, dict):
            factors = adapter["factors"]
            if scale is None:
                scale = adapter.get("scale", 1.0)
        else:
            factors = adapter
        if scale is None:
            scale = 1.0
        self._validate(factors)
        update = name in self._idx
        if update:
            idx = self._idx[name]
        else:
            used = set(self._idx.values())
            free = [i for i in range(1, self.capacity)
                    if i not in used]
            if not free:
                victim = next((n for n in self._lru
                               if not self._refs.get(n)), None)
                if victim is None:
                    raise RuntimeError(
                        "adapter table full and every row is held by "
                        "live requests — raise capacity or drain")
                self.evict(victim)
                free = [self._free_row()]
            idx = free[0]
        new_tables = []
        for layer, lf in zip(self.tables, factors):
            nl = {}
            for t, tab in layer.items():
                a, b = lf[t]
                nl[t] = {
                    "a": tab["a"].at[idx].set(
                        jnp.asarray(np.asarray(a), tab["a"].dtype)),
                    "b": tab["b"].at[idx].set(
                        jnp.asarray(np.asarray(b) * float(scale),
                                    tab["b"].dtype)),
                }
            new_tables.append(nl)
        self.tables = new_tables
        self._idx[name] = idx
        self._refs.setdefault(name, 0)
        if name in self._lru:
            self._lru.remove(name)
        self._lru.append(name)
        self.loads += 1
        _note_adapter("update" if update else "load", name)
        return idx

    def _free_row(self) -> int:
        used = set(self._idx.values())
        return next(i for i in range(1, self.capacity)
                    if i not in used)

    def evict(self, name: str):
        """Drop a loaded adapter. Refuses while live requests hold it
        (refcount > 0) — evict-under-traffic means draining first."""
        refs = self._refs.get(name, 0)
        if refs:
            raise RuntimeError(
                f"adapter {name!r} has {refs} live request(s) — "
                "cannot evict under traffic")
        if name not in self._idx:
            raise KeyError(name)
        del self._idx[name]
        self._refs.pop(name, None)
        if name in self._lru:
            self._lru.remove(name)
        self.evictions += 1
        _note_adapter("evict", name)

    def acquire(self, name: str) -> int:
        """Refcount +1 for a request entering the system; returns the
        table row its slot will gather. KeyError when not loaded."""
        idx = self._idx[name]
        self._refs[name] = self._refs.get(name, 0) + 1
        if name in self._lru:            # freshen the eviction order
            self._lru.remove(name)
            self._lru.append(name)
        return idx

    def release(self, name: str):
        """Refcount -1 at the request's terminal transition."""
        if name in self._refs and self._refs[name] > 0:
            self._refs[name] -= 1

    def stats(self) -> dict:
        return {"capacity": self.capacity, "rank": self.rank,
                "targets": list(self.targets),
                "loaded": self.loaded(),
                "free_rows": self.free_rows(),
                "loads": self.loads, "evictions": self.evictions,
                "refcounts": dict(self._refs)}


# -- weighted-fair scheduling ------------------------------------------------

class WeightedFairScheduler:
    """Stride (virtual-time) weighted-fair queueing over tenant names.

    Every tenant owns a monotone virtual ``pass``; :meth:`pick` takes
    the candidate with the minimum pass, :meth:`charge` advances the
    tenant by ``amount / weight``. Over any contended interval each
    tenant's charged amount converges to its weight share, and because
    passes only grow, every backlogged tenant is picked within a
    bounded number of rounds (starvation-freedom). A tenant
    re-entering after idling is snapped forward to the current virtual
    time (:meth:`activate`) so banked idle credit cannot buy a burst.
    """

    def __init__(self, weights: Optional[Dict[str, float]] = None,
                 default_weight: float = 1.0):
        self.default_weight = float(default_weight)
        self._w: Dict[str, float] = {}
        self._pass: Dict[str, float] = {}
        self._vtime = 0.0
        self._seq: Dict[str, int] = {}      # FIFO tiebreak
        self._next_seq = 0
        if weights:
            for t, w in weights.items():
                self.set_weight(t, w)

    def set_weight(self, tenant: str, weight: float):
        weight = float(weight)
        if weight <= 0:
            raise ValueError(f"weight must be > 0, got {weight}")
        self._w[tenant] = weight
        self._ensure(tenant)

    def weight(self, tenant: str) -> float:
        return self._w.get(tenant, self.default_weight)

    def pass_of(self, tenant: str) -> float:
        self._ensure(tenant)
        return self._pass[tenant]

    def _ensure(self, tenant: str):
        if tenant not in self._pass:
            self._pass[tenant] = self._vtime
            self._seq[tenant] = self._next_seq
            self._next_seq += 1

    def activate(self, tenant: str):
        """Tenant has pending work again after (possibly) idling:
        snap its pass forward to the virtual clock so idle time earns
        no credit."""
        self._ensure(tenant)
        self._pass[tenant] = max(self._pass[tenant], self._vtime)

    def pick(self, candidates) -> str:
        """The candidate tenant with the minimum pass (FIFO on ties).
        Advances the virtual clock to the winner's pass."""
        cands = list(candidates)
        if not cands:
            raise ValueError("pick() needs at least one candidate")
        for t in cands:
            self._ensure(t)
        best = min(cands,
                   key=lambda t: (self._pass[t], self._seq[t]))
        self._vtime = max(self._vtime, self._pass[best])
        return best

    def charge(self, tenant: str, amount: float):
        """Account `amount` units of service (tokens) to `tenant`."""
        if amount <= 0:
            return
        self._ensure(tenant)
        self._pass[tenant] += amount / self.weight(tenant)

    def snapshot(self) -> Dict[str, float]:
        return dict(self._pass)


# -- tenant QoS --------------------------------------------------------------

class TenantSpec:
    """One tenant's QoS contract: scheduler `weight`, `priority` class
    (shed ordering), an optional per-tenant queue bound `max_queued`
    (past it, submits are SHED — returned already-terminal with status
    ``rejected`` / reason ``shed``, never raised), and optional
    TTFT/latency SLO thresholds the convenience
    :meth:`objectives` turns into :class:`TenantObjective` entries."""

    def __init__(self, weight: float = 1.0,
                 priority: str = "standard",
                 max_queued: Optional[int] = None,
                 ttft_slo_s: Optional[float] = None,
                 tpot_slo_s: Optional[float] = None,
                 slo_target: float = 0.95):
        if float(weight) <= 0:
            raise ValueError("weight must be > 0")
        self.weight = float(weight)
        self.priority = str(priority)
        self.max_queued = None if max_queued is None else int(max_queued)
        self.ttft_slo_s = ttft_slo_s
        self.tpot_slo_s = tpot_slo_s
        self.slo_target = float(slo_target)

    @classmethod
    def coerce(cls, spec) -> "TenantSpec":
        if isinstance(spec, cls):
            return spec
        if isinstance(spec, dict):
            return cls(**spec)
        raise TypeError(f"cannot build a TenantSpec from {type(spec)}")

    def rank(self) -> int:
        return priority_rank(self.priority)

    def objectives(self, tenant: str) -> List["TenantObjective"]:
        out = []
        if self.ttft_slo_s is not None:
            out.append(TenantObjective(
                f"ttft[{tenant}]", tenant=tenant,
                metric="serving_ttft_seconds",
                target=self.slo_target, threshold_s=self.ttft_slo_s))
        if self.tpot_slo_s is not None:
            out.append(TenantObjective(
                f"tpot[{tenant}]", tenant=tenant,
                metric="serving_tpot_seconds",
                target=self.slo_target, threshold_s=self.tpot_slo_s))
        return out

    def __repr__(self):
        return (f"TenantSpec(weight={self.weight}, "
                f"priority={self.priority!r}, "
                f"max_queued={self.max_queued})")


class TenantObjective(_slo.Objective):
    """An SLO :class:`~mxnet_tpu.slo.Objective` scoped to ONE tenant:
    only children carrying ``tenant=<name>`` feed (good, total), so a
    noisy tenant's burn cannot hide (or inflate) another's. Rides the
    same burn-rate/alerting machinery as fleet objectives."""

    def __init__(self, name: str, *, tenant: str, **kw):
        super().__init__(name, **kw)
        self.tenant = str(tenant)

    def sample(self, registry):
        fam = registry.get(self.metric)
        if fam is None:
            return 0.0, 0.0
        good = total = 0.0
        for key, ch in list(fam.children.items()):
            labels = dict(key)
            if labels.get("tenant") != self.tenant:
                continue
            if self.threshold_s is not None:
                total += ch.count
                good += ch.zeros
                for e, n in list(ch.buckets.items()):
                    if e <= self._exp:
                        good += n
            else:
                status = labels.get("status")
                if status is None or status in self.ignore_statuses:
                    continue
                total += ch.value
                if status in self.good_statuses:
                    good += ch.value
        return good, total


# -- training + merged-weights parity ----------------------------------------

def train_adapter(net, batches, *, rank: int = 8,
                  targets: Tuple[str, ...] = ("wq", "wv"),
                  steps: int = 50, lr: float = 0.1,
                  alpha: Optional[float] = None, seed: int = 0
                  ) -> dict:
    """Train LoRA factors against a FROZEN base: gradients flow only
    through the low-rank (A, B) pairs (A ~ N(0, 0.02), B zero — the
    standard init, so step 0 is exactly the base model). `batches` is
    a list/sequence of int32 token arrays (B, T); the loss is
    next-token cross-entropy, optimizer plain SGD. One jitted
    value_and_grad serves every step (fixed shapes). Returns
    ``{"factors", "rank", "targets", "scale", "losses"}`` — feed it to
    :meth:`AdapterPool.load` or :func:`merged_weights` as-is."""
    from ..models.llama_infer import _params_tree
    params = _params_tree(net)
    cfg = net.model.cfg
    targets = tuple(targets)
    scale = (float(alpha) if alpha is not None else float(rank)) / rank
    key = jax.random.PRNGKey(seed)
    factors = []
    for lp in params["layers"]:
        lf = {}
        for t in targets:
            dout, din = lp[t].shape
            key, k1 = jax.random.split(key)
            lf[t] = (jax.random.normal(k1, (din, rank), jnp.float32)
                     * 0.02,
                     jnp.zeros((rank, dout), jnp.float32))
        factors.append(lf)

    def loss_fn(fs, ids):
        x = params["embed"][ids]
        pos = jnp.arange(ids.shape[1])
        for lp, lf in zip(params["layers"], fs):
            lora = {t: (a, b * scale) for t, (a, b) in lf.items()}
            x = llama_math.decoder_layer(
                lp, x, pos, cfg.rms_eps, cfg.rope_base,
                cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                lora=lora)
        logits = llama_math.final_logits(params, x, cfg.rms_eps)
        lsm = jax.nn.log_softmax(
            logits[:, :-1].astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(
            lsm, ids[:, 1:][..., None], axis=-1)[..., 0]
        return nll.mean()

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    batches = [jnp.asarray(np.asarray(b, np.int32)) for b in batches]
    losses = []
    for i in range(int(steps)):
        loss, g = grad_fn(factors, batches[i % len(batches)])
        factors = jax.tree_util.tree_map(
            lambda f, gg: f - lr * gg, factors, g)
        losses.append(float(loss))
    return {"factors": factors, "rank": int(rank), "targets": targets,
            "scale": scale, "losses": losses}


@contextlib.contextmanager
def merged_weights(net, adapter, scale: Optional[float] = None):
    """Temporarily fold ``scale * (A @ B)`` into the net's target
    weights (Dense convention: ``W += (A @ B).T``) — the offline
    merged-weights baseline that batched LoRA serving must match
    token-for-token (greedy). Restores the originals on exit. Any live
    server snapshot of these weights must be re-taken by the caller
    (``refresh_params()``) — serving through the AdapterPool instead
    never touches the base weights."""
    from .. import ndarray as _nd
    if isinstance(adapter, dict):
        factors = adapter["factors"]
        if scale is None:
            scale = adapter.get("scale", 1.0)
    else:
        factors = adapter
    if scale is None:
        scale = 1.0
    name_map = {"wq": "self_attn.q_proj.weight",
                "wk": "self_attn.k_proj.weight",
                "wv": "self_attn.v_proj.weight",
                "wo": "self_attn.o_proj.weight"}
    params = net.collect_params()
    saved = []
    try:
        for li, lf in enumerate(factors):
            for t, (a, b) in lf.items():
                p = params[f"model.layers.{li}.{name_map[t]}"]
                w = np.asarray(p.data()._data)
                delta = (np.asarray(a, np.float32)
                         @ np.asarray(b, np.float32)).T * float(scale)
                saved.append((p, w))
                p.set_data(_nd.array(w + delta.astype(w.dtype)))
        yield net
    finally:
        for p, w in reversed(saved):
            p.set_data(_nd.array(w))
