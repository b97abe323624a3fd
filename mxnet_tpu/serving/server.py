"""Continuous-batching inference server.

The scheduling model is the standard continuous-batching loop (Orca /
vLLM; the Gemma-on-TPU serving comparison in PAPERS.md sets the
TTFT / tokens-per-sec-per-chip bar this engine is instrumented for):

- `submit()` enqueues a request (prompt + per-request sampling params
  + max_new_tokens). FIFO by submission.
- every `step()` (one tick; ONE decode tick is kept queued on the
  device ahead of the one whose tokens are handed over, see `step`):
    1. ADMIT: while a batch slot and enough KV blocks are free, pop
       the queue head, allocate its blocks, run the persistent prefill
       executable (batch 1, padded to `max_prompt_len` — so 16
       mixed-length prompts are ONE compile), and seed the slot's
       logits/PRNG rows.
    2. ENSURE: lazily allocate the next block of each slot with a row
       in the tick about to be launched, when its write position
       crosses a block boundary. Pool exhausted → preempt the
       youngest running request (free its blocks, re-queue it at the
       front; greedy requests regenerate identically).
    3. LAUNCH decode tick n+1: one shared decode-tick executable for
       ALL slots — per-row sampling of the previous logits, one
       flash-decode step through the paged cache, per-row PRNG
       advance. Compiled once, reused for the lifetime of the server.
    4. HAND OVER tick n, launched by the step() before: block on its
       tokens and append them to their requests.
    5. EVICT: finished rows (eos hit or max_new_tokens reached) free
       their blocks and slots in the SAME step(), so the next step()
       admits from the queue immediately.

Telemetry (PR-4 registry, enabled via telemetry.enable()):
  serving_ttft_seconds        histogram — submit -> first token
  serving_tick_seconds        histogram — one decode tick
  serving_queue_depth         gauge
  serving_active_slots        gauge
  serving_kv_blocks_free      gauge
  serving_tokens_per_sec_per_chip  gauge (rolling 256-tick window)
  serving_tokens_total / serving_requests_total / _finished /
  serving_preemptions_total   counters
  serving_requests_total{status=...}  labeled terminal outcomes
  serving_watchdog_stalls_total       watchdog trips
  serving_gather_bytes_avoided_total  counter — HBM bytes the in-kernel
      paged decode saved vs the gather fallback (0 when the fallback
      is serving)
  serving_prefix_hits_total / serving_prefix_tokens_shared_total /
  serving_cow_copies_total    prefix-cache sharing activity
  serving_prefill_skipped_total  counter — admissions whose prompt the
      prefix cache fully covered (no prefill dispatch at all)
  serving_chunk_budget_utilization  gauge — fraction of the per-tick
      chunked-prefill token budget spent (chunked mode only)
  serving_tpot_seconds{spec=on|off}  histogram — per-request TPOT at
      finish, labeled by whether speculation was enabled
  serving_draft_accept_rate   gauge — rolling accepted/proposed drafts
  serving_spec_tokens_accepted_total / serving_spec_tokens_rejected_total
      counters — draft tokens the verify pass kept / threw away
  per-tick phase spans: serve_admit / serve_prefill / serve_decode
  (chrome trace + step_time_breakdown rows)

Tail-latency machinery (chunked prefill + speculative decoding):

- ``prefill_chunk_tokens=C`` switches prefill to SplitFuse/Sarathi-
  style chunking: every prompt prefills as ceil(T / C) bounded slices
  through ONE windowed executable (traced (chunk_start, chunk_len)),
  spent from a per-tick budget of C tokens between admit and decode —
  decode cadence stays bounded no matter the prompt-length mix. A
  request mid-prefill holds its slot and blocks (state visible in
  health_detail()["prefill_backlog_tokens"]) but doesn't decode; it is
  preemptable and deadline-expirable like any running request.
- ``speculative=k`` (or a proposer object) turns each greedy row's
  decode tick into a verify tick when the proposer has candidates: k
  draft tokens are scored in ONE dispatch alongside the sampled token
  (traced accept masks — every accept length shares the executable),
  accepted runs write straight into the page pool, and the rejected
  suffix is rewound by NOT advancing pos (kv_cache.rewind returns
  over-allocated blocks; stale rows are masked by valid lengths).
  Greedy output is token-identical to the plain tick; sampled rows
  never ride drafts.

Multi-LoRA + tenant QoS (serving/lora.py):

- ``lora=`` attaches an :class:`~mxnet_tpu.serving.lora.AdapterPool`:
  requests name a hot-loaded adapter and the slot's table INDEX rides
  into prefill/decode/verify as a traced operand — arbitrary adapter
  mixes, hot-loads, and evictions share the base 1 prefill + 1 decode
  (+1 verify) compiles. Adapter KV is prefix-cache-namespaced by
  adapter name (never shared with the base model or other adapters)
  and never tiers.
- ``tenants=`` / ``submit(tenant=...)`` engage a stride weighted-fair
  scheduler over admission order, the chunked-prefill token budget,
  and decode-token accounting; per-tenant ``max_queued`` sheds (status
  ``rejected``, reason ``shed``) instead of raising, and TenantSpec
  SLO thresholds become tenant-scoped Objectives over the bounded
  ``tenant=``-labeled ttft/tpot histograms.

Robustness (fault tolerance PR): per-request deadlines (expired
requests finish with status ``timed_out``), a preemption retry cap
(``preempted``), a watchdog that raises after `watchdog_ticks`
consecutive zero-progress ticks with work pending, and
:meth:`InferenceServer.drain` / :meth:`InferenceServer.shutdown` for
graceful teardown (``submit`` after shutdown raises; stragglers are
cancelled with status ``rejected``). :meth:`InferenceServer.cancel`
kills one queued/running request (status ``cancelled``, blocks freed
with prefix refcounts respected) — the hedging loser's exit;
:meth:`InferenceServer.begin_drain` / :meth:`end_drain` flip admission
without stepping, and :meth:`health_detail` is the structured /healthz
body the fleet router scores replicas by.
"""
from __future__ import annotations

import time
from collections import OrderedDict, deque
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from .. import faults as _ft
from .. import flight as _fl
from .. import goodput as _gp
from .. import telemetry
from ..ndarray import NDArray
from .kv_cache import PagedKVCache
from . import executables
from . import lora as _lora

__all__ = ["Request", "InferenceServer", "ServerStalledError"]

_QUEUED, _RUNNING, _FINISHED = "queued", "running", "finished"
#: terminal statuses — set exactly once when a request leaves the system
_OK, _TIMED_OUT, _PREEMPTED, _REJECTED, _CANCELLED = \
    "ok", "timed_out", "preempted", "rejected", "cancelled"


def _upload(a):
    """A host array the scheduler goes on changing, as an executable's
    operand: a copy, never a view. A launch may not have run yet when
    the host writes the next tick's values (on the CPU backend
    `jnp.asarray` of an aligned numpy array can share its memory)."""
    return jnp.asarray(np.array(a))


class _Flight:
    """One decode tick launched and not yet read: its outputs still on
    the device, and what the host knew of each row when it launched."""

    __slots__ = ("seq", "tok", "n_acc", "counts", "prefill_counts",
                 "admit", "warm", "dlens")

    def __init__(self, seq, tok, n_acc, counts, prefill_counts, admit,
                 warm, dlens):
        #: ordinal of the launch: `tick` on the `mx.serve_dispatch`
        #: that launched it and on the `mx.serve_wait` that reads it
        self.seq = seq
        self.tok, self.n_acc = tok, n_acc
        self.counts, self.prefill_counts = counts, prefill_counts
        #: admission stamp of each row's request, -2 where the tick
        #: has no row: a row counts only while its slot's stamp is this
        self.admit = admit
        self.warm, self.dlens = warm, dlens


class ServerStalledError(RuntimeError):
    """The decode loop made no progress for `watchdog_ticks` ticks
    while work was pending — the executable (or its device) is wedged.
    Raised out of step()/run() so the supervisor can restart the
    server instead of spinning forever."""


class Request:
    """One generation request and its lifecycle record."""

    _next_id = 0

    def __init__(self, prompt, max_new_tokens, temperature, top_k,
                 top_p, eos_id, seed, deadline_s=None, trace_ctx=None,
                 tenant=None, priority=None, adapter=None):
        self.id = Request._next_id
        Request._next_id += 1
        #: tenant QoS: owning tenant name (None = untenanted), priority
        #: class (shed ordering), LoRA adapter name + its table row
        #: (0 = the identity adapter — base-model rows)
        self.tenant = None if tenant is None else str(tenant)
        self.priority = None if priority is None else str(priority)
        self.adapter = None if adapter is None else str(adapter)
        self.adapter_idx = 0
        self._adapter_held = False
        #: distributed trace context: the fleet router's idempotency
        #: token for the attempt that carried this request (None for
        #: direct submits); stitched back into the fleet timeline
        self.trace_ctx = trace_ctx
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.eos_id = -1 if eos_id is None else int(eos_id)
        self.seed = int(seed)
        self.state = _QUEUED
        self.output_tokens: List[int] = []
        #: high-water mark of tokens already counted into the server's
        #: throughput metrics; survives preemption so regenerated
        #: tokens are not double-counted
        self.tokens_counted = 0
        self.finish_reason: Optional[str] = None
        #: terminal outcome: "ok" | "timed_out" | "preempted" |
        #: "rejected" | "cancelled"; None while the request is live
        self.status: Optional[str] = None
        self.t_submit = time.perf_counter()
        self.deadline_s = None if deadline_s is None else float(deadline_s)
        #: absolute wall-clock deadline; queue wait counts against it
        self.t_deadline = None if deadline_s is None \
            else self.t_submit + float(deadline_s)
        self.t_first_token: Optional[float] = None
        self.t_finish: Optional[float] = None
        self.preemptions = 0
        # per-request span timeline (tracing): discrete transitions in
        # `_trace`, decode ticks merged into contiguous windows (one
        # window per admit, so a preemption splits them). None = the
        # server is not tracing this request.
        self.t_admit: Optional[float] = None
        self.t_last_token: Optional[float] = None
        self.prefix_tokens_shared = 0
        self.cow_copies = 0
        self._trace: Optional[List[dict]] = None
        self._decode_windows: Optional[List[dict]] = None
        self._trace_seq = 0

    def _tev(self, name: str, t: Optional[float] = None, **kw):
        """Append one timeline event (no-op when tracing is off)."""
        if self._trace is not None:
            ev = {"name": name,
                  "t": time.perf_counter() if t is None else t}
            ev.update(kw)
            self._trace.append(ev)

    def _open_decode_window(self):
        if self._decode_windows is not None:
            self._decode_windows.append({"t0": None, "t1": None, "n": 0})

    def _note_decode(self, now: float):
        self.t_last_token = now
        if self._decode_windows is None:
            return
        if not self._decode_windows:
            self._decode_windows.append({"t0": None, "t1": None, "n": 0})
        w = self._decode_windows[-1]
        if w["t0"] is None:
            w["t0"] = now
        w["t1"] = now
        w["n"] += 1

    @property
    def ttft(self) -> Optional[float]:
        if self.t_first_token is None:
            return None
        return self.t_first_token - self.t_submit

    def tokens(self) -> np.ndarray:
        """prompt + generated tokens, 1-D int32."""
        return np.concatenate(
            [self.prompt, np.asarray(self.output_tokens, np.int32)])

    def __repr__(self):
        return (f"Request(id={self.id}, state={self.state}, "
                f"prompt={len(self.prompt)}t, "
                f"out={len(self.output_tokens)}t)")


class InferenceServer:
    """Continuous-batching engine over the paged KV cache and the
    persistent prefill/decode executables.

        server = InferenceServer(net, batch_slots=8, max_len=256)
        reqs = [server.submit(p, max_new_tokens=32) for p in prompts]
        server.run()
        for r in reqs: print(r.tokens())

    `max_len` (= max_blocks_per_seq * block_size) bounds
    prompt + generated per sequence; `num_blocks` sizes the shared
    pool (default: enough for every slot at full length, +1 scratch —
    shrink it to exercise preemption)."""

    def __init__(self, net, *, batch_slots: int = 8,
                 max_len: int = 256, block_size: int = 16,
                 max_prompt_len: Optional[int] = None,
                 kv_cache_dtype: str = "model",
                 num_blocks: Optional[int] = None,
                 window_num_blocks: Optional[int] = None,
                 max_preemptions: Optional[int] = 3,
                 watchdog_ticks: int = 256,
                 prefix_cache: bool = False,
                 trace_sample_every: int = 1,
                 trace_slow_s: Optional[float] = None,
                 trace_capacity: int = 256,
                 prefill_chunk_tokens: Optional[int] = None,
                 speculative=None,
                 kv_tiering: bool = False,
                 tier_host_blocks: Optional[int] = None,
                 tier_spill_exhaust_s: Optional[float] = 3.0,
                 tier_spill_batch: int = 4,
                 tier_prefetch_timeout_s: Optional[float] = None,
                 prefix_store_dir: Optional[str] = None,
                 lora=None, tenants=None):
        if max_len % block_size:
            raise ValueError("max_len must be a multiple of block_size")
        # the net describes its own decoder (models/decoder.py): the
        # server names no model
        self.decoder = dec = net.decoder()
        cfg = dec.cfg
        self.net = net
        self.cfg = cfg
        self.batch_slots = batch_slots
        self.max_len = max_len
        self.block_size = block_size
        self.max_prompt_len = max_prompt_len or min(max_len, 64)
        self.kv_cache_dtype = kv_cache_dtype
        # the tier hierarchy rides the content index — tiering (or a
        # persistent prefix store) implies the prefix cache
        if kv_tiering or prefix_store_dir is not None:
            prefix_cache = True
        self.prefix_cache = prefix_cache
        if prefill_chunk_tokens is not None:
            prefill_chunk_tokens = int(prefill_chunk_tokens)
            if prefill_chunk_tokens < 1:
                raise ValueError("prefill_chunk_tokens must be >= 1")
            prefill_chunk_tokens = min(prefill_chunk_tokens,
                                       self.max_prompt_len)
        self.prefill_chunk_tokens = prefill_chunk_tokens
        from .speculative import as_proposer
        self._spec = as_proposer(speculative)
        # a description says which of these its layer functions and
        # cache kinds implement; the rest raise here, by name
        for feature, wanted in (
                ("prefill_chunk", prefill_chunk_tokens is not None),
                ("speculative", self._spec is not None),
                ("lora", lora is not None),
                ("int8", kv_cache_dtype == "int8"),
                ("prefix_cache", prefix_cache and not kv_tiering
                 and prefix_store_dir is None),
                ("kv_tier", kv_tiering or prefix_store_dir is not None)):
            if wanted:
                dec.require(feature, f"InferenceServer({feature})")
        # batched multi-LoRA: a fixed-capacity device-resident adapter
        # table; per-slot table INDICES are traced executable operands,
        # so every adapter mix / hot-load / eviction shares the one
        # compiled prefill/decode(/verify). `lora` is an AdapterPool,
        # True (defaults), or a kwargs dict for AdapterPool(net, ...).
        if lora is not None and not isinstance(lora, _lora.AdapterPool):
            kw = {} if lora is True else dict(lora)
            lora = _lora.AdapterPool(net, **kw)
        self.lora = lora
        # tenant QoS: specs + lazily-engaged weighted-fair scheduler —
        # without tenants the admission path stays plain FIFO
        self._tenants = {}
        self._wfs = None
        self.tenant_objectives = {}
        #: bounded `tenant=` telemetry label space: past the cap every
        #: new tenant reports as "other" (cardinality contract)
        self._tenant_label_cap = 16
        self._tenant_labels = set()
        if tenants:
            for name, spec in tenants.items():
                self.register_tenant(name, spec)
        max_blocks = max_len // block_size
        if num_blocks is None:
            num_blocks = batch_slots * max_blocks + 1
        model_dtype = jnp.dtype(getattr(cfg, "dtype", "float32"))
        from ..models.llama_infer import _params_device
        params = dec.params_tree(net)
        # every array the executables take (weights, page pools,
        # logits/PRNG rows) is committed to the weights' device as a
        # plain single-device array: weights a mesh'd train step handed
        # back carry a NamedSharding, jit hands it on to the pools it
        # returns, and the second call would then miss the first one's
        # executable — one silent extra compile per program
        self._device = dev = _params_device(params)
        self._params = jax.device_put(params, dev)
        kinds = {}
        if dec.mixed or dec.recurrent or dec.latent:
            kinds["layer_kinds"] = dec.layer_kinds
        if dec.mixed:
            kinds.update(window=dec.window,
                         window_num_blocks=window_num_blocks)
        if dec.recurrent:
            kinds["state_shapes"] = dec.state_shapes()
        self.cache = PagedKVCache(
            num_layers=cfg.num_layers, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim, num_blocks=num_blocks,
            block_size=block_size, batch_slots=batch_slots,
            max_blocks_per_seq=max_blocks, dtype=model_dtype,
            quantized=kv_cache_dtype == "int8",
            prefix_cache=prefix_cache, device=dev, **kinds)
        #: a net with recurrent layers: prefill takes the slot, and the
        #: spans carry the counts its kernels' rooflines read
        self._recurrent = dec.recurrent
        #: a net with latent layers: the dispatch span carries `ctx`,
        #: the cached rows its sweep reads
        self._latent = dec.latent
        #: cached positions the decode ticks attended, summed over
        #: active slots and ticks: every one (`context_tokens`) and the
        #: last `window` of them (`window_context_tokens`, what a
        #: sliding layer's sweep reads); and the decoder's own counts
        self.context_tokens = 0
        self.window_context_tokens = 0
        self.decoder_counts = {n: 0 for n in dec.counts}
        self._prefill_counts = []
        self.programs = executables.paged_programs(
            net, batch_slots=batch_slots, max_blocks_per_seq=max_blocks,
            block_size=block_size, max_prompt_len=self.max_prompt_len,
            kv_cache_dtype=kv_cache_dtype,
            prefill_chunk=prefill_chunk_tokens or 0,
            spec_k=self._spec.k if self._spec is not None else 0,
            lora=self.lora.signature() if self.lora is not None
            else None)

        # KV-block memory hierarchy (serving/kv_tier.py): host-RAM
        # spill tier + optional disk-backed persistent prefix store.
        # With a tier attached, reclaiming a parked block demotes its
        # content instead of discarding it, preemptions spill instead
        # of forcing a recompute, and admits prefetch-restore matching
        # host/disk prefixes through the restore executable.
        self.tier = None
        if kv_tiering or prefix_store_dir is not None:
            from .kv_tier import KVTierManager, PrefixStore
            store = PrefixStore(prefix_store_dir) \
                if prefix_store_dir else None
            self.tier = KVTierManager(
                self.cache, self.programs,
                host_capacity_blocks=tier_host_blocks,
                store=store,
                spill_exhaust_s=tier_spill_exhaust_s,
                spill_batch=tier_spill_batch,
                prefetch_timeout_s=tier_prefetch_timeout_s)
            self.cache.attach_tier(self.tier)
            if store is not None:
                self.tier.load_store()

        # traced code cannot bump counters, so the per-tick HBM bytes
        # the in-kernel paged path avoids (vs the gather fallback's
        # contiguous view) are computed here and counted after each
        # decode tick. The probe and flash_decode_paged's trace-time
        # choice are the same call: paged_kernel_mode.
        from ..kernels.flash_decode import (paged_kernel_mode,
                                            paged_gather_bytes)
        q8 = kv_cache_dtype == "int8"
        # (a cache with no block pool has no paged call to probe)
        pool_k = next((pg["k"] for pg in self.cache.pages if "k" in pg),
                      None)
        self._kernel_paged = pool_k is not None and paged_kernel_mode(
            pool_k, quantized=q8) is not None
        self._gather_bytes_per_tick = 0 if pool_k is None else sum(
            "k" in pg for pg in self.cache.pages) * paged_gather_bytes(
            pool_k.shape, (batch_slots, max_blocks),
            pool_k.dtype.itemsize, quantized=q8)

        B, V = batch_slots, cfg.vocab_size
        # device_put to an explicit device = committed: the decode
        # executable's first call must present the same sharding
        # signature as steady-state calls (where these are jit
        # outputs), or jit recompiles once
        self._last_logits = jax.device_put(jnp.zeros((B, V),
                                                     model_dtype), dev)
        self._keys = jax.device_put(jnp.zeros((B, 2), jnp.uint32), dev)
        self._pos = np.zeros(B, np.int32)
        self._active = np.zeros(B, bool)
        self._temps = np.zeros(B, np.float32)
        self._top_ks = np.zeros(B, np.int32)
        self._top_ps = np.zeros(B, np.float32)
        # per-slot LoRA table row (0 = identity): a traced decode/
        # verify operand like temps/top_ks, so adapter mixes never
        # re-key the executables
        self._adapter_ids = np.zeros(B, np.int32)
        self._slot_req: List[Optional[Request]] = [None] * B
        # admission order stamp; -1 on an empty slot, so a stamp also
        # says whether a row computed for a slot still has its owner
        self._admit_seq = 0
        self._slot_admit = np.full(B, -1, np.int64)
        # one decode tick is kept queued on the device ahead of the
        # one whose tokens step() hands over: `_flights` holds the
        # ticks launched and not yet read (oldest first), `_left` the
        # tokens each slot has still to ask the device for
        self._flights: deque = deque()
        self._left = np.zeros(B, np.int32)
        self._launches = 0
        self.ticks_ahead = 0
        self.ticks_late = 0
        # chunked-prefill / speculative per-slot state: a prefilling
        # slot holds blocks + request but isn't decode-active yet; a
        # warm slot's next tick re-feeds the last prompt token (full
        # prefix-cache cover skipped the prefill dispatch entirely)
        self._prefilling = np.zeros(B, bool)
        self._prefill_pos = np.zeros(B, np.int32)
        self._warm = np.zeros(B, bool)
        self.prefills_skipped = 0
        #: hard preemptions (recompute cliff) vs spill preemptions
        #: (victim's prefix demoted to the host tier — re-admission
        #: restores it with a copy, not a recompute)
        self.preemptions = 0
        self.spill_preemptions = 0
        self.spec_tokens_accepted = 0
        self.spec_tokens_rejected = 0
        self._spec_window: deque = deque(maxlen=256)
        self.queue: deque = deque()
        self.finished: List[Request] = []
        self.ticks = 0
        self.tokens_generated = 0
        self._tok_window: deque = deque(maxlen=256)
        # robustness knobs: a request preempted more than
        # max_preemptions times fails terminally (None = unlimited);
        # the watchdog raises after watchdog_ticks consecutive ticks
        # without progress while work is pending
        self.max_preemptions = max_preemptions
        self.watchdog_ticks = int(watchdog_ticks)
        self._stall_ticks = 0
        self._stalled = False
        self._draining = False
        self._shutdown = False
        # per-request tracing: collect a span timeline for every
        # request while `trace_sample_every > 0` (or a slow-outlier
        # threshold is set); at finish, RETAIN the assembled trace only
        # for every `trace_sample_every`-th submission plus any request
        # whose latency/TTFT exceeds `trace_slow_s` — the retained
        # store is an LRU bounded by `trace_capacity`, so tracing can
        # stay on in production without growing memory
        self._trace_every = max(0, int(trace_sample_every))
        self._trace_slow_s = trace_slow_s
        self._trace_capacity = max(1, int(trace_capacity))
        self._trace_on = self._trace_every > 0 or trace_slow_s is not None
        self._traces: "OrderedDict[int, dict]" = OrderedDict()
        self._submit_seq = 0
        # KV-pool time-to-exhaustion forecaster: O(1) per-tick samples,
        # lazy rolling fit. critical_s=None keeps this server's own
        # /healthz steady — the FleetRouter reads `exhaust_in_s` from
        # health_detail() and steers long-prompt work away instead
        # (pass a threshold via PoolForecaster directly to make it
        # page; see docs/observability.md)
        self._forecaster = _gp.PoolForecaster()
        # /healthz flips to 503 during stall/drain/shutdown; chrome
        # traces gain the request-span pid (all weakref-held)
        telemetry.register_health_source(self)
        telemetry.register_health_source(self._forecaster)
        telemetry.register_request_trace_source(self)
        # opt-in /metrics endpoint (MXNET_TPU_METRICS_PORT): no-op
        # unless the env var is set
        telemetry.maybe_start_metrics_server()

    # -- request intake -----------------------------------------------------

    def refresh_params(self):
        """Re-snapshot the net's weights (after a training step /
        checkpoint load). Shapes are unchanged, so no recompile."""
        self._params = jax.device_put(
            self.decoder.params_tree(self.net), self._device)

    def _tables(self, slot=None):
        """The block table(s) as the executables take them: the one
        array, or with two kinds of layer the pair (full, sliding), or
        nothing at all for a cache with no block pool; `slot` picks
        that sequence's row."""
        c = self.cache
        if not c.paged:
            return ()
        tabs = (c.block_tables,) if c.window_tables is None \
            else (c.block_tables, c.window_tables)
        out = tuple(_upload(t if slot is None else t[slot])
                    for t in tabs)
        return out[0] if len(out) == 1 else out

    # -- tenants + adapters -------------------------------------------------

    def register_tenant(self, name: str, spec=None) -> "_lora.TenantSpec":
        """Register (or update) a tenant's QoS contract. `spec` is a
        :class:`~mxnet_tpu.serving.lora.TenantSpec`, a kwargs dict, or
        None (defaults). The first registration engages the weighted-
        fair scheduler for admission / prefill-budget / decode-token
        accounting; unknown tenants submitting later auto-register with
        default QoS."""
        name = str(name)
        spec = _lora.TenantSpec() if spec is None \
            else _lora.TenantSpec.coerce(spec)
        self._tenants[name] = spec
        if self._wfs is None:
            self._wfs = _lora.WeightedFairScheduler()
        self._wfs.set_weight(name, spec.weight)
        objs = spec.objectives(name)
        if objs:
            self.tenant_objectives[name] = objs
        return spec

    def _tenant_label(self, name: str) -> str:
        """Bounded telemetry label for a tenant name: first
        `_tenant_label_cap` distinct tenants keep their name, the rest
        collapse into "other" so label cardinality stays fixed."""
        if name in self._tenant_labels:
            return name
        if len(self._tenant_labels) < self._tenant_label_cap:
            self._tenant_labels.add(name)
            return name
        return "other"

    def load_adapter(self, name: str, adapter, scale=None) -> int:
        """Hot-load (or update) a LoRA adapter into the device table —
        safe under live traffic, ZERO recompiles (the table swap is
        functional; only its shape is an executable build key). Returns
        the table row."""
        if self.lora is None:
            raise RuntimeError(
                "LoRA serving is off — construct the server with "
                "lora=AdapterPool(net, ...) (or lora=True)")
        return self.lora.load(name, adapter, scale=scale)

    def evict_adapter(self, name: str):
        """Drop a loaded adapter (refuses while live requests hold
        it)."""
        if self.lora is None:
            raise RuntimeError("LoRA serving is off")
        self.lora.evict(name)

    def _lora_args(self, aids) -> tuple:
        """The trailing (adapters, aids) executable operands — empty
        when LoRA is off, so the dispatch signature exactly matches a
        LoRA-less build."""
        if self.lora is None:
            return ()
        return (self.lora.tables, _upload(np.asarray(aids, np.int32)))

    def _prefix_root(self, req: "Request"):
        """Prefix-cache chain root for a request: adapter requests get
        an adapter-namespaced sentinel root, so KV computed under
        adapter X is NEVER shared with adapter Y or the base model
        (same tokens, different weights => different cache content)."""
        if req.adapter is None:
            return None
        return ("__lora__", req.adapter)

    def _charge(self, req: "Request", amount: int):
        """Weighted-fair accounting: `amount` tokens of service
        (prefill or decode) against the request's tenant."""
        if self._wfs is not None and amount > 0:
            self._wfs.charge(req.tenant or "", amount)

    def submit(self, prompt_ids, max_new_tokens: int,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 0.0, eos_id: Optional[int] = None,
               seed: int = 0,
               deadline_s: Optional[float] = None,
               trace_ctx: Optional[str] = None,
               tenant: Optional[str] = None,
               priority: Optional[str] = None,
               adapter: Optional[str] = None) -> Request:
        """Enqueue one request. prompt_ids: 1-D (or (1, T)) ints.
        ``deadline_s`` bounds the request's total wall-clock lifetime
        (queue wait included); past it the request finishes with
        status ``timed_out``. ``trace_ctx`` stamps a distributed trace
        context (the fleet router's per-attempt idempotency token) onto
        the request so its span timeline can be correlated across
        processes.

        ``tenant`` attributes the request to a tenant's weighted-fair
        share + telemetry/SLO scope (unknown tenants auto-register
        with default QoS); ``priority`` overrides the tenant's shed
        class; ``adapter`` names a loaded LoRA adapter to serve the
        request through (ValueError when unknown — hot-load first).
        Past a tenant's ``max_queued`` the request is SHED: returned
        already-terminal (status ``rejected``, reason ``shed``), never
        raised, so a flooding tenant sees backpressure while others
        keep their share."""
        if self._shutdown or self._draining:
            if telemetry._ENABLED:
                telemetry.inc("serving_requests_total", status=_REJECTED)
            raise RuntimeError(
                "InferenceServer is "
                + ("shut down" if self._shutdown else "draining")
                + " — submit() rejected; start a new server (or submit "
                  "before calling drain()/shutdown())")
        if isinstance(prompt_ids, NDArray):
            prompt_ids = prompt_ids.asnumpy()
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if prompt.size > self.max_prompt_len:
            raise ValueError(f"prompt of {prompt.size} tokens exceeds "
                             f"max_prompt_len={self.max_prompt_len}")
        if prompt.size + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt({prompt.size}) + max_new_tokens"
                f"({max_new_tokens}) exceeds max_len={self.max_len}")
        # a request whose lifetime footprint exceeds the whole pool can
        # never be admitted (or never finish): _admit would leave it
        # queued forever and run() would spin. Reject it up front.
        need = self.cache.blocks_for(prompt.size + max_new_tokens)
        capacity = self.cache.num_blocks - 1    # block 0 is scratch
        if need > capacity:
            raise ValueError(
                f"request needs {need} KV blocks "
                f"(prompt {prompt.size} + {max_new_tokens} new tokens, "
                f"block_size={self.block_size}) but the pool only has "
                f"{capacity} — raise num_blocks or shrink the request")
        wneed = self.cache.window_blocks_for(prompt.size
                                             + max_new_tokens)
        if wneed > self.cache.window_blocks_capacity:
            raise ValueError(
                f"request needs {wneed} blocks of the sliding-window "
                f"layers' pool, which only has "
                f"{self.cache.window_blocks_capacity} — raise "
                "window_num_blocks")
        spec = None
        if tenant is not None:
            tenant = str(tenant)
            spec = self._tenants.get(tenant)
            if spec is None:
                spec = self.register_tenant(tenant)
        if adapter is not None:
            if self.lora is None:
                raise ValueError(
                    "request names adapter "
                    f"{adapter!r} but LoRA serving is off — construct "
                    "the server with lora=...")
            if adapter not in self.lora._idx:
                raise ValueError(
                    f"adapter {adapter!r} is not loaded "
                    f"(loaded: {self.lora.loaded()}) — "
                    "load_adapter() first")
        if priority is None and spec is not None:
            priority = spec.priority
        req = Request(prompt, max_new_tokens, temperature, top_k,
                      top_p, eos_id, seed, deadline_s=deadline_s,
                      trace_ctx=trace_ctx, tenant=tenant,
                      priority=priority, adapter=adapter)
        req._trace_seq = self._submit_seq
        self._submit_seq += 1
        if self._trace_on:
            req._trace = []
            req._decode_windows = []
            req._tev("queued", t=req.t_submit)
        # per-tenant queue bound: past it the request is shed, not
        # raised — terminal status "rejected", reason "shed", exactly
        # the FleetRouter overflow contract
        if spec is not None and spec.max_queued is not None:
            queued = sum(1 for r in self.queue if r.tenant == tenant)
            if queued >= spec.max_queued:
                _lora._note_shed(self._tenant_label(tenant),
                                 req.priority)
                self._terminate(req, "shed", _REJECTED)
                return req
        if req.adapter is not None:
            req.adapter_idx = self.lora.acquire(req.adapter)
            req._adapter_held = True
        self.queue.append(req)
        if self._wfs is not None and tenant is not None:
            self._wfs.activate(tenant)
        if telemetry._ENABLED:
            telemetry.inc("serving_requests_total")
        return req

    # -- scheduler ----------------------------------------------------------

    def _free_slots(self):
        return [i for i in range(self.batch_slots)
                if not self._active[i] and not self._prefilling[i]]

    def _copy_block(self, src: int, dst: int,
                    req: Optional[Request] = None):
        """Device-side CoW copy through the persistent executable."""
        self.cache.pages = self.programs["copy_block"](
            self.cache.pages, jnp.asarray(src, jnp.int32),
            jnp.asarray(dst, jnp.int32))
        if telemetry._ENABLED:
            telemetry.inc("serving_cow_copies_total")
        if req is not None:
            req.cow_copies += 1
            req._tev("cow", src=src, dst=dst)

    def _note_prefix_hit(self, req: Request, shared_len: int):
        if shared_len:
            req.prefix_tokens_shared += shared_len
            if telemetry._ENABLED:
                telemetry.inc("serving_prefix_hits_total")
                telemetry.inc("serving_prefix_tokens_shared_total",
                              shared_len)

    def _seed_key(self, slot: int, req: Request):
        """(Re)start the slot's PRNG row from the request's seed."""
        self._keys = self._keys.at[slot].set(
            jnp.asarray(jax.random.PRNGKey(req.seed), jnp.uint32))

    def _seed_slot(self, slot: int, req: Request):
        """Decode activation: PRNG row + per-row sampling params."""
        self._seed_key(slot, req)
        self._active[slot] = True
        self._left[slot] = req.max_new_tokens - len(req.output_tokens)
        self._temps[slot] = req.temperature
        self._top_ks[slot] = req.top_k
        self._top_ps[slot] = req.top_p
        self._adapter_ids[slot] = req.adapter_idx

    def _admit_one(self, slot: int, req: Request,
                   shared_len: int = 0, cow=None):
        T = len(req.prompt)
        if cow is not None:
            # the prompt extends into a shared block mid-block: give
            # the slot a private copy BEFORE prefill overwrites the
            # positions past shared_len
            self._copy_block(*cow, req=req)
        req.t_admit = time.perf_counter()
        req._tev("admit", t=req.t_admit, slot=slot,
                 shared_len=shared_len)
        if _fl._ENABLED:
            _fl.record("sched", "serving.admit", request=req.id,
                       slot=slot, prompt=T, shared_len=shared_len)
        self._slot_req[slot] = req
        self._slot_admit[slot] = self._admit_seq
        self._admit_seq += 1
        req.state = _RUNNING

        if self.prefix_cache and shared_len >= T:
            # the prefix cache fully covers the prompt — every k/v row
            # is already resident in adopted blocks, so skip the
            # prefill dispatch entirely. Seed a WARM tick instead:
            # pos = T-1 with one-hot logits on the last prompt token,
            # so the next decode tick deterministically re-feeds that
            # token (argmax AND categorical: every other logit is
            # -1e30, whose exp underflows to exactly 0), recomputes
            # its k/v into a CoW'd private block, and yields the true
            # last-prompt logits. The re-fed token is NOT emitted.
            self.prefills_skipped += 1
            if telemetry._ENABLED:
                telemetry.inc("serving_prefill_skipped_total")
            self._note_prefix_hit(req, T)
            one = np.full((self.cfg.vocab_size,), -1e30, np.float32)
            one[int(req.prompt[-1])] = 0.0
            self._last_logits = self._last_logits.at[slot].set(
                jnp.asarray(one).astype(self._last_logits.dtype))
            self._pos[slot] = T - 1
            self._warm[slot] = True
            self._seed_slot(slot, req)
            req._tev("prefill_skip", tokens=T)
            req._open_decode_window()
            return

        if self.prefill_chunk_tokens is not None:
            # chunked mode: hold the slot in the in-prefill state; the
            # chunks run from step()'s per-tick token budget
            self._prefilling[slot] = True
            self._prefill_pos[slot] = shared_len
            self._note_prefix_hit(req, shared_len)
            return

        ids = np.zeros((1, self.max_prompt_len), np.int32)
        ids[0, :T] = req.prompt
        bt_row = self._tables(slot)
        t_pf = time.perf_counter()
        # recurrent layers: the slot's row of the state pool is this
        # prefill's to write, and the scan's roofline reads the tokens
        slot_arg, scanned = ((jnp.asarray([slot], jnp.int32),),
                             {"scan_tokens": T}) \
            if self._recurrent else ((), {})
        with telemetry.phase("serve_prefill", tokens=T,
                             padded=self.max_prompt_len, **scanned):
            self.cache.pages, last, *counts = self.programs["prefill"](
                self._params, self.cache.pages, bt_row,
                jnp.asarray(ids), jnp.asarray([T], jnp.int32),
                jnp.asarray([shared_len], jnp.int32), *slot_arg,
                *self._lora_args([req.adapter_idx]))
        # the decoder's counts of this prefill stay on the device
        # until the tick's one sync reads them
        self._prefill_counts.extend(counts)
        self._charge(req, T - shared_len)
        req._tev("prefill", t=t_pf,
                 dur_s=time.perf_counter() - t_pf, tokens=T)
        req._open_decode_window()
        if self.prefix_cache:
            self.cache.register_prefix(slot, req.prompt,
                                       root=self._prefix_root(req))
            self._note_prefix_hit(req, shared_len)
        self._last_logits = self._last_logits.at[slot].set(
            last[0].astype(self._last_logits.dtype))
        self._pos[slot] = T
        self._seed_slot(slot, req)

    def _next_queued(self) -> int:
        """Queue index of the next request to admit: plain FIFO
        without tenants; with tenants, the weighted-fair pick over
        each tenant's FIFO head (untenanted requests compete as the
        "" tenant at default weight)."""
        if self._wfs is None or len(self.queue) <= 1:
            return 0
        heads = {}
        for i, r in enumerate(self.queue):
            t = r.tenant or ""
            if t not in heads:
                heads[t] = i
        if len(heads) == 1:
            return 0
        return heads[self._wfs.pick(heads)]

    def _admit(self):
        admitted = 0
        free = self._free_slots()
        while self.queue and free:
            qi = self._next_queued()
            req = self.queue[qi]
            root = self._prefix_root(req)
            # the prompt's blocks now; the first decode block comes
            # lazily via ensure()
            if self.prefix_cache:
                if self.tier is not None and root is None:
                    # prefetch-on-LCP-match: restore host/disk-tier
                    # blocks extending the device prefix into PARKED
                    # blocks, so alloc_shared below adopts them (a
                    # copy instead of a recompute). Adapter-rooted
                    # chains never tier — their content is only valid
                    # under that adapter's weights.
                    self.tier.prefetch(req.prompt)
                # alloc_shared is its own feasibility check: a prefix
                # hit can admit where a cold can_alloc would refuse
                plan = self.cache.alloc_shared(free[0], req.prompt,
                                               root=root)
                if plan is None:
                    break
                del self.queue[qi]
                slot = free.pop(0)
                self._admit_one(slot, req,
                                shared_len=plan["shared_len"],
                                cow=plan["cow"])
            else:
                if not self.cache.can_alloc(len(req.prompt)):
                    break
                del self.queue[qi]
                slot = free.pop(0)
                self.cache.alloc(slot, len(req.prompt))
                self._admit_one(slot, req)
            admitted += 1
        return admitted

    def _preempt_youngest(self, asker: int) -> bool:
        """Free the most recently admitted running request back to the
        queue head: `asker` itself when it is the youngest, so no
        request is ever evicted for a younger one, the oldest always
        runs to its end, and two requests cannot evict each other for
        ever. Returns False if nothing but `asker` is running."""
        running = [i for i in range(self.batch_slots)
                   if self._active[i] or self._prefilling[i]]
        if all(i == asker for i in running):
            return False
        victim = max(running, key=lambda i: self._slot_admit[i])
        req = self._slot_req[victim]
        req.preemptions += 1
        # with a tier attached this is a SPILL preemption: the
        # victim's registered prefix demotes to the host tier below,
        # so re-admission costs a restore copy instead of a recompute
        # — a tiered-latency event, not the preemption cliff
        spill = self.tier is not None
        if spill:
            self.spill_preemptions += 1
        else:
            self.preemptions += 1
        req._tev("preempt", slot=victim, n=req.preemptions,
                 spill=spill)
        if telemetry._ENABLED:
            telemetry.inc("serving_spill_preemptions_total" if spill
                          else "serving_preemptions_total")
        if _fl._ENABLED:
            _fl.record("sched", "serving.preempt", request=req.id,
                       slot=victim, n=req.preemptions, spill=spill)
        if self.max_preemptions is not None \
                and req.preemptions > self.max_preemptions:
            # retry budget exhausted: fail the request terminally
            # instead of thrashing the pool forever
            self._finish(victim, "preempted", status=_PREEMPTED)
            return True
        req.state = _QUEUED
        req.output_tokens = []          # greedy rerun is identical
        self._evict(victim)
        if spill:
            # demote every parked prefix NOW (the victim's prompt
            # chain included): the freed blocks become genuinely
            # reusable while the content stays restorable
            self.tier.spill_parked()
        self.queue.appendleft(req)
        return True

    def _ensure_blocks(self, send):
        """Every slot with a row in the tick about to be launched
        (`send`) needs the block holding its next write position."""
        order = sorted(np.flatnonzero(send),
                       key=lambda i: self._slot_admit[i])
        for slot in order:
            # a slot evicted in this pass — by an older slot, or by
            # itself as the youngest — asks for nothing more: ensure()
            # on it would allocate a block to an empty slot and poison
            # its next admission
            while self._active[slot] and not self.cache.ensure(
                    slot, int(self._pos[slot])):
                if not self._preempt_youngest(slot):
                    raise RuntimeError(
                        "KV pool too small for a single sequence — "
                        "raise num_blocks or lower max_len")
            # copy-on-write: this tick's token lands in a block some
            # other slot still references
            while self._active[slot]:
                pw = self.cache.prepare_write(slot,
                                              int(self._pos[slot]))
                if pw is False:
                    if not self._preempt_youngest(slot):
                        raise RuntimeError(
                            "KV pool too small for a single sequence "
                            "— raise num_blocks or lower max_len")
                    continue    # retry: the preemption freed blocks
                if pw is not None:
                    self._copy_block(*pw, req=self._slot_req[slot])
                break

    # -- chunked prefill + speculative drafting ------------------------------

    def _prefill_tick(self) -> int:
        """Spend this tick's chunk budget (prefill_chunk_tokens) on
        in-prefill slots, oldest admission first. Returns tokens
        prefilled (watchdog progress units)."""
        C = self.prefill_chunk_tokens
        budget = C
        any_work = False
        if self._wfs is None:
            order = sorted((i for i in range(self.batch_slots)
                            if self._prefilling[i]),
                           key=lambda i: self._slot_admit[i])
            for slot in order:
                while budget > 0 and self._prefilling[slot]:
                    budget -= self._prefill_chunk(slot, budget)
                    any_work = True
        else:
            # weighted-fair chunk budget: each chunk goes to the
            # minimum-pass tenant among in-prefill slots (admission-
            # order tiebreak within a tenant), and _prefill_chunk
            # charges the tokens — a long prompt from a flooding
            # tenant cannot monopolize the per-tick budget
            while budget > 0:
                heads = {}
                for slot in sorted(
                        (i for i in range(self.batch_slots)
                         if self._prefilling[i]),
                        key=lambda i: self._slot_admit[i]):
                    heads.setdefault(
                        self._slot_req[slot].tenant or "", slot)
                if not heads:
                    break
                slot = heads[self._wfs.pick(heads)]
                budget -= self._prefill_chunk(slot, budget)
                any_work = True
        used = C - budget
        if telemetry._ENABLED and any_work:
            telemetry.set_gauge("serving_chunk_budget_utilization",
                                used / C)
        return used

    def _prefill_chunk(self, slot: int, budget: int) -> int:
        """One windowed prefill dispatch for `slot`: at most
        min(budget, C, remaining prompt) tokens starting at the slot's
        prefill cursor. Completes the prefill (activates decode) when
        the cursor reaches the prompt end."""
        req = self._slot_req[slot]
        C = self.prefill_chunk_tokens
        T = len(req.prompt)
        start = int(self._prefill_pos[slot])
        n = min(T - start, budget, C)
        ids = np.zeros((1, C), np.int32)
        ids[0, :n] = req.prompt[start:start + n]
        bt_row = _upload(self.cache.block_tables[slot])
        t_pf = time.perf_counter()
        with telemetry.phase("serve_prefill", tokens=n, padded=C):
            self.cache.pages, last = self.programs["prefill_chunk"](
                self._params, self.cache.pages, bt_row,
                jnp.asarray(ids), jnp.asarray([start], jnp.int32),
                jnp.asarray([n], jnp.int32),
                *self._lora_args([req.adapter_idx]))
        self._charge(req, n)
        req._tev("prefill_chunk", t=t_pf,
                 dur_s=time.perf_counter() - t_pf, tokens=n,
                 start=start)
        if _fl._ENABLED:
            _fl.record("sched", "serving.prefill_chunk",
                       request=req.id, slot=slot, start=start,
                       tokens=n)
        self._prefill_pos[slot] = start + n
        if start + n >= T:
            self._prefilling[slot] = False
            if self.prefix_cache:
                self.cache.register_prefix(slot, req.prompt,
                                           root=self._prefix_root(req))
            self._last_logits = self._last_logits.at[slot].set(
                last[0].astype(self._last_logits.dtype))
            self._pos[slot] = T
            self._seed_slot(slot, req)
            req._open_decode_window()
        return n

    def _propose_drafts(self):
        """Ask the proposer for draft tokens for every active GREEDY
        slot and back the speculative window with pool blocks (CoW'd
        where shared). Returns (drafts (B, k), draft_lens (B,)) or
        (None, None) when no slot drafted this tick."""
        k = self._spec.k
        B = self.batch_slots
        drafts = np.zeros((B, k), np.int32)
        dlens = np.zeros(B, np.int32)
        any_draft = False
        for slot in range(B):
            req = self._slot_req[slot]
            if not self._active[slot] or req.temperature > 0:
                continue
            pos = int(self._pos[slot])
            # budget: drafts become real output tokens, so never
            # propose past max_new_tokens; the window's first position
            # is the sampled token (or the warm re-feed, which emits
            # nothing), and every position must fit below max_len
            room = min(k,
                       req.max_new_tokens - len(req.output_tokens)
                       - (0 if self._warm[slot] else 1),
                       self.max_len - pos - 1)
            if room <= 0:
                continue
            prop = np.asarray(self._spec.propose(req.tokens()),
                              np.int32).reshape(-1)
            if not self._warm[slot]:
                # the proposer's first guess targets the very token
                # this tick computes itself (window position 0), so
                # drafts ride one position later; on a WARM tick
                # position 0 is the known last prompt token and the
                # guesses align as-is
                prop = prop[1:]
            prop = prop[:room]
            if prop.size == 0:
                continue
            # back positions pos+1 .. pos+n with blocks; under pool
            # pressure SHRINK the draft instead of preempting — a
            # short draft is still correct, just less speculative
            n = self.cache.append_span(slot, pos + 1, int(prop.size))
            m = 0
            while m < n:
                pw = self.cache.prepare_write(slot, pos + 1 + m)
                if pw is False:
                    break
                if pw is not None:
                    self._copy_block(*pw, req=req)
                m += 1
            if m < int(prop.size):
                # return the blocks the shrunken tail had grabbed
                self.cache.rewind(slot, pos + 1 + m)
            if m <= 0:
                continue
            drafts[slot, :m] = prop[:m]
            dlens[slot] = m
            any_draft = True
        if not any_draft:
            return None, None
        return drafts, dlens

    def _evict(self, slot: int):
        if _fl._ENABLED:
            req = self._slot_req[slot]
            _fl.record("sched", "serving.evict", slot=slot,
                       request=None if req is None else req.id)
        self.cache.free_slot(slot)
        self._active[slot] = False
        self._pos[slot] = 0
        self._temps[slot] = 0.0
        self._top_ks[slot] = 0
        self._top_ps[slot] = 0.0
        self._prefilling[slot] = False
        self._prefill_pos[slot] = 0
        self._warm[slot] = False
        self._adapter_ids[slot] = 0
        self._slot_req[slot] = None
        self._slot_admit[slot] = -1
        self._left[slot] = 0
        # a tick in flight that held rows of this slot alone has
        # nothing left to hand over
        while self._flights and not (
                self._flights[0].admit == self._slot_admit).any():
            self._flights.popleft()

    def _finish(self, slot: int, reason: str, status: str = _OK):
        req = self._slot_req[slot]
        self._evict(slot)
        self._terminate(req, reason, status)

    def _terminate(self, req: Request, reason: str, status: str):
        """Terminal transition shared by running (post-evict) and
        still-queued requests."""
        if req._adapter_held:
            # refcount released here (not at evict): a preempted
            # request still holds its adapter through the requeue
            self.lora.release(req.adapter)
            req._adapter_held = False
        req.state = _FINISHED
        req.finish_reason = reason
        req.status = status
        req.t_finish = time.perf_counter()
        req._tev("finish", t=req.t_finish, reason=reason, status=status)
        self.finished.append(req)
        if telemetry._ENABLED:
            telemetry.inc("serving_requests_finished")
            telemetry.inc("serving_requests_total", status=status)
            n = len(req.output_tokens)
            if req.t_first_token is not None \
                    and req.t_last_token is not None and n > 1:
                tpot = (req.t_last_token - req.t_first_token) / (n - 1)
                spec = "on" if self._spec is not None else "off"
                if req.tenant is not None:
                    # tenant-labeled INSTEAD of unlabeled (a global
                    # Objective sums every child, so double-counting
                    # would skew fleet-level SLO arithmetic)
                    _lora._note_tpot(self._tenant_label(req.tenant),
                                     tpot, spec)
                else:
                    telemetry.observe("serving_tpot_seconds", tpot,
                                      spec=spec)
            if req.tenant is not None:
                lbl = self._tenant_label(req.tenant)
                _lora._note_finish(lbl, status)
                _lora._note_tokens(lbl, len(req.output_tokens))
        if _gp._ENABLED and req.tenant is not None:
            # same count, same label as serving_tenant_tokens_total —
            # the usage meter stays conservation-equal to the
            # tenant-labeled counter by construction
            _gp.note_tenant_tokens(self._tenant_label(req.tenant),
                                   len(req.output_tokens))
        if _fl._ENABLED:
            _fl.record("sched", "serving.finish", request=req.id,
                       reason=reason, status=status)
        self._retain_trace(req)

    def _retain_trace(self, req: Request):
        """Apply the sampling knob at the terminal transition: keep the
        assembled trace for sampled / slow requests, drop the raw
        timeline either way so finished requests stay O(1)."""
        if req._trace is None:
            return
        keep = self._trace_every > 0 \
            and req._trace_seq % self._trace_every == 0
        if not keep and self._trace_slow_s is not None:
            lat = (req.t_finish or 0.0) - req.t_submit
            ttft = req.ttft
            keep = lat > self._trace_slow_s or \
                (ttft is not None and ttft > self._trace_slow_s)
        if keep:
            self._traces[req.id] = self._assemble_trace(req)
            while len(self._traces) > self._trace_capacity:
                self._traces.popitem(last=False)
        req._trace = None
        req._decode_windows = None

    def _expire_deadlines(self):
        """Fail every request (queued or running) past its deadline
        with status ``timed_out``. Runs at the top of each tick, so a
        queued request cannot be admitted after it already expired."""
        now = time.perf_counter()
        for slot in range(self.batch_slots):
            req = self._slot_req[slot]
            if req is not None and req.t_deadline is not None \
                    and now > req.t_deadline:
                self._finish(slot, "timeout", status=_TIMED_OUT)
        if any(r.t_deadline is not None for r in self.queue):
            keep: deque = deque()
            while self.queue:
                req = self.queue.popleft()
                if req.t_deadline is not None and now > req.t_deadline:
                    self._terminate(req, "timeout", _TIMED_OUT)
                else:
                    keep.append(req)
            self.queue = keep

    # -- the tick -----------------------------------------------------------

    def step(self) -> int:
        """One tick: admit, queue the next decode tick on the device,
        hand over the tokens of the one before it, evict. Returns the
        tokens handed over (on ticks that only ran prefill chunks, the
        chunk tokens processed — drive loops must see prefill-only
        ticks as progress, not idleness).

        The server keeps ONE decode tick queued ahead: while tick n
        runs, `step()` admits, allocates blocks, uploads and launches
        tick n+1, and only then blocks on tick n's tokens, so the
        device never waits for the host between ticks. Everything one
        tick hands the next (page pools, last logits, PRNG rows) is a
        device array chained from output to input, and tick n's token
        is sampled inside tick n's program; what the host uploads for
        n+1 (tables, positions, sampling rows, the mask of rows)
        depends on n's tokens only through an `eos_id` finish. So:

        - a slot whose last token by `max_new_tokens` is already in
          flight is left out of the next launch; it stays `_active`
          until that token has been handed over;
        - a request that an `eos_id`, `cancel()`, a deadline or a
          preemption ends while a later row of it is in flight has
          that row DROPPED: computed, never appended to
          `output_tokens`, never counted. `output_tokens` grows only
          by tokens that count;
        - a prompt admitted beside running requests has its prefill
          queued behind the tick in flight and joins the launch of
          the same `step()`; its first token comes out of the next
          `step()`, at the same place on the device's queue;
        - from an idle server the first `step()` launches two ticks
          and hands over the first;
        - with `speculative=` the drafts are proposed from the tokens
          just handed over, so there the server reads before it
          launches and nothing is queued ahead.

        In a profiler trace one call is one `mx.serve_tick` span,
        early returns included, holding `mx.serve_admit` (with one
        `mx.serve_prefill` a prompt), `mx.serve_blocks`,
        `mx.serve_decode` (`mx.serve_dispatch`: the uploads and launch
        of the tick being queued, counts `tick` (the ordinal of the
        launch), `active`, `ahead` 1 when another was in flight and
        `late` 1 when that other's tokens were already there; then
        `mx.serve_wait`: the read of the tick handed over, count
        `tick` the ordinal ITS launch carried) and `mx.serve_emit`
        (the counts of the tick read). From an idle server a first
        `mx.serve_blocks` + `mx.serve_dispatch` precede those; a
        `step()` with nothing left to launch has no `mx.serve_blocks`
        and no `mx.serve_dispatch`.

        With one tick queued ahead a wait carries the `tick` that the
        dispatch of the `step()` before it carried; with
        `speculative=` both carry the same one. `late` says the
        tick in flight had ended before the host launched the next
        (`stats()["ticks_late"]` sums it, with or without a profiler
        session): the device had run out of decode work and stood
        idle, unless a prompt this `step()` admitted kept it busy —
        a `step()` that prefilled reads late too when the tick in
        flight ended inside the prefill. The flag is read before the
        launch's own uploads, so a launch that itself stalled there
        is not counted."""
        with telemetry.span("serve_tick"):
            return self._tick()

    def _tick(self) -> int:
        t_tick = time.perf_counter()
        done0 = len(self.finished)
        self._expire_deadlines()
        if _ft._ACTIVE and _ft.fire("serving.stall") is not None:
            # injected wedged tick: no admission, no decode — the
            # deterministic stimulus for the watchdog tests
            self._note_progress(0, done0)
            self._update_gauges()
            return 0
        with telemetry.phase("serve_admit"):
            admitted = self._admit()
        prefilled = 0
        if self.prefill_chunk_tokens is not None \
                and self._prefilling.any():
            prefilled = self._prefill_tick()
        plan = self._prepare()
        if plan is not None and not self._flights \
                and self._spec is None:
            # nothing in flight (an idle server): the tick to hand
            # over goes first, the one queued behind it second.
            # Speculation needs a tick's tokens to build the next, so
            # there nothing is queued ahead
            self._dispatch(*plan)
            plan = self._prepare()
        if plan is None and not self._flights:
            self._note_progress(admitted + prefilled, done0)
            self._update_gauges()
            return prefilled
        tick_counts = {}
        with telemetry.phase("serve_decode"):
            # the uploads + the launch of the tick being queued, then
            # the host blocked on the tick before it
            if plan is not None:
                self._dispatch(*plan)
            with telemetry.span("serve_wait", tick=self._flights[0].seq):
                flight = self._flights.popleft()
                if flight.n_acc is not None:
                    wtok_np = np.asarray(flight.tok)   # (B, k+1)
                    n_acc_np = np.asarray(flight.n_acc)
                else:
                    wtok_np = np.asarray(flight.tok).reshape(-1, 1)
                    n_acc_np = np.zeros(self.batch_slots, np.int32)
                # the decoder's counts of this tick (an expert layer's
                # pairs and touched experts), read at the same sync as
                # the tokens; those of the prefills queued before it
                # are already past
                if flight.counts:
                    tick_counts = dict(zip(
                        self.decoder.counts,
                        (int(c) for c in np.asarray(flight.counts[0]))))
                if flight.prefill_counts:
                    done = np.sum([np.asarray(c) for c in
                                   flight.prefill_counts], axis=0)
                    tick_counts.update(
                        ("prefill_" + n, int(c)) for n, c in
                        zip(self.decoder.counts, done))
        for name, n in tick_counts.items():
            self.decoder_counts[name] = \
                self.decoder_counts.get(name, 0) + n
        with telemetry.span("serve_emit", **tick_counts):
            return self._emit(flight, wtok_np, n_acc_np, t_tick,
                              admitted, done0)

    def _prepare(self):
        """The rows of the next tick to launch, with their blocks (and
        drafts): `(send, drafts, dlens)`, or None when no slot has a
        token left to ask the device for."""
        send = self._active & (self._left > 0)
        if not send.any():
            return None
        drafts = dlens = None
        with telemetry.span("serve_blocks"):
            if self.cache.paged:    # no pool: a token costs no block
                self._ensure_blocks(send)
            if self._spec is not None:
                drafts, dlens = self._propose_drafts()
        send &= self._active        # less the slots a preemption emptied
        return (send, drafts, dlens) if send.any() else None

    def _dispatch(self, send, drafts, dlens):
        """Upload and launch one decode (or verify) tick for the rows
        `send` and put it on `_flights`. Nothing here waits for the
        device: the positions advance now, not when the tokens are
        read."""
        ahead = int(bool(self._flights))
        self.ticks_ahead += ahead
        # the tick in flight has its tokens already: the device had
        # finished all it had been given before the host launched more.
        # Read before this launch's own uploads: one that stalls there
        # is not counted
        late = int(ahead and self._flights[0].tok.is_ready())
        self.ticks_late += late
        seq = self._launches
        self._launches += 1
        counts = ()
        n_acc = None
        with telemetry.span("serve_dispatch", tick=seq,
                            active=int(send.sum()), ahead=ahead,
                            late=late, **self._note_context(send)):
            args = (self._params, self.cache.pages, self._tables(),
                    _upload(self._pos), self._last_logits, self._keys,
                    _upload(self._temps), _upload(self._top_ks),
                    _upload(self._top_ps), jnp.asarray(send))
            if drafts is not None:
                (self.cache.pages, tok, n_acc, self._last_logits,
                 self._keys) = self.programs["verify"](
                    *args, jnp.asarray(drafts), jnp.asarray(dlens),
                    *self._lora_args(self._adapter_ids))
            else:
                (self.cache.pages, tok, self._last_logits,
                 self._keys, *counts) = self.programs["decode"](
                    *args, *self._lora_args(self._adapter_ids))
            warm = send & self._warm
            self._flights.append(_Flight(
                seq, tok, n_acc, counts, self._prefill_counts,
                np.where(send, self._slot_admit, -2), warm, dlens))
            self._prefill_counts = []
            self._pos[send] += 1
            self._left[send & ~warm] -= 1
            for slot in np.flatnonzero(warm):
                # the warm tick's one sample is the re-fed prompt
                # token, not output, and consumed one PRNG split:
                # re-seed behind it so the sampled stream matches the
                # cold (real-prefill) path tick for tick
                self._seed_key(slot, self._slot_req[slot])
            self._warm[warm] = False

    def _note_context(self, send) -> dict:
        """Count the cached positions the rows `send` of a decode tick
        attend. With sliding-window layers the two sums also go on
        `mx.serve_dispatch` (`ctx`, `window_ctx`): what the full and
        the sliding layers' sweeps read, for their roofline; with
        recurrent layers `ctx` and `ssm_rows`, the rows whose state the
        tick steps."""
        vl = self._pos[send].astype(np.int64) + 1
        ctx = int(vl.sum())
        self.context_tokens += ctx
        if self._recurrent:
            return {"ctx": ctx, "ssm_rows": int(send.sum())}
        if self._latent:
            return {"ctx": ctx}
        if self.decoder.window is None:
            return {}
        wctx = int(np.minimum(vl, self.decoder.window).sum())
        self.window_context_tokens += wctx
        return {"ctx": ctx, "window_ctx": wctx}

    def _emit(self, flight, wtok_np, n_acc_np, t_tick: float,
              admitted: int, done0: int) -> int:
        """Hand over the tick just read: each slot its tokens, finish
        and evict, feed the forecaster, the watchdog and the gauges.
        The next tick is already running on the device. A row whose
        slot has changed hands since the launch (an `eos_id` finish, a
        cancel, a deadline, a preemption) is dropped here."""
        now = time.perf_counter()
        emitted = 0
        net_new = 0
        dlens = flight.dlens
        tenant_tokens = {} if self._wfs is not None else None
        for slot in np.flatnonzero(flight.admit == self._slot_admit):
            req = self._slot_req[slot]
            warm = bool(flight.warm[slot])
            acc = int(n_acc_np[slot])
            proposed = int(dlens[slot]) if dlens is not None else 0
            finished = None
            for j in range(1 + acc):
                if warm and j == 0:
                    # warm re-feed of the last prompt token: its k/v
                    # write is the whole point; the token itself is
                    # NOT output
                    continue
                t = int(wtok_np[slot, j])
                req.output_tokens.append(t)
                emitted += 1
                if tenant_tokens is not None:
                    tt = req.tenant or ""
                    tenant_tokens[tt] = tenant_tokens.get(tt, 0) + 1
                # tokens regenerated after a preemption were already
                # counted before the preemption — only net-new tokens
                # feed the throughput counters and tokens/sec window
                if len(req.output_tokens) > req.tokens_counted:
                    req.tokens_counted = len(req.output_tokens)
                    net_new += 1
                if self._trace_on:
                    req._note_decode(now)
                else:
                    req.t_last_token = now
                if req.t_first_token is None:
                    req.t_first_token = now
                    if req.tenant is not None:
                        _lora._note_ttft(
                            self._tenant_label(req.tenant), req.ttft)
                    elif telemetry._ENABLED and req.ttft is not None:
                        telemetry.observe("serving_ttft_seconds",
                                          req.ttft)
                if req.eos_id >= 0 and t == req.eos_id:
                    finished = "eos"
                    break
                if len(req.output_tokens) >= req.max_new_tokens:
                    finished = "length"
                    break
            if proposed:
                self.spec_tokens_accepted += acc
                self.spec_tokens_rejected += proposed - acc
                self._spec_window.append((acc, proposed))
                if telemetry._ENABLED:
                    telemetry.inc("serving_spec_tokens_accepted_total",
                                  acc)
                    telemetry.inc("serving_spec_tokens_rejected_total",
                                  proposed - acc)
            if finished is not None:
                self._finish(slot, finished)
                continue
            if proposed:
                # the launch advanced over the sampled token alone:
                # the accepted drafts follow; the rejected suffix is
                # rewound by NOT advancing over it — return the blocks
                # the unconsumed tail had grabbed (stale rows are
                # masked by valid lengths and overwritten later)
                self._pos[slot] += acc
                self._left[slot] -= acc
                self.cache.rewind(slot, int(self._pos[slot]))
        if tenant_tokens:
            # decode tokens are weighted-fair service too: a tenant
            # hogging slots pays in admission priority next round
            for tt, n in tenant_tokens.items():
                self._wfs.charge(tt, n)
        self.ticks += 1
        self.tokens_generated += net_new
        self._tok_window.append((now, net_new))
        if self.cache.paged:
            self._forecaster.add(now, self.cache.num_free_blocks)
        if self.tier is not None \
                and self.tier.spill_exhaust_s is not None:
            # the forecaster's exhaust signal is the spill TRIGGER:
            # under forecast pressure, demote parked prefixes ahead of
            # the preemption cliff (spill-ahead)
            eta = self._forecaster.exhaust_in_s()
            if eta is not None and eta < self.tier.spill_exhaust_s:
                self.tier.spill_parked(self.tier.spill_batch)
        if _gp._ENABLED:
            _gp.note_tokens("serve", net_new,
                            len(self._last_logits.devices()))
            _gp.publish()
        if telemetry._ENABLED:
            telemetry.inc("serving_tokens_total", net_new)
            if self._kernel_paged:
                # the in-kernel paged path served this tick: credit the
                # HBM bytes the gather fallback would have materialized
                telemetry.inc("serving_gather_bytes_avoided_total",
                              self._gather_bytes_per_tick)
            telemetry.observe("serving_tick_seconds", now - t_tick)
        self._note_progress(admitted + emitted, done0)
        self._update_gauges()
        return emitted

    def _note_progress(self, progress: int, done_before: int):
        """Watchdog bookkeeping: `progress` units this tick (tokens
        emitted + admissions + requests finished). Zero progress with
        work still pending, `watchdog_ticks` ticks in a row, means the
        decode path is wedged — raise so a supervisor restarts the
        server instead of the loop spinning forever."""
        progress += len(self.finished) - done_before
        if progress > 0 or not (self.queue or self._active.any()
                                or self._prefilling.any()):
            self._stall_ticks = 0
            self._stalled = False
            return
        self._stall_ticks += 1
        if self._stall_ticks >= self.watchdog_ticks:
            stalled, self._stall_ticks = self._stall_ticks, 0
            self._stalled = True
            if telemetry._ENABLED:
                telemetry.inc("serving_watchdog_stalls_total")
            if _fl._ENABLED:
                # record the stall as the ring's final event, THEN dump:
                # the tail of the JSONL is the cause of death
                _fl.record("stall", "serving.watchdog", ticks=stalled,
                           queued=len(self.queue),
                           active=int(self._active.sum()))
                _fl.dump(reason="serving_stall")
            raise ServerStalledError(
                f"serving watchdog: {stalled} consecutive ticks without "
                f"progress ({len(self.queue)} queued, "
                f"{int(self._active.sum())} active) — decode path is "
                "stalled; restart the server")

    def _update_gauges(self):
        if not telemetry._ENABLED:
            return
        telemetry.set_gauge("serving_queue_depth", len(self.queue))
        telemetry.set_gauge("serving_active_slots",
                            int(self._active.sum()))
        telemetry.set_gauge("serving_kv_blocks_free",
                            self.cache.num_free_blocks)
        telemetry.set_gauge("serving_kv_fragmentation",
                            self.cache.fragmentation())
        telemetry.set_gauge("serving_kv_parked_blocks",
                            self.cache.parked_blocks())
        eta = self._forecaster.exhaust_in_s()
        if eta is not None:
            telemetry.set_gauge("serving_kv_exhaust_in_s", eta)
        if self.tier is not None:
            telemetry.set_gauge("serving_tier_host_blocks",
                                self.tier.host_blocks())
            for t, v in self.tier.hit_rates().items():
                telemetry.set_gauge("serving_tier_hit_rate", v, tier=t)
        if self._wfs is not None:
            counts = {}
            for r in self.queue:
                if r.tenant:
                    lbl = self._tenant_label(r.tenant)
                    q, a = counts.get(lbl, (0, 0))
                    counts[lbl] = (q + 1, a)
            for r in self._slot_req:
                if r is not None and r.tenant:
                    lbl = self._tenant_label(r.tenant)
                    q, a = counts.get(lbl, (0, 0))
                    counts[lbl] = (q, a + 1)
            if counts:
                _lora._note_tenant_gauges(counts)
        if self._spec is not None and self._spec_window:
            prop = sum(p for _, p in self._spec_window)
            if prop:
                acc = sum(a for a, _ in self._spec_window)
                telemetry.set_gauge("serving_draft_accept_rate",
                                    acc / prop)
        if len(self._tok_window) >= 2:
            t0 = self._tok_window[0][0]
            dt = self._tok_window[-1][0] - t0
            if dt > 0:
                n = sum(k for _, k in list(self._tok_window)[1:])
                chips = len(self._last_logits.devices())
                telemetry.set_gauge("serving_tokens_per_sec_per_chip",
                                    n / dt / chips)

    def run(self, max_ticks: Optional[int] = None) -> List[Request]:
        """Step until queue and slots drain (or max_ticks). Returns
        the requests finished during this call's ticks."""
        done_before = len(self.finished)
        ticks = 0
        try:
            while self.queue or self._active.any() \
                or self._prefilling.any():
                self.step()
                ticks += 1
                if max_ticks is not None and ticks >= max_ticks:
                    break
        except ServerStalledError:
            raise   # flight ring already dumped at the stall site
        except BaseException as e:
            if _fl._ENABLED:
                _fl.record("exception", "serving.run",
                           error=repr(e)[:200], tick=self.ticks)
                _fl.dump(reason="serving_exception")
            raise
        return self.finished[done_before:]

    def cancel(self, request_id: int) -> bool:
        """Cancel one queued or running request: free its slot and KV
        blocks (prefix-cache refcounts respected — shared blocks stay
        registered for other holders) and finish it with status
        ``cancelled``. True when the request was found live; False for
        unknown / already-finished ids. This is the hedging loser's
        exit and the operator's per-request kill switch."""
        for slot in range(self.batch_slots):
            req = self._slot_req[slot]
            if req is not None and req.id == request_id:
                self._finish(slot, "cancel", status=_CANCELLED)
                self._update_gauges()
                return True
        for req in self.queue:
            if req.id == request_id:
                self.queue.remove(req)
                self._terminate(req, "cancel", _CANCELLED)
                self._update_gauges()
                return True
        return False

    # -- warm-up -------------------------------------------------------------

    def warmup(self) -> float:
        """Compile every serving executable ahead of traffic: one tiny
        request through prefill + decode, plus the tier's
        spill/restore pair when tiering is on. This is THE standby
        warm-up — fleet workers run it before their first heartbeat,
        and the autoscaler's provisioner runs it before a spawned
        replica enters rotation, so scale-out adds capacity with zero
        compile stall. The compile wall time lands in the goodput
        ledger's *compile* category (via the executable build hooks),
        not productive time. Returns the wall seconds spent."""
        t0 = time.perf_counter()
        req = self.submit([1, 2], 2)
        while req.state != "finished":
            self.step()
        if self.tier is not None:
            self.warm_tier()
        return time.perf_counter() - t0

    # -- KV tier hierarchy ---------------------------------------------------

    def warm_tier(self):
        """Compile the spill/restore executable pair ahead of traffic
        (one round-trip through scratch block 0 — content unchanged).
        Fleet workers call this at warmup so tier adoption on a
        serving replica costs ZERO extra compiles."""
        if self.tier is None:
            return
        bundle = self.programs["spill_block"](
            self.cache.pages, jnp.asarray(0, jnp.int32))
        self.cache.pages = self.programs["restore_block"](
            self.cache.pages, bundle, jnp.asarray(0, jnp.int32))

    def export_prefix(self, prompt_ids) -> Optional[str]:
        """Serialize the resident KV chain covering `prompt_ids` to
        the wire format (prefill→decode block streaming: the payload a
        decode replica adopts via :meth:`adopt_wire_blocks`). Returns
        None when tiering is off or nothing of the prefix is
        resident."""
        if self.tier is None:
            return None
        if isinstance(prompt_ids, NDArray):
            prompt_ids = prompt_ids.asnumpy()
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        return self.tier.export_chain(prompt)

    def adopt_wire_blocks(self, wire: str) -> int:
        """Adopt streamed KV blocks (digest-verified) into the host
        tier; the next matching admit restores them through the
        restore executable + alloc_shared. Returns blocks adopted."""
        if self.tier is None or not wire:
            return 0
        return self.tier.adopt_wire(wire)

    def persist_prefixes(self) -> int:
        """Write the resident prefix chains to the disk store (no-op
        without ``prefix_store_dir``). Also called from
        :meth:`begin_drain` and :meth:`shutdown`, so rolling restarts
        come back warm."""
        if self.tier is None:
            return 0
        return self.tier.persist()

    # -- graceful teardown --------------------------------------------------

    def begin_drain(self):
        """Flip to draining WITHOUT stepping: submit() starts raising
        and :meth:`health` reports not-ready, but already-accepted work
        keeps running through the caller's own step()/run() loop. The
        non-blocking half of :meth:`drain` — a fleet router uses it to
        stop routing at a replica while it finishes in-flight work."""
        self._draining = True
        self.persist_prefixes()

    def end_drain(self):
        """Reopen admission after :meth:`begin_drain` (a cancelled
        rolling restart). Raises if the server is already shut down."""
        if self._shutdown:
            raise RuntimeError("cannot end_drain a shut-down server")
        self._draining = False

    def drain(self, max_ticks: Optional[int] = None,
              deadline_s: Optional[float] = None) -> List[Request]:
        """Stop admitting NEW submissions (submit() now raises) and run
        the already-accepted work to completion, bounded by `max_ticks`
        and/or `deadline_s`. Returns the requests finished during the
        drain; anything still unfinished at the bound is left for
        :meth:`shutdown` to cancel."""
        self._draining = True
        done_before = len(self.finished)
        t0 = time.perf_counter()
        ticks = 0
        while self.queue or self._active.any() \
                or self._prefilling.any():
            if max_ticks is not None and ticks >= max_ticks:
                break
            if deadline_s is not None \
                    and time.perf_counter() - t0 > deadline_s:
                break
            self.step()
            ticks += 1
        return self.finished[done_before:]

    def shutdown(self, drain: bool = True,
                 max_ticks: Optional[int] = None,
                 deadline_s: Optional[float] = None):
        """Graceful shutdown: optionally drain in-flight work, then
        cancel whatever remains with status ``rejected`` and refuse
        all further submissions. Idempotent."""
        if self._shutdown:
            return
        if drain:
            self.drain(max_ticks=max_ticks, deadline_s=deadline_s)
        for slot in range(self.batch_slots):
            if self._active[slot] or self._prefilling[slot]:
                self._finish(slot, "shutdown", status=_REJECTED)
        while self.queue:
            self._terminate(self.queue.popleft(), "shutdown", _REJECTED)
        # warm-restart path: the evicted slots' prefixes just parked,
        # so this persist captures the full resident chain set
        self.persist_prefixes()
        self._shutdown = True
        self._update_gauges()

    # -- introspection ------------------------------------------------------

    def health(self):
        """(ok, reason) for the /healthz probe (telemetry registers
        this at construction): 503-worthy while the watchdog has
        declared a stall, a drain has stopped admission, or the server
        is shut down."""
        if self._stalled:
            return False, ("stalled: watchdog declared the decode path "
                           "wedged — restart the server")
        if self._shutdown:
            return False, "shutdown: server no longer accepts work"
        if self._draining:
            return False, "draining: admission stopped"
        return True, "ok"

    def health_detail(self) -> dict:
        """Structured readiness detail for the /healthz JSON body (and
        the fleet heartbeat): everything a router needs to score this
        replica in ONE probe — readiness + why, drain state, queue ages,
        blocks free, load, and the admission geometry."""
        ok, reason = self.health()
        now = time.perf_counter()
        ages = [now - r.t_submit for r in self.queue]
        # prefill work not yet pushed through an executable: queued
        # prompts + the unprefilled remainder of in-prefill slots — a
        # budget-aware router steers long-prompt traffic away from
        # replicas already paying chunked-prefill ticks
        backlog = sum(len(r.prompt) for r in self.queue)
        for i in range(self.batch_slots):
            if self._prefilling[i]:
                backlog += len(self._slot_req[i].prompt) \
                    - int(self._prefill_pos[i])
        out = {"ok": ok, "reason": reason,
               "prefill_backlog_tokens": int(backlog),
               "prefill_chunk_tokens": self.prefill_chunk_tokens or 0,
               "speculative": self._spec is not None,
               "draining": self._draining,
               "shutdown": self._shutdown,
               "stalled": self._stalled,
               "queue_age_p50_s":
                   float(np.percentile(ages, 50)) if ages else 0.0,
               "queue_age_p95_s":
                   float(np.percentile(ages, 95)) if ages else 0.0,
               "blocks_free": self.cache.num_free_blocks,
               "kv_fragmentation": self.cache.fragmentation(),
               "exhaust_in_s": self._forecaster.exhaust_in_s(),
               "queued": len(self.queue),
               "active": int(self._active.sum()),
               "slots": self.batch_slots,
               "block_size": self.block_size,
               "max_prompt_len": self.max_prompt_len,
               "max_len": self.max_len,
               "tiering": self.tier is not None}
        if self.tier is not None:
            out["tier_host_blocks"] = self.tier.host_blocks()
        if self.lora is not None:
            # adapter residency: the fleet router routes adapter
            # traffic toward replicas that already hold the adapter
            out["adapters"] = self.lora.loaded()
            out["adapter_free_rows"] = self.lora.free_rows()
        return out

    def _assemble_trace(self, req: Request) -> dict:
        """The span timeline + derived latency breakdown for one traced
        request (the per-request view serving comparisons report)."""
        events = list(req._trace or [])
        windows = req._decode_windows or []
        dec_s = 0.0
        gaps = 0
        for w in windows:
            if w["t0"] is None:
                continue
            events.append({"name": "decode", "t": w["t0"],
                           "dur_s": w["t1"] - w["t0"], "tokens": w["n"]})
            dec_s += w["t1"] - w["t0"]
            gaps += max(0, w["n"] - 1)
        events.sort(key=lambda e: e["t"])
        queue_wait = None if req.t_admit is None \
            else req.t_admit - req.t_submit
        if queue_wait is not None:
            for ev in events:
                if ev["name"] == "queued":
                    ev["dur_s"] = queue_wait
                    break
        # TPOT from within-window time only, so preemption gaps and
        # requeue waits don't inflate the per-token decode latency
        tpot = dec_s / gaps if gaps > 0 else None
        latency = None if req.t_finish is None \
            else req.t_finish - req.t_submit
        return {"request_id": req.id, "state": req.state,
                "status": req.status, "finish_reason": req.finish_reason,
                "trace_ctx": req.trace_ctx,
                "events": events,
                "queue_wait_s": queue_wait, "ttft_s": req.ttft,
                "tpot_s": tpot, "latency_s": latency,
                "decode_tokens": len(req.output_tokens),
                "preemptions": req.preemptions,
                "prefix_tokens_shared": req.prefix_tokens_shared,
                "cow_copies": req.cow_copies}

    def trace(self, request_id: int) -> Optional[dict]:
        """The retained (or still-live) span timeline of one request:
        events (queued/admit/prefill/decode windows/preempt/cow/finish,
        perf_counter timestamps, `dur_s` on timed spans) plus derived
        queue_wait_s / ttft_s / tpot_s / latency_s / preemptions /
        prefix_tokens_shared / cow_copies. None when the request was
        never traced or its trace was sampled out."""
        stored = self._traces.get(request_id)
        if stored is not None:
            return stored
        for req in list(self.queue) + [r for r in self._slot_req
                                       if r is not None]:
            if req.id == request_id and req._trace is not None:
                return self._assemble_trace(req)
        return None

    def request_traces(self) -> List[dict]:
        """Every retained trace plus the live (running/queued) ones —
        the source `telemetry.export_chrome_trace` merges under its
        request-span pid."""
        out = list(self._traces.values())
        for req in [r for r in self._slot_req if r is not None] \
                + list(self.queue):
            if req._trace is not None:
                out.append(self._assemble_trace(req))
        return out

    def compile_stats(self) -> dict:
        # in chunked mode the windowed program IS the prefill path, so
        # the headline prefill counters point at it (the one-shot
        # program exists but is never dispatched)
        p = self.programs["prefill_chunk"] \
            if self.prefill_chunk_tokens is not None \
            else self.programs["prefill"]
        d = self.programs["decode"]
        c = self.programs["copy_block"]
        out = {"prefill_compiles": p.compiles, "prefill_calls": p.calls,
               "decode_compiles": d.compiles, "decode_calls": d.calls,
               "copy_compiles": c.compiles, "copy_calls": c.calls}
        if self.decoder.window is not None:
            kv = self.cache
            out.update(
                window_blocks_used=kv.window_blocks_used,
                window_blocks_capacity=kv.window_blocks_capacity,
                global_blocks_used=kv.global_blocks_used,
                global_blocks_capacity=kv.global_blocks_capacity,
                context_tokens=self.context_tokens,
                window_context_tokens=self.window_context_tokens)
        if self._recurrent or self._latent:
            out.update(context_tokens=self.context_tokens)
        out.update(self.decoder_counts)
        v = self.programs.get("verify")
        if v is not None:
            out["verify_compiles"] = v.compiles
            out["verify_calls"] = v.calls
        s = self.programs.get("spill_block")
        r = self.programs.get("restore_block")
        if s is not None:
            out["spill_compiles"] = s.compiles
            out["spill_calls"] = s.calls
        if r is not None:
            out["restore_compiles"] = r.compiles
            out["restore_calls"] = r.calls
        return out

    def stats(self) -> dict:
        by_status = {s: 0 for s in (_OK, _TIMED_OUT, _PREEMPTED,
                                    _REJECTED, _CANCELLED)}
        for r in self.finished:
            by_status[r.status or _OK] += 1
        # queue AGE (not just depth): p50/p95 of how long the queued
        # requests have been waiting — a router can tell a deep-but-
        # moving queue from a stuck one
        now = time.perf_counter()
        ages = [now - r.t_submit for r in self.queue]
        age_p50 = float(np.percentile(ages, 50)) if ages else 0.0
        age_p95 = float(np.percentile(ages, 95)) if ages else 0.0
        spec_prop = self.spec_tokens_accepted + self.spec_tokens_rejected
        extra = {}
        if self.lora is not None:
            extra["adapters"] = self.lora.stats()
        if self._wfs is not None:
            extra["tenant_passes"] = self._wfs.snapshot()
        if self._recurrent:
            extra.update(state_pool_bytes=self.cache.state_pool_bytes,
                         state_slots_used=self.cache.state_slots_used)
        if self._latent:
            extra.update(
                latent_pool_bytes=self.cache.latent_pool_bytes,
                latent_pool_tokens=self.cache.latent_pool_tokens)
        return {"ticks": self.ticks,
                "ticks_ahead": self.ticks_ahead,
                "ticks_late": self.ticks_late,
                **extra,
                "queue_age_p50_s": age_p50,
                "queue_age_p95_s": age_p95,
                "tokens_generated": self.tokens_generated,
                "queued": len(self.queue),
                "active": int(self._active.sum()),
                "prefilling": int(self._prefilling.sum()),
                "prefills_skipped": self.prefills_skipped,
                "preemptions": self.preemptions,
                "spill_preemptions": self.spill_preemptions,
                "spec_tokens_accepted": self.spec_tokens_accepted,
                "spec_tokens_rejected": self.spec_tokens_rejected,
                "draft_accept_rate":
                    self.spec_tokens_accepted / spec_prop
                    if spec_prop else 0.0,
                "finished": len(self.finished),
                "status_counts": by_status,
                "draining": self._draining,
                "shutdown": self._shutdown,
                **{f"kv_{k}": v for k, v in self.cache.stats().items()},
                **self.compile_stats()}
