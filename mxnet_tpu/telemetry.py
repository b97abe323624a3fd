"""Unified training telemetry: one process-wide metrics runtime for the
whole stack (SURVEY observability; MLPerf TPU-pod scaling,
arXiv:1909.09756, shows the step-time breakdown — input pipeline vs
compute vs collective — is the prerequisite for every scaling decision;
EQuARX, arXiv:2506.17615, motivates first-class wire-byte accounting
once compressed collectives exist).

Before this module, `profiler.py` (host scopes + resident bytes),
`tracing.py` (compile-cache stats), `monitor.py` (tensor stats) and
`kernels/dispatch.py` (fallback counts) were four disconnected islands
and nothing instrumented the Trainer/KVStore/DataLoader hot paths. Now
they all publish into ONE registry:

- `Counter` / `Gauge` / `Histogram` metric families with Prometheus
  label semantics. Histograms use fixed log2 buckets (power-of-two
  upper bounds) with p50/p95/p99 read-out — O(1) memory per family,
  no reservoir.
- Phase marks: `with telemetry.phase("forward"): ...` resolves into the
  `step_time_breakdown` histogram family (labels: phase = data /
  forward / backward / grad_comm / optimizer / weight_gather) plus a
  chrome-trace host event. Trainer.step, FusedTrainStep, autograd,
  KVStore, the DataLoader and the multi-tensor updater all mark their
  phases; `step_done(samples)` feeds a rolling `samples_per_sec`
  speedometer.
- Spans on the profiler's clock: every `phase(name)` and every
  `span(name, **counts)` is a `jax.profiler.TraceAnnotation` named
  `mx.<name>`, whether telemetry is enabled or not. While a
  `jax.profiler` session is open they land in its `.xplane.pb` on the
  same clock as the device's operations, with the counts as the
  event's stats; with no session open one costs well under a
  microsecond. An open profiler session is their only switch.
- `snapshot()` merges the registry with the pull-based providers:
  `profiler.resident_bytes()`, `kernels.dispatch.fallback_counts()`,
  and `tracing.cache_stats()` (compile counts + seconds, per block).
- Exposition: `to_prometheus()` (text format), `dump_json(path)`,
  `breakdown_table()` (human table), and `export_chrome_trace(path)` —
  one chrome://tracing-loadable JSON merging host phase events and
  host profiler scopes (host clock). Host AND device on one clock is
  the profiler's own trace: the `mx.*` spans above are in it.

Cost contract: the WHOLE layer is disabled by default and near-zero
cost while disabled — every instrumented hot path checks the single
module-level `_ENABLED` flag before doing any dict or string work
(tests/test_telemetry_lint.py enforces the gate; what the disabled
layer costs a step on the chip: no cell measures this, ROADMAP D7).
Enable with `telemetry.enable()` or MXNET_TPU_TELEMETRY=1.
"""
from __future__ import annotations

import json
import math
import os
import statistics
import threading
import time
import weakref
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation as _Annotation

from . import flight as _flight

__all__ = ["enable", "disable", "enabled", "reset",
           "Counter", "Gauge", "Histogram",
           "counter", "gauge", "histogram",
           "inc", "set_gauge", "observe",
           "read_gauge", "remove_series",
           "phase", "span", "mark_phase", "step_done",
           "snapshot", "to_prometheus", "dump_json", "breakdown_table",
           "export_chrome_trace",
           "start_metrics_server", "stop_metrics_server",
           "maybe_start_metrics_server",
           "register_health_source", "unregister_health_source", "health",
           "health_report",
           "register_request_trace_source",
           "register_fleet_trace_source",
           "set_fleet_metrics_provider",
           "publish_snapshot", "aggregate_snapshot",
           "to_prometheus_merged", "registry_delta",
           "publish_step_time", "step_times", "step_time_skew",
           "stragglers",
           "STEP_PHASES", "SERVE_PHASES", "REQUEST_PID",
           "ROUTER_PID", "REPLICA_PID_BASE"]

#: THE flag. Instrumented call sites across the stack guard with
#: `if telemetry._ENABLED:` (one module-attribute load + branch) so the
#: disabled path never touches the registry, builds a label tuple, or
#: formats a string.
_ENABLED = os.environ.get("MXNET_TPU_TELEMETRY", "0") == "1"

#: canonical per-step timeline phases (step_time_breakdown labels)
STEP_PHASES = ("data", "forward", "backward", "grad_comm", "optimizer",
               "weight_gather")

#: per-tick phases of the serving engine (mxnet_tpu/serving/): request
#: admission (incl. the prefill executable), the paged prefill itself,
#: and the shared continuous-batching decode tick. Serving also owns
#: the serving_ttft_seconds / serving_tick_seconds histograms and the
#: serving_queue_depth / serving_active_slots / serving_kv_blocks_free
#: / serving_tokens_per_sec_per_chip gauges.
SERVE_PHASES = ("serve_admit", "serve_prefill", "serve_decode")

_lock = threading.RLock()
_REGISTRY: "OrderedDict[str, _Family]" = OrderedDict()

#: chrome-trace host events ("X" spans); bounded so a long run cannot
#: grow without limit — oldest events drop first
_TRACE_CAP = 200_000
_TRACE_EVENTS: deque = deque(maxlen=_TRACE_CAP)

#: rolling speedometer window: (perf_counter at step end, samples)
_SPEED_WINDOW: deque = deque(maxlen=64)

#: chrome pid layout: host phases / profiler scopes on pid 0, device
#: spans (sync-measured) on pid 1; serving per-request span timelines
#: get their own far-away pid
HOST_PID = 0
DEVICE_PID = 1
REQUEST_PID = 9000
#: fleet pids: the router's own spans and one pid per replica (assigned
#: REPLICA_PID_BASE + index over sorted replica names at export time)
ROUTER_PID = 9500
REPLICA_PID_BASE = 9501

#: weakrefs to objects exposing `health() -> (ok, reason)`; consulted
#: by the /healthz endpoint (InferenceServer registers itself so a
#: watchdog stall or drain flips the probe to 503)
_HEALTH_SOURCES: List[weakref.ref] = []

#: weakrefs to objects exposing `request_traces() -> [trace dict]`;
#: export_chrome_trace merges their span timelines under REQUEST_PID
_REQUEST_TRACE_SOURCES: List[weakref.ref] = []

#: weakrefs to objects exposing `fleet_traces() -> [merged timeline]`
#: (FleetRouter); export_chrome_trace renders them with ROUTER_PID for
#: router-side spans and one pid per replica
_FLEET_TRACE_SOURCES: List[weakref.ref] = []

#: weakref to an object exposing `fleet_prometheus() -> str` (a
#: FleetRouter); when set, /metrics serves the fleet-merged view
_FLEET_METRICS_PROVIDER: Optional[weakref.ref] = None

#: goodput hooks (installed by mxnet_tpu.goodput.enable()): every
#: resolved phase mark feeds the wall-clock ledger, and
#: breakdown_table() appends the ledger's category section. Plain
#: module globals so the not-installed cost is one attribute load +
#: branch — the same contract as _ENABLED.
_goodput_note = None
_goodput_section = None


def enable():
    """Turn telemetry on for this process."""
    global _ENABLED
    _ENABLED = True


def disable():
    global _ENABLED
    _ENABLED = False


def enabled() -> bool:
    return _ENABLED


def reset():
    """Clear every metric, trace event, and the speedometer window.
    Keeps the enabled/disabled state."""
    with _lock:
        _REGISTRY.clear()
        _TRACE_EVENTS.clear()
        _SPEED_WINDOW.clear()


# -- metric model -----------------------------------------------------------

def _label_key(labels: dict) -> Tuple:
    return tuple(sorted(labels.items()))


def _label_suffix(key: Tuple) -> str:
    if not key:
        return ""
    return "{" + ",".join(f"{k}={v}" for k, v in key) + "}"


class _Child:
    __slots__ = ("label_key",)

    def __init__(self, label_key: Tuple):
        self.label_key = label_key


class Counter(_Child):
    """Monotonically increasing value (one label set of a family)."""
    __slots__ = ("value",)

    def __init__(self, label_key=()):
        super().__init__(label_key)
        self.value = 0.0

    def inc(self, value=1.0):
        if value < 0:
            raise ValueError("counters only go up; use a Gauge")
        self.value += value


class Gauge(_Child):
    """Last-write-wins value (one label set of a family)."""
    __slots__ = ("value",)

    def __init__(self, label_key=()):
        super().__init__(label_key)
        self.value = 0.0

    def set(self, value):
        self.value = float(value)

    def inc(self, value=1.0):
        self.value += value

    def dec(self, value=1.0):
        self.value -= value


#: log2 bucket exponent clamp: 2^-30 (~1ns in seconds, ~1 byte) up to
#: 2^50 (~1 PB, ~13 days) covers every quantity we record
_EXP_MIN, _EXP_MAX = -30, 50


class Histogram(_Child):
    """Fixed log2-bucket histogram: bucket e counts observations in
    (2^(e-1), 2^e]. O(#occupied buckets) memory, exact count/sum/min/
    max, and percentile read-out by geometric interpolation inside the
    hit bucket (clamped to the observed min/max)."""
    __slots__ = ("buckets", "count", "sum", "min", "max", "zeros")

    def __init__(self, label_key=()):
        super().__init__(label_key)
        self.buckets: Dict[int, int] = {}
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf
        self.zeros = 0  # observations <= 0 (no log2 bucket)

    def observe(self, value):
        v = float(value)
        self.count += 1
        self.sum += v
        if v < self.min:
            self.min = v
        if v > self.max:
            self.max = v
        if v <= 0.0:
            self.zeros += 1
            return
        # frexp: v = m * 2^e with m in [0.5, 1) -> v in (2^(e-1), 2^e]
        m, e = math.frexp(v)
        if m == 0.5:  # exact power of two belongs to the lower bucket
            e -= 1
        e = min(max(e, _EXP_MIN), _EXP_MAX)
        self.buckets[e] = self.buckets.get(e, 0) + 1

    def percentile(self, q: float) -> float:
        """q in [0, 1]; geometric interpolation within the log2 bucket
        that contains the q-th observation."""
        if self.count == 0:
            return 0.0
        target = q * self.count
        seen = self.zeros
        if target <= seen:
            return max(0.0, self.min)
        for e in sorted(self.buckets):
            n = self.buckets[e]
            if seen + n >= target:
                lo, hi = 2.0 ** (e - 1), 2.0 ** e
                frac = (target - seen) / n
                val = lo * (hi / lo) ** frac
                return min(max(val, self.min), self.max)
            seen += n
        return self.max

    def stats(self) -> dict:
        if self.count == 0:
            return {"count": 0, "sum": 0.0}
        return {"count": self.count,
                "sum": self.sum,
                "min": self.min,
                "max": self.max,
                "mean": self.sum / self.count,
                "p50": self.percentile(0.50),
                "p95": self.percentile(0.95),
                "p99": self.percentile(0.99)}


class _Family:
    """One named metric family holding children per label set."""
    __slots__ = ("name", "kind", "help", "child_cls", "children")

    def __init__(self, name: str, kind: str, child_cls, help: str = ""):
        self.name = name
        self.kind = kind
        self.help = help
        self.child_cls = child_cls
        self.children: "OrderedDict[Tuple, _Child]" = OrderedDict()

    def labels(self, **labels):
        key = _label_key(labels)
        ch = self.children.get(key)
        if ch is None:
            with _lock:
                ch = self.children.get(key)
                if ch is None:
                    ch = self.child_cls(key)
                    self.children[key] = ch
        return ch


def _family(name: str, kind: str, child_cls, help: str = "") -> _Family:
    fam = _REGISTRY.get(name)
    if fam is None:
        with _lock:
            fam = _REGISTRY.get(name)
            if fam is None:
                fam = _Family(name, kind, child_cls, help)
                _REGISTRY[name] = fam
    if fam.kind != kind:
        raise TypeError(f"metric {name!r} already registered as "
                        f"{fam.kind}, not {kind}")
    return fam


def counter(name: str, help: str = "") -> _Family:
    """Get-or-create a counter family; use .labels(**kv).inc(v)."""
    return _family(name, "counter", Counter, help)


def gauge(name: str, help: str = "") -> _Family:
    return _family(name, "gauge", Gauge, help)


def histogram(name: str, help: str = "") -> _Family:
    return _family(name, "histogram", Histogram, help)


# -- fast-path helpers (each one checks _ENABLED first) ---------------------

def inc(name: str, value=1.0, **labels):
    if not _ENABLED:
        return
    counter(name).labels(**labels).inc(value)


def set_gauge(name: str, value, **labels):
    if not _ENABLED:
        return
    gauge(name).labels(**labels).set(value)


def observe(name: str, value, **labels):
    if not _ENABLED:
        return
    histogram(name).labels(**labels).observe(value)


def read_gauge(name: str, default=None, **labels):
    """Read a gauge child's current value WITHOUT creating the family
    or the child (returns `default` when either is absent, or when the
    family is not a gauge). Works regardless of the enabled flag — it
    reads whatever earlier enabled-time writes left behind."""
    fam = _REGISTRY.get(name)
    if fam is None or fam.kind != "gauge":
        return default
    ch = fam.children.get(_label_key(labels))
    return default if ch is None else ch.value


def remove_series(name: str, **labels) -> bool:
    """Drop ONE labeled child from a family (e.g. the
    `router_replica_health{replica=w0}` gauge after w0 goes DEAD) so
    terminal label sets don't linger in /metrics forever. Returns True
    when a child was removed. The family itself stays registered."""
    fam = _REGISTRY.get(name)
    if fam is None:
        return False
    with _lock:
        return fam.children.pop(_label_key(labels), None) is not None


# -- per-step timeline ------------------------------------------------------

def mark_phase(name: str, seconds: float, t0: Optional[float] = None,
               device: bool = False):
    """Record one resolved phase span: observes the
    `step_time_breakdown{phase=name}` histogram (seconds) and appends a
    chrome-trace event (host pid, or the device pid for spans measured
    with a device sync)."""
    if not _ENABLED:
        return
    histogram("step_time_breakdown").labels(phase=name).observe(seconds)
    if _goodput_note is not None:
        _goodput_note(name, seconds, t0)
    if _flight._ENABLED:
        _flight.record("phase", name, dur_s=seconds)
    start = t0 if t0 is not None else time.perf_counter() - seconds
    _TRACE_EVENTS.append({
        "name": name, "ph": "X", "ts": start * 1e6,
        "dur": seconds * 1e6,
        "pid": DEVICE_PID if device else HOST_PID,
        "tid": threading.get_ident() % 1_000_000})


#: every span the program writes into a profiler trace starts so
SPAN_PREFIX = "mx."


def span(name: str, **counts):
    """A span on the profiler's clock and nothing else:
    `jax.profiler.TraceAnnotation("mx." + name, **counts)`. While a
    `jax.profiler` session is open it lands in the `.xplane.pb` beside
    the device's operations, the counts as the event's stats; with no
    session open it costs well under a microsecond. No flag, no host
    timestamp, no registry. For structure that has no histogram: a
    span nested in a `phase` must be a `span`, or the goodput ledger
    would count its seconds twice."""
    return _Annotation(SPAN_PREFIX + name, **counts)


class phase:
    """Phase mark. Always a `span` of the same name (`mx.<name>`, see
    there); while telemetry is enabled it also times the body on the
    host clock and resolves it into the step_time_breakdown histogram
    family + a chrome host event (`mark_phase`). Disabled, it stamps
    no time and touches no registry."""

    __slots__ = ("_name", "_device", "_ann", "_t0")

    def __init__(self, name: str, device: bool = False, **counts):
        self._name = name
        self._device = device
        self._ann = _Annotation(SPAN_PREFIX + name, **counts)
        self._t0 = None

    def __enter__(self):
        self._ann.__enter__()
        if _ENABLED:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        if self._t0 is not None:
            mark_phase(self._name, time.perf_counter() - self._t0,
                       t0=self._t0, device=self._device)
        return False


def record_pipeline_step(num_stages: int, num_microbatches: int,
                         seconds: float, t0: Optional[float] = None,
                         virtual: int = 1,
                         total_ticks: Optional[int] = None):
    """Resolve one pipeline-parallel step into the timeline: splits the
    measured fused-step span into `pipeline_fill` / `pipeline_steady` /
    `pipeline_drain` phases proportionally to the 1F1B tick counts
    (fill = drain = n-1 ticks of M + 2(n-1) total) and sets the
    `pipeline_bubble_ratio` gauge to the schedule's (n-1)/(M+n-1)
    inefficiency — the number a microbatch-count sweep should drive
    down. With interleaved virtual stages (`virtual` >= 2 and the
    schedule's measured `total_ticks`), the bubble is the schedule's
    own (T - 2·M·v)/T — the interleaving win shows up directly in the
    same gauge. XLA fuses the real phases into one executable, so the
    proportional split is the honest host-side attribution."""
    if not _ENABLED:
        return
    n, M, v = int(num_stages), int(num_microbatches), int(virtual)
    if v >= 2 and total_ticks:
        T = int(total_ticks)
        work = 2 * M * v
        bubble = max(0.0, (T - work) / T) if T > 0 else 0.0
        total = T
        fill_ticks = (T - work) / 2.0
    else:
        total = M + 2 * (n - 1)
        bubble = (n - 1) / (M + n - 1) if M + n - 1 > 0 else 0.0
        fill_ticks = float(n - 1)
    if total <= 0 or seconds <= 0:
        return
    fill = seconds * fill_ticks / total
    steady = seconds - 2 * fill
    base = t0 if t0 is not None else time.perf_counter() - seconds
    mark_phase("pipeline_fill", fill, t0=base, device=True)
    mark_phase("pipeline_steady", steady, t0=base + fill, device=True)
    mark_phase("pipeline_drain", fill, t0=base + fill + steady,
               device=True)
    set_gauge("pipeline_bubble_ratio", bubble)
    set_gauge("pipeline_num_stages", n)
    set_gauge("pipeline_num_microbatches", M)
    set_gauge("pipeline_virtual_stages", v)


def step_done(samples: Optional[int] = None, steps: int = 1):
    """Mark `steps` optimizer steps complete (default one). Feeds
    `steps_total` and — when `samples` (the TOTAL sample count across
    those steps, i.e. K·global-batch for a K-step fused-loop flush) is
    given — the rolling `samples_per_sec` speedometer gauge (window of
    the last 64 host events). A whole-loop dispatch is one host event
    carrying K steps' worth of samples, so the speedometer stays
    correct without one callback per step."""
    if not _ENABLED:
        return
    now = time.perf_counter()
    inc("steps_total", steps)
    if samples:
        _SPEED_WINDOW.append((now, int(samples)))
        if len(_SPEED_WINDOW) >= 2:
            t_first = _SPEED_WINDOW[0][0]
            dt = now - t_first
            if dt > 0:
                # samples of every step but the window anchor (its
                # duration lies before the window)
                n = sum(s for _, s in list(_SPEED_WINDOW)[1:])
                set_gauge("samples_per_sec", n / dt)


# -- snapshot / exposition --------------------------------------------------

def snapshot() -> dict:
    """One dict of everything: the metric registry plus the pull-based
    providers (profiler resident bytes, kernel fallback counts, compile
    cache stats) and the derived step-time breakdown. Empty dict while
    disabled — the disabled path records nothing, so there is nothing
    to report."""
    if not _ENABLED:
        return {}
    out: dict = {"counters": {}, "gauges": {}, "histograms": {}}
    with _lock:
        for fam in _REGISTRY.values():
            for key, ch in fam.children.items():
                label = fam.name + _label_suffix(key)
                if fam.kind == "counter":
                    out["counters"][label] = ch.value
                elif fam.kind == "gauge":
                    out["gauges"][label] = ch.value
                else:
                    out["histograms"][label] = ch.stats()
        breakdown = {}
        fam = _REGISTRY.get("step_time_breakdown")
        if fam is not None:
            for key, ch in fam.children.items():
                labels = dict(key)
                breakdown[labels.get("phase", "?")] = ch.stats()
    out["step_time_breakdown"] = breakdown
    sps = _REGISTRY.get("samples_per_sec")
    out["samples_per_sec"] = (
        sps.labels().value if sps is not None else 0.0)
    # pull-based providers — late imports keep this module import-clean
    try:
        from .kernels.dispatch import fallback_counts
        out["kernel_fallbacks"] = fallback_counts()
    except Exception:
        out["kernel_fallbacks"] = {}
    try:
        from . import profiler as _prof
        out["resident_bytes"] = _prof.resident_bytes()
    except Exception:
        out["resident_bytes"] = {}
    try:
        from . import tracing as _tracing
        out["compile"] = _tracing.cache_stats()
    except Exception:
        out["compile"] = {}
    return out


def to_prometheus() -> str:
    """Prometheus text exposition of the registry (counters/gauges as
    `name{labels} value`; histograms as `_count`/`_sum` plus log2
    `_bucket{le=...}` cumulative series). Empty string while disabled."""
    if not _ENABLED:
        return ""
    return _prometheus_text(_REGISTRY)


def _prometheus_text(registry: "OrderedDict[str, _Family]") -> str:
    lines: List[str] = []
    with _lock:
        for fam in registry.values():
            if fam.help:
                lines.append(f"# HELP {fam.name} {fam.help}")
            lines.append(f"# TYPE {fam.name} {fam.kind}")
            for key, ch in fam.children.items():
                if fam.kind in ("counter", "gauge"):
                    lines.append(
                        f"{fam.name}{_label_suffix(key)} {ch.value:g}")
                    continue
                base = dict(key)
                cum = ch.zeros
                for e in sorted(ch.buckets):
                    cum += ch.buckets[e]
                    le = dict(base, le=f"{2.0 ** e:g}")
                    lines.append(
                        f"{fam.name}_bucket{_label_suffix(_label_key(le))}"
                        f" {cum}")
                le = dict(base, le="+Inf")
                lines.append(
                    f"{fam.name}_bucket{_label_suffix(_label_key(le))}"
                    f" {ch.count}")
                sfx = _label_suffix(key)
                lines.append(f"{fam.name}_sum{sfx} {ch.sum:g}")
                lines.append(f"{fam.name}_count{sfx} {ch.count}")
    return "\n".join(lines) + ("\n" if lines else "")


# -- cross-process aggregation ----------------------------------------------
#
# Each process publishes a JSON serialization of its registry into the
# jax.distributed coordination-service KV store (the same gloo-safe
# side channel multihost/checkpoint already use — no device collective
# involved, so it works mid-training and from the serving thread). The
# primary pulls the last-published blob of every other process and
# merges: counters by sum, histograms bucket-wise, gauges one child per
# process under a `proc` label. A single-process run aggregates to its
# own registry (gauges gain `proc=0`), so tooling can use one code
# path.

_KV_PREFIX = "mxtpu/tm"


def _proc_info() -> Tuple[int, int]:
    """(process_index, process_count) without ever triggering backend
    init: (0, 1) unless multihost.initialize has run."""
    try:
        from .parallel import multihost as _mh
        if _mh.is_initialized():
            import jax
            return jax.process_index(), jax.process_count()
    except Exception:
        pass
    return 0, 1


def _registry_state() -> dict:
    """JSON-able serialization of the full registry: name ->
    {"k": kind, "h": help, "c": [[label_pairs, state], ...]} with
    counter/gauge state = value and histogram state = its bucket map
    plus exact count/sum/min/max/zeros."""
    out: dict = {}
    with _lock:
        for fam in _REGISTRY.values():
            ch = []
            for key, c in fam.children.items():
                if fam.kind in ("counter", "gauge"):
                    state = c.value
                else:
                    state = {"b": {str(e): n for e, n in c.buckets.items()},
                             "c": c.count, "s": c.sum,
                             "mn": c.min if math.isfinite(c.min) else None,
                             "mx": c.max if math.isfinite(c.max) else None,
                             "z": c.zeros}
                ch.append([[list(kv) for kv in key], state])
            out[fam.name] = {"k": fam.kind, "h": fam.help, "c": ch}
    return out


def registry_delta(prev: Optional[dict],
                   max_bytes: int = 65536) -> Tuple[dict, dict]:
    """Bounded, delta-encoded registry serialization for piggybacking
    on heartbeats: returns ``(delta, acked)`` where ``delta`` holds
    only the families whose state changed since ``prev`` (value None
    marks a family that disappeared, e.g. after reset) and ``acked`` is
    the state to pass as ``prev`` next time. Families that would push
    the encoded delta past ``max_bytes`` are deferred — they stay dirty
    in ``acked`` and ship on a later beat, so the channel stays bounded
    and the receiver stays eventually consistent. Family states are
    absolute (not increments), so re-applying a delta is idempotent —
    safe over an at-least-once heartbeat channel."""
    cur = _registry_state()
    prev = prev or {}
    delta: dict = {}
    acked = dict(prev)
    budget = int(max_bytes)
    for name in prev:
        if name not in cur:
            delta[name] = None
            acked.pop(name, None)
    for name, st in cur.items():
        if prev.get(name) == st:
            continue
        cost = len(json.dumps({name: st}))
        if delta and budget - cost < 0:
            continue  # over budget: defer this family to a later beat
        budget -= cost
        delta[name] = st
        acked[name] = st
    return delta, acked


def publish_snapshot() -> bool:
    """Publish this process's registry to the coordination-service KV
    store so `aggregate_snapshot` on any process (in practice: the
    primary's /metrics) can merge it. No-op (False) while telemetry is
    disabled or in a single-process job. TrainLoop calls this at every
    K-window boundary."""
    if not _ENABLED:
        return False
    pid, n = _proc_info()
    if n <= 1:
        return False
    from .parallel import multihost as _mh
    return _mh.kv_set(f"{_KV_PREFIX}/reg/{pid}",
                      json.dumps(_registry_state()))


def _merge_registry(blobs: Dict,
                    label: str = "proc") -> "OrderedDict[str, _Family]":
    """Merge per-process registry states into fresh (registry-detached)
    families: counters sum, histograms merge bucket-wise (exact
    count/sum/min/max/zeros), gauges keep one child per process under a
    `proc` label (or `label=` — the fleet router merges per-replica
    blobs keyed by replica NAME with ``label="replica"``)."""
    merged: "OrderedDict[str, _Family]" = OrderedDict()
    for pid in sorted(blobs):
        for name, st in blobs[pid].items():
            kind = st.get("k", "counter")
            cls = {"counter": Counter, "gauge": Gauge,
                   "histogram": Histogram}.get(kind, Counter)
            fam = merged.get(name)
            if fam is None or fam.kind != kind:
                if fam is not None:
                    continue  # kind clash across processes: first wins
                fam = _Family(name, kind, cls, st.get("h", ""))
                merged[name] = fam
            for pairs, state in st.get("c", []):
                labels = {str(k): str(v) for k, v in pairs}
                if kind == "gauge":
                    labels[label] = str(pid)
                ch = fam.labels(**labels)
                if kind == "counter":
                    ch.inc(float(state))
                elif kind == "gauge":
                    ch.set(float(state))
                else:
                    for e, cnt in state.get("b", {}).items():
                        e = int(e)
                        ch.buckets[e] = ch.buckets.get(e, 0) + int(cnt)
                    ch.count += int(state.get("c", 0))
                    ch.sum += float(state.get("s", 0.0))
                    mn, mx = state.get("mn"), state.get("mx")
                    if mn is not None and float(mn) < ch.min:
                        ch.min = float(mn)
                    if mx is not None and float(mx) > ch.max:
                        ch.max = float(mx)
                    ch.zeros += int(state.get("z", 0))
    return merged


def _gather_states(timeout_ms: int) -> Dict[int, dict]:
    """This process's live registry plus every other process's
    last-published blob (processes that never published are skipped —
    aggregation is best-effort by design: the scrape must not block on
    a replica that is mid-dispatch)."""
    pid, n = _proc_info()
    blobs: Dict[int, dict] = {pid: _registry_state()}
    if n > 1:
        from .parallel import multihost as _mh
        for p in range(n):
            if p == pid:
                continue
            blob = _mh.kv_get(f"{_KV_PREFIX}/reg/{p}",
                              timeout_ms=timeout_ms)
            if blob:
                try:
                    blobs[p] = json.loads(blob)
                except (ValueError, TypeError):
                    pass
    return blobs


def aggregate_snapshot(timeout_ms: int = 2000) -> dict:
    """The cross-process `snapshot()`: merge this process's registry
    with every published peer registry (counters summed, histograms
    merged bucket-wise, gauges labeled `proc=<i>`). Keys mirror
    `snapshot()` plus `processes` (the indices that contributed).
    Single-process: own registry with `proc=0` gauges. Empty while
    disabled."""
    if not _ENABLED:
        return {}
    blobs = _gather_states(timeout_ms)
    merged = _merge_registry(blobs)
    out: dict = {"counters": {}, "gauges": {}, "histograms": {},
                 "processes": sorted(blobs)}
    for fam in merged.values():
        for key, ch in fam.children.items():
            label = fam.name + _label_suffix(key)
            if fam.kind == "counter":
                out["counters"][label] = ch.value
            elif fam.kind == "gauge":
                out["gauges"][label] = ch.value
            else:
                out["histograms"][label] = ch.stats()
    return out


def to_prometheus_merged(timeout_ms: int = 2000) -> str:
    """Prometheus exposition of the merged cross-process registry (the
    body the primary's /metrics serves). Empty string while
    disabled."""
    if not _ENABLED:
        return ""
    return _prometheus_text(_merge_registry(_gather_states(timeout_ms)))


# -- straggler detection ----------------------------------------------------

def publish_step_time(seconds: float):
    """Record this process's per-step wall time (the `step_time_seconds`
    gauge) and publish it to the KV store; on the primary, refresh the
    `step_time_skew_ratio` gauge (max/median across processes — the
    first-order pod-scale diagnostic). TrainLoop calls this with
    window_seconds / K at every K-window boundary."""
    if not _ENABLED:
        return
    set_gauge("step_time_seconds", seconds)
    pid, n = _proc_info()
    if n > 1:
        from .parallel import multihost as _mh
        _mh.kv_set(f"{_KV_PREFIX}/steptime/{pid}", repr(float(seconds)))
        if pid == 0:
            step_time_skew()


def step_times(timeout_ms: int = 1000) -> Dict[int, float]:
    """Last-published per-process step time, keyed by process index
    (own value read live; peers that never published are skipped)."""
    if not _ENABLED:
        return {}
    pid, n = _proc_info()
    times: Dict[int, float] = {}
    fam = _REGISTRY.get("step_time_seconds")
    if fam is not None:
        ch = fam.children.get(())
        if ch is not None:
            times[pid] = ch.value
    if n > 1:
        from .parallel import multihost as _mh
        for p in range(n):
            if p == pid:
                continue
            raw = _mh.kv_get(f"{_KV_PREFIX}/steptime/{p}",
                             timeout_ms=timeout_ms)
            if raw:
                try:
                    times[p] = float(raw)
                except ValueError:
                    pass
    return times


def step_time_skew(timeout_ms: int = 1000) -> float:
    """max/median of the per-process step times (1.0 = perfectly even;
    a straggler drives it up). Sets the `step_time_skew_ratio` gauge
    plus a `step_time_seconds{proc=i}` gauge per contributing process.
    0.0 when nothing has been published yet."""
    times = step_times(timeout_ms)
    if not times:
        return 0.0
    med = statistics.median(times.values())
    ratio = max(times.values()) / med if med > 0 else 0.0
    set_gauge("step_time_skew_ratio", ratio)
    for p, t in times.items():
        set_gauge("step_time_seconds", t, proc=str(p))
    return ratio


def stragglers(threshold: float = 1.5,
               timeout_ms: int = 1000) -> List[int]:
    """Process indices whose step time exceeds `threshold` x the
    median — the replicas to look at first when skew climbs."""
    times = step_times(timeout_ms)
    if len(times) < 2:
        return []
    med = statistics.median(times.values())
    if med <= 0:
        return []
    return sorted(p for p, t in times.items() if t > threshold * med)


def _prune_register(sources: List[weakref.ref], obj):
    with _lock:
        sources[:] = [r for r in sources
                      if r() is not None and r() is not obj]
        sources.append(weakref.ref(obj))


def _live_sources(sources: List[weakref.ref]) -> list:
    with _lock:
        alive = [(r, r()) for r in sources]
        sources[:] = [r for r, o in alive if o is not None]
        return [o for _, o in alive if o is not None]


def register_health_source(obj):
    """Register an object exposing `health() -> (ok, reason)`; /healthz
    answers 503 with the reason while any source reports not-ok. Held
    by weakref — a collected source unregisters itself."""
    _prune_register(_HEALTH_SOURCES, obj)


def unregister_health_source(obj):
    with _lock:
        _HEALTH_SOURCES[:] = [r for r in _HEALTH_SOURCES
                              if r() is not None and r() is not obj]


def health() -> Tuple[bool, str]:
    """Merged health of every registered source: the first not-ok
    (ok, reason) wins; (True, "ok") when nothing objects."""
    for src in _live_sources(_HEALTH_SOURCES):
        try:
            ok, reason = src.health()
        except Exception:
            continue
        if not ok:
            return False, str(reason)
    return True, "ok"


def health_report() -> dict:
    """The structured /healthz body: merged ``ok``/``reason`` (as in
    :func:`health`) plus one detail dict per registered source — from
    its ``health_detail()`` when it has one (InferenceServer's carries
    drain state, queue age p50/p95, blocks-free), else the bare
    (ok, reason) pair. Routers and operators read this ONE probe
    instead of scraping /metrics for the same numbers."""
    ok, reason = True, "ok"
    sources = []
    for src in _live_sources(_HEALTH_SOURCES):
        try:
            s_ok, s_reason = src.health()
        except Exception:
            continue
        detail = None
        hd = getattr(src, "health_detail", None)
        if hd is not None:
            try:
                detail = hd()
            except Exception:
                detail = None
        if detail is None:
            detail = {"ok": bool(s_ok), "reason": str(s_reason)}
        sources.append(detail)
        if ok and not s_ok:
            ok, reason = False, str(s_reason)
    return {"ok": ok, "reason": reason, "sources": sources}


def register_request_trace_source(obj):
    """Register an object exposing `request_traces() -> [trace dict]`
    (InferenceServer); export_chrome_trace merges the spans under
    REQUEST_PID. Held by weakref."""
    _prune_register(_REQUEST_TRACE_SOURCES, obj)


def register_fleet_trace_source(obj):
    """Register an object exposing `fleet_traces() -> [merged timeline]`
    (FleetRouter); export_chrome_trace renders the router-side spans on
    ROUTER_PID and each replica's spans on its own pid. Held by
    weakref."""
    _prune_register(_FLEET_TRACE_SOURCES, obj)


def set_fleet_metrics_provider(obj):
    """Point /metrics at a fleet view: `obj` exposes
    `fleet_prometheus() -> str` (a FleetRouter serving the bucket-exact
    merge of its own registry plus every replica's heartbeat-shipped
    snapshot). Held by weakref; pass None to restore the local body."""
    global _FLEET_METRICS_PROVIDER
    with _lock:
        _FLEET_METRICS_PROVIDER = None if obj is None else weakref.ref(obj)


def _metrics_body() -> bytes:
    """The /metrics payload: the fleet-merged view when a FleetRouter
    registered itself as provider, else the merged cross-process view
    on the primary of an initialized multi-process job, the local
    registry everywhere else (and on any aggregation failure)."""
    ref = _FLEET_METRICS_PROVIDER
    provider = ref() if ref is not None else None
    if provider is not None:
        try:
            return provider.fleet_prometheus().encode()
        except Exception:
            pass
    try:
        from .parallel import multihost as _mh
        if _mh.is_initialized():
            import jax
            if jax.process_count() > 1 and jax.process_index() == 0:
                return to_prometheus_merged().encode()
    except Exception:
        pass
    return to_prometheus().encode()


class _MetricsServer:
    """Handle for a running /metrics endpoint: `.port`, `.url`,
    `.close()`. Construction binds and starts the daemon thread."""

    def __init__(self, port: int = 0, host: str = "127.0.0.1"):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        class _Handler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 (http.server API)
                if self.path.split("?")[0] == "/metrics":
                    body = _metrics_body()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type",
                        "text/plain; version=0.0.4; charset=utf-8")
                elif self.path.split("?")[0] == "/healthz":
                    rep = health_report()
                    body = (json.dumps(rep) + "\n").encode()
                    self.send_response(200 if rep["ok"] else 503)
                    self.send_header("Content-Type", "application/json")
                else:
                    body = b"not found\n"
                    self.send_response(404)
                    self.send_header("Content-Type", "text/plain")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):  # keep scrapes out of stderr
                pass

        self._httpd = ThreadingHTTPServer((host, int(port)), _Handler)
        self._httpd.daemon_threads = True
        self.host = host
        self.port = self._httpd.server_address[1]
        self.url = f"http://{host}:{self.port}/metrics"
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="mxnet-tpu-metrics",
            daemon=True)
        self._thread.start()

    def close(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)


_METRICS_SERVER: Optional[_MetricsServer] = None


def start_metrics_server(port: int = 0,
                         host: Optional[str] = None) -> _MetricsServer:
    """Serve `to_prometheus()` at GET /metrics (plus a /healthz probe)
    from a stdlib ThreadingHTTPServer daemon thread — the pull-based
    exposition for multi-host jobs where every worker scrapes its own
    process; the primary of a multi-process job serves the MERGED
    registry (see `aggregate_snapshot`). `port=0` binds an ephemeral
    port (see `.port`/`.url` on the returned handle). `host=None`
    honors MXNET_TPU_METRICS_HOST (default 127.0.0.1 — loopback stays
    the default; a pod primary sets 0.0.0.0 to expose the merged view).
    One server per process: repeated calls return the existing
    handle."""
    global _METRICS_SERVER
    if host is None:
        host = os.environ.get("MXNET_TPU_METRICS_HOST", "127.0.0.1")
    with _lock:
        if _METRICS_SERVER is None:
            _METRICS_SERVER = _MetricsServer(port=port, host=host)
    return _METRICS_SERVER


def stop_metrics_server():
    """Shut the /metrics endpoint down (no-op when none is running)."""
    global _METRICS_SERVER
    with _lock:
        srv, _METRICS_SERVER = _METRICS_SERVER, None
    if srv is not None:
        srv.close()


def maybe_start_metrics_server() -> Optional[_MetricsServer]:
    """Opt-in hook Trainer/InferenceServer call at construction: when
    MXNET_TPU_METRICS_PORT is set, enable telemetry and serve /metrics
    on that port (0 = ephemeral; MXNET_TPU_METRICS_HOST overrides the
    127.0.0.1 bind). Unset → None, nothing started."""
    spec = os.environ.get("MXNET_TPU_METRICS_PORT")
    if spec is None or spec == "":
        return None
    enable()
    return start_metrics_server(
        port=int(spec), host=os.environ.get("MXNET_TPU_METRICS_HOST",
                                            "127.0.0.1"))


def dump_json(path: Optional[str] = None) -> str:
    """JSON dump of snapshot(). With `path`, writes the file and
    returns the path; without, returns the JSON string."""
    payload = json.dumps(snapshot(), indent=1, sort_keys=True,
                         default=str)
    if path is None:
        return payload
    with open(path, "w") as f:
        f.write(payload)
    return path


def breakdown_table() -> str:
    """Human-readable step-time breakdown (the TelemetryHandler log
    line): per phase count / mean / p50 / p95 / p99 in ms plus the
    rolling samples/sec."""
    snap = snapshot()
    if not snap:
        return "telemetry disabled"
    lines = [f"{'phase':<16}{'count':>8}{'mean_ms':>10}{'p50_ms':>10}"
             f"{'p95_ms':>10}{'p99_ms':>10}{'total_s':>10}"]
    order = {p: i for i, p in enumerate(STEP_PHASES)}
    rows = sorted(snap["step_time_breakdown"].items(),
                  key=lambda kv: order.get(kv[0], 99))
    for name, st in rows:
        if not st.get("count"):
            continue
        lines.append(
            f"{name:<16}{st['count']:>8}"
            f"{st['mean'] * 1e3:>10.2f}{st['p50'] * 1e3:>10.2f}"
            f"{st['p95'] * 1e3:>10.2f}{st['p99'] * 1e3:>10.2f}"
            f"{st['sum']:>10.2f}")
    sps = snap.get("samples_per_sec", 0.0)
    if sps:
        lines.append(f"samples/sec: {sps:.1f}")
    if _goodput_section is not None:
        lines.extend(_goodput_section())
    return "\n".join(lines)


# -- chrome-trace export ----------------------------------------------------

def _request_trace_events() -> List[dict]:
    """Convert every registered source's per-request span timelines
    into chrome events on REQUEST_PID: one tid per request, timed
    events (queued wait, prefill, decode windows) as "X" spans, the
    discrete transitions (admit, preempt, cow, evict, finish) as
    instants."""
    events: List[dict] = []
    tids = set()
    for src in _live_sources(_REQUEST_TRACE_SOURCES):
        try:
            traces = src.request_traces()
        except Exception:
            continue
        for tr in traces:
            rid = int(tr.get("request_id", 0))
            tids.add(rid)
            for ev in tr.get("events", []):
                base = {"name": ev.get("name", "?"), "pid": REQUEST_PID,
                        "tid": rid, "ts": float(ev.get("t", 0.0)) * 1e6}
                args = {k: v for k, v in ev.items()
                        if k not in ("name", "t", "dur_s")}
                if args:
                    base["args"] = args
                dur = ev.get("dur_s")
                if dur is not None:
                    base["ph"] = "X"
                    base["dur"] = float(dur) * 1e6
                else:
                    base["ph"] = "i"
                    base["s"] = "t"
                events.append(base)
    if events:
        events.insert(0, {"ph": "M", "pid": REQUEST_PID,
                          "name": "process_name",
                          "args": {"name": "serving: request spans"}})
        for rid in sorted(tids):
            events.append({"ph": "M", "pid": REQUEST_PID, "tid": rid,
                           "name": "thread_name",
                           "args": {"name": f"request {rid}"}})
    return events


def _fleet_trace_events() -> List[dict]:
    """Convert every registered fleet source's merged request timelines
    (see FleetRouter.trace) into chrome events: router-side spans on
    ROUTER_PID, each replica's spans on REPLICA_PID_BASE + its index
    over the sorted replica names (stable across exports), one tid per
    request on every pid. Timestamps are unix seconds — the fleet's one
    shared clock after the heartbeat offset handshake."""
    raw: List[Tuple[str, int, dict]] = []   # (src, request_id, event)
    replicas = set()
    tids: Dict[str, set] = {}
    for src in _live_sources(_FLEET_TRACE_SOURCES):
        try:
            traces = src.fleet_traces()
        except Exception:
            continue
        for tr in traces:
            rid = int(tr.get("request_id", 0))
            for ev in tr.get("events", []):
                who = str(ev.get("src", "router"))
                if who != "router":
                    replicas.add(who)
                tids.setdefault(who, set()).add(rid)
                raw.append((who, rid, ev))
    if not raw:
        return []
    pid_of = {"router": ROUTER_PID}
    for i, name in enumerate(sorted(replicas)):
        pid_of[name] = REPLICA_PID_BASE + i
    events: List[dict] = []
    for who, name in sorted(pid_of.items(), key=lambda kv: kv[1]):
        label = ("fleet: router" if who == "router"
                 else f"fleet: replica {who}")
        events.append({"ph": "M", "pid": pid_of[who],
                       "name": "process_name", "args": {"name": label}})
        for rid in sorted(tids.get(who, ())):
            events.append({"ph": "M", "pid": pid_of[who], "tid": rid,
                           "name": "thread_name",
                           "args": {"name": f"request {rid}"}})
    for who, rid, ev in raw:
        base = {"name": ev.get("name", "?"), "pid": pid_of[who],
                "tid": rid, "ts": float(ev.get("t", 0.0)) * 1e6}
        args = {k: v for k, v in ev.items()
                if k not in ("name", "t", "dur_s", "src")}
        if args:
            base["args"] = args
        dur = ev.get("dur_s")
        if dur is not None:
            base["ph"] = "X"
            base["dur"] = float(dur) * 1e6
        else:
            base["ph"] = "i"
            base["s"] = "t"
        events.append(base)
    return events


def _normalize_trace_events(events: List[dict]) -> List[dict]:
    """Deterministic event ordering for export: metadata first (sorted
    by pid/name/tid), then spans sorted by (pid, ts, -dur, name, ph);
    host/device thread idents (which vary run to run) are renumbered to
    dense per-pid indices in first-encounter order of the sorted
    stream. Same recorded spans in -> byte-identical JSON out."""
    meta = [dict(e) for e in events if e.get("ph") == "M"]
    rest = [dict(e) for e in events if e.get("ph") != "M"]
    rest.sort(key=lambda e: (e.get("pid", 0), float(e.get("ts", 0.0)),
                             -float(e.get("dur", 0.0) or 0.0),
                             str(e.get("name", "")), str(e.get("ph", ""))))
    remap: Dict[Tuple, int] = {}
    counts: Dict[int, int] = {}
    for e in rest:
        pid = e.get("pid", 0)
        if pid in (HOST_PID, DEVICE_PID) and "tid" in e:
            key = (pid, e["tid"])
            if key not in remap:
                remap[key] = counts.get(pid, 0)
                counts[pid] = remap[key] + 1
            e["tid"] = remap[key]
    meta.sort(key=lambda e: (e.get("pid", 0), str(e.get("name", "")),
                             str(e.get("tid", ""))))
    return meta + rest


def export_chrome_trace(path: str) -> str:
    """Write ONE chrome://tracing-loadable JSON merging:

    - host phase events recorded by `phase`/`mark_phase` (pid 0),
    - host `profiler.scope` spans (pid 0),
    - device spans: sync-measured executable spans (pid 1, recorded by
      FusedTrainStep with `device=True`),
    - per-request serving span timelines from registered
      InferenceServers (pid REQUEST_PID, one tid per request),
    - fleet-merged request timelines from registered FleetRouters
      (router spans on pid ROUTER_PID, one pid per replica).

    Everything here is on the HOST clock. The device's own operations
    are not merged in: the installed JAX's profiler writes `.xplane.pb`,
    and since every phase is also a `TraceAnnotation` (`mx.<name>`)
    that file already holds host spans and device operations on one
    clock (`jax.profiler.ProfileData` reads it).

    Works with whatever has been recorded so far; events only exist
    for spans that ran while telemetry was enabled. The output is
    deterministic: same recorded spans produce byte-identical JSON
    (stable event order, dense per-pid thread ids, sorted keys)."""
    events: List[dict] = [
        {"ph": "M", "pid": HOST_PID, "name": "process_name",
         "args": {"name": "host: telemetry phases + profiler scopes"}},
        {"ph": "M", "pid": DEVICE_PID, "name": "process_name",
         "args": {"name": "device: sync-measured executable spans"}},
    ]
    events.extend(_TRACE_EVENTS)
    try:
        from . import profiler as _prof
        events.extend(dict(ev, pid=HOST_PID) for ev in _prof._EVENTS)
    except Exception:
        pass
    events.extend(_request_trace_events())
    events.extend(_fleet_trace_events())
    events = _normalize_trace_events(events)
    with open(path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f,
                  sort_keys=True, separators=(",", ":"))
    return path
