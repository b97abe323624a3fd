"""Goodput ledger, MFU/HFU accounting, and memory-pressure forecasting.

The two questions that decide whether a pod is worth its cost are
*what fraction of wall clock was productive* and *how close are we to
the hardware ceiling* (arXiv:1909.09756 ranks pod-scale systems by
per-chip efficiency; the serving comparisons in arXiv:2605.25645 rank
by tokens/sec/chip). This module turns the telemetry phase marks and
flight events the stack already emits into those numbers:

- ``GoodputLedger`` — a wall-clock ledger that attributes EVERY second
  of the job to ``productive`` or one of the badput categories in
  :data:`CATEGORIES`. Attribution is *frontier-clipping*: each charged
  span ``[end - dur, end]`` is clipped to the part after the ledger's
  frontier (the latest instant already attributed), the gap between the
  frontier and the span start accrues to ``idle``, and the frontier
  advances to the span end. Overlapping instrumentation (device vs
  host timings of the same step, an admit phase that brackets a
  prefill) therefore never double-counts, and the conservation
  invariant — categories sum exactly to elapsed wall clock — holds by
  construction (``tests/test_goodput.py`` fuzzes it).
- hooks — :func:`enable` installs a phase hook in ``telemetry``
  (every ``mark_phase`` feeds the ledger), an event hook in ``flight``
  (serving stalls / crashes become ``stall`` / ``fault_recovery``
  time), and a compile hook via ``tracing.record_compile_seconds``.
  Disabled, each hook site costs one attribute load + branch — the
  same cost contract the telemetry lint enforces.
- persistence — :func:`state_dict` rides the checkpoint manifest
  (``Checkpointer.save(extra=...)``) and :func:`restore_state` charges
  the wall-clock gap between the save and the restarted process's
  ledger start to ``fault_recovery``, so badput from a SIGKILL restart
  is charged, not lost.
- fleet merge — :func:`publish` exports settled ledger seconds as the
  ``goodput_seconds_total{category=}`` counter. Counters SUM across
  the registry-delta plane, so the primary's ``/metrics`` serves fleet
  goodput with no extra wiring, and :class:`mxnet_tpu.slo
  .GoodputObjective` can burn-rate-alert on efficiency collapse.
- efficiency — :func:`note_train_step` publishes ``goodput_mfu`` /
  ``goodput_hfu`` (model / hardware FLOPs per step ÷ step time × chips
  × per-chip peak from :data:`PEAK_FLOPS_BY_KIND`), labelled by flops
  source: ``analytic`` (6·N·D) vs ``cost_analysis``. There is no
  honest CPU peak, so neither gauge is published on the CPU platform,
  and an accelerator that is not in the table is an error.
  :func:`note_tokens` feeds the comparable headline gauges
  ``goodput_{train,serve}_tokens_per_sec_per_chip``; "per chip" is per
  chip the work spans, as its caller reports it.
- memory pressure — :func:`note_hbm_watermark` records per-executable
  HBM watermarks via ``memory_analysis()`` (``bytes_source`` label
  says whether the number is measured or an analytic fallback), and
  :class:`PoolForecaster` fits a rolling line over KV ``blocks_free``
  to forecast time-to-exhaustion; it registers as a ``/healthz``
  health source and feeds ``FleetRouter`` admission so a replica
  forecast to exhaust within its drain window stops taking long-prompt
  work *before* it preempts.

Everything here is off by default (``MXNET_TPU_GOODPUT=1`` or
:func:`enable` opts in) and rides — never replaces — the existing
telemetry registry.
"""
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

from . import flight as _fl
from . import telemetry as _tm

__all__ = [
    "CATEGORIES",
    "PEAK_FLOPS_BY_KIND",
    "peak_flops",
    "GoodputLedger",
    "PoolForecaster",
    "enable",
    "disable",
    "reset",
    "ledger",
    "charge_span",
    "charge_gap",
    "note_compile",
    "note_tokens",
    "note_tenant_tokens",
    "usage_report",
    "note_train_step",
    "note_hbm_watermark",
    "publish",
    "snapshot",
    "state_dict",
    "restore_state",
    "format_summary",
]

#: every second of wall clock lands in exactly one of these
CATEGORIES = (
    "productive",
    "compile",
    "data_wait",
    "checkpoint_save",
    "checkpoint_restore",
    "fault_recovery",
    "stall",
    "dispatch_overhead",
    "idle",
)

#: phase-mark name -> ledger category (prefix rules in _category_for)
_PHASE_CATEGORY = {
    "data": "data_wait",
    "serve_admit": "dispatch_overhead",
    "fused_step": "productive",
    "fused_step_host": "productive",
    "fused_loop_host": "productive",
    "forward": "productive",
    "backward": "productive",
    "optimizer": "productive",
    "grad_comm": "productive",
    "weight_gather": "productive",
    "serve_prefill": "productive",
    "serve_decode": "productive",
    "checkpoint_save": "checkpoint_save",
    "checkpoint_restore": "checkpoint_restore",
}

#: dense bf16 peak FLOPs per chip, keyed by ``jax.Device.device_kind``
#: (Google Cloud TPU documentation; "TPU v5 lite" is what a v5e chip
#: reports). The program's ONE peak table: chip_smoke.py reads it
#: through :func:`peak_flops`.
PEAK_FLOPS_BY_KIND = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,
}


def peak_flops(device=None) -> Optional[float]:
    """Per-chip dense bf16 peak of `device` (default: the first
    device). None on the CPU platform — there is no honest CPU peak,
    so nothing divides by one there. An accelerator whose device_kind
    is not in :data:`PEAK_FLOPS_BY_KIND` raises: a utilization against
    a guessed peak is worse than none."""
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    try:
        return PEAK_FLOPS_BY_KIND[device.device_kind]
    except KeyError:
        raise KeyError(
            f"device_kind {device.device_kind!r} has no entry in "
            "goodput.PEAK_FLOPS_BY_KIND — add its published bf16 peak "
            "(with the source) before reporting a utilization on it"
        ) from None


def _category_for(phase: str) -> Optional[str]:
    cat = _PHASE_CATEGORY.get(phase)
    if cat is None and phase.startswith(("pipeline", "stage")):
        cat = "productive"
    return cat


class GoodputLedger:
    """Frontier-clipping wall-clock attribution ledger.

    ``charge_span(cat, dur, end)`` clips the span ``[end - dur, end]``
    to the part after ``_frontier``, charges the frontier→start gap to
    ``idle``, and advances the frontier — so the invariant
    ``sum(seconds) == frontier - t0 + base_elapsed`` holds after every
    charge, and :meth:`snapshot` (which adds the frontier→now gap as
    pending idle) sums exactly to :meth:`elapsed`.
    """

    def __init__(self, t0: Optional[float] = None):
        self.t0 = time.perf_counter() if t0 is None else float(t0)
        self._frontier = self.t0
        #: wall-clock anchor for cross-restart gap accounting
        self._wall0 = time.time()
        #: elapsed seconds carried over from restored ledgers
        self._base_elapsed = 0.0
        self.seconds: Dict[str, float] = {c: 0.0 for c in CATEGORIES}
        self._lock = threading.Lock()

    # -- attribution --------------------------------------------------
    def charge_span(self, category: str, dur_s: float,
                    end: Optional[float] = None) -> None:
        if category not in self.seconds:
            raise KeyError(f"unknown goodput category {category!r}; "
                           f"one of {CATEGORIES}")
        now = time.perf_counter() if end is None else float(end)
        with self._lock:
            self._charge_locked(category, now - max(0.0, float(dur_s)),
                                now)

    def charge_gap(self, category: str,
                   now: Optional[float] = None) -> None:
        """Attribute everything since the frontier to *category*."""
        if category not in self.seconds:
            raise KeyError(f"unknown goodput category {category!r}; "
                           f"one of {CATEGORIES}")
        now = time.perf_counter() if now is None else float(now)
        with self._lock:
            self._charge_locked(category, self._frontier, now)

    def _charge_locked(self, category: str, start: float,
                       end: float) -> None:
        f = self._frontier
        if end <= f:
            return  # span entirely inside already-attributed time
        if start > f:
            self.seconds["idle"] += start - f
            f = start
        self.seconds[category] += end - f
        self._frontier = end

    # -- readout ------------------------------------------------------
    def elapsed(self, now: Optional[float] = None) -> float:
        now = time.perf_counter() if now is None else float(now)
        return (now - self.t0) + self._base_elapsed

    def snapshot(self, now: Optional[float] = None) -> dict:
        """Categories summing exactly to elapsed (pending frontier→now
        gap shown as idle, but NOT settled — a still-open phase may yet
        claim it)."""
        now = time.perf_counter() if now is None else float(now)
        with self._lock:
            secs = dict(self.seconds)
            secs["idle"] += max(0.0, now - self._frontier)
        return {"elapsed_s": self.elapsed(now), "seconds": secs}

    def settled(self) -> Tuple[Dict[str, float], float]:
        """Attributed seconds only (no pending idle) — what the fleet
        counters export, so a later stall/phase claim never makes the
        already-published sum overshoot elapsed."""
        with self._lock:
            return dict(self.seconds), \
                (self._frontier - self.t0) + self._base_elapsed

    # -- persistence --------------------------------------------------
    def state_dict(self) -> dict:
        snap = self.snapshot()
        return {"schema": 1, "wall": time.time(),
                "elapsed_s": snap["elapsed_s"],
                "seconds": snap["seconds"]}

    def restore_state(self, st: dict) -> None:
        """Merge a saved ledger; the dead time between the save and
        THIS process's ledger start is charged to ``fault_recovery``
        (time since our own start is already live-tracked)."""
        if not st:
            return
        gap = max(0.0, self._wall0 - float(st.get("wall", self._wall0)))
        with self._lock:
            for c, v in (st.get("seconds") or {}).items():
                if c in self.seconds:
                    self.seconds[c] += float(v)
            self.seconds["fault_recovery"] += gap
            self._base_elapsed += float(st.get("elapsed_s", 0.0)) + gap


# -- module state (one process-wide ledger, like telemetry's registry)
_ENABLED = False
_LEDGER: Optional[GoodputLedger] = None
_TOKENS: Dict[str, int] = {"train": 0, "serve": 0}
#: tenant-attributed serve tokens — the usage meter's raw material
#: (conservation-checked against serving_tenant_tokens_total)
_TENANT_TOKENS: Dict[str, int] = {}
_MODEL_FLOPS = 0.0
_HW_FLOPS = 0.0
_LAST_MFU: Optional[float] = None
_LAST_HFU: Optional[float] = None
#: chips each kind of work spans, as its caller last reported (the
#: train step's mesh size, the device count of the server's arrays) —
#: never the host's device count, which a one-chip program on a
#: four-chip host does not use
_CHIPS: Dict[str, int] = {}
_LAST_PUB: Dict[str, float] = {}
_PUB_LOCK = threading.Lock()


def enable() -> None:
    """Turn goodput accounting on (idempotent). Rides the telemetry
    phase marks, so this also enables telemetry."""
    global _ENABLED, _LEDGER
    if _ENABLED:
        return
    _tm.enable()
    if _LEDGER is None:
        _LEDGER = GoodputLedger()
    _ENABLED = True
    _tm._goodput_note = _note_phase
    _tm._goodput_section = _breakdown_section
    _fl._note_hook = _note_flight


def disable() -> None:
    """Stop accounting and uninstall the hooks (ledger kept for
    readout; see :func:`reset`)."""
    global _ENABLED
    _ENABLED = False
    _tm._goodput_note = None
    _tm._goodput_section = None
    _fl._note_hook = None


def reset() -> None:
    """disable() plus drop all ledger/efficiency state (tests)."""
    global _LEDGER, _MODEL_FLOPS, _HW_FLOPS, _LAST_MFU, _LAST_HFU
    disable()
    _LEDGER = None
    _TOKENS.clear()
    _TOKENS.update(train=0, serve=0)
    _TENANT_TOKENS.clear()
    _MODEL_FLOPS = 0.0
    _HW_FLOPS = 0.0
    _LAST_MFU = None
    _LAST_HFU = None
    _CHIPS.clear()
    _PLAN_AXES.clear()
    with _PUB_LOCK:
        _LAST_PUB.clear()


def ledger() -> Optional[GoodputLedger]:
    return _LEDGER


# -- hook targets (installed by enable()) -----------------------------
def _note_phase(name: str, seconds: float,
                t0: Optional[float] = None) -> None:
    """telemetry.mark_phase hook: every phase mark feeds the ledger."""
    if not _ENABLED or _LEDGER is None:
        return
    cat = _category_for(name)
    if cat is None:
        return  # unmapped phase: left to the idle remainder
    end = None if t0 is None else t0 + seconds
    _LEDGER.charge_span(cat, seconds, end=end)


def _note_flight(kind: str, site: str, payload: dict) -> None:
    """flight.record hook: stall watchdog fires / crashes become
    badput for the whole unattributed window leading up to them."""
    if not _ENABLED or _LEDGER is None:
        return
    if kind == "stall":
        _LEDGER.charge_gap("stall")
    elif kind == "exception":
        _LEDGER.charge_gap("fault_recovery")


# -- gated module-level helpers (the hot API; disabled cost is one
# attribute load + branch, enforced by tests/test_telemetry_lint.py)
def charge_span(category: str, dur_s: float,
                end: Optional[float] = None) -> None:
    if not _ENABLED or _LEDGER is None:
        return
    _LEDGER.charge_span(category, dur_s, end=end)


def charge_gap(category: str) -> None:
    if not _ENABLED or _LEDGER is None:
        return
    _LEDGER.charge_gap(category)


def note_compile(seconds: float) -> None:
    """tracing.record_compile_seconds feeds every jit compile here."""
    if not _ENABLED or _LEDGER is None:
        return
    _LEDGER.charge_span("compile", seconds)


def note_tokens(kind: str, n: int, chips: int = 1) -> None:
    """Accumulate train/serve tokens for the tokens/sec/chip gauges.
    `chips` is how many chips that work spans — the train step's mesh
    size, or the device count of the arrays the server computes on."""
    if not _ENABLED or n <= 0:
        return
    _TOKENS[kind] = _TOKENS.get(kind, 0) + int(n)
    _CHIPS[kind] = max(1, int(chips))


def note_tenant_tokens(tenant: Optional[str], n: int) -> None:
    """Tenant-attributed serve tokens for the usage meter (same cost
    contract as note_tokens — one flag check when disabled). The
    serving layer feeds this next to the tenant-labeled telemetry
    counter, so the two stay conservation-equal."""
    if not _ENABLED or n <= 0:
        return
    t = str(tenant) if tenant else "anonymous"
    _TENANT_TOKENS[t] = _TENANT_TOKENS.get(t, 0) + int(n)


def _chips(kind: str) -> int:
    return _CHIPS.get(kind, 1)


#: active ParallelPlan axis sizes — the MFU/HFU gauges carry them as labels
_PLAN_AXES: Dict[str, str] = {}


def set_plan_axes(dp: int = 1, tp: int = 1, pp: int = 1,
                  ep: int = 1) -> None:
    """Record the active parallel plan's mesh-axis sizes (set by the
    FusedTrainStep builders / ``ParallelPlan.lower``); every subsequent
    ``note_train_step`` labels its MFU/HFU gauges with them."""
    _PLAN_AXES.clear()
    _PLAN_AXES.update(dp=str(int(dp)), tp=str(int(tp)),
                      pp=str(int(pp)), ep=str(int(ep)))


def note_train_step(step_s: float, model_flops: Optional[float] = None,
                    hw_flops: Optional[float] = None,
                    chips: int = 1) -> None:
    """Publish MFU/HFU for one train step over `chips` chips (the
    step's mesh size). Nothing is published on the CPU platform.

    ``model_flops`` is the analytic 6·N·D estimate (MFU numerator);
    ``hw_flops`` is the traced ``cost_analysis()`` count, which
    includes rematerialization (HFU numerator). Either sticks for
    subsequent steps once seen. Gauges carry the active plan's axis
    sizes as labels (see :func:`set_plan_axes`).
    """
    global _MODEL_FLOPS, _HW_FLOPS, _LAST_MFU, _LAST_HFU
    if not _ENABLED:
        return
    if model_flops:
        _MODEL_FLOPS = float(model_flops)
    if hw_flops:
        _HW_FLOPS = float(hw_flops)
    _CHIPS["train"] = max(1, int(chips))
    peak = peak_flops()
    if step_s <= 0 or peak is None:
        return
    denom = step_s * _chips("train") * peak
    if _MODEL_FLOPS > 0:
        _LAST_MFU = _MODEL_FLOPS / denom
        _tm.set_gauge("goodput_mfu", _LAST_MFU,
                      flops_source="analytic", **_PLAN_AXES)
    if _HW_FLOPS > 0:
        _LAST_HFU = _HW_FLOPS / denom
        _tm.set_gauge("goodput_hfu", _LAST_HFU,
                      flops_source="cost_analysis", **_PLAN_AXES)


def note_hbm_watermark(name: str, jit_fn, args) -> None:
    """Per-executable HBM watermark from AOT ``memory_analysis()``.

    *args* is a tree of ``ShapeDtypeStruct`` avals (what the serving
    ``Program`` already builds for compile-cache tracing). Falls back
    to the summed aval footprint, honestly labelled
    ``bytes_source="analytic"``.
    """
    if not _ENABLED:
        return
    temp = arg_b = out_b = None
    total = None
    source = "analytic"
    try:
        mem = jit_fn.lower(*args).compile().memory_analysis()
        temp = float(getattr(mem, "temp_size_in_bytes", 0) or 0)
        arg_b = float(getattr(mem, "argument_size_in_bytes", 0) or 0)
        out_b = float(getattr(mem, "output_size_in_bytes", 0) or 0)
        alias = float(getattr(mem, "alias_size_in_bytes", 0) or 0)
        total = temp + arg_b + out_b - alias
        source = "memory_analysis"
    except Exception:
        try:
            import jax
            import numpy as np
            total = 0.0
            for leaf in jax.tree_util.tree_leaves(args):
                if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
                    total += float(np.dtype(leaf.dtype).itemsize *
                                   np.prod(leaf.shape, dtype=np.int64))
        except Exception:
            return
    _tm.set_gauge("goodput_hbm_bytes", total, program=name,
                  kind="peak", bytes_source=source)
    if source == "memory_analysis":
        for kind, v in (("temp", temp), ("args", arg_b),
                        ("output", out_b)):
            _tm.set_gauge("goodput_hbm_bytes", v, program=name,
                          kind=kind, bytes_source=source)


def publish() -> None:
    """Export the ledger over the fleet metrics plane.

    Settled seconds go out as deltas on the
    ``goodput_seconds_total{category=}`` counter (counters SUM on
    registry merge → the primary's /metrics shows fleet goodput), plus
    the headline fraction and tokens/sec/chip gauges.
    """
    if not _ENABLED or _LEDGER is None:
        return
    secs, settled_el = _LEDGER.settled()
    with _PUB_LOCK:
        for c, v in secs.items():
            d = v - _LAST_PUB.get(c, 0.0)
            if d > 0:
                _tm.inc("goodput_seconds_total", d, category=c)
                _LAST_PUB[c] = v
        for t, tok in _TENANT_TOKENS.items():
            k = f"tenant::{t}"
            d = tok - _LAST_PUB.get(k, 0.0)
            if d > 0:
                _tm.inc("goodput_tenant_tokens_total", d, tenant=t)
                _LAST_PUB[k] = float(tok)
    el = _LEDGER.elapsed()
    if el <= 0:
        return
    _tm.set_gauge("goodput_productive_fraction",
                  secs["productive"] / el)
    for kind in ("train", "serve"):
        tok = _TOKENS.get(kind, 0)
        if tok:
            _tm.set_gauge(f"goodput_{kind}_tokens_per_sec_per_chip",
                          tok / (el * _chips(kind)))


def usage_report() -> dict:
    """Billing-grade per-tenant usage: tokens + chip-seconds.

    Chip-seconds distribute the ledger's SETTLED productive seconds
    (times the chips the server spans) across tenants in proportion to
    their attributed serve tokens, so the per-tenant column plus the
    ``unattributed`` remainder always sums exactly to the ledger's
    productive chip-seconds — conservation by construction, checked in
    tests against both the ledger and the tenant-labeled
    ``serving_tenant_tokens_total`` counters."""
    if _LEDGER is None:
        secs, settled_el = {c: 0.0 for c in CATEGORIES}, 0.0
    else:
        secs, settled_el = _LEDGER.settled()
    chips = _chips("serve")
    prod_chip_s = secs.get("productive", 0.0) * chips
    serve_tok = _TOKENS.get("serve", 0)
    attr_tok = sum(_TENANT_TOKENS.values())
    # attribution base: every serve token the ledger saw; tenant-less
    # traffic lands in the unattributed bucket. A tenant total larger
    # than the serve total (possible only if a caller fed the meter
    # directly) still conserves: shares normalize over the larger sum.
    base = max(serve_tok, attr_tok)
    tenants = {}
    for t in sorted(_TENANT_TOKENS):
        tok = _TENANT_TOKENS[t]
        share = tok / base if base > 0 else 0.0
        tenants[t] = {"tokens": tok, "token_share": share,
                      "chip_seconds": share * prod_chip_s}
    unattr_tok = max(0, base - attr_tok)
    unattr_share = unattr_tok / base if base > 0 else 1.0
    return {"schema": 1,
            "chips": chips,
            "settled_elapsed_s": settled_el,
            "productive_chip_seconds": prod_chip_s,
            "serve_tokens": serve_tok,
            "tenants": tenants,
            "unattributed": {"tokens": unattr_tok,
                             "token_share": unattr_share,
                             "chip_seconds": unattr_share * prod_chip_s}}


def snapshot() -> dict:
    """Ledger snapshot (categories sum exactly to ``elapsed_s``)."""
    if _LEDGER is None:
        return {"elapsed_s": 0.0,
                "seconds": {c: 0.0 for c in CATEGORIES}}
    return _LEDGER.snapshot()


# -- persistence (rides the checkpoint manifest) ----------------------
def state_dict() -> dict:
    if _LEDGER is None:
        return {}
    st = _LEDGER.state_dict()
    st["tokens"] = dict(_TOKENS)
    st["tenant_tokens"] = dict(_TENANT_TOKENS)
    return st


def restore_state(st: dict) -> None:
    if not _ENABLED or _LEDGER is None or not st:
        return
    _LEDGER.restore_state(st)
    for k, v in (st.get("tokens") or {}).items():
        _TOKENS[k] = _TOKENS.get(k, 0) + int(v)
    for k, v in (st.get("tenant_tokens") or {}).items():
        _TENANT_TOKENS[k] = _TENANT_TOKENS.get(k, 0) + int(v)


# -- human-facing summary ---------------------------------------------
def format_summary() -> str:
    """Multi-line goodput summary (TrainLoop/Estimator exit print)."""
    if _LEDGER is None:
        return "goodput: ledger not enabled"
    snap = _LEDGER.snapshot()
    el = snap["elapsed_s"]
    secs = snap["seconds"]
    lines = [f"goodput over {el:.1f}s wall clock:"]
    for c in CATEGORIES:
        v = secs[c]
        if v <= 0.0 and c != "productive":
            continue
        lines.append(f"  {c:<18s} {v:10.2f}s  "
                     f"{100.0 * v / max(el, 1e-9):5.1f}%")
    if el > 0:
        for kind in ("train", "serve"):
            tok = _TOKENS.get(kind, 0)
            if tok:
                lines.append(f"  {kind} tokens/sec/chip: "
                             f"{tok / (el * _chips(kind)):.1f}")
    if _LAST_MFU is not None:
        lines.append(f"  MFU {100.0 * _LAST_MFU:.1f}% "
                     f"(analytic flops / {peak_flops() / 1e12:.0f} "
                     "TFLOPs/chip peak)")
    if _LAST_HFU is not None:
        lines.append(f"  HFU {100.0 * _LAST_HFU:.1f}% "
                     "(cost_analysis flops)")
    return "\n".join(lines)


def _breakdown_section() -> List[str]:
    """telemetry.breakdown_table() hook: compact goodput lines."""
    if _LEDGER is None:
        return []
    snap = _LEDGER.snapshot()
    el = max(snap["elapsed_s"], 1e-9)
    out = []
    for c in CATEGORIES:
        v = snap["seconds"][c]
        if v <= 0.0 and c != "productive":
            continue
        out.append((c, v))
    out.sort(key=lambda cv: -cv[1])
    return [f"  goodput {c:<18s} {v:9.2f}s {100.0 * v / el:5.1f}%"
            for c, v in out]


class PoolForecaster:
    """Time-to-exhaustion forecast over a shrinking block pool.

    O(1) ``add(t, blocks_free)`` per tick into a rolling window; a
    lazy least-squares fit turns the trend into seconds until
    ``blocks_free`` crosses zero. Registers as a telemetry health
    source: with ``critical_s`` set, ``/healthz`` flips not-ok when
    exhaustion is forecast inside that window; the serving
    ``health_detail`` carries ``exhaust_in_s`` either way so the
    ``FleetRouter`` can steer long-prompt work off the replica before
    it preempts.
    """

    def __init__(self, window: int = 64, min_samples: int = 8,
                 critical_s: Optional[float] = None,
                 name: str = "kv_pool"):
        self.window = int(window)
        self.min_samples = max(2, int(min_samples))
        self.critical_s = critical_s
        self.name = name
        self._samples = deque(maxlen=self.window)

    def add(self, t: float, blocks_free: float) -> None:
        self._samples.append((float(t), float(blocks_free)))

    def _fit(self) -> Optional[Tuple[float, float]]:
        """(slope blocks/s, intercept at the window's first sample)."""
        n = len(self._samples)
        if n < self.min_samples:
            return None
        t0 = self._samples[0][0]
        sx = sy = sxx = sxy = 0.0
        for t, y in self._samples:
            x = t - t0
            sx += x
            sy += y
            sxx += x * x
            sxy += x * y
        denom = n * sxx - sx * sx
        if denom <= 1e-12:
            return None
        slope = (n * sxy - sx * sy) / denom
        intercept = (sy - slope * sx) / n
        return slope, intercept

    def exhaust_in_s(self,
                     now: Optional[float] = None) -> Optional[float]:
        """Seconds until the pool is forecast empty; None when the
        trend is flat/recovering or the window is too thin."""
        fit = self._fit()
        if fit is None:
            return None
        slope, intercept = fit
        if slope >= -1e-9:
            return None
        t0 = self._samples[0][0]
        now = self._samples[-1][0] if now is None else float(now)
        free_now = intercept + slope * (now - t0)
        if free_now <= 0.0:
            return 0.0
        return free_now / -slope

    # -- telemetry health-source protocol -----------------------------
    def health(self) -> Tuple[bool, str]:
        if self.critical_s is not None:
            eta = self.exhaust_in_s()
            if eta is not None and eta < self.critical_s:
                return False, (f"{self.name} exhaustion forecast in "
                               f"{eta:.1f}s (< {self.critical_s:.0f}s)")
        return True, "ok"

    def health_detail(self) -> dict:
        ok, reason = self.health()
        fit = self._fit()
        last = self._samples[-1] if self._samples else (0.0, 0.0)
        return {"ok": ok, "reason": reason,
                "samples": len(self._samples),
                "blocks_free": last[1],
                "slope_blocks_per_s": fit[0] if fit else None,
                "exhaust_in_s": self.exhaust_in_s()}


if os.environ.get("MXNET_TPU_GOODPUT", "").lower() in ("1", "true",
                                                       "yes"):
    enable()

