"""Resilient serving fleet: a health-gated router over N replicas.

One `InferenceServer` is a replica; this module is the fleet. The
`FleetRouter` admits requests over N replicas — in the same process
(`LocalReplica`, the router drives each server's tick itself) or in
other processes (`ProcReplica`, speaking a kv message channel) — with
robustness as the first-class design axis:

- **Least-loaded admission** scored from the gauges each replica
  already exports (`health_detail()`: queue age p50/p95, blocks-free,
  queued/active vs slots) — the same numbers the `/healthz` JSON body
  carries, so a replica is scored by ONE probe.
- **Prefix-affinity routing**: the prompt's leading block-sized chunks
  are exactly the prefix cache's chain keys (`kv_cache.PagedKVCache`
  content index), so hashing them routes repeated system prompts to
  the replica that already holds the shared blocks. Affinity degrades
  to least-loaded the moment the target is unhealthy or saturated.
- **Health tracking + circuit breaker** per replica: detail probes and
  heartbeat staleness classify each replica HEALTHY / DRAINING /
  UNHEALTHY / DEAD (`router_replica_health` gauge); consecutive
  failures open a breaker (open → half-open probe → close).
- **Failover with capped-exponential-backoff retries**: unfinished
  requests on a dead/stalled replica are resubmitted elsewhere under
  an idempotency token — first completed attempt wins, late
  duplicates are ignored, so no request is lost or double-counted
  (`serve_failovers_total`, `serve_retries_total`).
- **Hedged requests**: a request stuck in flight past the fleet
  queue-age p95 (or a fixed threshold) is duplicated on a second
  replica; first responder wins, the loser is cancelled through
  `InferenceServer.cancel` (`serve_hedges_total{won}`).
- **Load shedding**: the fleet queue is bounded; at saturation
  `submit()` returns the request already terminal with status
  ``rejected`` instead of queueing forever (`serve_shed_total`).
- **Drain-aware rolling restart**: flip one replica to draining (its
  health source now reports not-ready, so admission stops), wait for
  its in-flight work, restart it, wait until healthy, move on.

The channel behind `ProcReplica` is the PR-10 coordination-service
side channel's kv semantics (`set` / blocking `get` / `dir` prefix
scan), with two backends:

- `CoordKV` — `multihost.kv_set/kv_get/kv_dir_get`: for pods, where
  every replica already joined one `jax.distributed` job. Note the
  coordination service itself force-terminates surviving clients when
  a member dies, so this backend suits drain/rolling-restart flows,
  not SIGKILL failover.
- `FileKV` — the same semantics over a shared directory with
  atomic-rename writes: kill-tolerant, so the SIGKILL fleet tests
  ride it.

Fault sites (armed via `MXNET_TPU_FAULTS`, see `mxnet_tpu.faults`):
``replica.kill`` (worker dies after a productive tick — in-process,
the handle is marked dead), ``replica.stall`` (worker sleeps ``ms`` /
handle skips ``ticks``), ``replica.degrade`` (short ``ms`` sleep per
productive tick — latency inflates but heartbeats keep flowing, the
degraded-but-alive adversary for the anomaly outlier detector and the
canary gate), ``router.drop`` (a completed attempt's result is
discarded, exercising retry + idempotency).

Worker side: `run_fleet_worker(channel, name, ...)` drives one server
against the channel protocol; ``python -m mxnet_tpu.serving.router
--dir D --name r0`` is the subprocess entry the tests spawn.

Fleet observability (telemetry-gated end to end):

- **Distributed tracing**: every attempt is stamped with the
  idempotency token as trace context; workers ship the finished
  request's span timeline back inside the ``res/<token>`` payload, and
  heartbeats carry a paired perf/wall clock anchor recorded at worker
  warm-up, so `FleetRouter.trace(id)` merges router queue wait, the
  routing decision, every retry/hedge/failover attempt (replica id +
  outcome) and the winner's prefill/decode spans onto ONE wall-clock
  axis. `telemetry.export_chrome_trace` renders the merged timelines
  with a router pid plus one pid per replica.
- **Fleet metrics**: heartbeats piggyback bounded, delta-encoded
  registry snapshots (`telemetry.registry_delta`); the router merges
  them bucket-exactly (`fleet_registry`) and
  `FleetRouter.start_metrics_server` serves the fleet view on
  /metrics with ``replica=<name>`` gauge labels.
- **SLO engine**: `attach_slo` wires an `mxnet_tpu.slo.SLOEngine` to
  the fleet-merged registry, ticks it from `step()`, flips /healthz to
  degraded while an alert fires, and collects a cross-process flight
  bundle (`collect_flight_bundle` -> ``flight-bundle-<reason>/``,
  stitched by ``python -m mxnet_tpu.flight merge``).

Cost contract: all router telemetry/flight calls are gated on the
module flags (`telemetry._ENABLED` / `_fl._ENABLED` / `_ft._ACTIVE`),
AST-enforced by tests/test_telemetry_lint.py.
"""
from __future__ import annotations

import json
import os
import signal as _signal_mod
import time
import uuid
from collections import OrderedDict, deque
from typing import Callable, Dict, List, Optional

import numpy as np

from .. import faults as _ft
from .. import flight as _fl
from .. import telemetry
from .lora import priority_rank
from .server import InferenceServer

__all__ = ["FleetRouter", "FleetRequest", "LocalReplica", "ProcReplica",
           "CircuitBreaker", "FileKV", "CoordKV", "RouterStalledError",
           "run_fleet_worker",
           "HEALTHY", "DRAINING", "UNHEALTHY", "DEAD"]

#: replica health states (the `router_replica_health` gauge value)
HEALTHY, DRAINING, UNHEALTHY, DEAD = 0, 1, 2, 3
_STATE_NAMES = {HEALTHY: "healthy", DRAINING: "draining",
                UNHEALTHY: "unhealthy", DEAD: "dead"}

#: fleet-level terminal statuses; "ok"/"timed_out"/"cancelled" mirror
#: the server's, "rejected" is the shed outcome, "failed" means the
#: retry budget ran out
_OK, _REJECTED, _FAILED, _TIMED_OUT, _CANCELLED = \
    "ok", "rejected", "failed", "timed_out", "cancelled"


class RouterStalledError(RuntimeError):
    """The fleet made no progress for `watchdog_s` seconds with work
    pending — every replica is dead/wedged and retries are parked.
    Raised out of step()/run() so a supervisor restarts the fleet."""


# -- the kv channel ----------------------------------------------------------

class FileKV:
    """The coordination channel's kv semantics over a shared directory:
    `set` is write-to-temp + atomic rename (readers never see a torn
    value), `get` polls for the key up to `timeout_ms`, `dir` is a
    non-blocking prefix scan. Keys are slash-separated paths. Unlike
    the coordination service, a SIGKILLed participant takes nothing
    else down — this is the kill-tolerant backend the fleet tests
    use."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)

    def _path(self, key: str) -> str:
        p = os.path.normpath(os.path.join(self.root, key.lstrip("/")))
        if not p.startswith(self.root):
            raise ValueError(f"key {key!r} escapes the channel root")
        return p

    def set(self, key: str, value: str):
        path = self._path(key)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.__tmp{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(value)
        os.replace(tmp, path)

    def get(self, key: str, timeout_ms: int = 0) -> Optional[str]:
        deadline = time.perf_counter() + timeout_ms / 1e3
        path = self._path(key)
        while True:
            try:
                with open(path) as f:
                    return f.read()
            except OSError:
                pass
            if time.perf_counter() >= deadline:
                return None
            time.sleep(0.001)

    def dir(self, prefix: str) -> List[tuple]:
        d = self._path(prefix)
        out = []
        if not os.path.isdir(d):
            return out
        for name in sorted(os.listdir(d)):
            if "__tmp" in name:
                continue        # in-flight write, not yet renamed
            full = os.path.join(d, name)
            if not os.path.isfile(full):
                continue
            try:
                with open(full) as f:
                    out.append((prefix.rstrip("/") + "/" + name,
                                f.read()))
            except OSError:
                pass
        return out

    def delete(self, key: str) -> bool:
        try:
            os.remove(self._path(key))
            return True
        except OSError:
            return False


class CoordKV:
    """The same channel interface over the jax coordination-service kv
    store (`multihost.kv_set/kv_get/kv_dir_get`) — for pod fleets where
    every replica already joined one `jax.distributed` job. The service
    tears down surviving clients when a member SIGKILLs, so use this
    backend for drain/rolling-restart flows and `FileKV` for
    kill-failover testing."""

    def set(self, key: str, value: str):
        from ..parallel import multihost as _mh
        _mh.kv_set(key, value)

    def get(self, key: str, timeout_ms: int = 0) -> Optional[str]:
        from ..parallel import multihost as _mh
        return _mh.kv_get(key, timeout_ms=max(1, int(timeout_ms)))

    def dir(self, prefix: str) -> List[tuple]:
        from ..parallel import multihost as _mh
        return _mh.kv_dir_get(prefix)

    def delete(self, key: str) -> bool:
        from ..parallel import multihost as _mh
        return _mh.kv_delete(key)


# -- circuit breaker ---------------------------------------------------------

class CircuitBreaker:
    """Per-replica circuit breaker: `threshold` consecutive failures
    open it (admission stops); after `cooldown_s` one probe request is
    allowed through (half-open); that probe's success closes the
    breaker, its failure re-opens it. All transitions take the caller's
    `now` so tests drive the state machine with a fake clock."""

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"

    def __init__(self, threshold: int = 3, cooldown_s: float = 1.0):
        self.threshold = int(threshold)
        self.cooldown_s = float(cooldown_s)
        self.state = self.CLOSED
        self.failures = 0
        self._opened_t = 0.0
        self._probe_out = False

    def allow(self, now: float) -> bool:
        """May a request be routed here right now? Consumes the single
        half-open probe slot when it grants one."""
        if self.state == self.CLOSED:
            return True
        if self.state == self.OPEN:
            if now - self._opened_t >= self.cooldown_s:
                self.state = self.HALF_OPEN
                self._probe_out = True
                return True
            return False
        if not self._probe_out:         # half-open, probe slot free
            self._probe_out = True
            return True
        return False

    def record_success(self):
        self.state = self.CLOSED
        self.failures = 0
        self._probe_out = False

    def record_failure(self, now: float):
        self.failures += 1
        if self.state == self.HALF_OPEN or \
                self.failures >= self.threshold:
            self.state = self.OPEN
            self._opened_t = now
            self._probe_out = False


# -- requests ----------------------------------------------------------------

class FleetRequest:
    """One fleet-level request: prompt + sampling params + lifecycle.
    `token` is the idempotency token every attempt carries — results
    are deduped on it, so a request resubmitted after a failover (or
    hedged) completes exactly once."""

    _next_id = 0

    def __init__(self, prompt, max_new_tokens: int, temperature=0.0,
                 top_k=0, top_p=0.0, eos_id=None, seed=0,
                 deadline_s=None, tenant=None, priority=None,
                 adapter=None):
        self.id = FleetRequest._next_id
        FleetRequest._next_id += 1
        self.token = f"q{self.id}-{uuid.uuid4().hex[:8]}"
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        # tenant/priority/adapter ride params so LocalReplica and the
        # ProcReplica wire protocol ship them without a second channel
        self.params = {"temperature": float(temperature),
                       "top_k": int(top_k), "top_p": float(top_p),
                       "eos_id": eos_id, "seed": int(seed),
                       "tenant": tenant, "priority": priority,
                       "adapter": adapter}
        self.state = "queued"           # queued | inflight | finished
        #: terminal: "ok" | "rejected" | "failed" | "timed_out" |
        #: "cancelled"; None while live
        self.status: Optional[str] = None
        self.finish_reason: Optional[str] = None
        self.output_tokens: List[int] = []
        #: fleet-level time-to-first-token of the WINNING attempt:
        #: router queue wait + the replica's own TTFT (when reported)
        self.ttft_s: Optional[float] = None
        self.replica: Optional[str] = None      # who served the winner
        self.tries = 0                  # attempts started (incl. hedges)
        self.retries = 0                # re-dispatches after a failure
        self.hedged = False
        self.attempts: List["_Attempt"] = []
        #: disaggregated serving: the serialized KV-block wire payload
        #: produced by a prefill replica (None = not yet / not
        #: disaggregating, "" = disaggregation fell back to a combined
        #: replica — don't try again)
        self.kv_wire: Optional[str] = None
        #: distributed-trace record, one entry per attempt (replica,
        #: routing decision, outcome, shipped worker timeline + clock
        #: offset); only populated while telemetry is enabled
        self.attempt_log: List[dict] = []
        self.next_eligible_t = 0.0
        self.t_submit = time.time()
        self.t_deadline = None if deadline_s is None \
            else self.t_submit + float(deadline_s)
        self.t_finish: Optional[float] = None

    @property
    def terminal(self) -> bool:
        return self.status is not None

    def tokens(self) -> np.ndarray:
        """prompt + generated tokens, 1-D int32 (server parity)."""
        return np.concatenate(
            [self.prompt, np.asarray(self.output_tokens, np.int32)])

    def __repr__(self):
        return (f"FleetRequest(token={self.token}, state={self.state}, "
                f"status={self.status}, tries={self.tries})")


class _Attempt:
    """One dispatch of a request to one replica."""
    __slots__ = ("rep", "sub", "t0", "hedge", "log")

    def __init__(self, rep, sub, t0, hedge):
        self.rep = rep
        self.sub = sub
        self.t0 = t0
        self.hedge = hedge
        self.log: Optional[dict] = None     # its fr.attempt_log entry


# -- replica handles ---------------------------------------------------------

class LocalReplica:
    """An in-process `InferenceServer` behind the replica interface:
    probes are synchronous `health_detail()` calls, `drive()` runs one
    scheduler tick, poll/cancel act on the server's Request objects.
    `factory` (a zero-arg server builder) enables `restart()` for the
    rolling-restart flow."""

    def __init__(self, server: Optional[InferenceServer] = None,
                 factory: Optional[Callable[[], InferenceServer]] = None,
                 name: Optional[str] = None,
                 role: Optional[str] = None,
                 spot: bool = False):
        if server is None:
            if factory is None:
                raise ValueError("need a server or a factory")
            server = factory()
        self.server = server
        self.factory = factory
        self.name = name or f"local{id(server) & 0xffff:x}"
        #: disaggregated serving role ("prefill" | "decode" | None =
        #: combined); the router's `disaggregate` flow keys off this
        self.role = role
        #: preemptible capacity: `replica.spot_preempt` reclaims only
        #: spot-marked replicas, and the autoscaler prefers them as
        #: scale-in victims
        self.spot = spot
        self.dead = False
        self.restarts = 0
        self._stall_ticks_left = 0
        #: `replica.degrade` arm: sleep this long before every drive
        #: tick — latency inflates, health/probes keep answering
        self._degrade_ms = 0.0
        self._dropped = set()           # sub ids with discarded results

    def probe(self, now: float) -> Optional[dict]:
        if self.dead:
            return None                 # no heartbeat from the dead
        d = self.server.health_detail()
        d["t"] = now
        # paired clock anchor (same-process, so sampled fresh): lets
        # the router convert the server's perf_counter span timestamps
        # to wall clock, mirroring the ProcReplica handshake
        d["clock"] = {"perf": time.perf_counter(), "unix": time.time()}
        return d

    def submit(self, fr: FleetRequest, attempt_key: str,
               deadline_s: Optional[float]):
        if self.dead:
            raise RuntimeError(f"replica {self.name} is dead")
        wire = getattr(fr, "kv_wire", None)
        if wire:
            # streamed prefill: adopt the shipped KV blocks into the
            # host tier BEFORE admission, so the prefix match covers
            # the prompt and prefill is skipped (adoption is
            # best-effort — a mismatched wire just means a cold
            # prefill, never a failed request)
            try:
                self.server.adopt_wire_blocks(wire)
            except Exception:
                pass
        req = self.server.submit(
            fr.prompt, fr.max_new_tokens,
            temperature=fr.params["temperature"],
            top_k=fr.params["top_k"], top_p=fr.params["top_p"],
            eos_id=fr.params["eos_id"], seed=fr.params["seed"],
            deadline_s=deadline_s, trace_ctx=attempt_key,
            tenant=fr.params.get("tenant"),
            priority=fr.params.get("priority"),
            adapter=fr.params.get("adapter"))
        return req

    def prefill_export(self, fr: FleetRequest, key: str):
        """Start a prefill-and-export job: run the prompt through this
        replica's prefill (one generated token, discarded) so its KV
        blocks land in the prefix cache, ready to serialize. Returns a
        job handle for `poll_export`."""
        if self.dead:
            raise RuntimeError(f"replica {self.name} is dead")
        req = self.server.submit(fr.prompt, 1,
                                 seed=fr.params["seed"], trace_ctx=key)
        return (req, fr.prompt)

    def poll_export(self, job) -> Optional[str]:
        """None while the prefill is still running; the wire payload
        once exported; "" when the export failed (caller falls back to
        combined serving)."""
        req, prompt = job
        if req.state != "finished":
            return None
        if req.status != "ok":
            return ""
        return self.server.export_prefix(prompt) or ""

    def drive(self) -> int:
        """One scheduler tick (0 tokens when dead/stalled/idle)."""
        if self.dead:
            return 0
        if self._stall_ticks_left > 0:
            self._stall_ticks_left -= 1
            return 0
        if self.server.queue or self.server._active.any():
            if self._degrade_ms > 0:
                time.sleep(self._degrade_ms / 1e3)
            return self.server.step()
        return 0

    def poll(self, sub) -> Optional[dict]:
        if sub.state != "finished" or id(sub) in self._dropped:
            return None
        res = {"status": sub.status,
               "tokens": [int(t) for t in sub.output_tokens],
               "finish_reason": sub.finish_reason,
               "ttft": getattr(sub, "ttft", None)}
        if telemetry._ENABLED:
            tr = self.server.trace(sub.id)
            if tr is not None:
                res["trace"] = tr
        return res

    def discard(self, sub):
        """Forget a result (the `router.drop` fault's sink)."""
        self._dropped.add(id(sub))

    def cancel(self, sub):
        self.server.cancel(sub.id)

    def begin_drain(self):
        self.server.begin_drain()

    def end_drain(self):
        self.server.end_drain()

    def restart(self):
        if self.factory is None:
            raise RuntimeError(
                f"replica {self.name} has no factory — cannot restart")
        telemetry.unregister_health_source(self.server)
        self.server = self.factory()
        self.dead = False
        self._stall_ticks_left = 0
        self._degrade_ms = 0.0
        self._dropped.clear()
        self.restarts += 1


class ProcReplica:
    """A replica living in another process, spoken to over the kv
    channel under namespace ``fleet/<name>``:

    - ``cmd/<seq>``: router → worker command stream (submit / cancel /
      drain / undrain / restart / stop), consumed in order.
    - ``res/<attempt-token>``: worker → router per-attempt results.
    - ``hb``: worker → router heartbeat — the `health_detail()` dict
      plus a wall-clock stamp; staleness past `heartbeat_timeout_s`
      (router-side) is how a SIGKILLed worker is detected.
    - ``kv/<token>``: worker → router exported KV-block wire payloads
      (disaggregated prefill; "" marks a failed export).
    """

    def __init__(self, channel, name: str,
                 role: Optional[str] = None,
                 spot: bool = False):
        self.channel = channel
        self.name = name
        self.role = role
        self.spot = spot                # preemptible capacity
        self.ns = f"fleet/{name}"
        self.dead = False               # router marks on staleness
        self._cmd_seq = 0
        self._results: Dict[str, dict] = {}
        self._dropped = set()

    def _send(self, obj: dict):
        self.channel.set(f"{self.ns}/cmd/{self._cmd_seq}",
                         json.dumps(obj))
        self._cmd_seq += 1

    def probe(self, now: float) -> Optional[dict]:
        raw = self.channel.get(f"{self.ns}/hb", timeout_ms=0)
        if raw is None:
            return None
        try:
            return json.loads(raw)
        except ValueError:
            return None

    def submit(self, fr: FleetRequest, attempt_key: str,
               deadline_s: Optional[float]):
        cmd = {"op": "submit", "token": attempt_key,
               "prompt": [int(t) for t in fr.prompt],
               "max_new": fr.max_new_tokens,
               "deadline_s": deadline_s, **fr.params}
        wire = getattr(fr, "kv_wire", None)
        if wire:
            cmd["kv"] = wire            # worker adopts before admit
        self._send(cmd)
        return attempt_key

    def prefill_export(self, fr: FleetRequest, key: str):
        self._send({"op": "prefill_export", "token": key,
                    "prompt": [int(t) for t in fr.prompt],
                    "seed": fr.params["seed"]})
        return key

    def poll_export(self, job) -> Optional[str]:
        return self.channel.get(f"{self.ns}/kv/{job}", timeout_ms=0)

    def drive(self) -> int:
        return 0                        # the worker drives itself

    def fetch_results(self):
        """Pull newly published results from the channel (one prefix
        scan per router tick)."""
        for key, val in self.channel.dir(f"{self.ns}/res/"):
            tok = key.rsplit("/", 1)[-1]
            if tok in self._results or tok in self._dropped:
                continue
            try:
                self._results[tok] = json.loads(val)
            except ValueError:
                pass

    def poll(self, sub) -> Optional[dict]:
        return self._results.get(sub)

    def discard(self, sub):
        self._results.pop(sub, None)
        self._dropped.add(sub)          # don't re-fetch from the file

    def cancel(self, sub):
        self._send({"op": "cancel", "token": sub})

    def begin_drain(self):
        self._send({"op": "drain"})

    def end_drain(self):
        self._send({"op": "undrain"})

    def restart(self):
        self._send({"op": "restart"})
        self.dead = False

    def stop(self):
        self._send({"op": "stop"})

    def final_stats(self, timeout_ms: int = 10_000) -> Optional[dict]:
        """The worker's closing `stats()` dump (published on stop)."""
        raw = self.channel.get(f"{self.ns}/stats",
                               timeout_ms=timeout_ms)
        return None if raw is None else json.loads(raw)


class _Rep:
    """Router-side per-replica state: the handle plus everything the
    router derives about it."""
    __slots__ = ("handle", "name", "breaker", "state", "detail",
                 "last_seen", "attempts", "clock_offset", "tm_state",
                 "hb_seq")

    def __init__(self, handle, breaker, now):
        self.handle = handle
        self.name = handle.name
        self.breaker = breaker
        self.state = UNHEALTHY          # until the first good probe
        self.detail: Optional[dict] = None
        self.last_seen = now            # heartbeat staleness baseline
        self.attempts: Dict[int, tuple] = {}    # id(att) -> (fr, att)
        #: unix - perf_counter offset from the replica's clock anchor
        #: (the cross-process trace alignment handshake)
        self.clock_offset: Optional[float] = None
        #: latest heartbeat-shipped registry state, family -> blob
        self.tm_state: Dict[str, dict] = {}
        self.hb_seq = None              # last heartbeat seq applied


class _CanaryState:
    """One replica under canary analysis after a gated restart:
    the spec, the running `CanaryAnalysis`, and the stride counter
    that meters the replica's routing weight."""
    __slots__ = ("spec", "analysis", "bundle_dir", "tokens")

    def __init__(self, spec, analysis, bundle_dir=None):
        self.spec = spec
        self.analysis = analysis
        self.bundle_dir = bundle_dir
        self.tokens = 0.0


# -- the router --------------------------------------------------------------

class FleetRouter:
    """Health-gated request router over a fleet of replicas.

        fleet = FleetRouter([LocalReplica(s1), LocalReplica(s2)])
        reqs = [fleet.submit(p, max_new_tokens=16) for p in prompts]
        fleet.run()
        for r in reqs: print(r.status, r.tokens())

    Robustness knobs (see the module docstring for semantics):
    `max_fleet_queue` bounds the fleet queue (overflow sheds with
    status ``rejected``); `max_retries` / `backoff_base_s` /
    `backoff_max_s` shape the capped-exponential retry schedule;
    `hedge_after_s` (None = off, float = fixed, ``"auto"`` = fleet
    queue-age p95 floored at `hedge_min_s`) arms hedging;
    `attempt_timeout_s` bounds one attempt's in-flight time;
    `heartbeat_timeout_s` declares a silent ProcReplica dead;
    `breaker_threshold` / `breaker_cooldown_s` shape the circuit
    breaker; `affinity_blocks` is how many leading prompt blocks feed
    the prefix-affinity hash (0 disables affinity);
    `exhaust_window_s` (None = off) arms memory-pressure steering — a
    replica whose heartbeat forecasts KV-pool exhaustion within the
    window (the `exhaust_in_s` health detail from the goodput
    forecaster) stops receiving prompts of `long_prompt_blocks` blocks
    or more BEFORE it has to preempt; short prompts still land, and if
    every eligible replica is at risk the filter is dropped
    (availability over protection);
    `disaggregate` arms prefill/decode disaggregation — a queued
    request is first prefilled on a ``role="prefill"`` replica, its KV
    blocks exported over the kv channel, then dispatched (wire
    attached) to a ``role="decode"`` replica that adopts the blocks
    and skips prefill; when no prefill replica is eligible the request
    falls back to ordinary least-loaded combined serving."""

    def __init__(self, replicas, *,
                 max_fleet_queue: int = 256,
                 per_replica_queue: Optional[int] = None,
                 max_retries: int = 3,
                 backoff_base_s: float = 0.02,
                 backoff_max_s: float = 1.0,
                 hedge_after_s=None,
                 hedge_min_s: float = 0.05,
                 attempt_timeout_s: Optional[float] = None,
                 heartbeat_timeout_s: float = 2.0,
                 breaker_threshold: int = 3,
                 breaker_cooldown_s: float = 0.5,
                 affinity_blocks: int = 2,
                 affinity_capacity: int = 4096,
                 block_size: int = 16,
                 exhaust_window_s: Optional[float] = None,
                 long_prompt_blocks: int = 4,
                 disaggregate: bool = False,
                 watchdog_s: float = 120.0,
                 poll_s: float = 0.002):
        if not replicas:
            raise ValueError("need at least one replica")
        now = time.time()
        self.breaker_threshold = int(breaker_threshold)
        self.breaker_cooldown_s = float(breaker_cooldown_s)
        self._reps = [_Rep(h, CircuitBreaker(breaker_threshold,
                                             breaker_cooldown_s), now)
                      for h in replicas]
        names = [r.name for r in self._reps]
        if len(set(names)) != len(names):
            raise ValueError(f"replica names must be unique: {names}")
        self.max_fleet_queue = int(max_fleet_queue)
        self.per_replica_queue = per_replica_queue
        self.max_retries = int(max_retries)
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_max_s = float(backoff_max_s)
        self.hedge_after_s = hedge_after_s
        self.hedge_min_s = float(hedge_min_s)
        self.attempt_timeout_s = attempt_timeout_s
        self.heartbeat_timeout_s = float(heartbeat_timeout_s)
        self.affinity_blocks = int(affinity_blocks)
        self.affinity_capacity = int(affinity_capacity)
        self.block_size = int(block_size)
        self.exhaust_window_s = exhaust_window_s
        self.long_prompt_blocks = int(long_prompt_blocks)
        self.disaggregate = bool(disaggregate)
        #: fr.token -> (fr, rep, job, t0): prefill-export jobs in
        #: flight on prefill-role replicas
        self._prefill_jobs: Dict[str, tuple] = {}
        self.watchdog_s = float(watchdog_s)
        self.poll_s = float(poll_s)
        self._queue: deque = deque()
        self._inflight: Dict[str, FleetRequest] = {}
        self.finished: List[FleetRequest] = []
        self._affinity: "OrderedDict[int, _Rep]" = OrderedDict()
        self.ticks = 0
        self._last_progress_t = now
        # python-side counters mirroring the telemetry ones, so
        # stats() answers even with telemetry disabled
        self.n_shed = 0
        self.n_adapter_misses = 0
        self.n_retries = 0
        self.n_failovers = 0
        self.n_hedges = 0
        self.n_duplicates = 0
        self.n_prefill_exports = 0
        self.n_stream_dispatches = 0
        self.n_disagg_fallbacks = 0
        self.n_canary_rollbacks = 0
        self.n_canary_promotions = 0
        self._pick_how = "least_loaded"     # last routing decision
        self._slo = None                    # attach_slo() sets this
        self._anomaly = None                # attach_anomaly() sets this
        self._autoscaler = None             # attach_autoscale() sets this
        #: priority-class admission floor (None = open door): submits
        #: whose declared class ranks BELOW this class are shed on
        #: arrival — the autoscaler raises it when even max_replicas
        #: can't hold the SLO, so overload costs batch, not interactive
        self.admission_floor: Optional[str] = None
        #: replica name -> _CanaryState while under canary analysis
        self._canaries: Dict[str, _CanaryState] = {}
        self._bundle_seq = 0
        self.last_bundle_path: Optional[str] = None
        telemetry.register_fleet_trace_source(self)

    # -- intake --------------------------------------------------------------

    def _shed(self, fr: FleetRequest):
        """Terminate one request as shed (status ``rejected``, reason
        ``shed``) — class-labeled so dashboards see WHO overload is
        costing."""
        fr.state = "finished"
        fr.status = _REJECTED
        fr.finish_reason = "shed"
        fr.t_finish = time.time()
        self.finished.append(fr)
        self.n_shed += 1
        if telemetry._ENABLED:
            telemetry.inc("serve_shed_total")
            telemetry.inc(
                "serve_shed_total",
                **{"class": fr.params.get("priority") or "standard"})
        if _fl._ENABLED:
            _fl.record("route", "router.shed", token=fr.token,
                       queued=len(self._queue),
                       priority=fr.params.get("priority"))

    def submit(self, prompt_ids, max_new_tokens: int,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 0.0, eos_id: Optional[int] = None,
               seed: int = 0,
               deadline_s: Optional[float] = None,
               tenant: Optional[str] = None,
               priority: Optional[str] = None,
               adapter: Optional[str] = None) -> FleetRequest:
        """Enqueue one request on the fleet. Under saturation (the
        bounded fleet queue is full) shedding is by PRIORITY CLASS,
        not FIFO: if some queued request ranks below the newcomer, the
        lowest-ranked most-recently-queued one is shed to make room;
        otherwise the newcomer itself is shed. Either way the shed
        request is returned/left already terminal with status
        ``rejected`` — shedding never raises, so drivers can count
        rejections like any other outcome. When `admission_floor`
        is set (the autoscaler's maxed-and-still-burning response),
        requests whose class ranks below the floor are shed at the
        door before consuming a queue slot. ``tenant`` / ``priority``
        / ``adapter`` forward to the serving replica (tenant QoS +
        batched LoRA); the adapter must be hot-loaded on the replicas
        that will serve it."""
        fr = FleetRequest(prompt_ids, max_new_tokens, temperature,
                          top_k, top_p, eos_id, seed, deadline_s,
                          tenant=tenant, priority=priority,
                          adapter=adapter)
        if self.admission_floor is not None \
                and priority_rank(priority) \
                < priority_rank(self.admission_floor):
            self._shed(fr)              # class-aware overload: at the
            return fr                   # door, before any queue slot
        if len(self._queue) >= self.max_fleet_queue:
            rank = priority_rank(priority)
            victim = None
            for i in range(len(self._queue) - 1, -1, -1):
                q = self._queue[i]
                qr = priority_rank(q.params.get("priority"))
                if qr < rank and (victim is None or qr < victim[1]):
                    victim = (i, qr)
            if victim is None:
                self._shed(fr)
                return fr
            shed_fr = self._queue[victim[0]]
            del self._queue[victim[0]]
            self._shed(shed_fr)
        self._queue.append(fr)
        return fr

    # -- one scheduling tick -------------------------------------------------

    def step(self) -> int:
        """One router tick: refresh health, fail over the dead,
        dispatch, drive local replicas, collect results, hedge.
        Returns a progress count (dispatches + tokens + deliveries)."""
        now = time.time()
        if _ft._ACTIVE and self._reps:
            sp = _ft.fire("replica.kill")
            if sp is not None:
                self._kill_replica(int(sp.get("replica", 0)))
            sp = _ft.fire("replica.stall")
            if sp is not None:
                h = self._reps[int(sp.get("replica", 0))
                               % len(self._reps)].handle
                if hasattr(h, "_stall_ticks_left"):
                    h._stall_ticks_left = int(sp.get("ticks", 1 << 30))
            sp = _ft.fire("replica.degrade")
            if sp is not None:
                h = self._reps[int(sp.get("replica", 0))
                               % len(self._reps)].handle
                if hasattr(h, "_degrade_ms"):
                    h._degrade_ms = float(sp.get("ms", 50))
            sp = _ft.fire("replica.spot_preempt")
            if sp is not None:
                self._spot_preempt(int(sp.get("replica", 0)))
        self._refresh(now)
        progress = self._failover_dead(now)
        self._expire(now)
        if self.disaggregate:
            progress += self._prefill_tick(now)
        progress += self._dispatch(now)
        progress += self._drive(now)
        progress += self._collect(now)
        progress += self._hedge(now)
        self.ticks += 1
        self._note_progress(progress, now)
        if self._slo is not None and telemetry._ENABLED:
            self._slo.tick()
        if self._anomaly is not None and telemetry._ENABLED:
            self._anomaly.tick()
        if self._autoscaler is not None:
            # NOT telemetry-gated: the autoscaler drives real capacity
            # (its own emissions are gated internally)
            self._autoscaler.tick(now)
        if self._canaries:
            self._canary_tick(now)
        return progress

    def run(self, max_ticks: Optional[int] = None,
            timeout_s: Optional[float] = None) -> List[FleetRequest]:
        """Step until every submitted request is terminal (or a
        bound). Returns the requests finished during this call."""
        done0 = len(self.finished)
        t0 = time.time()
        ticks = 0
        while self._queue or self._inflight:
            progress = self.step()
            ticks += 1
            if max_ticks is not None and ticks >= max_ticks:
                break
            if timeout_s is not None and time.time() - t0 > timeout_s:
                break
            if not progress:
                time.sleep(self.poll_s)
        return self.finished[done0:]

    # -- health --------------------------------------------------------------

    def _refresh(self, now: float):
        for rep in self._reps:
            h = rep.handle
            if isinstance(h, ProcReplica):
                h.fetch_results()
            try:
                d = h.probe(now)
            except Exception:
                d = None
            if d is not None:
                rep.detail = d
                rep.last_seen = float(d.get("t", now))
                ck = d.get("clock")
                if ck is not None:
                    rep.clock_offset = (float(ck.get("unix", 0.0))
                                        - float(ck.get("perf", 0.0)))
                seq = d.get("hb_seq")
                if seq is None or seq != rep.hb_seq:
                    rep.hb_seq = seq
                    tm = d.get("tm")
                    if tm:
                        for fam_name, st in tm.items():
                            if st is None:
                                rep.tm_state.pop(fam_name, None)
                            else:
                                rep.tm_state[fam_name] = st
            if isinstance(h, ProcReplica) and rep.detail is not None:
                # heartbeat staleness is the liveness signal for a
                # remote worker — and a fresh beat REVIVES one that was
                # only stalled (a never-seen worker is "starting", not
                # dead). LocalReplica.dead stays sticky until restart.
                h.dead = now - rep.last_seen > self.heartbeat_timeout_s
                if rep.detail.get("goodbye"):
                    # the worker's parting beat (spot preemption /
                    # SIGTERM): it told us it is gone — don't wait out
                    # heartbeat staleness, and don't let the fresh
                    # stamp revive it
                    h.dead = True
            if getattr(h, "dead", False):
                state = DEAD
            elif rep.detail is None:
                state = UNHEALTHY
            elif rep.detail.get("draining"):
                state = DRAINING
            elif not rep.detail.get("ok", False) or \
                    rep.breaker.state != CircuitBreaker.CLOSED:
                state = UNHEALTHY
            else:
                state = HEALTHY
            if state != rep.state:
                if _fl._ENABLED:
                    _fl.record("route", "router.health",
                               replica=rep.name,
                               state=_STATE_NAMES[state],
                               was=_STATE_NAMES[rep.state])
                rep.state = state
                if state == DEAD:
                    # terminal state: drop the replica's labeled series
                    # (and its heartbeat-shipped registry contribution)
                    # instead of leaving stale rows in /metrics forever
                    rep.tm_state.clear()
                    if telemetry._ENABLED:
                        telemetry.remove_series("router_replica_health",
                                                replica=rep.name)
                        telemetry.remove_series("router_replica_inflight",
                                                replica=rep.name)
        if telemetry._ENABLED:
            for rep in self._reps:
                if rep.state == DEAD:
                    continue
                telemetry.set_gauge("router_replica_health", rep.state,
                                    replica=rep.name)
                telemetry.set_gauge("router_replica_inflight",
                                    len(rep.attempts), replica=rep.name)
            telemetry.set_gauge("router_fleet_queue_depth",
                                len(self._queue))

    def _kill_replica(self, idx: int):
        """In-process `replica.kill`: mark the handle dead (there is no
        separate process to SIGKILL) — failover rescues its work."""
        rep = self._reps[idx % len(self._reps)]
        rep.handle.dead = True

    def _spot_preempt(self, idx: int):
        """In-process `replica.spot_preempt`: reclaim one SPOT replica
        (``idx`` picks among the spot-marked handles) — it dies like a
        preemption, failover rescues its in-flight work, and an
        attached autoscaler backfills the capacity."""
        spots = [rep for rep in self._reps
                 if getattr(rep.handle, "spot", False)
                 and rep.state != DEAD]
        if not spots:
            return
        rep = spots[idx % len(spots)]
        rep.handle.dead = True
        if _fl._ENABLED:
            _fl.record("route", "router.spot_preempt", replica=rep.name)

    def _failover_dead(self, now: float) -> int:
        """Resubmit every in-flight request held by a dead replica
        (the idempotency token makes the resubmission safe even if the
        old attempt's result later surfaces)."""
        n = 0
        for rep in self._reps:
            if rep.state != DEAD or not rep.attempts:
                continue
            for fr, att in list(rep.attempts.values()):
                self._drop_attempt(fr, att, outcome="failover")
                self.n_failovers += 1
                n += 1
                if telemetry._ENABLED:
                    telemetry.inc("serve_failovers_total")
                if _fl._ENABLED:
                    _fl.record("route", "router.failover",
                               token=fr.token, replica=rep.name)
                self._retry(fr, now, f"replica {rep.name} dead")
        return n

    # -- dispatch ------------------------------------------------------------

    def _affinity_key(self, prompt, adapter=None,
                      tenant=None) -> Optional[int]:
        """Hash of the prompt's leading block-sized chunks — exactly
        the prefix cache's chain keys, so equal keys mean shareable
        blocks on whichever replica served the key last. The adapter
        name and tenant join the hash: adapter KV is namespaced in the
        replica's prefix cache (same tokens under adapter X share
        nothing with adapter Y), and same-tenant traffic tends to
        repeat the same system prompts, so splitting affinity by
        tenant keeps each tenant's working set hot on its replica."""
        if self.affinity_blocks <= 0:
            return None
        bs = self.block_size
        for rep in self._reps:          # prefer a replica-reported size
            if rep.detail and rep.detail.get("block_size"):
                bs = int(rep.detail["block_size"])
                break
        n = (min(len(prompt), self.affinity_blocks * bs) // bs) * bs
        if n == 0:
            return None
        return hash((adapter, tenant)
                    + tuple(int(t) for t in prompt[:n]))

    def _eligible(self, rep: _Rep, now: float) -> bool:
        if rep.state in (DEAD, DRAINING) or rep.detail is None:
            return False
        d = rep.detail
        if not d.get("ok", False):
            return False
        slots = int(d.get("slots", 1))
        cap = slots + (slots if self.per_replica_queue is None
                       else self.per_replica_queue)
        load = max(int(d.get("queued", 0)) + int(d.get("active", 0)),
                   len(rep.attempts))
        if load >= cap:
            return False
        return rep.breaker.allow(now)

    def _load(self, rep: _Rep) -> tuple:
        d = rep.detail or {}
        load = max(int(d.get("queued", 0)) + int(d.get("active", 0)),
                   len(rep.attempts))
        # prefill_backlog_tokens: un-prefilled prompt tokens (queued +
        # mid-chunk) the replica still owes its chunk budget to — a
        # chunked-prefill replica digesting a long prompt scores worse
        # than an equally-loaded one that is already all-decode
        return (load, float(d.get("queue_age_p95_s", 0.0)),
                int(d.get("prefill_backlog_tokens", 0)),
                -int(d.get("blocks_free", 0)))

    def _exhaust_risk(self, rep: _Rep) -> bool:
        """Replica forecast to exhaust its KV pool inside the
        admission window (the goodput forecaster's `exhaust_in_s`
        rides health_detail / the ProcReplica heartbeat wholesale, so
        no wire change was needed)."""
        if self.exhaust_window_s is None:
            return False
        eta = (rep.detail or {}).get("exhaust_in_s")
        return eta is not None and eta < self.exhaust_window_s

    @staticmethod
    def _role(rep: _Rep) -> Optional[str]:
        """A replica's disaggregation role: the handle attribute when
        set, else whatever the heartbeat reports (None = combined)."""
        r = getattr(rep.handle, "role", None)
        if r is None and rep.detail is not None:
            r = rep.detail.get("role")
        return r

    def _pick(self, fr: FleetRequest, now: float,
              exclude=(), role: Optional[str] = None) -> Optional[_Rep]:
        elig = [rep for rep in self._reps
                if rep not in exclude and self._eligible(rep, now)]
        if not elig:
            return None
        if self._canaries:
            # canary weight gate: a replica under analysis is offered
            # only a `spec.weight` fraction of picks (stride
            # scheduling — a 0.25 weight admits every 4th offer); when
            # nothing else is eligible, availability wins and the gate
            # drops
            gated = []
            for rep in elig:
                cs = self._canaries.get(rep.name)
                if cs is None:
                    gated.append(rep)
                    continue
                cs.tokens += cs.spec.weight
                if cs.tokens >= 1.0:
                    cs.tokens -= 1.0
                    gated.append(rep)
            if gated:
                elig = gated
        if role is not None:
            match = [rep for rep in elig if self._role(rep) == role]
            if match:
                elig = match
            elif role == "prefill":
                # no prefill replica eligible: the caller falls back
                # to combined least-loaded serving, NOT to prefilling
                # on a decode replica
                return None
        if self.exhaust_window_s is not None and len(fr.prompt) >= \
                self.long_prompt_blocks * self.block_size:
            # memory-pressure steering: long prompts avoid replicas
            # forecast to exhaust — BEFORE they preempt. Short prompts
            # still land (they fit the margin), and when every replica
            # is at risk the filter drops: availability wins.
            safe = [rep for rep in elig
                    if not self._exhaust_risk(rep)]
            if safe:
                if len(safe) < len(elig) and telemetry._ENABLED:
                    telemetry.inc("router_exhaust_diverted_total")
                elig = safe
        adapter = fr.params.get("adapter")
        if adapter is not None:
            # adapter-residency routing: prefer replicas that already
            # hold the adapter in their device table (loading is a
            # host->device table write, not a recompile, but the
            # factors still have to ship). No resident replica is a
            # MISS — counted, then served least-loaded anyway:
            # availability over affinity.
            resident = [rep for rep in elig
                        if adapter in ((rep.detail or {})
                                       .get("adapters") or ())]
            if resident:
                elig = resident
            else:
                self.n_adapter_misses += 1
                if telemetry._ENABLED:
                    telemetry.inc("serve_adapter_misses_total")
        key = self._affinity_key(fr.prompt, adapter,
                                 fr.params.get("tenant"))
        if key is not None:
            tgt = self._affinity.get(key)
            if tgt is not None and tgt in elig:
                self._affinity.move_to_end(key)
                self._pick_how = "prefix_affinity"
                return tgt
        best = min(elig, key=self._load)
        self._pick_how = "least_loaded"
        if key is not None:
            self._affinity[key] = best
            self._affinity.move_to_end(key)
            while len(self._affinity) > self.affinity_capacity:
                self._affinity.popitem(last=False)
        return best

    def _prefill_tick(self, now: float) -> int:
        """Poll in-flight prefill-export jobs: a finished export
        attaches the wire payload to its request (next dispatch ships
        it to a decode replica); a dead prefill replica, a timed-out
        job, or a failed export falls the request back to combined
        serving."""
        n = 0
        for tok, (fr, rep, job, t0) in list(self._prefill_jobs.items()):
            if fr.terminal:
                del self._prefill_jobs[tok]
                continue
            wire = None
            failed = rep.state == DEAD
            if not failed:
                try:
                    wire = rep.handle.poll_export(job)
                except Exception:
                    failed = True
            if self.attempt_timeout_s is not None and \
                    wire is None and now - t0 > self.attempt_timeout_s:
                failed = True
            if failed or wire == "":
                del self._prefill_jobs[tok]
                fr.kv_wire = ""         # combined serving from here on
                self.n_disagg_fallbacks += 1
                if telemetry._ENABLED:
                    telemetry.inc("router_disagg_fallback_total")
                if _fl._ENABLED:
                    _fl.record("route", "router.disagg_fallback",
                               token=fr.token, replica=rep.name)
                continue
            if wire is None:
                continue                # still prefilling
            del self._prefill_jobs[tok]
            fr.kv_wire = wire
            self.n_prefill_exports += 1
            n += 1
            if telemetry._ENABLED:
                telemetry.inc("router_prefill_exports_total")
            if _fl._ENABLED:
                _fl.record("route", "router.prefill_export",
                           token=fr.token, replica=rep.name,
                           bytes=len(wire))
        return n

    def _start_prefill(self, fr: FleetRequest, now: float) -> bool:
        """Try to start a prefill-export job for a queued request.
        False means no prefill replica took it — fall back."""
        rep = self._pick(fr, now, role="prefill")
        if rep is None:
            return False
        try:
            job = rep.handle.prefill_export(fr, f"{fr.token}.pf")
        except Exception as e:
            rep.breaker.record_failure(now)
            if _fl._ENABLED:
                _fl.record("route", "router.prefill_error",
                           token=fr.token, replica=rep.name,
                           error=repr(e)[:120])
            return False
        self._prefill_jobs[fr.token] = (fr, rep, job, now)
        if _fl._ENABLED:
            _fl.record("route", "router.prefill_start",
                       token=fr.token, replica=rep.name)
        return True

    def _dispatch(self, now: float) -> int:
        n = 0
        work = list(self._queue)
        self._queue.clear()
        keep = []
        for fr in work:
            if fr.terminal:
                continue
            if fr.next_eligible_t > now:
                keep.append(fr)
                continue
            if self.disaggregate and fr.kv_wire is None:
                if fr.token in self._prefill_jobs:
                    keep.append(fr)     # prefill still in flight
                    continue
                if self._start_prefill(fr, now):
                    keep.append(fr)
                    n += 1
                    continue
                # least-loaded fallback: no prefill replica eligible
                fr.kv_wire = ""
                self.n_disagg_fallbacks += 1
                if telemetry._ENABLED:
                    telemetry.inc("router_disagg_fallback_total")
            rep = self._pick(fr, now,
                             role="decode" if fr.kv_wire else None)
            if rep is None:
                keep.append(fr)
                continue
            if self._send(fr, rep, now):
                n += 1
            # on submit failure _send already re-routed fr via _retry
        for fr in keep:
            self._queue.append(fr)
        return n

    def _send(self, fr: FleetRequest, rep: _Rep, now: float,
              hedge: bool = False) -> bool:
        attempt_key = f"{fr.token}.{fr.tries}"
        fr.tries += 1
        deadline_s = None if fr.t_deadline is None \
            else max(0.001, fr.t_deadline - now)
        try:
            sub = rep.handle.submit(fr, attempt_key, deadline_s)
        except Exception as e:
            rep.breaker.record_failure(now)
            if _fl._ENABLED:
                _fl.record("route", "router.submit_error",
                           token=fr.token, replica=rep.name,
                           error=repr(e)[:120])
            if not hedge:
                self._retry(fr, now, f"submit to {rep.name}: {e}")
            return False
        att = _Attempt(rep, sub, now, hedge)
        if fr.kv_wire:
            self.n_stream_dispatches += 1
            if telemetry._ENABLED:
                telemetry.inc("router_stream_dispatch_total")
        if telemetry._ENABLED:
            att.log = {"attempt": fr.tries - 1, "replica": rep.name,
                       "key": attempt_key, "t0": now, "hedge": hedge,
                       "decision": self._pick_how, "outcome": None,
                       "t_end": None, "clock": rep.clock_offset,
                       "trace": None}
            fr.attempt_log.append(att.log)
        fr.attempts.append(att)
        rep.attempts[id(att)] = (fr, att)
        fr.state = "inflight"
        self._inflight[fr.token] = fr
        if _fl._ENABLED:
            _fl.record("route", "router.dispatch", token=fr.token,
                       replica=rep.name, attempt=fr.tries - 1,
                       hedge=hedge)
        return True

    # -- drive / collect -----------------------------------------------------

    def _drive(self, now: float) -> int:
        toks = 0
        for rep in self._reps:
            try:
                toks += rep.handle.drive()
            except Exception as e:
                # a wedged local server (ServerStalledError etc.):
                # treat like a death — failover will rescue its work
                rep.handle.dead = True
                rep.breaker.record_failure(now)
                if _fl._ENABLED:
                    _fl.record("route", "router.replica_error",
                               replica=rep.name, error=repr(e)[:120])
        return toks

    def _drop_attempt(self, fr: FleetRequest, att: _Attempt,
                      cancel: bool = False,
                      outcome: Optional[str] = None):
        if att.log is not None and outcome is not None \
                and att.log.get("outcome") is None:
            att.log["outcome"] = outcome
            att.log["t_end"] = time.time()
        if att in fr.attempts:
            fr.attempts.remove(att)
        att.rep.attempts.pop(id(att), None)
        if cancel:
            try:
                att.rep.handle.cancel(att.sub)
            except Exception:
                pass

    def _note_result(self, att: _Attempt, res: dict, outcome: str,
                     now: float):
        """Record an attempt's terminal outcome and stitch the worker's
        shipped span timeline (plus the clock offset that aligns it)
        into the distributed trace."""
        if att.log is None:
            return
        if att.log.get("outcome") is None:
            att.log["outcome"] = outcome
            att.log["t_end"] = now
        tr = res.get("trace") if isinstance(res, dict) else None
        if tr is not None:
            att.log["trace"] = tr
            att.log["clock"] = att.rep.clock_offset

    def _retry(self, fr: FleetRequest, now: float, why: str):
        """Requeue after a failed/lost attempt under capped-exponential
        backoff; out of budget -> terminal ``failed``."""
        if fr.terminal or fr.attempts:
            return                      # a live attempt may still win
        self._inflight.pop(fr.token, None)
        if fr.t_deadline is not None and now > fr.t_deadline:
            self._finalize(fr, _TIMED_OUT, "deadline", now)
            return
        if fr.retries >= self.max_retries:
            self._finalize(fr, _FAILED, f"retries exhausted: {why}",
                           now)
            return
        fr.retries += 1
        fr.next_eligible_t = now + min(
            self.backoff_max_s,
            self.backoff_base_s * (2 ** (fr.retries - 1)))
        fr.state = "queued"
        self._queue.appendleft(fr)
        self.n_retries += 1
        if telemetry._ENABLED:
            telemetry.inc("serve_retries_total")
        if _fl._ENABLED:
            _fl.record("route", "router.retry", token=fr.token,
                       n=fr.retries, why=why[:120])

    def _collect(self, now: float) -> int:
        delivered = 0
        for fr in list(self._inflight.values()):
            for att in list(fr.attempts):
                try:
                    res = att.rep.handle.poll(att.sub)
                except Exception:
                    res = None
                if res is None:
                    if self.attempt_timeout_s is not None and \
                            now - att.t0 > self.attempt_timeout_s:
                        att.rep.breaker.record_failure(now)
                        self._drop_attempt(fr, att, cancel=True,
                                           outcome="timeout")
                        if _fl._ENABLED:
                            _fl.record("route", "router.attempt_timeout",
                                       token=fr.token,
                                       replica=att.rep.name)
                        self._retry(fr, now,
                                    f"attempt timeout on {att.rep.name}")
                    continue
                if _ft._ACTIVE and \
                        _ft.fire("router.drop") is not None:
                    # injected lost reply: forget the result, abandon
                    # the attempt, and let the retry + idempotency
                    # machinery prove the request still finishes once
                    att.rep.handle.discard(att.sub)
                    self._drop_attempt(fr, att, outcome="dropped")
                    self._retry(fr, now, "router.drop")
                    continue
                if res.get("status") == "ok":
                    self._deliver(fr, att, res, now)
                    delivered += 1
                else:
                    # timed_out / preempted / rejected / cancelled at
                    # the replica: the attempt failed
                    if res.get("status") != _CANCELLED:
                        att.rep.breaker.record_failure(now)
                    self._note_result(att, res,
                                      res.get("status") or "failed", now)
                    self._drop_attempt(fr, att)
                    self._retry(fr, now,
                                f"{res.get('status')} on {att.rep.name}")
        return delivered

    def _deliver(self, fr: FleetRequest, att: _Attempt, res: dict,
                 now: float):
        att.rep.breaker.record_success()
        self._note_result(att, res, "duplicate" if fr.terminal
                          else "won", now)
        self._drop_attempt(fr, att)
        if fr.terminal:
            # idempotency: a late duplicate (the request already won
            # elsewhere after a failover/drop) is ignored, not
            # double-counted
            self.n_duplicates += 1
            if telemetry._ENABLED:
                telemetry.inc("serve_duplicate_results_total")
            return
        fr.output_tokens = [int(t) for t in res.get("tokens", [])]
        fr.replica = att.rep.name
        if res.get("ttft") is not None:
            fr.ttft_s = (att.t0 - fr.t_submit) + float(res["ttft"])
        # hedge resolution: cancel the loser(s) before finalizing
        for other in list(fr.attempts):
            self._drop_attempt(fr, other, cancel=True,
                               outcome="lost_hedge")
        self._finalize(fr, _OK, res.get("finish_reason"), now,
                       won=("hedge" if att.hedge else "primary"))

    def _finalize(self, fr: FleetRequest, status: str,
                  reason: Optional[str], now: float,
                  won: str = "none"):
        for att in list(fr.attempts):
            self._drop_attempt(fr, att, cancel=True, outcome="cancelled")
        self._inflight.pop(fr.token, None)
        try:
            self._queue.remove(fr)
        except ValueError:
            pass
        fr.state = "finished"
        fr.status = status
        fr.finish_reason = reason
        fr.t_finish = now
        self.finished.append(fr)
        if telemetry._ENABLED:
            telemetry.inc("serve_requests_total", status=status)
            if fr.hedged:
                telemetry.inc("serve_hedges_total", won=won)
        if _fl._ENABLED:
            _fl.record("route", "router.finish", token=fr.token,
                       status=status, replica=fr.replica,
                       tries=fr.tries)

    # -- hedging / deadlines -------------------------------------------------

    def _hedge_threshold(self, now: float) -> Optional[float]:
        if self.hedge_after_s is None:
            return None
        if self.hedge_after_s == "auto":
            p95s = [float(rep.detail.get("queue_age_p95_s", 0.0))
                    for rep in self._reps if rep.detail is not None]
            return max([self.hedge_min_s] + p95s)
        return float(self.hedge_after_s)

    def _hedge(self, now: float) -> int:
        thr = self._hedge_threshold(now)
        if thr is None:
            return 0
        n = 0
        for fr in list(self._inflight.values()):
            if fr.hedged or len(fr.attempts) != 1:
                continue
            att = fr.attempts[0]
            if now - att.t0 < thr:
                continue
            rep = self._pick(fr, now, exclude=(att.rep,),
                             role="decode" if fr.kv_wire else None)
            if rep is None:
                continue
            fr.hedged = True
            self.n_hedges += 1
            if _fl._ENABLED:
                _fl.record("route", "router.hedge", token=fr.token,
                           stuck_on=att.rep.name, to=rep.name,
                           after_s=round(now - att.t0, 4))
            if self._send(fr, rep, now, hedge=True):
                n += 1
            else:
                fr.hedged = False       # try hedging again later
        return n

    def _expire(self, now: float):
        for fr in list(self._queue) + list(self._inflight.values()):
            if fr.t_deadline is not None and now > fr.t_deadline \
                    and not fr.terminal:
                self._finalize(fr, _TIMED_OUT, "deadline", now)

    def cancel(self, fr: FleetRequest) -> bool:
        """Cancel a fleet request wherever it is (queued or in
        flight); True when it was still live."""
        if fr.terminal:
            return False
        self._finalize(fr, _CANCELLED, "cancel", time.time())
        return True

    # -- watchdog ------------------------------------------------------------

    def _note_progress(self, progress: int, now: float):
        if progress > 0 or not (self._queue or self._inflight):
            self._last_progress_t = now
            return
        if now - self._last_progress_t > self.watchdog_s:
            self._last_progress_t = now
            if _fl._ENABLED:
                _fl.record("stall", "router.watchdog",
                           queued=len(self._queue),
                           inflight=len(self._inflight))
                _fl.dump(reason="router_stall")
            raise RouterStalledError(
                f"fleet router: no progress for {self.watchdog_s:.0f}s "
                f"({len(self._queue)} queued, {len(self._inflight)} in "
                "flight) — every replica is dead or wedged")

    # -- fleet lifecycle -----------------------------------------------------

    def add_replica(self, handle) -> str:
        """Dynamically add one replica to the fleet (the autoscaler's
        scale-out primitive, usable standalone). The handle enters as
        UNHEALTHY until its first good probe; if an anomaly engine is
        attached its per-replica state for this name is forgotten —
        a fresh incarnation recompiling and re-anchoring its clock is
        planned churn, not an incident. Returns the replica name."""
        if any(r.name == handle.name for r in self._reps):
            raise ValueError(f"replica name {handle.name!r} already "
                             "in the fleet")
        rep = _Rep(handle, CircuitBreaker(self.breaker_threshold,
                                          self.breaker_cooldown_s),
                   time.time())
        self._reps.append(rep)
        if self._anomaly is not None:
            self._anomaly.forget_replica(rep.name)
        if _fl._ENABLED:
            _fl.record("route", "router.add_replica", replica=rep.name)
        return rep.name

    def remove_replica(self, name: str, *,
                       allow_empty: bool = False) -> bool:
        """Remove one replica from the fleet (the scale-in primitive).
        Any in-flight attempts it still holds are failed over first —
        a planned removal loses nothing — then every trace of the
        replica is swept: its prefix-affinity entries, its
        heartbeat-shipped registry contribution (so the fleet-merged
        ``replica=<name>`` series disappear from /metrics instead of
        freezing), its ``router_replica_*`` gauge rows, and its
        anomaly-engine state. Refuses to empty the fleet unless
        ``allow_empty`` (the autoscaler passes it for scale-to-zero).
        Returns False when no such replica exists."""
        rep = next((r for r in self._reps if r.name == name), None)
        if rep is None:
            return False
        if len(self._reps) == 1 and not allow_empty:
            raise ValueError("refusing to remove the last replica "
                             "(allow_empty=False)")
        now = time.time()
        for fr, att in list(rep.attempts.values()):
            self._drop_attempt(fr, att, cancel=True, outcome="failover")
            self.n_failovers += 1
            if telemetry._ENABLED:
                telemetry.inc("serve_failovers_total")
            if _fl._ENABLED:
                _fl.record("route", "router.failover",
                           token=fr.token, replica=rep.name)
            self._retry(fr, now, f"replica {rep.name} removed")
        self._reps.remove(rep)
        for key in [k for k, v in self._affinity.items() if v is rep]:
            del self._affinity[key]
        rep.tm_state.clear()
        if telemetry._ENABLED:
            telemetry.remove_series("router_replica_health",
                                    replica=name)
            telemetry.remove_series("router_replica_inflight",
                                    replica=name)
        if self._anomaly is not None:
            self._anomaly.forget_replica(name)
        if _fl._ENABLED:
            _fl.record("route", "router.remove_replica", replica=name)
        return True

    def rolling_restart(self, drain_timeout_s: float = 60.0,
                        restart_timeout_s: float = 60.0,
                        canary=None,
                        canary_timeout_s: Optional[float] = None,
                        bundle_dir: Optional[str] = None,
                        replicas=None) -> List[dict]:
        """Drain-aware rolling restart, one replica at a time: flip it
        to draining (its health source reports not-ready, so dispatch
        stops), keep stepping the fleet until its work finishes, then
        restart it and wait until it probes healthy again. Admission
        to the OTHER replicas continues throughout.

        With ``canary=CanarySpec(...)`` (see `mxnet_tpu.anomaly`) each
        restarted replica re-enters rotation at ``spec.weight``
        routing weight while a `CanaryAnalysis` compares its fresh
        metric distributions bucket-exactly against the merged fleet
        peers: promotion restores full weight
        (`router_canary_promotions_total`); failure drains it back out
        of rotation, collects ``flight-bundle-canary_fail`` and bumps
        `router_canary_rollbacks_total` (the replica is left draining
        for the operator — `end_drain()` re-admits it). The analysis
        reads the heartbeat-shipped registry snapshots, so it needs
        worker-side telemetry; with no data the window expires into
        ``spec.on_timeout``. ``replicas`` restricts the rollout to the
        named subset (default: all). Returns one record per restarted
        replica: ``{"replica", "canary": None | "promoted" |
        "rolled_back", "report"}``."""
        results = []
        targets = [rep for rep in self._reps
                   if replicas is None or rep.name in set(replicas)]
        for rep in targets:
            if _fl._ENABLED:
                _fl.record("route", "router.drain", replica=rep.name)
            try:
                rep.handle.begin_drain()
            except Exception:
                pass
            t0 = time.time()
            while time.time() - t0 < drain_timeout_s:
                self.step()
                if rep.state == DEAD:
                    break
                d = rep.detail or {}
                if not rep.attempts and d.get("draining") \
                        and int(d.get("queued", 0)) == 0 \
                        and int(d.get("active", 0)) == 0:
                    break
                time.sleep(self.poll_s)
            rep.handle.restart()
            rep.breaker = CircuitBreaker(rep.breaker.threshold,
                                         rep.breaker.cooldown_s)
            rep.detail = None
            rep.last_seen = time.time()
            if self._anomaly is not None:
                # the rebuilt worker recompiles and re-anchors its
                # clock by design — not a storm, not jitter
                self._anomaly.forget_replica(rep.name)
            if _fl._ENABLED:
                _fl.record("route", "router.restart", replica=rep.name)
            t0 = time.time()
            while time.time() - t0 < restart_timeout_s:
                self.step()
                if rep.state == HEALTHY:
                    break
                time.sleep(self.poll_s)
            rec = {"replica": rep.name, "canary": None, "report": None}
            if canary is not None:
                cs = self._start_canary(rep, canary, bundle_dir)
                limit = canary_timeout_s if canary_timeout_s is not None \
                    else canary.window_s + 30.0
                t0 = time.time()
                while rep.name in self._canaries \
                        and time.time() - t0 < limit:
                    if not self.step():
                        time.sleep(self.poll_s)
                self._canaries.pop(rep.name, None)
                rec["canary"] = cs.analysis.verdict
                rec["report"] = cs.analysis.report
            results.append(rec)
        return results

    # -- canary-gated rollout ------------------------------------------------

    def _rep_hist_state(self, rep: _Rep, metrics) -> dict:
        """``{metric: (buckets, count, zeros)}`` from one replica's
        heartbeat-shipped registry blob — the per-replica histogram
        view the merged registry cannot give back."""
        from .. import anomaly as _anom
        out = {}
        for m in metrics:
            fam = rep.tm_state.get(m)
            if isinstance(fam, dict):
                out[m] = _anom.blob_hist(fam)
        return out

    def _peer_hist_state(self, canary_rep: _Rep, metrics) -> dict:
        """The same view merged over every live non-canary peer — the
        fleet baseline the canary is compared against."""
        from .. import anomaly as _anom
        per: Dict[str, list] = {m: [] for m in metrics}
        for rep in self._reps:
            if rep is canary_rep or rep.state == DEAD \
                    or rep.name in self._canaries:
                continue
            for m in metrics:
                fam = rep.tm_state.get(m)
                if isinstance(fam, dict):
                    per[m].append(_anom.blob_hist(fam))
        return {m: _anom.merge_hists(ts) for m, ts in per.items() if ts}

    def _start_canary(self, rep: _Rep, spec,
                      bundle_dir: Optional[str] = None) -> _CanaryState:
        from .. import anomaly as _anom
        analysis = _anom.CanaryAnalysis(spec)
        analysis.start(self._rep_hist_state(rep, spec.metrics),
                       self._peer_hist_state(rep, spec.metrics))
        cs = _CanaryState(spec, analysis, bundle_dir)
        self._canaries[rep.name] = cs
        if _fl._ENABLED:
            _fl.record("route", "router.canary_start",
                       replica=rep.name, weight=spec.weight)
        return cs

    def _canary_tick(self, now: float):
        for name, cs in list(self._canaries.items()):
            rep = next((r for r in self._reps if r.name == name), None)
            if rep is None or rep.state == DEAD:
                cs.analysis.verdict = "rolled_back"
                cs.analysis.report = {"reason":
                                      "replica died under canary"}
                verdict = "rolled_back"
            else:
                verdict = cs.analysis.evaluate(
                    self._rep_hist_state(rep, cs.spec.metrics),
                    self._peer_hist_state(rep, cs.spec.metrics))
            if verdict is None:
                continue
            del self._canaries[name]
            reason = cs.analysis.report.get("reason")
            if verdict == "promoted":
                self.n_canary_promotions += 1
                if telemetry._ENABLED:
                    telemetry.inc("router_canary_promotions_total")
                if _fl._ENABLED:
                    _fl.record("route", "router.canary_promote",
                               replica=name, reason=reason)
                continue
            self.n_canary_rollbacks += 1
            if telemetry._ENABLED:
                telemetry.inc("router_canary_rollbacks_total")
            if _fl._ENABLED:
                _fl.record("route", "router.canary_rollback",
                           replica=name, reason=reason)
            if rep is not None and rep.state != DEAD:
                try:
                    rep.handle.begin_drain()
                except Exception:
                    pass
            path = None if cs.bundle_dir is None else os.path.join(
                cs.bundle_dir, "flight-bundle-canary_fail")
            try:
                self.collect_flight_bundle("canary_fail", path=path)
            except Exception:
                pass

    def stop_fleet(self, timeout_ms: int = 10_000) -> dict:
        """Send stop to every ProcReplica and collect their closing
        stats dumps ({name: stats or None})."""
        out = {}
        for rep in self._reps:
            h = rep.handle
            if isinstance(h, ProcReplica):
                h.stop()
        for rep in self._reps:
            h = rep.handle
            if isinstance(h, ProcReplica):
                out[rep.name] = None if h.dead \
                    else h.final_stats(timeout_ms=timeout_ms)
        return out

    def stats(self) -> dict:
        by_status: Dict[str, int] = {}
        for fr in self.finished:
            by_status[fr.status or _OK] = \
                by_status.get(fr.status or _OK, 0) + 1
        return {"ticks": self.ticks,
                "queued": len(self._queue),
                "inflight": len(self._inflight),
                "finished": len(self.finished),
                "status_counts": by_status,
                "shed": self.n_shed,
                "adapter_misses": self.n_adapter_misses,
                "retries": self.n_retries,
                "failovers": self.n_failovers, "hedges": self.n_hedges,
                "duplicates": self.n_duplicates,
                "prefill_exports": self.n_prefill_exports,
                "stream_dispatches": self.n_stream_dispatches,
                "disagg_fallbacks": self.n_disagg_fallbacks,
                "canary_rollbacks": self.n_canary_rollbacks,
                "canary_promotions": self.n_canary_promotions,
                "canaries": sorted(self._canaries),
                "admission_floor": self.admission_floor,
                "autoscale": None if self._autoscaler is None
                else self._autoscaler.stats(),
                "replicas": {rep.name: {
                    "state": _STATE_NAMES[rep.state],
                    "breaker": rep.breaker.state,
                    "attempts": len(rep.attempts),
                    "restarts": getattr(rep.handle, "restarts", 0),
                    "role": self._role(rep),
                } for rep in self._reps}}

    # -- distributed tracing -------------------------------------------------

    def _find_request(self, request) -> Optional[FleetRequest]:
        if isinstance(request, FleetRequest):
            return request
        if isinstance(request, str):
            fr = self._inflight.get(request)
            if fr is not None:
                return fr
            for fr in self.finished + list(self._queue):
                if fr.token == request:
                    return fr
            return None
        rid = int(request)
        for fr in (list(self._inflight.values()) + self.finished
                   + list(self._queue)):
            if fr.id == rid:
                return fr
        return None

    def trace(self, request) -> Optional[dict]:
        """ONE merged distributed timeline for a request (by id, token,
        or the FleetRequest itself): the router's queue wait, every
        attempt as a span carrying its replica id / routing decision /
        outcome (won, failover, timeout, dropped, lost_hedge, ...), and
        each attempt's shipped worker span timeline (prefill, decode
        windows, CoW, preemptions) converted from the worker's
        perf_counter clock to wall time via the heartbeat clock
        handshake. Every event carries ``src`` ("router" or the replica
        name) and a unix ``t``; timed spans carry ``dur_s``. None when
        the request is unknown or was never traced (telemetry was
        off)."""
        fr = self._find_request(request)
        if fr is None or not fr.attempt_log:
            return None
        now = time.time()
        t_first = fr.attempt_log[0]["t0"]
        events: List[dict] = [
            {"name": "queued", "t": fr.t_submit, "src": "router",
             "dur_s": max(0.0, t_first - fr.t_submit)}]
        attempts = []
        for entry in fr.attempt_log:
            t_end = entry.get("t_end") or fr.t_finish or now
            events.append(
                {"name": f"attempt {entry['attempt']}",
                 "t": entry["t0"],
                 "dur_s": max(0.0, t_end - entry["t0"]),
                 "src": "router", "replica": entry["replica"],
                 "outcome": entry.get("outcome"),
                 "hedge": entry["hedge"],
                 "decision": entry.get("decision"),
                 "token": entry["key"]})
            attempts.append({k: entry.get(k) for k in
                             ("attempt", "replica", "key", "t0", "t_end",
                              "hedge", "decision", "outcome")})
            wt, off = entry.get("trace"), entry.get("clock")
            if wt and off is not None:
                for wev in wt.get("events", []):
                    cev = dict(wev)
                    cev["t"] = float(wev.get("t", 0.0)) + off
                    cev["src"] = entry["replica"]
                    events.append(cev)
        if fr.t_finish is not None:
            events.append({"name": "finish", "t": fr.t_finish,
                           "src": "router", "status": fr.status})
        events.sort(key=lambda e: e["t"])
        latency = None if fr.t_finish is None \
            else fr.t_finish - fr.t_submit
        return {"request_id": fr.id, "token": fr.token,
                "state": fr.state, "status": fr.status,
                "finish_reason": fr.finish_reason,
                "replica": fr.replica, "tries": fr.tries,
                "retries": fr.retries, "hedged": fr.hedged,
                "queue_wait_s": max(0.0, t_first - fr.t_submit),
                "ttft_s": fr.ttft_s, "latency_s": latency,
                "attempts": attempts, "events": events}

    def fleet_traces(self, limit: int = 256) -> List[dict]:
        """Merged timelines of the most recent finished requests plus
        everything in flight — the source `telemetry.export_chrome_trace`
        renders under the router/replica pids."""
        frs = self.finished[-int(limit):] + list(self._inflight.values())
        out = []
        for fr in frs:
            if not fr.attempt_log:
                continue
            tr = self.trace(fr)
            if tr is not None:
                out.append(tr)
        return out

    # -- fleet metrics plane -------------------------------------------------

    def fleet_registry(self) -> "OrderedDict":
        """The bucket-exact merge of the router's own registry with
        every replica's latest heartbeat-shipped snapshot: counters
        sum, histograms merge bucket-wise, gauges get one child per
        source under a ``replica=<name>`` label (the router's own
        gauges appear as ``replica=router``)."""
        blobs = {"router": telemetry._registry_state()}
        for rep in self._reps:
            if rep.tm_state:
                blobs[rep.name] = rep.tm_state
        return telemetry._merge_registry(blobs, label="replica")

    def fleet_prometheus(self) -> str:
        """Prometheus exposition of `fleet_registry()` — the body the
        router's /metrics serves."""
        return telemetry._prometheus_text(self.fleet_registry())

    def start_metrics_server(self, port: int = 0,
                             host: Optional[str] = None):
        """Serve the FLEET view at GET /metrics (and /healthz, which a
        firing SLO alert flips to 503): registers this router as the
        process's fleet metrics provider, then starts (or reuses) the
        telemetry metrics server."""
        telemetry.set_fleet_metrics_provider(self)
        return telemetry.start_metrics_server(port=port, host=host)

    # -- SLO engine ----------------------------------------------------------

    def attach_slo(self, engine=None, *, bundle_on_alert: bool = True,
                   bundle_dir: Optional[str] = None,
                   bundle_timeout_s: float = 5.0, **engine_kw):
        """Wire an SLO engine to this fleet: sample the fleet-merged
        registry, tick from `step()` (behind the telemetry gate),
        register as a /healthz source (a firing alert answers 503
        naming the violated objective), and — on each alert's rising
        edge — collect a cross-process flight bundle. Pass an
        `SLOEngine` to reuse one, or kwargs for a default engine over
        `slo.default_objectives` (availability measured on the fleet's
        `serve_requests_total`, i.e. after retry/hedge/failover
        rescue). Returns the engine."""
        from .. import slo as _slo
        if engine is None:
            objectives = engine_kw.pop("objectives", None) \
                or _slo.default_objectives(
                    availability_metric="serve_requests_total")
            engine = _slo.SLOEngine(objectives,
                                    source=self.fleet_registry,
                                    **engine_kw)
        user_alert = engine.on_alert

        def _on_alert(name, info):
            if _fl._ENABLED:
                _fl.record("slo", "slo.alert", objective=name,
                           burn_fast=round(info.get("burn_rate_fast",
                                                    0.0), 3),
                           burn_slow=round(info.get("burn_rate_slow",
                                                    0.0), 3))
            if bundle_on_alert:
                path = None if bundle_dir is None else os.path.join(
                    bundle_dir, f"flight-bundle-slo-{name}")
                try:
                    self.collect_flight_bundle(
                        f"slo-{name}", path=path,
                        timeout_s=bundle_timeout_s)
                except Exception:
                    pass
            if user_alert is not None:
                user_alert(name, info)

        engine.on_alert = _on_alert
        telemetry.register_health_source(engine)
        self._slo = engine
        return engine

    # -- anomaly engine ------------------------------------------------------

    def attach_anomaly(self, engine=None, *,
                       bundle_on_alert: bool = True,
                       bundle_dir: Optional[str] = None,
                       bundle_timeout_s: float = 5.0, **engine_kw):
        """Wire an `mxnet_tpu.anomaly.AnomalyEngine` to this fleet:
        detectors sample the fleet-merged registry plus the
        per-replica heartbeat state (`_replica_snapshot` — histogram
        blobs, compile stats, clock anchors), tick from `step()`
        behind the telemetry gate, register as a /healthz source (a
        firing detector answers 503), and — on each alert's rising
        edge — collect a cross-process flight bundle
        (``flight-bundle-anomaly-<detector>/``). Pass an engine to
        reuse one (e.g. with restored baselines), or kwargs for a
        default engine. Returns the engine."""
        from .. import anomaly as _anom
        if engine is None:
            engine = _anom.AnomalyEngine(
                source=self.fleet_registry,
                replica_source=self._replica_snapshot, **engine_kw)
        user_alert = engine.on_alert

        def _on_alert(name, info):
            if bundle_on_alert:
                safe = "".join(c if c.isalnum() or c in "-_" else "-"
                               for c in name)
                path = None if bundle_dir is None else os.path.join(
                    bundle_dir, f"flight-bundle-anomaly-{safe}")
                try:
                    self.collect_flight_bundle(
                        f"anomaly-{name}", path=path,
                        timeout_s=bundle_timeout_s)
                except Exception:
                    pass
            if user_alert is not None:
                user_alert(name, info)

        engine.on_alert = _on_alert
        telemetry.register_health_source(engine)
        self._anomaly = engine
        return engine

    # -- autoscaler ----------------------------------------------------------

    def attach_autoscale(self, autoscaler=None, *, provisioner=None,
                         policy=None, **policy_kw):
        """Wire a `mxnet_tpu.serving.autoscale.FleetAutoscaler` to
        this fleet: it adopts the current replicas, then ticks from
        `step()` — UNgated (capacity control must run with telemetry
        off; its emissions gate themselves) — spawning and draining
        replicas through ``provisioner`` against the policy. Pass an
        autoscaler to reuse one, or a provisioner plus a policy /
        policy kwargs for a fresh one. Returns the autoscaler."""
        from . import autoscale as _as
        if autoscaler is None:
            if provisioner is None:
                raise ValueError("need an autoscaler or a provisioner")
            autoscaler = _as.FleetAutoscaler(self, provisioner,
                                             policy=policy, **policy_kw)
        self._autoscaler = autoscaler
        return autoscaler

    def _replica_snapshot(self) -> List[dict]:
        """Per-replica view for the anomaly detectors: name, health
        state, last heartbeat detail (incl. compile stats), the
        heartbeat-shipped registry blob, and the clock-anchor
        offset."""
        return [{"name": rep.name, "state": rep.state,
                 "detail": rep.detail, "tm": rep.tm_state,
                 "clock_offset": rep.clock_offset,
                 "last_seen": rep.last_seen}
                for rep in self._reps]

    # -- cross-process flight correlation ------------------------------------

    def collect_flight_bundle(self, reason: str = "manual",
                              path: Optional[str] = None,
                              timeout_s: float = 5.0) -> str:
        """Dump the router's own flight ring and command every live
        ProcReplica to publish its ring over the channel, collecting
        everything into a ``flight-bundle-<reason>/`` directory (one
        ``<who>.jsonl`` per process plus ``manifest.json``). Each dump
        header carries paired monotonic/unix clock anchors, so
        ``python -m mxnet_tpu.flight merge <dir>`` stitches the files
        into one clock-aligned timeline. Returns the bundle path;
        workers that fail to answer within `timeout_s` are listed under
        ``missing`` in the manifest."""
        safe = "".join(c if c.isalnum() or c in "-_" else "-"
                       for c in reason) or "manual"
        if path is None:
            d = os.environ.get("MXNET_TPU_FLIGHT_DIR") or os.getcwd()
            path = os.path.join(d, f"flight-bundle-{safe}")
        os.makedirs(path, exist_ok=True)
        sources = []
        text = _fl.dump_text(reason)
        if text is not None:
            fname = f"router-p{os.getpid()}.jsonl"
            with open(os.path.join(path, fname), "w") as f:
                f.write(text)
            sources.append(fname)
        self._bundle_seq += 1
        seq = self._bundle_seq
        pending = []
        for rep in self._reps:
            h = rep.handle
            if isinstance(h, ProcReplica) and rep.state != DEAD:
                h._send({"op": "flight_dump", "reason": reason,
                         "seq": seq})
                pending.append(rep)
        deadline = time.time() + timeout_s
        while pending and time.time() < deadline:
            for rep in list(pending):
                h = rep.handle
                raw = h.channel.get(f"{h.ns}/flight/{seq}",
                                    timeout_ms=0)
                if raw is None:
                    continue
                fname = f"{rep.name}.jsonl"
                with open(os.path.join(path, fname), "w") as f:
                    f.write(raw)
                sources.append(fname)
                pending.remove(rep)
            if pending:
                time.sleep(0.01)
        manifest = {"bundle": 1, "reason": reason,
                    "time_unix": time.time(),
                    "router_pid": os.getpid(), "sources": sources,
                    "missing": [rep.name for rep in pending]}
        with open(os.path.join(path, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=1)
        self.last_bundle_path = path
        return path


# -- the worker side ---------------------------------------------------------

def run_fleet_worker(channel, name: str,
                     server: Optional[InferenceServer] = None,
                     server_factory=None, *,
                     hb_interval_s: float = 0.1,
                     idle_sleep_s: float = 0.002,
                     max_wall_s: Optional[float] = None,
                     warmup: bool = True,
                     spot: bool = False):
    """Drive one `InferenceServer` as a fleet replica against the kv
    channel protocol (the counterpart of `ProcReplica`): consume the
    ``cmd/<seq>`` stream in order, tick the server, publish per-attempt
    results under ``res/<token>``, heartbeat `health_detail()` every
    `hb_interval_s`. Results are remembered, so a duplicate submit for
    an already-finished token republishes instead of recomputing —
    the worker half of the idempotency contract.

    Fault sites fire here when armed via ``MXNET_TPU_FAULTS`` in the
    worker's environment: ``replica.kill`` / ``replica.stall`` are hit
    once per PRODUCTIVE tick (tokens were emitted), so a kill always
    lands mid-stream with real in-flight work for the router to
    fail over. ``replica.spot_preempt`` (and a real SIGTERM — the
    cloud's reclaim notice) triggers the spot-preemption exit: one
    parting ``goodbye`` heartbeat so the router fails the work over
    instantly instead of waiting out staleness, then a prompt return.
    Returns the server on a clean ``stop``."""
    if server is None:
        if server_factory is None:
            raise ValueError("need a server or a server_factory")
        server = server_factory()
    ns = f"fleet/{name}"
    next_cmd = 0
    live: Dict[str, object] = {}        # attempt token -> Request
    done: Dict[str, str] = {}           # attempt token -> result json
    live_exports: Dict[str, tuple] = {}  # token -> (Request, prompt)
    done_exports: Dict[str, str] = {}    # token -> wire ("" = failed)
    last_hb = 0.0
    t_start = time.time()
    stopping = False
    preempted = False
    fatal: Optional[str] = None

    def _on_sigterm(signum, frame):
        nonlocal preempted
        preempted = True                # handled at the loop top
    try:
        _signal_mod.signal(_signal_mod.SIGTERM, _on_sigterm)
    except ValueError:
        pass                            # not the main thread

    if warmup:
        # compile prefill + decode (+ the tier program pair) BEFORE
        # the first heartbeat: the single-threaded worker cannot beat
        # mid-compile, and a silent worker reads as dead — warming up
        # front keeps the liveness signal honest. The compile
        # discipline stays 1+1: this IS the one compile, every served
        # request reuses it.
        server.warmup()

    # clock handshake, recorded at warm-up: perf_counter and wall clock
    # sampled together, shipped on every heartbeat so the router can
    # convert this worker's span timestamps to the fleet's shared
    # wall-clock axis
    clock_anchor = {"perf": time.perf_counter(), "unix": time.time()}
    hb_state = {"seq": 0, "tm_prev": None}

    def _beat(now, reason=None, goodbye=False):
        d = server.health_detail()
        d["t"] = now
        d["name"] = name
        if spot:
            d["spot"] = True            # preemptible, on every beat
        if goodbye:
            # the parting beat: tells the router this worker is GONE
            # (dead on arrival, immune to staleness-revival)
            d["goodbye"] = True
        d["compile"] = server.compile_stats()
        d["clock"] = clock_anchor
        hb_state["seq"] += 1
        d["hb_seq"] = hb_state["seq"]
        if telemetry._ENABLED:
            # bounded delta-encoded registry snapshot rides the beat;
            # every 20th beat resends the full state so a router that
            # missed intermediate beats heals
            prev = None if hb_state["seq"] % 20 == 1 \
                else hb_state["tm_prev"]
            delta, hb_state["tm_prev"] = telemetry.registry_delta(prev)
            if delta:
                d["tm"] = delta
        if reason is not None:
            d["ok"] = False
            d["reason"] = reason
        channel.set(f"{ns}/hb", json.dumps(d))

    while True:
        now = time.time()
        while True:                     # drain the command stream
            raw = channel.get(f"{ns}/cmd/{next_cmd}", timeout_ms=0)
            if raw is None:
                break
            next_cmd += 1
            cmd = json.loads(raw)
            op = cmd.get("op")
            if op == "submit":
                tok = cmd["token"]
                if tok in done:         # idempotent republish
                    channel.set(f"{ns}/res/{tok}", done[tok])
                elif tok not in live:
                    kv = cmd.get("kv")
                    if kv:
                        # disaggregated decode: adopt the streamed
                        # prefill blocks before admission (best
                        # effort — failure just means a cold prefill)
                        try:
                            server.adopt_wire_blocks(kv)
                        except Exception:
                            pass
                    try:
                        live[tok] = server.submit(
                            cmd["prompt"], cmd["max_new"],
                            temperature=cmd.get("temperature", 0.0),
                            top_k=cmd.get("top_k", 0),
                            top_p=cmd.get("top_p", 0.0),
                            eos_id=cmd.get("eos_id"),
                            seed=cmd.get("seed", 0),
                            deadline_s=cmd.get("deadline_s"),
                            trace_ctx=tok,
                            tenant=cmd.get("tenant"),
                            priority=cmd.get("priority"),
                            adapter=cmd.get("adapter"))
                    except Exception as e:
                        res = json.dumps(
                            {"status": "rejected", "tokens": [],
                             "finish_reason": f"submit: {e}"[:200]})
                        done[tok] = res
                        channel.set(f"{ns}/res/{tok}", res)
            elif op == "prefill_export":
                tok = cmd["token"]
                if tok in done_exports:  # idempotent republish
                    channel.set(f"{ns}/kv/{tok}", done_exports[tok])
                elif tok not in live_exports:
                    try:
                        req = server.submit(cmd["prompt"], 1,
                                            seed=cmd.get("seed", 0),
                                            trace_ctx=tok)
                        live_exports[tok] = (req, cmd["prompt"])
                    except Exception:
                        done_exports[tok] = ""
                        channel.set(f"{ns}/kv/{tok}", "")
            elif op == "cancel":
                req = live.get(cmd.get("token"))
                if req is not None:
                    server.cancel(req.id)
            elif op == "drain":
                server.begin_drain()
            elif op == "undrain":
                server.end_drain()
            elif op == "restart":
                if server_factory is not None:
                    telemetry.unregister_health_source(server)
                    server = server_factory()
                    live.clear()
                    live_exports.clear()
                    if getattr(server, "tier", None) is not None:
                        server.warm_tier()
                else:
                    server.end_drain()  # best effort: reopen admission
            elif op == "flight_dump":
                # router-commanded ring dump for a flight bundle:
                # publish the serialized ring (clock anchors in the
                # header) on the channel instead of the local disk
                text = _fl.dump_text(cmd.get("reason", "bundle"))
                if text is None:        # recorder disabled here
                    text = json.dumps(
                        {"flight": 1, "disabled": True,
                         "reason": cmd.get("reason"),
                         "pid": os.getpid(), "events": 0,
                         "t_monotonic": time.monotonic(),
                         "time_unix": time.time()}) + "\n"
                channel.set(f"{ns}/flight/{cmd.get('seq', 0)}", text)
            elif op == "stop":
                stopping = True
        emitted = 0
        if server.queue or server._active.any():
            try:
                emitted = server.step()
            except Exception as e:      # wedged server: report + die
                fatal = repr(e)[:200]
        if _ft._ACTIVE and emitted:
            _ft.kill_point("replica.kill")
            sp = _ft.fire("replica.stall")
            if sp is not None:
                time.sleep(float(sp.get("ms", 500)) / 1e3)
            sp = _ft.fire("replica.degrade")
            if sp is not None:
                # latency inflation, NOT a stall: the sleep is short
                # relative to hb_interval_s, so heartbeats keep
                # flowing — the degraded-but-alive adversary
                time.sleep(float(sp.get("ms", 50)) / 1e3)
            sp = _ft.fire("replica.spot_preempt")
            if sp is not None:
                preempted = True        # lands mid-stream, like a real
                                        # reclaim notice
        for tok, req in list(live.items()):
            if req.state == "finished":
                payload = {"status": req.status,
                           "tokens": [int(t) for t in req.output_tokens],
                           "finish_reason": req.finish_reason,
                           "ttft": getattr(req, "ttft", None)}
                if telemetry._ENABLED:
                    # ship the span timeline with the result so the
                    # router can stitch the distributed trace
                    tr = server.trace(req.id)
                    if tr is not None:
                        payload["trace"] = tr
                res = json.dumps(payload)
                done[tok] = res
                channel.set(f"{ns}/res/{tok}", res)
                live.pop(tok)
        for tok, (req, prompt) in list(live_exports.items()):
            if req.state != "finished":
                continue
            wire = ""
            if req.status == "ok":
                try:
                    wire = server.export_prefix(prompt) or ""
                except Exception:
                    wire = ""
            done_exports[tok] = wire
            channel.set(f"{ns}/kv/{tok}", wire)
            live_exports.pop(tok)
        if preempted:
            # spot reclaim: finished results are already published
            # above; whatever is still decoding is abandoned for the
            # router to fail over (idempotency tokens make the
            # resubmission safe). One goodbye beat, then out.
            _beat(now, reason="spot_preempt", goodbye=True)
            return server
        if fatal is not None:
            _beat(now, reason=f"fatal: {fatal}")
            raise RuntimeError(f"fleet worker {name}: {fatal}")
        if now - last_hb >= hb_interval_s or stopping:
            _beat(now)
            last_hb = now
        if stopping:
            channel.set(f"{ns}/stats",
                        json.dumps({"name": name, **server.stats()}))
            return server
        if max_wall_s is not None and now - t_start > max_wall_s:
            raise RuntimeError(f"fleet worker {name}: max_wall_s "
                               f"{max_wall_s} exceeded")
        if not emitted:
            time.sleep(idle_sleep_s)


def _worker_main(argv=None):
    """Subprocess fleet-worker entry::

        python -m mxnet_tpu.serving.router --dir /tmp/fleet --name r0 \\
            --model llama_tiny --slots 4 --max-len 64 --block 8 \\
            --max-prompt 16

    Builds the model deterministically (seeded), then serves over a
    `FileKV` channel rooted at ``--dir`` until a ``stop`` command.
    ``--config`` takes LlamaConfig kwargs as JSON instead of a model
    zoo name."""
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", required=True)
    ap.add_argument("--name", required=True)
    ap.add_argument("--model", default="llama_tiny")
    ap.add_argument("--config", default=None,
                    help="LlamaConfig kwargs as JSON (overrides --model)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--block", type=int, default=8)
    ap.add_argument("--max-prompt", type=int, default=16)
    ap.add_argument("--prefix-cache", action="store_true")
    ap.add_argument("--tiering", action="store_true",
                    help="enable the KV-block memory hierarchy "
                         "(host spill tier + block streaming)")
    ap.add_argument("--persist-dir", default=None,
                    help="disk-backed prefix store directory "
                         "(implies tiering)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-wall-s", type=float, default=None)
    ap.add_argument("--spot", action="store_true",
                    help="mark this worker preemptible (SIGTERM / the "
                         "replica.spot_preempt site triggers the "
                         "goodbye-beat exit either way; --spot just "
                         "stamps the heartbeats)")
    args = ap.parse_args(argv)

    import mxnet_tpu as mx
    from .. import tracing
    # a restarted or newly provisioned worker finds its executables
    tracing.enable_compile_cache()
    mx.random.seed(args.seed)
    if args.config:
        from ..models.llama import LlamaConfig, LlamaForCausalLM
        net = LlamaForCausalLM(LlamaConfig(**json.loads(args.config)))
        net.initialize()
    else:
        net = mx.models.get_model(args.model)
        net.initialize()
    net(mx.nd.array(np.zeros((1, 4)), dtype="int32"))  # materialize

    def factory():
        return InferenceServer(
            net, batch_slots=args.slots, max_len=args.max_len,
            block_size=args.block, max_prompt_len=args.max_prompt,
            prefix_cache=args.prefix_cache,
            kv_tiering=args.tiering,
            prefix_store_dir=args.persist_dir)

    run_fleet_worker(FileKV(args.dir), args.name,
                     server_factory=factory,
                     max_wall_s=args.max_wall_s,
                     spot=args.spot)


if __name__ == "__main__":
    _worker_main()
