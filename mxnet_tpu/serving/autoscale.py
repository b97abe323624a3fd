"""Self-scaling fleet: SLO-burn-driven autoscaling over the router.

The serving stack publishes every signal a capacity controller needs —
multi-window SLO burn rate (`mxnet_tpu.slo`), queue-age percentiles,
per-replica load, and the goodput ledger's tokens/sec/chip — but the
fleet size was still a constant a human picked. This module closes the
loop: a :class:`FleetAutoscaler` that the :class:`~.router.FleetRouter`
ticks from ``step()`` (the `attach_slo` / `attach_anomaly` pattern),
moving a replica *target* against an :class:`AutoscalePolicy` and
reconciling the live fleet toward it through a
:class:`ReplicaProvisioner`.

Control loop, once per ``tick_interval_s``:

- **Scale-out** when the multi-window SLO burn signal (min of the fast
  and slow windows, max over objectives — the same both-windows rule
  the alert uses, so a scale-out can pre-empt the page) or the fleet
  queue-age p95 crosses threshold. The decision is *sized* by the
  goodput ledger's own currency: ``add = ceil(backlog_tokens /
  (tokens_per_sec_per_chip x chips_per_replica x drain_target_s))`` —
  one decision can add several replicas instead of ratcheting one per
  cooldown.
- **Scale-in** when fleet load sits under ``scale_in_load`` with no
  burn for ``scale_in_hold_s`` (the hold window is the hysteresis):
  one replica per decision is *drained*, not killed — in-flight work
  finishes, then the empty replica is removed and reaped. With
  ``min_replicas=0`` the fleet parks to ZERO replicas through a
  trough (scale-to-zero); the first queued request spawns capacity
  back, bypassing the cooldown.
- **Warm standbys** (``warm_standbys=N``) are spawned drained: the
  replica warm-compiles prefill+decode (+ ``warm_tier()``) before its
  first beat, then parks out of rotation. Promotion is one
  ``end_drain()`` — scale-out adds capacity with zero compile stall.
- **Spot replicas** (``spot=True`` handles) are preemptible: reclaim
  rides the existing SIGTERM-drain / zero-loss-failover machinery
  (fault site ``replica.spot_preempt``), and the reconciler backfills
  the lost capacity immediately — preemption moves no target, costs
  no cooldown.
- **Admission control**: when even ``max_replicas`` can't hold the
  SLO for ``overload_hold_s``, the router's admission floor is raised
  to ``shed_below`` — requests whose declared priority class ranks
  below it are shed AT THE DOOR, so interactive traffic survives a
  flood that batch traffic absorbs. The floor clears the moment the
  overload signal does.

Every planned transition calls the anomaly engine's
``forget_replica`` (via the router's add/remove paths) so planned
churn never reads as an incident, and every decision is flight-recorded
WITH its input signals (burn, queue-age p95, backlog tokens, tps/chip)
so a post-mortem shows *why* the fleet moved.

Chip-seconds are the ledger: the autoscaler meters every replica's
alive span (``chips_per_replica x seconds``) into ``usage()``. Whether
that beats a static fleet over a diurnal curve: no cell measures this
(ROADMAP D7).

Cost contract: the tick itself is control-plane (it must run even with
telemetry disabled — it drives real capacity), but every metric /
flight emission inside it is gated on the module flags like the rest
of the stack.
"""
from __future__ import annotations

import math
import time
from typing import Callable, Dict, List, Optional

from .. import flight as _fl
from .. import telemetry
from .router import DEAD, DRAINING, HEALTHY
from .server import InferenceServer

__all__ = ["AutoscalePolicy", "ReplicaProvisioner", "LocalProvisioner",
           "FleetAutoscaler"]

#: gauge the sizing math reads for measured per-chip throughput
_TPS_GAUGE = "goodput_serve_tokens_per_sec_per_chip"


class AutoscalePolicy:
    """Knobs for the control loop. Everything has a production-shaped
    default; the tests tighten the windows.

    - ``min_replicas`` / ``max_replicas``: target clamp. ``min=0``
      enables scale-to-zero (the router tolerates an empty fleet while
      an autoscaler is attached; queued work spawns capacity back).
    - ``chips_per_replica``: chip-seconds multiplier for the usage
      ledger and the sizing math.
    - ``burn_out``: scale out when the SLO engine's multi-window burn
      signal exceeds this (1.0 = burning budget exactly at the
      sustainable rate).
    - ``queue_age_out_s``: ... or when the fleet queue-age p95 does.
    - ``drain_target_s`` / ``default_tokens_per_s``: sizing — add
      enough replicas to drain the queued-token backlog within
      ``drain_target_s`` at the measured (or declared fallback)
      per-replica token rate.
    - ``scale_in_load`` / ``scale_in_hold_s``: scale in after load
      fraction (queued+active over fleet slots) holds under the
      threshold, burn-free and queue-empty, for the hold window.
    - ``cooldown_out_s`` / ``cooldown_in_s``: decision rate limits
      (hysteresis); scale-from-zero and spot backfill bypass them.
    - ``warm_standbys``: drained pre-compiled spares kept warm beyond
      the active target.
    - ``shed_below`` / ``overload_hold_s``: admission floor — after
      the fleet is maxed AND the scale-out trigger has held for
      ``overload_hold_s``, shed classes ranking below ``shed_below``
      at the door (None disables).
    - ``tick_interval_s``: decision cadence (the router may step far
      faster).
    """

    def __init__(self, *,
                 min_replicas: int = 1,
                 max_replicas: int = 4,
                 chips_per_replica: int = 1,
                 burn_out: float = 1.0,
                 queue_age_out_s: float = 1.0,
                 drain_target_s: float = 5.0,
                 default_tokens_per_s: Optional[float] = None,
                 scale_in_load: float = 0.5,
                 scale_in_hold_s: float = 5.0,
                 cooldown_out_s: float = 2.0,
                 cooldown_in_s: float = 10.0,
                 warm_standbys: int = 0,
                 shed_below: Optional[str] = None,
                 overload_hold_s: float = 2.0,
                 tick_interval_s: float = 0.25):
        if min_replicas < 0:
            raise ValueError("min_replicas must be >= 0")
        if max_replicas < max(1, min_replicas):
            raise ValueError("max_replicas must be >= max(1, min)")
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        self.chips_per_replica = int(chips_per_replica)
        self.burn_out = float(burn_out)
        self.queue_age_out_s = float(queue_age_out_s)
        self.drain_target_s = float(drain_target_s)
        self.default_tokens_per_s = default_tokens_per_s
        self.scale_in_load = float(scale_in_load)
        self.scale_in_hold_s = float(scale_in_hold_s)
        self.cooldown_out_s = float(cooldown_out_s)
        self.cooldown_in_s = float(cooldown_in_s)
        self.warm_standbys = int(warm_standbys)
        self.shed_below = shed_below
        self.overload_hold_s = float(overload_hold_s)
        self.tick_interval_s = float(tick_interval_s)


class ReplicaProvisioner:
    """How the autoscaler obtains and releases capacity: a ``spawn``
    callable returning a ready-to-add replica handle (LocalReplica or
    ProcReplica — anything the router speaks) and an optional ``reap``
    called after the handle leaves the fleet (kill the subprocess,
    release the chips). Subprocess provisioning stays out of this
    module: the tests pass their own spawn/reap closures."""

    def __init__(self, spawn: Callable, reap: Optional[Callable] = None):
        self._spawn = spawn
        self._reap = reap

    def spawn(self, name: str, spot: bool = False):
        return self._spawn(name, spot)

    def reap(self, handle):
        if self._reap is not None:
            self._reap(handle)


class LocalProvisioner(ReplicaProvisioner):
    """In-process provisioner over a server factory: ``spawn`` builds
    an `InferenceServer`, warm-compiles it (`InferenceServer.warmup` —
    the wall time lands in the goodput ledger's *compile* category,
    not productive time), and wraps it in a `LocalReplica`."""

    def __init__(self, server_factory: Callable[[], InferenceServer],
                 warm: bool = True):
        self.server_factory = server_factory
        self.warm = warm
        super().__init__(self._spawn_local)

    def _spawn_local(self, name: str, spot: bool):
        from .router import LocalReplica
        server = self.server_factory()
        if self.warm:
            server.warmup()
        return LocalReplica(server, factory=self.server_factory,
                            name=name, spot=spot)


class _Managed:
    """Autoscaler-side record of one replica: where it is in the
    warming -> (standby ->) active -> draining lifecycle, whether the
    provisioner owns it (adopted seed replicas are managed but never
    reaped through the provisioner), and its usage-ledger span."""
    __slots__ = ("name", "handle", "spot", "spawned", "standby",
                 "state", "t_spawn", "t_warm", "t_alive0")

    def __init__(self, name, handle, *, spot, spawned, standby, now):
        self.name = name
        self.handle = handle
        self.spot = spot
        self.spawned = spawned          # provisioner-created
        self.standby = standby          # parked out of rotation
        self.state = "warming"          # warming|standby|active|draining
        self.t_spawn = now
        self.t_warm: Optional[float] = None
        self.t_alive0 = now             # chip-seconds span open


class FleetAutoscaler:
    """The control loop. Construct via
    ``router.attach_autoscale(provisioner=..., policy=...)`` — the
    router ticks it from ``step()`` unconditionally (capacity control
    is not observability; it runs with telemetry off)."""

    def __init__(self, router, provisioner: ReplicaProvisioner,
                 policy: Optional[AutoscalePolicy] = None, **policy_kw):
        if policy is None:
            policy = AutoscalePolicy(**policy_kw)
        elif policy_kw:
            raise ValueError("pass a policy OR kwargs, not both")
        self.router = router
        self.provisioner = provisioner
        self.policy = policy
        now = time.time()
        self._managed: Dict[str, _Managed] = {}
        for rep in router._reps:        # adopt the seed fleet
            m = _Managed(rep.name, rep.handle,
                         spot=getattr(rep.handle, "spot", False),
                         spawned=False, standby=False, now=now)
            m.state = "active"
            self._managed[rep.name] = m
        self.target = min(policy.max_replicas,
                          max(policy.min_replicas, len(self._managed)))
        self._seq = 0                   # spawned-replica name counter
        self._last_tick_t = 0.0
        self._last_out_t = 0.0
        self._last_in_t = now           # arm the scale-in cooldown
        self._idle_since: Optional[float] = None
        self._overload_since: Optional[float] = None
        self._floor_active = False
        self._chip_seconds_closed = 0.0
        # python-side counters so stats() answers with telemetry off
        self.n_scale_out = 0
        self.n_scale_in = 0
        self.n_spawned = 0
        self.n_reaped = 0
        self.n_spot_preemptions = 0
        self.n_backfills = 0

    # -- signals -------------------------------------------------------------

    def _burn(self) -> float:
        """The SLO engine's multi-window burn signal (0.0 with no
        engine attached — queue age still drives scale-out)."""
        eng = getattr(self.router, "_slo", None)
        if eng is None:
            return 0.0
        sig = getattr(eng, "burn_signal", None)
        return float(sig()) if sig is not None else 0.0

    def _queue_age_p95(self, now: float) -> float:
        q = self.router._queue
        if not q:
            return 0.0
        ages = sorted(now - fr.t_submit for fr in q)
        return ages[min(len(ages) - 1, int(0.95 * len(ages)))]

    def _backlog_tokens(self) -> int:
        return sum(len(fr.prompt) + fr.max_new_tokens
                   for fr in self.router._queue)

    def _tokens_per_replica(self) -> Optional[float]:
        tps = None
        if telemetry._ENABLED:
            tps = telemetry.read_gauge(_TPS_GAUGE)
        if not tps:
            tps = self.policy.default_tokens_per_s
        if not tps:
            return None
        return float(tps) * self.policy.chips_per_replica

    def _load_fraction(self) -> float:
        """queued+active over fleet slots, actives only."""
        used = slots = 0
        for m in self._actives():
            d = self._rep(m.name)
            d = d.detail if d is not None else None
            if d is None:
                continue
            slots += int(d.get("slots", 1))
            used += int(d.get("queued", 0)) + int(d.get("active", 0))
        if slots == 0:
            return 0.0
        return used / slots

    # -- bookkeeping ---------------------------------------------------------

    def _rep(self, name: str):
        for rep in self.router._reps:
            if rep.name == name:
                return rep
        return None

    def _actives(self) -> List[_Managed]:
        return [m for m in self._managed.values()
                if m.state in ("warming", "active") and not m.standby]

    def _standbys(self) -> List[_Managed]:
        return [m for m in self._managed.values() if m.standby]

    def _close_span(self, m: _Managed, now: float):
        self._chip_seconds_closed += \
            (now - m.t_alive0) * self.policy.chips_per_replica
        m.t_alive0 = now

    def chip_seconds(self, now: Optional[float] = None) -> float:
        """The usage ledger: chips x alive-seconds over every replica
        the autoscaler has managed (adopted seeds included), closed
        spans plus the still-open ones."""
        now = time.time() if now is None else now
        open_s = sum((now - m.t_alive0) for m in self._managed.values())
        return (self._chip_seconds_closed
                + open_s * self.policy.chips_per_replica)

    # -- lifecycle primitives ------------------------------------------------

    def _spawn(self, now: float, *, standby: bool,
               spot: bool = False) -> Optional[_Managed]:
        name = f"as{self._seq}"
        self._seq += 1
        try:
            handle = self.provisioner.spawn(name, spot)
        except Exception:
            return None                 # provider out of capacity
        spot = bool(getattr(handle, "spot", spot))
        self.router.add_replica(handle)
        if standby:
            try:
                handle.begin_drain()    # park out of rotation, warm
            except Exception:
                pass
        m = _Managed(handle.name, handle, spot=spot, spawned=True,
                     standby=standby, now=now)
        self._managed[handle.name] = m
        self.n_spawned += 1
        if _fl._ENABLED:
            _fl.record("autoscale", "autoscale.spawn",
                       replica=handle.name, standby=standby, spot=spot)
        return m

    def _promote(self, m: _Managed, now: float):
        """Standby -> active: one end_drain, zero compile stall."""
        m.standby = False
        m.state = "active" if m.t_warm is not None else "warming"
        try:
            m.handle.end_drain()
        except Exception:
            pass
        if _fl._ENABLED:
            _fl.record("autoscale", "autoscale.promote", replica=m.name)

    def _drain(self, m: _Managed, now: float):
        m.state = "draining"
        try:
            m.handle.begin_drain()
        except Exception:
            pass
        anom = getattr(self.router, "_anomaly", None)
        if anom is not None:            # planned churn, not an incident
            anom.forget_replica(m.name)
        if _fl._ENABLED:
            _fl.record("autoscale", "autoscale.drain", replica=m.name)

    def _reap(self, m: _Managed, now: float):
        self._close_span(m, now)
        self._managed.pop(m.name, None)
        allow_empty = self.policy.min_replicas == 0
        try:
            self.router.remove_replica(m.name, allow_empty=allow_empty)
        except ValueError:
            # last replica and the policy floor forbids an empty
            # fleet: put it back in rotation instead
            self._managed[m.name] = m
            m.state = "active"
            try:
                m.handle.end_drain()
            except Exception:
                pass
            return
        if m.spawned:
            try:
                self.provisioner.reap(m.handle)
            except Exception:
                pass
        self.n_reaped += 1
        if _fl._ENABLED:
            _fl.record("autoscale", "autoscale.reap", replica=m.name,
                       spot=m.spot)

    # -- the tick ------------------------------------------------------------

    def tick(self, now: Optional[float] = None):
        now = time.time() if now is None else now
        if now - self._last_tick_t < self.policy.tick_interval_s:
            return
        self._last_tick_t = now
        pol = self.policy
        self._reconcile_deaths(now)
        self._note_warm(now)
        self._reap_drained(now)

        burn = self._burn()
        q_p95 = self._queue_age_p95(now)
        backlog = self._backlog_tokens()
        n_active = len(self._actives())
        trigger = burn > pol.burn_out or q_p95 > pol.queue_age_out_s
        has_work = bool(self.router._queue) or bool(self.router._inflight)

        # scale-out: sized by the goodput ledger's tokens/sec/chip
        if trigger and n_active < pol.max_replicas \
                and now - self._last_out_t >= pol.cooldown_out_s:
            add = self._size_out(backlog)
            self._decide(now, "out", min(pol.max_replicas,
                                         n_active + add),
                         burn, q_p95, backlog)
        elif n_active == 0 and self.target == 0 and has_work:
            # scale-from-zero: queued work against a parked fleet is
            # an immediate spawn, no cooldown — nothing can serve it
            self._decide(now, "out", max(1, pol.min_replicas),
                         burn, q_p95, backlog)

        # scale-in: load under target, burn-free, queue empty, held
        idle = (not trigger and not self.router._queue
                and burn <= pol.burn_out
                and self._load_fraction() < pol.scale_in_load)
        if idle:
            if self._idle_since is None:
                self._idle_since = now
            elif now - self._idle_since >= pol.scale_in_hold_s \
                    and now - self._last_in_t >= pol.cooldown_in_s \
                    and self.target > pol.min_replicas:
                self._decide(now, "in", self.target - 1,
                             burn, q_p95, backlog)
        else:
            self._idle_since = None

        self._reconcile(now)
        self._admission_floor(now, trigger, n_active)
        if telemetry._ENABLED:
            telemetry.set_gauge("autoscale_replicas_target", self.target)
            telemetry.set_gauge("autoscale_replicas_active",
                                len(self._actives()))

    def _size_out(self, backlog_tokens: int) -> int:
        per_rep = self._tokens_per_replica()
        if per_rep is None or per_rep <= 0 or backlog_tokens <= 0:
            return 1
        return max(1, math.ceil(
            backlog_tokens / (per_rep * self.policy.drain_target_s)))

    def _decide(self, now: float, direction: str, target: int,
                burn: float, q_p95: float, backlog: int):
        """Move the target and record the decision WITH its input
        signals — the post-mortem answer to 'why did the fleet
        move'."""
        target = min(self.policy.max_replicas,
                     max(self.policy.min_replicas, target))
        if direction == "out":
            if target <= self.target:
                return
            self._last_out_t = now
            self.n_scale_out += 1
        else:
            if target >= self.target:
                return
            self._last_in_t = now
            self._idle_since = None
            self.n_scale_in += 1
        prev, self.target = self.target, target
        if telemetry._ENABLED:
            telemetry.inc("autoscale_scale_events_total",
                          direction=direction)
        if _fl._ENABLED:
            tps = self._tokens_per_replica()
            _fl.record("autoscale", "autoscale.decision",
                       direction=direction, target=target, was=prev,
                       burn=round(burn, 3), queue_age_p95=round(q_p95, 3),
                       backlog_tokens=backlog,
                       tokens_per_replica=None if tps is None
                       else round(tps, 1))

    def _reconcile_deaths(self, now: float):
        """Remove dead managed replicas; a reclaimed spot replica is
        counted (its backfill is just the reconciler seeing capacity
        under target — no cooldown, no target change)."""
        for m in list(self._managed.values()):
            rep = self._rep(m.name)
            if rep is None:
                self._close_span(m, now)
                self._managed.pop(m.name, None)
                continue
            if rep.state != DEAD:
                continue
            if m.spot:
                self.n_spot_preemptions += 1
                if telemetry._ENABLED:
                    telemetry.inc("autoscale_spot_preemptions_total")
                if _fl._ENABLED:
                    _fl.record("autoscale", "autoscale.spot_preempt",
                               replica=m.name)
            self._close_span(m, now)
            self._managed.pop(m.name, None)
            try:
                self.router.remove_replica(
                    m.name, allow_empty=True)
            except ValueError:
                pass
            if m.spawned:
                try:
                    self.provisioner.reap(m.handle)
                except Exception:
                    pass

    def _note_warm(self, now: float):
        """First healthy probe after spawn: the standby-warm latency
        (spawn -> ready) — the number that proves scale-out has no
        compile stall."""
        for m in self._managed.values():
            if m.t_warm is not None:
                continue
            rep = self._rep(m.name)
            if rep is None or rep.detail is None:
                continue
            # a parked standby probes as draining; in-rotation warming
            # probes healthy — either way the compile is behind it
            if rep.state == HEALTHY or (m.standby
                                        and rep.state == DRAINING):
                m.t_warm = now
                if m.state == "warming":
                    m.state = "standby" if m.standby else "active"
                if m.spawned and telemetry._ENABLED:
                    telemetry.observe("autoscale_standby_warm_seconds",
                                      now - m.t_spawn)

    def _reap_drained(self, now: float):
        for m in list(self._managed.values()):
            if m.state != "draining":
                continue
            rep = self._rep(m.name)
            if rep is None:
                self._close_span(m, now)
                self._managed.pop(m.name, None)
                continue
            d = rep.detail or {}
            if rep.state == DEAD or (not rep.attempts
                                     and d.get("draining")
                                     and int(d.get("queued", 0)) == 0
                                     and int(d.get("active", 0)) == 0):
                self._reap(m, now)

    def _reconcile(self, now: float):
        """Drive the live fleet toward the target: under target,
        un-drain > promote a warm standby > spawn fresh (that order is
        the zero-compile-stall ladder); over target, drain the
        preferred victim. Then top the standby pool back up."""
        pol = self.policy
        while len(self._actives()) < self.target:
            draining = [m for m in self._managed.values()
                        if m.state == "draining"]
            if draining:                # cheapest capacity: cancel a drain
                m = draining[-1]
                m.state = "active"
                try:
                    m.handle.end_drain()
                except Exception:
                    pass
                self.n_backfills += 1
                continue
            ready = [m for m in self._standbys()
                     if m.t_warm is not None]
            if ready:
                self._promote(ready[0], now)
                continue
            if self._spawn(now, standby=False) is None:
                break
            self.n_backfills += 1
        extra = len(self._actives()) - self.target
        if extra > 0:
            victims = sorted(
                self._actives(),
                key=lambda m: (not m.spot, not m.spawned, -m.t_spawn))
            for m in victims[:extra]:
                self._drain(m, now)
        want_standby = pol.warm_standbys - len(self._standbys())
        while want_standby > 0 and len(self._actives()) >= self.target:
            if self._spawn(now, standby=True) is None:
                break
            want_standby -= 1

    def _admission_floor(self, now: float, trigger: bool, n_active: int):
        pol = self.policy
        if pol.shed_below is None:
            return
        maxed = n_active >= pol.max_replicas
        if trigger and maxed:
            if self._overload_since is None:
                self._overload_since = now
            elif not self._floor_active \
                    and now - self._overload_since >= pol.overload_hold_s:
                self._floor_active = True
                self.router.admission_floor = pol.shed_below
                if _fl._ENABLED:
                    _fl.record("autoscale", "autoscale.floor",
                               shed_below=pol.shed_below, active=True)
        else:
            self._overload_since = None
            if self._floor_active:
                self._floor_active = False
                self.router.admission_floor = None
                if _fl._ENABLED:
                    _fl.record("autoscale", "autoscale.floor",
                               active=False)

    # -- reporting -----------------------------------------------------------

    def usage(self) -> dict:
        """The chip-seconds ledger plus lifecycle counters."""
        return {"chip_seconds": round(self.chip_seconds(), 3),
                "spawned": self.n_spawned, "reaped": self.n_reaped,
                "backfills": self.n_backfills}

    def stats(self) -> dict:
        return {"target": self.target,
                "active": len(self._actives()),
                "standbys": len(self._standbys()),
                "draining": sum(1 for m in self._managed.values()
                                if m.state == "draining"),
                "scale_out": self.n_scale_out,
                "scale_in": self.n_scale_in,
                "spot_preemptions": self.n_spot_preemptions,
                "admission_floor": self.router.admission_floor
                if self._floor_active else None,
                **self.usage()}
