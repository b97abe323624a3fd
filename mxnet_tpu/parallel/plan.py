"""One composable ParallelPlan: pp × tp × dp(+ZeRO) × MoE in one step.

The pairwise degrade matrices that grew around FusedTrainStep (pipeline
clamps zero=3→2 and rejects TP shardings; TP and MoE each live in their
own module; wire compression re-plumbed per special case) made the
compositions the MLPerf-on-TPU-pods recipe needs (arXiv:1909.09756)
inexpressible. ``ParallelPlan`` replaces them with one declaration:

    plan = ParallelPlan(dp=2, pp=4, zero=3, microbatches=8, virtual=2,
                        compression={"activations": "int8"})
    step = plan.lower(net, loss_fn, trainer)   # one compiled step

The plan owns the mesh axes (dp/tp/pp; ep rides the dp axis), validates
the REQUESTED combination once — every violation in one loud
:class:`PlanError`, no warn-and-degrade — and lowers through
``FusedTrainStep`` with ``plan=self``, which switches the builders from
the legacy clamp/drop behavior to the real compositions:

=============  =============================================== =========
combination    how it runs                                     notes
=============  =============================================== =========
dp             GSPMD batch sharding (plain fused step)
dp × zero1-3   shard_map flat-bucket update sharding           dp >= 2
dp × tp        GSPMD via Parameter.sharding                    pp == 1
pp × dp        1F1B shard_map (stages × replicas)              needs M
pp × virtual   interleaved Megatron schedule (chunks = pp·v)   M % pp == 0
pp × zero1-3   flat per-stage shards; zero=3 keeps residents
               sharded and gathers transiently in-step
pp × tp        manual region: local matmuls + psum(tp)         zero == 0,
                                                               elementwise
                                                               optimizer
ep × dp(+z1)   manual MoE: expert-local FFN + token exchange   ep == dp
compression    quantized gathers / ppermutes per requesting
               axis (grads: dp buckets; weights: zero gathers;
               activations: pp hops)
=============  =============================================== =========

Rejected (loud, never silently degraded): tp × zero, tp × ep, ep × pp,
ep × zero>=2, grads-compression × {tp, pp, ep}, weight-residual
compression with pp or zero != 3, virtual without pp, pp without
microbatches. See docs/parallel_plan.md for the full matrix rationale.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from .mesh import Mesh, make_mesh

__all__ = ["ParallelPlan", "PlanError"]


class PlanError(ValueError):
    """A ParallelPlan validation failure. Carries EVERY violation of
    the compatibility matrix (``.violations``), not just the first —
    the single loud error path that replaced the scattered warn-once
    degrades."""

    def __init__(self, violations):
        self.violations = [str(v) for v in violations]
        super().__init__(
            "invalid ParallelPlan:\n" +
            "\n".join(f"  - {v}" for v in self.violations))


@dataclasses.dataclass(frozen=True)
class ParallelPlan:
    """Declarative parallelism plan over a dp × pp × tp device mesh.

    Axis sizes: ``dp`` (data/ZeRO), ``tp`` (tensor), ``pp`` (pipeline),
    ``ep`` (experts — shares the dp mesh axis, so ``ep == dp`` when
    used). ``zero`` is the ZeRO stage over dp; ``microbatches`` the
    1F1B M (required when pp > 1); ``virtual`` the interleaved
    virtual-stage count per pp rank (Megatron arXiv:2104.04473 §2.2);
    ``compression`` the per-direction wire config FusedTrainStep
    accepts ({"grads"|"weights"|"activations": ...}).

    Validation runs at construction and raises :class:`PlanError` with
    every violation. :meth:`lower` builds the mesh (unless given one)
    and returns the compiled-step wrapper.
    """

    dp: int = 1
    tp: int = 1
    pp: int = 1
    ep: int = 1
    zero: int = 0
    virtual: int = 1
    microbatches: Optional[int] = None
    grad_accum: int = 1
    compression: Optional[dict] = None
    dp_axis: str = "dp"
    tp_axis: str = "tp"
    pp_axis: str = "pp"

    def __post_init__(self):
        if self.compression is not None:
            object.__setattr__(self, "compression",
                               dict(self.compression))
        self.validate()

    # -- compatibility matrix -------------------------------------------
    def _comp_parts(self):
        """(grads, weights, activations) wire-compression requests —
        the legacy flat {"type": ...} dict counts as grads."""
        c = self.compression
        if not c:
            return None, None, None
        if {"grads", "weights", "activations"} & set(c.keys()):
            return c.get("grads"), c.get("weights"), c.get("activations")
        return c, None, None

    def validate(self) -> None:
        """Check the full combination against the compatibility matrix;
        raise :class:`PlanError` listing EVERY violation."""
        v = []
        for name in ("dp", "tp", "pp", "ep", "virtual", "grad_accum"):
            val = getattr(self, name)
            if not isinstance(val, int) or val < 1:
                v.append(f"{name} must be an int >= 1; got {val!r}")
        if self.zero not in (0, 1, 2, 3):
            v.append(f"zero must be 0..3; got {self.zero!r}")
        M = self.microbatches
        if M is not None and (not isinstance(M, int) or M < 1):
            v.append(f"microbatches must be an int >= 1; got {M!r}")
        # collect size/type errors first; the matrix below assumes sane
        # scalars
        if v:
            raise PlanError(v)

        if self.zero >= 1 and self.dp < 2:
            v.append(f"zero={self.zero} shards the update over dp; "
                     f"needs dp >= 2 (got dp={self.dp})")
        if self.pp > 1 and M is None:
            v.append(f"pp={self.pp} runs the 1F1B schedule; set "
                     "microbatches=M")
        if self.pp == 1 and M is not None:
            v.append("microbatches is a pipeline knob; drop it or set "
                     "pp > 1 (use grad_accum for plain accumulation)")
        if self.virtual > 1:
            if self.pp == 1:
                v.append(f"virtual={self.virtual} interleaves pipeline "
                         "chunks; needs pp > 1")
            elif M is not None and M % self.pp != 0:
                v.append(f"the interleaved schedule needs microbatches "
                         f"% pp == 0; got M={M}, pp={self.pp}")
        if self.tp > 1 and self.zero >= 1:
            v.append("tp x zero is not supported: the manual/GSPMD TP "
                     "weight shards cannot ride the flat dp update "
                     "buckets — drop zero or tp")
        if self.tp > 1 and self.ep > 1:
            v.append("tp x ep is not supported — shard experts (ep) or "
                     "features (tp), not both")
        if self.ep > 1 and self.pp > 1:
            v.append("ep x pp is not supported — keep MoE nets "
                     "unpipelined")
        if self.ep > 1 and self.ep != self.dp:
            v.append(f"ep rides the dp mesh axis; needs ep == dp "
                     f"(got ep={self.ep}, dp={self.dp})")
        if self.ep > 1 and self.zero >= 2:
            v.append(f"ep x zero={self.zero} is not supported: expert-"
                     "local state composes with zero=1 only")

        grads, weights, acts = self._comp_parts()
        if grads is not None and self.tp > 1:
            v.append("gradient compression x tp is not supported: tp "
                     "grads are per-shard, not dp buckets")
        if grads is not None and self.pp > 1:
            v.append("gradient compression x pp is not supported: the "
                     "pipeline step reduces grads inside the schedule "
                     "(compress 'activations' and/or 'weights' instead)")
        if grads is not None and self.ep > 1:
            v.append("gradient compression x ep is not supported: "
                     "expert grads never ride the dp buckets")
        if acts is not None and self.pp == 1:
            v.append("compression={'activations': ...} quantizes the "
                     "pipeline ppermute hops; needs pp > 1")
        if weights is not None and self.zero == 0:
            v.append("compression={'weights': ...} quantizes the ZeRO "
                     "weight all-gather; needs zero >= 1")
        wres = isinstance(weights, dict) and bool(weights.get("residual"))
        if wres and self.zero != 3:
            v.append("weight-compression residual mode needs zero=3 "
                     "(only re-gathered residents drift)")
        if wres and self.pp > 1:
            v.append("weight-compression residual mode is not wired "
                     "into the pipeline zero=3 path — drop residual")
        if v:
            raise PlanError(v)

    # -- lowering ---------------------------------------------------------
    @property
    def total_devices(self) -> int:
        return self.dp * self.pp * self.tp

    def build_mesh(self, devices=None) -> Mesh:
        """dp × pp × tp mesh (tp innermost — fastest links; ep shares
        the dp axis, so no extra mesh dimension)."""
        return make_mesh([self.dp, self.pp, self.tp],
                         [self.dp_axis, self.pp_axis, self.tp_axis],
                         devices)

    def lower(self, net, loss_fn, trainer, mesh=None, **kwargs):
        """Build (or take) the mesh and lower net+loss+trainer into one
        compiled FusedTrainStep carrying this plan — the builders run
        the REAL compositions (manual pp×tp, true pp×zero=3,
        interleaved virtual stages, manual ep) instead of the legacy
        warn/clamp paths. Extra kwargs pass through to FusedTrainStep
        (n_model_inputs, donate, ...)."""
        from .. import goodput as _gp
        from .data_parallel import FusedTrainStep
        if self.tp > 1 and self.pp > 1:
            from .. import multi_tensor as _mt
            opt = getattr(trainer, "_optimizer", trainer)
            if not _mt.is_elementwise_rule(opt):
                raise PlanError([
                    "pp x tp keeps each weight's tp shard local "
                    "through the update, which needs an elementwise "
                    f"optimizer; {type(opt).__name__} consumes "
                    "per-tensor norms"])
        if mesh is None:
            mesh = self.build_mesh()
        step = FusedTrainStep(
            net, loss_fn, trainer, mesh=mesh,
            dp_axis=self.dp_axis, pp_axis=self.pp_axis,
            compression=self.compression, zero=self.zero,
            pipeline=self.microbatches,
            grad_accum=self.grad_accum, plan=self,
            virtual=self.virtual, **kwargs)
        _gp.set_plan_axes(dp=self.dp, tp=self.tp, pp=self.pp,
                          ep=self.ep)
        return step

    def describe(self) -> str:
        """Human-readable one-plan summary."""
        parts = [f"dp={self.dp}", f"tp={self.tp}", f"pp={self.pp}",
                 f"ep={self.ep}", f"zero={self.zero}"]
        if self.pp > 1:
            parts.append(f"microbatches={self.microbatches}")
            parts.append(f"virtual={self.virtual}")
        if self.grad_accum > 1:
            parts.append(f"grad_accum={self.grad_accum}")
        if self.compression:
            g, w, a = self._comp_parts()
            on = [k for k, c in
                  (("grads", g), ("weights", w), ("activations", a))
                  if c is not None]
            parts.append("compression=" + "+".join(on))
        return ("ParallelPlan(" + ", ".join(parts) +
                f") over {self.total_devices} devices")
