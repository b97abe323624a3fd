"""Reader ``model_flops_share``: model FLOP/s utilization — the
operations the window's work requires (``perfbench/costs.py``; no
recomputation, no padding) over window seconds x chips x peak bf16
FLOP/s, in percent. Spec: ``{"cost": name}``."""
from perfbench import costs


def read(spec, ctx):
    if not ctx.window_s:
        return None
    cost = costs.COSTS[spec["cost"]](ctx.config, ctx.work)
    return 100.0 * cost["flops"] / (
        ctx.window_s * ctx.chips * ctx.peaks["flops_bf16"])
