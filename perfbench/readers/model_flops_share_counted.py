"""Reader ``model_flops_share_counted``: model FLOP/s utilization like
``model_flops_share``, for a step whose work the shapes alone do not
give: the operations the window's work requires, from a cost module of
its own over the generator's counters with the PROGRAM's span counts
laid over them (``kernel_roofline_mixed.counted``), over window seconds
x chips x peak bf16 FLOP/s, in percent. Spec: ``{"costs": module under
perfbench, "cost": key of its COSTS, "counts": {name: [span, count
key], ...}}``. The counts are those of the steps READ in the window
(a step's counts ride the span of a later call), as many as ran."""
import importlib

from perfbench.readers import kernel_roofline_mixed


def read(spec, ctx):
    work = kernel_roofline_mixed.counted(spec, ctx) \
        if ctx.window_s else None
    if work is None:
        return None
    table = importlib.import_module("perfbench." + spec["costs"]).COSTS
    return 100.0 * table[spec["cost"]](ctx.config, work)["flops"] / (
        ctx.window_s * ctx.chips * ctx.peaks["flops_bf16"])
