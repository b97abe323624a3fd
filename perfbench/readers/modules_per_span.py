"""Reader ``modules_per_span``: executables the device ran in the
window (events of the trace's "XLA Modules" line, first device) over
the number of the program's spans called ``span``: how many programs
one tick or one step launches. Spec: ``{"span": name}``."""
from perfbench import mxspans


def read(spec, ctx):
    n = len(mxspans.of(ctx).named(spec["span"]))
    devs = ctx.trace.devices
    if not n or not devs:
        return None
    return len(ctx.trace.device_modules[devs[0]]) / n
