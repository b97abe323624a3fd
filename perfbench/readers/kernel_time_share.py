"""Reader ``kernel_time_share``: the share of the device's busy time
spent in the named Pallas kernels, in percent.
Spec: ``{"kernels": [names...]}``."""


def read(spec, ctx):
    busy = ctx.trace.busy_s()
    if busy <= 0:
        return None
    seconds = sum(ctx.trace.kernel_seconds(k)[0] for k in spec["kernels"])
    return 100.0 * seconds / busy
