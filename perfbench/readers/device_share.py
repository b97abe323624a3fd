"""Reader ``device_share``: the share of the traced window in which no
operation ran on the device (``"what": "idle"``) or one did
(``"busy"``), in percent, averaged over the chips used."""


def read(spec, ctx):
    t0, t1 = ctx.trace.window()
    if t1 <= t0 or not ctx.trace.devices:
        return None
    busy = ctx.trace.busy_s() / ((t1 - t0) * 1e-9)
    return 100.0 * (busy if spec["what"] == "busy" else 1.0 - busy)
