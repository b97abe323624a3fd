"""Reader ``exposed_collective``: seconds in which a collective ran on
the device and no other operation did, over the traced window, in
percent (averaged over chips). Nothing to read on one chip."""


def read(spec, ctx):
    t0, t1 = ctx.trace.window()
    exposed, total = ctx.trace.exposed_collective_s()
    if t1 <= t0 or total <= 0:
        return None
    return 100.0 * exposed / ((t1 - t0) * 1e-9)
