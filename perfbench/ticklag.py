"""Every decode tick joined to its launch, its run on the device and
its read-back, on the one clock the program's spans and the device's
executables share.

The server numbers its launches: ``mx.serve_dispatch`` carries
``tick``, the ordinal of the tick it launches, and ``mx.serve_wait``
the ordinal of the tick it reads. The decode executables of the first
device (module name and kernel inside, as ``decode_dev_ms_p50.*`` tells
them) run in the order they were launched, so within a run of
consecutive numbers tick ``k0 + j`` ran as module ``j + o`` for ONE
offset ``o``. A module cannot start before its dispatch began, so
``o >= lb(j) - j`` for every tick, ``lb(j)`` being the first module
that starts at or after dispatch ``j`` began; and the bound is met by
every tick whose module is that first one, which is every tick but one
launched while the module before it had not started yet (the device
still busy with a prefill). So ``o`` is the largest of the bounds: the
join is anchored on the ticks that bind, not on the first alone. Where
a number is missing a new run starts behind the modules already
matched. A tick whose matched module ends after the wait that read it
(its own module is not in the trace) gets no module: ``None``, not a
guess, and the ticks behind it are anchored anew; a tick with no
module left to match gets none either.

Nothing here walks the device's operations or its idle intervals: the
modules and the spans are a few thousand a window.

The percentiles the benchmark keeps of this table cannot name a tick.
For the ticks themselves, after a traced run (it leaves ``.pb_trace/``
in the checkout)::

    python3 -m perfbench.ticklag [trace directory or .xplane.pb] [rows]

prints the ticks read back latest after their module ended and the
``mx.serve_tick`` spans in which the host itself took longest, with
their parts (``docs/observability.md``, the runbook, says how to read
them).
"""
import bisect
import os
import re
import sys

DISPATCH = "mx.serve_dispatch"
WAIT = "mx.serve_wait"
TICK = "tick"


class Tick:
    """One launch: its ordinal, the dispatch span that launched it, the
    wait span that read it (None where none did inside the window),
    the ``(start, end)`` of its module on the device (None where the
    join refused), and the two lags in ns (None where a part is
    missing)."""

    __slots__ = ("seq", "dispatch", "wait", "module", "read_after_done",
                 "launch_lag")

    def __init__(self, seq, dispatch, wait):
        self.seq, self.dispatch, self.wait = seq, dispatch, wait
        self.module = self.read_after_done = self.launch_lag = None


def decode_modules(trace, pattern, contains=None):
    """``(start, end)`` of the first device's executables whose name
    matches ``pattern`` and, with ``contains``, in which that kernel
    ran, in start order (what ``Trace.module_durations_s`` keeps the
    durations of)."""
    devs = trace.devices
    if not devs:
        return []
    pat = re.compile(pattern)
    mods = sorted((s, s + d) for n, s, d in trace.device_modules[devs[0]]
                  if pat.search(n))
    if contains is None:
        return mods
    marks = sorted(s for _, s, _ in trace.kernel(devs[0], contains))
    out = []
    for s, e in mods:
        i = bisect.bisect_left(marks, s)
        if i < len(marks) and marks[i] < e:
            out.append((s, e))
    return out


def every_module(trace):
    """``(start, end)`` of every executable of the first device, in
    start order."""
    devs = trace.devices
    if not devs:
        return []
    return sorted((s, s + d) for _, s, d in trace.device_modules[devs[0]])


def runs(ticks):
    """``ticks`` (sorted by number) split where a number is missing."""
    out = []
    for t in ticks:
        if out and t.seq == out[-1][-1].seq + 1:
            out[-1].append(t)
        else:
            out.append([t])
    return out


def join(spans, modules, every):
    """The ``Tick`` rows of a window, by number: ``spans`` a
    ``mxspans.Spans``, ``modules`` the decode executables as
    :func:`decode_modules` gives them, ``every`` the ``(start, end)``
    of every executable of the device in start order. None where no
    whole dispatch carries a number (a program from before the counts),
    a number comes twice, or no decode module ran."""
    waits = {s.counts[TICK]: s for s in spans.named(WAIT, whole=True)
             if TICK in s.counts}
    ticks = sorted((Tick(s.counts[TICK], s, waits.get(s.counts[TICK]))
                    for s in spans.named(DISPATCH, whole=True)
                    if TICK in s.counts), key=lambda t: t.seq)
    if not ticks or not modules \
            or len({t.seq for t in ticks}) < len(ticks):
        return None         # two servers' numbers in one window: no guess
    starts = [s for s, _ in modules]
    every_starts = [s for s, _ in every]
    first = 0               # modules before it are matched already
    for run in runs(ticks):
        while run:
            offset = max(bisect.bisect_left(starts, t.dispatch.start,
                                            first) - j
                         for j, t in enumerate(run))
            rest = []
            for j, t in enumerate(run):
                i = j + offset
                if i >= len(modules):
                    break
                s, e = modules[i]
                if t.wait is not None and e > t.wait.end:
                    # read before it ended: not this tick's module,
                    # whose own the trace has lost. The ticks behind it
                    # are anchored anew
                    rest = run[j + 1:]
                    break
                t.module = (s, e)
                first = i + 1
                if t.wait is not None:
                    t.read_after_done = t.wait.end - e
                # the executables of one device run one after another:
                # the one that started before this one ended before it
                before = bisect.bisect_left(every_starts, s) - 1
                ready = t.dispatch.end if before < 0 \
                    else max(t.dispatch.end, every[before][1])
                t.launch_lag = max(0, s - ready)
            run = rest
    return ticks


def of(ctx, spans, pattern, contains=None):
    """The joined ticks of a reader's context, worked out once for each
    kind of decode module and kept on the context."""
    kept = getattr(ctx, "_ticklag", None)
    if kept is None:
        kept = ctx._ticklag = {}
    if (pattern, contains) not in kept:
        if not any(TICK in s.counts for s in spans.named(DISPATCH)):
            # a program from before the counts: the device's modules
            # and kernels are not walked
            return None
        with ctx.phases.timed("read.ticklag"):
            kept[pattern, contains] = join(
                spans, decode_modules(ctx.trace, pattern, contains),
                every_module(ctx.trace))
    return kept[pattern, contains]


def rows(ticks, spans, n=12):
    """What a person looks at when a run was slow, in ms: the ``n``
    ticks with the largest ``read_after_done`` (a late read-back), and
    the ``n`` whole ``mx.serve_tick`` spans with the largest self time
    less their wait, with their parts (a stalled upload is a long
    ``serve_dispatch`` there; ``late`` does not count it)."""
    def ms(ns):
        return None if ns is None else round(ns / 1e6, 3)

    def parts(tick):
        out = {}
        for s in tick.descendants():
            name = s.name[len("mx."):]
            out[name] = out.get(name, 0) + s.ns
        return {k: ms(v) for k, v in out.items()}

    slow = sorted((t for t in ticks if t.read_after_done is not None),
                  key=lambda t: -t.read_after_done)[:n]
    host = sorted(spans.named("mx.serve_tick", whole=True),
                  key=lambda s: -spans.self_ns(s, [WAIT]))[:n]
    return {
        "read_back": [{"tick": t.seq,
                       "late": t.dispatch.counts.get("late"),
                       "read_after_done": ms(t.read_after_done),
                       "launch_lag": ms(t.launch_lag),
                       "dispatch": ms(t.dispatch.ns),
                       "module": ms(t.module[1] - t.module[0]),
                       "wait": ms(t.wait.ns)} for t in slow],
        "host": [{"self": ms(spans.self_ns(s, [WAIT])), **parts(s)}
                 for s in host]}


def main(argv):
    from perfbench import mxspans, xtrace

    path = argv[1] if len(argv) > 1 else ".pb_trace"
    n = int(argv[2]) if len(argv) > 2 else 12
    if os.path.isdir(path):
        path = xtrace.find_xplane(path)
    trace = xtrace.load(path).windowed()
    spans = mxspans.build(mxspans.read_threads(path), trace)
    ticks = join(spans, decode_modules(trace, "jit_counted",
                                       "flash_decode_paged"),
                 every_module(trace))
    if ticks is None:
        print("no numbered tick joined: a program from before the "
              "`tick` counts, two servers in one window, or no decode "
              "module")
        return 1
    out = rows(ticks, spans, n)
    print(f"{len(ticks)} ticks, "
          f"{sum(t.module is not None for t in ticks)} joined; ms")
    for title, key in (("read back latest after the device was done",
                        "read_back"),
                       ("mx.serve_tick: the host's own time (less the "
                        "wait), and its parts", "host")):
        print(title)
        for r in out[key]:
            print("  " + "  ".join(f"{k}={v}" for k, v in r.items()))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
