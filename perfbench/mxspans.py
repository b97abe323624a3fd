"""The program's own spans (``mx.*`` TraceAnnotations) read from the
same ``.xplane.pb`` and on the same clock as the device's operations.

``xtrace.load`` keeps the benchmark's ``pb.*`` spans only; this module
loads the ``mx.*`` events with their counts (the event's ``stats``),
cuts them to the traced window, rebuilds parent and child by
containment on each host thread, and offers the reductions the
``span_*`` readers use: a span's time less named children, the
device's idle time inside a set of spans or outside them, how many
spans of one name lie inside another, the mean of a count.

A trace of a program without the spans (the parent of the PR that
brought them) loads as an empty ``Spans``; every reader then finds
nothing to read and returns None. No host-clock value is joined to a
device time here: spans and operations come from one file.
"""
import os

from perfbench import harness, xtrace

PREFIX = "mx."


class Span:
    """One ``mx.*`` event cut to the window. ``whole`` is False where
    the window's edge cut it; ``children`` are the spans directly
    inside it on the same thread."""

    __slots__ = ("name", "start", "end", "counts", "whole", "children")

    def __init__(self, name, start, end, counts=None, whole=True):
        self.name, self.start, self.end = name, start, end
        self.counts = counts or {}
        self.whole = whole
        self.children = []

    @property
    def ns(self):
        return self.end - self.start

    def descendants(self):
        for c in self.children:
            yield c
            yield from c.descendants()


def nest(spans):
    """Set ``children`` by containment, for the spans of ONE thread
    (they nest or are disjoint there). Returns them in start order."""
    spans = sorted(spans, key=lambda s: (s.start, -s.end))
    stack = []
    for s in spans:
        s.children = []
        while stack and stack[-1].end <= s.start:
            stack.pop()
        if stack and s.end <= stack[-1].end:
            stack[-1].children.append(s)
        stack.append(s)
    return spans


def intersect(a, b):
    """Parts of merged intervals ``a`` covered by merged ``b``."""
    return xtrace.subtract(a, xtrace.subtract(a, b))


class Spans:
    """The ``mx.*`` spans of a traced window, with the device's idle
    intervals in it (first device that ran anything)."""

    def __init__(self, threads, window, idle):
        self.t0, self.t1 = window
        self.idle = idle            # merged [start, end) ns, or None
        self.spans = [s for th in threads for s in nest(th)]

    def named(self, name, whole=False):
        return [s for s in self.spans
                if s.name == name and (s.whole or not whole)]

    def self_ns(self, span, less=()):
        """The span's time less its descendants of the names in
        ``less`` (they do not overlap each other on one thread unless
        one is inside the other; the union counts each ns once)."""
        cover = xtrace.union((c.start, c.end) for c in span.descendants()
                             if c.name in less)
        return span.ns - xtrace.total(cover)

    def inside(self, span, name):
        return [c for c in span.descendants() if c.name == name]

    def cover(self, name, less=()):
        """Merged intervals covered by the spans called ``name``, less
        those covered by the spans called any of ``less``."""
        a = xtrace.union((s.start, s.end) for s in self.named(name))
        b = xtrace.union((s.start, s.end) for s in self.spans
                         if s.name in less)
        return xtrace.subtract(a, b)

    def outside(self, name):
        """The window less every span called ``name``."""
        return xtrace.subtract([[self.t0, self.t1]], self.cover(name))

    def idle_ns(self, intervals):
        """Nanoseconds of ``intervals`` in which no operation ran on
        the device; None where the trace holds no device."""
        if self.idle is None:
            return None
        return xtrace.total(intersect(self.idle, intervals))

    def count_mean(self, name, key):
        xs = [s.counts[key] for s in self.named(name) if key in s.counts]
        return sum(xs) / len(xs) if xs else None


def device_idle(trace):
    """Merged idle intervals of the window on the first device that ran
    anything, as ``xtrace.Trace.idle_gaps`` takes them; None where no
    device ran anything."""
    t0, t1 = trace.window()
    devs = trace.devices
    if not devs or t1 <= t0:
        return None
    return trace.idle(devs[0])


def build(threads, trace):
    """``Spans`` from per-thread lists of ``(name, start_ns, dur_ns,
    counts)`` and a windowed ``xtrace.Trace``."""
    t0, t1 = trace.window()
    cut = []
    for events in threads:
        th = []
        for name, s, d, counts in events:
            a, b = max(s, t0), min(s + d, t1)
            if b > a:
                th.append(Span(name, a, b, counts,
                               whole=(a == s and b == s + d)))
        if th:
            cut.append(th)
    return Spans(cut, (t0, t1), device_idle(trace))


def read_threads(path):
    """Per host thread, the ``mx.*`` events of an ``.xplane.pb`` file as
    ``(name, start_ns, dur_ns, counts)``."""
    from jax.profiler import ProfileData

    threads = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns), int(e.duration_ns),
                    dict(e.stats))
                   for e in line.events if e.name.startswith(PREFIX)]
            if evs:
                threads.append(evs)
    return threads


def of(ctx):
    """The ``Spans`` of a reader's context: loaded once from the trace
    directory the harness wrote and kept on the context."""
    got = getattr(ctx, "_mxspans", None)
    if got is None:
        with ctx.phases.timed("read.mxspans"):
            try:
                threads = read_threads(xtrace.find_xplane(
                    os.path.join(harness.ROOT, ".pb_trace")))
            except FileNotFoundError:
                threads = []
            got = ctx._mxspans = build(threads, ctx.trace)
    return got
