"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy/idle by interval union, time by
operation and by kernel name, exposed collective time, and idle gaps
attributed to the host span that covers them.

Read with nothing but JAX (``jax.profiler.ProfileData``). The interval
arithmetic works on plain ``(name, start_ns, duration_ns)`` tuples so
the tests can check it on a synthetic timeline as well as on the small
recorded trace in ``perfbench/tests/data``.
"""
import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast)")
#: host spans the benchmark itself writes (TraceAnnotation) start so
HOST_SPAN_PREFIX = "pb."
#: the span the harness lays over the whole traced window
WINDOW_SPAN = "pb.window"
_SUFFIX = re.compile(r"(\.\d+)+$")


def base_name(name):
    """``fusion.123`` -> ``fusion``; ``%all-reduce-start.1`` ->
    ``all-reduce-start``. The TPU's trace names an operation by its
    whole HLO line (``fusion.271 = (bf16[...]) fusion(...)``): only the
    result's name before `` = `` is kept. Pallas custom calls carry
    their kernel's ``name=`` and keep it."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0].strip().lstrip("%"))


def union(intervals):
    """Merged, sorted ``[start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def clip(events, t0, t1):
    """Events cut to the window ``[t0, t1)`` (ns)."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def leaf_events(events):
    """Drop events that enclose other events of the same line (a
    ``while`` op spans its body's ops): time by name then counts each
    nanosecond once. A collective is never a container: compute that
    runs while it is in flight overlaps it and is not its body.
    O(n log n) sweep over start-sorted events."""
    evs = sorted(events, key=lambda e: (e[1], -(e[2])))
    out = []
    stack = []   # (end, index into out or None)
    for name, s, d in evs:
        e = s + d
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack and e <= stack[-1][0]:
            # nested inside the top of the stack: the parent is not a
            # leaf
            parent = stack[-1][1]
            if parent is not None and out[parent] is not None \
                    and not COLLECTIVE.match(base_name(out[parent][0])):
                out[parent] = None
        out.append((name, s, d))
        stack.append((e, len(out) - 1))
    return [ev for ev in out if ev is not None]


def time_by_name(events, leaves=None):
    """{base name: seconds} over leaf events (``leaves``: the events'
    leaves where the caller holds them already)."""
    acc = {}
    for name, _, d in leaf_events(events) if leaves is None else leaves:
        k = base_name(name)
        acc[k] = acc.get(k, 0.0) + d * 1e-9
    return acc


def kernel_events(events, kernel, leaves=None):
    """Events of one Pallas kernel: the custom call's name carries the
    kernel's ``name=`` (PR 21 gave every pallas_call one). Inside a
    differentiated step JAX decorates it (``jvp_flash_attention_fwd_``,
    ``transpose_jvp_flash_attention_dkv__``): a prefix ending in ``_``
    and trailing underscores are allowed, a longer kernel name
    (``flash_decode_paged_q8``) is not."""
    pat = re.compile(r"(^|_)" + re.escape(kernel) + r"_*$")
    return [ev for ev in (leaf_events(events) if leaves is None
                          else leaves)
            if pat.search(base_name(ev[0]))]


def exposed_collective_s(events, leaves=None):
    """Seconds in which a collective ran on the device and nothing else
    did: union(collectives) minus union(everything else)."""
    if leaves is None:
        leaves = leaf_events(events)
    coll = union((s, s + d) for n, s, d in leaves
                 if COLLECTIVE.match(base_name(n)))
    rest = union((s, s + d) for n, s, d in leaves
                 if not COLLECTIVE.match(base_name(n)))
    return total(subtract(coll, rest)) * 1e-9, total(coll) * 1e-9


def host_spans_by_start(host_spans):
    """The spans an idle gap may be charged to, by start: all but the
    one the harness lays over the whole window."""
    return sorted((e for e in host_spans if e[0] != WINDOW_SPAN),
                  key=lambda e: e[1])


def charge_gaps(gaps, spans):
    """``({span name: idle seconds}, span visits)``: each gap of the
    sorted, disjoint ``gaps`` is charged to the span of ``spans``
    (sorted by start) that overlaps it most, the earliest-starting one
    on a tie, or to ``pb.unattributed`` where no overlap is positive.

    ONE sweep: ``live`` holds, in start order, the spans that start
    before the gap's end and have not ended by its start, which are
    exactly the spans a walk from the first span would find overlapping
    it. A span enters once and leaves once, so the cost is gaps + spans
    + overlaps (the visits returned), not gaps x spans; a span over the
    whole window costs one visit a gap and hides no later one."""
    acc = {}
    live = []
    nxt = visits = 0
    for gs, ge in gaps:
        while nxt < len(spans) and spans[nxt][1] < ge:
            live.append(spans[nxt])
            nxt += 1
            visits += 1
        live = [sp for sp in live if sp[1] + sp[2] > gs]
        visits += len(live)
        best, best_ov = "pb.unattributed", 0
        for name, s, d in live:
            ov = min(ge, s + d) - max(gs, s)
            if ov > best_ov:
                best, best_ov = name, ov
        acc[best] = acc.get(best, 0.0) + (ge - gs) * 1e-9
    return acc, visits


def idle_gaps(events, host_spans, t0, t1):
    """{host span name: idle seconds}: each maximal interval of
    ``[t0, t1)`` with no device operation is charged to the benchmark's
    host span (``pb.*``) that overlaps it most, or to
    ``pb.unattributed``."""
    busy = union((s, s + d) for _, s, d in events)
    return charge_gaps(subtract([[t0, t1]], busy),
                       host_spans_by_start(host_spans))[0]


class Trace:
    """One loaded trace: per-device op and module events, and the
    benchmark's host spans, all as ``(name, start_ns, dur_ns)``."""

    def __init__(self, device_ops, device_modules, host_spans):
        self.device_ops = device_ops          # {device id: [events]}
        self.device_modules = device_modules  # {device id: [events]}
        self.host_spans = host_spans          # [events], names pb.*
        # what several readers ask for again, worked out once a device:
        # the events are never changed after loading
        self._busy, self._leaves, self._kernel = {}, {}, {}
        self._gaps = None

    def busy(self, dev):
        """Merged intervals in which an operation ran on ``dev``."""
        if dev not in self._busy:
            self._busy[dev] = union((s, s + d)
                                    for _, s, d in self.device_ops[dev])
        return self._busy[dev]

    def leaves(self, dev):
        """:func:`leaf_events` of ``dev``'s operations."""
        if dev not in self._leaves:
            self._leaves[dev] = leaf_events(self.device_ops[dev])
        return self._leaves[dev]

    def kernel(self, dev, kernel):
        """:func:`kernel_events` of one kernel on ``dev``."""
        if (dev, kernel) not in self._kernel:
            self._kernel[dev, kernel] = kernel_events(
                self.device_ops[dev], kernel, self.leaves(dev))
        return self._kernel[dev, kernel]

    @property
    def devices(self):
        return sorted(d for d, ev in self.device_ops.items() if ev)

    def window(self):
        """The traced window on the trace's clock: the span named
        ``pb.window`` when the harness wrote one, else first device op
        to last."""
        for name, s, d in self.host_spans:
            if name == WINDOW_SPAN:
                return s, s + d
        starts = [e[1] for ev in self.device_ops.values() for e in ev]
        ends = [e[1] + e[2] for ev in self.device_ops.values() for e in ev]
        return (min(starts), max(ends)) if starts else (0, 0)

    def windowed(self):
        """A copy cut to :meth:`window`."""
        t0, t1 = self.window()
        return Trace({d: clip(ev, t0, t1)
                      for d, ev in self.device_ops.items()},
                     {d: clip(ev, t0, t1)
                      for d, ev in self.device_modules.items()},
                     clip(self.host_spans, t0, t1))

    def busy_s(self):
        """Seconds in which an operation ran, averaged over the devices
        that ran anything."""
        devs = self.devices
        if not devs:
            return 0.0
        return sum(total(self.busy(k)) for k in devs) * 1e-9 / len(devs)

    def op_seconds(self):
        """{base op name: seconds}, averaged over devices."""
        devs = self.devices
        acc = {}
        for k in devs:
            for n, v in time_by_name(self.device_ops[k],
                                  self.leaves(k)).items():
                acc[n] = acc.get(n, 0.0) + v / len(devs)
        return acc

    def kernel_seconds(self, kernel):
        """(seconds, calls) of one kernel, averaged over devices."""
        devs = self.devices
        if not devs:
            return 0.0, 0
        evs = [self.kernel(k, kernel) for k in devs]
        return (sum(d for ev in evs for _, _, d in ev) * 1e-9 / len(devs),
                sum(len(ev) for ev in evs) // len(devs))

    def module_durations_s(self, pattern, contains=None):
        """Durations (s) of the executables whose module name matches
        ``pattern``, on the first device; with ``contains``, only those
        in which an operation of that (kernel) name ran. The serving
        programs are all called ``jit_counted``: the decode executable
        is the one holding ``flash_decode_paged``, the prefill the one
        holding ``flash_attention_fwd``."""
        devs = self.devices
        if not devs:
            return []
        pat = re.compile(pattern)
        mods = [(s, s + d) for n, s, d in self.device_modules[devs[0]]
                if pat.search(n)]
        if contains is None:
            return [(e - s) * 1e-9 for s, e in mods]
        marks = sorted(s for _, s, _ in self.kernel(devs[0], contains))
        out = []
        for s, e in mods:
            i = bisect.bisect_left(marks, s)
            if i < len(marks) and marks[i] < e:
                out.append((e - s) * 1e-9)
        return out

    def exposed_collective_s(self):
        devs = self.devices
        if not devs:
            return 0.0, 0.0
        pairs = [exposed_collective_s(self.device_ops[k], self.leaves(k))
                 for k in devs]
        return (sum(p[0] for p in pairs) / len(devs),
                sum(p[1] for p in pairs) / len(devs))

    def idle_gaps(self):
        devs = self.devices
        if not devs:
            return {}
        if self._gaps is None:
            self._gaps = charge_gaps(self.idle(devs[0]),
                                     host_spans_by_start(self.host_spans))[0]
        return self._gaps

    def idle(self, dev):
        """Merged intervals of the window in which no operation ran on
        ``dev``."""
        t0, t1 = self.window()
        return subtract([[t0, t1]], self.busy(dev))

    def breakdown(self, top=10):
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        gaps = sorted(self.idle_gaps().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[n, v] for n, v in ops[:top]],
                "idle_gaps": [[n, v] for n, v in gaps[:top]]}


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path):
    """Load an ``.xplane.pb`` file (or the newest one under a trace
    directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[dev] = [(e.name, int(e.start_ns),
                                 int(e.duration_ns)) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[dev] = [(e.name, int(e.start_ns),
                                     int(e.duration_ns))
                                    for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        host.append((e.name, int(e.start_ns),
                                     int(e.duration_ns)))
    for dev in ops:
        modules.setdefault(dev, [])
    return Trace(ops, modules, host)
