"""What every cell's run shares: finding the cell's files by name,
refusing to run without the chip, the compile cache, the compile
meter, host spans, the traced window, and the result line.

Nothing here knows a cell, a configuration, a traffic mix or a metric
by name: a cell is its entry in ``BENCHMARK.json``, a configuration is
``perfbench/configs/<config>.json`` (naming its ``family``), a traffic
mix is ``perfbench/traffic/<traffic>.json`` (naming its ``generator``),
a per-layer metric is ``perfbench/metrics/<metric>.json`` (naming its
``reader``). Families, generators and readers are modules found by
those names.
"""
import contextlib
import importlib
import json
import os
import sys
import time

from perfbench import stats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "perfbench")


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""

    def __init__(self, name, benchmark=None):
        bm = benchmark or load_json(ROOT, "BENCHMARK.json")
        cells = {w["name"]: w for w in bm["workloads"]}
        if name not in cells:
            raise SystemExit(f"perfbench: no workload {name!r} in "
                             f"BENCHMARK.json (have {sorted(cells)})")
        w = cells[name]
        self.name = name
        self.chips = w["chips"]
        self.config_name = w["config"]
        self.traffic_name = w["traffic"]
        cfg_entry = {c["name"]: c for c in bm["configs"]}[w["config"]]
        self.config = load_json(ROOT, cfg_entry["file"])
        self.traffic = load_json(HERE, "traffic", w["traffic"] + ".json")

        def applies(m):
            return "workloads" not in m or name in m["workloads"]

        self.end_to_end = [m for m in bm["end_to_end"] if applies(m)]
        self.per_layer = [m for m in bm["per_layer"] if applies(m)]

    def family(self):
        return importlib.import_module(
            "perfbench.families." + self.config["family"])

    def generator(self):
        return importlib.import_module(
            "perfbench.generators." + self.traffic["generator"])


# -- the device --------------------------------------------------------------

def require_tpu(chips):
    """The devices of this run, or exit non-zero with no result: a
    number from anything but the chip is never written under a device
    metric's name."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.stderr.write(
            f"perfbench: this cell needs {chips} TPU chip(s); JAX sees "
            f"{len(devs)} x {devs[0].platform!r} "
            f"({devs[0].device_kind!r}). The CPU rehearsal is "
            "perfbench/rehearse.py.\n")
        raise SystemExit(3)
    return devs[:chips]


def enable_compile_cache():
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    if set, else at the fixed path ``<checkout>/.jax_cache`` (the same
    rule as ``mxnet_tpu.tracing.enable_compile_cache``, copied so the
    benchmark decides it). Every executable is kept, the small eager
    ones too: a warm run should build nothing."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


class CompileMeter:
    """Counts what XLA builds, from ``jax.monitoring``: every
    executable (``builds``, with the seconds spent) and how many the
    persistent cache served (``hits``). Copied from
    ``chip_smoke.CompileMeter``. A build inside the timed window fails
    the run."""

    def __init__(self):
        from jax import monitoring
        self.builds = 0
        self.hits = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.builds += 1
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return (self.builds, self.hits, self.seconds)

    def since(self, mark):
        return {"builds": self.builds - mark[0],
                "cache_hits": self.hits - mark[1],
                "compile_s": round(self.seconds - mark[2], 2)}


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest chip (0 where the backend does
    not report it)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def device_block(devices, peak_bytes):
    """The ``device`` object of the result line; ``peak_bytes`` is the
    program's peak, read when the window closed."""
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": peak_bytes}


# -- where a run's seconds go ------------------------------------------------

class Phases:
    """Seconds of a run by part, for the one line a traced run writes
    at its exit: ``timed(name)`` around a part, ``add(name, seconds)``
    for one that was timed elsewhere, outside any open part. A part
    timed inside another is taken out of the outer one, so the parts
    add up to the run."""

    def __init__(self):
        self.rows = []      # [name, seconds], in the order they ended
        self._inner = []    # seconds timed inside each open part

    def add(self, name, seconds):
        self.rows.append([name, seconds])

    @contextlib.contextmanager
    def timed(self, name):
        t0 = time.perf_counter()
        self._inner.append(0.0)
        try:
            yield
        finally:
            whole = time.perf_counter() - t0
            own = whole - self._inner.pop()
            self.rows.append([name, own])
            if self._inner:
                self._inner[-1] += whole

    def line(self, total_s):
        """The parts in the order they ended, those of the reduction
        (``read.*``) under a second summed into one, and what no part
        covers as ``other``."""
        out, small = [("total", total_s)], 0.0
        for name, v in self.rows:
            if name.startswith("read.") and v < 1.0:
                small += v
            else:
                out.append((name, v))
        out.append(("read.rest", small))
        out.append(("other", total_s - sum(v for _, v in self.rows)))
        return "[phases] " + ", ".join(f"{n}_s: {v:.2f}" for n, v in out)


# -- spans and the traced window --------------------------------------------

class Tracer:
    """Host spans from the benchmark's own files, around the calls into
    each layer. With the profiler on they are ``TraceAnnotation``s on
    the profiler's clock (names ``pb.*``), so the reduction can charge
    each idle gap of the device to what the host was doing. With it off
    a span costs one ``nullcontext``."""

    def __init__(self, on, trace_dir, phases):
        self.on = bool(on)
        self.dir = trace_dir
        self.phases = phases                # Phases: the run's seconds
        self._started = False

    def span(self, name):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("pb." + name)

    def start(self):
        """Profiler on (no Python tracer: it slows the host and bloats
        the file). Called a moment BEFORE the window so that starting
        it disturbs nothing that is measured; the reduction cuts the
        trace to the ``pb.window`` span."""
        if not self.on or self._started:
            return
        import shutil
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._started = True

    @contextlib.contextmanager
    def window(self):
        """The traced window: one ``pb.window`` span over it, profiler
        off at its end."""
        if not self.on:
            yield
            return
        import jax
        self.start()
        try:
            with jax.profiler.TraceAnnotation("pb.window"):
                yield
        finally:
            with self.phases.timed("stop_trace"):
                jax.profiler.stop_trace()
            self._started = False


# -- checks and the result line ----------------------------------------------

class Checks:
    """The numbers compared, each beside its limit; every run prints
    them all."""

    def __init__(self):
        self.rows = []

    def at_most(self, name, value, limit):
        ok = value is not None and value == value and value <= limit
        self.rows.append((name, value, "<=", limit, ok))
        return ok

    def at_least(self, name, value, limit):
        ok = value is not None and value == value and value >= limit
        self.rows.append((name, value, ">=", limit, ok))
        return ok

    def equal(self, name, value, want):
        ok = value == want
        self.rows.append((name, value, "==", want, ok))
        return ok

    @property
    def ok(self):
        return bool(self.rows) and all(r[-1] for r in self.rows)

    def print(self, file=None):
        for name, value, op, limit, ok in self.rows:
            v = f"{value:.6g}" if isinstance(value, float) else str(value)
            print(f"[check] {name}: {v} {op} {limit} "
                  f"{'ok' if ok else 'FAILED'}", file=file, flush=True)

    def as_dict(self):
        """{name: [number, its limit]} for the result line."""
        return {name: [value, f"{op} {limit}"]
                for name, value, op, limit, _ in self.rows}


def say(tag, **facts):
    print(f"[{tag}] " + ", ".join(f"{k}: {v}" for k, v in facts.items()),
          flush=True)


def end_to_end_values(cell, outcome):
    """The cell's end-to-end metrics from the generator's quantities, as
    the traffic file's ``end_to_end`` says: ``{"from": quantity}`` takes
    a number as it is, ``{"from": quantity, "percentile": q}`` the q-th
    percentile of a sample."""
    out = {}
    specs = cell.traffic["end_to_end"]
    for m in cell.end_to_end:
        if m["name"] == "setup_s":
            value = outcome["setup_s"]
        else:
            spec = specs[m["name"]]
            value = outcome["quantities"][spec["from"]]
            if "percentile" in spec:
                value = stats.percentile(value, spec["percentile"])
        if value is None:
            raise RuntimeError(f"no value for end-to-end metric "
                               f"{m['name']} in {cell.name}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def per_layer_values(cell, ctx):
    """The cell's per-layer metrics: each is its own small reader,
    found by the metric's file; a reader that finds nothing to read
    returns None and the metric is left out of the line."""
    out = {}
    for m in cell.per_layer:
        spec = load_json(HERE, "metrics", m["name"] + ".json")
        reader = importlib.import_module(
            "perfbench.readers." + spec["reader"])
        with ctx.phases.timed("read." + m["name"]):
            value = reader.read(spec, ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


class ReadContext:
    """What a per-layer reader may look at."""

    def __init__(self, cell, outcome, trace, peaks, phases):
        self.phases = phases                 # Phases: readers time parts
        self.config = cell.config
        self.traffic = cell.traffic
        self.chips = cell.chips
        self.spans = outcome["spans"]        # {name: [seconds, ...]}
        self.counters = outcome["counters"]  # {name: number}
        self.work = outcome["work"]          # counters for perfbench/costs
        self.window_s = outcome["window_s"]
        self.trace = trace                   # xtrace.Trace cut to window
        self.peaks = peaks


def run_cell(cell, seed, seconds, trace, t_start, devices, on_chip=True):
    """Set up, measure, check; returns the result object, the numbers
    compared beside their limits as its last key."""
    from perfbench import peaks as peak_table, xtrace

    meter = CompileMeter()
    phases = Phases()
    tracer = Tracer(trace and on_chip, os.path.join(ROOT, ".pb_trace"),
                    phases)
    gen = cell.generator()
    outcome = gen.run(cell, seed=seed, seconds=seconds, tracer=tracer,
                      meter=meter, devices=devices, t_start=t_start)
    # the generator timed these two itself; they come first in the line
    phases.rows[:0] = [["setup", outcome["setup_s"]],
                       ["window", outcome["window_s"]]]
    checks = outcome["checks"]
    checks.equal("compiles_in_window", outcome["window_builds"], 0)
    checks.print()
    result = {"correct": checks.ok, "attempted": outcome["attempted"],
              "failed": outcome["failed"]}
    dev = device_block(devices, outcome["memory_peak_bytes"])
    if not on_chip:
        # the rehearsal prints counts only: no device metric from a CPU
        result["metrics"] = {}
    elif tracer.on:
        with phases.timed("xtrace.load"):
            tr = xtrace.load(tracer.dir).windowed()
        t0, t1 = tr.window()
        with phases.timed("read.busy_s"):
            dev["busy_s"] = tr.busy_s()
        dev["window_s"] = (t1 - t0) * 1e-9
        ctx = ReadContext(cell, outcome, tr,
                          peak_table.peaks_for(devices[0].device_kind),
                          phases)
        result["metrics"] = per_layer_values(cell, ctx)
        with phases.timed("read.idle_gaps"):
            tr.idle_gaps()          # kept: the breakdown asks again
        with phases.timed("read.device_ops"):
            result["breakdown"] = tr.breakdown()
    else:
        result["metrics"] = end_to_end_values(cell, outcome)
    result["device"] = dev
    result["checks"] = checks.as_dict()
    if tracer.on:
        print(phases.line(time.perf_counter() - t_start), file=sys.stderr)
    # the last lines on standard error: each number beside its limit
    checks.print(sys.stderr)
    return result
