"""Flight recorder: a bounded in-memory ring of structured events that
explains *why a run died* (the black box the fault-tolerance substrate
was missing).

The telemetry registry answers "how fast, right now"; this module keeps
the last N discrete *decisions and transitions* — phase marks, kvstore
collective entry/exit with byte counts, fault injections, serving
scheduler admit/preempt/evict, checkpoint save/restore/fallback,
gradient-sanitizer skips, compile events — as `(t_monotonic, kind,
site, payload)` tuples in a fixed-capacity deque. When something goes
wrong the runtime dumps the ring as JSONL so the post-mortem starts
from the event sequence instead of from a stack trace alone.

Auto-dump triggers wired across the stack (each records the triggering
event LAST, then dumps, so the tail of the file is the cause):

- the serving watchdog declaring :class:`ServerStalledError`
- the fleet router's watchdog declaring :class:`RouterStalledError`
  (``router_stall`` — no request made progress for ``watchdog_s``)
- :class:`GradSanitizer` aborting on the consecutive-skip cap (eager
  and fused-loop paths)
- :class:`PreemptionHandler` receiving SIGTERM
- any armed fault site firing (``mxnet_tpu.faults``)
- an uncaught exception escaping ``TrainLoop.run`` or
  ``InferenceServer.run``

Cost contract: identical to telemetry — the whole layer is off by
default and every instrumented call site guards on the module-level
``_ENABLED`` flag (one attribute load + branch), so the disabled path
never builds a payload dict or touches the ring
(``tests/test_telemetry_lint.py`` enforces the gate pattern; its cost
on the chip: no cell measures this, ROADMAP D7).

Env: ``MXNET_TPU_FLIGHT=1`` enables at import, ``MXNET_TPU_FLIGHT_DIR``
picks the dump directory (default: cwd), ``MXNET_TPU_FLIGHT_EVENTS``
sets the ring capacity (default 4096).

This module deliberately imports nothing from the package so every
other module (telemetry included) can import it without cycles.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import List, Optional, Tuple

__all__ = ["enable", "disable", "enabled", "record", "events", "clear",
           "dump", "dump_text", "merge", "main",
           "set_capacity", "capacity", "last_dump_path",
           "DEFAULT_CAPACITY"]

DEFAULT_CAPACITY = 4096

#: THE flag. Instrumented call sites guard with `if flight._ENABLED:`
#: (one module-attribute load + branch) so the disabled path records
#: nothing and allocates nothing.
_ENABLED = os.environ.get("MXNET_TPU_FLIGHT", "0") == "1"

_lock = threading.RLock()


def _env_capacity() -> int:
    try:
        return max(16, int(os.environ.get("MXNET_TPU_FLIGHT_EVENTS",
                                          DEFAULT_CAPACITY)))
    except (TypeError, ValueError):
        return DEFAULT_CAPACITY


_EVENTS: deque = deque(maxlen=_env_capacity())

#: path of the most recent dump (None until the first one) — tests and
#: post-mortem tooling read this instead of globbing the dump dir
last_dump_path: Optional[str] = None

#: event hook set EXTERNALLY by mxnet_tpu.goodput.enable() (this
#: module stays import-free); called as hook(kind, site, payload) for
#: every recorded event so stalls/crashes become badput
_note_hook = None

_DUMP_SEQ = 0


def enable(capacity: Optional[int] = None):
    """Turn the flight recorder on (optionally resizing the ring)."""
    global _ENABLED
    if capacity is not None:
        set_capacity(capacity)
    _ENABLED = True


def disable():
    global _ENABLED
    _ENABLED = False


def enabled() -> bool:
    return _ENABLED


def capacity() -> int:
    return _EVENTS.maxlen


def set_capacity(capacity: int):
    """Resize the ring (keeps the newest events that still fit)."""
    global _EVENTS
    cap = max(16, int(capacity))
    with _lock:
        _EVENTS = deque(_EVENTS, maxlen=cap)


def record(kind: str, site: str, **payload):
    """Append one `(t_monotonic, kind, site, payload)` event. Callers
    on hot paths must guard with `if flight._ENABLED:` — this re-check
    only protects direct callers."""
    if not _ENABLED:
        return
    _EVENTS.append((time.monotonic(), kind, site, payload or None))
    if _note_hook is not None:
        _note_hook(kind, site, payload)


def events() -> List[Tuple[float, str, str, Optional[dict]]]:
    """Snapshot of the ring, oldest first."""
    with _lock:
        return list(_EVENTS)


def clear():
    with _lock:
        _EVENTS.clear()


def _render(reason: str, evs: list, seq: int) -> str:
    """Serialize a ring snapshot as JSONL text: one header line
    (reason, pid, PAIRED clock anchors `t_monotonic`/`time_unix` —
    sampled together so a reader can convert event times to wall
    clock), then one line per event, oldest first."""
    header = {"flight": 1, "reason": reason, "pid": os.getpid(),
              "seq": seq, "events": len(evs),
              "capacity": _EVENTS.maxlen,
              "t_monotonic": time.monotonic(),
              "time_unix": time.time()}
    lines = [json.dumps(header)]
    for t, kind, site, payload in evs:
        line = {"t": t, "kind": kind, "site": site}
        if payload:
            line["payload"] = payload
        lines.append(json.dumps(line, default=str))
    return "\n".join(lines) + "\n"


def dump_text(reason: str = "manual") -> Optional[str]:
    """The ring serialized as JSONL text (same format as :func:`dump`)
    without touching the filesystem — the fleet router ships this over
    the kv channel when it collects a cross-process flight bundle.
    Returns None while disabled."""
    global _DUMP_SEQ
    if not _ENABLED:
        return None
    with _lock:
        evs = list(_EVENTS)
        _DUMP_SEQ += 1
        seq = _DUMP_SEQ
    return _render(reason, evs, seq)


def dump(reason: str = "manual", path: Optional[str] = None) -> Optional[str]:
    """Write the ring as JSONL: one header line (reason, pid, clock
    anchors, capacity) then one line per event, oldest first — the
    FINAL lines are the newest events, i.e. the trigger of whatever
    prompted the dump. Returns the path (None while disabled).

    Default location: ``MXNET_TPU_FLIGHT_DIR`` (or cwd) with a
    per-reason filename, so repeated fires of the same trigger
    overwrite one file instead of flooding the directory."""
    global last_dump_path
    text = dump_text(reason)
    if text is None:
        return None
    if path is None:
        d = os.environ.get("MXNET_TPU_FLIGHT_DIR") or os.getcwd()
        try:
            os.makedirs(d, exist_ok=True)
        except OSError:
            d = os.getcwd()
        safe = "".join(c if c.isalnum() or c in "-_" else "-"
                       for c in reason) or "manual"
        path = os.path.join(d, f"flight-{safe}-p{os.getpid()}.jsonl")
    try:
        with open(path, "w") as f:
            f.write(text)
    except OSError:
        return None
    last_dump_path = path
    return path


# -- cross-process merge (the `python -m mxnet_tpu.flight merge` CLI) -------

def _collect_paths(sources: List[str]) -> List[str]:
    paths: List[str] = []
    for src in sources:
        if os.path.isdir(src):
            # skip a previous merge output so re-merging a bundle
            # directory stays idempotent
            paths.extend(sorted(
                os.path.join(src, n) for n in os.listdir(src)
                if n.endswith(".jsonl") and n != "merged.jsonl"))
        else:
            paths.append(src)
    return paths


def merge(sources: List[str], out: Optional[str] = None) -> str:
    """Stitch per-process flight dumps (files or directories of
    ``*.jsonl`` — e.g. a router-written ``flight-bundle-<reason>/``)
    into ONE clock-aligned timeline. Each dump's header carries paired
    ``t_monotonic``/``time_unix`` anchors, so every event's monotonic
    timestamp converts to wall clock via the per-process offset
    ``time_unix - t_monotonic``; events from all sources are then
    sorted on that shared axis. Output: a header line (sources with
    their offsets) followed by
    ``{"t_unix", "src", "kind", "site", "payload"?}`` lines. Returns
    the output path (default: ``merged.jsonl`` next to the first
    source)."""
    paths = _collect_paths(sources)
    if not paths:
        raise ValueError("no flight dumps to merge")
    srcs = []
    merged = []
    for p in paths:
        name = os.path.splitext(os.path.basename(p))[0]
        with open(p) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        if not lines:
            continue
        header = json.loads(lines[0])
        offset = float(header.get("time_unix", 0.0)) - \
            float(header.get("t_monotonic", 0.0))
        n = 0
        for ln in lines[1:]:
            ev = json.loads(ln)
            rec = {"t_unix": float(ev.get("t", 0.0)) + offset,
                   "src": name, "kind": ev.get("kind"),
                   "site": ev.get("site")}
            if ev.get("payload") is not None:
                rec["payload"] = ev["payload"]
            merged.append(rec)
            n += 1
        srcs.append({"file": os.path.basename(p),
                     "pid": header.get("pid"),
                     "reason": header.get("reason"),
                     "offset_s": offset, "events": n})
    merged.sort(key=lambda r: (r["t_unix"], r["src"]))
    if out is None:
        base = paths[0]
        d = base if os.path.isdir(base) else os.path.dirname(base) or "."
        out = os.path.join(d, "merged.jsonl")
    with open(out, "w") as f:
        f.write(json.dumps({"flight_merge": 1, "sources": srcs,
                            "events": len(merged)}) + "\n")
        for rec in merged:
            f.write(json.dumps(rec, default=str) + "\n")
    return out


def main(argv: Optional[List[str]] = None) -> int:
    """``python -m mxnet_tpu.flight merge <dir-or-files...> [-o OUT]``:
    stitch a flight bundle into one ordered timeline (see
    :func:`merge`). Stdlib-only, like the rest of this module."""
    import argparse
    ap = argparse.ArgumentParser(prog="python -m mxnet_tpu.flight")
    sub = ap.add_subparsers(dest="cmd", required=True)
    mp = sub.add_parser("merge", help="merge per-process flight dumps "
                                      "into one clock-aligned timeline")
    mp.add_argument("sources", nargs="+",
                    help="dump files and/or bundle directories")
    mp.add_argument("-o", "--out", default=None,
                    help="output path (default: merged.jsonl next to "
                         "the first source)")
    args = ap.parse_args(argv)
    out = merge(args.sources, out=args.out)
    print(out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
