"""Three-tier KV-block memory hierarchy: device pool → host RAM → disk.

Before this module, the HBM page pool was the only home a KV block
could have: parked prefix blocks died the moment the allocator reused
them, the whole prefix cache died with the process, and every replica
had to run its own prefill. The tier manager turns "pool full" into a
tiered-latency event instead of a recompute cliff:

- **Host tier** (`KVTierManager._host`): when `PagedKVCache._purge`
  reclaims a parked block, the content demotes here (one device gather
  through the `serving_spill_block` executable + a host fetch) instead
  of vanishing. Spill-ahead under `PoolForecaster` pressure and
  spill-on-preempt ride the same path. At admit, `prefetch()` extends
  the device longest-common-prefix by restoring matching host blocks
  through `serving_restore_block` into PARKED device blocks — the
  subsequent `alloc_shared` resurrects them exactly like a finished
  request's cache, so a restored prefix costs a copy, not a recompute.
- **Disk tier** (`PrefixStore`): the resident prefix chains serialize
  via the checkpoint-manifest pattern (payload files named by content
  digest, generation manifests committed with tmp + `os.replace`,
  digests re-verified before an entry is trusted) so
  `rolling_restart()` and fresh autoscaled replicas come back with a
  warm prefix cache.
- **Wire**: `export_chain()` / `adopt_wire()` serialize a prefix chain
  to a JSON-safe string (the host-tier block format, base64-packed —
  int8 pool payloads travel quantized) for prefill→decode streaming
  over the router's existing kv channel.

Content keys are the FULL flat token prefix a block certifies: the
allocator's chain key `(parent_key, chunk)` embeds its ancestry, so
the flat expansion is lossless both ways (`_flatten_key` /
`_chain_key`). A key is resident in exactly ONE tier (device chain
XOR host dict — `check()` asserts it); the disk store is a backing
copy, not a residency tier.

Integrity: every spilled block carries a sha256 over its payload
arrays, computed at spill time. Restores re-verify; a mismatch drops
the entry and falls back to recompute (`kv.spill_corrupt` exercises
this, `kv.restore_slow` the prefetch-timeout path).

Telemetry rides the standard cost contract: every `_tm.*` / `_gp.*`
site is flag-gated (enforced by tests/test_telemetry_lint.py), and
spill/restore wall time lands in the goodput ledger under the
checkpoint categories (tier traffic IS state save/restore).
"""
from __future__ import annotations

import base64
import hashlib
import json
import os
import time
from collections import OrderedDict
from typing import Dict, List, Optional

import numpy as np

import jax.numpy as jnp

from .. import faults as _ft
from .. import goodput as _gp
from .. import telemetry as _tm

__all__ = ["KVTierManager", "PrefixStore", "TierBlock"]


# -- content keys -----------------------------------------------------------

def _flatten_key(key) -> tuple:
    """Expand an allocator chain key (parent_key, chunk) into the flat
    token tuple of the WHOLE prefix it certifies. Adapter-namespaced
    chains (rooted at a non-chain sentinel like ``("__lora__", name)``
    instead of None) return () — their content is only valid under
    that adapter's weights, so the tier never spills, persists, or
    streams it (the on_register/on_purge hooks no-op on empty
    tokens)."""
    parts = []
    while key is not None:
        if not (isinstance(key, tuple) and len(key) == 2
                and isinstance(key[1], tuple)):
            return ()
        parts.append(key[1])
        key = key[0]
    out: List[int] = []
    for chunk in reversed(parts):
        out.extend(chunk)
    return tuple(out)


def _chain_key(tokens, block_size: int):
    """Rebuild the allocator chain key certifying flat prefix
    `tokens` (the final chunk may be partial)."""
    key = None
    toks = tuple(int(t) for t in tokens)
    for i in range(0, len(toks), block_size):
        key = (key, toks[i:i + block_size])
    return key


# -- payload codec ----------------------------------------------------------

def _pack(payload: Dict[str, np.ndarray]) -> bytes:
    """Serialize a payload bundle: length-prefixed JSON header + raw
    array bytes. Hand-rolled (not npz) so extension dtypes like
    bfloat16 round-trip byte-exactly."""
    header = []
    chunks = []
    for f in sorted(payload):
        a = np.ascontiguousarray(payload[f])
        header.append({"f": f, "dtype": a.dtype.name,
                       "shape": list(a.shape), "nbytes": a.nbytes})
        chunks.append(a.tobytes())
    hb = json.dumps(header).encode()
    return len(hb).to_bytes(8, "little") + hb + b"".join(chunks)


def _unpack(data: bytes) -> Dict[str, np.ndarray]:
    n = int.from_bytes(data[:8], "little")
    header = json.loads(data[8:8 + n].decode())
    payload = {}
    off = 8 + n
    for h in header:
        raw = data[off:off + h["nbytes"]]
        a = np.frombuffer(raw, dtype=np.dtype(h["dtype"]))
        payload[h["f"]] = a.reshape(h["shape"])
        off += h["nbytes"]
    return payload


def _payload_digest(payload: Dict[str, np.ndarray]) -> str:
    h = hashlib.sha256()
    for f in sorted(payload):
        a = np.ascontiguousarray(payload[f])
        h.update(f.encode())
        h.update(a.dtype.name.encode())
        h.update(str(tuple(a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


class TierBlock:
    """One spilled block: the flat content prefix it certifies, its
    per-layer payload bundle {field: (L, K, bs, ·)}, and the
    integrity digest sealed at spill time."""
    __slots__ = ("tokens", "payload", "digest", "nbytes", "source")

    def __init__(self, tokens, payload, digest=None, source="spill"):
        self.tokens = tuple(int(t) for t in tokens)
        self.payload = payload
        self.nbytes = int(sum(a.nbytes for a in payload.values()))
        self.digest = digest if digest is not None \
            else _payload_digest(payload)
        self.source = source


def encode_wire(entries) -> str:
    """Serialize TierBlocks to a JSON-safe wire string (the router's
    kv channel carries strings); payloads travel in the host-tier
    packed format, so int8 pools stream quantized."""
    recs = [{"tokens": list(e.tokens), "digest": e.digest,
             "data": base64.b64encode(_pack(e.payload)).decode("ascii")}
            for e in entries]
    return json.dumps(recs)


def decode_wire(wire: str) -> list:
    """Inverse of encode_wire; entries whose digest does not match
    their payload are silently dropped (the receiver recomputes)."""
    out = []
    try:
        recs = json.loads(wire)
    except (ValueError, TypeError):
        return out
    for r in recs:
        try:
            payload = _unpack(base64.b64decode(r["data"]))
            e = TierBlock(r["tokens"], payload, source="wire")
            if e.digest != r["digest"]:
                continue
            out.append(e)
        except (KeyError, ValueError, TypeError):
            continue
    return out


# -- disk tier --------------------------------------------------------------

class PrefixStore:
    """Disk-backed persistent prefix store (checkpoint-manifest
    pattern): payload files named by content digest under `blocks/`,
    generations committed as `_manifests/<gen>.json`. Every write is
    tmp + `os.replace`; `load()` re-verifies digests and falls back
    across generations, so a damaged store degrades to a cold start,
    never a crash."""

    def __init__(self, root: str):
        self.root = root
        self._bdir = os.path.join(root, "blocks")
        self._mdir = os.path.join(root, "_manifests")
        os.makedirs(self._bdir, exist_ok=True)
        os.makedirs(self._mdir, exist_ok=True)

    def _generations(self) -> List[int]:
        gens = []
        try:
            names = os.listdir(self._mdir)
        except OSError:
            return []
        for n in names:
            if n.endswith(".json") and not n.startswith("__tmp"):
                try:
                    gens.append(int(n[:-5]))
                except ValueError:
                    pass
        return sorted(gens)

    def save(self, entries) -> int:
        """Persist `entries` as a new generation; payload files are
        content-addressed so unchanged blocks are written once across
        generations. Returns payload bytes newly written."""
        written = 0
        man = []
        for e in entries:
            fname = e.digest + ".bin"
            path = os.path.join(self._bdir, fname)
            if not os.path.exists(path):
                data = _pack(e.payload)
                tmp = path + ".__tmp"
                with open(tmp, "wb") as f:
                    f.write(data)
                os.replace(tmp, path)
                written += len(data)
            man.append({"tokens": list(e.tokens), "digest": e.digest,
                        "file": fname, "nbytes": e.nbytes})
        gens = self._generations()
        gen = (gens[-1] if gens else 0) + 1
        mpath = os.path.join(self._mdir, f"{gen}.json")
        tmp = mpath + ".__tmp"
        with open(tmp, "w") as f:
            json.dump({"generation": gen, "entries": man}, f)
        os.replace(tmp, mpath)
        return written

    def load(self) -> list:
        """Entries from the newest READABLE generation (older
        generations are the fallback when the newest manifest is
        damaged). Entries whose payload file is missing or fails the
        digest are skipped."""
        for gen in reversed(self._generations()):
            mpath = os.path.join(self._mdir, f"{gen}.json")
            try:
                with open(mpath) as f:
                    man = json.load(f)
                recs = man["entries"]
            except (OSError, ValueError, KeyError, TypeError):
                continue
            out = []
            for r in recs:
                try:
                    with open(os.path.join(self._bdir, r["file"]),
                              "rb") as f:
                        payload = _unpack(f.read())
                    if _payload_digest(payload) != r["digest"]:
                        continue
                    out.append(TierBlock(r["tokens"], payload,
                                         digest=r["digest"],
                                         source="disk"))
                except (OSError, ValueError, KeyError, TypeError):
                    continue
            return out
        return []


# -- tier manager -----------------------------------------------------------

class KVTierManager:
    """Owns the host tier and the disk store for ONE PagedKVCache.

    The cache calls `on_purge` (demote instead of discard); the server
    calls `spill_parked` (forecast-pressure spill-ahead and
    spill-on-preempt), `prefetch` (restore-on-LCP-match at admit),
    `export_chain`/`adopt_wire` (prefill→decode streaming) and
    `persist`/`load_store` (warm restarts)."""

    def __init__(self, cache, programs, *,
                 host_capacity_blocks: Optional[int] = None,
                 store: Optional[PrefixStore] = None,
                 spill_exhaust_s: Optional[float] = 3.0,
                 spill_batch: int = 4,
                 prefetch_timeout_s: Optional[float] = None):
        self.cache = cache
        self.programs = programs
        self.host_capacity = host_capacity_blocks
        self.store = store
        self.spill_exhaust_s = spill_exhaust_s
        self.spill_batch = spill_batch
        self.prefetch_timeout_s = prefetch_timeout_s
        #: host tier: flat prefix tuple -> TierBlock, LRU order
        self._host: "OrderedDict[tuple, TierBlock]" = OrderedDict()
        self._in_spill = False  # re-entrancy latch for _purge hooks
        # conservation counters — check() holds
        #   spills + adopted == restores + dropped + len(_host)
        self.spills = 0          # device -> host (demote / spill-ahead)
        self.restores = 0        # host -> device
        self.adopted = 0         # wire / disk -> host
        self.dropped = 0         # digest-failed or capacity-evicted
        self.spill_bytes = 0
        self.restore_bytes = 0
        self.restore_failed = 0
        self.restore_timeouts = 0
        self.streamed_in = 0
        self.persist_saved = 0
        self.persist_loaded = 0
        self.persist_bytes = 0
        #: admit-level hit attribution for the per-tier hit-rate gauges
        self.admits = 0
        self.hits = {"device": 0, "host": 0, "disk": 0}

    # -- telemetry hooks ------------------------------------------------

    def _note_spill(self, nbytes: int, dur: float):
        if _tm._ENABLED:
            _tm.inc("serving_tier_spills_total")
            _tm.inc("serving_tier_spill_bytes_total", nbytes)
            _tm.observe("serving_tier_spill_seconds", dur)
        if _gp._ENABLED:
            _gp.charge_span("checkpoint_save", dur)

    def _note_restore(self, nbytes: int, dur: float):
        if _tm._ENABLED:
            _tm.inc("serving_tier_restores_total")
            _tm.inc("serving_tier_restore_bytes_total", nbytes)
            _tm.observe("serving_tier_restore_seconds", dur)
        if _gp._ENABLED:
            _gp.charge_span("checkpoint_restore", dur)

    def _note_restore_failed(self):
        if _tm._ENABLED:
            _tm.inc("serving_tier_restore_failed_total")

    def _note_restore_timeout(self):
        if _tm._ENABLED:
            _tm.inc("serving_tier_restore_timeout_total")

    def _note_stream(self, nblocks: int, nbytes: int):
        if _tm._ENABLED and nblocks:
            _tm.inc("serving_blocks_streamed_total", nblocks)
            _tm.inc("serving_blocks_streamed_bytes_total", nbytes)

    def _note_persist(self, op: str, n: int, nbytes: int, dur: float):
        if _tm._ENABLED:
            _tm.inc(f"serving_prefix_persist_{op}_total", n)
            _tm.inc("serving_prefix_persist_bytes_total", nbytes)
        if _gp._ENABLED:
            cat = "checkpoint_save" if op == "saved" \
                else "checkpoint_restore"
            _gp.charge_span(cat, dur)

    # -- device <-> host ------------------------------------------------

    def _snapshot(self, blk: int) -> Dict[str, np.ndarray]:
        """Gather one device block across every layer into a host
        bundle {field: (L, K, bs, ·)} — read-only, no cache
        mutation."""
        bundle = self.programs["spill_block"](
            self.cache.pages, jnp.asarray(blk, jnp.int32))
        return {f: np.asarray(a) for f, a in bundle.items()}

    def _insert_host(self, entry: TierBlock):
        self._host[entry.tokens] = entry
        self._host.move_to_end(entry.tokens)
        if self.host_capacity is not None:
            while len(self._host) > self.host_capacity:
                self._host.popitem(last=False)
                self.dropped += 1

    def _spill_tokens(self, tokens: tuple, blk: int) -> TierBlock:
        t0 = time.perf_counter()
        entry = TierBlock(tokens, self._snapshot(blk))
        if _ft._ACTIVE:
            sp = _ft.fire("kv.spill_corrupt")
            if sp is not None:
                _corrupt_payload(entry.payload)
        self._insert_host(entry)
        self.spills += 1
        self.spill_bytes += entry.nbytes
        self._note_spill(entry.nbytes, time.perf_counter() - t0)
        return entry

    def on_register(self, key):
        """Registration hook — the cache just published `key` on
        device (a recomputed prefill, e.g. after a prefetch that found
        no free block or a digest failure). Drop any host-tier copy:
        a content key lives in exactly one tier, and the fresh device
        copy wins."""
        toks = _flatten_key(key)
        if toks and self._host.pop(toks, None) is not None:
            self.dropped += 1

    def on_purge(self, blk: int, key):
        """Demote hook — `PagedKVCache._purge` is dropping `key`'s
        device registration because block `blk` is being reclaimed;
        capture the content into the host tier instead of losing it.
        (The block's data is still intact at purge time: reuse writes
        happen after the claim.)"""
        if self._in_spill:
            return
        tokens = _flatten_key(key)
        if not tokens or tokens in self._host:
            return
        self._in_spill = True
        try:
            self._spill_tokens(tokens, blk)
        finally:
            self._in_spill = False

    def spill_parked(self, max_blocks: Optional[int] = None) -> int:
        """Spill-ahead: move parked blocks (free-list residents still
        holding registered content, always refcount 0) to the host
        tier and release their device registration, turning them into
        plain free blocks. Oldest parked first. Returns blocks
        spilled."""
        c = self.cache
        parked = [b for b in c._free if b in c._block_key]
        if max_blocks is not None:
            parked = parked[:max_blocks]
        n = 0
        for b in parked:
            key = c._block_key.get(b)
            if key is None:
                continue
            tokens = _flatten_key(key)
            if tokens and tokens not in self._host:
                self._spill_tokens(tokens, b)
            self._in_spill = True
            try:
                c._purge(b)
            finally:
                self._in_spill = False
            n += 1
        return n

    def _restore_entry(self, entry: TierBlock):
        """Host → device: digest-verify, claim a parked slot through
        `park_restored`, run the restore executable. Returns True on
        success, False on integrity failure (entry dropped — caller
        recomputes), None when no device block is free."""
        t0 = time.perf_counter()
        if _ft._ACTIVE:
            sp = _ft.fire("kv.restore_slow")
            if sp is not None:
                time.sleep(float(sp.get("ms", 50)) / 1000.0)
        if not self._payload_fits(entry.payload) \
                or _payload_digest(entry.payload) != entry.digest:
            self._host.pop(entry.tokens, None)
            self.dropped += 1
            self.restore_failed += 1
            self._note_restore_failed()
            return False
        key = _chain_key(entry.tokens, self.cache.block_size)
        blk = self.cache.park_restored(key)
        if blk is None:
            return None
        payload = {f: np.ascontiguousarray(a)
                   for f, a in entry.payload.items()}
        self.cache.pages = self.programs["restore_block"](
            self.cache.pages, payload, jnp.asarray(blk, jnp.int32))
        # MOVE, not copy — a content key lives in exactly one tier
        self._host.pop(entry.tokens, None)
        self.restores += 1
        self.restore_bytes += entry.nbytes
        if entry.source == "disk":
            self._disk_hit = True
        self._note_restore(entry.nbytes, time.perf_counter() - t0)
        return True

    def prefetch(self, tokens) -> tuple:
        """Admit-time tier prefetch: extend the device LCP for
        `tokens` by restoring matching host-tier blocks into parked
        device blocks (the following `alloc_shared` adopts them).
        Time-boxed by `prefetch_timeout_s`; a digest failure stops the
        chain (recompute fallback). Returns
        (device_shared_len, restored_tokens)."""
        c = self.cache
        if not c.prefix_cache or not self._host:
            self.admits += 1
            _, dev_len = c.match_prefix(tokens) if c.prefix_cache \
                else ([], 0)
            if dev_len:
                self.hits["device"] += 1
            return dev_len, 0
        toks = tuple(int(t) for t in tokens)
        _, dev_len = c.match_prefix(toks)
        bs = c.block_size
        covered = (dev_len // bs) * bs  # full-chunk device frontier
        restored = 0
        self._disk_hit = False
        deadline = None
        if self.prefetch_timeout_s is not None:
            deadline = time.perf_counter() + self.prefetch_timeout_s
        limit = min(len(toks), c.max_blocks_per_seq * bs)
        while covered < limit:
            entry = self._host.get(toks[:min(covered + bs, limit)])
            if entry is None:
                entry = self._partial_tail(toks, covered, limit)
            if entry is None:
                break
            span = len(entry.tokens) - covered
            ok = self._restore_entry(entry)
            if not ok:  # False (digest) or None (no free block)
                break
            covered += span
            restored += span
            if deadline is not None \
                    and time.perf_counter() > deadline:
                self.restore_timeouts += 1
                self._note_restore_timeout()
                break
        self.admits += 1
        if dev_len:
            self.hits["device"] += 1
        if restored:
            self.hits["disk" if self._disk_hit else "host"] += 1
        return dev_len, restored

    def _partial_tail(self, toks, covered, limit):
        """A host entry whose final chunk is partial and agrees with
        the prompt remainder (match_prefix's tail-scan semantics)."""
        rem = toks[covered:limit]
        if not rem:
            return None
        best = None
        for k, e in self._host.items():
            if not (covered < len(k) < covered + self.cache.block_size):
                continue
            if k[:covered] != toks[:covered]:
                continue
            chunk = k[covered:]
            n = min(len(chunk), len(rem))
            if n and chunk[:n] == rem[:n] and len(chunk) <= len(rem):
                if best is None or len(chunk) > len(best.tokens):
                    best = e
        return best

    # -- streaming ------------------------------------------------------

    def export_chain(self, tokens) -> Optional[str]:
        """Serialize the resident chain covering `tokens` (device
        registrations are snapshotted read-only; host entries ship
        as-is) to a wire string, or None when nothing is resident."""
        c = self.cache
        toks = tuple(int(t) for t in tokens)
        bs = c.block_size
        entries = []
        parent = None
        i = 0
        while i < len(toks):
            chunk = toks[i:i + bs]
            key = (parent, chunk)
            flat = toks[:i + len(chunk)]
            blk = c._chain.get(key)
            if blk is not None:
                entries.append(TierBlock(flat, self._snapshot(blk),
                                         source="device"))
            else:
                e = self._host.get(flat)
                if e is None:
                    break
                entries.append(e)
            parent = key
            i += len(chunk)
        if not entries:
            return None
        return encode_wire(entries)

    def adopt_wire(self, wire: str) -> int:
        """Adopt streamed blocks into the host tier (digest-verified;
        keys already resident in either tier are skipped). Returns
        blocks adopted."""
        n = 0
        nbytes = 0
        for e in decode_wire(wire):
            if e.tokens in self._host:
                continue
            key = _chain_key(e.tokens, self.cache.block_size)
            if self.cache._chain.get(key) is not None:
                continue
            if not self._payload_fits(e.payload):
                continue
            self._insert_host(e)
            self.adopted += 1
            self.streamed_in += 1
            n += 1
            nbytes += e.nbytes
        self._note_stream(n, nbytes)
        return n

    # -- persistence ----------------------------------------------------

    def persist(self) -> int:
        """Write every resident prefix block (host tier + a read-only
        snapshot of device-registered chains) to the disk store as one
        new generation. Residency is unchanged — the store is a
        backing copy. Returns entries written."""
        if self.store is None:
            return 0
        t0 = time.perf_counter()
        entries = list(self._host.values())
        seen = set(self._host)
        for blk, key in list(self.cache._block_key.items()):
            flat = _flatten_key(key)
            if not flat or flat in seen:
                continue
            entries.append(TierBlock(flat, self._snapshot(blk),
                                     source="device"))
            seen.add(flat)
        if not entries:
            return 0
        nbytes = self.store.save(entries)
        self.persist_saved += len(entries)
        self.persist_bytes += nbytes
        self._note_persist("saved", len(entries), nbytes,
                           time.perf_counter() - t0)
        return len(entries)

    def load_store(self) -> int:
        """Warm the host tier from the disk store (damaged entries
        were already filtered by PrefixStore.load). Returns entries
        adopted."""
        if self.store is None:
            return 0
        t0 = time.perf_counter()
        n = 0
        nbytes = 0
        for e in self.store.load():
            if e.tokens in self._host:
                continue
            key = _chain_key(e.tokens, self.cache.block_size)
            if self.cache._chain.get(key) is not None:
                continue
            if not self._payload_fits(e.payload):
                continue
            self._insert_host(e)
            self.adopted += 1
            n += 1
            nbytes += e.nbytes
        self.persist_loaded += n
        if n:
            self._note_persist("loaded", n, nbytes,
                               time.perf_counter() - t0)
        return n

    # -- introspection --------------------------------------------------

    def _payload_fits(self, payload) -> bool:
        """Shape/dtype guard: a payload is only restorable into a pool
        with the same per-layer geometry (protects cross-config
        stores)."""
        pg0 = self.cache.pages[0]
        if set(payload) != set(pg0):
            return False
        L = self.cache.num_layers
        for f, a in payload.items():
            ref = pg0[f]
            if tuple(a.shape) != (L,) + tuple(ref.shape[1:]):
                return False
            if np.dtype(a.dtype) != np.dtype(ref.dtype):
                return False
        return True

    def resident_keys(self):
        """Flat content keys currently resident in the host tier."""
        return self._host.keys()

    @staticmethod
    def flat_key(chain_key) -> tuple:
        """The flat token tuple an allocator chain key certifies."""
        return _flatten_key(chain_key)

    def host_blocks(self) -> int:
        return len(self._host)

    def host_bytes(self) -> int:
        return sum(e.nbytes for e in self._host.values())

    def hit_rates(self) -> dict:
        n = max(1, self.admits)
        return {t: self.hits[t] / n for t in ("device", "host", "disk")}

    def stats(self) -> dict:
        return {"tier_host_blocks": self.host_blocks(),
                "tier_host_bytes": self.host_bytes(),
                "tier_spills": self.spills,
                "tier_restores": self.restores,
                "tier_adopted": self.adopted,
                "tier_dropped": self.dropped,
                "tier_spill_bytes": self.spill_bytes,
                "tier_restore_bytes": self.restore_bytes,
                "tier_restore_failed": self.restore_failed,
                "tier_restore_timeouts": self.restore_timeouts,
                "tier_blocks_streamed_in": self.streamed_in,
                "tier_persist_saved": self.persist_saved,
                "tier_persist_loaded": self.persist_loaded,
                "tier_persist_bytes": self.persist_bytes,
                "tier_hit_rates": self.hit_rates()}

    def check(self):
        """Tier invariants, called from `PagedKVCache.check()`:
        a content key is resident in exactly one tier, spilled
        entries only ever came from refcount-0 reclaims (implied by
        disjointness — refcounted registered blocks stay in the device
        chain), and the entry counters conserve."""
        c = self.cache
        dev = set()
        for key in c._chain:
            flat = _flatten_key(key)
            if flat:
                dev.add(flat)
        host = set(self._host)
        both = dev & host
        assert not both, \
            f"content resident in two tiers: {sorted(both)[:3]}"
        for toks, e in self._host.items():
            assert toks == e.tokens, "host tier key out of sync"
        assert self.spills + self.adopted \
            == self.restores + self.dropped + len(self._host), \
            f"tier conservation broken: {self.spills} spills + " \
            f"{self.adopted} adopted != {self.restores} restores + " \
            f"{self.dropped} dropped + {len(self._host)} resident"


def _corrupt_payload(payload: Dict[str, np.ndarray]):
    """Flip one byte of the first field AFTER the digest was sealed,
    so the restore-side verification catches it (kv.spill_corrupt)."""
    f = sorted(payload)[0]
    a = np.ascontiguousarray(payload[f]).copy()
    flat = a.reshape(-1).view(np.uint8)
    flat[0] ^= 0xFF
    payload[f] = a
