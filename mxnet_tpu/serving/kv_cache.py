"""Paged KV cache: a fixed pool of fixed-size blocks shared by every
in-flight sequence (vLLM-style paged attention, adapted to the
cache-native (·, K, S, d) layout of kernels/flash_decode.py).

Why paging: the one-shot `generate()` cache is (B, max_len, ...) per
call — every sequence pays for the longest possible sequence, and
sequences of different lengths cannot share a batch without wasting
HBM on the short rows. Here the device cache is a pool of
`num_blocks` blocks of `block_size` tokens each; a sequence holds
exactly ceil(len / block_size) blocks, tracked by a per-slot block
table that maps logical block index -> physical block id. The decode
kernel reads through the table (flash_decode_paged), so 16 requests at
wildly different lengths share one fixed-shape decode batch.

Split of responsibilities:

- THIS class owns the host-side allocator: the free list, the block
  tables, per-slot lengths, and the device page pool arrays.
- The compiled executables (serving/executables.py) receive the pool +
  tables as arguments and return the updated pool; the server threads
  the returned arrays back in (donation-friendly — the pool is never
  copied).

Block 0 is reserved as a scratch sink: inactive batch slots and
masked-out prompt padding write there, so the compiled step never
needs a conditional around its cache writes. It is never allocated.

Quantized mode ("int8") mirrors the contiguous int8 cache: int8 data
blocks plus per-token fp32 scale blocks (quantize_kv semantics), so
paged serving composes with the halved-HBM-traffic decode kernel.

Prefix-cache sharing (prefix_cache=True): prompts that share a prefix
with resident content — running slots AND finished requests whose
blocks still sit in the free list — adopt the cached blocks by
refcount instead of re-writing them. Safe because prefill attention is
causal (k/v at position t depend only on tokens <= t), so identical
prefixes produce identical cache content. Writes into a shared block
go through copy-on-write (prepare_write); the scratch block 0 is never
registered or shared.

Two kinds of layer (layer_kinds with "sliding" entries, window=W): a
FULL layer caches every position, as above; a SLIDING layer attends the
last W positions only, so its layers share a second pool with its own
free list and per-sequence table (`window_tables`, the same
position-indexed shape as `block_tables`). There a sequence owns only
the blocks that hold one of its last W positions: `alloc` takes the
tail of a prompt, `ensure` RELEASES the leading blocks that fell out of
the window before it takes the block of the next position, so a
sequence never owns more than ceil(W / block_size) + 1 of them
(`window_blocks_per_seq`). Released entries of the table read 0; the
windowed sweep never looks at them (kernels/flash_decode.py). Prefill
writes for positions outside the window sink into scratch block 0 by
the same zero entries. Admission, `ensure`, `free_slot` and `check()`
count both kinds; prefix sharing, rewind and tiering know one kind
only, and the server refuses them for such a net.

Recurrent layers (layer_kinds with "recurrent" entries, `state_shapes`
= {name: (shape, dtype)}): such a layer caches no rows, it keeps a
fixed-size state a sequence. Its entry of `pages` is the STATE POOL
{name: (batch_slots,) + shape}: row `slot` is the state of the
sequence in batch slot `slot`, so the pool needs no table, and block
pools exist for the attention layers only. `alloc` takes the slot's
row, `free_slot` gives it back (a preemption is a `free_slot`: the
state is not snapshotted, re-admission prefills it anew); the prefill
program overwrites the whole row, so a reused row carries nothing
over. A row is in use exactly while its slot owns blocks
(`state_slots_used`); `check()` holds every layer's pool to its kind.
A state cannot be shared by prefix nor rewound by a token, and the
server refuses those features for such a net.

A cache ALL of whose layers are recurrent (`paged` False) holds the
state pool and nothing else: no block pool on the device, not even the
scratch block (nothing is written a token, so nothing needs a sink), no
table, an empty free list. A sequence costs one row of the pool and
nothing a token: `alloc` takes the slot's row whatever the length,
`can_alloc` and `ensure` always hold, `blocks_for` is 0, so admission
is by free slot alone, nothing is ever preempted for room, and the
server does no block work a tick. `num_blocks` is ignored.

Latent layers (layer_kinds with "latent" entries): such a layer caches
every position, like a FULL one and under the same allocator, table
and `check()`, but ONE row a position that all its heads share and
that is read as keys and as values both: its entry of `pages` is
{"k": (N, 1, bs, row)} alone, `num_kv_heads` 1 and `head_dim` the
row's width. `latent_pool_bytes` is what those pools hold. No int8
pool and no prefix sharing (the server refuses them by the
description).
"""
from __future__ import annotations

import math
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["PagedKVCache"]


class PagedKVCache:
    """Block allocator + device page pool for `num_layers` layers.

    Device layout per layer:
      "model" dtype: {"k": (N, K, bs, d), "v": (N, K, bs, d)}
      "int8":        {"k": int8 (N, K, bs, d), "ks": f32 (N, K, bs, 1),
                      "v": int8 (N, K, bs, d), "vs": f32 (N, K, bs, 1)}
    """

    def __init__(self, *, num_layers: int, num_kv_heads: int,
                 head_dim: int, num_blocks: int, block_size: int,
                 batch_slots: int, max_blocks_per_seq: int,
                 dtype=jnp.float32, quantized: bool = False,
                 prefix_cache: bool = False, device=None,
                 layer_kinds=None, window: Optional[int] = None,
                 window_num_blocks: Optional[int] = None,
                 state_shapes=None):
        kinds = tuple(layer_kinds) if layer_kinds is not None \
            else ("full",) * num_layers
        #: False where every layer is recurrent: the state pool alone,
        #: no block pool, no table, nothing to allocate a token
        self.paged = any(kind != "recurrent" for kind in kinds)
        if not self.paged:
            num_blocks, max_blocks_per_seq = 1, 0
        elif num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is the "
                             "reserved scratch block)")
        if len(kinds) != num_layers:
            raise ValueError(f"layer_kinds names {len(kinds)} layers, "
                             f"num_layers={num_layers}")
        self.layer_kinds = kinds
        self.state_shapes = dict(state_shapes or {}) \
            if "recurrent" in kinds else {}
        if "recurrent" in kinds and (quantized or prefix_cache
                                     or not self.state_shapes):
            raise NotImplementedError(
                "a cache with recurrent layers needs their state_shapes"
                " and has no int8 pool and no prefix sharing")
        if "latent" in kinds and (quantized or prefix_cache
                                  or num_kv_heads != 1):
            raise NotImplementedError(
                "a cache with latent layers holds one row a position "
                "(num_kv_heads 1) and has no int8 pool and no prefix "
                "sharing")
        self.window = int(window) if "sliding" in kinds else None
        if self.window is not None and (quantized or prefix_cache):
            raise NotImplementedError(
                "a cache with sliding-window layers has no int8 pool "
                "and no prefix sharing")
        #: most blocks one sequence owns in the sliding layers' pool
        self.window_blocks_per_seq = 0 if self.window is None else \
            min(max_blocks_per_seq,
                -(-self.window // block_size) + 1)
        if self.window is not None and window_num_blocks is None:
            window_num_blocks = \
                batch_slots * self.window_blocks_per_seq + 1
        self.window_num_blocks = window_num_blocks \
            if self.window is not None else 0
        self.num_layers = num_layers
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.batch_slots = batch_slots
        self.max_blocks_per_seq = max_blocks_per_seq
        self.quantized = quantized
        self.dtype = dtype

        N, K, bs, d = num_blocks, num_kv_heads, block_size, head_dim
        # device_put with an EXPLICIT device = committed initial
        # pools. Fresh eager arrays are uncommitted, and the
        # executables' first call would then carry a different
        # sharding signature than every later call (whose pools are
        # jit outputs) — one silent extra XLA compile per program.
        # The server passes the device its weights are on.
        dev = jax.devices()[0] if device is None else device
        if quantized:
            self.pages = [jax.device_put(
                {"k": jnp.zeros((N, K, bs, d), jnp.int8),
                 "ks": jnp.full((N, K, bs, 1), 1e-8 / 127.0,
                                jnp.float32),
                 "v": jnp.zeros((N, K, bs, d), jnp.int8),
                 "vs": jnp.full((N, K, bs, 1), 1e-8 / 127.0,
                                jnp.float32)}, dev)
                          for _ in range(num_layers)]
        else:
            def layer_pages(kind):
                if kind == "recurrent":
                    return {name: jnp.zeros((batch_slots,) + tuple(shape),
                                            dt)
                            for name, (shape, dt)
                            in self.state_shapes.items()}
                n = self.window_num_blocks if kind == "sliding" else N
                if kind == "latent":
                    return {"k": jnp.zeros((n, K, bs, d), dtype)}
                return {"k": jnp.zeros((n, K, bs, d), dtype),
                        "v": jnp.zeros((n, K, bs, d), dtype)}

            self.pages = [jax.device_put(layer_pages(kind), dev)
                          for kind in kinds]
        #: bytes of the recurrent layers' state pool (0 without them)
        self.state_pool_bytes = sum(
            int(a.size) * a.dtype.itemsize
            for kind, pg in zip(kinds, self.pages) if kind == "recurrent"
            for a in pg.values())

        #: bytes of the latent layers' pools (0 without them)
        self.latent_pool_bytes = sum(
            int(pg["k"].size) * pg["k"].dtype.itemsize
            for kind, pg in zip(kinds, self.pages) if kind == "latent")
        #: positions those pools hold (block 0, the scratch, apart)
        self.latent_pool_tokens = (num_blocks - 1) * block_size \
            if "latent" in kinds else 0

        # host-side allocator state. Free list is LIFO (hot blocks get
        # reused first); block 0 never enters it.
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        #: (slots, max_blocks) physical ids in logical order; 0 =
        #: unallocated (reads of those positions are masked by
        #: valid_len, writes only ever target allocated blocks or the
        #: scratch sink)
        self.block_tables = np.zeros((batch_slots, max_blocks_per_seq),
                                     np.int32)
        self._slot_blocks: List[List[int]] = [[] for _ in
                                              range(batch_slots)]
        self._slot_len = np.zeros(batch_slots, np.int64)
        #: slots a sequence holds (with their blocks, where there are
        #: blocks; the one book an all-recurrent cache keeps)
        self._slot_held = np.zeros(batch_slots, bool)
        self.alloc_count = 0
        self.free_count = 0
        # the sliding layers' pool: its own free list and table; a
        # slot owns the logical blocks [first, first + len(blocks))
        self.window_tables = None
        if self.window is not None:
            self._wfree: List[int] = list(
                range(self.window_num_blocks - 1, 0, -1))
            self.window_tables = np.zeros(
                (batch_slots, max_blocks_per_seq), np.int32)
            self._wslot_blocks: List[List[int]] = [
                [] for _ in range(batch_slots)]
            self._wslot_first = np.zeros(batch_slots, np.int64)

        # -- prefix-cache sharing state (refcounts are ALWAYS
        # maintained so check() can enforce them; the content index
        # and matching only run when prefix_cache=True) ---------------
        self.prefix_cache = prefix_cache
        #: per-block reference count; rc[0] (scratch) stays 0 forever
        self._refcount = np.zeros(num_blocks, np.int32)
        #: content index: chain key (parent_key, chunk_tokens) ->
        #: physical block. A key embeds its whole ancestry, so a hit
        #: guarantees the ENTIRE prefix up to that block matches, not
        #: just the block's own tokens.
        self._chain: dict = {}
        #: reverse map block -> its chain key (one key per block),
        #: purged when the block is reallocated or rewritten in place
        self._block_key: dict = {}
        self.prefix_hits = 0
        self.prefix_tokens_shared = 0
        self.cow_count = 0
        #: optional KVTierManager (serving/kv_tier.py). When attached,
        #: _purge DEMOTES registered content to the host tier instead
        #: of discarding it, and park_restored re-admits it.
        self.tier = None

    # -- accounting ---------------------------------------------------------

    @property
    def num_free_blocks(self) -> int:
        return len(self._free)

    @property
    def num_used_blocks(self) -> int:
        # excludes the reserved scratch block
        return (self.num_blocks - 1) - len(self._free)

    # both kinds, by name: the full layers' pool is the one the
    # unqualified counters above have always meant
    @property
    def global_blocks_used(self) -> int:
        return self.num_used_blocks

    @property
    def global_blocks_capacity(self) -> int:
        return self.num_blocks - 1

    @property
    def window_blocks_used(self) -> int:
        if self.window is None:
            return 0
        return (self.window_num_blocks - 1) - len(self._wfree)

    @property
    def window_blocks_capacity(self) -> int:
        return max(0, self.window_num_blocks - 1)

    @property
    def state_slots_used(self) -> int:
        """Rows of the state pool a sequence holds: a slot's row goes
        with the slot, taken by `alloc` and given back by
        `free_slot`."""
        return int(self._slot_held.sum()) if self.state_shapes else 0

    def blocks_for(self, num_tokens: int) -> int:
        if not self.paged:
            return 0
        return max(1, math.ceil(num_tokens / self.block_size))

    def _window_span(self, num_tokens: int):
        """(first, count) of the logical blocks a sliding layer keeps
        for a sequence of `num_tokens` cached positions whose next
        token attends the last `window` of them, itself included."""
        first = max(0, num_tokens - self.window + 1) // self.block_size
        last = max(num_tokens - 1, 0) // self.block_size
        return first, last - first + 1

    def window_blocks_for(self, num_tokens: int) -> int:
        """Most blocks of the sliding pool a sequence that grows to
        `num_tokens` owns at one time (0 without sliding layers)."""
        if self.window is None:
            return 0
        return min(self.blocks_for(num_tokens),
                   self.window_blocks_per_seq)

    def can_alloc(self, num_tokens: int) -> bool:
        if self.window is not None and \
                len(self._wfree) < self._window_span(num_tokens)[1]:
            return False
        return len(self._free) >= self.blocks_for(num_tokens)

    def fragmentation(self) -> float:
        """Free-list contiguity: 1 − (largest contiguous free run /
        free blocks). 0.0 when the free pool is one solid run (or has
        ≤1 block); →1.0 as the pool shatters into single-block holes.
        Paged attention doesn't need physical contiguity, but a
        shattered pool is the fingerprint of alloc/free churn and of
        prefix-parked blocks pinning holes open — the memory-pressure
        signal goodput exports alongside the exhaustion forecast.
        Tier-aware by construction: spilling a parked block to the
        host tier leaves it plain-free (registration demoted with the
        content), so spilled prefixes stop pinning holes open and the
        gauge relaxes instead of double-counting them."""
        n = len(self._free)
        if n <= 1:
            return 0.0
        ids = sorted(self._free)
        best = run = 1
        for prev, cur in zip(ids, ids[1:]):
            run = run + 1 if cur == prev + 1 else 1
            if run > best:
                best = run
        return 1.0 - best / n

    def parked_blocks(self) -> int:
        """Free blocks still holding registered prefix content
        (resurrectable until reused) — the prefix cache's share of the
        free pool. Tier-aware: content that has DEMOTED to the host
        tier no longer pins a device block, so spilled prefixes never
        double-count as free-list pressure (the PoolForecaster reads
        num_free_blocks; this gauge explains how much of it is
        parked)."""
        if self.tier is None:
            return sum(1 for b in self._free if b in self._block_key)
        host = self.tier.resident_keys()
        n = 0
        for b in self._free:
            key = self._block_key.get(b)
            if key is None:
                continue
            # defensive: a key resident in the host tier is not
            # parked here (check() asserts the tiers are disjoint)
            if self.tier.flat_key(key) in host:
                continue
            n += 1
        return n

    def stats(self) -> dict:
        cap = self.num_blocks - 1
        out = {"num_blocks": cap, "block_size": self.block_size,
               "free_blocks": self.num_free_blocks,
               "used_blocks": self.num_used_blocks,
               "utilization": self.num_used_blocks / cap if cap else 0,
               "allocs": self.alloc_count, "frees": self.free_count,
               "shared_blocks": int((self._refcount > 1).sum()),
               "prefix_hits": self.prefix_hits,
               "prefix_tokens_shared": self.prefix_tokens_shared,
               "cow_copies": self.cow_count,
               "fragmentation": self.fragmentation(),
               "parked_blocks": self.parked_blocks()}
        if self.state_shapes:
            out.update(state_pool_bytes=self.state_pool_bytes,
                       state_slots_used=self.state_slots_used)
        if self.latent_pool_bytes:
            out.update(latent_pool_bytes=self.latent_pool_bytes,
                       latent_pool_tokens=self.latent_pool_tokens)
        if self.window is not None:
            out.update(
                window_blocks_used=self.window_blocks_used,
                window_blocks_capacity=self.window_blocks_capacity,
                global_blocks_used=self.global_blocks_used,
                global_blocks_capacity=self.global_blocks_capacity)
        if self.tier is not None:
            out.update(self.tier.stats())
        return out

    def slot_len(self, slot: int) -> int:
        return int(self._slot_len[slot])

    def slot_blocks(self, slot: int) -> List[int]:
        return list(self._slot_blocks[slot])

    # -- alloc / extend / free ----------------------------------------------

    def attach_tier(self, tier):
        """Attach a KVTierManager: from now on reclaiming a parked
        block demotes its content to the host tier instead of erasing
        the index entry outright."""
        self.tier = tier

    def _purge(self, blk: int):
        """Drop the block's content registration (its data is about to
        be reused or overwritten below the registered length). With a
        tier attached, the content demotes to the host tier first —
        the block's data is still intact at purge time."""
        key = self._block_key.pop(blk, None)
        if key is not None and self._chain.get(key) == blk:
            del self._chain[key]
            if self.tier is not None:
                self.tier.on_purge(blk, key)

    def _pop_free(self) -> int:
        """Claim a fresh block for private use: registered content (a
        finished request's cache parked in the free list) is purged
        here, never earlier — resurrection stays possible until the
        block is actually reused."""
        blk = self._free.pop()
        self._purge(blk)
        self._refcount[blk] = 1
        return blk

    def alloc(self, slot: int, num_tokens: int) -> bool:
        """Allocate blocks for a fresh sequence of `num_tokens` in
        `slot`. Returns False (and allocates nothing) if the pool
        cannot cover it; the slot must be empty."""
        if self._slot_held[slot]:
            raise ValueError(f"slot {slot} already holds "
                             f"{len(self._slot_blocks[slot])} blocks")
        need = self.blocks_for(num_tokens)
        if need > self.max_blocks_per_seq:
            raise ValueError(
                f"sequence of {num_tokens} tokens needs {need} blocks "
                f"> max_blocks_per_seq={self.max_blocks_per_seq}")
        if not self.can_alloc(num_tokens):
            return False
        blocks = [self._pop_free() for _ in range(need)]
        self._slot_blocks[slot] = blocks
        self.block_tables[slot, :need] = blocks
        self._slot_len[slot] = num_tokens
        self._slot_held[slot] = True
        self.alloc_count += need
        if self.window is not None:
            first, count = self._window_span(num_tokens)
            wblocks = [self._wfree.pop() for _ in range(count)]
            self._wslot_blocks[slot] = wblocks
            self._wslot_first[slot] = first
            self.window_tables[slot, first:first + count] = wblocks
        return True

    def _window_ensure(self, slot: int, pos: int) -> bool:
        """The sliding pool's half of `ensure`: give back the leading
        blocks no position >= pos - window + 1 lies in, then take the
        block of `pos` if the slot lacks it (the block just given back
        is the one taken: a long sequence cycles through its own)."""
        held = self._wslot_blocks[slot]
        first = int(self._wslot_first[slot])
        keep = max(0, pos - self.window + 1) // self.block_size
        while held and first < keep:
            self.window_tables[slot, first] = 0
            self._wfree.append(held.pop(0))
            first += 1
        if not held:
            first = pos // self.block_size
        self._wslot_first[slot] = first
        if pos // self.block_size < first + len(held):
            return True
        if not self._wfree:
            return False
        blk = self._wfree.pop()
        self.window_tables[slot, first + len(held)] = blk
        held.append(blk)
        return True

    def ensure(self, slot: int, pos: int) -> bool:
        """Make sure the block holding token position `pos` is
        allocated for `slot` (called before every decode tick for the
        slot's next write position). Allocates at most one block.
        Returns False if the pool is exhausted — the scheduler then
        preempts another sequence and retries."""
        if not self.paged:
            return True
        if self.window is not None \
                and not self._window_ensure(slot, pos):
            return False
        need = pos // self.block_size + 1
        held = len(self._slot_blocks[slot])
        if need <= held:
            self._slot_len[slot] = max(self._slot_len[slot], pos + 1)
            return True
        if need > self.max_blocks_per_seq:
            raise ValueError(f"position {pos} exceeds "
                             f"max_blocks_per_seq={self.max_blocks_per_seq}"
                             f" * block_size={self.block_size}")
        if not self._free:
            return False
        blk = self._pop_free()
        self._slot_blocks[slot].append(blk)
        self.block_tables[slot, held] = blk
        self._slot_len[slot] = pos + 1
        self._slot_held[slot] = True
        self.alloc_count += 1
        return True

    def append_span(self, slot: int, pos: int, n: int) -> int:
        """Multi-token (speculative) append: make blocks available for
        writing positions pos .. pos+n-1. Allocates as many as the
        pool can cover and returns how many positions are backed
        (possibly < n under pool pressure — the scheduler then shrinks
        the draft instead of preempting; rewind() returns the blocks
        if the tokens are rejected)."""
        covered = 0
        for p in range(pos, pos + n):
            if not self.ensure(slot, p):
                break
            covered += 1
        return covered

    def rewind(self, slot: int, num_tokens: int):
        """Roll the slot's logical length back to `num_tokens`
        (speculative rejected-suffix rewind): trailing blocks that
        hold ONLY positions >= num_tokens are released, refcount-
        aware like free_slot. Stale rows inside the kept tail block
        are masked by valid lengths and overwritten by later writes."""
        keep = self.blocks_for(num_tokens)
        held = self._slot_blocks[slot]
        while len(held) > keep:
            b = held.pop()
            self.block_tables[slot, len(held)] = 0
            self._refcount[b] -= 1
            self.free_count += 1
            if self._refcount[b] == 0:
                if self.prefix_cache and b in self._block_key:
                    self._free.insert(0, b)
                else:
                    self._free.append(b)
        self._slot_len[slot] = min(int(self._slot_len[slot]),
                                   max(num_tokens, 0))

    def free_slot(self, slot: int):
        """Release the slot's block references and clear its table row
        (so an evicted slot's reads resolve to the scratch block).
        Shared blocks only return to the pool when the LAST reference
        drops; registered content parks at the BOTTOM of the LIFO so
        fresh allocations purge it last (maximizing prefix-cache
        lifetime)."""
        blocks = self._slot_blocks[slot]
        self.free_count += len(blocks)
        for b in reversed(blocks):
            self._refcount[b] -= 1
            if self._refcount[b] == 0:
                if self.prefix_cache and b in self._block_key:
                    self._free.insert(0, b)
                else:
                    # LIFO reuse keeps the pool compact under churn
                    self._free.append(b)
        self._slot_blocks[slot] = []
        self.block_tables[slot, :] = 0
        self._slot_len[slot] = 0
        self._slot_held[slot] = False
        if self.window is not None:
            self._wfree.extend(reversed(self._wslot_blocks[slot]))
            self._wslot_blocks[slot] = []
            self._wslot_first[slot] = 0
            self.window_tables[slot, :] = 0

    # -- prefix-cache sharing -----------------------------------------------

    def match_prefix(self, tokens, root=None) -> tuple:
        """Admit-time longest-common-prefix match of `tokens` against
        registered resident content (running AND finished-but-not-yet-
        reused slots). Returns (blocks, shared_len): the physical
        blocks covering the first shared_len tokens — a chain of
        full-chunk matches plus at most one tail block where one
        side's tokens are a prefix of the other's. Never shares on
        genuine mid-block divergence (that would require overwriting
        shared content at admit time).

        `root` namespaces the chain: None is the base-model namespace;
        a LoRA request passes its adapter sentinel (the server's
        ``("__lora__", name)``) so KV content computed under adapter X
        is NEVER matched by adapter Y or the base model — same tokens,
        different weights, different cache rows."""
        if not self.prefix_cache or len(tokens) == 0:
            return [], 0
        bs = self.block_size
        toks = tuple(int(t) for t in tokens)
        blocks: List[int] = []
        parent = root
        i = 0
        limit = min(len(toks), self.max_blocks_per_seq * bs)
        while i + bs <= limit:
            key = (parent, toks[i:i + bs])
            blk = self._chain.get(key)
            if blk is None:
                break
            blocks.append(blk)
            parent = key
            i += bs
        shared_len = i
        rem = toks[i:limit]
        if rem:
            best: Optional[tuple] = None
            for (pk, chunk), blk in self._chain.items():
                if pk != parent:
                    continue
                n = min(len(rem), len(chunk))
                if n and chunk[:n] == rem[:n]:
                    if best is None or n > best[1]:
                        best = (blk, n)
            if best is not None:
                blocks.append(best[0])
                shared_len += best[1]
        return blocks, shared_len

    def alloc_shared(self, slot: int, tokens,
                     root=None) -> Optional[dict]:
        """Allocate `slot` for prompt `tokens`, adopting matched
        prefix blocks (refcount + 1) instead of writing them again.
        Returns None (nothing allocated) if the pool cannot cover the
        unshared remainder, else
            {"shared_len": L, "cow": (src, dst) | None}.
        `cow` is set when the prompt extends past the shared content
        mid-block: the caller must device-copy block src -> dst BEFORE
        the prefill that overwrites positions >= shared_len. When the
        prompt ENDS inside a shared block (T == shared_len), the block
        is adopted as-is and the first decode write triggers
        copy-on-write via prepare_write()."""
        if self._slot_held[slot]:
            raise ValueError(f"slot {slot} already holds "
                             f"{len(self._slot_blocks[slot])} blocks")
        T = len(tokens)
        need = self.blocks_for(T)
        if need > self.max_blocks_per_seq:
            raise ValueError(
                f"sequence of {T} tokens needs {need} blocks "
                f"> max_blocks_per_seq={self.max_blocks_per_seq}")
        bs = self.block_size
        shared, shared_len = self.match_prefix(tokens, root=root)
        cow_src = None
        claim_tail = False
        if T > shared_len and shared_len % bs != 0:
            # prompt continues inside the shared tail block: it needs
            # a private copy up front (unless nobody else holds it —
            # then claim it outright, content below shared_len intact)
            tail = shared[-1]
            if self._refcount[tail] == 0:
                claim_tail = True  # resurrect privately, no copy
            else:
                cow_src = shared.pop()
        # feasibility BEFORE any mutation: resurrected shared blocks
        # come out of the free list without consuming "fresh" budget
        n_resurrect = sum(1 for b in shared if self._refcount[b] == 0)
        n_fresh = need - len(shared) + (1 if cow_src is not None else 0)
        if len(self._free) - n_resurrect < n_fresh:
            return None
        cow = None
        blocks: List[int] = []
        for b in shared:
            if self._refcount[b] == 0:
                # resurrect from the free list: content (and its
                # registration) stays — it is being shared, not reused
                self._free.remove(b)
            self._refcount[b] += 1
            blocks.append(b)
        if claim_tail:
            # the tail block becomes private and will be overwritten
            # past shared_len — its registration is now stale
            self._purge(blocks[-1])
        if cow_src is not None:
            dst = self._pop_free()
            blocks.append(dst)
            cow = (cow_src, dst)
            self.cow_count += 1
        while len(blocks) < need:
            blocks.append(self._pop_free())
        self._slot_blocks[slot] = blocks
        self.block_tables[slot, :len(blocks)] = blocks
        self._slot_len[slot] = T
        self._slot_held[slot] = True
        self.alloc_count += need
        if shared_len:
            self.prefix_hits += 1
            self.prefix_tokens_shared += shared_len
        return {"shared_len": shared_len, "cow": cow}

    def prepare_write(self, slot: int, pos: int):
        """Copy-on-write hook: call before writing token position
        `pos` into `slot`'s cache. Returns
          None        — write in place (nothing to do),
          (src, dst)  — the caller must device-copy block src -> dst
                        before the write (table already repointed),
          False       — pool exhausted; preempt something and retry.
        Also purges a private block's stale registration when the
        write lands below its registered content length."""
        idx = pos // self.block_size
        held = self._slot_blocks[slot]
        if idx >= len(held):
            return None  # a fresh block will come from ensure()
        blk = held[idx]
        if self._refcount[blk] > 1:
            if not self._free:
                return False
            dst = self._pop_free()
            self._refcount[blk] -= 1
            held[idx] = dst
            self.block_tables[slot, idx] = dst
            self.cow_count += 1
            self.alloc_count += 1
            self.free_count += 1
            return (blk, dst)
        key = self._block_key.get(blk)
        if key is not None \
                and (pos - idx * self.block_size) < len(key[1]):
            self._purge(blk)
        return None

    def register_prefix(self, slot: int, tokens, root=None):
        """Publish `slot`'s prefilled content into the prefix index
        (call AFTER the prefill that wrote it). Chunks chain onto the
        canonical path: if identical content is already registered
        under another block, the existing entry wins and our block
        stays unregistered (dedup prefers the older copy). `root`
        namespaces the chain per adapter — see :meth:`match_prefix`."""
        if not self.prefix_cache:
            return
        bs = self.block_size
        toks = tuple(int(t) for t in tokens)
        parent = root
        for idx, blk in enumerate(self._slot_blocks[slot]):
            chunk = toks[idx * bs:(idx + 1) * bs]
            if not chunk:
                break
            key = (parent, chunk)
            if key not in self._chain and blk not in self._block_key \
                    and blk != 0:
                self._chain[key] = blk
                self._block_key[blk] = key
                if self.tier is not None:
                    # the freshly computed device copy supersedes any
                    # stale host-tier copy (one-tier residency)
                    self.tier.on_register(key)
            parent = key

    def park_restored(self, key) -> Optional[int]:
        """Tier-restore adoption point: claim a free block and
        register restored content under chain `key`, PARKED (refcount
        0, free-list bottom) — exactly the state of a finished
        request's prefix, so the next alloc_shared resurrects it
        through the normal sharing path. The caller (KVTierManager)
        then runs the restore executable into the returned block.
        Returns None when the pool has no free block or the key is
        already resident."""
        if not self.prefix_cache or key is None:
            return None
        if key in self._chain or not self._free:
            return None
        blk = self._free.pop()
        self._purge(blk)  # demotes the evicted content, if any
        self._chain[key] = blk
        self._block_key[blk] = key
        self._free.insert(0, blk)
        return blk

    def check(self):
        """Allocator invariants (tests + debugging): refcounts match
        ownership exactly, scratch never handed out or shared,
        conservation of blocks, content index consistent."""
        owned = [b for blks in self._slot_blocks for b in blks]
        assert all(bool(b) <= bool(h) for b, h
                   in zip(self._slot_blocks, self._slot_held)), \
            "blocks on a slot no sequence holds"
        assert self.paged or (
            self.num_blocks == 1 and not owned and not self._free
            and self.block_tables.size == 0
            and not any("k" in pg for pg in self.pages or ())), \
            "an all-recurrent cache holds a block pool or a table"
        assert 0 not in owned, "scratch block allocated"
        assert 0 not in self._free, "scratch block in free list"
        counts: dict = {}
        for b in owned:
            counts[b] = counts.get(b, 0) + 1
        for b, c in counts.items():
            assert int(self._refcount[b]) == c, \
                f"block {b}: refcount {int(self._refcount[b])} != " \
                f"{c} owners"
            assert c == 1 or self.prefix_cache, \
                f"block {b} shared with prefix_cache disabled"
        for b in self._free:
            assert int(self._refcount[b]) == 0, \
                f"free block {b} has refcount {int(self._refcount[b])}"
        assert int(self._refcount[0]) == 0, "scratch block refcounted"
        assert int(self._refcount.sum()) == len(owned), \
            "refcounts on unreachable blocks"
        assert not (set(owned) & set(self._free)), \
            "block both owned and free"
        assert len(set(owned)) + len(self._free) \
            == self.num_blocks - 1, "block leak"
        # content index is a bijection over live blocks
        for blk, key in self._block_key.items():
            assert self._chain.get(key) == blk, \
                f"block {blk} registration out of sync"
        for key, blk in self._chain.items():
            assert self._block_key.get(blk) == key, \
                f"chain entry for block {blk} out of sync"
            assert blk != 0, "scratch block registered"
        if self.tier is not None:
            # tier invariants: one tier per content key, conservation
            # across spill/restore/adopt (KVTierManager.check)
            self.tier.check()
        for kind, pg in zip(self.layer_kinds, self.pages or ()):
            # a recurrent layer holds the state pool, a row a slot, and
            # no block pool; an attention layer the reverse, a latent
            # one its rows' pool alone
            want = {n: (self.batch_slots,) + tuple(shape)
                    for n, (shape, _) in self.state_shapes.items()} \
                if kind == "recurrent" else None
            assert want is None and "k" in pg or \
                {n: a.shape for n, a in pg.items()} == want, \
                f"a {kind} layer's pool out of shape: {sorted(pg)}"
            assert (kind == "latent") == (sorted(pg) == ["k"]), \
                f"a {kind} layer's pools: {sorted(pg)}"
        if self.window is not None:
            wowned = [b for blks in self._wslot_blocks for b in blks]
            assert 0 not in wowned and 0 not in self._wfree, \
                "sliding pool's scratch block handed out"
            assert len(set(wowned)) == len(wowned), \
                "sliding block owned twice"
            assert not (set(wowned) & set(self._wfree)), \
                "sliding block both owned and free"
            assert len(wowned) + len(self._wfree) \
                == self.window_num_blocks - 1, "sliding block leak"
            for slot, blks in enumerate(self._wslot_blocks):
                assert len(blks) <= self.window_blocks_per_seq, \
                    f"slot {slot} owns {len(blks)} sliding blocks > " \
                    f"{self.window_blocks_per_seq}"
                first = int(self._wslot_first[slot])
                row = self.window_tables[slot]
                assert list(row[first:first + len(blks)]) == blks \
                    and not row[:first].any() \
                    and not row[first + len(blks):].any(), \
                    f"slot {slot}: sliding table out of sync"
