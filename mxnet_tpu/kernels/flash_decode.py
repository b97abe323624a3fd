"""Flash-decode: single-token attention against a static KV cache.

Reference analogue: the fork's fused decoder-attention kernels
(interleaved_matmul_encdec_* / fmha inference paths). TPU-first: during
autoregressive decoding the bottleneck is streaming the KV cache from
HBM; this kernel tiles the cache through VMEM with an
online-softmax accumulator and never materializes the GQA head
repetition (q rows for one kv head attend to the SAME cache block, so
the block is read once per kv head instead of once per query head —
1/rep of the naive jnp.repeat traffic).

Layout: q (B, H, d) for ONE decode position, caches (B, K, S, d)
("cache-native": kv-head major, so the kernel's blocked trailing dims
span the array and NO per-step transpose/copy of the cache is needed)
with H = K * rep, valid lengths (B,) masking the un-filled tail.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import tuning
from .dispatch import KernelFallback, kernel_mode

__all__ = ["flash_decode", "flash_decode_quantized",
           "quantize_kv", "dequantize_kv",
           "reference_decode_attention",
           "gather_kv_pages", "flash_decode_paged",
           "flash_decode_paged_quantized",
           "flash_decode_paged_latent", "paged_latent_mode",
           "reference_paged_latent_attention",
           "paged_kernel_mode", "paged_gather_bytes",
           "reference_paged_window_attention",
           "flash_decode_paged_window",
           "flash_decode_paged_window_quantized",
           "paged_window_mode"]

_fallback = KernelFallback("flash-decode", "FLASH")

#: distinct fallback site for the in-kernel paged path, so a paged
#: regression is visible separately from the contiguous kernel in
#: telemetry's kernel_fallbacks provider
_paged_fallback = KernelFallback("flash-decode-paged", "FLASH")


def reference_decode_attention(q, k_cache, v_cache, valid_len,
                               scale=None, window=None):
    """jnp reference on (B, K, S, d) caches. GQA WITHOUT jnp.repeat:
    fold the rep axis into the einsum so XLA reads the cache once per
    kv head. `window` keeps the last `window` valid positions only."""
    B, H, d = q.shape
    K, S = k_cache.shape[1], k_cache.shape[2]
    rep = H // K
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qr = q.reshape(B, K, rep, d).astype(jnp.float32)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    s = jnp.einsum("bkrd,bksd->bkrs", qr, kf) * scale
    mask = jnp.arange(S)[None, :] < valid_len[:, None]        # (B, S)
    if window is not None:
        mask &= jnp.arange(S)[None, :] >= valid_len[:, None] - window
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkrs,bksd->bkrd", p, vf)
    return out.reshape(B, H, d).astype(q.dtype)


def _flash_decode_pallas(q, k_cache, v_cache, valid_len, scale,
                         interpret, block_s=256):
    """Grid (B, K): one kernel instance owns a kv head's full cache
    (S, d) in VMEM and sweeps it in blocks with a fori_loop — the same
    walk as flash_attention's forward, but with one (rep, d) query
    block and a valid-length mask instead of the causal mask.

    Mosaic layout notes: caches arrive (B, K, S, d) — already the
    layout whose blocked trailing dims span the array, so no per-step
    copy; valid_len rides in SMEM via scalar prefetch (a rank-1 VMEM
    block of size 1 is rejected)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, d = q.shape
    K, S = k_cache.shape[1], k_cache.shape[2]
    rep = H // K
    blk = max(1, min(block_s, S))
    while S % blk:
        blk //= 2
    n_s = S // blk
    qr = q.reshape(B, K, rep, d)

    def kernel(vl_ref, q_ref, k_ref, v_ref, o_ref):
        qblk = q_ref[...].astype(jnp.float32) * scale    # (rep, d)
        vl = vl_ref[pl.program_id(0)]
        m = jnp.full((rep,), -jnp.inf, jnp.float32)
        l = jnp.zeros((rep,), jnp.float32)
        acc = jnp.zeros((rep, d), jnp.float32)

        def body(sj, carry):
            m_, l_, acc_ = carry
            kblk = k_ref[pl.dslice(sj * blk, blk), :] \
                .astype(jnp.float32)                     # (blk, d)
            vblk = v_ref[pl.dslice(sj * blk, blk), :] \
                .astype(jnp.float32)
            s = qblk @ kblk.T                            # (rep, blk)
            pos = sj * blk + jax.lax.broadcasted_iota(
                jnp.int32, (rep, blk), 1)
            s = jnp.where(pos < vl, s, -jnp.inf)
            m_new = jnp.maximum(m_, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[:, None])
            # comparison instead of jnp.isfinite: Mosaic has no
            # is_finite lowering; the running max only leaves -inf
            # once a valid key has been seen
            p = jnp.where((m_new > -jnp.inf)[:, None], p, 0.0)
            corr = jnp.where(m_ > -jnp.inf,
                             jnp.exp(m_ - m_new), 0.0)
            return (m_new, corr * l_ + jnp.sum(p, axis=-1),
                    corr[:, None] * acc_ + p @ vblk)

        # only sweep blocks that can contain valid positions
        upper = jnp.minimum(n_s, (vl + blk - 1) // blk)
        m, l, acc = jax.lax.fori_loop(0, upper, body, (m, l, acc))
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[...] = (acc / safe_l[:, None]).astype(o_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K),
        in_specs=[
            pl.BlockSpec((None, None, rep, d),
                         lambda b, h, vl: (b, h, 0, 0)),
            pl.BlockSpec((None, None, S, d),
                         lambda b, h, vl: (b, h, 0, 0)),
            pl.BlockSpec((None, None, S, d),
                         lambda b, h, vl: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, rep, d),
                               lambda b, h, vl: (b, h, 0, 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, rep, d), q.dtype),
        interpret=interpret,
        name="flash_decode",
    )(valid_len.astype(jnp.int32), qr, k_cache, v_cache)
    return out.reshape(B, H, d)


def _pallas_mode(cache, scale_bytes=0):
    """Gate of the contiguous kernels, from static shapes: Mosaic
    tiling needs S % 128 == 0, and one kv head's K+V (with
    `scale_bytes` of per-token scale a row beside an int8 cache) must
    fit VMEM (~16 MiB/core) next to the working blocks — beyond the
    budget (kernels/tuning.py: flash_decode.vmem_cache_budget_bytes)
    the (B, K)-grid kernel would fail at Mosaic compile time INSIDE the
    caller's jit, where no try/except can catch it."""
    S, d = cache.shape[2], cache.shape[3]
    cache_bytes = 2 * S * (d * cache.dtype.itemsize + scale_bytes)
    return kernel_mode("FLASH", cache, ok=S % 128 == 0 and cache_bytes
                       <= tuning.get("flash_decode",
                                     "vmem_cache_budget_bytes"))


def flash_decode(q, k_cache, v_cache, valid_len, scale=None):
    """Single-position attention against the cache; Pallas on TPU, the
    no-repeat jnp formulation elsewhere."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _fallback.run(
        _pallas_mode(k_cache),
        lambda interpret: _flash_decode_pallas(
            q, k_cache, v_cache, valid_len, scale, interpret),
        lambda: reference_decode_attention(q, k_cache, v_cache,
                                           valid_len, scale))


# -- paged (block-allocated) KV cache ---------------------------------------
# The serving engine (mxnet_tpu/serving/) stores the cache as a pool of
# fixed-size blocks shared by all sequences; a per-sequence block table
# maps logical block index -> physical block id. Two read paths:
#
# - IN-KERNEL (the serving hot path): the block table and the lengths
#   ride in scalar-prefetch memory, the (N, K, bs, d) pools stay in
#   HBM, and the kernel copies each sequence's pages itself, many a
#   step and only those that hold a token (a page of all kv heads is
#   one contiguous run), double-buffered under the arithmetic — so no
#   contiguous (B, K, S, d) view is ever materialized and decode HBM
#   bytes are the cache's own (the recipe of the paged_attention that
#   ships with JAX). A page a grid cell, fetched by a BlockSpec whose
#   index map is `bt[b, i]`, paid Pallas's fixed cost a step ~10^6
#   times a tick and ran at 1.4-1.7% of the HBM roofline (PERF.md,
#   PR 25); the int8 and the window twins below still do.
# - GATHER (fallback): `gather_kv_pages` materializes the contiguous
#   view with jnp.take, then the contiguous flash sweep runs on it.
#   Correct everywhere (interpret off, odd shapes)
#   but re-creates exactly the pool-sized HBM traffic paging exists
#   to avoid; every fall-through is counted at the
#   "flash-decode-paged" site.

def gather_kv_pages(pages, block_tables):
    """Gather per-sequence logical caches from a paged pool.

    pages: (N, K, bs, ...) physical blocks (block 0 is the serving
    layer's scratch sink); block_tables: (B, nb) int32 physical block
    ids in logical order. Returns (B, K, nb*bs, ...) — the
    cache-native layout flash_decode expects. Stale data in
    unallocated/padded blocks is masked downstream by valid_len."""
    g = jnp.take(pages, block_tables, axis=0)        # (B, nb, K, bs, .)
    g = jnp.moveaxis(g, 2, 1)                        # (B, K, nb, bs, .)
    B, K, nb, bs = g.shape[:4]
    return g.reshape((B, K, nb * bs) + g.shape[4:])


def _paged_grid_spec(pl, pltpu, B, K, nb, rep, bs, d):
    """PrefetchScalarGridSpec of the int8 twin's page-a-cell schedule
    (the window twin builds the same by hand; the bf16 kernel left it
    for _flash_decode_paged_pallas's sweep): the block table (B, nb)
    and valid_len (B,) are scalar-prefetched, and the pool specs'
    index maps resolve `bt[b, i] -> physical block` BEFORE each grid
    cell runs — Pallas's pipeline emitter turns that into the
    per-block HBM->VMEM DMA (double-buffered across cells). Correct,
    and slow: one (bs, d) block of one kv head a grid step."""
    q_spec = pl.BlockSpec((None, None, rep, d),
                          lambda b, h, i, bt, vl: (b, h, 0, 0))
    pool_spec = pl.BlockSpec((None, None, bs, d),
                             lambda b, h, i, bt, vl: (bt[b, i], h, 0, 0))
    scale_spec = pl.BlockSpec(
        (None, None, bs, 1), lambda b, h, i, bt, vl: (bt[b, i], h, 0, 0))
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, nb),
        in_specs=[q_spec, pool_spec, scale_spec, pool_spec, scale_spec],
        out_specs=pl.BlockSpec((None, None, rep, d),
                               lambda b, h, i, bt, vl: (b, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((rep, 1), jnp.float32),   # m
                        pltpu.VMEM((rep, 1), jnp.float32),   # l
                        pltpu.VMEM((rep, d), jnp.float32)])  # acc


def _paged_compiler_params(pltpu, interpret):
    """Page-a-cell schedule (int8 and window twins): (batch, kv-head)
    cells are independent; only the block sweep is order-dependent
    (the online-softmax carry lives in scratch)."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))}


def _paged_sweep_pages(pool_shape, itemsize, nb=None, group=1):
    """Pages one step of the paged sweep moves: as many as the tuned
    VMEM budget holds, from static shapes alone; 0 when not even one
    fits, in which case the gate below says "gather".

    A page is every kv head's (bs, d) block of k or of v — one
    contiguous run of the (N, K, bs, d) pool, so ONE copy. The kernel
    holds two steps of k and of v (one computing, the next landing)
    and widens one head's k and v of a step to fp32 when the MXU
    cannot take them as stored; beside them the q and o blocks the
    pipeline double-buffers and the fp32 m / l / acc scratch, reckoned
    for the `group` of query heads a kv head in whole bf16 tiles of 16
    rows (a group of 20 takes two). On a v5e the kernel's time falls
    with every page added up to the budget (tuned.json has the
    sweep)."""
    _, K, bs, d = pool_shape
    page = K * bs * d * itemsize
    rows = 16 * -(-group // 16)
    fixed = 4 * K * rows * d * itemsize + 3 * K * rows * max(d, 128) * 4
    fit = (tuning.get("flash_decode_paged", "vmem_budget_bytes")
           - fixed) // (4 * page + 2 * bs * d * 4)
    if nb is not None:
        fit = min(fit, nb)
    lane = max(1, 128 // bs)     # whole 128-lane tiles of scores a step
    return int(max(0, fit - fit % lane if fit > lane else fit))


def _flash_decode_paged_pallas(q, k_pages, v_pages, block_tables,
                               valid_len, scale, interpret,
                               window=None):
    """In-kernel paged decode: grid (B,), one cell a sequence, which
    sweeps that sequence's pages P at a time (_paged_sweep_pages) in a
    loop as long as the sequence, not as the block table. The pools
    stay in HBM; a step's P pages (all kv heads of a page are one
    contiguous run: one copy for k, one for v) land in one half of a
    VMEM scratch while the arithmetic runs on the other, and the last
    step of a sequence starts the first step of the next, so the
    copies never drain. Pages past valid_len are neither fetched nor
    stepped; the ragged tail of the last step is masked (its v rows
    are what an earlier step left there: finite, under an exact 0).

    The schedule of the copies (PR 46; measured, docs/serving.md). A
    step starts the 2 * P copies of the step after it in the global
    order (this sequence's next, or the next sequence's first) as
    straight-line code, each page under a predicate of its own, dealt
    out in 2 * K shares (_dealt_starts): half of them in front of the
    step's waits (the half they fill was freed by the step before, and
    the DMA engine else stands still while the step waits), the others
    between the score and value products of the kv heads; the shares
    in front past the first sit behind one branch, which a successor
    that fits the first share skips (an idle slot's row of one token:
    nothing co-issues with a start's scalar work in front of the
    waits). A share of
    more than _UNROLLED_SHARE pages (one kv head) goes in a loop. The
    step waits for its own pages by the bits of their
    count (_wait_by_bits), k and v each on the semaphore of their half:
    a full step in a wait a set bit of P, not one a page. Every step of
    a sequence, plain or windowed, runs the ONE body: whose copies it
    starts and how many, how many pages it waits for and where its
    mask falls are scalars. A row of valid_len 0 takes one step,
    fetches nothing and writes a finite 0.

    The online softmax (m, l, acc per kv head and query row) lives in
    VMEM scratch across the steps, fp32 throughout. bf16 q, k and v
    feed the MXU as stored (bf16 x bf16 accumulated in fp32 is exact)
    and the scale multiplies the fp32 scores; the fp32 probabilities
    go in as three bf16 terms that sum to them exactly, stacked on the
    rows of ONE product with v — Mosaic's default fp32 x fp32 dot is a
    single bf16 pass (2.5e-3 off on a v5e), which would round them.

    With `window` the sweep starts at the first page that holds one of
    the last `window` positions and masks the ragged head of that page:
    a sliding-window layer reads min(valid_len, window) positions, and
    the table entries before that page are never looked at."""
    pages = _paged_sweep_pages(k_pages.shape, k_pages.dtype.itemsize,
                               block_tables.shape[1],
                               q.shape[1] // k_pages.shape[1])
    return _paged_sweep(q, k_pages, v_pages, block_tables, valid_len,
                        scale=float(scale), pages=pages,
                        interpret=bool(interpret),
                        window=None if window is None else int(window))


#: pages of a share that _dealt_starts writes out under predicates; a
#: larger share goes in a loop. Measured on a v5e (PR 46, PERF.md
#: section 6): shares of 3 pages (8 kv heads, 48 pages a step) gain by
#: being written out, one of 124 (Jamba's one kv head, 248 a step)
#: takes 113 ns a page where the loop takes 67: every start's scalar
#: work runs whether its predicate holds or not.
_UNROLLED_SHARE = 8


def _dealt_starts(pl, pages, calls, first, end, count, start):
    """-> feed(c): the c-th of a step's `calls` calls starts its share
    of the step's `pages` page copies, `start(j, at)` for page j whose
    table entry is `at` = first + j, those of them under `count`. Each
    start is straight-line code under a predicate of its own (a page
    that holds no token is not fetched; the compiler if-converts it),
    so dealt out between the pieces of a step's arithmetic the copies'
    bookkeeping is scalar work inside one basic block and the DMA queue
    is fed evenly. `at` is clamped to `end`, the table's last entry: a
    start whose predicate is false still reads its index. (lax on int32
    scalars: a jnp operator a start is most of the body's tracing
    time.) A share of more than _UNROLLED_SHARE pages is a loop as
    long as its pages under `count`."""
    end = jnp.int32(end)

    def feed(c):
        lo, hi = pages * c // calls, pages * (c + 1) // calls
        if hi - lo > _UNROLLED_SHARE:
            def one(j, _):
                start(j, jax.lax.min(first + j, end))
            jax.lax.fori_loop(lo, jnp.clip(count, lo, hi), one, None)
            return
        for j in range(lo, hi):
            at = jax.lax.min(jax.lax.add(first, jnp.int32(j)), end)

            @pl.when(jax.lax.gt(count, jnp.int32(j)))
            def _start(j=j, at=at):
                start(j, at)
    return feed


def _wait_by_bits(pl, count, most, wait):
    """Wait for `count` (0 .. `most`) pages that signal one semaphore:
    `wait(k)` waits for k pages' bytes (the semaphore counts bytes, so
    one descriptor of k pages does), under a predicate for every bit of
    the count: a full step in a wait a set bit of `most`, none in a
    loop."""
    k = 1 << (most.bit_length() - 1)
    while k:
        @pl.when((count & k) != 0)
        def _wait(k=k):
            wait(k)
        k >>= 1


@functools.partial(jax.jit, static_argnames=("scale", "pages",
                                             "interpret", "window"))
def _paged_sweep(q, k_pages, v_pages, block_tables, valid_len, *,
                 scale, pages, interpret, window=None):
    """_flash_decode_paged_pallas's kernel at `pages` a step. A jit of
    its own, so that the layers of a decode program share one trace
    and one Mosaic lowering of the body (its kv heads and its page
    starts are unrolled: traced a layer it cost a 16-layer program 2 s
    of every start)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, d = q.shape
    K, bs = k_pages.shape[1], k_pages.shape[2]
    nb = block_tables.shape[1]
    rep = H // K
    P, T = pages, pages * bs
    qr = q.reshape(B, K, rep, d)
    native = q.dtype == k_pages.dtype == jnp.bfloat16
    nt = (((1,), (1,)), ((), ()))                  # (r, d) x (t, d)

    def kernel(bt_ref, vl_ref, q_ref, k_hbm, v_hbm, o_ref,
               kbuf, vbuf, sem, slot_ref, m_ref, l_ref, acc_ref):
        b = pl.program_id(0)
        vl = vl_ref[b]

        def first(row):
            """The first page of `row` the sweep looks at."""
            if window is None:
                return 0
            return jnp.maximum(vl_ref[row] - window, 0) // bs

        def held(row, i):
            """Pages of `row`'s step `i` that hold a token (none: <= 0)."""
            return jnp.minimum(
                (vl_ref[row] + bs - 1) // bs - first(row) - i * P, P)

        def start_page(page, slot, j):
            """k and v of pool page `page` into place j of half `slot`."""
            pltpu.make_async_copy(k_hbm.at[page], kbuf.at[slot, j],
                                  sem.at[0, slot]).start()
            pltpu.make_async_copy(v_hbm.at[page], vbuf.at[slot, j],
                                  sem.at[1, slot]).start()

        def starter(row, i, slot, count):
            """-> feed(c): the c-th of a step's 2 * K calls starts its
            share of the pages of `row`'s step `i` into half `slot`,
            those of them under `count`."""
            return _dealt_starts(
                pl, P, 2 * K, row * nb + first(row) + i * P,
                B * nb - 1, count,
                lambda j, at: start_page(bt_ref[at], slot, j))

        @pl.when(b == 0)
        def _first():
            # a v row no copy ever wrote must be finite under its 0
            vbuf[...] = jnp.zeros_like(vbuf)
            slot_ref[0] = 0
            # the one step no arithmetic hides: a loop will do
            jax.lax.fori_loop(
                0, held(0, 0), lambda j, _: start_page(
                    bt_ref[first(0) + j], 0, j), None)

        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # a sequence takes at least one step, so that the hand-over of
        # the halves below never skips a row (valid_len 0: all masked)
        n = jnp.maximum(
            ((vl + bs - 1) // bs - first(b) + P - 1) // P, 1)
        slot0 = slot_ref[0]
        nxt = jnp.minimum(b + 1, B - 1)

        def step(i, _):
            """ONE body for every step, plain or windowed: whose copies
            it starts and how many, how many of its own pages it waits
            for and where its mask falls are scalars."""
            slot = jax.lax.rem(slot0 + i, 2)
            last = i + 1 == n
            row, ahead = jnp.where(last, nxt, b), jnp.where(last, 0, i + 1)
            count = jnp.where(jnp.logical_and(last, b + 1 == B), 0,
                              held(row, ahead))
            feed = starter(row, ahead, 1 - slot, count)

            def wait(k):
                for hbm, buf, done in ((k_hbm, kbuf, sem.at[0, slot]),
                                       (v_hbm, vbuf, sem.at[1, slot])):
                    pltpu.make_async_copy(
                        hbm.at[pl.ds(0, k)], buf.at[slot, pl.ds(0, k)],
                        done).wait()
            # half of the shares (two at least) go in front of the
            # waits: the half they fill was freed by the step before,
            # and the DMA engine then never runs dry while this step
            # waits; the others after every other piece (measured,
            # docs/serving.md: all in front is the parent's order).
            # In front of the waits nothing co-issues with a start's
            # scalar work, so the shares there past the first sit
            # behind ONE branch: a successor that fits the first share
            # (an idle slot's row of one token) skips them
            calls = 2 * K
            lead = max(calls // 2, 2)
            after = [[] for _ in range(calls)]
            for c in range(lead, calls):
                after[(c - lead) * calls // (calls - lead)].append(c)
            feed(0)

            @pl.when(count > P // calls)
            def _rest_of_the_lead():
                for c in range(1, lead):
                    feed(c)
            _wait_by_bits(pl, jnp.maximum(held(b, i), 0), P, wait)
            at = first(b) * bs + i * T + jax.lax.broadcasted_iota(
                jnp.int32, (rep, T), 1)
            live = at < vl
            if window is not None:
                live = jnp.logical_and(live, at >= vl - window)
            for h in range(K):
                qh = q_ref[h]                                # (rep, d)
                kh = kbuf[slot, :, h].reshape(T, d)
                vh = vbuf[slot, :, h].reshape(T, d)
                if native:
                    s = jax.lax.dot_general(
                        qh, kh, nt,
                        preferred_element_type=jnp.float32) * scale
                else:
                    s = jax.lax.dot_general(
                        qh.astype(jnp.float32) * scale,
                        kh.astype(jnp.float32), nt,
                        preferred_element_type=jnp.float32)
                for c in after[2 * h]:
                    feed(c)
                s = jnp.where(live, s, -jnp.inf)             # (rep, T)
                m_prev = m_ref[h]                            # (rep, 1)
                m_new = jnp.maximum(
                    m_prev, jnp.max(s, axis=-1, keepdims=True))
                # `live`, a comparison, instead of jnp.isfinite:
                # Mosaic has no is_finite lowering
                p = jnp.where(live, jnp.exp(s - m_new), 0.0)
                corr = jnp.where(m_prev > -jnp.inf,
                                 jnp.exp(m_prev - m_new), 0.0)
                m_ref[h] = m_new
                l_ref[h] = corr * l_ref[h] \
                    + jnp.sum(p, axis=-1, keepdims=True)
                if native:
                    hi = p.astype(jnp.bfloat16)
                    rest = p - hi.astype(jnp.float32)
                    mid = rest.astype(jnp.bfloat16)
                    lo = (rest - mid.astype(jnp.float32)) \
                        .astype(jnp.bfloat16)
                    pv = jnp.dot(
                        jnp.concatenate([hi, mid, lo], axis=0), vh,
                        preferred_element_type=jnp.float32)
                    pv = pv[:rep] + pv[rep:2 * rep] + pv[2 * rep:]
                else:
                    pv = jnp.dot(p, vh.astype(jnp.float32),
                                 preferred_element_type=jnp.float32)
                acc_ref[h] = corr * acc_ref[h] + pv
                for c in after[2 * h + 1]:
                    feed(c)

        jax.lax.fori_loop(0, n, step, None)
        slot_ref[0] = jax.lax.rem(slot0 + n, 2)
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)) \
            .astype(o_ref.dtype)

    row_spec = pl.BlockSpec((None, K, rep, d),
                            lambda b, bt, vl: (b, 0, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[row_spec, pool_spec, pool_spec],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((2, P, K, bs, d), k_pages.dtype),
            pltpu.VMEM((2, P, K, bs, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),         # (k | v, half)
            pltpu.SMEM((1,), jnp.int32),             # half of step 0
            pltpu.VMEM((K, rep, 1), jnp.float32),    # m
            pltpu.VMEM((K, rep, 1), jnp.float32),    # l
            pltpu.VMEM((K, rep, d), jnp.float32)])   # acc
    # the sequences hand the scratch's halves and the copy in flight
    # from one to the next: in order, on one core
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))}
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, rep, d), q.dtype),
        interpret=interpret,
        name="flash_decode_paged",
        **params,
    )(block_tables.astype(jnp.int32).reshape(-1),
      valid_len.astype(jnp.int32), qr, k_pages, v_pages)
    return out.reshape(B, H, d)


def _flash_decode_paged_pallas_q8(q, k8_pages, ks_pages, v8_pages,
                                  vs_pages, block_tables, valid_len,
                                  scale, interpret):
    """Int8 twin of _flash_decode_paged_pallas: data AND per-token
    scale blocks are DMA'd by the same table lookup, the int8 block
    upcasts to fp32 in VMEM, and the scales fold into the score /
    probability rows exactly like _flash_decode_pallas_q8."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, d = q.shape
    K, bs = k8_pages.shape[1], k8_pages.shape[2]
    nb = block_tables.shape[1]
    rep = H // K
    qr = q.reshape(B, K, rep, d)

    def kernel(bt_ref, vl_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
               o_ref, m_ref, l_ref, acc_ref):
        i = pl.program_id(2)
        vl = vl_ref[pl.program_id(0)]

        @pl.when(i == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(i * bs < vl)
        def _block():
            qblk = q_ref[...].astype(jnp.float32) * scale    # (rep, d)
            kblk = k_ref[...].astype(jnp.float32)            # (bs, d)
            vblk = v_ref[...].astype(jnp.float32)
            ksb = ks_ref[...][:, 0]                          # (bs,)
            vsb = vs_ref[...][:, 0]
            s = (qblk @ kblk.T) * ksb[None, :]               # (rep, bs)
            pos = i * bs + jax.lax.broadcasted_iota(
                jnp.int32, (rep, bs), 1)
            s = jnp.where(pos < vl, s, -jnp.inf)
            m_prev = m_ref[...][:, 0]
            l_prev = l_ref[...][:, 0]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[:, None])
            p = jnp.where((m_new > -jnp.inf)[:, None], p, 0.0)
            corr = jnp.where(m_prev > -jnp.inf,
                             jnp.exp(m_prev - m_new), 0.0)
            ps = p * vsb[None, :]                            # v scale
            m_ref[...] = m_new[:, None]
            l_ref[...] = (corr * l_prev + jnp.sum(p, axis=-1))[:, None]
            acc_ref[...] = corr[:, None] * acc_ref[...] + ps @ vblk

        @pl.when(i == nb - 1)
        def _finish():
            l = l_ref[...][:, 0]
            safe_l = jnp.where(l > 0, l, 1.0)
            o_ref[...] = (acc_ref[...] / safe_l[:, None]) \
                .astype(o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid_spec=_paged_grid_spec(pl, pltpu, B, K, nb, rep, bs, d),
        out_shape=jax.ShapeDtypeStruct((B, K, rep, d), q.dtype),
        interpret=interpret,
        name="flash_decode_paged_q8",
        **_paged_compiler_params(pltpu, interpret),
    )(block_tables.astype(jnp.int32), valid_len.astype(jnp.int32),
      qr, k8_pages, ks_pages, v8_pages, vs_pages)
    return out.reshape(B, H, d)


def paged_kernel_mode(pool_operand, quantized=False):
    """Dispatch gate for the in-kernel paged path — None means "use
    the gather fallback". Shared by flash_decode_paged(_quantized) at
    trace time and by the serving layer's host-side probe (the
    `serving_gather_bytes_avoided_total` accounting must agree with
    what the executable actually traced), so it answers from the
    pool's static shape alone.

    Constraints: Mosaic wants the block's sublane dim (block_size) a
    multiple of 8, and the working set must fit the tuned VMEM budget
    (kernels/tuning.py: flash_decode_paged.vmem_budget_bytes): for the
    bf16/fp32 sweep two steps of at least one page of all kv heads
    (_paged_sweep_pages), for the int8 twin its page-a-cell blocks.
    Compiled, the sweep also needs head_dim in whole 128-lane rows:
    Mosaic cannot slice a page out of a pool of narrower ones."""
    N, K, bs, d = pool_operand.shape
    if quantized:
        per_block = bs * d * pool_operand.dtype.itemsize + bs * 4
        # 2 operands (k, v) x 2 pipeline buffers + q block + scratch
        fits = 4 * per_block + 2 * d * 4 + (d + 2) * 4 * 8 \
            <= tuning.get("flash_decode_paged", "vmem_budget_bytes")
    else:
        fits = _paged_sweep_pages(pool_operand.shape,
                                  pool_operand.dtype.itemsize) >= 1
    return kernel_mode("FLASH", pool_operand, ok=bs % 8 == 0 and fits,
                       ok_compiled=quantized or d % 128 == 0)


def paged_gather_bytes(pool_shape, table_shape, itemsize,
                       quantized=False):
    """Bytes ONE flash_decode_paged(_quantized) call's gather fallback
    materializes in HBM (the contiguous (B, K, nb*bs, d) k AND v
    views, plus fp32 per-token scale views when quantized) — i.e. the
    per-layer traffic the in-kernel path avoids every decode tick."""
    N, K, bs, d = pool_shape
    B, nb = table_shape
    per = 2 * B * K * nb * bs * d * itemsize
    if quantized:
        per += 2 * B * K * nb * bs * 4
    return per


def flash_decode_paged(q, k_pages, v_pages, block_tables, valid_len,
                       scale=None, window=None):
    """Block-table decode attention straight off the page pool: the
    in-kernel Pallas path when the gate admits it, else gather the
    contiguous view and run the standard flash sweep. Both paths are
    value-identical at every position < valid_len. `window` (a
    sliding-window layer) attends the last `window` of them only; the
    table's entries before the window may be stale or zero."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])

    def gathered():
        k = gather_kv_pages(k_pages, block_tables)
        v = gather_kv_pages(v_pages, block_tables)
        if window is not None:
            return reference_decode_attention(q, k, v, valid_len, scale,
                                              window)
        return flash_decode(q, k, v, valid_len, scale=scale)

    return _paged_fallback.run(
        paged_kernel_mode(k_pages),
        lambda interpret: _flash_decode_paged_pallas(
            q, k_pages, v_pages, block_tables, valid_len, scale,
            interpret, window),
        gathered)


def flash_decode_paged_quantized(q, k8_pages, ks_pages, v8_pages,
                                 vs_pages, block_tables, valid_len,
                                 scale=None):
    """Paged variant of flash_decode_quantized: int8 data + per-token
    scale blocks, in-kernel when the gate admits, gathered otherwise."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _paged_fallback.run(
        paged_kernel_mode(k8_pages, quantized=True),
        lambda interpret: _flash_decode_paged_pallas_q8(
            q, k8_pages, ks_pages, v8_pages, vs_pages, block_tables,
            valid_len, scale, interpret),
        lambda: flash_decode_quantized(
            q, *(gather_kv_pages(p, block_tables) for p in
                 (k8_pages, ks_pages, v8_pages, vs_pages)),
            valid_len, scale=scale))


# -- a paged pool of latents (MLA) -------------------------------------------
# A LATENT layer (models/decoder.py) caches ONE row a position,
# `[latent | rotated key | 0]`, that all H query heads share: its pool is
# (N, 1, bs, row) and there is no second pool. The absorbed query is as
# wide as the row, the scores read the row whole and the values are its
# first `latent` entries, so a row is fetched from HBM once and used
# twice. H query rows to one cached row is 2 * H * (row + latent) FLOPs
# for 2 * row bytes, ~115 FLOP/byte at 64 heads: the one decode sweep
# here where the MXU's time is not free beside the copies'. Measured
# on a v5e at 596k rows of 640 in 16-row pages (PERF.md section 6,
# PR 42): the arithmetic alone 0.68 ms a call, the copies alone (their
# starts and waits in loops of their own) 1.24, the two in series 1.88;
# with the starts spread between the pieces of the arithmetic the
# copies alone take 1.08 (the DMA engine's pace, 28 ns a 20 KB page)
# and the whole 1.13.

def reference_paged_latent_attention(q, pages, block_tables, valid_len,
                                     latent, scale):
    """jnp twin of the latent sweep: q (B, H, row), pages
    (N, 1, bs, row), -> (B, H, latent). Gathers the contiguous view."""
    rows = gather_kv_pages(pages, block_tables)[:, 0] \
        .astype(jnp.float32)                            # (B, S, row)
    s = jnp.einsum("bhr,bsr->bhs", q.astype(jnp.float32), rows) * scale
    mask = jnp.arange(rows.shape[1])[None, :] < valid_len[:, None]
    p = jax.nn.softmax(jnp.where(mask[:, None, :], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhs,bsl->bhl", p, rows[..., :latent]) \
        .astype(q.dtype)


def _latent_sweep_sizes(bs, nb):
    """(pages a step, keys a sub-chunk) of the latent sweep: the tuned
    counts, a step no longer than a table and in whole 128-lane tiles
    of scores where it can be, a sub-chunk in whole pages."""
    fit = max(1, min(tuning.get("flash_decode_paged_latent", "pages"),
                     nb))
    lane = max(1, 128 // bs)
    pages = int(fit - fit % lane if fit > lane else fit)
    chunk = max(bs, int(tuning.get("flash_decode_paged_latent",
                                   "chunk")) // bs * bs)
    return pages, chunk


def paged_latent_mode(pool_operand, latent):
    """Dispatch gate of the latent sweep, from static shapes alone:
    None (the jnp twin), "interpret" or "compiled". One row a position
    (K == 1), a block of whole sublane tiles; compiled, the row and the
    latent in whole 128-lane tiles (Mosaic slices the values out of the
    row at a tile's edge)."""
    N, K, bs, row = pool_operand.shape
    return kernel_mode(
        "FLASH", pool_operand,
        ok=K == 1 and bs % 8 == 0 and 0 < latent <= row,
        ok_compiled=row % 128 == 0 and latent % 128 == 0)


@functools.partial(jax.jit, static_argnames=(
    "latent", "scale", "pages", "chunk", "interpret"))
def _paged_latent_sweep(q, pool, block_tables, valid_len, *, latent,
                        scale, pages, chunk, interpret):
    """Grid (B,), a cell a sequence; a step is `pages` pages of the ONE
    pool in one half of a VMEM scratch while the copies of the step
    after it in the global order (the sequence's next, or the next
    sequence's first) land in the other. A step's rows feed the MXU
    twice as stored: scores q (H, row) x rows^T, then the probabilities
    x rows[:, :latent]; online softmax in fp32 scratch.

    The schedule (PR 42; measured, docs/serving.md). A step's keys are
    worked in sub-chunks of `chunk` keys, each one update of the carry
    (m, l, acc), and both products of a sub-chunk in PIECES of a
    128-lane tile of scores. After every piece go the next few of the
    next step's page copies, each start under its own predicate (a page
    that holds no token is not fetched): the copies' bookkeeping is
    scalar work between the pieces of one basic block, not a loop in
    front of the arithmetic, and the DMA queue is fed evenly. A
    sub-chunk's pages signal a semaphore of their own and are waited
    for by the bits of their count, a full sub-chunk in ONE wait (the
    semaphore counts bytes), so the arithmetic starts when the first
    sub-chunk has landed. Every step of a sequence, its last too, runs
    the one body: whose copies it starts, how many pages it waits for
    and where its mask falls are scalars.

    Against a bf16 pool the probabilities enter the value product
    rounded to bf16, ONE term and not `_paged_sweep`'s exact three:
    each term more cost the cell 6% of its tokens a second for
    0.0002-0.0004 of mean logit gap (measured, docs/serving.md)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, R = q.shape
    bs = pool.shape[2]
    nb = block_tables.shape[1]
    P, T = pages, pages * bs
    G = max(1, min(chunk // bs, P))                # pages a sub-chunk
    groups = [(a, min(a + G, P)) for a in range(0, P, G)]
    Q = max(1, min(128 // bs, G))                  # pages a piece
    pieces = [list(range(a, z, Q)) for a, z in groups]
    # a sub-chunk's pieces, scores then values, each followed by its
    # share of the next step's P starts
    first_call = [2 * sum(map(len, pieces[:g]))
                  for g in range(len(groups) + 1)]
    n_calls = first_call[-1]
    native = q.dtype == pool.dtype == jnp.bfloat16
    nt = (((1,), (1,)), ((), ()))                  # (h, r) x (t, r)

    def kernel(bt_ref, vl_ref, q_ref, hbm, o_ref, buf, sem, slot_ref,
               m_ref, l_ref, acc_ref):
        b = pl.program_id(0)
        vl = vl_ref[b]

        def held(row, i):
            """Pages of `row`'s step `i` that hold a token."""
            return jnp.minimum((vl_ref[row] + bs - 1) // bs - i * P, P)

        def wait_pages(slot, a, k):
            """ONE wait for `k` pages of the sub-chunk at page `a` of
            half `slot`: the semaphore counts bytes."""
            pltpu.make_async_copy(
                hbm.at[pl.ds(0, k)], buf.at[slot, pl.ds(a, k)],
                sem.at[slot, a // G]).wait()

        def starter(row, i, slot, count):
            """-> feed(c): the c-th of a step's n_calls calls starts its
            share of the pages of `row`'s step `i` into half `slot`,
            those of them under `count`."""
            into, sems = buf.at[slot], sem.at[slot]

            def start(j, at):
                pltpu.make_async_copy(hbm.at[bt_ref[at]], into.at[j],
                                      sems.at[j // G]).start()
            return _dealt_starts(pl, P, n_calls, row * nb + i * P,
                                 B * nb - 1, count, start)

        def mix(p, vals):
            if native:
                return jnp.dot(p.astype(jnp.bfloat16), vals,
                               preferred_element_type=jnp.float32)
            return jnp.dot(p, vals.astype(jnp.float32),
                           preferred_element_type=jnp.float32)

        def sub_chunk(slot, g, carry, left, feed):
            """Sub-chunk `g` of half `slot` into the carry, the keys at
            and past `left` masked. A step's first key is live, so m is
            finite from its first sub-chunk on and exp(-inf - m) is 0:
            no select but the mask's own."""
            a, z = groups[g]
            cuts, c0 = pieces[g], first_call[g]

            def rows(k):
                return buf[slot, k:min(k + Q, z), 0].reshape(-1, R)

            def scores(k):
                if native:
                    return jax.lax.dot_general(
                        q_ref[...], rows(k), nt,
                        preferred_element_type=jnp.float32) * scale
                return jax.lax.dot_general(
                    q_ref[...].astype(jnp.float32) * scale,
                    rows(k).astype(jnp.float32), nt,
                    preferred_element_type=jnp.float32)

            parts = []
            for t, k in enumerate(cuts):
                parts.append(scores(k))
                feed(c0 + t)
            s = jnp.concatenate(parts, axis=1)               # (H, chunk)
            live = a * bs + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1) < left
            s = jnp.where(live, s, -jnp.inf)
            m, l, acc = carry
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l = corr * l + jnp.sum(p, axis=-1, keepdims=True)
            acc = corr * acc
            for t, k in enumerate(cuts):
                vals = rows(k)[:, :latent]
                at = (k - a) * bs
                acc = acc + mix(p[:, at:at + vals.shape[0]], vals)
                feed(c0 + len(cuts) + t)
            return m_new, l, acc

        @pl.when(b == 0)
        def _first():
            # a row no copy ever wrote must be finite under its 0
            buf[...] = jnp.zeros_like(buf)
            slot_ref[0] = 0
            jax.lax.fori_loop(
                0, held(0, 0), lambda j, _: pltpu.make_async_copy(
                    hbm.at[bt_ref[j]], buf.at[0, j],
                    sem.at[0, j // G]).start(), None)

        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # at least one step a sequence: the halves' hand-over never
        # skips a row
        n = jnp.maximum((vl + T - 1) // T, 1)
        slot0 = slot_ref[0]
        nxt = jnp.minimum(b + 1, B - 1)

        def step(i, _):
            """ONE body for every step: what differs (whose copies it
            starts and how many, how many of its own pages it waits
            for, where its mask falls) is scalars."""
            slot = jax.lax.rem(slot0 + i, 2)
            last = i + 1 == n
            row, ahead = jnp.where(last, nxt, b), jnp.where(last, 0, i + 1)
            count = jnp.where(jnp.logical_and(last, b + 1 == B), 0,
                              held(row, ahead))
            feed = starter(row, ahead, 1 - slot, count)
            # valid_len 0: one key of a stale row, and a 0 below
            left = jnp.maximum(vl - i * T, 1)
            carry = m_ref[...], l_ref[...], acc_ref[...]
            for g, (a, z) in enumerate(groups):
                # the sub-chunk's pages that were started, waited for
                # by the bits of their count: a full one in ONE wait
                _wait_by_bits(pl, jnp.clip(held(b, i) - a, 0, z - a), z - a,
                              functools.partial(wait_pages, slot, a))
                carry = sub_chunk(slot, g, carry, left, feed)
            m_ref[...], l_ref[...], acc_ref[...] = carry

        jax.lax.fori_loop(0, n, step, None)
        slot_ref[0] = jax.lax.rem(slot0 + n, 2)
        l = l_ref[...]
        o_ref[...] = jnp.where(
            vl > 0, acc_ref[...] / jnp.where(l > 0, l, 1.0), 0.0) \
            .astype(o_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec((None, H, R), lambda b, bt, vl: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, H, latent),
                               lambda b, bt, vl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, P, 1, bs, R), pool.dtype),
            pltpu.SemaphoreType.DMA((2, len(groups))),  # a sub-chunk each
            pltpu.SMEM((1,), jnp.int32),             # half of step 0
            pltpu.VMEM((H, 1), jnp.float32),         # m
            pltpu.VMEM((H, 1), jnp.float32),         # l
            pltpu.VMEM((H, latent), jnp.float32)])   # acc
    # the sequences hand the scratch's halves and the copies in flight
    # from one to the next: in order, on one core
    params = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))}
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, latent), q.dtype),
        interpret=interpret,
        name="flash_decode_paged_latent",
        **params,
    )(block_tables.astype(jnp.int32).reshape(-1),
      valid_len.astype(jnp.int32), q, pool)


def flash_decode_paged_latent(q, pages, block_tables, valid_len, *,
                              latent, scale):
    """Decode attention straight off a pool of latents: q (B, H, row)
    the absorbed queries, pages (N, 1, bs, row) the cached rows, ->
    (B, H, latent), the probabilities' mix of the rows' first `latent`
    entries. The in-kernel sweep when the gate admits it, else the jnp
    twin on the gathered view (counted at "flash-decode-paged")."""
    def sweep(interpret):
        step, chunk = _latent_sweep_sizes(pages.shape[2],
                                          block_tables.shape[1])
        return _paged_latent_sweep(
            q, pages, block_tables, valid_len, latent=int(latent),
            scale=float(scale), pages=step, chunk=chunk,
            interpret=interpret)

    return _paged_fallback.run(
        paged_latent_mode(pages, latent), sweep,
        lambda: reference_paged_latent_attention(
            q, pages, block_tables, valid_len, latent, scale))


# -- multi-position window attention off the page pool ----------------------
# Chunked prefill and speculative verify both attend a small window of
# W query positions (a prefill chunk, or 1 sampled token + k draft
# candidates) against the SAME paged pool decode reads. Causality
# inside the window never needs a (W, S) causal mask: each query row
# carries its own valid length (global position + 1), so row j simply
# cannot see rows > j — the identical masking contract the single-
# position path uses, lifted to a (B, W) valid-length matrix. That
# keeps the window math elementwise-identical to W independent
# single-position calls, which is what makes speculative greedy decode
# token-identical to the plain tick.

def reference_paged_window_attention(q, k_cache, v_cache, valid_lens,
                                     scale=None):
    """jnp window reference on gathered (B, K, S, d) caches: q is
    (B, W, H, d), valid_lens (B, W) gives EACH query row its own
    attendable length. Same no-repeat GQA einsum as
    reference_decode_attention with a window axis carried through."""
    B, W, H, d = q.shape
    K, S = k_cache.shape[1], k_cache.shape[2]
    rep = H // K
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qr = q.reshape(B, W, K, rep, d).astype(jnp.float32)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    s = jnp.einsum("bwkrd,bksd->bwkrs", qr, kf) * scale
    mask = jnp.arange(S)[None, None, :] < valid_lens[:, :, None]
    s = jnp.where(mask[:, :, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bwkrs,bksd->bwkrd", p, vf)
    return out.reshape(B, W, H, d).astype(q.dtype)


def paged_window_mode(pool_operand, window, quantized=False):
    """Dispatch gate for the in-kernel windowed path. Same constraints
    as paged_kernel_mode with the q/scratch VMEM terms scaled by the
    window width; the int8 window path always takes the gathered
    dequantize reference (in-kernel q8 window is a chip-window
    follow-up), so quantized=True returns None."""
    N, K, bs, d = pool_operand.shape
    per_block = bs * d * pool_operand.dtype.itemsize
    cell_bytes = 4 * per_block \
        + int(window) * (2 * d * 4 + (d + 2) * 4 * 8)
    return kernel_mode(
        "FLASH", pool_operand,
        ok=not quantized and bs % 8 == 0 and cell_bytes
        <= tuning.get("flash_decode_paged", "vmem_budget_bytes"))


def _flash_decode_paged_window_pallas(q, k_pages, v_pages,
                                      block_tables, valid_lens, scale,
                                      interpret):
    """Windowed twin of _flash_decode_paged_pallas: the W window
    positions fold into the rep axis, so one (b, h, i) grid cell
    carries (W*rep, d) query rows through the same per-block DMA sweep
    with per-ROW valid lengths (row w*rep+r masks at valid_lens[b, w])
    instead of one per-sequence scalar. The row lengths ride as a
    (B, R, 1) VMEM operand — SMEM only serves scalar loads, so the
    prefetched scalar is just each sequence's longest row, which is
    all the block-skip predicate needs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, W, H, d = q.shape
    K, bs = k_pages.shape[1], k_pages.shape[2]
    nb = block_tables.shape[1]
    rep = H // K
    R = W * rep
    qr = q.reshape(B, W, K, rep, d).transpose(0, 2, 1, 3, 4) \
        .reshape(B, K, R, d)
    vl = valid_lens.astype(jnp.int32)                    # (B, W)

    def kernel(bt_ref, vmax_ref, q_ref, vlr_ref, k_ref, v_ref, o_ref,
               m_ref, l_ref, acc_ref):
        i = pl.program_id(2)

        @pl.when(i == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(i * bs < vmax_ref[pl.program_id(0)])
        def _block():
            qblk = q_ref[...].astype(jnp.float32) * scale  # (R, d)
            kblk = k_ref[...].astype(jnp.float32)          # (bs, d)
            vblk = v_ref[...].astype(jnp.float32)
            s = qblk @ kblk.T                              # (R, bs)
            pos = i * bs + jax.lax.broadcasted_iota(
                jnp.int32, (R, bs), 1)
            s = jnp.where(pos < vlr_ref[...], s, -jnp.inf)  # (R, 1) rows
            m_prev = m_ref[...][:, 0]
            l_prev = l_ref[...][:, 0]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[:, None])
            p = jnp.where((m_new > -jnp.inf)[:, None], p, 0.0)
            corr = jnp.where(m_prev > -jnp.inf,
                             jnp.exp(m_prev - m_new), 0.0)
            m_ref[...] = m_new[:, None]
            l_ref[...] = (corr * l_prev + jnp.sum(p, axis=-1))[:, None]
            acc_ref[...] = corr[:, None] * acc_ref[...] + p @ vblk

        @pl.when(i == nb - 1)
        def _finish():
            l = l_ref[...][:, 0]
            safe_l = jnp.where(l > 0, l, 1.0)
            o_ref[...] = (acc_ref[...] / safe_l[:, None]) \
                .astype(o_ref.dtype)

    q_spec = pl.BlockSpec((None, None, R, d),
                          lambda b, h, i, bt, vl: (b, h, 0, 0))
    vlr_spec = pl.BlockSpec((None, R, 1),
                            lambda b, h, i, bt, vl: (b, 0, 0))
    pool_spec = pl.BlockSpec((None, None, bs, d),
                             lambda b, h, i, bt, vl: (bt[b, i], h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, nb),
        in_specs=[q_spec, vlr_spec, pool_spec, pool_spec],
        out_specs=pl.BlockSpec((None, None, R, d),
                               lambda b, h, i, bt, vl: (b, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((R, 1), jnp.float32),   # m
                        pltpu.VMEM((R, 1), jnp.float32),   # l
                        pltpu.VMEM((R, d), jnp.float32)])  # acc
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, R, d), q.dtype),
        interpret=interpret,
        name="flash_decode_paged_window",
        **_paged_compiler_params(pltpu, interpret),
    )(block_tables.astype(jnp.int32), jnp.max(vl, axis=1),
      qr, jnp.repeat(vl, rep, axis=1)[:, :, None], k_pages, v_pages)
    return out.reshape(B, K, W, rep, d).transpose(0, 2, 1, 3, 4) \
        .reshape(B, W, H, d)


def flash_decode_paged_window(q, k_pages, v_pages, block_tables,
                              valid_lens, scale=None):
    """W-position window attention straight off the page pool
    (chunked prefill / speculative verify): in-kernel Pallas when the
    gate admits it, else gather the contiguous view and run the window
    reference. Value-identical to W single-position flash_decode_paged
    calls at matching valid lengths."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _paged_fallback.run(
        paged_window_mode(k_pages, q.shape[1]),
        lambda interpret: _flash_decode_paged_window_pallas(
            q, k_pages, v_pages, block_tables, valid_lens, scale,
            interpret),
        lambda: reference_paged_window_attention(
            q, gather_kv_pages(k_pages, block_tables),
            gather_kv_pages(v_pages, block_tables), valid_lens, scale))


def flash_decode_paged_window_quantized(q, k8_pages, ks_pages,
                                        v8_pages, vs_pages,
                                        block_tables, valid_lens,
                                        scale=None):
    """Window attention against the int8 pool: gather + dequantize to
    fp32, then the window reference (paged_window_mode gates the
    in-kernel path off for quantized pools). Cast back to q.dtype so
    the executable's activation dtype matches the unquantized path."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    k8 = gather_kv_pages(k8_pages, block_tables)
    ks = gather_kv_pages(ks_pages, block_tables)
    v8 = gather_kv_pages(v8_pages, block_tables)
    vs = gather_kv_pages(vs_pages, block_tables)
    return reference_paged_window_attention(
        q, dequantize_kv(k8, ks, jnp.float32),
        dequantize_kv(v8, vs, jnp.float32), valid_lens,
        scale).astype(q.dtype)


# -- int8-quantized KV cache ------------------------------------------------
# Decode is HBM-bandwidth-bound (the whole cache streams per token);
# an int8 cache with per-token scales halves that HBM traffic vs bf16
# — that is the win. Inside VMEM the blocks upcast to fp32 for the
# dot (scales fold into the (rep, blk) score/probability matrices, so
# the per-row rescale never touches the (blk, d) axis). Reference
# analogue: the fork's int8 inference identity
# (src/operator/quantization/) applied to the KV cache.

def quantize_kv(k_cache, v_cache):
    """(B, K, S, d) caches -> int8 data + per-token fp32 scales
    (B, K, S, 1). Symmetric abs-max over d."""
    def one(c):
        cf = c.astype(jnp.float32)
        amax = jnp.max(jnp.abs(cf), axis=-1, keepdims=True)
        scale = jnp.maximum(amax, 1e-8) / 127.0
        q8 = jnp.clip(jnp.round(cf / scale), -127, 127).astype(jnp.int8)
        return q8, scale

    k8, ks = one(k_cache)
    v8, vs = one(v_cache)
    return k8, ks, v8, vs


def dequantize_kv(q8, scale, dtype=jnp.bfloat16):
    return (q8.astype(jnp.float32) * scale).astype(dtype)


def _flash_decode_pallas_q8(q, k8, ks, v8, vs, valid_len, scale,
                            interpret, block_s=256):
    """Same sweep as _flash_decode_pallas with int8 cache blocks;
    k scales fold into the score rows (s = (q @ k8^T) * ks^T) and v
    scales into the probability rows (p * vs^T) — both exact."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, d = q.shape
    K, S = k8.shape[1], k8.shape[2]
    rep = H // K
    blk = max(1, min(block_s, S))
    while S % blk:
        blk //= 2
    qr = q.reshape(B, K, rep, d)
    n_s = S // blk

    def kernel(vl_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref, o_ref):
        qblk = q_ref[...].astype(jnp.float32) * scale    # (rep, d)
        vl = vl_ref[pl.program_id(0)]
        m = jnp.full((rep,), -jnp.inf, jnp.float32)
        l = jnp.zeros((rep,), jnp.float32)
        acc = jnp.zeros((rep, d), jnp.float32)

        def body(sj, carry):
            m_, l_, acc_ = carry
            kblk = k_ref[pl.dslice(sj * blk, blk), :] \
                .astype(jnp.float32)                     # (blk, d) i8
            vblk = v_ref[pl.dslice(sj * blk, blk), :] \
                .astype(jnp.float32)
            ksb = ks_ref[pl.dslice(sj * blk, blk), :]    # (blk, 1) f32
            vsb = vs_ref[pl.dslice(sj * blk, blk), :]
            s = (qblk @ kblk.T) * ksb[:, 0][None, :]     # (rep, blk)
            pos = sj * blk + jax.lax.broadcasted_iota(
                jnp.int32, (rep, blk), 1)
            s = jnp.where(pos < vl, s, -jnp.inf)
            m_new = jnp.maximum(m_, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[:, None])
            p = jnp.where((m_new > -jnp.inf)[:, None], p, 0.0)
            corr = jnp.where(m_ > -jnp.inf,
                             jnp.exp(m_ - m_new), 0.0)
            ps = p * vsb[:, 0][None, :]                  # fold v scale
            return (m_new, corr * l_ + jnp.sum(p, axis=-1),
                    corr[:, None] * acc_ + ps @ vblk)

        upper = jnp.minimum(n_s, (vl + blk - 1) // blk)
        m, l, acc = jax.lax.fori_loop(0, upper, body, (m, l, acc))
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[...] = (acc / safe_l[:, None]).astype(o_ref.dtype)

    cache_spec = pl.BlockSpec((None, None, S, d),
                              lambda b, h, vl: (b, h, 0, 0))
    scale_spec = pl.BlockSpec((None, None, S, 1),
                              lambda b, h, vl: (b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K),
        in_specs=[
            pl.BlockSpec((None, None, rep, d),
                         lambda b, h, vl: (b, h, 0, 0)),
            cache_spec, scale_spec, cache_spec, scale_spec,
        ],
        out_specs=pl.BlockSpec((None, None, rep, d),
                               lambda b, h, vl: (b, h, 0, 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, rep, d), q.dtype),
        interpret=interpret,
        name="flash_decode_q8",
    )(valid_len.astype(jnp.int32), qr, k8, ks, v8, vs)
    return out.reshape(B, H, d)


def flash_decode_quantized(q, k8, ks, v8, vs, valid_len, scale=None):
    """Single-position attention against an int8 cache with per-token
    scales (see quantize_kv). Pallas on TPU; dequantize + the
    no-repeat jnp formulation elsewhere."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    # the twin casts to q.dtype so both dispatch paths agree (the
    # Pallas kernel's out_shape is q.dtype; the fp32-dequantized
    # reference would otherwise leak fp32 into the bf16 decode step)
    return _fallback.run(
        _pallas_mode(k8, scale_bytes=4),    # an fp32 scale a token
        lambda interpret: _flash_decode_pallas_q8(
            q, k8, ks, v8, vs, valid_len, scale, interpret),
        lambda: reference_decode_attention(
            q, dequantize_kv(k8, ks, jnp.float32),
            dequantize_kv(v8, vs, jnp.float32), valid_len,
            scale).astype(q.dtype))
