"""Persistent compiled prefill/decode executables.

Before this module, every `generate()` call rebuilt `build_decoder`'s
closures and wrapped them in FRESH `jax.jit` objects — a full retrace
+ XLA compile per call. Here every executable is a `Program`: a
named, compile-counting `jax.jit` wrapper cached on the net object by
its build signature. Callers get back the SAME jit object for the
same signature, so jit's own shape-keyed cache makes repeat calls
genuinely warm, and the counters prove it:

- trace-time side effect counts compiles (the counted body only runs
  when jit misses);
- every call records a hit or a compile (with wall seconds) into
  `tracing.cache_stats()` under the program's name — the serving
  acceptance bar ("exactly one prefill compile + one decode compile
  for a 16-request mixed workload") is asserted against these.

Three program families:

- `decoder_programs(net, max_len, kv_cache_dtype)`: the contiguous
  prefill + single step from models/llama_infer.build_decoder,
  shared by generate(), generate_beam(), and tests.
- `scan_program(net, ..., mode)`: a chunk of decode steps as one
  `lax.scan` with traced per-row sampling params + eos bookkeeping
  (mode "greedy" skips the sampler entirely).
- `paged_programs(net, ...)`: the serving engine's block-table
  prefill (writes straight into the page pool) and continuous-batch
  decode tick (sample + step + page write + per-row PRNG advance in
  ONE executable).

Donation: page pools and caches are donated on every backend (the
caller always threads the returned arrays back), so serving holds one
pool's worth of HBM, not two — and a stale reference to a donated pool
fails under the CPU tests the same way it would on the chip.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .. import goodput as _gp
from .. import tracing

__all__ = ["Program", "decoder_programs", "scan_program",
           "paged_programs", "reset_programs", "program_store"]


class _LowerShim:
    """Duck-typed _CacheEntry so tracing.record_compile can dump HLO
    (MXNET_TPU_DUMP_HLO) for serving programs too."""

    def __init__(self, jit_fn, avals):
        self.jit_fn = jit_fn
        self._example_avals = avals


class Program:
    """One named persistent executable with honest compile/hit
    accounting into tracing.cache_stats() (and, through it, the
    telemetry compile counters)."""

    def __init__(self, name, fn, donate_argnums=(), compiler_options=None):
        self.name = name
        self.compiles = 0
        self.calls = 0

        def counted(*args):
            # executes at TRACE time only — jit cache hits never
            # re-enter the Python body
            self.compiles += 1
            return fn(*args)

        # the executable's name in a profiler trace: jit_counted_<name>
        counted.__name__ = counted.__qualname__ = "counted_" + name
        # a description's options are the TPU compiler's: no other
        # backend knows them
        options = {"compiler_options": dict(compiler_options)} \
            if compiler_options and jax.default_backend() == "tpu" else {}
        self._jit = jax.jit(counted, donate_argnums=donate_argnums,
                            **options)

    def __call__(self, *args):
        self.calls += 1
        before = self.compiles
        t0 = time.perf_counter()
        out = self._jit(*args)
        if self.compiles > before:
            avals = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                               jnp.result_type(x)),
                args)
            tracing.record_compile(self.name,
                                   _LowerShim(self._jit, avals))
            tracing.record_compile_seconds(
                self.name, time.perf_counter() - t0)
            if _gp._ENABLED:
                # per-executable HBM watermark off the fresh compile
                # (goodput is opt-in, so the AOT re-lower is off the
                # default path entirely)
                _gp.note_hbm_watermark(self.name, self._jit, avals)
        else:
            tracing.record_hit(self.name)
        return out


# -- per-net program store --------------------------------------------------

def program_store(net) -> dict:
    """The net's signature-keyed program cache (created on demand).
    Lives on the net object so it dies with it — no global registry
    pinning model weights."""
    st = getattr(net, "_serving_programs", None)
    if st is None:
        st = {}
        object.__setattr__(net, "_serving_programs", st)
    return st


def reset_programs(net):
    """Drop every cached program for `net` (tests / reconfiguration)."""
    program_store(net).clear()


def decoder_programs(net, max_len: int, kv_cache_dtype: str = "model"):
    """Contiguous-cache prefill + step as cached Programs. The
    returned dict also exposes the raw (untraced) step for scan
    builders."""
    st = program_store(net)
    key = ("decoder", max_len, kv_cache_dtype)
    ent = st.get(key)
    if ent is None:
        from ..models.llama_infer import build_decoder
        _, prefill, step = build_decoder(net, max_len,
                                         kv_cache_dtype=kv_cache_dtype)
        ent = {"prefill": Program("gen_prefill", prefill),
               "step": Program("gen_step", step),
               "raw_step": step}
        st[key] = ent
    return ent


def _make_scan(step, mode: str):
    """A chunk of decode steps as one scanned executable.

    Carry: (cache, logits, pos, finished). Per step: sample from the
    incoming logits (per-row traced params), freeze finished rows to
    eos, run the cached decode step, note fresh eos hits. `eos` is a
    traced scalar (-1 = disabled), so eos and non-eos calls share one
    executable."""
    from .sampling import sample_tokens

    def scan_chunk(params, cache, logits, pos, finished, eos, temps,
                   top_ks, top_p, keys):
        def body(carry, key_t):
            cache, logits, pos, finished = carry
            if mode == "sample":
                row_keys = jax.random.split(key_t, logits.shape[0])
                tok = sample_tokens(logits, row_keys, temps, top_ks,
                                    top_p)
            else:
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # finished rows keep emitting eos (and keep stepping —
            # rows are independent, their cache writes are inert)
            tok = jnp.where(finished, jnp.maximum(eos, 0), tok)
            finished = finished | ((eos >= 0) & (tok == eos))
            cache, logits = step(params, cache, pos, tok)
            return (cache, logits, pos + 1, finished), tok

        (cache, logits, pos, finished), toks = lax.scan(
            body, (cache, logits, pos, finished), keys)
        return cache, logits, pos, finished, toks

    return scan_chunk


def scan_program(net, max_len: int, kv_cache_dtype: str, mode: str):
    """Cached scan-chunk Program. mode: 'greedy' | 'sample'."""
    assert mode in ("greedy", "sample"), mode
    st = program_store(net)
    key = ("scan", max_len, kv_cache_dtype, mode)
    prog = st.get(key)
    if prog is None:
        step = decoder_programs(net, max_len, kv_cache_dtype)["raw_step"]
        prog = Program(f"gen_scan_{mode}", _make_scan(step, mode),
                       donate_argnums=(1,))
        st[key] = prog
    return prog


# -- paged serving programs -------------------------------------------------

def _quant_rows(rows):
    """Per-token symmetric int8 over the trailing dim — EXACTLY
    quantize_kv's math (kernels/flash_decode.py) so paged int8 serving
    is token-identical to the contiguous int8 generate() path.
    rows (..., d) -> (int8 rows, f32 scales (..., 1))."""
    rf = rows.astype(jnp.float32)
    amax = jnp.max(jnp.abs(rf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q8 = jnp.clip(jnp.round(rf / scale), -127, 127).astype(jnp.int8)
    return q8, scale


def _put_rows(pool, blk_ids, offs, rows):
    """rows (T, K, d) to `pool[blk_ids, :, offs, :]` of a pool
    (N, K, bs, d), written on the pool's (N, K*bs, d) view. A scatter
    over dims 0 and 2 of the pool itself makes XLA:TPU re-lay the
    WHOLE pool out with those dims major and back again, twice a pool
    a call (docs/serving.md); on the view the scattered dims are the
    major ones of the row-major pool as it stands, the reshapes are
    bitcasts and the rows land in place."""
    N, K, bs, d = pool.shape
    at = jnp.arange(K) * bs + offs[:, None]                  # (T, K)
    return pool.reshape(N, K * bs, d) \
        .at[blk_ids[:, None], at].set(rows).reshape(pool.shape)


def write_rows(pg, blk_ids, offs, k_rows, v_rows):
    """Scatter per-token rows into a layer's pools. blk_ids/offs (T,),
    rows (T, K, d); a pool with scales ("ks", "vs") stores int8. The
    scale pools (N, K, bs, 1) keep the indexed write: advanced indices
    around the K slice put the token axis first, so the value shape
    (T, K, 1) matches the scales. A LATENT layer has the one pool,
    "k", of the rows its heads share, and hands None as `v_rows`."""
    if v_rows is None:
        return {"k": _put_rows(pg["k"], blk_ids, offs, k_rows)}
    if "ks" in pg:
        k8, ks = _quant_rows(k_rows)
        v8, vs = _quant_rows(v_rows)
        return {"k": _put_rows(pg["k"], blk_ids, offs, k8),
                "ks": pg["ks"].at[blk_ids, :, offs, :].set(ks),
                "v": _put_rows(pg["v"], blk_ids, offs, v8),
                "vs": pg["vs"].at[blk_ids, :, offs, :].set(vs)}
    return {"k": _put_rows(pg["k"], blk_ids, offs, k_rows),
            "v": _put_rows(pg["v"], blk_ids, offs, v_rows)}


def paged_programs(net, *, batch_slots: int, max_blocks_per_seq: int,
                   block_size: int, max_prompt_len: int,
                   kv_cache_dtype: str = "model",
                   prefill_chunk: int = 0, spec_k: int = 0,
                   lora=None):
    """Serving executables over a paged pool:

    prefill(params, pages, bt_row, ids, valid_len, shared_len[, slot])
        -> (pages, last_logits):  ONE request (batch 1, right-padded
        to max_prompt_len) through the training-identical layer math,
        k/v written straight into its allocated blocks (padding tokens
        route to the scratch block). Positions below `shared_len` (a
        traced (1,) int32 — prefix-cache hits share ONE compiled
        prefill with cold prompts) also sink to scratch: their cache
        content is already resident in adopted shared blocks. A net
        with RECURRENT layers (models/decoder.py) takes one operand
        more, `slot` (1,) int32: such a layer's entry of `pages` is a
        state pool {name: (batch_slots, ...)} and the prefill writes
        row `slot` of it whole, with the state as it stands after
        `valid_len` positions; the layer is handed the positions as an
        attention layer is (a retention layer rotates by them, a
        state-space layer ignores them). A net ALL of whose layers are
        recurrent has no block pool: `bt_row` here and `block_tables`
        in `decode` are `()`.

    copy_block(pages, src, dst) -> pages: device-side block copy for
        prefix-cache copy-on-write (src/dst traced scalars, so every
        CoW shares one executable).

    spill_block(pages, src) -> {field: (L, K, bs, ·)} /
    restore_block(pages, payload, dst) -> pages: the KV tier
        hierarchy's device↔host block movers (serving/kv_tier.py).
        Same traced-index discipline as copy_block — one executable
        each, regardless of which block spills or restores.

    decode(params, pages, block_tables, pos, last_logits, keys,
           temps, top_ks, top_ps, active)
        -> (pages, tok, logits, keys): one continuous-batching tick —
        per-row sampling of the PREVIOUS logits, one decode step for
        all batch slots, paged cache write, per-row PRNG advance.
        Inactive slots compute against the scratch block and their
        outputs are discarded by the scheduler. A LATENT layer's entry
        of `pages` is the one pool {"k": (N, 1, bs, row)} of the rows
        its heads share; the tick writes the new row in place and the
        latent sweep reads keys and values off it. A RECURRENT layer has
        no scratch: its step is masked by `active`, and an inactive
        row's state comes back as it went in.

    prefill_chunk(params, pages, bt_row, ids, chunk_start, chunk_len)
        -> (pages, last_logits)  [when prefill_chunk > 0]: ONE
        token-budgeted slice of a prefill. `ids` is (1, C) with C the
        STATIC chunk width; `(chunk_start, chunk_len)` are traced (1,)
        int32 — every chunk of every prompt shares one executable.
        Window rows attend the page pool (earlier chunks + adopted
        shared-prefix blocks are already resident) with per-row valid
        lengths, so causality needs no (C, C) mask; k/v land in the
        pool before the window reads it. The returned last-position
        logits only matter on the final chunk.

    verify(params, pages, block_tables, pos, last_logits, keys,
           temps, top_ks, top_ps, active, draft, draft_len)
        -> (pages, window_tokens, n_accepted, logits, keys)
        [when spec_k > 0]: a speculative decode tick. Samples token 0
        from the previous logits EXACTLY like decode (same PRNG
        split), then scores the k draft candidates at the following
        positions in the SAME dispatch; the accept mask (greedy
        longest-prefix match, gated on traced temps <= 0 and
        per-row draft_len) is traced, so every accept length shares
        this one executable. Rows with draft_len == 0 compute the
        decode tick bit-for-bit (token 0 + position-0 write +
        logits[:, 0]); the scheduler discards rejected-suffix writes
        by not advancing pos (stale rows are masked by valid lengths
        and overwritten later).

    ``lora`` (an AdapterPool ``signature()`` tuple — capacity, rank,
    targets — or None) appends two traced operands to prefill /
    prefill_chunk (``adapters, aid (1,)``) and decode / verify
    (``adapters, aids (B,)``): the stacked per-layer factor tables and
    the per-row table indices. The factors are GATHERED inside the
    executable and applied as low-rank residuals on the target
    matmuls, so every adapter mix, hot-load, and eviction shares the
    same compiled program — only the table SHAPE (the signature) is
    static. Index 0 is the identity adapter (exact +0.0).
    """
    st = program_store(net)
    key = ("paged", batch_slots, max_blocks_per_seq, block_size,
           max_prompt_len, kv_cache_dtype, prefill_chunk, spec_k,
           lora)
    ent = st.get(key)
    if ent is not None:
        return ent

    from ..models.decoder import LATENT, RECURRENT, SLIDING
    from ..models.llama_math import final_logits, rms
    from ..kernels.flash_decode import (
        flash_decode_paged, flash_decode_paged_latent,
        flash_decode_paged_quantized, flash_decode_paged_window,
        flash_decode_paged_window_quantized)
    from .sampling import sample_tokens

    # the net's own description of its decoder (models/decoder.py):
    # layer kinds, the layer's functions, the counts it hands back
    dec = net.decoder()
    cfg = dec.cfg
    K, d = cfg.num_kv_heads, cfg.head_dim
    q8 = kv_cache_dtype == "int8"
    # a LATENT layer's paged call takes the values' width and the scale
    latent = dec.latent_shapes() if dec.latent else None
    for feature, wanted in (("int8", q8), ("lora", lora),
                            ("prefill_chunk", prefill_chunk),
                            ("speculative", spec_k)):
        if wanted:
            dec.require(feature, f"paged_programs({feature})")

    def kind_of(tables, li):
        """A layer's block table(s): with two kinds of layer the
        programs take the pair (full, sliding), else the one array."""
        if not dec.mixed:
            return tables
        return tables[dec.layer_kinds[li] == SLIDING]

    def add_counts(total, counts):
        if counts is None:
            return total
        return counts if total is None else total + counts

    bs = block_size
    nb = max_blocks_per_seq

    def window_attention(q, npg, block_tables, vl):
        """(B, W) window rows against the pool with per-row valid
        lengths — the attention core shared by prefill_chunk and
        verify."""
        if q8:
            return flash_decode_paged_window_quantized(
                q, npg["k"], npg["ks"], npg["v"], npg["vs"],
                block_tables, vl)
        return flash_decode_paged_window(q, npg["k"], npg["v"],
                                         block_tables, vl)

    n_layers = cfg.num_layers

    def gather_lora(lo):
        """Per-layer, per-target gather of each row's (A, B) factors
        from the stacked adapter tables. `lo` is the optional trailing
        (adapters, aids) operand pair — aids is a traced int32 row
        vector, so every adapter mix shares the executable. Returns a
        per-layer list of llama_math `lora` dicts (all None when LoRA
        is off: the traced graph is then IDENTICAL to a LoRA-less
        build)."""
        if not lo:
            return [None] * n_layers
        adapters, aids = lo
        return [{t: (tab["a"][aids], tab["b"][aids])
                 for t, tab in layer.items()} for layer in adapters]

    def write_state(pg, state, slot):
        """Row `slot` of a recurrent layer's state pool, whole."""
        return {name: lax.dynamic_update_slice_in_dim(
            pg[name], state[name].astype(pg[name].dtype), slot, axis=0)
            for name in pg}

    def prefill(params, pages, bt_row, ids, valid_len, shared_len,
                *lo):
        B, T = ids.shape                       # B == 1
        if dec.recurrent:
            slot, lo = lo[0][0], lo[1:]
        la = gather_lora(lo)
        x = dec.embed(params, ids)
        positions = jnp.arange(T)
        t = jnp.arange(T)
        # padding tokens (t >= valid) AND already-cached shared-prefix
        # tokens (t < shared) sink into scratch block 0; the forward
        # still runs over the whole prompt (causal attention is
        # self-contained), only the cache writes are masked. A sliding
        # layer's table reads 0 before the window: those rows sink too
        keep = (t >= shared_len[0]) & (t < valid_len[0])
        offs = t % bs
        new_pages = []
        counts = None
        for li, (lp, pg) in enumerate(zip(params["layers"], pages)):
            if dec.layer_kinds[li] == RECURRENT:
                x, state, c = dec.prefill_recurrent(
                    li, lp, x, positions, valid_len)
                counts = add_counts(counts, c)
                new_pages.append(write_state(pg, state, slot))
                continue
            x, k, v, c = dec.prefill_layer(li, lp, x, positions,
                                           valid_len, lora=la[li])
            counts = add_counts(counts, c)
            blk = jnp.where(keep, kind_of(bt_row, li)[t // bs], 0)
            new_pages.append(write_rows(pg, blk, offs, k[0],
                                        None if v is None else v[0]))
        x = rms(x, params["norm"], cfg.rms_eps)
        idx = jnp.maximum(valid_len - 1, 0)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        if dec.counts:
            return new_pages, last @ params["head"].T, counts
        return new_pages, last @ params["head"].T

    def decode(params, pages, block_tables, pos, last_logits, keys,
               temps, top_ks, top_ps, active, *lo):
        la = gather_lora(lo)
        split = jax.vmap(partial(jax.random.split, num=2))(keys)
        keys_sample, keys_next = split[:, 0], split[:, 1]
        tok = sample_tokens(last_logits, keys_sample, temps, top_ks,
                            top_ps)
        rows = jnp.arange(batch_slots)
        offs = jnp.where(active, pos % bs, 0)
        vl = jnp.where(active, pos + 1, 1)
        x = dec.embed(params, tok)[:, None, :]
        new_pages = []
        counts = None
        for li, (lp, pg) in enumerate(zip(params["layers"], pages)):
            if dec.layer_kinds[li] == RECURRENT:
                x, npg, c = dec.decode_recurrent(
                    li, lp, x, pos, pg, active)
                counts = add_counts(counts, c)
                new_pages.append(npg)
                continue
            q, k, v, carry = dec.layer_qkv(li, lp, x, pos[:, None],
                                           lora=la[li])
            bt = kind_of(block_tables, li)
            blk = jnp.where(active, bt[rows, pos // bs], 0)
            npg = write_rows(pg, blk, offs, k[:, 0],
                             None if v is None else v[:, 0])
            if dec.layer_kinds[li] == LATENT:
                att = flash_decode_paged_latent(
                    q[:, 0], npg["k"], bt, vl, latent=latent["latent"],
                    scale=latent["scale"])[:, None]
            elif q8:
                att = flash_decode_paged_quantized(
                    q[:, 0], npg["k"], npg["ks"], npg["v"], npg["vs"],
                    bt, vl)[:, None]
            else:
                att = flash_decode_paged(
                    q[:, 0], npg["k"], npg["v"], bt, vl,
                    window=dec.layer_window(li))[:, None]
            x, c = dec.layer_finish(li, lp, x, att, carry,
                                    lora=la[li], valid=active[:, None])
            counts = add_counts(counts, c)
            new_pages.append(npg)
        logits = final_logits(params, x, cfg.rms_eps)[:, 0]
        if dec.counts:
            # a few int32 the scheduler reads at its one sync a tick
            return new_pages, tok, logits, keys_next, counts
        return new_pages, tok, logits, keys_next

    def make_prefill_chunk(C):
        def prefill_chunk_fn(params, pages, bt_row, ids, chunk_start,
                             chunk_len, *lo):
            la = gather_lora(lo)
            t = jnp.arange(C)
            gpos = chunk_start[0] + t                    # global pos
            valid = t < chunk_len[0]
            # rows past the chunk (and their out-of-range gpos) sink
            # into scratch block 0, like prefill's padding rows
            blk = jnp.where(valid,
                            bt_row[jnp.clip(gpos // bs, 0, nb - 1)], 0)
            offs = jnp.where(valid, gpos % bs, 0)
            vl = jnp.where(valid, gpos + 1, 1)[None, :]  # (1, C)
            x = dec.embed(params, ids)
            positions = gpos[None, :]
            bt2 = bt_row[None, :]
            new_pages = []
            for li, (lp, pg) in enumerate(zip(params["layers"],
                                              pages)):
                qh, k, v, carry = dec.layer_qkv(li, lp, x, positions,
                                                lora=la[li])
                npg = write_rows(pg, blk, offs, k[0], v[0])
                att = window_attention(qh, npg, bt2, vl)
                x, _ = dec.layer_finish(li, lp, x, att, carry,
                                        lora=la[li])
                new_pages.append(npg)
            x = rms(x, params["norm"], cfg.rms_eps)
            idx = jnp.maximum(chunk_len - 1, 0)
            last = jnp.take_along_axis(x, idx[:, None, None],
                                       axis=1)[:, 0]
            return new_pages, last @ params["head"].T

        return prefill_chunk_fn

    def make_verify(W):
        def verify(params, pages, block_tables, pos, last_logits,
                   keys, temps, top_ks, top_ps, active, draft,
                   draft_len, *lo):
            la = gather_lora(lo)
            # token 0: the SAME split + sample as decode, so sampled
            # rows' PRNG streams are tick-for-tick identical
            split = jax.vmap(partial(jax.random.split, num=2))(keys)
            keys_sample, keys_next = split[:, 0], split[:, 1]
            t0 = sample_tokens(last_logits, keys_sample, temps,
                               top_ks, top_ps)
            w = jnp.concatenate([t0[:, None], draft], axis=1)
            rows = jnp.arange(batch_slots)
            j = jnp.arange(W)
            P = pos[:, None] + j[None, :]                  # (B, W)
            valid = active[:, None] & (j[None, :]
                                       <= draft_len[:, None])
            blk = jnp.where(
                valid,
                block_tables[rows[:, None],
                             jnp.clip(P // bs, 0, nb - 1)], 0)
            offs = jnp.where(valid, P % bs, 0)
            vl = jnp.where(valid, P + 1, 1)                # (B, W)
            x = dec.embed(params, w)                       # (B, W, D)
            fb, fo = blk.reshape(-1), offs.reshape(-1)
            new_pages = []
            for li, (lp, pg) in enumerate(zip(params["layers"],
                                              pages)):
                qh, k, v, carry = dec.layer_qkv(li, lp, x, P,
                                                lora=la[li])
                npg = write_rows(pg, fb, fo, k.reshape(-1, K, d),
                                 v.reshape(-1, K, d))
                att = window_attention(qh, npg, block_tables, vl)
                x, _ = dec.layer_finish(li, lp, x, att, carry,
                                        lora=la[li])
                new_pages.append(npg)
            logits = final_logits(params, x, cfg.rms_eps)
            # greedy accept: candidate j survives iff every candidate
            # <= j matched the model's argmax at its position
            pred = jnp.argmax(logits[:, :-1, :], axis=-1) \
                .astype(jnp.int32)
            spec_ok = active & (temps <= 0.0)
            match = (pred == draft) \
                & (j[1:][None, :] <= draft_len[:, None]) \
                & spec_ok[:, None]
            acc = jnp.cumprod(match.astype(jnp.int32), axis=1)
            n_acc = jnp.sum(acc, axis=1).astype(jnp.int32)
            new_last = jnp.take_along_axis(
                logits, n_acc[:, None, None], axis=1)[:, 0]
            return new_pages, w, n_acc, new_last, keys_next

        return verify

    def copy_block(pages, src, dst):
        # dynamic-index gather + scatter: src/dst are traced scalars,
        # so every copy-on-write rides one executable
        return [{f: a.at[dst].set(a[src]) for f, a in pg.items()}
                for pg in pages]

    def spill_block(pages, src):
        # gather ONE block across every layer into a host-transfer
        # bundle {field: (L, K, bs, ·)}; src is a traced scalar, so
        # every spill rides one executable (copy_block discipline).
        # Pages are NOT donated: the spill is a read-only snapshot.
        return {f: jnp.stack([pg[f][src] for pg in pages])
                for f in pages[0]}

    def restore_block(pages, payload, dst):
        # inverse scatter of a spill_block bundle into block `dst` of
        # every layer; dst traced, payload shape fixed at (L, ...) —
        # zero per-shape recompiles
        return [{f: a.at[dst].set(payload[f][layer])
                 for f, a in pg.items()}
                for layer, pg in enumerate(pages)]

    ent = {"prefill": Program("serving_prefill", prefill,
                              donate_argnums=(1,)),
           "decode": Program(
               "serving_decode", decode, donate_argnums=(1,),
               compiler_options=dec.decode_compiler_options),
           "copy_block": Program("serving_copy_block", copy_block,
                                 donate_argnums=(0,)),
           "spill_block": Program("serving_spill_block", spill_block),
           "restore_block": Program("serving_restore_block",
                                    restore_block,
                                    donate_argnums=(0,))}
    if prefill_chunk:
        ent["prefill_chunk"] = Program(
            "serving_prefill_chunk", make_prefill_chunk(prefill_chunk),
            donate_argnums=(1,))
    if spec_k:
        ent["verify"] = Program("serving_verify", make_verify(spec_k + 1),
                                donate_argnums=(1,))
    st[key] = ent
    return ent
