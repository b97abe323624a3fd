"""Tuned kernel constants (reference analogue: the fork's per-arch
kernel tuning — cuDNN autotune / MSHADOW tuning env knobs).

Every perf-sensitive Pallas constant (flash-attention block sizes,
norm/CE row-block targets, the flash-decode VMEM gate) resolves through
`get(family, key)` so a measured sweep can re-tune them WITHOUT code
edits: `benchmarks/autotune_kernels.py` sweeps the space on whatever
backend is available and (with --write) commits the winners to
`tuned.json` next to this file, keyed by platform. Lookup order:

    tuned.json[platform][family][key]   (platform = jax.default_backend())
    tuned.json["any"][family][key]
    DEFAULTS[family][key]

The committed defaults below are hand-chosen values: they compile and
give right answers on a v5e (PR 21). Timed there so far: the paged
decode's VMEM budget (PR 25); PERF.md tracks the rest.
"""
from __future__ import annotations

import json
import os
from typing import Optional

__all__ = ["get", "DEFAULTS", "tuned_path", "reload", "set_runtime",
           "clear_runtime"]

#: hand-chosen starting points (see each kernel module for the
#: constraint story: Mosaic (8, 128) tiling, ~16 MiB VMEM/core)
DEFAULTS = {
    "flash_attention": {"block_q": 256, "block_k": 256},
    "fused_norm": {"row_block_want": 512,
                   "vmem_budget_bytes": 8 << 20},
    # min_vocab: below it the jnp path's extra round trips over the
    # logits cost less than the kernel's launch (hand-chosen)
    "fused_ce": {"row_block_want": 256, "min_vocab": 1024},
    "flash_decode": {"vmem_cache_budget_bytes": 10 << 20},
    # in-kernel paged decode: what one step of the sweep may hold in
    # VMEM — two steps of P pages of k and of v (a page is every kv
    # head's (bs, d) block: 32 KB at 8 x 16 x 128 bf16, so P = 56 and
    # 3.5 MB of pool a step), q / o / scratch beside them; the kernel
    # takes as many pages as fit (measured on a v5e, PERF.md PR 25) —
    # and the pool block size the serving cache should prefer so
    # blocks land on Mosaic's (8, 128) tiling
    "flash_decode_paged": {"vmem_budget_bytes": 8 << 20,
                           "preferred_block_size": 16},
    # the latent (MLA) sweep: pages a step of ONE pool (a page is a
    # (bs, row) block: 20 KB at 16 x 640 bf16, two steps of 128 in
    # VMEM are 5.2 MB) and keys a sub-chunk, one update of the online
    # softmax's carry (timed on a v5e, tuned.json's note)
    "flash_decode_paged_latent": {"pages": 128, "chunk": 1024},
    # the expert layer's grouped matmul: a (block_k, block_n) tile of
    # an expert's matrix a step (1 MB in bf16, two of them when gate
    # and up share a pass), and how many tokens of a long prefill the
    # layer routes at a time (its row buffers are sized for the worst
    # case, every pair on a held expert). Hand-chosen. A layer that
    # lays out `large_rows` pairs or more (a training chunk) takes row
    # tiles of `block_m_large`, so that an expert's matrices are read
    # once for 512 rows (at 128 the product is bound by reading them:
    # 128 FLOPs a byte under a ridge of 240), and with them blocks of
    # up to 1024 either way (the backward's products contract over an
    # expert's own width, 896 at Mellum's: one block). A chunk that a
    # training step rebuilds in its backward is sized by its rows as
    # the row tile is: `chunk_rows_remat` pairs (8,192 tokens at top-8;
    # twice that is no faster on a v5e and asks for 0.8 GB more)
    "moe_grouped_matmul": {"block_k": 512, "block_n": 1024,
                           "chunk_tokens": 2048,
                           "large_rows": 32768, "block_m_large": 512,
                           "block_large": 1024,
                           "chunk_rows_remat": 65536},
    # the state-space kernels: positions a grid step of the prompt scan
    # holds in VMEM (x, dt and y blocks of 8 x 128 channels, fp32,
    # double-buffered: 6 MB at 256; hand-chosen), and rows of the state
    # and tail pools a grid step of the one-call decode step moves in
    # and out (10.5 MB of state each way at 32 rows of 16 x 5120 fp32;
    # swept on a v5e, flat from 8 to 32: tuned.json's note).
    "selective_scan": {"time_chunk": 256},
    "ssm_state_update": {"rows": 32},
    # the power-retention prompt form: positions a grid step works (its
    # five (heads x chunk, 128) float32 scratches are 6.5 MB at 512
    # beside the head's 4.3 MB state). Swept on a v5e (tuned.json's
    # note; the decode step has no constant: a grid step moves a kv
    # head's whole state, every smaller block was slower there)
    "power_retention_chunked": {"chunk": 512},
}

_cache: Optional[dict] = None

#: in-process overrides, highest priority — the autotune harness sets
#: these while sweeping candidate values (no file writes mid-sweep)
_runtime: dict = {}


def set_runtime(family: str, key: str, value) -> None:
    _runtime[(family, key)] = value


def clear_runtime() -> None:
    _runtime.clear()


def tuned_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tuned.json")


def _table() -> dict:
    global _cache
    if _cache is None:
        try:
            with open(tuned_path()) as f:
                _cache = json.load(f)
        except (OSError, ValueError):
            _cache = {}
    return _cache


def reload() -> None:
    """Drop the cached tuned.json (tests; post-autotune refresh)."""
    global _cache
    _cache = None


def get(family: str, key: str, platform: Optional[str] = None):
    """Tuned value for `family.key` on `platform` (default: current
    jax backend), falling back to the "any" section, then DEFAULTS."""
    if (family, key) in _runtime:
        return _runtime[(family, key)]
    tab = _table()
    if platform is None:    # kernels ask at trace time: a backend exists
        import jax

        platform = jax.default_backend()
    for section in (platform, "any"):
        try:
            return tab[section][family][key]
        except (KeyError, TypeError):
            pass
    return DEFAULTS[family][key]
