"""Per-row token sampling as TRACED arrays, not Python constants.

`generate()`'s old `pick()` baked temperature/top_k/top_p into the
trace, so every sampling config was a fresh executable. Here the
knobs ride in as (B,) vectors, so ONE compiled step serves any mix of
per-request sampling params — the requirement for continuous batching,
where a greedy request and a top-p request share the same decode tick.

Semantics (per row, matching the old pick() pipeline exactly):
  temperature <= 0  -> greedy argmax (the sampled branch is computed
                       and discarded — where() keeps shapes static)
  top_k > 0         -> keep the k best logits
  0 < top_p < 1     -> nucleus: keep the smallest descending-prob
                       prefix whose mass reaches p (top token always
                       survives); composes after top_k

How the two thresholds are found. Each knob needs ONE number a row:
the k-th largest logit, and the smallest logit of the nucleus. Both
are the largest threshold `c` for which a predicate that only falls
as `c` rises still holds — "at least k logits are >= c", "the logits
>= c hold mass >= p" — so neither needs the row sorted (until PR 40
the row was sorted twice a tick: 61% of a 256 x 65,536 tick). A
float32 maps onto an unsigned key of the same order (`_ordered_key`),
and `_largest_key_that` settles a key's 32 bits two at a time, high
bits first: a round compares the row with the 3 keys that extend the
settled prefix by one digit, each in a reduction over the vocabulary,
and the number of keys that still hold IS the digit. Sixteen rounds a
knob, whatever the shape; the threshold that comes out is a logit of
the row, bit for bit the one a sort would have picked, and the masks
stay the float comparisons `lg < kth`, `lg < thresh`. So (each held
by `tests/test_sampling.py` against the sorted sampler kept there):
  ties at the k-th value or at the nucleus' edge are all kept
                       (`test_tied_logits_*`)
  k >= V keeps all, k <= 0 is off; p outside (0, 1) is off
                       (`test_the_knobs_edges`)
  -inf entries, -0.0 beside +0.0 (the keys tell them apart, the float
  masks do not), bfloat16 logits, a one-hot row
                       (`test_rows_a_sort_orders_specially`)
  a nucleus whose prefix mass is within rounding of p may differ from
  the sort's by that one value: the masses are added in another order
                       (`test_a_float64_sort_keeps_the_same_set`)
A row whose whole mass rounds below p (p = 1 - 6e-8) finds no
threshold: the search ends on key 0, a NaN no logit is less than, so
all of the row is kept, as the sort kept it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["sample_tokens"]

# 3 candidates a round, 16 rounds a threshold. A round's own work costs
# the chip's vector unit about what one more candidate does, so the
# work goes as 2**bits / bits: 2 is the least with half the rounds of
# 1. Measured whole at 256 x 65,536: 3.1 ms at 2 bits, 3.7 at 4 (the
# sorted sampler: 47.2); at 20-48 rows both read 0.23-0.28 ms
# (PERF.md, PR 40).
_DIGIT_BITS = 2


def _ordered_key(x):
    """float32 -> uint32 with the floats' order: the bits with the
    sign flipped, and all of them for a negative (-0.0 just under
    +0.0, -inf lowest of the numbers)."""
    b = lax.bitcast_convert_type(x, jnp.int32)
    flip = (b >> 31) | jnp.int32(-0x80000000)
    return lax.bitcast_convert_type(b ^ flip, jnp.uint32)


def _key_to_float(key):
    """`_ordered_key`'s inverse."""
    u = lax.bitcast_convert_type(key, jnp.int32)
    flip = ~(u >> 31) | jnp.int32(-0x80000000)
    return lax.bitcast_convert_type(u ^ flip, jnp.float32)


def _largest_key_that(keys, holds):
    """Per row of `keys` (B, V) uint32, the largest uint32 `c` for
    which `holds(keys >= c)` is true, where `holds` maps a (B, V) mask
    to (B,) and can only turn false as `c` rises. Radix descent: with
    the bits above `shift` settled in `prefix`, `keys >= prefix |
    d << shift` reads `above >= d`, where `above` is how far a key's
    high bits lie over the prefix's (0 at or under it) — written so,
    a round takes ONE (B,) operand and compares the row with
    constants. A round's reductions share their operands: XLA makes
    them ONE multi-output fusion that reads the row once (and
    whatever made `keys` is recomputed inside it, so no (B, V) key
    array is kept), and one (B,) fusion settles the digit."""
    prefix = jnp.zeros(keys.shape[:1], jnp.uint32)
    for shift in range(32 - _DIGIT_BITS, -1, -_DIGIT_BITS):
        high, settled = keys >> shift, (prefix >> shift)[:, None]
        above = jnp.where(high >= settled, high - settled, 0)
        digit = jnp.zeros_like(prefix)
        for d in range(1, 1 << _DIGIT_BITS):
            digit = digit + holds(above >= d).astype(jnp.uint32)
        prefix = prefix | (digit << shift)
    return prefix


def filter_logits(logits, temperature, top_k, top_p):
    """The row `sample_tokens` draws from: logits / temperature in
    float32 with everything outside top-k, then outside the nucleus,
    at -inf. (B, V)."""
    lg = logits.astype(jnp.float32)
    t = jnp.asarray(temperature, jnp.float32)
    lg = lg / jnp.where(t > 0, t, 1.0)[:, None]

    k = jnp.asarray(top_k, jnp.int32)
    need = jnp.clip(k, 1, lg.shape[-1])
    kth = _key_to_float(_largest_key_that(
        _ordered_key(lg),
        lambda ge: jnp.sum(ge, axis=-1, dtype=jnp.int32) >= need))
    lg = jnp.where((k > 0)[:, None] & (lg < kth[:, None]), -jnp.inf, lg)

    p = jnp.asarray(top_p, jnp.float32)
    probs = jax.nn.softmax(lg, axis=-1)
    thresh = _key_to_float(_largest_key_that(
        _ordered_key(lg),
        lambda ge: jnp.sum(jnp.where(ge, probs, 0.0), axis=-1) >= p))
    use_p = (p > 0) & (p < 1)
    return jnp.where(use_p[:, None] & (lg < thresh[:, None]), -jnp.inf, lg)


def sample_tokens(logits, row_keys, temperature, top_k, top_p):
    """logits (B, V); row_keys (B, 2) uint32 PRNG keys (one per row —
    rows sample independently, so evicting one request never shifts
    another's stream); temperature/top_p (B,) f32; top_k (B,) i32
    (0 = disabled). Returns (B,) int32 tokens."""
    greedy = jnp.argmax(logits.astype(jnp.float32), axis=-1) \
        .astype(jnp.int32)
    lg = filter_logits(logits, temperature, top_k, top_p)
    sampled = jax.vmap(jax.random.categorical)(row_keys, lg) \
        .astype(jnp.int32)
    return jnp.where(jnp.asarray(temperature, jnp.float32) > 0,
                     sampled, greedy)
