"""The sampler's thresholds by selection against the sorted sampler.

Until PR 40 `sample_tokens` sorted every row twice a tick; that
function is kept HERE, as the oracle: for the same keys the new one
returns the same tokens and masks the same logits, with one licence —
the nucleus' masses are added in another order, so the two may
disagree about a logit value where the mass down to it lies within
1e-6 of `top_p` (one value at the nucleus' edge, unless p is within
1e-6 of the whole mass). A float64 sort in numpy agrees on the kept
set under the same licence.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.serving.sampling import filter_logits, sample_tokens


def sorted_sampler(logits, row_keys, temperature, top_k, top_p):
    """`sample_tokens` as it was, returning the row after top-k and
    the row after top-p beside the tokens."""
    lg0 = logits.astype(jnp.float32)
    greedy = jnp.argmax(lg0, axis=-1).astype(jnp.int32)
    t = jnp.asarray(temperature, jnp.float32)
    safe_t = jnp.where(t > 0, t, 1.0)
    lg = lg0 / safe_t[:, None]
    V = lg.shape[-1]

    k = jnp.asarray(top_k, jnp.int32)
    asc = jnp.sort(lg, axis=-1)
    kth = jnp.take_along_axis(
        asc, jnp.clip(V - k, 0, V - 1)[:, None], axis=-1)
    lg_k = lg = jnp.where((k > 0)[:, None] & (lg < kth), -jnp.inf, lg)

    p = jnp.asarray(top_p, jnp.float32)
    desc = jnp.sort(lg, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = cum - probs < p[:, None]
    thresh = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1,
                     keepdims=True)
    use_p = (p > 0) & (p < 1)
    lg = jnp.where(use_p[:, None] & (lg < thresh), -jnp.inf, lg)

    sampled = jax.vmap(jax.random.categorical)(row_keys, lg) \
        .astype(jnp.int32)
    return jnp.where(t > 0, sampled, greedy), lg_k, lg


_old = jax.jit(sorted_sampler)
_new = jax.jit(lambda lg, keys, t, k, p: (
    sample_tokens(lg, keys, t, k, p), filter_logits(lg, t, k, p)))


def _row_keys(n, seed):
    return jax.random.key_data(jax.random.split(jax.random.PRNGKey(seed),
                                                n))


def _edge_mass_near_p(row_k, value, p):
    """Is the float64 mass of the logits above `value`, or of those
    at and above it, within 1e-6 of p? (`row_k`: the row after top-k.)"""
    x = row_k.astype(np.float64)
    e = np.exp(x - x.max())
    e /= e.sum()
    return min(abs(e[x > value].sum() - p),
               abs(e[x >= value].sum() - p)) <= 1e-6


def _only_at_the_edge(row_k, differ, p):
    """The logits two samplers disagree about all sit where the mass
    is within 1e-6 of p (the mass only grows down the row, so the
    largest and the smallest of them speak for the rest)."""
    values = row_k[differ]
    return all(_edge_mass_near_p(row_k, v, p)
               for v in (values.min(), values.max()))


def check_against_the_sorted_sampler(logits, t, k, p, seed=0):
    """Tokens and masks of every row against the oracle's. Returns
    the new sampler's (tokens, masked rows) for further asserts."""
    logits = jnp.asarray(logits)
    B = logits.shape[0]
    t, p = (np.asarray(a, np.float32) for a in (t, p))
    k = np.asarray(k, np.int32)
    keys = _row_keys(B, seed)
    tok_old, lg_k, lg_old = map(np.asarray, _old(logits, keys, t, k, p))
    tok_new, lg_new = map(np.asarray, _new(logits, keys, t, k, p))
    for b in range(B):
        differ = np.isinf(lg_new[b]) != np.isinf(lg_old[b])
        if differ.any():
            assert _only_at_the_edge(lg_k[b], differ, p[b]), (
                f"row {b}: {differ.sum()} logits masked differently "
                f"away from the nucleus' edge, top_k {k[b]}, "
                f"top_p {p[b]}")
        else:
            np.testing.assert_array_equal(lg_new[b], lg_old[b])
            assert tok_new[b] == tok_old[b], f"row {b}"
        if t[b] <= 0:
            assert tok_new[b] == np.argmax(
                np.asarray(logits[b], np.float32))
    return tok_new, lg_new


def _mixed_rows(rng, B, V):
    t = rng.choice([0.0, -1.0, 0.6, 0.8, 1.0, 1.7], B)
    k = rng.choice([0, 1, 5, 50, min(5000, V - 1)], B)
    p = rng.choice([0.0, 0.5, 0.9, 0.95, 1.0], B)
    return t, k, p


@pytest.mark.parametrize("V,B", [(257, 16), (4099, 16), (25024, 8),
                                 (32000, 8), (65536, 4)])
def test_same_tokens_and_masks_as_the_sorted_sampler(V, B):
    """Greedy beside sampled rows, every knob mixed, at the
    vocabularies the cells and the tests serve; a third of the
    batches hold tied logits (rounded to halves)."""
    rng = np.random.default_rng(V)
    for batch in range(3):
        lg = rng.normal(size=(B, V)).astype(np.float32) * 3
        if batch == 2:
            lg = np.round(lg * 2) / 2
        tok, _ = check_against_the_sorted_sampler(
            lg, *_mixed_rows(rng, B, V), seed=batch)
        assert ((tok >= 0) & (tok < V)).all()


_EDGE_V = 4099


@pytest.mark.parametrize("knob,value", [
    ("top_k", 0), ("top_k", 1), ("top_k", 2), ("top_k", 50),
    ("top_k", _EDGE_V - 1), ("top_k", _EDGE_V), ("top_k", _EDGE_V + 7),
    ("top_k", -3),
    ("top_p", 0.0), ("top_p", 1e-6), ("top_p", 0.5), ("top_p", 0.95),
    ("top_p", 1.0), ("top_p", 1.5), ("top_p", float(1 - 2.0 ** -24))])
def test_the_knobs_edges(knob, value):
    """One knob held at an edge in every row while the other takes
    its mix: k >= V keeps all, k <= 0 and p outside (0, 1) are off,
    k = 1 and p = 1e-6 keep the top token alone; at p = 1 - 6e-8,
    which a row's whole float32 mass may round below, the two agree
    up to the tail whose mass is the rounding."""
    B, V = 8, _EDGE_V
    rng = np.random.default_rng(int(abs(value) * 1000) + len(knob))
    lg = rng.normal(size=(B, V)).astype(np.float32) * 2
    t, k, p = _mixed_rows(rng, B, V)
    t = np.where(np.arange(B) % 4 == 0, 0.0, np.abs(t) + 0.5)
    if knob == "top_k":
        k = np.full(B, value)
    else:
        p = np.full(B, value, np.float32)
    _, kept = check_against_the_sorted_sampler(lg, t, k, p)
    finite = np.isfinite(kept).sum(axis=-1)
    if knob == "top_k" and (value <= 0 or value >= V):
        assert (finite[(p <= 0) | (p >= 1)] == V).all()
    if knob == "top_k" and 0 < value < V:
        assert (finite <= value).all() and \
            (finite[(p <= 0) | (p >= 1)] == value).all()
    if knob == "top_p" and not 0 < value < 1:
        assert (finite[k == 0] == V).all()
    if knob == "top_p" and value == 1e-6:
        assert (finite == 1).all()


@pytest.mark.parametrize("where", ["k-th value", "nucleus edge"])
def test_tied_logits_are_all_kept(where):
    """Ties at the threshold: top-k keeps every logit equal to the
    k-th (more than k survive), the nucleus every logit equal to its
    smallest member."""
    B, V = 4, 257
    lg = np.full((B, V), -4.0, np.float32)
    lg[:, :3] = [5.0, 4.0, 4.0]
    lg[:, 3:9] = 2.0                       # six tied logits
    rng = np.random.default_rng(7)
    lg = np.take_along_axis(lg, rng.permuted(
        np.tile(np.arange(V), (B, 1)), axis=1), axis=1)
    t = np.ones(B)
    if where == "k-th value":
        k, p = np.array([4, 5, 8, 9]), np.zeros(B)
    else:       # the three best hold 0.841 of the mass, the ties 0.145
        k, p = np.zeros(B, int), np.array([0.85, 0.9, 0.95, 0.98])
    _, kept = check_against_the_sorted_sampler(lg, t, k, p)
    assert (np.isfinite(kept).sum(axis=-1) == 9).all()


@pytest.mark.parametrize("what", ["-inf entries", "signed zeros",
                                  "bfloat16 logits", "one-hot row"])
def test_rows_a_sort_orders_specially(what):
    B, V = 8, 257
    rng = np.random.default_rng(len(what))
    lg = rng.normal(size=(B, V)).astype(np.float32) * 2
    t, k, p = _mixed_rows(rng, B, V)
    if what == "-inf entries":      # a row masked before it arrives
        lg[:, rng.permutation(V)[:200]] = -np.inf
        k = np.array([0, 1, 5, 50, 57, 58, 100, 300])
    elif what == "signed zeros":    # the keys tell -0.0 from +0.0,
        lg = np.abs(lg)             # the float masks must not
        lg[:, :40] = 0.0
        lg[:, 40:80] = -0.0
        lg[:, 80:] = -lg[:, 80:]
        k = np.array([0, 1, 40, 41, 79, 80, 81, 200])
        p = np.array([0, .3, .5, .9, 0, .99, .95, 1.], np.float32)
    elif what == "bfloat16 logits":     # many ties by construction
        lg = jnp.asarray(lg, jnp.bfloat16)
    else:                           # p reached by the first token
        lg = np.full((B, V), -30.0, np.float32)
        lg[np.arange(B), rng.integers(0, V, B)] = 30.0
        t = np.abs(t) + 0.5
    tok, kept = check_against_the_sorted_sampler(lg, t, k, p)
    if what == "one-hot row":
        assert (tok == np.argmax(np.asarray(lg), axis=-1)).all()
        assert (np.isfinite(kept).sum(axis=-1)[(p > 0) & (p < 1)]
                == 1).all()
    if what == "signed zeros":      # both zeros stay or go together
        zeros = np.isfinite(kept[:, :80])
        assert (zeros.all(axis=-1) | ~zeros.any(axis=-1)).all()


@pytest.mark.parametrize("V", [257, 4099, 32000])
def test_a_float64_sort_keeps_the_same_set(V):
    """An independent reference: numpy, float64, a stable sort. The
    kept set is equal, or differs by the one logit value at which the
    descending mass comes within 1e-6 of p."""
    B = 8
    rng = np.random.default_rng(V + 1)
    lg = rng.normal(size=(B, V)).astype(np.float32) * 3
    t = rng.choice([0.6, 0.8, 1.0, 1.7], B).astype(np.float32)
    k = rng.choice([0, 1, 5, 50, 200], B)
    p = rng.choice([0.0, 0.5, 0.9, 0.95, 1.0], B).astype(np.float32)
    kept = np.isfinite(np.asarray(jax.jit(filter_logits)(lg, t, k, p)))
    for b in range(B):
        x = lg[b] / t[b]                   # float32, as the program
        keep = np.ones(V, bool)
        if k[b] > 0:
            keep &= x >= np.sort(x)[::-1][min(k[b], V) - 1]
        row_k = np.where(keep, x, -np.inf)
        if 0 < p[b] < 1:
            desc = np.sort(row_k.astype(np.float64))[::-1]
            e = np.exp(desc - desc[0])
            cum = np.cumsum(e / e.sum())
            nucleus = desc[:np.searchsorted(cum, p[b]) + 1]
            keep &= x >= nucleus[-1]
        differ = kept[b] != keep
        if differ.any():
            assert _only_at_the_edge(row_k, differ, p[b]), \
                f"row {b}: kept sets differ away from the nucleus' edge"
