"""Mixture-of-Experts with expert parallelism over an `ep` mesh axis.

Reference parity: MXNet's sparse/contrib mixture layers route on the
host and launch per-expert kernels; here routing is the GShard/Switch
einsum formulation — a dispatch one-hot (tokens×experts×capacity)
contracted against the token matrix — so the whole layer is dense
einsums XLA can partition. Expert weights carry a leading expert dim
sharded `P('ep', ...)`; with the dispatched activations constrained to
the same axis, the SPMD partitioner inserts the token all-to-all over
ICI exactly where the reference would call NCCL alltoall.

Top-k routing with capacity dropping (overflowed tokens pass through
via the residual connection of the surrounding block) + the standard
load-balance auxiliary loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import nd
from ..ndarray import NDArray
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..kernels.dispatch import float0_like
from .mesh import current_manual_axes
from .tensor_parallel import sharding_constraint

__all__ = ["MoEMLP", "held_expert_ffn", "route_top_k",
           "route_softmax_top_k"]


class MoEMLP(HybridBlock):
    """Switch/GShard-style MoE feed-forward block.

    forward(x: (B, T, H)) -> (B, T, H)  [or (out, aux_loss) when
    ``return_aux_loss=True``; aux_loss is the load-balance penalty].
    """

    def __init__(self, hidden, intermediate, num_experts, top_k=2,
                 capacity_factor=1.5, activation="gelu", ep_axis="ep",
                 return_aux_loss=False, dtype="float32", **kwargs):
        super().__init__(**kwargs)
        E = num_experts
        self._E, self._k = E, top_k
        self._cf = capacity_factor
        self._act = activation
        self._ep = ep_axis
        self._return_aux = return_aux_loss
        self.gate = Parameter("gate", shape=(E, hidden), dtype=dtype,
                              init="xavier")
        self.w_up = Parameter("w_up", shape=(E, intermediate, hidden),
                              dtype=dtype, init="xavier",
                              sharding=P(ep_axis, None, None))
        self.b_up = Parameter("b_up", shape=(E, intermediate), dtype=dtype,
                              init="zeros", sharding=P(ep_axis, None))
        self.w_down = Parameter("w_down", shape=(E, hidden, intermediate),
                                dtype=dtype, init="xavier",
                                sharding=P(ep_axis, None, None))
        self.b_down = Parameter("b_down", shape=(E, hidden), dtype=dtype,
                                init="zeros", sharding=P(ep_axis, None))

    def _route(self, flat):
        """Top-k routing with per-expert capacity. flat: (S, H)."""
        S = flat.shape[0]
        E, k = self._E, self._k
        C = max(1, int(S * k * self._cf / E))
        logits = flat @ self.gate.data()._data.T  # (S, E)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        topv, topi = jax.lax.top_k(probs, k)  # (S, k)
        gates = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

        dispatch = jnp.zeros((S, E, C), jnp.float32)
        combine = jnp.zeros((S, E, C), jnp.float32)
        counts = jnp.zeros((E,), jnp.int32)
        for j in range(k):  # static unroll (k is 1 or 2 in practice)
            oh = jax.nn.one_hot(topi[:, j], E, dtype=jnp.int32)  # (S, E)
            pos = jnp.cumsum(oh, axis=0) - 1 + counts[None, :]
            counts = counts + oh.sum(axis=0)
            keep = (pos < C) & (oh > 0)
            pos_oh = jax.nn.one_hot(jnp.clip(pos, 0, C - 1), C)  # (S,E,C)
            d = pos_oh * keep[..., None].astype(jnp.float32)
            dispatch = dispatch + d
            combine = combine + d * gates[:, j][:, None, None]

        # load-balance aux loss (Switch eq. 4): E * sum_e f_e * p_e
        me = probs.mean(axis=0)  # mean router prob per expert
        fe = dispatch.sum(axis=(0, 2)) / jnp.maximum(
            dispatch.sum(), 1.0)  # fraction of routed tokens per expert
        aux = E * jnp.sum(fe * me)
        return dispatch, combine, aux, C

    def _ffn(self, exp_in):
        """Per-expert FFN over whatever expert rows are bound — the
        full (E, C, H) dispatch under GSPMD, or this rank's local
        (E/N, N*C, H) slice inside a manual-ep region."""
        ein = jnp.einsum
        wu = self.w_up.data()._data
        bu = self.b_up.data()._data
        wd = self.w_down.data()._data
        bd = self.b_down.data()._data
        h = ein("ech,eih->eci", exp_in, wu) + bu[:, None, :]
        h = nd.Activation(NDArray(h), act_type=self._act)._data
        return ein("eci,ehi->ech", h, wd) + bd[:, None, :]

    def _exchange_manual(self, exp_in, ax):
        """Manual-ep token exchange: routing ran locally against the
        FULL (replicated) gate, so `exp_in` is (E, C, H) built from
        this rank's tokens. all_gather every rank's dispatch, run the
        local experts over all ranks' tokens, all_gather the outputs
        back and slice this rank's rows — two all_gathers standing in
        for the GSPMD all-to-all pair, with the same totals."""
        E, C, H = exp_in.shape
        nsh = jax.lax.psum(1, ax)
        El = E // nsh
        r = jax.lax.axis_index(ax)
        g = jax.lax.all_gather(exp_in, ax)          # (N, E, C, H)
        mine = jax.lax.dynamic_slice_in_dim(g, r * El, El, axis=1)
        mine = jnp.swapaxes(mine, 0, 1)             # (El, N, C, H)
        out_l = self._ffn(mine.reshape(El, nsh * C, H))
        out_l = jnp.swapaxes(out_l.reshape(El, nsh, C, H), 0, 1)
        g2 = jax.lax.all_gather(out_l, ax)          # (N_src, N_tok, El, C, H)
        back = jax.lax.dynamic_index_in_dim(g2, r, axis=1,
                                            keepdims=False)
        return back.reshape(E, C, H)                # owner-major == id order

    def forward(self, x):
        raw = x._data if isinstance(x, NDArray) else x
        B, T, H = raw.shape
        flat = raw.reshape(B * T, H)
        dispatch, combine, aux, C = self._route(flat)

        ein = jnp.einsum  # dispatch: (S,E,C) ⊗ (S,H) → (E,C,H)
        exp_in = ein("sec,sh->ech", dispatch.astype(raw.dtype), flat)
        ax = current_manual_axes().get("ep")
        if ax is not None:
            out_e = self._exchange_manual(exp_in, ax)
        else:
            exp_in = sharding_constraint(exp_in, self._ep, None, None)
            out_e = self._ffn(exp_in)
            out_e = sharding_constraint(out_e, self._ep, None, None)
        out = ein("sec,ech->sh", combine.astype(raw.dtype), out_e)
        out = out.reshape(B, T, H)
        res = NDArray(out) if isinstance(x, NDArray) else out
        if self._return_aux:
            a = NDArray(aux) if isinstance(x, NDArray) else aux
            return res, a
        return res


# -- a dropless token-choice layer over the experts held here ---------------

def route_top_k(x, router_w, bias, top_k, route_scale):
    """Sigmoid scores in float32 over ALL experts, top-k of score +
    bias (the bias picks, it does not weigh), weights normalised over
    the picked and scaled. x (T, D), router_w (E, D), bias (E,).
    Returns (sel (T, k) int32, w (T, k) float32)."""
    s = jax.nn.sigmoid(x.astype(jnp.float32)
                       @ router_w.astype(jnp.float32).T)
    _, sel = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(s, sel, axis=1)
    w = route_scale * picked / jnp.sum(picked, axis=1, keepdims=True)
    return sel.astype(jnp.int32), w


def route_softmax_top_k(x, router_w, bias, top_k, route_scale):
    """A softmax in float32 over ALL experts, its top-k, the picked
    probabilities normalised to sum to one (`norm_topk_prob`) and
    scaled; no bias (`bias` is None). Same shapes as `route_top_k`. The
    weights differentiate into the router through the softmax and the
    normalisation; the picks carry no gradient."""
    del bias
    # a true float32 product: the MXU's default single bf16 pass flips
    # near-tied picks (T x E x D is small change)
    p = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32).T,
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    picked, sel = jax.lax.top_k(p, top_k)
    w = route_scale * picked / jnp.sum(picked, axis=1, keepdims=True)
    return sel.astype(jnp.int32), w


# The rows' way to the experts and back. Forward they are the two
# gathers they always were; backward each is a gather too (the layout
# knows both directions: `row_token` row -> token, `pair_row` pair ->
# row), where the transposes XLA would derive are scatter-adds over
# every row.

def _picked(y, pair_row, held):
    # a row no tile wrote is whatever the buffer held: where(), not a
    # product with a zero weight
    return jnp.where(held.reshape(-1, 1),
                     y[pair_row].astype(jnp.float32), 0.0)


@jax.custom_vjp
def _to_rows(x, row_token, pair_row, held):
    return x[row_token]


def _to_rows_fwd(x, row_token, pair_row, held):
    return x[row_token], (row_token, pair_row, held,
                          jnp.zeros((0,), x.dtype))


def _to_rows_bwd(res, drows):
    row_token, pair_row, held, like = res
    d = _picked(drows, pair_row, held).reshape(held.shape + (-1,))
    return (d.sum(axis=1).astype(like.dtype), float0_like(row_token),
            float0_like(pair_row), float0_like(held))


_to_rows.defvjp(_to_rows_fwd, _to_rows_bwd)


@jax.custom_vjp
def _from_rows(y, w, pair_row, held, row_token, row_pair):
    y = _picked(y, pair_row, held) * w.reshape(-1, 1)
    return y.reshape(w.shape + (-1,)).sum(axis=1)


def _from_rows_fwd(y, w, pair_row, held, row_token, row_pair):
    return _from_rows(y, w, pair_row, held, row_token, row_pair), \
        (y, w, pair_row, held, row_token, row_pair)


def _from_rows_bwd(res, dout):
    y, w, pair_row, held, row_token, row_pair = res
    # row_pair: a row's pair, or T * k for a row no pair owns — those
    # get a ZERO cotangent (the weights' gradient sums every row of a
    # live tile)
    row_w = jnp.concatenate(
        [w.reshape(-1), jnp.zeros((1,), w.dtype)])[row_pair]
    # the rows are gathered in y's type (half the bytes of float32;
    # `held_expert_ffn`'s docstring says what that rounds) and
    # weighted in float32
    dy = (dout.astype(y.dtype)[row_token].astype(jnp.float32)
          * row_w[:, None]).astype(y.dtype)
    dw = jnp.sum(_picked(y, pair_row, held).reshape(w.shape + (-1,))
                 * dout[:, None, :], axis=-1)
    return (dy, dw, float0_like(pair_row), float0_like(held),
            float0_like(row_token), float0_like(row_pair))


_from_rows.defvjp(_from_rows_fwd, _from_rows_bwd)


def _held_rows(x, g, w, ex_gate, ex_up, ex_down, use_kernel):
    """The held experts' weighted sum for the tokens of `x`; g (T, k)
    names each pair's held expert (0 .. n - 1), or n where the pair
    falls on no expert held here. The held pairs are sorted by expert,
    each expert's rows padded to whole tiles, a grouped SwiGLU runs
    over them and the rows go back to their tokens weighted. Every
    shape is the worst case's: all T * k pairs held."""
    from ..kernels.grouped_matmul import grouped_matmul, row_tile

    T, k = g.shape
    n = ex_gate.shape[0]
    P_ = T * k
    tm = row_tile(P_)
    m_pad = -(-P_ // tm) * tm + n * tm
    held = g < n
    g = g.reshape(-1)
    counts = jnp.zeros((n + 1,), jnp.int32).at[g].add(1)
    padded = -(-counts[:n] // tm) * tm
    pend = jnp.cumsum(padded)
    pstart = jnp.concatenate([pend - padded, jnp.full((1,), m_pad)])
    ustart = jnp.cumsum(counts) - counts
    order = jnp.argsort(g, stable=True)
    gs = g[order]
    row = jnp.where(gs < n, pstart[gs] + jnp.arange(P_) - ustart[gs],
                    m_pad)                                 # m_pad: drop
    row_token = jnp.zeros((m_pad,), jnp.int32).at[row].set(
        (order // k).astype(jnp.int32), mode="drop")
    pair_row = jnp.zeros((P_,), jnp.int32).at[order].set(
        jnp.minimum(row, m_pad - 1).astype(jnp.int32))
    # only the backward reads it (dead code in a forward program)
    row_pair = jnp.full((m_pad,), P_, jnp.int32).at[row].set(
        order.astype(jnp.int32), mode="drop")
    tiles = m_pad // tm
    tile_group = jnp.minimum(
        jnp.searchsorted(pend, jnp.arange(tiles) * tm, side="right"),
        n - 1).astype(jnp.int32)
    n_tiles = pend[-1] // tm
    rows = _to_rows(x, row_token, pair_row, held)
    h = grouped_matmul(rows, ex_gate, tile_group, n_tiles, tm,
                       rhs2=ex_up, use_kernel=use_kernel)
    y = grouped_matmul(h, ex_down, tile_group, n_tiles, tm,
                       use_kernel=use_kernel)
    return _from_rows(y, w, pair_row, held, row_token, row_pair)


def held_expert_ffn(x, router_w, bias, ex_gate, ex_up, ex_down, *, lo,
                    top_k, route, route_scale=1.0, valid=None,
                    use_kernel=True, remat=False):
    """One chip's share of a token-choice expert layer, dropless.

    x (T, D); router_w (E, D) and bias (E,) at the PUBLISHED expert
    count; ex_gate, ex_up (n, D, I) and ex_down (n, I, D): the stacked
    SwiGLU weights of the experts [lo, lo + n) held here, input-major.
    `route(x, router_w, bias, top_k, route_scale) -> (sel, w)` is the
    model's routing rule (`route_top_k`: sigmoid scores and a bias that
    picks; `route_softmax_top_k`: a softmax's top-k, normalised): every
    token is scored and its top-k picked over all E experts; the layer
    returns the sum over picked AND held experts of w_e * SwiGLU_e(x)
    in float32 — what the absent experts would add lives on the chips
    that hold them, and on one chip the layer runs without its
    exchange. `valid` (T,) bool keeps padding rows and idle batch slots
    out of the experts. No capacity, nothing dropped: shapes are the
    worst case's (T * k rows).

    The layer differentiates end to end: x and the experts' matrices
    through the grouped products (`kernels/grouped_matmul.py`: the
    kernel again on the transposed matrices, and a wgrad kernel), the
    rows' way there and back by gathers both ways, the router through
    the weights `route` returns.

    Returns (y (T, D) float32, pairs, touched, pairs_max): the pairs
    that fell on held experts, the held experts with at least one row
    and the fullest held expert's rows, int32.

    More than a chunk of tokens are routed a chunk at a time, so the
    row buffers stay a chunk's worst case: the tuned
    `moe_grouped_matmul.chunk_tokens` (a long prefill's), or with
    `remat`, where a chunk's buffers are rebuilt in the backward and
    not kept (a training step keeps one chunk's at a time),
    `chunk_rows_remat` pairs — sized by its rows, as the row tile is.

    The cotangent reaches the experts' rows rounded to their type
    (`_from_rows_bwd` gathers it in y's type, half the bytes): the
    gradients are exact to the type the experts compute in, not to the
    float32 the layer returns. A caller that casts the output to that
    type (the decoder blocks do) loses nothing by it."""
    from ..kernels import tuning

    sel, w = route(x, router_w, bias, top_k, route_scale)
    n = ex_gate.shape[0]
    held = (sel >= lo) & (sel < lo + n)
    if valid is not None:
        held = held & valid[:, None]
    T = x.shape[0]
    g = jnp.where(held, sel - lo, n)        # n: on no expert held here
    hits = jnp.zeros((n + 1,), jnp.int32).at[g.reshape(-1)].add(1)[:n]
    counts = (jnp.sum(hits), jnp.sum(hits > 0), jnp.max(hits))
    chunk = tuning.get("moe_grouped_matmul", "chunk_rows_remat") // top_k \
        if remat else tuning.get("moe_grouped_matmul", "chunk_tokens")

    def one(c):
        return _held_rows(*c, ex_gate, ex_up, ex_down, use_kernel)

    if remat:
        one = jax.checkpoint(one)
    if T <= chunk or T % chunk:
        return (one((x, g, w)),) + counts

    split = lambda a: a.reshape((T // chunk, chunk) + a.shape[1:])
    out = jax.lax.map(one, (split(x), split(g), split(w)))
    return (out.reshape(T, -1),) + counts
