"""Loss blocks (reference: mxnet/gluon/loss.py)."""
from __future__ import annotations

import jax.numpy as jnp

from .. import nd
from .block import HybridBlock

__all__ = ["Loss", "L1Loss", "L2Loss", "SigmoidBinaryCrossEntropyLoss",
           "SigmoidBCELoss", "SoftmaxCrossEntropyLoss", "SoftmaxCELoss",
           "KLDivLoss", "HuberLoss", "HingeLoss", "SquaredHingeLoss",
           "LogisticLoss", "TripletLoss", "CosineEmbeddingLoss", "CTCLoss"]


def _apply_weighting(loss, weight=None, sample_weight=None):
    if sample_weight is not None:
        loss = loss * sample_weight
    if weight is not None:
        loss = loss * weight
    return loss


def _reshape_like(pred, label):
    return label.reshape(pred.shape) if pred.shape != label.shape else label


class Loss(HybridBlock):
    def __init__(self, weight=None, batch_axis=0, **kwargs):
        super().__init__(**kwargs)
        self._weight = weight
        self._batch_axis = batch_axis

    def _mean(self, loss):
        axes = tuple(i for i in range(loss.ndim) if i != self._batch_axis)
        return loss.mean(axis=axes) if axes else loss


class L1Loss(Loss):
    def __init__(self, weight=None, batch_axis=0, **kw):
        super().__init__(weight, batch_axis, **kw)

    def forward(self, pred, label, sample_weight=None):
        loss = (pred - _reshape_like(pred, label)).abs()
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean(loss)


class L2Loss(Loss):
    """0.5 * (pred - label)^2 (reference keeps the 1/2 factor)."""

    def __init__(self, weight=1.0, batch_axis=0, **kw):
        super().__init__(weight, batch_axis, **kw)

    def forward(self, pred, label, sample_weight=None):
        loss = (pred - _reshape_like(pred, label)).square() * 0.5
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean(loss)


class SigmoidBinaryCrossEntropyLoss(Loss):
    def __init__(self, from_sigmoid=False, weight=None, batch_axis=0, **kw):
        super().__init__(weight, batch_axis, **kw)
        self._from_sigmoid = from_sigmoid

    def forward(self, pred, label, sample_weight=None, pos_weight=None):
        label = _reshape_like(pred, label)
        if not self._from_sigmoid:
            # max(x,0) - x*z + log(1+exp(-|x|)) — numerically stable
            loss = nd.relu(pred) - pred * label + \
                nd.Activation(-pred.abs(), act_type="softrelu")
            if pos_weight is not None:
                loss = loss + (pos_weight - 1) * label * (
                    nd.Activation(-pred.abs(), act_type="softrelu") +
                    nd.relu(-pred))
        else:
            eps = 1e-12
            loss = -((pred + eps).log() * label +
                     (1.0 - pred + eps).log() * (1.0 - label))
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean(loss)


SigmoidBCELoss = SigmoidBinaryCrossEntropyLoss


class SoftmaxCrossEntropyLoss(Loss):
    def __init__(self, axis=-1, sparse_label=True, from_logits=False,
                 weight=None, batch_axis=0, **kw):
        super().__init__(weight, batch_axis, **kw)
        self._axis = axis
        self._sparse = sparse_label
        self._from_logits = from_logits

    def forward(self, pred, label, sample_weight=None):
        if (self._sparse and not self._from_logits
                and self._axis in (-1, pred.ndim - 1) and pred.ndim >= 2):
            from ..kernels import fused_ce

            if fused_ce.eligible(pred.shape[-1],
                                 pred._data.dtype.itemsize):
                # LM hot path: one fused Pallas pass over the (N, V)
                # logits, no materialized log-probabilities
                from ..ndarray import invoke

                vocab = pred.shape[-1]
                lbl_shape = pred.shape[:-1]

                def f(x, lbl):
                    per_row = fused_ce.fused_softmax_ce_raw(
                        x.reshape(-1, vocab),
                        lbl.reshape(-1).astype(jnp.int32))
                    return per_row.reshape(lbl_shape + (1,))

                loss = invoke(f, [pred, label])
                loss = _apply_weighting(loss, self._weight, sample_weight)
                return self._mean(loss)
        if not self._from_logits:
            pred = nd.log_softmax(pred, axis=self._axis)
        if self._sparse:
            loss = -nd.pick(pred, label, axis=self._axis, keepdims=True)
        else:
            label = _reshape_like(pred, label)
            loss = -(pred * label).sum(axis=self._axis, keepdims=True)
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean(loss)


SoftmaxCELoss = SoftmaxCrossEntropyLoss


class KLDivLoss(Loss):
    def __init__(self, from_logits=True, axis=-1, weight=None, batch_axis=0,
                 **kw):
        super().__init__(weight, batch_axis, **kw)
        self._from_logits = from_logits
        self._axis = axis

    def forward(self, pred, label, sample_weight=None):
        if not self._from_logits:
            pred = nd.log_softmax(pred, axis=self._axis)
        loss = label * ((label + 1e-12).log() - pred)
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean(loss)


class HuberLoss(Loss):
    def __init__(self, rho=1.0, weight=None, batch_axis=0, **kw):
        super().__init__(weight, batch_axis, **kw)
        self._rho = rho

    def forward(self, pred, label, sample_weight=None):
        err = (pred - _reshape_like(pred, label)).abs()
        loss = nd.where(err > self._rho,
                        err - 0.5 * self._rho,
                        (0.5 / self._rho) * err.square())
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean(loss)


class HingeLoss(Loss):
    def __init__(self, margin=1.0, weight=None, batch_axis=0, **kw):
        super().__init__(weight, batch_axis, **kw)
        self._margin = margin

    def forward(self, pred, label, sample_weight=None):
        loss = nd.relu(self._margin - pred * _reshape_like(pred, label))
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean(loss)


class SquaredHingeLoss(HingeLoss):
    def forward(self, pred, label, sample_weight=None):
        loss = nd.relu(self._margin - pred *
                       _reshape_like(pred, label)).square()
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean(loss)


class LogisticLoss(Loss):
    def __init__(self, weight=None, batch_axis=0, label_format="signed",
                 **kw):
        super().__init__(weight, batch_axis, **kw)
        self._fmt = label_format

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        if self._fmt == "signed":
            label = (label + 1.0) / 2.0
        loss = nd.relu(pred) - pred * label + \
            nd.Activation(-pred.abs(), act_type="softrelu")
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean(loss)


class TripletLoss(Loss):
    def __init__(self, margin=1.0, weight=None, batch_axis=0, **kw):
        super().__init__(weight, batch_axis, **kw)
        self._margin = margin

    def forward(self, pred, positive, negative, sample_weight=None):
        pos = (pred - positive).square().sum(
            axis=tuple(range(1, pred.ndim)))
        neg = (pred - negative).square().sum(
            axis=tuple(range(1, pred.ndim)))
        loss = nd.relu(pos - neg + self._margin)
        return _apply_weighting(loss, self._weight, sample_weight)


class CosineEmbeddingLoss(Loss):
    def __init__(self, weight=None, batch_axis=0, margin=0.0, **kw):
        super().__init__(weight, batch_axis, **kw)
        self._margin = margin

    def forward(self, input1, input2, label, sample_weight=None):
        def cos(a, b):
            num = (a * b).sum(axis=-1)
            return num / (a.norm(axis=-1) * b.norm(axis=-1) + 1e-12)
        sim = cos(input1, input2)
        label = label.reshape(sim.shape)
        loss = nd.where(label == 1.0, 1.0 - sim,
                        nd.relu(sim - self._margin))
        return _apply_weighting(loss, self._weight, sample_weight)


class CTCLoss(Loss):
    """Connectionist temporal classification (reference: contrib CTCLoss,
    warp-ctc). Lowered to a lax.scan dynamic program — jit/TPU friendly."""

    def __init__(self, layout="NTC", label_layout="NT", weight=None, **kw):
        super().__init__(weight, batch_axis=0, **kw)
        self._layout = layout
        self._label_layout = label_layout

    def forward(self, pred, label, pred_lengths=None, label_lengths=None,
                sample_weight=None):
        import jax
        from ..ndarray import invoke

        blank = 0  # reference uses alphabet_size-1 by default in warpctc;
        # gluon CTCLoss uses 0 as blank ('first' convention)

        def ctc(logits, labels):
            # logits (N, T, C) log-probs; labels (N, L) padded with -1
            logp = jax.nn.log_softmax(logits, axis=-1)
            N, T, C = logp.shape
            L = labels.shape[1]
            lab = labels.astype(jnp.int32)
            lab_len = jnp.sum((lab >= 0).astype(jnp.int32), axis=1)
            lab = jnp.where(lab < 0, 0, lab)
            S = 2 * L + 1
            ext = jnp.zeros((N, S), jnp.int32)
            ext = ext.at[:, 1::2].set(lab)  # blank interleaved
            neg_inf = -1e30
            alpha0 = jnp.full((N, S), neg_inf)
            alpha0 = alpha0.at[:, 0].set(logp[:, 0, blank])
            alpha0 = alpha0.at[:, 1].set(
                jnp.take_along_axis(logp[:, 0, :], ext[:, 1:2], axis=1)[:, 0])

            def step(alpha, logp_t):
                a0 = alpha
                a1 = jnp.pad(alpha, ((0, 0), (1, 0)),
                             constant_values=neg_inf)[:, :-1]
                a2 = jnp.pad(alpha, ((0, 0), (2, 0)),
                             constant_values=neg_inf)[:, :-2]
                same = jnp.pad(ext[:, :-2] == ext[:, 2:], ((0, 0), (2, 0)),
                               constant_values=True)
                is_blank = (ext == blank)
                allow2 = ~(is_blank | same)
                m = jnp.maximum(a0, jnp.maximum(
                    a1, jnp.where(allow2, a2, neg_inf)))
                m_safe = jnp.where(m == neg_inf, 0.0, m)
                s = jnp.exp(a0 - m_safe) + jnp.exp(a1 - m_safe) + \
                    jnp.where(allow2, jnp.exp(a2 - m_safe), 0.0)
                new = m_safe + jnp.log(jnp.maximum(s, 1e-37))
                new = jnp.where(m == neg_inf, neg_inf, new)
                emit = jnp.take_along_axis(logp_t, ext, axis=1)
                return new + emit, None

            logp_t = jnp.moveaxis(logp, 1, 0)  # (T, N, C)
            alpha, _ = jax.lax.scan(step, alpha0, logp_t[1:])
            end1 = 2 * lab_len
            end2 = 2 * lab_len - 1
            a_end1 = jnp.take_along_axis(alpha, end1[:, None], axis=1)[:, 0]
            a_end2 = jnp.take_along_axis(
                alpha, jnp.maximum(end2, 0)[:, None], axis=1)[:, 0]
            m = jnp.maximum(a_end1, a_end2)
            m_safe = jnp.where(m == neg_inf, 0.0, m)
            ll = m_safe + jnp.log(jnp.exp(a_end1 - m_safe) +
                                  jnp.exp(a_end2 - m_safe))
            return -ll

        p = pred if self._layout == "NTC" else pred.transpose((1, 0, 2))
        return invoke(ctc, [p, label])
