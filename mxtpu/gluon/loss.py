"""Losses — parity with ``python/mxnet/gluon/loss.py`` (11 losses: L2/L1/SigmoidBCE/
SoftmaxCE/KLDiv/CTC/Huber/Hinge/SquaredHinge/Logistic/Triplet + PoissonNLL/Cosine)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .. import ndarray as nd
from ..ndarray.ndarray import NDArray
from .block import HybridBlock


def _apply_weighting(loss, weight: Optional[float], sample_weight: Optional[NDArray]):
    if sample_weight is not None:
        loss = loss * sample_weight
    if weight is not None:
        loss = loss * weight
    return loss


def _reshape_like(pred, label):
    return label.reshape(pred.shape) if pred.shape != label.shape else label


class Loss(HybridBlock):
    def __init__(self, weight: Optional[float], batch_axis: int = 0, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._weight = weight
        self._batch_axis = batch_axis

    def _mean_all_but_batch(self, loss):
        axes = tuple(i for i in range(loss.ndim) if i != self._batch_axis)
        return nd.mean(loss, axis=axes) if axes else loss


class L2Loss(Loss):
    def __init__(self, weight: float = 1.0, batch_axis: int = 0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        loss = nd.square(label - pred)
        loss = _apply_weighting(loss, self._weight / 2, sample_weight)
        return self._mean_all_but_batch(loss)


class L1Loss(Loss):
    def __init__(self, weight: Optional[float] = None, batch_axis: int = 0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        loss = nd.abs(label - pred)
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean_all_but_batch(loss)


class SigmoidBinaryCrossEntropyLoss(Loss):
    """BCE with optional from_sigmoid (loss.py SigmoidBCELoss) — numerically stable
    log-sum-exp form when given logits."""

    def __init__(self, from_sigmoid: bool = False, weight: Optional[float] = None,
                 batch_axis: int = 0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_sigmoid = from_sigmoid

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        if not self._from_sigmoid:
            loss = nd.relu(pred) - pred * label + nd.softrelu(-nd.abs(pred))
        else:
            eps = 1e-12
            loss = -(nd.log(pred + eps) * label + nd.log(1 - pred + eps) * (1 - label))
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean_all_but_batch(loss)


SigmoidBCELoss = SigmoidBinaryCrossEntropyLoss


class SoftmaxCrossEntropyLoss(Loss):
    """loss.py SoftmaxCELoss: sparse or dense labels, optional pre-softmax inputs."""

    def __init__(self, axis: int = -1, sparse_label: bool = True,
                 from_logits: bool = False, weight: Optional[float] = None,
                 batch_axis: int = 0, ignore_label=None, **kwargs):
        """``ignore_label`` (extension beyond the reference gluon loss, matching
        the symbolic ``SoftmaxOutput(use_ignore=True)`` capability): sparse
        label positions equal to it contribute zero loss and zero gradient —
        the masking contract bucketed/padded pipelines need."""
        super().__init__(weight, batch_axis, **kwargs)
        if ignore_label is not None and not sparse_label:
            raise ValueError("ignore_label requires sparse_label=True "
                             "(dense one-hot labels have no ignore id)")
        self._axis = axis
        self._sparse = sparse_label
        self._from_logits = from_logits
        self._ignore_label = ignore_label

    def forward(self, pred, label, sample_weight=None):
        if not self._from_logits:
            pred = nd.log_softmax(pred, axis=self._axis)
        if self._sparse:
            loss = -nd.pick(pred, label, axis=self._axis, keepdims=False)
            if self._ignore_label is not None:
                loss = loss * (label != float(self._ignore_label))
        else:
            label = _reshape_like(pred, label)
            loss = -nd.sum(pred * label, axis=self._axis)
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean_all_but_batch(loss)


SoftmaxCELoss = SoftmaxCrossEntropyLoss


class NextTokenLoss(Loss):
    """Next-token training of a language model that may also predict further
    ahead (a ``HybridDecoderLM(mtp_layers=1)`` in train mode). ``label``
    ``(B, T)``: ``label[:, i]`` is the token after position ``i``.

    ``pred`` ONE array ``(B, T, V)``: the cross entropy of every position,
    ``(B * T,)``: ``SoftmaxCrossEntropyLoss`` over the flattened rows.

    ``pred`` a tuple ``(logits, logits2, ...)``, depth ``k`` at index ``k``:
    a scalar, ``mean_i CE(logits_i, label_i) + weight * sum_k mean_{i < T -
    k} CE(logits{k+1}_i, label_{i+k})``: depth ``k`` predicts one token
    further, so its targets are the labels rolled left by ``k`` and its last
    ``k`` positions, which have none, are masked out of its mean. ``weight``
    is DeepSeek-V3's lambda. Scopes ``main`` and ``mtp``."""

    def __init__(self, weight: float = 0.3, batch_axis: int = 0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self.rows = SoftmaxCrossEntropyLoss()

    def _rows(self, pred, label):
        b, t, v = pred.shape
        return self.rows(pred.reshape((b * t, v)), label.reshape((b * t,)))

    def forward(self, pred, label):
        if not isinstance(pred, (tuple, list)):
            return self._rows(pred, label)
        B, T = label.shape
        with jax.named_scope("main"):
            total = nd.mean(self._rows(pred[0], label))
        for k, further in enumerate(pred[1:], start=1):
            with jax.named_scope("mtp"):
                ahead = NDArray(jnp.roll(label.data, -k, axis=1))
                seen = NDArray(jnp.tile(jnp.arange(T) < T - k, B)
                               .astype(jnp.float32))
                rows = self._rows(further, ahead) * seen
                total = total + nd.sum(rows) * (self._weight / (B * (T - k)))
        return total


class KLDivLoss(Loss):
    def __init__(self, from_logits: bool = True, axis: int = -1,
                 weight: Optional[float] = None, batch_axis: int = 0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_logits = from_logits
        self._axis = axis

    def forward(self, pred, label, sample_weight=None):
        if not self._from_logits:
            pred = nd.log_softmax(pred, axis=self._axis)
        loss = label * (nd.log(label + 1e-12) - pred)
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return nd.mean(loss, axis=self._batch_axis, exclude=True)


class HuberLoss(Loss):
    def __init__(self, rho: float = 1.0, weight: Optional[float] = None,
                 batch_axis: int = 0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._rho = rho

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        err = nd.abs(label - pred)
        loss = nd.where(err > self._rho, err - 0.5 * self._rho,
                        0.5 / self._rho * nd.square(err))
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean_all_but_batch(loss)


class HingeLoss(Loss):
    def __init__(self, margin: float = 1.0, weight: Optional[float] = None,
                 batch_axis: int = 0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        loss = nd.relu(self._margin - pred * label)
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean_all_but_batch(loss)


class SquaredHingeLoss(Loss):
    def __init__(self, margin: float = 1.0, weight: Optional[float] = None,
                 batch_axis: int = 0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        loss = nd.square(nd.relu(self._margin - pred * label))
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean_all_but_batch(loss)


class LogisticLoss(Loss):
    def __init__(self, label_format: str = "signed", weight: Optional[float] = None,
                 batch_axis: int = 0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._fmt = label_format

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        if self._fmt == "binary":
            label = 2 * label - 1
        loss = nd.softrelu(-pred * label)
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return self._mean_all_but_batch(loss)


class TripletLoss(Loss):
    def __init__(self, margin: float = 1.0, weight: Optional[float] = None,
                 batch_axis: int = 0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def forward(self, pred, positive, negative, sample_weight=None):
        pos = nd.sum(nd.square(pred - positive), axis=self._batch_axis, exclude=True)
        neg = nd.sum(nd.square(pred - negative), axis=self._batch_axis, exclude=True)
        loss = nd.relu(pos - neg + self._margin)
        return _apply_weighting(loss, self._weight, sample_weight)


class PoissonNLLLoss(Loss):
    def __init__(self, from_logits: bool = True, compute_full: bool = False,
                 weight: Optional[float] = None, batch_axis: int = 0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._from_logits = from_logits
        self._compute_full = compute_full

    def forward(self, pred, label, sample_weight=None):
        label = _reshape_like(pred, label)
        if self._from_logits:
            loss = nd.exp(pred) - label * pred
        else:
            loss = pred - label * nd.log(pred + 1e-8)
        if self._compute_full:
            stirling = (label * nd.log(label + 1e-12) - label
                        + 0.5 * nd.log(2 * 3.14159265 * (label + 1e-12)))
            loss = loss + nd.where(label > 1, stirling, nd.zeros_like(label))
        loss = _apply_weighting(loss, self._weight, sample_weight)
        return nd.mean(loss)


class CosineEmbeddingLoss(Loss):
    def __init__(self, weight: Optional[float] = None, batch_axis: int = 0,
                 margin: float = 0.0, **kwargs):
        super().__init__(weight, batch_axis, **kwargs)
        self._margin = margin

    def forward(self, input1, input2, label, sample_weight=None):
        num = nd.sum(input1 * input2, axis=-1)
        den = nd.sqrt(nd.sum(nd.square(input1), axis=-1)
                      * nd.sum(nd.square(input2), axis=-1) + 1e-12)
        cos = num / den
        pos = 1 - cos
        neg = nd.relu(cos - self._margin)
        loss = nd.where(label == 1, pos, neg)
        return _apply_weighting(loss, self._weight, sample_weight)


class CTCLoss(Loss):
    """Connectionist temporal classification (loss.py CTCLoss → contrib.ctc_loss op).

    Layout follows the reference default NTC; labels (N, L) with 0 reserved for blank.
    """

    def __init__(self, layout: str = "NTC", label_layout: str = "NT",
                 weight: Optional[float] = None, **kwargs):
        super().__init__(weight, batch_axis=0, **kwargs)
        self._layout = layout
        self._label_layout = label_layout

    def forward(self, pred, label, pred_lengths=None, label_lengths=None,
                sample_weight=None):
        if self._layout == "NTC":
            pred = pred.swapaxes(0, 1)  # -> (T, N, C)
        if self._label_layout == "TN":
            label = label.swapaxes(0, 1)  # -> (N, L)
        T, N = pred.shape[0], pred.shape[1]
        if label_lengths is None:
            lab = label.data.astype(jnp.int32)
            label_lengths = NDArray(jnp.sum(lab > 0, axis=1).astype(jnp.int32))
        if pred_lengths is None:
            pred_lengths = NDArray(jnp.full((N,), T, jnp.int32))
        loss = nd.contrib.ctc_loss(pred, label, pred_lengths, label_lengths)
        return _apply_weighting(loss, self._weight, sample_weight)
