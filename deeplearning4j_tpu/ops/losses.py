"""Loss functions — the reference's ``ILossFunction`` surface.

The reference's loss set (nd4j ILossFunction impls, exercised by
deeplearning4j-core's LossFunctionGradientCheck.java): MSE, L1, L2,
XENT (binary cross-entropy), MCXENT (multi-class cross-entropy),
NEGATIVELOGLIKELIHOOD, COSINE_PROXIMITY, HINGE, SQUARED_HINGE,
KL_DIVERGENCE, MEAN_ABSOLUTE_ERROR, MEAN_ABSOLUTE_PERCENTAGE_ERROR,
MEAN_SQUARED_LOGARITHMIC_ERROR, POISSON.

Each loss takes ``(labels, preoutput, activation_name, mask)`` and returns
per-example scores of shape [N].  Working on pre-activations lets the
softmax+cross-entropy and sigmoid+binary-cross-entropy pairs lower to the
numerically-stable fused forms, which XLA then fuses into one kernel; the
gradient comes from jax.grad of the whole jitted step rather than the
reference's hand-written computeGradient methods.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops import activations

EPS = 1e-7

LossFn = Callable[..., jnp.ndarray]


def _activate(preout: jnp.ndarray, activation: str) -> jnp.ndarray:
    return activations.get(activation)(preout)


def _reduce_features(per_elem: jnp.ndarray, mask: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Sum per-element losses over all non-batch axes → per-example score [N]."""
    if mask is not None:
        per_elem = per_elem * mask
    axes = tuple(range(1, per_elem.ndim))
    return jnp.sum(per_elem, axis=axes) if axes else per_elem


def mse(labels, preout, activation="identity", mask=None):
    out = _activate(preout, activation)
    return _reduce_features(jnp.square(out - labels), mask) / labels.shape[-1]


def l2(labels, preout, activation="identity", mask=None):
    out = _activate(preout, activation)
    return _reduce_features(jnp.square(out - labels), mask)


def l1(labels, preout, activation="identity", mask=None):
    out = _activate(preout, activation)
    return _reduce_features(jnp.abs(out - labels), mask)


def mae(labels, preout, activation="identity", mask=None):
    out = _activate(preout, activation)
    return _reduce_features(jnp.abs(out - labels), mask) / labels.shape[-1]


def xent(labels, preout, activation="sigmoid", mask=None):
    """Binary cross-entropy.  Stable fused path when activation is sigmoid."""
    if activation == "sigmoid":
        # -[y*log σ(x) + (1-y)*log(1-σ(x))] = max(x,0) - x*y + log(1+exp(-|x|))
        per = jnp.maximum(preout, 0) - preout * labels + jnp.log1p(jnp.exp(-jnp.abs(preout)))
    else:
        out = jnp.clip(_activate(preout, activation), EPS, 1.0 - EPS)
        per = -(labels * jnp.log(out) + (1.0 - labels) * jnp.log(1.0 - out))
    return _reduce_features(per, mask)


def _fused_xent_wanted(labels, preout, mask) -> bool:
    """Dispatch gate for the Pallas fused softmax+CE kernel
    (ops/pallas_kernels.softmax_xent_rows): shape/mask legality decided
    here (only row-level masks — a per-class mask needs the elementwise
    path); platform/size selection delegated to the helper tier
    (ops/helpers.softmax_xent_wanted, which also meters the decision and
    honors the DL4J_FUSED_XENT=1|0 test override)."""
    if preout.ndim < 2 or (preout.shape != labels.shape
                           and not _class_ids(labels, preout)):
        return False
    if mask is not None and mask.ndim == preout.ndim \
            and mask.shape[-1] == preout.shape[-1] and preout.shape[-1] != 1:
        return False  # genuine per-class mask
    from deeplearning4j_tpu.ops import helpers
    V = preout.shape[-1]
    n_rows = 1
    for d in preout.shape[:-1]:
        n_rows *= d
    return helpers.softmax_xent_wanted(n_rows, V)


def _class_ids(labels, preout) -> bool:
    """Are the labels integer class ids, one a row of ``preout``?"""
    return (jnp.issubdtype(labels.dtype, jnp.integer)
            and labels.shape == preout.shape[:-1])


def mcxent_id_rows(ids, preout, activation="softmax", mask=None):
    """The cross-entropy of every row, [...], of integer class ids [...]
    against preout [..., V]: what ``mcxent`` sums, through the tier the
    registry selects (``mask`` only tells the selection that no
    per-class mask is in play; it is not applied).  An id outside
    [0, V) is a row without a label: it scores 0 and sends no gradient,
    as an all-zero one-hot row does."""
    V = preout.shape[-1]
    if activation == "softmax" and _fused_xent_wanted(ids, preout, mask):
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        return pk.softmax_xent_rows(
            preout.reshape(-1, V), ids.reshape(-1)).reshape(ids.shape)
    labelled = (ids >= 0) & (ids < V)
    at = jnp.clip(ids, 0, V - 1)[..., None]
    if activation == "softmax":
        logp = jnp.take_along_axis(preout, at, axis=-1)[..., 0] \
            - jax.nn.logsumexp(preout, axis=-1)
    else:
        out = jnp.clip(_activate(preout, activation), EPS, 1.0 - EPS)
        logp = jnp.log(jnp.take_along_axis(out, at, axis=-1)[..., 0])
    return jnp.where(labelled, -logp, 0.0)


def _mcxent_ids(ids, preout, activation, mask):
    """``mcxent`` on integer class ids [...] against preout [..., V]:
    what the one-hot labels would give, without the [..., V] label
    array."""
    return _sum_rows(mcxent_id_rows(ids, preout, activation, mask), mask)


def _sum_rows(rows, mask):
    """Per-example score from per-row losses [N, ...] under a row-level
    mask (an expanded [..., 1] mask is squeezed)."""
    if mask is not None:
        m = mask
        if m.ndim == rows.ndim + 1 and m.shape[-1] == 1:
            m = m[..., 0]
        rows = rows * m
    axes = tuple(range(1, rows.ndim))
    return jnp.sum(rows, axis=axes) if axes else rows


def mcxent(labels, preout, activation="softmax", mask=None):
    """Multi-class cross-entropy.  Stable fused path when activation is
    softmax; above the size threshold the softmax+CE+grad runs as one
    Pallas VMEM pass (ref analog: the fused libnd4j SoftMaxWithLoss op).
    Labels are rows of class weights shaped like ``preout``, or integer
    class ids with one axis less."""
    if _class_ids(labels, preout):
        return _mcxent_ids(labels, preout, activation, mask)
    if activation == "softmax":
        if _fused_xent_wanted(labels, preout, mask):
            from deeplearning4j_tpu.ops import pallas_kernels as pk
            V = preout.shape[-1]
            rows = pk.softmax_xent_rows(
                preout.reshape(-1, V), labels.reshape(-1, V)
            ).reshape(labels.shape[:-1])
            return _sum_rows(rows, mask)
        logz = jax.nn.logsumexp(preout, axis=-1, keepdims=True)
        per = -labels * (preout - logz)
    else:
        out = jnp.clip(_activate(preout, activation), EPS, 1.0 - EPS)
        per = -labels * jnp.log(out)
    return _reduce_features(per, mask)


def negativeloglikelihood(labels, preout, activation="softmax", mask=None):
    # In the reference NLL == MCXENT when paired with softmax output.
    return mcxent(labels, preout, activation, mask)


def cosine_proximity(labels, preout, activation="identity", mask=None):
    out = _activate(preout, activation)
    if mask is not None:
        out = out * mask
        labels = labels * mask
    dot = jnp.sum(labels * out, axis=-1)
    nl = jnp.linalg.norm(labels, axis=-1)
    no = jnp.linalg.norm(out, axis=-1)
    cos = dot / jnp.maximum(nl * no, EPS)
    per = -cos
    axes = tuple(range(1, per.ndim))
    return jnp.sum(per, axis=axes) if axes else per


def hinge(labels, preout, activation="identity", mask=None):
    # labels expected in {-1, +1}
    out = _activate(preout, activation)
    return _reduce_features(jnp.maximum(0.0, 1.0 - labels * out), mask)


def squared_hinge(labels, preout, activation="identity", mask=None):
    out = _activate(preout, activation)
    return _reduce_features(jnp.square(jnp.maximum(0.0, 1.0 - labels * out)), mask)


def kl_divergence(labels, preout, activation="softmax", mask=None):
    out = jnp.clip(_activate(preout, activation), EPS, 1.0)
    lab = jnp.clip(labels, EPS, 1.0)
    return _reduce_features(labels * (jnp.log(lab) - jnp.log(out)), mask)


def mape(labels, preout, activation="identity", mask=None):
    out = _activate(preout, activation)
    per = 100.0 * jnp.abs((labels - out) / jnp.maximum(jnp.abs(labels), EPS))
    return _reduce_features(per, mask) / labels.shape[-1]


def msle(labels, preout, activation="identity", mask=None):
    out = _activate(preout, activation)
    per = jnp.square(jnp.log1p(jnp.maximum(out, -1 + EPS)) - jnp.log1p(jnp.maximum(labels, -1 + EPS)))
    return _reduce_features(per, mask) / labels.shape[-1]


def poisson(labels, preout, activation="identity", mask=None):
    out = jnp.maximum(_activate(preout, activation), EPS)
    return _reduce_features(out - labels * jnp.log(out), mask)


_REGISTRY: dict[str, LossFn] = {
    "mse": mse,
    "squared_loss": mse,
    "l1": l1,
    "l2": l2,
    "mae": mae,
    "mean_absolute_error": mae,
    "xent": xent,
    "mcxent": mcxent,
    "negativeloglikelihood": negativeloglikelihood,
    "nll": negativeloglikelihood,
    "cosine_proximity": cosine_proximity,
    "hinge": hinge,
    "squared_hinge": squared_hinge,
    "kl_divergence": kl_divergence,
    "reconstruction_crossentropy": xent,
    "mean_absolute_percentage_error": mape,
    "mape": mape,
    "mean_squared_logarithmic_error": msle,
    "msle": msle,
    "poisson": poisson,
}


def get(name: str) -> LossFn:
    try:
        return _REGISTRY[name.lower()]
    except KeyError:
        raise ValueError(f"Unknown loss '{name}'. Known: {sorted(_REGISTRY)}") from None


def register(name: str, fn: LossFn) -> None:
    _REGISTRY[name.lower()] = fn


def unregister(name: str) -> None:
    """Remove a user-registered loss (no-op when absent)."""
    _REGISTRY.pop(name.lower(), None)


def names() -> list[str]:
    return sorted(_REGISTRY)
