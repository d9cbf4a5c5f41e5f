"""What the plain references share: the layer equations as published, in
``jax.numpy`` and float32, and the training loop that follows the
program's first steps.  Nothing here imports the program.

``numerics`` names how the matrix operands are rounded before each
convolution and dense product (the products themselves always accumulate
in float32 at ``highest`` precision, as the MXU accumulates):

  float32   as they are: the reference proper
  bfloat16  rounded to bfloat16: what the configurations state
  float8    scaled to the tensor's largest magnitude and rounded to 4
            exponent and 3 mantissa bits: the control, the nearest
            precision below
  <one of the two>_mxu  the cotangents rounded as well

Rounding is straight-through for the gradient, as low-precision training
recipes have it.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F8_MAX = 240.0  # largest finite value with 4 exponent and 3 mantissa bits (IEEE style)


def _round_to(numerics: str):
    """Rounding by ``lax.reduce_precision``, which the compiler keeps: a
    cast down and up again it may drop as excess precision, and on the
    TPU it does (PERF.md, PR 25)."""
    if numerics == "bfloat16":
        return lambda x: lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    if numerics == "float8":
        def r(x):
            s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
            return lax.reduce_precision(x / s, exponent_bits=4, mantissa_bits=3) * s
        return r
    m = re.fullmatch(r"e(\d+)m(\d+)", numerics)   # any other width, for a look
    if m:
        e, mant = int(m.group(1)), int(m.group(2))
        return lambda x: lax.reduce_precision(x, exponent_bits=e, mantissa_bits=mant)
    raise ValueError(f"unknown numerics {numerics!r}")


def rounder(numerics: str):
    """(rnd, ct): ``rnd`` rounds a product's operand (straight-through
    for the gradient); ``ct`` leaves a product's result alone and rounds
    the cotangent that comes back through it, so that the two backward
    products see rounded operands too, as they do on the MXU.  ``ct``
    rounds only under the ``*_mxu`` numerics."""
    ident = lambda x: x  # noqa: E731
    if numerics == "float32":
        return ident, ident
    base, _, mxu = numerics.partition("_")
    r = _round_to(base)
    rnd = lambda x: x + lax.stop_gradient(r(x) - x)  # noqa: E731
    if not mxu:
        return rnd, ident

    @jax.custom_vjp
    def ct(y):
        return y
    ct.defvjp(lambda y: (y, None), lambda _, g: (r(g),))
    return rnd, ct


def conv2d(x, w, b, stride, pad, rnd):
    """x [N,C,H,W], w [O,I,kh,kw] (cross-correlation, as every framework)."""
    y = lax.conv_general_dilated(
        rnd[0](x), rnd[0](w), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST)
    return rnd[1](y) + b[None, :, None, None]


def dense(x, w, b, rnd):
    return rnd[1](jnp.dot(rnd[0](x), rnd[0](w), precision=HIGHEST)) + b


def max_pool(x, k, stride, pad=0):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 1, k, k), (1, 1, stride, stride),
        [(0, 0), (0, 0), (pad, pad), (pad, pad)])


def batch_norm(x, gamma, beta, eps):
    """Training-mode batch normalisation over N, H, W (Ioffe & Szegedy
    2015): the batch's own mean and biased variance."""
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    xn = (x - mean) / jnp.sqrt(var + eps)
    return gamma[None, :, None, None] * xn + beta[None, :, None, None]


def softmax_xent(logits, onehot):
    """Mean over rows of -sum(y * log softmax(z))."""
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    return -jnp.mean(jnp.sum(onehot * logp, axis=-1))


def he_normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * jnp.sqrt(2.0 / fan_in)


def small_normal(key, shape, scale, mean=0.0):
    return mean + scale * jax.random.normal(key, shape, jnp.float32)


def leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


def follow(loss_fn, params, batches, lr, mu, row_blocks=1, rows=None):
    """Train ``len(batches)`` steps from ``params`` and return what the
    comparison reads: each step's loss, the first gradient with its
    per-leaf norms, and the per-leaf norm of the parameters' change over
    all the steps.

    The updater is Nesterov momentum as Sutskever et al. 2013 write it
    for the parameters one keeps: v' = mu v - lr g;  p' = p + mu v' - lr g.
    ``loss_fn(params, x, y) -> mean loss over the rows``.  With
    ``row_blocks`` > 1 the rows are taken in that many equal blocks and
    the block gradients averaged, which is exact where no layer mixes
    rows.  ``rows`` keeps only the first so many rows of each batch (the
    half-batch fault of the benchmark's tests).
    """
    grad = jax.jit(jax.value_and_grad(loss_fn))
    tm = jax.tree_util.tree_map

    @jax.jit
    def update(p, v, g):
        v2 = tm(lambda b, c: mu * b - lr * c, v, g)
        return tm(lambda a, b2, c: a + mu * b2 - lr * c, p, v2, g), v2

    @jax.jit
    def accumulate(acc, g):
        return tm(lambda a, b: a + b / row_blocks, acc, g)

    p0 = params
    p = params
    v = tm(jnp.zeros_like, params)
    losses, g1, first = [], None, None
    for x, y in batches:
        x, y = jnp.asarray(x), jnp.asarray(y)
        if rows is not None:
            x, y = x[:rows], y[:rows]
        n = x.shape[0] // row_blocks
        loss, g = 0.0, None
        for i in range(row_blocks):
            li, gi = grad(p, x[i * n:(i + 1) * n], y[i * n:(i + 1) * n])
            loss = loss + li / row_blocks
            g = (tm(lambda a: a / row_blocks, gi) if g is None
                 else accumulate(g, gi))
        losses.append(float(loss))
        if g1 is None:
            g1, first = jax.device_get(leaf_norms(g)), jax.device_get(g)
        p, v = update(p, v, g)
        del g
    dp = jax.device_get(jax.jit(
        lambda a, b: leaf_norms(tm(lambda s, t: s - t, a, b)))(p, p0))
    return {"losses": losses, "grad_norms": g1, "change_norms": dp,
            "first_grad": first}
