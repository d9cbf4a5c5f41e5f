"""Convolution and pooling primitives.

The reference lowers conv through cuDNN or im2col+gemm
(ref: nn/layers/convolution/ConvolutionLayer.java:171-212, im2col at
Convolution.im2col).  On TPU the idiomatic lowering is a single
``lax.conv_general_dilated`` HLO which XLA tiles directly onto the MXU —
no im2col materialization, and elementwise bias+activation fuse into the
same kernel.  Data layout is NCHW at the API surface (reference
convention); weights are OIHW ([out, in, kh, kw], matching
ConvolutionParamInitializer).
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.ops import dtypes as dtype_ops

_DIMNUMS = ("NCHW", "OIHW", "NCHW")


def _nhwc_internal() -> bool:
    """DL4J_CONV_LAYOUT=nhwc runs the conv HLO in channels-last layout
    (inputs/weights transposed at the op boundary, NCHW preserved at the
    API surface).  TPU conv tiling generally prefers NHWC; whether XLA's
    layout assignment already absorbs the logical-NCHW cost has no trial
    on the chip yet.  Read at TRACE time: flip it before building a
    model, not between steps of an already-jitted one."""
    import os
    return os.environ.get("DL4J_CONV_LAYOUT", "").lower() == "nhwc"  # dl4j: noqa[DL4J103] env flag read at trace time by design (fixed per process)


def _same_pad(kernel: Sequence[int], stride: Sequence[int], pad: Sequence[int],
              mode: str) -> list[Tuple[int, int]]:
    if mode == "same":
        return "SAME"
    return [(pad[0], pad[0]), (pad[1], pad[1])]


def conv2d(x, w, b=None, stride=(1, 1), pad=(0, 0), dilation=(1, 1),
           border_mode: str = "truncate", accum_dtype=None):
    """2D convolution, NCHW in / OIHW weights.

    border_mode: 'truncate' (explicit pad, the reference's Truncate) or
    'same' (the reference's ConvolutionMode.Same).  MXU accumulation is
    float32 for low-precision inputs (bf16 compute / f32 accumulate);
    float64 inputs (gradient checks on CPU) accumulate in f64.
    """
    if accum_dtype is None:
        accum_dtype = dtype_ops.accum_dtype_for(x.dtype)
    padding = _same_pad(w.shape[2:], stride, pad, "same" if border_mode == "same" else "explicit")
    nhwc = _nhwc_internal()
    if nhwc:
        x = jnp.transpose(x, (0, 2, 3, 1))        # NCHW → NHWC
        w = jnp.transpose(w, (2, 3, 1, 0))        # OIHW → HWIO
    y = lax.conv_general_dilated(
        x, w,
        window_strides=tuple(stride),
        padding=padding,
        rhs_dilation=tuple(dilation),
        dimension_numbers=("NHWC", "HWIO", "NHWC") if nhwc else _DIMNUMS,
        preferred_element_type=accum_dtype,
    )
    if b is not None:
        y = y + (b.reshape(1, 1, 1, -1) if nhwc else b.reshape(1, -1, 1, 1))
    if nhwc:
        y = jnp.transpose(y, (0, 3, 1, 2))        # back to the NCHW API
    return y.astype(x.dtype)


def conv2d_output_shape(in_hw, kernel, stride, pad, dilation=(1, 1),
                        border_mode: str = "truncate"):
    if border_mode == "same":
        return tuple(-(-d // s) for d, s in zip(in_hw, stride))
    out = []
    for d, k, s, p, dl in zip(in_hw, kernel, stride, pad, dilation):
        eff_k = (k - 1) * dl + 1
        out.append((d + 2 * p - eff_k) // s + 1)
    return tuple(out)


def pool2d(x, kind: str, kernel=(2, 2), stride=(2, 2), pad=(0, 0),
           border_mode: str = "truncate", pnorm: int = 2):
    """Pooling over NCHW spatial dims: max | avg | sum | pnorm.

    Matches the reference's SubsamplingLayer pooling types
    (ref: nn/layers/convolution/subsampling/SubsamplingLayer.java:76).
    """
    window = (1, 1, kernel[0], kernel[1])
    strides = (1, 1, stride[0], stride[1])
    if border_mode == "same":
        padding = "SAME"
    else:
        padding = [(0, 0), (0, 0), (pad[0], pad[0]), (pad[1], pad[1])]
    kind = kind.lower()
    if kind == "max":
        neg_inf = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, neg_inf, lax.max, window, strides, padding)
    if kind in ("avg", "mean"):
        summed = lax.reduce_window(x, 0.0, lax.add, window, strides, padding)
        if padding == "SAME":
            ones = jnp.ones_like(x)
            counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, padding)
            return summed / counts
        return summed / (kernel[0] * kernel[1])
    if kind == "sum":
        return lax.reduce_window(x, 0.0, lax.add, window, strides, padding)
    if kind == "pnorm":
        p = float(pnorm)
        summed = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, window, strides, padding)
        return summed ** (1.0 / p)
    raise ValueError(f"Unknown pooling type '{kind}'")


def conv1d(x, w, b=None, stride=1, pad=0, dilation=1,
           border_mode: str = "truncate", accum_dtype=None):
    """1D convolution over sequences [N, T, C] with weights [K, C_in, C_out]
    (ref: nn/conf/layers/Convolution1DLayer.java — operates on RNN-format
    data).  One conv HLO on the MXU; NWC layout is TPU-friendly (channels
    minor → lane dimension)."""
    if accum_dtype is None:
        accum_dtype = dtype_ops.accum_dtype_for(x.dtype)
    padding = "SAME" if border_mode == "same" else [(pad, pad)]
    y = lax.conv_general_dilated(
        x, w,
        window_strides=(stride,),
        padding=padding,
        rhs_dilation=(dilation,),
        dimension_numbers=("NWC", "WIO", "NWC"),
        preferred_element_type=accum_dtype,
    )
    if b is not None:
        y = y + b.reshape(1, 1, -1)
    return y.astype(x.dtype)


def pool1d(x, kind: str, kernel=2, stride=2, pad=0,
           border_mode: str = "truncate", pnorm: int = 2):
    """1D pooling over [N, T, C]
    (ref: nn/conf/layers/Subsampling1DLayer.java).  Delegates to pool2d on
    a [N, C, T, 1] view — the transposes are layout-only and fuse away."""
    x2 = jnp.transpose(x, (0, 2, 1))[..., None]
    y2 = pool2d(x2, kind, (kernel, 1), (stride, 1), (pad, 0),
                border_mode, pnorm)
    return jnp.transpose(y2[..., 0], (0, 2, 1))


def conv1d_output_len(t, kernel, stride, pad, dilation=1,
                      border_mode: str = "truncate"):
    if border_mode == "same":
        return -(-t // stride)
    eff_k = (kernel - 1) * dilation + 1
    return (t + 2 * pad - eff_k) // stride + 1


def zero_pad2d(x, pad_top, pad_bottom, pad_left, pad_right):
    """ZeroPaddingLayer (ref: nn/conf/layers/ZeroPaddingLayer)."""
    return jnp.pad(x, ((0, 0), (0, 0), (pad_top, pad_bottom), (pad_left, pad_right)))


def global_pool(x, kind: str, axes, pnorm: int = 2, mask=None):
    """GlobalPoolingLayer semantics (ref: nn/layers/pooling/GlobalPoolingLayer.java).

    axes: the dims to reduce (e.g. (2,3) for CNN NCHW, (2,) for RNN [N,C,T]).
    mask: optional broadcastable mask (1=keep) for variable-length inputs —
    matches MaskedReductionUtil semantics.
    """
    kind = kind.lower()
    if mask is not None:
        mask = mask.astype(x.dtype)
        if kind == "max":
            x = jnp.where(mask > 0, x, -jnp.inf)
        else:
            x = x * mask
    if kind == "max":
        return jnp.max(x, axis=axes)
    if kind == "sum":
        return jnp.sum(x, axis=axes)
    if kind in ("avg", "mean"):
        if mask is not None:
            denom = jnp.sum(mask, axis=axes)
            return jnp.sum(x, axis=axes) / jnp.maximum(denom, 1e-8)
        return jnp.mean(x, axis=axes)
    if kind == "pnorm":
        p = float(pnorm)
        return jnp.sum(jnp.abs(x) ** p, axis=axes) ** (1.0 / p)
    raise ValueError(f"Unknown global pooling type '{kind}'")
