"""Layer configuration types — the reference's ``nn/conf/layers`` surface.

Each config is a dataclass that is simultaneously (a) the JSON-serializable
hyperparameter record (parity with the reference's Jackson-polymorphic layer
configs, ref: nn/conf/layers/*.java) and (b) the functional layer
implementation: ``initialize`` builds the param/state pytrees,
``forward`` is the pure apply.  Unlike the reference's Layer impl class
hierarchy with mutable param views (ref: nn/layers/BaseLayer.java), there
is no separate impl object — the whole forward pass composes into one
traced function that XLA compiles and fuses.

Custom layers register via ``@register_layer`` (the analog of the
reference's classpath-scanned subtype registration,
ref: nn/conf/NeuralNetConfiguration.java:340-367).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.ops import activations as act_ops
from deeplearning4j_tpu.ops import convolution as conv_ops
from deeplearning4j_tpu.ops import helpers as helper_ops
from deeplearning4j_tpu.ops import initializers
from deeplearning4j_tpu.ops import losses as loss_ops
from deeplearning4j_tpu.ops import mask_rules
from deeplearning4j_tpu.ops import normalization as norm_ops
from deeplearning4j_tpu.ops import recompute
from deeplearning4j_tpu.ops import recurrent as rnn_ops
from deeplearning4j_tpu.ops import row_segments

LAYER_REGISTRY: Dict[str, type] = {}


def register_layer(cls):
    LAYER_REGISTRY[cls.__name__] = cls
    return cls


def field(default=None, **kw):
    return dataclasses.field(default=default, **kw)


@dataclasses.dataclass
class Layer:
    """Base hyperparameters every layer config can carry.

    ``None`` means "inherit from the global NeuralNetConfiguration" —
    mirroring the reference's global-conf-then-per-layer-override merge
    (ref: NeuralNetConfiguration.Builder.layer handling).
    ``dropout`` is the RETAIN probability as in the reference 0.8.x
    (0.0 = disabled; ref: util/Dropout.java).
    """

    name: Optional[str] = None
    activation: Optional[str] = None
    weight_init: Optional[str] = None
    bias_init: Optional[float] = None
    dist: Optional[dict] = None
    learning_rate: Optional[float] = None
    bias_learning_rate: Optional[float] = None
    l1: Optional[float] = None
    l2: Optional[float] = None
    l1_bias: Optional[float] = None
    l2_bias: Optional[float] = None
    dropout: Optional[float] = None
    use_drop_connect: Optional[bool] = None
    updater: Optional[str] = None
    momentum: Optional[float] = None
    rho: Optional[float] = None
    rms_decay: Optional[float] = None
    adam_mean_decay: Optional[float] = None
    adam_var_decay: Optional[float] = None
    epsilon: Optional[float] = None
    gradient_normalization: Optional[str] = None
    gradient_normalization_threshold: Optional[float] = None

    # ---- what monitor/profile.py reads of a layer (no fields) ----
    #: the parts the layer names (``jax.named_scope``) inside its own
    #: scope, summed as ``sub_scope_s``
    scope_parts = ()
    #: {prefix of a kernel's name: part} for operations of a part that
    #: the compiler expands into kernels it names itself, dropping the
    #: scope; a prefix belongs to one layer type
    scope_kernels = {}
    #: True on an output layer that owns its score from its INPUT
    #: (``score_from_input``): the graph engine asks it for no
    #: pre-activations, which then never exist whole
    scores_from_input = False

    # ---- capability flags ----
    def has_params(self) -> bool:
        return True

    def master_leaves(self) -> Tuple[str, ...]:
        """Parameter leaves the training step hands this layer in their
        master dtype under a mixed-precision policy
        (``Policy.cast_params_to_compute``): the layer casts them itself,
        or its kernels do, a block at a time in VMEM."""
        return ()

    def is_pretrain_layer(self) -> bool:
        return False

    # ---- functional API ----
    def initialize(self, key, input_type: InputType, dtype=jnp.float32
                   ) -> Tuple[dict, dict, InputType]:
        raise NotImplementedError

    def forward(self, params: dict, state: dict, x, *, train: bool, rng,
                mask=None) -> Tuple[Any, dict, Any]:
        raise NotImplementedError

    def output_type(self, input_type: InputType) -> InputType:
        raise NotImplementedError

    # ---- shared helpers ----
    def _act(self, x):
        return act_ops.get(self.activation or "identity")(x)

    def _maybe_dropout(self, x, train: bool, rng):
        # DropConnect reuses the dropout probability on WEIGHTS instead of
        # activations — mutually exclusive with input dropout (ref:
        # util/Dropout.java applyDropConnect vs applyDropout; BaseLayer
        # applies one or the other depending on conf.isUseDropConnect())
        if self.use_drop_connect:
            return x
        if train and self.dropout and 0.0 < self.dropout < 1.0 and rng is not None:
            # helper selection (ops/helpers.py): in-kernel threshold
            # dropout on TPU, jax.random.bernoulli fallback elsewhere
            return helper_ops.dropout(x, self.dropout, rng)
        return x

    def _maybe_drop_connect(self, params: dict, train: bool, rng):
        """DropConnect (Wan et al.; ref: util/Dropout.java:applyDropConnect):
        zero each weight with retain probability ``dropout``, inverted
        scaling, leaving biases intact."""
        if not (self.use_drop_connect and train and self.dropout
                and 0.0 < self.dropout < 1.0 and rng is not None and
                "W" in params):
            return params
        return {**params,
                "W": norm_ops.dropout(params["W"], self.dropout,
                                      jax.random.fold_in(rng, 0x0D20))}

    def _winit(self, key, shape, dtype, fan_in=None, fan_out=None):
        return initializers.init(
            key, self.weight_init or "xavier", shape, dtype,
            fan_in=fan_in, fan_out=fan_out, distribution=self.dist)

    def _binit(self, shape, dtype):
        return jnp.full(shape, self.bias_init or 0.0, dtype)

    # ---- serde ----
    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["@class"] = type(self).__name__
        return d

    @staticmethod
    def from_dict(d: dict) -> "Layer":
        d = dict(d)
        cls = LAYER_REGISTRY[d.pop("@class")]
        return cls(**d)


# ==========================================================================
# Feed-forward layers
# ==========================================================================

@register_layer
@dataclasses.dataclass
class DenseLayer(Layer):
    """Fully connected: y = act(x @ W + b)
    (ref: nn/conf/layers/DenseLayer.java; impl nn/layers/BaseLayer.java:373)."""

    n_in: Optional[int] = None
    n_out: int = 0
    bias: bool = True       # False: y = act(x @ W), no "b" leaf

    def _affine_params(self, key, n_in, dtype):
        kW, _ = jax.random.split(key)
        params = {"W": self._winit(kW, (n_in, self.n_out), dtype)}
        if self.bias:
            params["b"] = self._binit((self.n_out,), dtype)
        return params

    def initialize(self, key, input_type, dtype=jnp.float32):
        n_in = self.n_in or input_type.flat_size()
        return (self._affine_params(key, n_in, dtype), {},
                InputType.feed_forward(self.n_out))

    def forward(self, params, state, x, *, train, rng, mask=None):
        x = self._maybe_dropout(x, train, rng)
        p = self._maybe_drop_connect(params, train, rng)
        return self._act(_affine(p, x)), state, mask

    def output_type(self, input_type):
        return InputType.feed_forward(self.n_out)


def _affine(params, x):
    """x @ W, plus b where the layer has one."""
    y = x @ params["W"]
    return y + params["b"] if "b" in params else y


RMS_EPS = 1e-5


def _rms_norm(x, gamma, eps=RMS_EPS):
    """x / sqrt(mean(x^2) + eps) * gamma over the last axis, the
    statistics in float32 (float64 under a gradient check) whatever the
    compute dtype."""
    xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                           + eps)
    return y.astype(x.dtype) * gamma


@dataclasses.dataclass
class BaseOutputLayer(DenseLayer):
    """Shared loss machinery for output layers
    (ref: nn/layers/BaseOutputLayer computeScore)."""

    loss: str = "mcxent"

    def compute_score(self, labels, preout, mask=None):
        """Per-example loss [N] from pre-activations (stable fused path)."""
        return loss_ops.get(self.loss)(labels, preout,
                                       self.activation or "softmax", mask)

    def preoutput(self, params, x):
        return _affine(params, x)

    def score_from_input(self, params, state, x, labels, mask=None):
        """(per-example loss [N], new state) from the layer's input, for a
        layer that sets ``scores_from_input``."""
        raise NotImplementedError


@register_layer
@dataclasses.dataclass
class OutputLayer(BaseOutputLayer):
    """Dense + loss head (ref: nn/conf/layers/OutputLayer.java)."""


@register_layer
@dataclasses.dataclass
class LossLayer(Layer):
    """Loss without params: activation + loss on raw input
    (ref: nn/conf/layers/LossLayer.java)."""

    loss: str = "mcxent"

    def has_params(self):
        return False

    def initialize(self, key, input_type, dtype=jnp.float32):
        return {}, {}, input_type

    def forward(self, params, state, x, *, train, rng, mask=None):
        return self._act(x), state, mask

    def output_type(self, input_type):
        return input_type

    def compute_score(self, labels, preout, mask=None):
        return loss_ops.get(self.loss)(labels, preout,
                                       self.activation or "identity", mask)

    def preoutput(self, params, x):
        return x


@register_layer
@dataclasses.dataclass
class ActivationLayer(Layer):
    """Pure activation (ref: nn/conf/layers/ActivationLayer.java)."""

    def has_params(self):
        return False

    def initialize(self, key, input_type, dtype=jnp.float32):
        return {}, {}, input_type

    def forward(self, params, state, x, *, train, rng, mask=None):
        return self._act(x), state, mask

    def output_type(self, input_type):
        return input_type


@register_layer
@dataclasses.dataclass
class DropoutLayer(Layer):
    """Standalone dropout (ref: nn/conf/layers/DropoutLayer.java)."""

    def has_params(self):
        return False

    def initialize(self, key, input_type, dtype=jnp.float32):
        return {}, {}, input_type

    def forward(self, params, state, x, *, train, rng, mask=None):
        return self._maybe_dropout(x, train, rng), state, mask

    def output_type(self, input_type):
        return input_type


@register_layer
@dataclasses.dataclass
class EmbeddingLayer(Layer):
    """Index → embedding row lookup; input is int indices [N] or one-hot
    (ref: nn/layers/feedforward/embedding/EmbeddingLayer.java — mathematically
    a dense layer with one-hot input; here a gather, which XLA lowers to a
    dynamic-slice on TPU).  Sequence form: int ids [N, T] under a
    recurrent input type (``InputType.recurrent(vocab, T)``) give
    [N, T, n_out]."""

    n_in: Optional[int] = None  # vocab size
    n_out: int = 0
    bias: bool = True

    def initialize(self, key, input_type, dtype=jnp.float32):
        n_in = self.n_in or input_type.flat_size()
        kW, _ = jax.random.split(key)
        params = {"W": self._winit(kW, (n_in, self.n_out), dtype)}
        if self.bias:
            params["b"] = self._binit((self.n_out,), dtype)
        return params, {}, self.output_type(input_type)

    def forward(self, params, state, x, *, train, rng, mask=None):
        if jnp.issubdtype(x.dtype, jnp.integer):
            # [N] and [N, 1] are one index a row; [N, T] is a sequence
            idx = x.reshape(x.shape[0]) if x.ndim > 1 and x.shape[1] == 1 \
                else x
            emb = params["W"][idx]
        else:
            # one-hot [N, vocab] input
            emb = x @ params["W"]
        if "b" in params:
            emb = emb + params["b"]
        return self._act(emb), state, mask

    def output_type(self, input_type):
        if input_type is not None and input_type.kind == "rnn":
            return InputType.recurrent(self.n_out, input_type.timesteps)
        return InputType.feed_forward(self.n_out)


# ==========================================================================
# Convolutional family (NCHW)
# ==========================================================================

@register_layer
@dataclasses.dataclass
class ConvolutionLayer(Layer):
    """2D convolution (ref: nn/conf/layers/ConvolutionLayer.java; impl
    nn/layers/convolution/ConvolutionLayer.java — im2col+gemm replaced by a
    single conv HLO on the MXU).  Weights OIHW [n_out, c_in, kh, kw]."""

    n_in: Optional[int] = None
    n_out: int = 0
    kernel: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    dilation: Tuple[int, int] = (1, 1)
    convolution_mode: str = "truncate"  # 'truncate' | 'same'

    def initialize(self, key, input_type, dtype=jnp.float32):
        c_in = self.n_in or input_type.channels
        kh, kw = self.kernel
        fan_in = c_in * kh * kw
        fan_out = self.n_out * kh * kw
        kW, _ = jax.random.split(key)
        params = {
            "W": self._winit(kW, (self.n_out, c_in, kh, kw), dtype,
                             fan_in=fan_in, fan_out=fan_out),
            "b": self._binit((self.n_out,), dtype),
        }
        return params, {}, self.output_type(input_type)

    def forward(self, params, state, x, *, train, rng, mask=None):
        x = self._maybe_dropout(x, train, rng)
        p = self._maybe_drop_connect(params, train, rng)
        y = self._act(conv_ops.conv2d(
            x, p["W"], p["b"], self.stride, self.padding, self.dilation,
            self.convolution_mode))
        return y, state, mask

    def output_type(self, input_type):
        oh, ow = conv_ops.conv2d_output_shape(
            (input_type.height, input_type.width), self.kernel, self.stride,
            self.padding, self.dilation, self.convolution_mode)
        return InputType.convolutional(oh, ow, self.n_out)


@register_layer
@dataclasses.dataclass
class SubsamplingLayer(Layer):
    """Pooling (ref: nn/conf/layers/SubsamplingLayer.java)."""

    pooling_type: str = "max"  # max | avg | sum | pnorm
    kernel: Tuple[int, int] = (2, 2)
    stride: Tuple[int, int] = (2, 2)
    padding: Tuple[int, int] = (0, 0)
    convolution_mode: str = "truncate"
    pnorm: int = 2

    def has_params(self):
        return False

    def initialize(self, key, input_type, dtype=jnp.float32):
        return {}, {}, self.output_type(input_type)

    def forward(self, params, state, x, *, train, rng, mask=None):
        y = conv_ops.pool2d(x, self.pooling_type, self.kernel, self.stride,
                            self.padding, self.convolution_mode, self.pnorm)
        return y, state, mask

    def output_type(self, input_type):
        oh, ow = conv_ops.conv2d_output_shape(
            (input_type.height, input_type.width), self.kernel, self.stride,
            self.padding, (1, 1), self.convolution_mode)
        return InputType.convolutional(oh, ow, input_type.channels)


@register_layer
@dataclasses.dataclass
class ZeroPaddingLayer(Layer):
    """(ref: nn/conf/layers/ZeroPaddingLayer.java)"""

    pad: Tuple[int, int, int, int] = (0, 0, 0, 0)  # top, bottom, left, right

    def has_params(self):
        return False

    def initialize(self, key, input_type, dtype=jnp.float32):
        return {}, {}, self.output_type(input_type)

    def forward(self, params, state, x, *, train, rng, mask=None):
        t, b, l, r = self.pad
        return conv_ops.zero_pad2d(x, t, b, l, r), state, mask

    def output_type(self, input_type):
        t, b, l, r = self.pad
        return InputType.convolutional(input_type.height + t + b,
                                       input_type.width + l + r,
                                       input_type.channels)


@register_layer
@dataclasses.dataclass
class BatchNormalization(Layer):
    """(ref: nn/conf/layers/BatchNormalization.java; impl
    nn/layers/normalization/BatchNormalization.java:228 — BN applies NO
    activation; activation defaults to identity here rather than
    inheriting the global default).  Running statistics are carried in
    the functional `state` pytree instead of mutated."""

    activation: Optional[str] = "identity"
    decay: float = 0.9
    eps: float = 1e-5
    lock_gamma_beta: bool = False
    n_features: Optional[int] = None

    def _nfeat(self, input_type):
        return self.n_features or (
            input_type.channels if input_type.kind == "cnn" else input_type.flat_size())

    def initialize(self, key, input_type, dtype=jnp.float32):
        n = self._nfeat(input_type)
        params = {} if self.lock_gamma_beta else {
            "gamma": jnp.ones((n,), dtype), "beta": jnp.zeros((n,), dtype)}
        state = {"mean": jnp.zeros((n,), dtype), "var": jnp.ones((n,), dtype)}
        return params, state, input_type

    def forward(self, params, state, x, *, train, rng, mask=None):
        n = state["mean"].shape[0]
        gamma = params.get("gamma", jnp.ones((n,), x.dtype))
        beta = params.get("beta", jnp.zeros((n,), x.dtype))
        if train:
            y, m, v = norm_ops.batch_norm_train(
                x, gamma, beta, state["mean"], state["var"],
                decay=self.decay, eps=self.eps)
            return self._act(y), {"mean": m, "var": v}, mask
        y = norm_ops.batch_norm_infer(x, gamma, beta, state["mean"],
                                      state["var"], eps=self.eps)
        return self._act(y), state, mask

    def output_type(self, input_type):
        return input_type


@register_layer
@dataclasses.dataclass
class LocalResponseNormalization(Layer):
    """(ref: nn/layers/normalization/LocalResponseNormalization.java:69)"""

    k: float = 2.0
    n: int = 5
    alpha: float = 1e-4
    beta: float = 0.75

    def has_params(self):
        return False

    def initialize(self, key, input_type, dtype=jnp.float32):
        return {}, {}, input_type

    def forward(self, params, state, x, *, train, rng, mask=None):
        return norm_ops.local_response_norm(
            x, k=self.k, n=self.n, alpha=self.alpha, beta=self.beta), state, mask

    def output_type(self, input_type):
        return input_type


@register_layer
@dataclasses.dataclass
class GlobalPoolingLayer(Layer):
    """Collapse spatial/time dims (ref: nn/layers/pooling/GlobalPoolingLayer.java);
    mask-aware for variable-length RNN input (MaskedReductionUtil semantics)."""

    pooling_type: str = "max"
    pnorm: int = 2
    collapse_dimensions: bool = True

    def has_params(self):
        return False

    def initialize(self, key, input_type, dtype=jnp.float32):
        return {}, {}, self.output_type(input_type)

    def forward(self, params, state, x, *, train, rng, mask=None):
        if x.ndim == 4:   # CNN NCHW → pool over H,W
            y = conv_ops.global_pool(x, self.pooling_type, (2, 3), self.pnorm)
        elif x.ndim == 3:  # RNN [N, T, C] → pool over T, mask-aware
            m = mask[..., None] if mask is not None else None
            y = conv_ops.global_pool(x, self.pooling_type, (1,), self.pnorm, m)
        else:
            y = x
        return y, state, None  # mask consumed

    def output_type(self, input_type):
        if input_type.kind == "cnn":
            return InputType.feed_forward(input_type.channels)
        if input_type.kind == "rnn":
            return InputType.feed_forward(input_type.size)
        return input_type


# ==========================================================================
# Recurrent family  (native layout [N, T, C])
# ==========================================================================

@register_layer
@dataclasses.dataclass
class GravesLSTM(Layer):
    """Peephole LSTM over the full sequence as one lax.scan
    (ref: nn/conf/layers/GravesLSTM.java; impl
    nn/layers/recurrent/LSTMHelpers.java:60-526)."""

    n_in: Optional[int] = None
    n_out: int = 0
    gate_activation: str = "sigmoid"
    forget_gate_bias_init: float = 1.0

    def initialize(self, key, input_type, dtype=jnp.float32):
        n_in = self.n_in or input_type.size
        H = self.n_out
        kW, kR, kP = jax.random.split(key, 3)
        b = jnp.zeros((4 * H,), dtype)
        # forget-gate block [H:2H] gets forget_gate_bias_init (ref default 1.0)
        b = b.at[H:2 * H].set(self.forget_gate_bias_init)
        params = {
            "W": self._winit(kW, (n_in, 4 * H), dtype, fan_in=n_in, fan_out=4 * H),
            "RW": self._winit(kR, (H, 4 * H), dtype, fan_in=H, fan_out=4 * H),
            "b": b,
            "pI": jnp.zeros((H,), dtype),
            "pF": jnp.zeros((H,), dtype),
            "pO": jnp.zeros((H,), dtype),
        }
        return params, {}, InputType.recurrent(H, input_type.timesteps)

    def forward(self, params, state, x, *, train, rng, mask=None):
        x = self._maybe_dropout(x, train, rng)
        gate = act_ops.get(self.gate_activation)
        cell = act_ops.get(self.activation or "tanh")
        init = state.get("rnn_state") if state else None
        hs, final = rnn_ops.lstm_scan(params, x, init, mask,
                                      gate_act=gate, cell_act=cell)
        new_state = dict(state) if state else {}
        new_state["rnn_state"] = final  # for rnnTimeStep stateful inference
        return hs, new_state, mask

    def output_type(self, input_type):
        return InputType.recurrent(self.n_out, input_type.timesteps)


@register_layer
@dataclasses.dataclass
class GravesBidirectionalLSTM(Layer):
    """Fwd + bwd peephole LSTMs with separate params; the two directions'
    outputs are SUMMED, giving output size n_out (ref:
    nn/layers/recurrent/GravesBidirectionalLSTM.java:204
    ``fwdOutput.addi(backOutput)``)."""

    n_in: Optional[int] = None
    n_out: int = 0
    gate_activation: str = "sigmoid"
    forget_gate_bias_init: float = 1.0

    def initialize(self, key, input_type, dtype=jnp.float32):
        sub = GravesLSTM(n_in=self.n_in, n_out=self.n_out,
                         activation=self.activation,
                         weight_init=self.weight_init, dist=self.dist,
                         gate_activation=self.gate_activation,
                         forget_gate_bias_init=self.forget_gate_bias_init)
        kf, kb = jax.random.split(key)
        pf, _, out = sub.initialize(kf, input_type, dtype)
        pb, _, _ = sub.initialize(kb, input_type, dtype)
        params = {f"f_{k}": v for k, v in pf.items()}
        params.update({f"b_{k}": v for k, v in pb.items()})
        return params, {}, out

    def forward(self, params, state, x, *, train, rng, mask=None):
        x = self._maybe_dropout(x, train, rng)
        gate = act_ops.get(self.gate_activation)
        cell = act_ops.get(self.activation or "tanh")
        pf = {k[2:]: v for k, v in params.items() if k.startswith("f_")}
        pb = {k[2:]: v for k, v in params.items() if k.startswith("b_")}
        hf, _ = rnn_ops.lstm_scan(pf, x, None, mask, gate_act=gate, cell_act=cell)
        hb, _ = rnn_ops.lstm_scan(pb, x, None, mask, reverse=True,
                                  gate_act=gate, cell_act=cell)
        return hf + hb, state, mask

    def output_type(self, input_type):
        return InputType.recurrent(self.n_out, input_type.timesteps)


@register_layer
@dataclasses.dataclass
class RnnOutputLayer(BaseOutputLayer):
    """Per-timestep dense + loss over [N, T, C]
    (ref: nn/conf/layers/RnnOutputLayer.java).  Labels are [N, T, C], or
    int class ids [N, T] (``mcxent``).  ``time_reduction``: an example's
    score is the ``sum`` over its timesteps, as the reference scores, or
    their ``mean`` (over the unmasked ones), which makes the minibatch
    score the mean over tokens when the sequences are equally long, or
    the sum over the ``steps`` there are, T, whatever the mask: the
    labels mask is then a row's WEIGHT, any float (a diffusion loss
    weighs a masked token by 1 / t and the others by 0, and averages
    over the sequence's length, not over the count of weighted rows)."""

    time_reduction: str = "sum"     # sum | mean | steps

    def initialize(self, key, input_type, dtype=jnp.float32):
        n_in = self.n_in or input_type.size
        return (self._affine_params(key, n_in, dtype), {},
                InputType.recurrent(self.n_out, input_type.timesteps))

    def forward(self, params, state, x, *, train, rng, mask=None):
        x = self._maybe_dropout(x, train, rng)
        return self._act(_affine(params, x)), state, mask

    def compute_score(self, labels, preout, mask=None):
        # labels/preout: [N, T, C]; mask [N, T].  Score per example sums
        # over time (masked), matching reference RnnOutputLayer scoring.
        m = mask[..., None] if mask is not None else None
        if self.time_reduction == "steps":
            with jax.named_scope("weighted_rows"):
                return loss_ops.get(self.loss)(
                    labels, preout, self.activation or "softmax", m) \
                    / preout.shape[1]
        per_ex = loss_ops.get(self.loss)(labels, preout,
                                         self.activation or "softmax", m)
        if self.time_reduction == "mean":
            steps = (jnp.maximum(jnp.sum(mask, axis=1), 1.0)
                     if mask is not None else preout.shape[1])
            return per_ex / steps
        if self.time_reduction != "sum":
            raise ValueError(f"unknown time_reduction "
                             f"{self.time_reduction!r} (sum | mean | steps)")
        return per_ex

    def output_type(self, input_type):
        return InputType.recurrent(self.n_out, input_type.timesteps)


@register_layer
@dataclasses.dataclass
class LoopExitOutputLayer(RnnOutputLayer):
    """The head of a looped stack (``LoopVertex``): its input is every
    pass's hidden state ``h [R, N, T, C]``, its leaves the head ``W``
    [C, V] and an exit gate ``w_g`` [C], ``b_g`` [1].  Per token, with
    CE_r the cross-entropy of pass r's logits ``h_r W``:

        lam_r = sigmoid(h_r w_g + b_g)
        p_1 = lam_1;  p_r = lam_r prod_{j<r}(1 - lam_j);
        p_R = prod_{j<R}(1 - lam_j)          (the last pass takes the rest)
        loss = sum_r p_r CE_r - entropy_weight H(p),  H(p) = -sum_r p_r log p_r

    the expected loss under the exit distribution less an entropy bonus
    (Zhu et al. 2025, arXiv:2510.25741).  An example's score is the mean
    (``time_reduction``) over its unmasked tokens.  ``forward`` gives the
    last pass's distribution.

    It scores from its input (``scores_from_input``): R x N x T x V
    logits never exist together.  One pass's logits at a time, through
    the cross-entropy tier the registry selects, under recomputation, so
    the backward pass computes them again and the forward keeps ``h``
    alone.  The gate, ``p`` and the entropy are float32 whatever the
    compute dtype.  It leaves in state the means over tokens of ``p_r``
    and of ``CE_r`` (``loop_exit_mass``, ``loop_exit_loss``, [R] each),
    which the fit loop publishes as ``dl4j_loop_exit_mass`` /
    ``dl4j_loop_exit_loss``."""

    scores_from_input = True
    scope_parts = ("head", "gate")

    passes: int = 1
    entropy_weight: float = 0.1
    time_reduction: str = "mean"
    bias: bool = False

    def initialize(self, key, input_type, dtype=jnp.float32):
        n_in = self.n_in or input_type.size
        kh, kg = jax.random.split(key)
        params = self._affine_params(kh, n_in, dtype)
        params["w_g"] = self._winit(kg, (n_in, 1), dtype)[:, 0]
        params["b_g"] = jnp.zeros((1,), dtype)
        zeros = jnp.zeros((self.passes,), dtype)
        return (params, {"loop_exit_mass": zeros, "loop_exit_loss": zeros},
                InputType.recurrent(self.n_out, input_type.timesteps))

    def forward(self, params, state, x, *, train, rng, mask=None):
        return self._act(_affine(params, x[-1])), state, mask

    def exit_log_distribution(self, params, x):
        """log p [R, N, T] in float32 (float64 under a gradient check),
        from the gate's logits z_r = h_r w_g + b_g: log lam_r is
        log_sigmoid(z_r) and log(1 - lam_j) is log_sigmoid(-z_j), so a
        gate that saturates gives a large negative number and never
        log 0."""
        ft = jnp.promote_types(x.dtype, jnp.float32)
        z = jnp.einsum("rntc,c->rnt", x.astype(ft), params["w_g"].astype(ft),
                       precision=jax.lax.Precision.HIGHEST) \
            + params["b_g"].astype(ft)
        stay = jnp.cumsum(jax.nn.log_sigmoid(-z), axis=0)
        before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]])
        return jnp.concatenate(
            [jax.nn.log_sigmoid(z[:-1]) + before[:-1], before[-1:]])

    def exit_distribution(self, params, x):
        """p [R, N, T]: it sums to 1 over the passes."""
        return jnp.exp(self.exit_log_distribution(params, x))

    def score_from_input(self, params, state, x, labels, mask=None):
        if x.ndim != 4 or x.shape[0] != self.passes:
            raise ValueError(f"LoopExitOutputLayer(passes={self.passes}) "
                             f"wants [passes, N, T, C], got {x.shape}")
        ft = jnp.promote_types(x.dtype, jnp.float32)
        by_id = jnp.issubdtype(labels.dtype, jnp.integer)

        with jax.named_scope("head"):
            @jax.checkpoint
            def pass_rows(h):
                z = _affine({"W": params["W"]}, h).astype(ft)
                if by_id:
                    return loss_ops.mcxent_id_rows(labels, z)
                return -jnp.sum(labels * jax.nn.log_softmax(z), axis=-1)
            ce = jax.lax.map(pass_rows, x)                  # [R, N, T]
        with jax.named_scope("gate"):
            logp = self.exit_log_distribution(params, x)
            p = jnp.exp(logp)
            # p log p with log p finite: no 0 x inf where a gate saturates
            entropy = -jnp.sum(p * logp, axis=0)
            rows = jnp.sum(p * ce, axis=0) - self.entropy_weight * entropy
            if mask is not None:
                m = (mask[..., 0] if mask.ndim == 3 else mask).astype(ft)
                rows, count = rows * m, jnp.maximum(jnp.sum(m), 1.0)
                steps = jnp.maximum(jnp.sum(m, axis=1), 1.0)
            else:
                m, count, steps = 1.0, rows.size, rows.shape[1]
            per_ex = jnp.sum(rows, axis=1)
            if self.time_reduction == "mean":
                per_ex = per_ex / steps
            elif self.time_reduction != "sum":
                raise ValueError(f"unknown time_reduction "
                                 f"{self.time_reduction!r} (sum | mean)")
            sd = state["loop_exit_mass"].dtype
            new_state = {**state, **jax.lax.stop_gradient({
                "loop_exit_mass": (jnp.sum(p * m, axis=(1, 2)) / count
                                   ).astype(sd),
                "loop_exit_loss": (jnp.sum(ce * m, axis=(1, 2)) / count
                                   ).astype(sd)})}
        return per_ex, new_state


@register_layer
@dataclasses.dataclass
class LastTimeStepLayer(Layer):
    """[N,T,C] → [N,C] at the last unmasked timestep (sequential-network
    analog of the reference's rnn/LastTimeStepVertex.java; used e.g. for
    Keras LSTM(return_sequences=False) import)."""

    def has_params(self):
        return False

    def initialize(self, key, input_type, dtype=jnp.float32):
        return {}, {}, self.output_type(input_type)

    def forward(self, params, state, x, *, train, rng, mask=None):
        if mask is None:
            return x[:, -1], state, None
        idx = jnp.maximum(jnp.sum(mask > 0, axis=1).astype(jnp.int32) - 1, 0)
        return x[jnp.arange(x.shape[0]), idx], state, None

    def output_type(self, input_type):
        return InputType.feed_forward(input_type.size)


# ==========================================================================
# Pre-norm sequence blocks: RMS normalisation, the gated MLP and the gated
# short convolution.  All three act on the last axis of [N, T, C] (or
# [N, C]) and have no bias.
# ==========================================================================

@register_layer
@dataclasses.dataclass
class RMSNormLayer(Layer):
    """y = x / sqrt(mean(x^2) + eps) * gamma over the feature axis
    (Zhang & Sennrich 2019); no mean, no shift."""

    activation: Optional[str] = "identity"
    eps: float = RMS_EPS

    def initialize(self, key, input_type, dtype=jnp.float32):
        return ({"gamma": jnp.ones((input_type.flat_size(),), dtype)}, {},
                input_type)

    def forward(self, params, state, x, *, train, rng, mask=None):
        return self._act(_rms_norm(x, params["gamma"], self.eps)), state, mask

    def output_type(self, input_type):
        return input_type


@register_layer
@dataclasses.dataclass
class GatedDenseLayer(Layer):
    """Gated MLP (Shazeer 2020): y = (act(x W1) * (x W3)) W2, ``act``
    the layer's activation (swish = SiLU by default), width ``hidden``.

    The output is offered to a recomputed run (``ops/recompute.py``).
    Where a norm follows the layer (a sandwich block), the norm's
    backward reads ``y``, and a run that kept nothing would do the
    last product, the layer's dearest third, a second time for that
    one ``[N, T, n_out]`` value.  Where nothing reads ``y`` (a pre-norm
    block's residual add) the offer costs nothing."""

    activation: Optional[str] = "swish"
    n_in: Optional[int] = None
    n_out: int = 0
    hidden: int = 0

    def initialize(self, key, input_type, dtype=jnp.float32):
        n_in = self.n_in or input_type.flat_size()
        k1, k2, k3 = jax.random.split(key, 3)
        params = {"W1": self._winit(k1, (n_in, self.hidden), dtype),
                  "W3": self._winit(k3, (n_in, self.hidden), dtype),
                  "W2": self._winit(k2, (self.hidden, self.n_out), dtype)}
        return params, {}, self.output_type(input_type)

    def forward(self, params, state, x, *, train, rng, mask=None):
        x = self._maybe_dropout(x, train, rng)
        y = (self._act(x @ params["W1"]) * (x @ params["W3"])) @ params["W2"]
        return recompute.offer(y), state, mask

    def output_type(self, input_type):
        if input_type.kind == "rnn":
            return InputType.recurrent(self.n_out, input_type.timesteps)
        return InputType.feed_forward(self.n_out)


@register_layer
@dataclasses.dataclass
class GatedShortConvLayer(Layer):
    """Gated short convolution over [N, T, C]: ``[b, c, z] = split3(x
    W_in)``; a depthwise causal convolution of length ``kernel`` over
    ``s = b * z`` (``g_t = sum_j K[j] s_{t-(kernel-1)+j}``, zeros before
    the sequence); ``y = (c * g) W_out``."""

    activation: Optional[str] = "identity"
    n_in: Optional[int] = None
    n_out: int = 0
    kernel: int = 3

    def initialize(self, key, input_type, dtype=jnp.float32):
        n_in = self.n_in or input_type.size
        ki, kk, ko = jax.random.split(key, 3)
        params = {
            "W_in": self._winit(ki, (n_in, 3 * self.n_out), dtype),
            "K": self._winit(kk, (self.kernel, self.n_out), dtype,
                             fan_in=self.kernel, fan_out=self.kernel),
            "W_out": self._winit(ko, (self.n_out, self.n_out), dtype)}
        return params, {}, InputType.recurrent(self.n_out,
                                               input_type.timesteps)

    def forward(self, params, state, x, *, train, rng, mask=None):
        x = self._maybe_dropout(x, train, rng)
        T, L = x.shape[1], self.kernel
        b, c, z = jnp.split(x @ params["W_in"], 3, axis=-1)
        s = b * z
        if mask is not None:    # a padded step feeds no later one
            s = s * mask[:, :, None].astype(s.dtype)
        sp = jnp.pad(s, ((0, 0), (L - 1, 0), (0, 0)))
        g = sum(params["K"][j] * sp[:, j:j + T] for j in range(L))
        y = self._act((c * g) @ params["W_out"])
        if mask is not None:
            y = y * mask[:, :, None].astype(y.dtype)
        return y, state, mask

    def output_type(self, input_type):
        return InputType.recurrent(self.n_out, input_type.timesteps)


# ==========================================================================
# Attention (long-context extension — SURVEY.md §5: the reference's only
# long-sequence mechanism is TBPTT; this layer plus parallel/sequence.py
# adds exact ring / all-to-all sequence-parallel attention over the mesh).
# ==========================================================================

@register_layer
@dataclasses.dataclass
class SelfAttentionLayer(Layer):
    """Multi-head self-attention over recurrent input [B, T, F].

    The attention core dispatches through
    ``parallel.sequence.attention``: dense on one device, ring /
    all-to-all sequence-parallel when a mesh with a non-trivial 'seq'
    axis is active (``parallel.sequence.sequence_mesh``).

    Under the engines' carried decode step
    (``parallel.sequence.kv_decode_scope`` — entered by
    ``rnn_time_step`` and the serving decode pool), the layer instead
    decodes INCREMENTALLY against a per-stream KV ring carried in
    ``rnn_state``: each new token appends its K/V at ``pos % window``
    and attends over only the valid ring entries
    (``parallel.sequence.attend_cached``) — O(window) per token, flat
    in stream length, instead of re-running the whole window.
    Streaming decode is inherently causal: with ``cache_window >=``
    the stream length the step-by-step outputs match full causal
    ``dense_attention``; older tokens fall out of the ring (sliding
    window).  ``cache_window=None`` resolves to the declared input
    timesteps at init (128 when variable-length).

    ``causal`` is the mask's rule (``ops/mask_rules.py``): False (every
    pair lives), True (row i sees 0..i), ``("block_diffusion", seq_len,
    block_length)``: the 2 x seq_len rows are a noised and a clean copy
    of one sequence, both at positions 0..seq_len-1, and a row sees its
    own block of its own half and the clean blocks before it, or
    ``("sliding_window", W)``: row i sees i - W + 1 .. i.  The rule also
    gives the rotary embedding its positions.  The carried decode step
    knows False and True only, and refuses any other rule.  The core
    runs inside the layer's scope under the part ``attn_core``.

    ``output_gate`` multiplies the concatenated heads by ``sigmoid(x
    Wg)`` (a leaf ``Wg`` of n_in x heads x head_dim) before the output
    projection, under the part ``attn_gate``.  ``recompute=True`` runs a
    training forward again in the backward pass
    (``recompute.keeping_offers``): it keeps the layer's input and the
    core's output with its row statistics, and projects, normalises,
    rotates and gates again instead of keeping those values.
    """

    scope_parts = ("attn_core", "attn_gate")

    n_in: Optional[int] = None
    n_out: int = 0
    n_heads: int = 1
    causal: Any = False         # False | True | a rule (ops/mask_rules.py)
    strategy: str = "auto"      # auto | ring | ulysses | dense
    project_output: bool = True
    cache_window: Optional[int] = None   # KV-ring length for decode
    # grouped-query heads: keys and values have n_kv_heads heads (a
    # divisor of n_heads), each serving n_heads / n_kv_heads consecutive
    # query heads; None = one a query head
    n_kv_heads: Optional[int] = None
    # rotary position embedding over the whole head, halves paired
    # (i with i + Dh/2), base rotary_theta; None = no position signal
    rotary_theta: Optional[float] = None
    # RMSNorm (eps qk_norm_eps) with a learned weight over each head of q
    # and of k, before the rotation ("q_norm", "k_norm" leaves of [Dh])
    qk_norm: bool = False
    qk_norm_eps: float = RMS_EPS
    bias: bool = True           # False: no bq/bk/bv/bo leaves
    # a head's width where it is not n_out / n_heads (q, k and v project
    # to heads x head_dim, the output projection back to n_out)
    head_dim: Optional[int] = None
    # the heads gated by sigmoid(x Wg) before the output projection
    output_gate: bool = False
    # training runs the layer again in the backward pass, keeping its
    # input and what the core offers (ops/recompute.py)
    recompute: bool = False

    def initialize(self, key, input_type, dtype=jnp.float32):
        n_in = self.n_in or input_type.size
        if self.n_out % self.n_heads:
            raise ValueError(f"n_out={self.n_out} % n_heads={self.n_heads}")
        Hkv = self.n_kv_heads or self.n_heads
        if self.n_heads % Hkv:
            raise ValueError(f"n_heads={self.n_heads} % n_kv_heads={Hkv}")
        Dh = self._head_dim()
        inner = self.n_heads * Dh
        if inner != self.n_out and not self.project_output:
            raise ValueError(f"head_dim={Dh} x n_heads={self.n_heads} is "
                             f"not n_out={self.n_out}: the output "
                             "projection is what brings it back")
        if self.rotary_theta is not None and Dh % 2:
            raise ValueError(f"rotary embedding pairs halves: head dim "
                             f"{Dh} is odd")
        if self.cache_window is None:
            self.cache_window = int(getattr(input_type, "timesteps", None)
                                    or 128)
        kq, kk, kv, ko = jax.random.split(key, 4)
        params = {
            "Wq": self._winit(kq, (n_in, inner), dtype),
            "Wk": self._winit(kk, (n_in, Hkv * Dh), dtype),
            "Wv": self._winit(kv, (n_in, Hkv * Dh), dtype),
        }
        if self.bias:
            params["bq"] = self._binit((inner,), dtype)
            params["bk"] = self._binit((Hkv * Dh,), dtype)
            params["bv"] = self._binit((Hkv * Dh,), dtype)
        if self.qk_norm:
            params["q_norm"] = jnp.ones((Dh,), dtype)
            params["k_norm"] = jnp.ones((Dh,), dtype)
        if self.project_output:
            params["Wo"] = self._winit(ko, (inner, self.n_out), dtype)
            if self.bias:
                params["bo"] = self._binit((self.n_out,), dtype)
        if self.output_gate:
            params["Wg"] = self._winit(jax.random.fold_in(key, 5),
                                       (n_in, inner), dtype)
        return params, {}, InputType.recurrent(self.n_out, input_type.timesteps)

    def _head_dim(self) -> int:
        return self.head_dim or self.n_out // self.n_heads

    @staticmethod
    def _rotate(a, theta, positions=None):
        """Rotary embedding of [B, H, T, Dh] at ``positions`` [T]
        (0..T-1 when None), the angles and the rotation in float32."""
        T, Dh = a.shape[2], a.shape[3]
        ft = jnp.promote_types(a.dtype, jnp.float32)
        inv = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=ft) / Dh))
        pos = (jnp.arange(T, dtype=ft) if positions is None
               else positions.astype(ft))
        ang = pos[:, None] * inv[None, :]                       # [T, Dh/2]
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        a1, a2 = jnp.split(a.astype(ft), 2, axis=-1)
        return jnp.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin],
                               axis=-1).astype(a.dtype)

    def forward(self, params, state, x, *, train, rng, mask=None):
        from deeplearning4j_tpu.parallel import sequence as seq_ops
        x = self._maybe_dropout(x, train, rng)
        if self.recompute and train and not seq_ops.kv_decode_active():
            out = recompute.keeping_offers(
                lambda p, x: self._attend(p, state, x, train, mask)[0])(
                    params, x)
            return out, state, mask
        out, new_state = self._attend(params, state, x, train, mask)
        return out, new_state, mask

    def _attend(self, params, state, x, train, mask):
        """The layer's output and its new state, after dropout."""
        from deeplearning4j_tpu.parallel import sequence as seq_ops
        B, T, _ = x.shape
        H, Dh = self.n_heads, self._head_dim()
        Hkv = self.n_kv_heads or H

        def heads(w, b, n):  # [B, T, F] -> [B, n, T, Dh]
            a = x @ params[w]
            if b in params:
                a = a + params[b]
            return a.reshape(B, T, n, Dh).transpose(0, 2, 1, 3)

        q = heads("Wq", "bq", H)
        k = heads("Wk", "bk", Hkv)
        v = heads("Wv", "bv", Hkv)
        if self.qk_norm:
            q = _rms_norm(q, params["q_norm"], self.qk_norm_eps)
            k = _rms_norm(k, params["k_norm"], self.qk_norm_eps)
        if self.rotary_theta is not None:
            if seq_ops.kv_decode_active() and not train:
                raise NotImplementedError(
                    "SelfAttentionLayer: rotary positions are not carried "
                    "in the KV-ring decode step")
            rule = mask_rules.resolve(self.causal)
            pos = None if rule is None else rule.positions(T)
            q = self._rotate(q, self.rotary_theta, pos)
            k = self._rotate(k, self.rotary_theta, pos)
        if Hkv != H:
            # the attention core wants one key/value head a query head
            k = jnp.repeat(k, H // Hkv, axis=1)
            v = jnp.repeat(v, H // Hkv, axis=1)
        new_state = state
        if seq_ops.kv_decode_active() and not train:
            if mask_rules.resolve(self.causal) not in (None,
                                                       mask_rules.CAUSAL):
                raise NotImplementedError(
                    f"SelfAttentionLayer: the KV-ring decode step attends "
                    f"causally over its ring; the mask rule "
                    f"{self.causal!r} is not carried there")
            # incremental decode: append this chunk's K/V to the
            # per-stream ring and attend over valid entries only —
            # O(window)/token instead of O(T)/token re-runs.  The ring
            # is the layer's rnn_state carry, so it lives on device in
            # the decode pool's slot buffer and rides migration.
            W = int(self.cache_window or 128)
            tape = seq_ops.paged_tape()
            if tape is not None:
                # paged decode: K/V pages live in the pool-shared arena
                # (drawn from the trace-time tape); the carry holds only
                # the int32 block table + write position.  `aid` is the
                # layer's arena id, encoded in the leaf's trailing dim
                # (shape survives eval_shape templates, values do not)
                # so export/import can map a carry node back to its
                # arena without relying on pytree walk order.
                _, nbs = seq_ops.block_geometry(W, tape.block_size)
                aid, arena, tbl = tape.next_layer(H, Dh, W, x.dtype)
                if tbl is None:
                    tbl = jnp.zeros((B, nbs), jnp.int32)
                prev = state.get("rnn_state") if state else None
                pos = (prev["pos"] if isinstance(prev, dict)
                       and "pos" in prev else jnp.zeros((B,), jnp.int32))
                if tape.record_undo:
                    out, pos, arena, journal = seq_ops.attend_paged(
                        q, k, v, pos, tbl, arena, window=W,
                        key_mask=mask, undo=True)
                    tape.put_undo(aid, journal)
                else:
                    out, pos, arena = seq_ops.attend_paged(
                        q, k, v, pos, tbl, arena, window=W, key_mask=mask)
                tape.put(aid, arena)
                new_state = dict(state) if state else {}
                new_state["rnn_state"] = {
                    "aid": jnp.full((B, aid + 1), aid, jnp.int32),
                    "pos": pos, "tbl": tbl}
            else:
                ring = state.get("rnn_state") if state else None
                if ring is None:
                    ring = seq_ops.kv_ring_init(B, H, W, Dh, x.dtype)
                out, ring = seq_ops.attend_cached(q, k, v, ring,
                                                  key_mask=mask)
                new_state = dict(state) if state else {}
                new_state["rnn_state"] = ring
        else:
            with jax.named_scope("attn_core"):
                out = seq_ops.attention(q, k, v, causal=self.causal,
                                        key_mask=mask,
                                        strategy=self.strategy)
        out = out.transpose(0, 2, 1, 3).reshape(B, T, H * Dh)
        if self.output_gate:
            with jax.named_scope("attn_gate"):
                out = out * jax.nn.sigmoid(x @ params["Wg"])
        if self.project_output:
            out = out @ params["Wo"]
            if "bo" in params:
                out = out + params["bo"]
        out = self._act(out)
        if mask is not None:
            out = out * mask[:, :, None].astype(out.dtype)
        return out, new_state

    def output_type(self, input_type):
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def core_tiles(self, T: int) -> Optional[dict]:
        """{"visited", "boundary", "skipped"}: of the tiles a head of
        this layer's flash core has over ``T`` rows under its mask rule,
        those the kernels visit whole, visit with the rule's comparison
        inside and never touch (``mask_rules.tile_counts``: the plan the
        kernels run); None where a single-device step runs the dense
        core instead (helper selection, the shape gate)."""
        from deeplearning4j_tpu.ops import helpers, mask_rules
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        Dh = self.head_dim or self.n_out // self.n_heads
        q = jax.ShapeDtypeStruct((1, self.n_heads, T, Dh), jnp.float32)
        if self.strategy not in ("auto", "dense") \
                or not helpers.available("attention") \
                or not pk.flash_attention_supported(q):
            return None
        rule = mask_rules.resolve(self.causal)
        Tp = T + (-T) % pk.LANE
        return mask_rules.tile_counts(rule, Tp, pk._flash_block(Tp, rule))


def _where_equal(index, ids, values):
    """``values`` (one an id, broadcast against ``index[..., None]``)
    where ``index`` equals the id, else 0, summed over the ids: a lookup
    written as comparisons.  An index costs the chip as much to gather as
    a whole row does (``PERF.md`` 6, PR 38), and tokens x k x ids
    comparisons next to nothing."""
    return jnp.sum(jnp.where(index[..., None] == ids, values, 0), axis=-1)


@register_layer
@dataclasses.dataclass
class MixtureOfExpertsLayer(Layer):
    """Sparse mixture-of-experts feed-forward block.  No reference analog
    — DL4J predates MoE; this layer exists so the mesh's 'expert' axis is
    a first-class layout: expert weight stacks [E, ...] shard over
    'expert' (parallel/mesh.param_sharding).

    ``top_k=None`` is the GShard-style top-1 path: softmax gate → top-1
    expert per token, fixed capacity ``capacity_factor·N/E`` per expert,
    a dense [N, E, C] dispatch whose einsums XLA partitions into
    expert-parallel all-to-alls; overflow tokens pass through unchanged
    (the block adds its input).  Aux load-balancing loss is returned in
    state under "moe_aux_loss" (mean over experts of
    fraction·probability, scaled by ``aux_loss_weight``); ``top_k=k`` has
    none, and leaves it at zero.

    ``top_k=k`` routes without capacity and drops no token.  Scores are
    ``scoring`` (sigmoid | softmax) of the router's product, which runs
    in float32 whatever the compute dtype so that no selection flips on
    the rounding of a score; the k experts with the largest score (plus,
    with ``expert_bias``, a constant per-expert bias held in state, which
    steers the selection and not the weights) are selected, and their
    scores renormalised over the k (``norm_topk``; over their sum + 1e-6
    under sigmoid scoring) and multiplied by ``route_scale``.  With
    ``bias_update_rate`` r > 0 the bias moves with the load after every
    training step (auxiliary-loss-free balancing): with c the step's
    assignment counts over all E experts and d = r sign(mean(c) - c), the
    state's bias becomes bias + d - mean(d), from the counts the layer
    computes anyway.  The N·k assignments
    are sorted by held expert, the others behind them, and the sorted
    order is cut into segments of static shape (``segment_shape``: twice
    the even-load share N·k·G/E of the G experts held, so that an even
    load lies inside the first and not on its edge; the segment count
    follows the share and nothing else, no argument sets it).  **No array
    of the layer that is as wide as the model or as an expert has more
    than one segment's rows**; only the index arrays are N·k long.  A
    segment goes from the tokens to the tokens' sum in one piece
    (``ops/row_segments.routed_sum``): its rows are gathered, the expert
    products are grouped matrix products over them (the gmm tier's
    kernels, or ``jax.lax.ragged_dot`` where the tier is off, with the
    global group sizes clipped to the segment), gated, and
    summed back into the tokens by k gathers of N rows in float32.  The
    group sizes are data, and so is the number of segments that hold a
    row of a held expert.  The first segment runs once outside any loop,
    nothing zeroed first, and its rows and hidden rows are all the
    backward keeps; the later ones run in a loop whose trip count the
    device reads, forward adding into the tokens' sum, backward computing
    their hidden rows again and adding their weights' gradients to the
    first's (no step retraces whatever the routing, no token is dropped:
    with every assignment on a held expert every segment runs; a later
    segment runs when the load passes twice its even share, and is
    counted as ``recomputed``).  A layer that holds every expert has one
    segment: the same expressions once over all N·k rows, no loop.
    Memory grows with the segment, N·k·2G/E, not with N·k or N·E·C.
    ``gated`` experts are ``(silu(x W1) * (x W3)) W2``, plain ones
    ``gelu(x W1) W2``; neither has a bias.

    ``experts_held`` names the experts whose weights this layer holds
    (expert parallelism's share of the layer; None = all): the router
    keeps its width ``n_experts``, selection and renormalisation run
    over all of them, and the layer returns the part of the sum that its
    own experts give.  Nothing stands in for the others.  The per-expert
    assignment counts of the step are left in state under
    "moe_expert_counts" (published by the fit loop as
    ``dl4j_moe_assignments_total`` / ``dl4j_moe_expert_load_max_over_mean``),
    the segments run and skipped under "moe_row_segments"
    (``dl4j_moe_row_segments_total``).
    ``residual=False`` returns the routed sum alone (a graph adds the
    residual with an ElementWiseVertex).  ``recompute=True`` runs the
    top_k path again in the backward pass (``jax.checkpoint``) instead of
    keeping the first segment's rows and hidden rows, (D + 2 H) values a
    row of 2 N·k·G/E; what it keeps is the routing's integers (the
    selection, the order and its inverse, the weights: a few numbers a
    token, offered through ``ops/recompute.py``), so that the step
    selects and sorts once.  The four parts are named
    inside the layer's scope (``scope_parts``; the last three inside a
    segment's body, so also inside the later segments' loop), and the
    grouped products' kernels are claimed for ``experts`` by name
    (``scope_kernels``): XLA's, which the chip's compiler names itself,
    and the gmm tier's, whose one lowering a program shares between its
    call sites and so carries one site's scope."""

    scope_parts = ("route", "dispatch", "experts", "combine")
    scope_kernels = {"ragged-dot": "experts", "dl4j_gmm": "experts",
                     "dl4j_tgmm": "experts"}

    n_in: Optional[int] = None
    n_out: int = 0
    n_experts: int = 4
    hidden: Optional[int] = None       # expert MLP width (default 4×n_out)
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01      # the top-1 path's
    top_k: Optional[int] = None        # None: the top-1 capacity path
    # the top_k path's:
    scoring: str = "softmax"           # softmax | sigmoid
    norm_topk: bool = True
    route_scale: float = 1.0           # times the (renormalised) weights
    expert_bias: bool = False
    # the bias's step after each training step, against the load
    bias_update_rate: float = 0.0
    gated: bool = False
    experts_held: Optional[Tuple[int, ...]] = None
    residual: bool = True
    # the top_k path under jax.checkpoint: the backward pass routes,
    # gathers and multiplies again instead of keeping the first segment's
    # rows and hidden rows
    recompute: bool = False

    def _held(self) -> Tuple[int, ...]:
        held = tuple(range(self.n_experts)) if self.experts_held is None \
            else tuple(int(e) for e in self.experts_held)
        if not held or list(held) != sorted(set(held)) \
                or held[0] < 0 or held[-1] >= self.n_experts:
            raise ValueError(f"experts_held={self.experts_held!r}: want "
                             f"distinct ascending ids in [0, "
                             f"{self.n_experts})")
        return held

    def initialize(self, key, input_type, dtype=jnp.float32):
        n_in = self.n_in or input_type.size
        if self.n_out != n_in:
            raise ValueError("MoE block is residual: n_out must equal n_in "
                             f"(got n_in={n_in}, n_out={self.n_out})")
        H = self.hidden or 4 * self.n_out
        kg, k1, k2, k3, kb = jax.random.split(key, 5)
        E = self.n_experts
        # aux loss lives in state from step 0 so the state pytree
        # structure never changes (jit/sharding trees are built once)
        state = {"moe_aux_loss": jnp.zeros((), dtype)}
        if self.top_k is None:
            params = {
                "Wg": self._winit(kg, (n_in, E), dtype),
                "W1": self._winit(k1, (E, n_in, H), dtype, fan_in=n_in,
                                  fan_out=H),
                "b1": jnp.zeros((E, H), dtype),
                "W2": self._winit(k2, (E, H, self.n_out), dtype, fan_in=H,
                                  fan_out=self.n_out),
                "b2": jnp.zeros((E, self.n_out), dtype),
            }
            return params, state, input_type
        if not 1 <= self.top_k <= E:
            raise ValueError(f"top_k={self.top_k} of {E} experts")
        if self.bias_update_rate and not self.expert_bias:
            raise ValueError("bias_update_rate moves the expert bias: "
                             "expert_bias=True wanted")
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown scoring {self.scoring!r} "
                             "(softmax | sigmoid)")
        G = len(self._held())
        # the grouped products' tier checks its kernels beside the
        # weights' set-up, not in the first step's trace
        helper_ops.prevalidate("gmm")
        params = {
            "Wg": self._winit(kg, (n_in, E), dtype),
            "W1": self._winit(k1, (G, n_in, H), dtype, fan_in=n_in,
                              fan_out=H),
            "W2": self._winit(k2, (G, H, self.n_out), dtype, fan_in=H,
                              fan_out=self.n_out),
        }
        if self.gated:
            params["W3"] = self._winit(k3, (G, n_in, H), dtype, fan_in=n_in,
                                       fan_out=H)
        state["moe_expert_counts"] = jnp.zeros((E,), jnp.int32)
        state["moe_row_segments"] = jnp.zeros((2,), jnp.int32)
        if self.expert_bias:
            state["expert_bias"] = 0.01 * jax.random.normal(kb, (E,), dtype)
        return params, state, input_type

    def master_leaves(self) -> Tuple[str, ...]:
        """The top_k path's expert stacks reach the layer in float32 under
        the bf16 policy: the gmm tier's kernels cast each block in VMEM
        and give the stacks' gradients in float32, so no pass casts a
        stack whole; XLA's products take them cast in ``routed_sum``."""
        return ("W1", "W2", "W3") if self.top_k is not None else ()

    def segment_shape(self, rows: int) -> Tuple[int, int]:
        """(rows of a segment, segments) into which the top_k path cuts
        its ``rows`` = tokens x k sorted assignments: from the share of
        the experts held, nothing else (``ops/row_segments.py``)."""
        return row_segments.segment_rows(rows, len(self._held()),
                                         self.n_experts)

    def _forward_top_k(self, params, state, x, mask, train=False):
        """The routed sum of the top_k path, [.., n_out], and the new
        state."""
        shape = x.shape
        D = shape[-1]
        tokens = x.reshape(-1, D)                       # [N, D]
        N, E, k = tokens.shape[0], self.n_experts, self.top_k
        held = self._held()
        G = len(held)
        tok_mask = (mask.reshape(-1) > 0
                    if mask is not None and x.ndim == 3 else None)
        with jax.named_scope("route"):
            ft = jnp.promote_types(x.dtype, jnp.float32)
            logits = jnp.dot(tokens.astype(ft), params["Wg"].astype(ft),
                             precision=jax.lax.Precision.HIGHEST)
            scores = (jax.nn.sigmoid(logits) if self.scoring == "sigmoid"
                      else jax.nn.softmax(logits, axis=-1))       # [N, E]
            select = scores
            if self.expert_bias:
                select = scores + state["expert_bias"].astype(ft)
            _, top_e = jax.lax.top_k(select, k)                   # [N, k]
            # a few integers a token, here and below: a recomputed run
            # keeps them, and selects and sorts once a step
            top_e = recompute.offer(top_e)
            w = _where_equal(top_e, jnp.arange(E), scores[:, None, :])
            if self.norm_topk:
                # sigmoid scores may all be near 0 (the family that scores
                # so adds 1e-6); the k largest of a softmax sum to k / E
                # at the least, and are divided as they are
                w = w / (jnp.sum(w, axis=-1, keepdims=True)
                         + (1e-6 if self.scoring == "sigmoid" else 0.0))
            if self.route_scale != 1.0:
                w = w * self.route_scale
            if tok_mask is not None:
                w = w * tok_mask[:, None]
        seg, n_seg = self.segment_shape(N * k)
        with jax.named_scope("dispatch"):
            # the held experts' position in this layer's stacks; G = "not
            # here", which sorts last and belongs to no group
            local = G + _where_equal(
                top_e, jnp.asarray(held, jnp.int32),
                jnp.arange(G, dtype=jnp.int32) - G)               # [N, k]
            if tok_mask is not None:    # padding claims no expert
                local = jnp.where(tok_mask[:, None], local, G)
            flat = local.reshape(-1)                              # [N·k]
            # whole segments: the filling sorts behind every row
            fill = jnp.full((n_seg * seg - N * k,), G, flat.dtype)
            perm = jnp.argsort(jnp.concatenate([flat, fill]) if fill.size
                               else flat, stable=True)
            sizes = jnp.sum(flat[:, None] == jnp.arange(G + 1)[None, :],
                            axis=0, dtype=jnp.int32)              # [G + 1]
            group_sizes = sizes[:G]
            # row r of the sorted order is assignment perm[r] = token
            # perm[r] // k, and back[n, j] the row of assignment (n, j)
            held_rows = jnp.sum(group_sizes)
            # (the inverse of a permutation by sorting it: a sort of N·k
            # integers costs the chip a seventh of their scatter)
            back = jnp.argsort(perm)[:N * k].astype(jnp.int32).reshape(N, k)
        w, perm, back, group_sizes = (
            recompute.offer(v) for v in (w, perm, back, group_sizes))
        # a segment at a time from the tokens to the tokens' sum, under the
        # names "dispatch", "experts" and "combine" inside
        routed = row_segments.routed_sum(
            tokens, w.astype(tokens.dtype),
            (params["W1"], params["W3"]) if self.gated else (params["W1"],),
            params["W2"], perm, back, group_sizes, seg, k,
            row_segments.gated_silu if self.gated else jax.nn.gelu)
        counted = top_e if tok_mask is None \
            else jnp.where(tok_mask[:, None], top_e, E)
        counts = jnp.sum(counted.reshape(-1)[:, None]
                         == jnp.arange(E)[None, :], axis=0, dtype=jnp.int32)
        new_state = dict(state)
        new_state["moe_expert_counts"] = counts
        if self.bias_update_rate and train:
            c = counts.astype(ft)
            d = self.bias_update_rate * jnp.sign(jnp.mean(c) - c)
            new_state["expert_bias"] = (
                state["expert_bias"] + (d - jnp.mean(d)).astype(
                    state["expert_bias"].dtype))
        run = row_segments.segments_run(held_rows, seg)
        new_state["moe_row_segments"] = jnp.stack(
            [run, n_seg - run]).astype(jnp.int32)
        return routed.reshape(shape[:-1] + (self.n_out,)), new_state

    def forward(self, params, state, x, *, train, rng, mask=None):
        x = self._maybe_dropout(x, train, rng)
        if self.top_k is not None:
            run = functools.partial(self._forward_top_k, train=train)
            if self.recompute and train:
                run = recompute.keeping_offers(run)
            routed, new_state = run(params, state, x, mask)
            out = self._act(x + routed if self.residual else routed)
            if mask is not None and out.ndim == 3:
                out = out * mask[:, :, None].astype(out.dtype)
            return out, new_state, mask
        shape = x.shape
        D = shape[-1]
        tokens = x.reshape(-1, D)                       # [N, D]
        N = tokens.shape[0]
        E = self.n_experts
        C = max(1, int(self.capacity_factor * N / E))

        gates = jax.nn.softmax(tokens @ params["Wg"], axis=-1)   # [N, E]
        top_p = gates.max(axis=-1)                               # [N]
        top_e = gates.argmax(axis=-1)                            # [N]
        onehot = jax.nn.one_hot(top_e, E, dtype=x.dtype)         # [N, E]
        # padding tokens must not claim capacity or train the gate
        if mask is not None and x.ndim == 3:
            tok_mask = mask.reshape(-1).astype(x.dtype)          # [N]
            onehot = onehot * tok_mask[:, None]
        else:
            tok_mask = None

        # position of each token within its expert's queue
        pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot        # [N, E]
        in_cap = (pos < C).astype(x.dtype) * onehot
        pos_idx = pos.sum(axis=-1).astype(jnp.int32)             # [N]
        cap_oh = jax.nn.one_hot(pos_idx, C, dtype=x.dtype)       # [N, C]
        dispatch = in_cap[:, :, None] * cap_oh[:, None, :]       # [N, E, C]

        # dispatch → per-expert batch, expert MLP, combine (GShard einsums;
        # the E dimension is sharded over 'expert' — XLA inserts a2a)
        expert_in = jnp.einsum("nec,nd->ecd", dispatch, tokens)
        h = jax.nn.gelu(
            jnp.einsum("ecd,edh->ech", expert_in, params["W1"])
            + params["b1"][:, None, :])
        expert_out = (jnp.einsum("ech,eho->eco", h, params["W2"])
                      + params["b2"][:, None, :])
        combine = dispatch * top_p[:, None, None]
        routed = jnp.einsum("nec,eco->no", combine, expert_out)

        # residual: routed contribution is zero for overflow/unrouted
        # tokens, so they pass through unchanged
        out = (tokens + routed).reshape(shape[:-1] + (self.n_out,))

        # load-balance aux loss (Switch/GShard): E·Σ_e fraction_e·prob_e
        # — averaged over VALID tokens only
        if tok_mask is not None:
            n_valid = jnp.maximum(tok_mask.sum(), 1.0)
            frac = onehot.sum(axis=0) / n_valid
            prob = (gates * tok_mask[:, None]).sum(axis=0) / n_valid
        else:
            frac = onehot.mean(axis=0)
            prob = gates.mean(axis=0)
        aux = self.aux_loss_weight * E * jnp.sum(frac * prob)
        new_state = dict(state) if state else {}
        new_state["moe_aux_loss"] = aux
        out = self._act(out)
        if mask is not None and out.ndim == 3:
            out = out * mask[:, :, None].astype(out.dtype)
        return out, new_state, mask

    def output_type(self, input_type):
        return input_type


# ==========================================================================
# Misc
# ==========================================================================

@register_layer
@dataclasses.dataclass
class FrozenLayerConf(Layer):
    """Wraps another layer; gradients are zeroed by the engine
    (ref: nn/layers/FrozenLayer.java — transfer learning)."""

    inner: Optional[dict] = None  # serialized inner layer

    def _inner(self) -> Layer:
        return Layer.from_dict(self.inner)

    def has_params(self):
        return self._inner().has_params()

    def initialize(self, key, input_type, dtype=jnp.float32):
        return self._inner().initialize(key, input_type, dtype)

    def forward(self, params, state, x, *, train, rng, mask=None):
        # Frozen layers run in inference mode (no dropout) per the reference.
        return self._inner().forward(params, state, x, train=False, rng=rng, mask=mask)

    def output_type(self, input_type):
        return self._inner().output_type(input_type)

    @staticmethod
    def wrap(layer: Layer) -> "FrozenLayerConf":
        return FrozenLayerConf(inner=layer.to_dict())
