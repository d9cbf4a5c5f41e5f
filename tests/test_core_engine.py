"""Core engine tests: config round-trip, fit on Iris/synthetic-MNIST,
score decrease, evaluation — modeled on the reference's
deeplearning4j-core test strategy (MultiLayerTest.java, BackPropMLPTest.java)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    BatchNormalization, ConvolutionLayer, DenseLayer, OutputLayer,
    SubsamplingLayer,
)
from deeplearning4j_tpu.nn.conf.network import (
    MultiLayerConfiguration, NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.datasets.fetchers import IrisDataSetIterator, load_iris


def iris_mlp_conf(updater="sgd", lr=0.1):
    return (NeuralNetConfiguration.builder()
            .seed(42)
            .learning_rate(lr)
            .updater(updater)
            .weight_init("xavier")
            .list()
            .layer(DenseLayer(n_in=4, n_out=16, activation="relu"))
            .layer(OutputLayer(n_in=16, n_out=3, activation="softmax", loss="mcxent"))
            .build())


class TestConfig:
    def test_json_roundtrip(self):
        conf = iris_mlp_conf()
        j = conf.to_json()
        conf2 = MultiLayerConfiguration.from_json(j)
        assert len(conf2.layers) == 2
        assert conf2.layers[0].n_out == 16
        assert conf2.layers[1].loss == "mcxent"
        assert conf2.to_json() == j

    def test_global_override_merge(self):
        conf = (NeuralNetConfiguration.builder()
                .learning_rate(0.5)
                .updater("adam")
                .activation("tanh")
                .list()
                .layer(DenseLayer(n_in=4, n_out=8))
                .layer(DenseLayer(n_out=8, activation="relu", learning_rate=0.1))
                .layer(OutputLayer(n_out=3, loss="mcxent", activation="softmax"))
                .build())
        assert conf.layers[0].activation == "tanh"
        assert conf.layers[0].learning_rate == 0.5
        assert conf.layers[1].activation == "relu"
        assert conf.layers[1].learning_rate == 0.1
        assert conf.layers[0].updater == "adam"

    def test_input_type_inference_cnn(self):
        conf = (NeuralNetConfiguration.builder()
                .list()
                .layer(ConvolutionLayer(n_out=6, kernel=(5, 5)))
                .layer(SubsamplingLayer(kernel=(2, 2), stride=(2, 2)))
                .layer(DenseLayer(n_out=32, activation="relu"))
                .layer(OutputLayer(n_out=10, activation="softmax"))
                .set_input_type(InputType.convolutional(28, 28, 1))
                .build())
        # conv: 28-5+1=24 → pool 12 → dense nIn = 12*12*6
        assert conf.layers[0].n_in == 1
        assert conf.layers[2].n_in == 12 * 12 * 6
        assert 2 in conf.preprocessors  # CnnToFF inserted


class TestTraining:
    def test_iris_score_decreases(self):
        net = MultiLayerNetwork(iris_mlp_conf()).init()
        ds = load_iris().shuffle(0)
        s0 = net.score(ds)
        net.fit(IrisDataSetIterator(50), epochs=30)
        s1 = net.score(ds)
        assert s1 < s0 * 0.7, f"score did not decrease: {s0} -> {s1}"

    def test_iris_accuracy(self):
        from deeplearning4j_tpu.datasets.normalizers import NormalizerStandardize
        from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
        net = MultiLayerNetwork(iris_mlp_conf(updater="adam", lr=0.02)).init()
        ds = load_iris().shuffle(0)
        norm = NormalizerStandardize().fit(ds)
        ds = norm.transform(ds)
        net.fit(ListDataSetIterator(ds, 50), epochs=60)
        ev = net.evaluate(ds)
        assert ev.accuracy() > 0.9, ev.stats()

    @pytest.mark.parametrize("updater", ["sgd", "adam", "nesterovs", "rmsprop",
                                         "adagrad", "adadelta"])
    def test_all_updaters_reduce_loss(self, updater):
        lr = {"adadelta": 1.0, "adam": 0.05, "rmsprop": 0.01}.get(updater, 0.1)
        net = MultiLayerNetwork(iris_mlp_conf(updater=updater, lr=lr)).init()
        ds = load_iris().shuffle(1)
        s0 = net.score(ds)
        net.fit(IrisDataSetIterator(150), epochs=40)
        assert net.score(ds) < s0

    def test_param_flat_view_roundtrip(self):
        net = MultiLayerNetwork(iris_mlp_conf()).init()
        flat = net.params()
        assert flat.shape == (4 * 16 + 16 + 16 * 3 + 3,)
        net2 = MultiLayerNetwork(iris_mlp_conf()).init()
        net2.set_params(flat)
        np.testing.assert_allclose(np.asarray(net2.params()),
                                   np.asarray(flat), rtol=1e-6)
        out1 = net.output(load_iris().features[:5])
        out2 = net2.output(load_iris().features[:5])
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-5)


class TestCnn:
    def test_lenet_forward_shapes(self):
        conf = (NeuralNetConfiguration.builder()
                .seed(7)
                .list()
                .layer(ConvolutionLayer(n_out=20, kernel=(5, 5), activation="identity"))
                .layer(SubsamplingLayer(pooling_type="max"))
                .layer(ConvolutionLayer(n_out=50, kernel=(5, 5), activation="identity"))
                .layer(SubsamplingLayer(pooling_type="max"))
                .layer(DenseLayer(n_out=500, activation="relu"))
                .layer(OutputLayer(n_out=10, activation="softmax"))
                .set_input_type(InputType.convolutional(28, 28, 1))
                .build())
        net = MultiLayerNetwork(conf).init()
        x = np.random.default_rng(0).normal(size=(4, 1, 28, 28)).astype(np.float32)
        out = net.output(x)
        assert out.shape == (4, 10)
        np.testing.assert_allclose(np.asarray(out).sum(axis=1), 1.0, rtol=1e-4)

    def test_cnn_with_batchnorm_trains(self):
        conf = (NeuralNetConfiguration.builder()
                .seed(3)
                .learning_rate(0.05)
                .updater("adam")
                .list()
                .layer(ConvolutionLayer(n_out=8, kernel=(3, 3), activation="identity"))
                .layer(BatchNormalization(activation="relu"))
                .layer(SubsamplingLayer())
                .layer(DenseLayer(n_out=32, activation="relu"))
                .layer(OutputLayer(n_out=10, activation="softmax"))
                .set_input_type(InputType.convolutional(14, 14, 1))
                .build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.default_rng(1)
        from deeplearning4j_tpu.datasets.dataset import DataSet
        x = rng.normal(size=(64, 1, 14, 14)).astype(np.float32)
        y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 64)]
        ds = DataSet(x, y)
        s0 = net.score(ds)
        from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
        net.fit(ListDataSetIterator(ds, 32), epochs=20)
        assert net.score(ds) < s0
        # BN running stats must have moved
        assert not np.allclose(np.asarray(net.net_state[1]["mean"]), 0.0)


class TestConvInternalLayout:
    def test_nhwc_internal_matches_nchw(self, monkeypatch):
        """DL4J_CONV_LAYOUT=nhwc is a pure layout change: forward AND
        gradients must match the NCHW path."""
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.ops import convolution as conv_ops

        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(2, 3, 8, 8)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(5, 3, 3, 3)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(5,)).astype(np.float32))

        def loss(x, w, b):
            return jnp.sum(conv_ops.conv2d(x, w, b, stride=(2, 2),
                                           pad=(1, 1)) ** 2)

        monkeypatch.delenv("DL4J_CONV_LAYOUT", raising=False)
        y_nchw = conv_ops.conv2d(x, w, b, stride=(2, 2), pad=(1, 1))
        g_nchw = jax.grad(loss, argnums=(0, 1, 2))(x, w, b)
        monkeypatch.setenv("DL4J_CONV_LAYOUT", "nhwc")
        y_nhwc = conv_ops.conv2d(x, w, b, stride=(2, 2), pad=(1, 1))
        g_nhwc = jax.grad(loss, argnums=(0, 1, 2))(x, w, b)

        np.testing.assert_allclose(np.asarray(y_nchw), np.asarray(y_nhwc),
                                   rtol=1e-5, atol=1e-5)
        for a, bb in zip(g_nchw, g_nhwc):
            np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                       rtol=1e-4, atol=1e-4)

    def test_nhwc_same_padding(self, monkeypatch):
        import jax.numpy as jnp
        from deeplearning4j_tpu.ops import convolution as conv_ops
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(1, 2, 7, 7)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(4, 2, 3, 3)).astype(np.float32))
        monkeypatch.delenv("DL4J_CONV_LAYOUT", raising=False)
        y0 = conv_ops.conv2d(x, w, border_mode="same")
        monkeypatch.setenv("DL4J_CONV_LAYOUT", "nhwc")
        y1 = conv_ops.conv2d(x, w, border_mode="same")
        assert y0.shape == y1.shape == (1, 4, 7, 7)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   rtol=1e-5, atol=1e-5)


# ConvolutionLayer.forward is the only convolution the benchmark's cells
# run.  The reference below shares nothing with it: no lax.conv, patches
# gathered by strided slices and summed in float32, activations spelled out.
_REF_ACTS = {"identity": lambda y: y, "relu": lambda y: jnp.maximum(y, 0.0),
             "tanh": jnp.tanh}


def _conv_layer_reference(x, w, b, stride, pad, dilation, mode, act):
    """NCHW x, OIHW w -> act(conv(x, w) + b), all float32."""
    (sh, sw), (dh, dw) = stride, dilation
    kh, kw = w.shape[2:]
    pads = []
    for size, k, s, d, p in zip(x.shape[2:], (kh, kw), stride, dilation, pad):
        if mode == "same":      # XLA's SAME: the odd cell goes high
            total = max((-(-size // s) - 1) * s + (k - 1) * d + 1 - size, 0)
            pads.append((total // 2, total - total // 2))
        else:
            pads.append((p, p))
    xp = jnp.pad(x, ((0, 0), (0, 0), pads[0], pads[1]))
    oh = (xp.shape[2] - (kh - 1) * dh - 1) // sh + 1
    ow = (xp.shape[3] - (kw - 1) * dw - 1) // sw + 1
    y = 0.0
    for i in range(kh):
        for j in range(kw):
            patch = xp[:, :, i * dh:i * dh + (oh - 1) * sh + 1:sh,
                       j * dw:j * dw + (ow - 1) * sw + 1:sw]
            y = y + jnp.einsum("nchw,oc->nohw", patch, w[:, :, i, j])
    return _REF_ACTS[act](y + b[None, :, None, None])


_CONV_SHAPES = [       # the grid the fused kernel was held to, stride 1
    ((2, 3, 10, 10), (3, 3), (0, 0), "truncate"),
    ((2, 3, 10, 10), (3, 3), (1, 1), "truncate"),
    ((1, 1, 28, 28), (5, 5), (0, 0), "truncate"),
    ((2, 4, 9, 7), (3, 3), (0, 0), "same"),
    ((2, 2, 8, 8), (2, 2), (0, 0), "same"),   # even kernel: SAME pads high
]
_CONV_CASES = [
    pytest.param(shape, kernel, (1, 1), pad, (1, 1), mode, act, "float32",
                 id=f"{'x'.join(map(str, shape))}-k{kernel[0]}-p{pad[0]}-"
                    f"{mode}-{act}")
    for shape, kernel, pad, mode in _CONV_SHAPES
    for act in ("identity", "relu", "tanh")
] + [
    pytest.param((2, 3, 9, 8), (3, 3), (s, s), (1, 1), (d, d), mode, "relu",
                 dtype, id=f"s{s}-d{d}-{mode}-{dtype}")
    for s in (1, 2) for d in (1, 2) for mode in ("same", "truncate")
    for dtype in ("float32", "bfloat16")
]


class TestConvolutionLayerForward:
    @pytest.mark.parametrize(
        "shape,kernel,stride,pad,dilation,mode,act,dtype", _CONV_CASES)
    def test_forward_and_grad_match_reference(self, shape, kernel, stride,
                                              pad, dilation, mode, act,
                                              dtype):
        rng = np.random.default_rng(11)
        cout = 6
        layer = ConvolutionLayer(
            n_in=shape[1], n_out=cout, kernel=kernel, stride=stride,
            padding=pad, dilation=dilation, convolution_mode=mode,
            activation=act)
        # values a bfloat16 holds exactly, so that both sides see the same
        # operands and differ only in how they round on the way
        x, w, b = (jnp.asarray(a, jnp.bfloat16).astype(jnp.float32)
                   for a in (rng.normal(size=shape),
                             rng.normal(size=(cout, shape[1]) + kernel) * 0.2,
                             rng.normal(size=(cout,))))
        ref = _conv_layer_reference(x, w, b, stride, pad, dilation, mode, act)
        out = layer.output_type(
            InputType.convolutional(shape[2], shape[3], shape[1]))
        assert (out.channels, out.height, out.width) == ref.shape[1:]
        cot = jnp.asarray(rng.normal(size=ref.shape), jnp.float32)

        def ours(x, w, b):
            y, _, _ = layer.forward(
                {"W": w.astype(dtype), "b": b.astype(dtype)}, {},
                x.astype(dtype), train=False, rng=None)
            assert y.dtype == jnp.dtype(dtype)
            return y.astype(jnp.float32)

        got = ours(x, w, b)
        assert got.shape == ref.shape
        g_got = jax.grad(lambda *a: jnp.sum(ours(*a) * cot), (0, 1, 2))(x, w, b)
        g_ref = jax.grad(lambda *a: jnp.sum(_conv_layer_reference(
            *a, stride, pad, dilation, mode, act) * cot), (0, 1, 2))(x, w, b)
        for a, r in zip((got,) + g_got, (ref,) + g_ref):
            a, r = np.asarray(a), np.asarray(r)
            if dtype == "float32":
                np.testing.assert_allclose(a, r, rtol=1e-5, atol=1e-5)
            else:
                # 8 bits of mantissa: 2^-8 an operation, over a sum of up
                # to 27 products and a rounded result; and a relu whose
                # input rounds across zero moves single entries
                assert np.linalg.norm(a - r) <= 2e-2 * np.linalg.norm(r)


class TestFusedSteps:
    """fit(fused_steps=K): K batches per compiled launch via lax.scan —
    the dispatch-elimination mode (no reference analog; its fit loop is
    per-batch, MultiLayerNetwork.fit :996)."""

    def _net(self):
        return (NeuralNetConfiguration.builder()
                .seed(11).learning_rate(0.1).updater("adam")
                .list()
                .layer(DenseLayer(n_in=4, n_out=16, activation="relu"))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"))
                .build())

    def _batches(self, n_batches, batch=8, seed=0):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        rng = np.random.default_rng(seed)
        out = []
        for _ in range(n_batches):
            x = rng.normal(size=(batch, 4)).astype(np.float32)
            y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, batch)]
            out.append(DataSet(x, y))
        return out

    def test_fused_matches_per_step_exactly(self):
        from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
        batches = self._batches(9)
        a = MultiLayerNetwork(self._net()).init()
        b = MultiLayerNetwork(self._net()).init()
        b.net_params = jax.tree_util.tree_map(jnp.array, a.net_params)
        a.fit(ListDataSetIterator(list(batches)))
        b.fit(ListDataSetIterator(list(batches)), fused_steps=4)
        assert a.iteration == b.iteration == 9
        for pa, pb in zip(a.net_params, b.net_params):
            for kk in pa:
                np.testing.assert_allclose(
                    np.asarray(pa[kk]), np.asarray(pb[kk]),
                    rtol=2e-5, atol=2e-6)

    def test_ragged_tail_and_listener_cadence(self):
        from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
        from deeplearning4j_tpu.nn.listeners import IterationListener

        fired = []

        class Probe(IterationListener):
            def iteration_done(self, model, iteration):
                fired.append(iteration)

        net = MultiLayerNetwork(self._net()).init()
        net.set_listeners(Probe())
        # 7 batches, K=3: first launch per-step (structure warmup),
        # then scan groups; every batch is consumed exactly once
        net.fit(ListDataSetIterator(self._batches(7)), fused_steps=3)
        assert net.iteration == 7
        assert fired[-1] == 7
        assert fired == sorted(fired)

    def test_fused_respects_dropout_rng_difference(self):
        # not a bit-exactness case (per-step path splits the host key per
        # batch; fused folds per index) — just convergence sanity
        from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
        conf = (NeuralNetConfiguration.builder()
                .seed(5).learning_rate(0.05).updater("sgd")
                .list()
                .layer(DenseLayer(n_in=4, n_out=32, activation="relu",
                                  dropout=0.5))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"))
                .build())
        net = MultiLayerNetwork(conf).init()
        net.fit(ListDataSetIterator(self._batches(8)), epochs=3,
                fused_steps=4)
        assert np.isfinite(float(net._score))

    def test_fused_with_rnn_layer_standard_backprop(self):
        """Round-4 review: an RNN layer under standard backprop emits a
        carried rnn_state; the fused scan must strip it in-body (closed
        carry structure, no cross-batch state leak)."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
        from deeplearning4j_tpu.nn.conf.layers import (GravesLSTM,
                                                       RnnOutputLayer)
        conf = (NeuralNetConfiguration.builder()
                .seed(2).learning_rate(0.05).updater("sgd")
                .list()
                .layer(GravesLSTM(n_in=5, n_out=8))
                .layer(RnnOutputLayer(n_out=3, activation="softmax",
                                      loss="mcxent"))
                .build())
        rng = np.random.default_rng(1)
        bs = []
        for _ in range(6):
            x = rng.normal(size=(4, 7, 5)).astype(np.float32)
            y = np.eye(3, dtype=np.float32)[
                rng.integers(0, 3, (4, 7))].astype(np.float32)
            bs.append(DataSet(x, y))
        a = MultiLayerNetwork(conf).init()
        b = MultiLayerNetwork(conf).init()
        b.net_params = jax.tree_util.tree_map(jnp.array, a.net_params)
        a.fit(ListDataSetIterator(list(bs)))
        b.fit(ListDataSetIterator(list(bs)), fused_steps=3)
        assert a.iteration == b.iteration == 6
        for pa, pb in zip(a.net_params, b.net_params):
            for kk in pa:
                np.testing.assert_allclose(
                    np.asarray(pa[kk]), np.asarray(pb[kk]),
                    rtol=2e-5, atol=2e-6)

    def test_iterations_gt1_falls_back_to_per_step(self):
        from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
        conf = self._net()
        conf.global_conf.iterations = 3
        net = MultiLayerNetwork(conf).init()
        net.fit(ListDataSetIterator(self._batches(4)), fused_steps=2)
        # 4 batches x 3 iterations each — fused path would have lost 2
        assert net.iteration == 12
