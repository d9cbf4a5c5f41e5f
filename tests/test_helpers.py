"""Helper-selection tier (ops/helpers.py): availability/kill-switch
semantics, trace-time selection metering, warm validation, and the
fallback-equivalence contract through the public fit()/output() path —
the cuDNN-helper-with-builtin-fallback pattern the reference runs
(ConvolutionLayer.java:157-212), TPU-native."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import helpers
from deeplearning4j_tpu.ops import pallas_kernels as pk


@pytest.fixture(autouse=True)
def _clean_tiers():
    pk._disabled.clear()
    helpers.reset_validation()
    yield
    pk._disabled.clear()
    helpers.reset_validation()


def _counter_value(name, op):
    from deeplearning4j_tpu import monitor
    fam = monitor.get_registry().get(name)
    if fam is None:
        return 0.0
    for s in fam.samples():
        if s["labels"].get("op") == op:
            return s["value"]
    return 0.0


# ---------------------------------------------------------------------------
# Availability / kill-switch matrix
# ---------------------------------------------------------------------------

class TestAvailability:
    def test_off_tpu_default_is_fallback(self):
        for op in helpers.OPS:
            assert not helpers.available(op)

    def test_global_kill_beats_force(self, monkeypatch):
        monkeypatch.setenv("DL4J_PALLAS", "0")
        monkeypatch.setenv("DL4J_PALLAS_DROPOUT", "1")
        assert not helpers.available("dropout")

    def test_per_tier_force_on_and_off(self, monkeypatch):
        monkeypatch.setenv("DL4J_PALLAS_DROPOUT", "1")
        assert helpers.available("dropout")
        assert not helpers.available("lstm_step")  # other tiers untouched
        monkeypatch.setenv("DL4J_PALLAS_DROPOUT", "0")
        assert not helpers.available("dropout")

    def test_runtime_kill_switch_beats_force(self, monkeypatch):
        monkeypatch.setenv("DL4J_PALLAS_LSTM", "1")
        assert helpers.available("lstm_step")
        pk.disable_kernels("mosaic said no", tier="lstm")
        assert not helpers.available("lstm_step")

    @pytest.mark.parametrize("op", helpers.OPS)
    def test_fake_tpu_default(self, monkeypatch, op):
        monkeypatch.setenv("DL4J_TPU", "1")
        assert helpers.available(op)

    def test_disable_all_tiers(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU", "1")
        pk.disable_kernels("everything broke")
        for op in helpers.OPS:
            assert not helpers.available(op)
        assert set(pk._disabled) == set(pk.ALL_TIERS)


# ---------------------------------------------------------------------------
# Trace-time selection + metering
# ---------------------------------------------------------------------------

class TestSelection:
    def test_dropout_selection(self, monkeypatch):
        x = jnp.ones((64, 128), jnp.float32)
        key = jax.random.PRNGKey(0)
        out_dense = helpers.dropout(x, 0.5, key)
        monkeypatch.setenv("DL4J_PALLAS_DROPOUT", "1")
        out_fused = helpers.dropout(x, 0.5, key)
        # different streams (bernoulli vs counter hash), same contract
        for out in (out_dense, out_fused):
            kept = float(jnp.mean(out != 0))
            assert abs(kept - 0.5) < 0.1
            assert bool(jnp.all((out == 0) | (out == 2.0)))
        np.testing.assert_array_equal(
            np.asarray(out_fused),
            np.asarray(pk.fused_threshold_dropout(x, 0.5, key)))

    def test_lstm_wanted_gate(self, monkeypatch):
        from deeplearning4j_tpu.ops import activations as act_ops
        params = {"RW": jnp.zeros((16, 64)), "pI": jnp.zeros(16),
                  "pF": jnp.zeros(16), "pO": jnp.zeros(16)}
        x = jnp.zeros((4, 8, 8), jnp.float32)
        assert not helpers.lstm_step_wanted(params, x, jax.nn.sigmoid,
                                            jnp.tanh)   # off-TPU
        monkeypatch.setenv("DL4J_PALLAS_LSTM", "1")
        assert helpers.lstm_step_wanted(params, x, jax.nn.sigmoid, jnp.tanh)
        assert helpers.lstm_step_wanted(params, x, act_ops.get("sigmoid"),
                                        act_ops.get("tanh"))
        # exotic gate activation keeps the composable XLA cell
        assert not helpers.lstm_step_wanted(params, x, act_ops.get("relu"),
                                            jnp.tanh)
        assert not helpers.lstm_step_wanted(params, x, jax.nn.sigmoid,
                                            jnp.tanh, peephole=False)

    def test_xent_wanted_thresholds(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU", "1")
        assert helpers.softmax_xent_wanted(512, 512)
        assert not helpers.softmax_xent_wanted(4, 64)      # narrow vocab
        monkeypatch.setenv("DL4J_FUSED_XENT", "0")
        assert not helpers.softmax_xent_wanted(512, 512)   # forced off
        monkeypatch.delenv("DL4J_TPU")
        monkeypatch.setenv("DL4J_FUSED_XENT", "1")
        assert helpers.softmax_xent_wanted(4, 64)          # forced on

    def test_attention_wanted(self, monkeypatch):
        q = jnp.zeros((2, 2, 256, 64), jnp.float32)
        assert not helpers.attention_wanted(q)
        monkeypatch.setenv("DL4J_PALLAS_FLASH", "1")
        assert helpers.attention_wanted(q)
        assert not helpers.attention_wanted(
            jnp.zeros((2, 2, 64, 64), jnp.float32))  # short T: dense


# ---------------------------------------------------------------------------
# Warm validation / self-test
# ---------------------------------------------------------------------------

class TestWarmValidation:
    def test_self_test_covers_every_registered_helper(self):
        st = helpers.kernel_self_test()
        for h in (helpers.helper_for(op) for op in helpers.OPS):
            assert st[h.test_name] == "ok"
        assert st["interpret_mode"] is True
        assert "disabled" not in st

    def test_selftest_metrics_exposed(self):
        from deeplearning4j_tpu import monitor
        helpers.kernel_self_test()
        snap = monitor.get_registry().snapshot()
        ok = {s["labels"]["op"]: s["value"]
              for s in snap["dl4j_pallas_selftest_ok"]["samples"]}
        assert set(helpers.OPS) <= set(ok)
        assert all(v == 1.0 for v in ok.values())
        tiers = {s["labels"]["tier"]: s["value"]
                 for s in snap["dl4j_pallas_tier_disabled"]["samples"]}
        assert set(pk.ALL_TIERS) <= set(tiers)

    def test_failing_helper_disables_only_its_tier(self, monkeypatch):
        def boom(*a, **k):
            raise RuntimeError("mosaic rejected")
        monkeypatch.setattr(pk, "fused_threshold_dropout", boom)
        st = helpers.kernel_self_test()
        assert st["dropout"].startswith("error")
        assert st["lstm_step"] == "ok"
        assert st["softmax_xent"] == "ok"
        assert "dropout" in pk._disabled
        assert "lstm" not in pk._disabled and "flash" not in pk._disabled

    def test_ensure_validated_cheap_off_tpu(self):
        res = helpers.ensure_validated()
        assert "skipped" in res
        assert helpers.ensure_validated() is res   # cached

    def test_ensure_validated_runs_eligible_tiers(self, monkeypatch):
        monkeypatch.setenv("DL4J_PALLAS_DROPOUT", "1")
        res = helpers.ensure_validated()
        assert res["dropout"] == "ok"
        assert "lstm_step" not in res              # only eligible tiers run

# ---------------------------------------------------------------------------
# Fallback equivalence through the public fit()/output() path
# ---------------------------------------------------------------------------

def _fit_conv_net(monkeypatch, env, steps=3):
    """Train a tiny conv net; returns (flat params, output) — fresh model
    per call, same seed/data."""
    helpers.reset_validation()
    for k, v in env.items():
        if v is None:
            monkeypatch.delenv(k, raising=False)
        else:
            monkeypatch.setenv(k, v)
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.params import flatten
    conf = (NeuralNetConfiguration.builder().seed(7).learning_rate(0.05)
            .updater("sgd").list()
            .layer(L.ConvolutionLayer(n_out=4, kernel=(3, 3),
                                      activation="relu",
                                      convolution_mode="same"))
            .layer(L.SubsamplingLayer())
            .layer(L.DenseLayer(n_out=16, activation="relu"))
            .layer(L.OutputLayer(n_out=10, activation="softmax",
                                 loss="mcxent"))
            .set_input_type(InputType.convolutional(8, 8, 1))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(16, 1, 8, 8)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 16)]
    for _ in range(steps):
        net.fit(x, y)
    out = np.asarray(net.output(x))
    return np.asarray(flatten(net.net_params)), out


def _fit_lstm_net(monkeypatch, env, steps=3):
    helpers.reset_validation()
    for k, v in env.items():
        if v is None:
            monkeypatch.delenv(k, raising=False)
        else:
            monkeypatch.setenv(k, v)
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.params import flatten
    conf = (NeuralNetConfiguration.builder().seed(11).learning_rate(0.05)
            .updater("sgd").list()
            .layer(L.GravesLSTM(n_in=6, n_out=16))
            .layer(L.RnnOutputLayer(n_in=16, n_out=5, activation="softmax",
                                    loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(1)
    x = rng.normal(size=(8, 7, 6)).astype(np.float32)
    y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, (8, 7))]
    for _ in range(steps):
        net.fit(x, y)
    out = np.asarray(net.output(x))
    return np.asarray(flatten(net.net_params)), out


class TestFallbackEquivalence:
    """Disabling any tier must reproduce byte-identical fit()/output()
    results through the dense fallback (the helper refactor cannot
    perturb the builtin path), and the forced-fused leg must agree to
    kernel-parity tolerance."""

    def test_conv_net_tier_disable_is_byte_identical(self, monkeypatch):
        p_base, o_base = _fit_conv_net(monkeypatch, {})
        p_off, o_off = _fit_conv_net(monkeypatch, {"DL4J_PALLAS": "0"})
        assert np.array_equal(p_base, p_off)
        assert np.array_equal(o_base, o_off)

    def test_lstm_net_tier_disable_is_byte_identical(self, monkeypatch):
        p_base, o_base = _fit_lstm_net(monkeypatch, {})
        p_off, o_off = _fit_lstm_net(monkeypatch, {"DL4J_PALLAS": "0"})
        p_tier, o_tier = _fit_lstm_net(monkeypatch,
                                       {"DL4J_PALLAS_LSTM": "0"})
        assert np.array_equal(p_base, p_off)
        assert np.array_equal(o_base, o_off)
        assert np.array_equal(p_base, p_tier)
        assert np.array_equal(o_base, o_tier)

    def test_lstm_net_fused_matches_dense(self, monkeypatch):
        p_base, o_base = _fit_lstm_net(monkeypatch, {})
        p_fused, o_fused = _fit_lstm_net(monkeypatch,
                                         {"DL4J_PALLAS_LSTM": "1"})
        np.testing.assert_allclose(p_fused, p_base, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(o_fused, o_base, rtol=1e-4, atol=1e-5)

    def test_xent_tier_disable_is_byte_identical(self, monkeypatch):
        """The migrated xent tier keeps its fallback-equivalence too:
        forcing the tier off through the helper layer reproduces the
        dense logsumexp scores bit-for-bit."""
        from deeplearning4j_tpu.ops import losses
        rng = np.random.default_rng(3)
        logits = jnp.asarray(rng.normal(size=(64, 512)), jnp.float32)
        y = jnp.asarray(np.eye(512, dtype=np.float32)[
            rng.integers(0, 512, 64)])
        monkeypatch.setenv("DL4J_PALLAS", "0")
        a = np.asarray(losses.mcxent(y, logits, "softmax"))
        monkeypatch.delenv("DL4J_PALLAS")
        monkeypatch.setenv("DL4J_PALLAS_XENT", "0")
        b = np.asarray(losses.mcxent(y, logits, "softmax"))
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# The selections the chip makes by default, on the CPU: bf16 policy + every
# fused tier (interpret mode).  The f32/dense branches the rest of the suite
# runs are NOT the ones platform.is_tpu() picks.
# ---------------------------------------------------------------------------

@pytest.fixture
def chip_default_selections(monkeypatch):
    """bf16 and every tier a chip selects, forced (interpret mode)."""
    from deeplearning4j_tpu.ops import dtypes
    for env in helpers._ENV_TIER.values():
        monkeypatch.setenv(env, "1")
    dtypes.set_default_policy(dtypes.BF16)
    yield
    dtypes.set_default_policy(None)


def _lenet():
    from deeplearning4j_tpu.models.lenet import lenet
    return lenet(), (1, 28, 28)


def _vgg16_cifar10():
    from deeplearning4j_tpu.models.vgg import vgg16_cifar10
    return vgg16_cifar10(), (3, 32, 32)


def _resnet18():
    from deeplearning4j_tpu.models.resnet import resnet18
    return resnet18(height=32, width=32, channels=3, n_classes=10), \
        (3, 32, 32)


class TestChipDefaultSelections:
    @pytest.mark.parametrize("build", [_lenet, _vgg16_cifar10, _resnet18],
                             ids=["lenet", "vgg16_cifar10", "resnet18-graph"])
    def test_one_fit_step_bf16_all_tiers(self, chip_default_selections,
                                         build):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        net, in_shape = build()
        net.init()
        rng = np.random.default_rng(0)
        x = rng.normal(size=(8,) + in_shape).astype(np.float32)
        y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 8)]
        net.fit(DataSet(x, y))
        assert np.isfinite(float(net.score()))
        assert not pk._disabled       # the warm self-test passed in bf16 too

    def test_fake_tpu_cnn_step_holds_no_pallas_call(self, monkeypatch):
        # the step a chip traces for a CNN: bf16 convolutions as XLA's own
        # HLO, no kernel call (and no NCHW/NHWC transposes around one)
        monkeypatch.setenv("DL4J_TPU", "1")
        net, in_shape = _resnet18()
        net.init()
        args = (net.net_params, net.net_state, net.opt_states,
                (jnp.zeros((4,) + in_shape),), (jnp.zeros((4, 10)),),
                None, None, jnp.int32(0), jax.random.PRNGKey(0))
        jaxpr = str(jax.make_jaxpr(net._build_step_raw())(*args))
        assert "conv_general_dilated" in jaxpr and "bf16" in jaxpr
        assert "pallas_call" not in jaxpr

    def test_disabled_tier_is_logged_once_with_the_message(
            self, monkeypatch, caplog):
        def boom(*a, **k):
            raise RuntimeError("mosaic rejected block shape")
        monkeypatch.setattr(pk, "fused_lstm_step", boom)
        with caplog.at_level("WARNING", logger=helpers.log.name):
            helpers.kernel_self_test()
            helpers.kernel_self_test()
        said = [r for r in caplog.records if "lstm" in r.getMessage()]
        assert len(said) == 1 and said[0].levelname == "WARNING"
        assert "mosaic rejected block shape" in said[0].getMessage()

    def test_self_test_can_report_without_disabling(self, monkeypatch):
        def boom(*a, **k):
            raise RuntimeError("mosaic rejected")
        monkeypatch.setattr(pk, "fused_lstm_step", boom)
        st = helpers.kernel_self_test(disable_on_error=False)
        assert st["lstm_step"].startswith("error")
        assert not pk._disabled and "disabled" not in st


class TestPartitionedTrace:
    """A Mosaic kernel cannot be partitioned by GSPMD: under a sharding
    plan the tiers leave automatic selection.  DL4J_TPU=1 steers the
    chip's branches; a kernel that WAS selected would then be lowered
    without interpret mode and the CPU backend would refuse it, so these
    fits passing is the proof that none was."""

    def test_tiers_leave_selection_inside_the_scope(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU", "1")
        assert all(helpers.available(op) for op in helpers.OPS)
        with pk.partitioned_trace():
            assert not any(helpers.available(op) for op in helpers.OPS)
            monkeypatch.setenv("DL4J_PALLAS_DROPOUT", "1")
            assert helpers.available("dropout")  # an explicit force wins
        assert helpers.available("lstm_step")

    @pytest.mark.parametrize("how", ["parallel_wrapper", "conf_sharding"])
    def test_sharded_model_traces_dense_ops(self, monkeypatch, how):
        import jax
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
        from deeplearning4j_tpu.nn.conf import layers as L
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.conf.network import \
            NeuralNetConfiguration
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.parallel import (
            MeshConfig, ParallelWrapper, make_mesh)
        monkeypatch.setenv("DL4J_TPU", "1")
        b = (NeuralNetConfiguration.builder().seed(7).learning_rate(0.05)
             .updater("sgd"))
        if how == "conf_sharding":
            b.sharding(data=2, fsdp=4)
            # fit() warm-validates eligible tiers first; on the CPU that
            # self-test cannot pass, and it is not what is under test
            monkeypatch.setattr(helpers, "ensure_validated", lambda: {})
        # the dense layer's dropout sees [16, 256]: a size the dropout
        # tier takes on a chip
        conf = (b.list()
                .layer(L.ConvolutionLayer(n_out=16, kernel=(3, 3),
                                          activation="relu",
                                          convolution_mode="same"))
                .layer(L.SubsamplingLayer())
                .layer(L.DenseLayer(n_out=16, activation="relu",
                                    dropout=0.5))
                .layer(L.OutputLayer(n_out=10, activation="softmax",
                                     loss="mcxent"))
                .set_input_type(InputType.convolutional(8, 8, 1))
                .build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.default_rng(0)
        ds = DataSet(rng.normal(size=(16, 1, 8, 8)).astype(np.float32),
                     np.eye(10, dtype=np.float32)[rng.integers(0, 10, 16)])
        selected = _counter_value("dl4j_pallas_selected_total", "dropout")
        fallback = _counter_value("dl4j_pallas_fallback_total", "dropout")
        if how == "parallel_wrapper":
            mesh = make_mesh(MeshConfig(data=2, fsdp=2),
                             devices=jax.devices()[:4])
            ParallelWrapper(net, mesh).fit(ListDataSetIterator([ds]))
        else:
            net.fit(ds)
        # the trained model's params stay on the mesh: score() and
        # output() are partitioned programs too
        assert np.isfinite(net.score(ds))
        assert np.all(np.isfinite(np.asarray(net.output(ds.features))))
        assert _counter_value("dl4j_pallas_selected_total",
                              "dropout") == selected
        assert _counter_value("dl4j_pallas_fallback_total",
                              "dropout") >= fallback + 1
