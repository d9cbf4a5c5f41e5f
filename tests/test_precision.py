"""Mixed-precision policy tests (VERDICT r1 item 2).

The engine casts params+inputs to the compute dtype inside the loss
closure (ops/dtypes.Policy), keeps float32 master params/updater state,
and accumulates the loss in float32.  On this CPU test mesh the auto
policy is FLOAT32, so these tests force bfloat16 explicitly and assert
(a) the compiled step really computes in bf16 (jaxpr inspection),
(b) master params/optimizer state stay f32, (c) training still learns.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    BatchNormalization, ConvolutionLayer, DenseLayer, OutputLayer,
    SubsamplingLayer)
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops import dtypes as dtype_ops


def _toy_net(precision):
    return (NeuralNetConfiguration.builder()
            .seed(7).learning_rate(0.1).updater("adam")
            .precision(precision)
            .list()
            .layer(DenseLayer(n_in=8, n_out=16, activation="relu"))
            .layer(BatchNormalization())
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())


def _toy_data(n=64):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 8)).astype(np.float32)
    labels = rng.integers(0, 3, n)
    x[np.arange(n), labels] += 2.5  # separable
    y = np.eye(3, dtype=np.float32)[labels]
    return x, y


def test_policy_resolution():
    assert dtype_ops.resolve("float32") is dtype_ops.FLOAT32
    assert dtype_ops.resolve("float") is dtype_ops.FLOAT32  # reference name
    assert dtype_ops.resolve("bf16") is dtype_ops.BF16
    assert dtype_ops.resolve("half") is dtype_ops.BF16  # no fp16 on TPU
    assert dtype_ops.resolve("double") is dtype_ops.FLOAT64
    # auto on the CPU test backend is f32
    assert dtype_ops.resolve(None) is dtype_ops.FLOAT32
    with pytest.raises(ValueError):
        dtype_ops.resolve("int7")


def test_cast_to_compute_leaves_f64_and_ints_alone():
    p = dtype_ops.BF16
    with jax.enable_x64(True):
        tree = {"w": jnp.ones((2, 2), jnp.float32),
                "idx": jnp.zeros((3,), jnp.int32),
                "check": jnp.ones((2,), jnp.float64)}
        out = p.cast_to_compute(tree)
        assert out["w"].dtype == jnp.bfloat16
        assert out["idx"].dtype == jnp.int32
        assert out["check"].dtype == jnp.float64  # gradient-check path untouched


def test_bf16_step_computes_in_bf16_with_f32_master():
    net = MultiLayerNetwork(_toy_net("bfloat16")).init()
    x, y = _toy_data()
    # (a) the traced step contains bf16 compute
    step = net._build_step_raw()
    jaxpr = str(jax.make_jaxpr(step)(
        net.net_params, net.net_state, net.opt_states,
        jnp.asarray(x), jnp.asarray(y), None, None,
        jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0)))
    assert "bf16" in jaxpr, "no bfloat16 compute in the compiled step"
    # the dense matmul itself runs in bf16 (not just a stray cast)
    assert "dot_general" in jaxpr

    net.fit(x, y)
    # (b) master params, updater state, BN running stats all stay f32
    for leaf in jax.tree_util.tree_leaves(net.net_params):
        assert leaf.dtype == jnp.float32
    for leaf in jax.tree_util.tree_leaves(net.opt_states):
        assert leaf.dtype == jnp.float32
    for leaf in jax.tree_util.tree_leaves(net.net_state):
        assert leaf.dtype == jnp.float32
    assert np.isfinite(net.score())


def test_bf16_training_learns():
    net = MultiLayerNetwork(_toy_net("bfloat16")).init()
    x, y = _toy_data()
    net.fit(x, y)
    first = net.score()
    for _ in range(30):
        net.fit(x, y)
    assert net.score() < first
    acc = (net.predict(x) == np.argmax(y, axis=1)).mean()
    assert acc > 0.8


def test_bf16_output_returns_f32():
    net = MultiLayerNetwork(_toy_net("bfloat16")).init()
    x, _ = _toy_data(8)
    out = net.output(x)
    assert out.dtype == jnp.float32
    assert out.shape == (8, 3)


def test_bf16_matches_f32_direction():
    """One bf16 step moves params in (approximately) the f32 direction."""
    x, y = _toy_data(32)
    updates = {}
    for prec in ("float32", "bfloat16"):
        net = MultiLayerNetwork(_toy_net(prec)).init()
        before = np.asarray(net.params())
        net.fit(x, y)
        updates[prec] = np.asarray(net.params()) - before
    # identical seeds → identical init; update directions near-parallel
    # (elementwise comparison is meaningless under Adam's sign-normalized
    # steps, where a bf16-rounded tiny gradient can flip an element)
    a, b = updates["float32"], updates["bfloat16"]
    cos = a @ b / (np.linalg.norm(a) * np.linalg.norm(b))
    assert cos > 0.98, cos


def test_bf16_cnn_step():
    conf = (NeuralNetConfiguration.builder()
            .seed(3).learning_rate(0.05).updater("sgd")
            .precision("bfloat16")
            .list()
            .layer(ConvolutionLayer(n_out=4, kernel=(3, 3), activation="relu"))
            .layer(SubsamplingLayer(pooling_type="max"))
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.convolutional(8, 8, 1))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(1)
    x = rng.normal(size=(16, 1, 8, 8)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 16)]
    net.fit(x, y)
    assert np.isfinite(net.score())
    for leaf in jax.tree_util.tree_leaves(net.net_params):
        assert leaf.dtype == jnp.float32


def test_bf16_computation_graph():
    from deeplearning4j_tpu.nn.conf.graph_conf import GraphBuilder
    from deeplearning4j_tpu.nn.conf.network import GlobalConf
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    g = GlobalConf(seed=5, learning_rate=0.1, updater="adam",
                   precision="bfloat16")
    conf = (GraphBuilder(g)
            .add_inputs("in")
            .add_layer("d", DenseLayer(n_in=8, n_out=16, activation="relu"), "in")
            .add_layer("out", OutputLayer(n_in=16, n_out=3, activation="softmax",
                                          loss="mcxent"), "d")
            .set_outputs("out")
            .build())
    net = ComputationGraph(conf).init()
    x, y = _toy_data(32)
    net.fit(x, y)
    assert np.isfinite(net.score())
    for leaf in jax.tree_util.tree_leaves(net.net_params):
        assert leaf.dtype == jnp.float32
    out = net.output(x)[0]
    assert out.dtype == jnp.float32


def test_bf16_conv_after_bn_inference():
    """Round-5 bug (caught by examples/resnet50_data_parallel.py):
    BN INFERENCE promoted bf16 activations to f32 through its float32
    running stats, crashing the next conv (lax.conv requires equal
    dtypes).  score()/output() on a bf16 conv->BN->conv net must work."""
    import numpy as np
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (
        BatchNormalization, ConvolutionLayer, OutputLayer)
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder()
            .seed(1).learning_rate(0.1).updater("sgd").precision("bf16")
            .list()
            .layer(ConvolutionLayer(n_out=4, kernel=(3, 3),
                                    activation="relu"))
            .layer(BatchNormalization())
            .layer(ConvolutionLayer(n_out=4, kernel=(3, 3),
                                    activation="relu"))
            .layer(OutputLayer(n_out=2, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.convolutional(8, 8, 1))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4, 1, 8, 8)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[[0, 1, 0, 1]]
    net.fit(x, y)                       # train mode already worked
    s = float(net.score(DataSet(x, y)))     # eval mode used to crash
    out = np.asarray(net.output(x))
    assert np.isfinite(s) and out.shape == (4, 2)


def test_bf16_resnet18_graph_score():
    """Same bug through the ComputationGraph eval path (residual conv
    net with BN between convs)."""
    import numpy as np
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.resnet import resnet18

    net = resnet18(height=16, width=16, n_classes=4)
    net.conf.global_conf.precision = "bf16"
    net.init()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4, 3, 16, 16)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[[0, 1, 2, 3]]
    net.fit(x, y)
    assert np.isfinite(float(net.score(DataSet(x, y))))
    assert np.asarray(net.output(x)[0]).shape == (4, 4)


# ----------------------------------------------------------------------
# Precision tiers end-to-end (ISSUE 19): quantized serving, quantized
# gradient collectives, kill switches, checkpoints
# ----------------------------------------------------------------------
@pytest.fixture
def _clean_tiers():
    from deeplearning4j_tpu.ops import helpers as prec_helpers
    from deeplearning4j_tpu.ops import quantize as qz
    prec_helpers.reset_precision_validation()
    qz.reset_disabled()
    yield
    prec_helpers.reset_precision_validation()
    qz.reset_disabled()


def _counter_value(name, **labels):
    from deeplearning4j_tpu import monitor
    fam = monitor.get_registry().get(name)
    if fam is None:
        return 0.0
    return sum(s["value"] for s in fam.samples()
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def test_tier_off_byte_identical_serving(_clean_tiers, monkeypatch):
    """DL4J_PRECISION=0 globally kills every tier: a net that ASKS for
    bf16 compute + int8 serving trains and serves bit-identically to
    plain dense (the compute tier gates at ops/dtypes.resolve)."""
    monkeypatch.setenv("DL4J_PRECISION", "0")
    x, y = _toy_data(32)

    def leg(quant):
        b = (NeuralNetConfiguration.builder()
             .seed(7).learning_rate(0.1).updater("adam"))
        if quant:
            b.precision(compute="bfloat16", infer_quant="int8",
                        grad_allreduce="int8")
        net = MultiLayerNetwork(
            b.list()
            .layer(DenseLayer(n_in=8, n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .build()).init()
        net.fit(x, y)
        if quant:
            net.quantize_inference("int8")   # must degrade to dense
        return np.asarray(net.params()), np.asarray(net.output(x))

    p0, o0 = leg(False)
    p1, o1 = leg(True)
    np.testing.assert_array_equal(p0, p1)
    np.testing.assert_array_equal(o0, o1)


def test_int8_infer_top1_agreement(_clean_tiers):
    # wide enough that the int8 matrices dominate the f32 scales/biases
    # — the ~4x resident-weight claim is about real matmul weights
    conf = (NeuralNetConfiguration.builder()
            .seed(7).learning_rate(0.1).updater("adam")
            .list()
            .layer(DenseLayer(n_in=8, n_out=128, activation="relu"))
            .layer(DenseLayer(n_out=128, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    x, y = _toy_data()
    for _ in range(10):
        net.fit(x, y)
    dense = np.asarray(net.output(x))
    net.quantize_inference("int8")
    q = np.asarray(net.output(x))
    stats = net._q_stats
    assert stats["dense_bytes"] / stats["quantized_bytes"] > 3.0
    agree = (np.argmax(q, 1) == np.argmax(dense, 1)).mean()
    assert agree >= 0.95, agree
    assert float(np.max(np.abs(q - dense))) < 0.05
    # restoring dense serving is byte-exact
    net.quantize_inference(None)
    np.testing.assert_array_equal(np.asarray(net.output(x)), dense)


def test_fp8_infer_when_supported(_clean_tiers):
    from deeplearning4j_tpu.ops import quantize as qz
    if not qz.fp8_supported():
        pytest.skip("backend has no fp8")
    net = MultiLayerNetwork(_toy_net(None)).init()
    x, y = _toy_data()
    for _ in range(10):
        net.fit(x, y)
    dense = np.asarray(net.output(x))
    net.quantize_inference("fp8")
    q = np.asarray(net.output(x))
    assert np.all(np.isfinite(q))
    agree = (np.argmax(q, 1) == np.argmax(dense, 1)).mean()
    assert agree >= 0.9, agree


def test_bf16_final_loss_close_to_f32():
    x, y = _toy_data(32)
    scores = {}
    for prec in ("float32", "bfloat16"):
        net = MultiLayerNetwork(_toy_net(prec)).init()
        for _ in range(10):
            net.fit(x, y)
        scores[prec] = float(net.score())
    assert abs(scores["bfloat16"] - scores["float32"]) < 0.05, scores


def test_error_feedback_reset_on_generation_roll(_clean_tiers):
    from deeplearning4j_tpu.ops import quantize as qz
    ef = qz.ErrorFeedback()
    rng = np.random.default_rng(0)
    v = rng.normal(size=(5000,)).astype(np.float32)
    comp, codes, scales = ef.compensate(v)
    ef.commit(comp, codes, scales)
    assert ef.residual is not None and float(np.abs(ef.residual).sum()) > 0
    before = _counter_value("dl4j_precision_ef_resets_total")
    ef.reset("generation_rolled")
    assert ef.residual is None
    assert _counter_value("dl4j_precision_ef_resets_total") >= before + 1
    # next contribution re-seeds a zero residual of the right size
    comp2, _, _ = ef.compensate(v)
    np.testing.assert_array_equal(comp2, v)


def _dist_conf(quant=None):
    b = (NeuralNetConfiguration.builder().seed(99).learning_rate(0.05)
         .updater("adam"))
    if quant is not False:
        b.distributed(processes=2, heartbeat_ms=60)
    if quant:
        b.precision(grad_allreduce=quant)
    return (b.list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .build())


def _dist_batches(n=6, rows=16, seed=7):
    from deeplearning4j_tpu.datasets.dataset import DataSet
    rng = np.random.default_rng(seed)
    return [DataSet(rng.normal(size=(rows, 4)).astype(np.float32),
                    np.eye(3, dtype=np.float32)[rng.integers(0, 3, rows)])
            for _ in range(n)]


def _run_quant_cluster(quant, epochs=2):
    """2 worker threads against one coordinator; returns
    {wid: (params, score)}."""
    import threading

    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.distributed import Coordinator, DistSession

    co = Coordinator(expected=2, lease_ms=2000)
    batches = _dist_batches()
    results, died = {}, []

    def work(wid):
        try:
            net = MultiLayerNetwork(_dist_conf(quant)).init()
            sess = DistSession(co, wid, heartbeat_ms=60)
            sess.connect()
            net._dist_session = sess
            net.fit(ListDataSetIterator(list(batches)), epochs=epochs)
            results[wid] = (np.asarray(net.params()), float(net.score()))
            sess.close()
        except BaseException as e:  # noqa: BLE001
            died.append((wid, f"{type(e).__name__}: {e}"))

    threads = [threading.Thread(target=work, args=(f"w{i}",))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(180)
        assert not t.is_alive(), "cluster worker thread hung"
    assert not died, died
    return results


def test_grad_quant_cluster_parity(_clean_tiers):
    """The quantized-collective cluster: both workers end bit-identical
    (they all apply the same reduced update), and the final loss stays
    within the documented ε=1e-2 of the single-host dense twin (error
    feedback carries the quantization error instead of dropping it)."""
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    ref = MultiLayerNetwork(_dist_conf(False)).init()
    ref.fit(ListDataSetIterator(_dist_batches()), epochs=2)
    ref_score = float(ref.score())

    int8_before = _counter_value("dl4j_precision_grad_bytes_total",
                                 dtype="int8")
    results = _run_quant_cluster("int8")
    np.testing.assert_array_equal(results["w0"][0], results["w1"][0])
    assert abs(results["w0"][1] - ref_score) <= 1e-2, \
        (results["w0"][1], ref_score)
    # the wire really was int8: the byte meter moved
    assert _counter_value("dl4j_precision_grad_bytes_total",
                          dtype="int8") > int8_before


def test_grad_quant_wire_bytes_shrink_3_5x(_clean_tiers):
    """The same steps over the f32 wire and over the int8 wire (codes
    plus one f32 scale a block): the engine's own byte meter reads at
    least 3.5x fewer bytes contributed to the barrier."""
    def wire_bytes(quant, dtype):
        before = _counter_value("dl4j_precision_grad_bytes_total",
                                dtype=dtype)
        _run_quant_cluster(quant, epochs=1)
        return _counter_value("dl4j_precision_grad_bytes_total",
                              dtype=dtype) - before

    dense = wire_bytes(None, "float32")
    int8 = wire_bytes("int8", "int8")
    assert int8 > 0 and dense >= 3.5 * int8, (dense, int8)


def test_grad_quant_kill_switch_byte_identical(_clean_tiers, monkeypatch):
    """DL4J_DIST_QUANT=0 forces the dense wire even when the conf asks
    for int8 — the cluster result is bit-identical to a dense cluster."""
    dense = _run_quant_cluster(None)
    monkeypatch.setenv("DL4J_DIST_QUANT", "0")
    killed = _run_quant_cluster("int8")
    np.testing.assert_array_equal(dense["w0"][0], killed["w0"][0])
    assert dense["w0"][1] == killed["w0"][1]


def test_checkpoint_round_trip_across_tiers(_clean_tiers, tmp_path):
    """A conf with every tier set survives write_model/load_model (the
    serde keeps the tier fields), serves identically after reload, and
    the checkpoint manifest records the active tiers."""
    from deeplearning4j_tpu.nn import serialization
    from deeplearning4j_tpu.nn.checkpoint import (
        CheckpointListener, read_manifest)

    conf = (NeuralNetConfiguration.builder()
            .seed(7).learning_rate(0.1).updater("adam")
            .precision(compute="bfloat16", infer_quant="int8",
                       grad_allreduce="int8")
            .list()
            .layer(DenseLayer(n_in=8, n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    ckpt_dir = tmp_path / "ckpt"
    net.add_listener(CheckpointListener(str(ckpt_dir),
                                        save_every_n_iterations=1))
    x, y = _toy_data(32)
    net.fit(x, y)
    entries = read_manifest(str(ckpt_dir))
    assert entries, "no checkpoint written"
    prec = entries[-1].get("precision")
    assert prec and prec["infer_quant"] == "int8", prec
    assert prec["grad_quant"] == "int8", prec
    assert prec["compute"] == "bfloat16", prec

    path = str(tmp_path / "tiers.dl4j")
    serialization.write_model(net, path)
    loaded = serialization.load_model(path)
    g = loaded.conf.global_conf
    assert g.precision == "bfloat16"
    assert g.precision_infer_quant == "int8"
    assert g.dist_grad_quant == "int8"
    np.testing.assert_array_equal(np.asarray(net.output(x)),
                                  np.asarray(loaded.output(x)))
    # the reloaded model can serve quantized straight away
    loaded.quantize_inference("int8")
    q = np.asarray(loaded.output(x))
    assert np.all(np.isfinite(q)) and q.shape == (32, 3)
