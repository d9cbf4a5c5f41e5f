"""Mesh data-parallelism tests on the virtual 8-device CPU mesh —
the reference's ParallelWrapperTest/ParallelInferenceTest pattern
(multi-worker over one host, SURVEY.md §4)."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.fetchers import load_iris
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.datasets.normalizers import NormalizerStandardize
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel import MeshConfig, ParallelInference, ParallelWrapper, make_mesh


def _net(lr=0.05, updater="adam", seed=1):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).learning_rate(lr).updater(updater)
            .list()
            .layer(DenseLayer(n_in=4, n_out=16, activation="relu"))
            .layer(OutputLayer(n_in=16, n_out=3, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _data():
    ds = load_iris().shuffle(0)
    return NormalizerStandardize().fit(ds).transform(ds)


def test_mesh_has_8_devices():
    assert len(jax.devices()) == 8
    mesh = make_mesh()
    assert mesh.shape["data"] == 8


def test_allreduce_training_decreases_loss():
    ds = _data()
    net = _net()
    pw = ParallelWrapper(net, make_mesh())
    s0 = net.score(ds)
    pw.fit(ListDataSetIterator(ds, 48), epochs=20)
    assert net.score(ds) < s0 * 0.7


def test_allreduce_matches_single_device_math():
    """Data-parallel psum training must equal single-device training on the
    same global batch (the whole point of per-step all-reduce)."""
    ds = _data()
    batch = DataSet(ds.features[:64], ds.labels[:64])

    net_a = _net(updater="sgd", lr=0.1)
    net_a.fit(ListDataSetIterator(batch, 64), epochs=3)

    net_b = _net(updater="sgd", lr=0.1)
    pw = ParallelWrapper(net_b, make_mesh())
    pw.fit(ListDataSetIterator(batch, 64), epochs=3)

    np.testing.assert_allclose(np.asarray(net_a.params()),
                               np.asarray(net_b.params()), rtol=2e-4, atol=2e-6)


def test_allreduce_nondivisible_batch_pads_not_drops():
    """Round-4 verdict weak #5: a batch not divisible by the data degree
    must train EVERY example (the reference's round-robin feedDataSet —
    ParallelWrapper.java:383) — padded rows are masked out and the valid
    rows' mask rescaled, so the sharded step equals the unsharded step
    on the ragged batch exactly.  No warning may fire."""
    import warnings
    ds = _data()
    batch = DataSet(ds.features[:58], ds.labels[:58])   # 58 % 8 = 2

    net_a = _net(updater="sgd", lr=0.1)
    net_a.fit(ListDataSetIterator(batch, 58), epochs=3)

    net_b = _net(updater="sgd", lr=0.1)
    pw = ParallelWrapper(net_b, make_mesh())
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        pw.fit(ListDataSetIterator(batch, 58), epochs=3)
    assert not [w for w in rec if "dropping" in str(w.message)]

    np.testing.assert_allclose(np.asarray(net_a.params()),
                               np.asarray(net_b.params()),
                               rtol=2e-4, atol=2e-6)
    assert net_b.last_batch_size == 58  # real examples, not padded count


def test_allreduce_pads_batch_smaller_than_degree():
    """n < data degree (6 examples over 8 devices) used to drop the
    WHOLE batch; now it pads up and trains all 6."""
    ds = _data()
    batch = DataSet(ds.features[:6], ds.labels[:6])
    net_a = _net(updater="sgd", lr=0.1)
    net_a.fit(ListDataSetIterator(batch, 6), epochs=2)
    net_b = _net(updater="sgd", lr=0.1)
    ParallelWrapper(net_b, make_mesh()).fit(
        ListDataSetIterator(batch, 6), epochs=2)
    np.testing.assert_allclose(np.asarray(net_a.params()),
                               np.asarray(net_b.params()),
                               rtol=2e-4, atol=2e-6)


def test_rnn_masked_nondivisible_batch_pads_exactly():
    """Variable-length RNN batch (features_mask set, labels_mask None)
    with a ragged size: the pad path must scale the PROPAGATED time mask
    rather than overriding it with an all-ones row mask (round-5 review
    finding) — padded training equals the unsharded step."""
    from deeplearning4j_tpu.nn.conf.layers import GravesLSTM, RnnOutputLayer
    rng = np.random.default_rng(5)
    N, T = 12, 6                      # 12 % 8 = 4
    x = rng.normal(size=(N, T, 3)).astype(np.float32)
    fm = np.zeros((N, T), np.float32)
    for i in range(N):
        fm[i, : rng.integers(2, T + 1)] = 1.0
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, (N, T))]

    def build():
        conf = (NeuralNetConfiguration.builder()
                .seed(7).learning_rate(0.1).updater("sgd")
                .list()
                .layer(GravesLSTM(n_in=3, n_out=5))
                .layer(RnnOutputLayer(n_out=2, activation="softmax",
                                      loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    ds = DataSet(x, y, features_mask=fm)
    net_a = build()
    net_a.fit(ListDataSetIterator(ds, N), epochs=2)
    net_b = build()
    ParallelWrapper(net_b, make_mesh()).fit(
        ListDataSetIterator(ds, N), epochs=2)
    np.testing.assert_allclose(np.asarray(net_a.params()),
                               np.asarray(net_b.params()),
                               rtol=3e-4, atol=3e-6)


def test_sum_reduced_net_falls_back_to_trim():
    """mini_batch=False (sum loss reduction) cannot use the mask-rescale
    padding — the trim fallback must warn instead of silently scaling
    gradients by target/n."""
    import warnings
    ds = _data()
    conf = (NeuralNetConfiguration.builder()
            .seed(1).learning_rate(0.05).updater("sgd").mini_batch(False)
            .list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    pw = ParallelWrapper(net, make_mesh())
    batch = DataSet(ds.features[:58], ds.labels[:58])
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        pw.fit(ListDataSetIterator(batch, 58), epochs=1)
    assert [w for w in rec if "dropping" in str(w.message)]


def test_param_averaging_mode():
    """averaging_frequency>1 reference-compat mode trains and converges."""
    ds = _data()
    net = _net(lr=0.05)
    pw = ParallelWrapper(net, make_mesh(MeshConfig(data=4, fsdp=1),
                                        devices=jax.devices()[:4]),
                         averaging_frequency=3)
    s0 = net.score(ds)
    pw.fit(ListDataSetIterator(ds, 48), epochs=25)
    s1 = net.score(ds)
    assert s1 < s0 * 0.8
    # params must be identical across (collapsed) replicas — single copy now
    assert net.params().ndim == 1


def test_fsdp_sharded_params_train():
    """fsdp axis shards params; training still converges and outputs match
    replicated math."""
    ds = _data()
    net = _net()
    mesh = make_mesh(MeshConfig(data=2, fsdp=4))
    pw = ParallelWrapper(net, mesh)
    s0 = net.score(ds)
    pw.fit(ListDataSetIterator(ds, 48), epochs=15)
    assert net.score(ds) < s0


def test_parallel_inference_batching():
    ds = _data()
    net = _net()
    net.fit(ListDataSetIterator(ds, 50), epochs=5)
    pi = ParallelInference(net, batch_limit=16)
    try:
        expected = np.asarray(net.output(ds.features[:10]))
        results = {}

        def call(i):
            results[i] = pi.output(ds.features[i:i + 1])

        threads = [threading.Thread(target=call, args=(i,)) for i in range(10)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i in range(10):
            np.testing.assert_allclose(results[i][0], expected[i], rtol=1e-4)
    finally:
        pi.shutdown()


def test_tensor_parallel_model_axis():
    """dp×tp mesh: last weight axis sharded over 'model' (Megatron
    column-parallel via GSPMD) — trains and matches dp-only numerics."""
    from deeplearning4j_tpu.datasets.normalizers import NormalizerStandardize
    ds = load_iris()
    n = NormalizerStandardize(); n.fit(ds); ds = n.transform(ds).shuffle(seed=0)
    ds = ds.get_range(0, 144)  # batches of 24 divide both 4- and 8-way

    def conf():
        return (NeuralNetConfiguration.builder()
                .seed(42).learning_rate(0.1).updater("adam")
                .list()
                .layer(DenseLayer(n_in=4, n_out=16, activation="relu"))
                .layer(OutputLayer(n_in=16, n_out=3, activation="softmax",
                                   loss="mcxent"))
                .build())

    import numpy as np
    tp_net = MultiLayerNetwork(conf()).init()
    tp_mesh = make_mesh(MeshConfig(data=4, model=2))
    ParallelWrapper(tp_net, tp_mesh).fit(
        ListDataSetIterator(ds, 24), epochs=3)

    dp_net = MultiLayerNetwork(conf()).init()
    dp_mesh = make_mesh(MeshConfig(data=8))
    ParallelWrapper(dp_net, dp_mesh).fit(
        ListDataSetIterator(ds, 24), epochs=3)

    np.testing.assert_allclose(
        np.asarray(tp_net.params()), np.asarray(dp_net.params()),
        rtol=1e-4, atol=1e-5)
    assert np.isfinite(float(tp_net.score()))


def test_allreduce_fused_steps_matches_per_step():
    """ParallelWrapper(fused_steps=K) — K sharded batches per scan
    launch — must take exactly the steps the per-step wrapper takes."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    rng = np.random.default_rng(9)
    batches = []
    for _ in range(7):
        x = rng.normal(size=(16, 4)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)]
        batches.append(DataSet(x, y))
    a = _net(updater="adam", seed=3)
    b = _net(updater="adam", seed=3)
    b.init()
    a.init()
    b.net_params = jax.tree_util.tree_map(jnp.array, a.net_params)
    mesh = make_mesh(MeshConfig(data=8))
    ParallelWrapper(a, mesh).fit(ListDataSetIterator(list(batches)))
    ParallelWrapper(b, mesh, fused_steps=3).fit(
        ListDataSetIterator(list(batches)))
    assert a.iteration == b.iteration == 7
    for pa, pb in zip(a.net_params, b.net_params):
        for k in pa:
            np.testing.assert_allclose(np.asarray(pa[k]), np.asarray(pb[k]),
                                       rtol=2e-5, atol=2e-6)


def test_cg_rnn_features_mask_falls_back_to_trim():
    """CG batches wrap masks in LISTS, so the features-mask-without-
    labels-mask guard must inspect entries, not containers (round-5
    high review): a ragged CG RNN batch with a features mask must trim
    + warn, never synthesize a mask that overrides the propagated one."""
    import warnings
    from deeplearning4j_tpu.nn.conf.graph_conf import GraphBuilder
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import GravesLSTM, RnnOutputLayer
    from deeplearning4j_tpu.nn.conf.network import GlobalConf
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    g = GlobalConf(seed=1, learning_rate=0.1, updater="sgd")
    conf = (GraphBuilder(g)
            .add_inputs("in")
            .add_layer("lstm", GravesLSTM(n_in=3, n_out=5), "in")
            .add_layer("out", RnnOutputLayer(n_out=2, activation="softmax",
                                             loss="mcxent"), "lstm")
            .set_outputs("out")
            .set_input_types(InputType.recurrent(3, 6))
            .build())
    net = ComputationGraph(conf).init()
    rng = np.random.default_rng(2)
    N, T = 12, 6                       # 12 % 8 = 4 → ragged
    x = rng.normal(size=(N, T, 3)).astype(np.float32)
    fm = np.ones((N, T), np.float32)
    fm[:, 4:] = 0.0
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, (N, T))]
    ds = DataSet(x, y, features_mask=fm)
    pw = ParallelWrapper(net, make_mesh())
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        pw.fit(ListDataSetIterator(ds, N), epochs=1)
    assert [w for w in rec if "dropping" in str(w.message)], \
        "guard must fire (trim+warn), not silently pad"


def test_moe_net_falls_back_to_trim():
    """MixtureOfExpertsLayer's batch-coupled aux loss makes exact
    padding impossible; _pad_supported must detect the real class name
    (round-5 high review: the old 'MoE' substring never matched)."""
    from deeplearning4j_tpu.nn.conf.layers import MixtureOfExpertsLayer
    conf = (NeuralNetConfiguration.builder()
            .seed(1).learning_rate(0.05).updater("sgd")
            .list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
            .layer(MixtureOfExpertsLayer(n_out=8, n_experts=2))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    pw = ParallelWrapper(net, make_mesh())
    assert not pw._pad_supported()


# --- the all-reduce loop under the engines' phases (PR 33) --------------------
def _phase_counts():
    from deeplearning4j_tpu import monitor
    fam = monitor.get_registry().snapshot().get("dl4j_phase_seconds", {})
    return {s["labels"]["phase"]: int(s["count"])
            for s in fam.get("samples", [])
            if s["labels"].get("span") == "fit/step"}


@pytest.mark.parametrize("graph", [False, True], ids=["list", "graph"])
def test_allreduce_fit_runs_under_the_fit_step_phases(graph):
    """ParallelWrapper.fit tiles its loop with the phases the engines'
    own loops have, waits for every step and keeps their books: a host
    batch is taken (``data_wait``), normalized to the data degree
    (``bucket``) and scattered over the mesh (``shard_h2d``), then
    dispatched as the engines dispatch."""
    ds = _data()
    if graph:
        from deeplearning4j_tpu.nn.conf.graph_conf import GraphBuilder
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.conf.network import GlobalConf
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        b = (GraphBuilder(GlobalConf(seed=1, learning_rate=0.05,
                                     updater="adam"))
             .add_inputs("in")
             .add_layer("d", DenseLayer(n_out=16, activation="relu"), "in")
             .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                           loss="mcxent"), "d"))
        net = ComputationGraph(b.set_outputs("out").set_input_types(
            InputType.feed_forward(4)).build()).init()
    else:
        net = _net()
    seen = []

    class Seen:
        def iteration_done(self, model, iteration):
            # the step is over when its listeners hear of it
            seen.append((iteration, float(model._score)))
    net.set_listeners(Seen())
    before = _phase_counts()
    ParallelWrapper(net, make_mesh()).fit(ListDataSetIterator(ds, 48),
                                          epochs=2)
    after = _phase_counts()
    steps = 2 * 4                       # 150 rows in batches of 48, twice
    assert [i for i, _ in seen] == list(range(1, steps + 1))
    assert net.iteration == steps and net.last_batch_size == 150 - 3 * 48
    moved = {p: after[p] - before.get(p, 0) for p in after}
    for phase in ("data_wait", "bucket", "shard_h2d", "dispatch_prep",
                  "jit_call", "block_until_ready", "score_fetch",
                  "bookkeeping", "publish", "listeners"):
        assert moved[phase] == steps, (phase, moved)
    assert moved["has_next"] == steps + 2 and moved["epoch"] == 2
    assert net.compile_telemetry.retraces <= 2      # 48 rows, and the 6 padded to 8


def test_allreduce_stream_goes_on_past_a_batch_that_is_dropped(monkeypatch):
    """A batch whose rows would all be dropped runs no step; the stream
    does not end there."""
    from deeplearning4j_tpu.parallel import fsdp
    ds = _data()
    net = _net()
    real, calls = fsdp.normalize_batch, []

    def drop_the_second(model, batch, *a, **k):
        calls.append(batch.num_examples())
        return None if len(calls) == 2 else real(model, batch, *a, **k)
    monkeypatch.setattr(fsdp, "normalize_batch", drop_the_second)
    ParallelWrapper(net, make_mesh()).fit(ListDataSetIterator(ds, 48))
    assert calls == [48, 48, 48, 6] and net.iteration == 3
