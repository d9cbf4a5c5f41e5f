"""Shape-bucketing compile cache + retrace telemetry (ops/bucketing.py).

The contract under test: with ``conf.shape_bucketing(True)`` a ragged
minibatch stream (mixed batch sizes, mixed RNN time lengths, with and
without real masks) trains/scores/outputs numerically identically to
the unbucketed run — padded rows/timesteps are mask-excluded and
outputs un-padded — while the retrace count (CompileTelemetry) is
bounded by the number of buckets hit, not the number of distinct batch
shapes.
"""

import os

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.datasets.iterators import (
    AsyncDataSetIterator, ListDataSetIterator, ListMultiDataSetIterator)
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.graph_conf import GraphBuilder
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.network import (
    GlobalConf, MultiLayerConfiguration, NeuralNetConfiguration)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.listeners import CompileTelemetryListener
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops import bucketing


# ---------------------------------------------------------------------------
# Bucket ladder + primitives
# ---------------------------------------------------------------------------
def test_bucket_size_pow2_default():
    assert [bucketing.bucket_size(n) for n in (1, 2, 3, 5, 8, 9, 100)] == \
        [1, 2, 4, 8, 8, 16, 128]


def test_bucket_size_configured_ladder():
    assert bucketing.bucket_size(5, [4, 16, 64]) == 16
    assert bucketing.bucket_size(16, [4, 16, 64]) == 16
    # past the top rung: fall back to the pow2 ladder (can't pad down)
    assert bucketing.bucket_size(100, [4, 16, 64]) == 128


def test_scaled_mask_mean_identity():
    # mean over the padded batch with the scaled mask == unpadded mean
    rng = np.random.default_rng(0)
    per_ex = rng.normal(size=7).astype(np.float32)
    m = bucketing.scaled_mask(None, np.zeros((7, 3)), 7, 8)[:, 0]
    padded = np.concatenate([per_ex, np.zeros(1, np.float32)])
    np.testing.assert_allclose((padded * m).mean(), per_ex.mean(),
                               rtol=1e-6)


def test_bucket_train_dataset_idempotent():
    g = GlobalConf()
    rng = np.random.default_rng(1)
    ds = DataSet(rng.normal(size=(5, 4)).astype(np.float32),
                 np.eye(3, dtype=np.float32)[rng.integers(0, 3, 5)])
    once, b1 = bucketing.bucket_train_dataset(ds, g)
    twice, b2 = bucketing.bucket_train_dataset(once, g)
    assert b1 == b2 == (8, None)
    assert twice is once  # fast path: already bucket-shaped, no host copy
    assert once.features.shape == (8, 4)
    assert once.labels_mask is not None


# ---------------------------------------------------------------------------
# Network factories
# ---------------------------------------------------------------------------
def dense_net(bucketed, seed=7, **conf_kw):
    b = (NeuralNetConfiguration.builder().seed(seed).learning_rate(0.05)
         .updater("sgd"))
    if bucketed:
        b.shape_bucketing(True, **conf_kw)
    conf = (b.list()
            .layer(L.DenseLayer(n_in=8, n_out=16, activation="tanh"))
            .layer(L.OutputLayer(n_in=16, n_out=3, activation="softmax",
                                 loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def rnn_net(bucketed, seed=3, bidirectional=False):
    b = (NeuralNetConfiguration.builder().seed(seed).learning_rate(0.02)
         .updater("adam"))
    if bucketed:
        b.shape_bucketing(True)
    lstm = (L.GravesBidirectionalLSTM if bidirectional else L.GravesLSTM)
    conf = (b.list()
            .layer(lstm(n_in=5, n_out=8, activation="tanh"))
            .layer(L.RnnOutputLayer(n_out=5, activation="softmax",
                                    loss="mcxent"))
            .set_input_type(InputType.recurrent(5))
            .build())
    return MultiLayerNetwork(conf).init()


def ragged_dense_batches(rng, sizes):
    return [DataSet(rng.normal(size=(s, 8)).astype(np.float32),
                    np.eye(3, dtype=np.float32)[rng.integers(0, 3, s)])
            for s in sizes]


def rnn_batch(rng, n, t, masked):
    x = rng.normal(size=(n, t, 5)).astype(np.float32)
    y = np.eye(5, dtype=np.float32)[rng.integers(0, 5, (n, t))]
    fm = None
    if masked:
        fm = np.ones((n, t), np.float32)
        for i in range(n):
            fm[i, rng.integers(1, t + 1):] = 0.0
    return DataSet(x, y, fm, None)


# ---------------------------------------------------------------------------
# Parity: ragged streams train/score/output identically to unbucketed
# ---------------------------------------------------------------------------
def test_ragged_dense_fit_parity_and_retrace_bound():
    rng = np.random.default_rng(0)
    batches = ragged_dense_batches(rng, [7, 5, 8, 3, 12, 6, 7, 9])
    raw, bucketed = dense_net(False), dense_net(True)
    raw.fit(ListDataSetIterator(list(batches)))
    bucketed.fit(ListDataSetIterator(list(batches)))
    np.testing.assert_allclose(np.asarray(raw.params()),
                               np.asarray(bucketed.params()),
                               rtol=1e-6, atol=1e-7)
    snap = bucketed.compile_telemetry.snapshot()
    buckets_hit = {k for k in snap["bucket_hits"]
                   if k.startswith("train_step:")}
    # retrace count bounded by buckets hit, NOT by distinct batch shapes
    assert snap["by_kind"]["train_step"] <= len(buckets_hit)
    assert raw.compile_telemetry.retraces > len(buckets_hit)
    # loss parity on a fresh ragged batch
    ds = ragged_dense_batches(rng, [5])[0]
    assert abs(raw.score(ds) - bucketed.score(ds)) < 1e-5


def test_ragged_rnn_fit_parity_mixed_time_and_masks():
    rng = np.random.default_rng(1)
    batches = [rnn_batch(rng, 6, 9, False), rnn_batch(rng, 3, 13, True),
               rnn_batch(rng, 8, 9, True), rnn_batch(rng, 5, 5, False)]
    raw, bucketed = rnn_net(False), rnn_net(True)
    raw.fit(ListDataSetIterator(list(batches)))
    bucketed.fit(ListDataSetIterator(list(batches)))
    # atol from the observed f32 reduction-order noise: padding a batch
    # to its bucket changes XLA:CPU's summation order, and after four
    # f32 steps jax 0.9.0 leaves 1 of 517 elements 1.7e-6 apart (a
    # near-zero weight, so rtol does not cover it).  3x that, still far
    # under anything a bucketing bug would produce.
    np.testing.assert_allclose(np.asarray(raw.params()),
                               np.asarray(bucketed.params()),
                               rtol=1e-5, atol=5e-6)
    snap = bucketed.compile_telemetry.snapshot()
    assert snap["by_kind"]["train_step"] <= len(snap["bucket_hits"])
    # score + per-example parity on masked AND unmasked ragged batches
    for ds in (batches[1], batches[3]):
        assert abs(raw.score(ds) - bucketed.score(ds)) < 1e-5
        np.testing.assert_allclose(raw.score_examples(ds),
                                   bucketed.score_examples(ds),
                                   rtol=1e-5, atol=1e-6)


def test_output_unpadded_and_exact():
    rng = np.random.default_rng(2)
    raw, bucketed = rnn_net(False, seed=5), rnn_net(True, seed=5)
    ds = rnn_batch(rng, 3, 7, True)
    out_r = np.asarray(raw.output(ds.features, mask=ds.features_mask))
    out_b = np.asarray(bucketed.output(ds.features, mask=ds.features_mask))
    assert out_b.shape == out_r.shape == (3, 7, 5)  # un-padded
    np.testing.assert_allclose(out_r, out_b, rtol=1e-6, atol=1e-6)


def test_bidirectional_output_exact_under_time_padding():
    # the backward scan must not see the padded timesteps: masked steps
    # are identity carries, so real outputs are exact
    rng = np.random.default_rng(3)
    raw = rnn_net(False, seed=5, bidirectional=True)
    bucketed = rnn_net(True, seed=5, bidirectional=True)
    ds = rnn_batch(rng, 3, 7, True)
    out_r = np.asarray(raw.output(ds.features, mask=ds.features_mask))
    out_b = np.asarray(bucketed.output(ds.features, mask=ds.features_mask))
    np.testing.assert_allclose(out_r, out_b, rtol=1e-6, atol=1e-6)


def test_fused_ragged_group_stays_fused():
    """Satellite: ragged groups under fit(fused_steps=K) bucket to
    uniform shapes and stay on the scan path instead of unconditionally
    falling back per-step — and still match per-step training."""
    rng = np.random.default_rng(4)
    # bucket to a COMMON bucket (8) so the fused group really fuses
    batches = ragged_dense_batches(rng, [7, 5, 8, 6, 7, 8])
    raw, bucketed = dense_net(False), dense_net(True)
    raw.fit(ListDataSetIterator(list(batches)))  # per-step reference
    bucketed.fit(ListDataSetIterator(list(batches)), fused_steps=3)
    np.testing.assert_allclose(np.asarray(raw.params()),
                               np.asarray(bucketed.params()),
                               rtol=1e-6, atol=1e-7)
    kinds = bucketed.compile_telemetry.snapshot()["by_kind"]
    assert any(k.startswith("fused_step_k") for k in kinds), kinds


# ---------------------------------------------------------------------------
# ComputationGraph paths
# ---------------------------------------------------------------------------
def cg_net(bucketed, seed=4):
    g = GlobalConf(seed=seed, learning_rate=0.05)
    g.shape_bucketing = bucketed
    gb = (GraphBuilder(g)
          .add_inputs("in")
          .add_layer("d", L.DenseLayer(n_in=8, n_out=16, activation="tanh"),
                     "in")
          .add_layer("out", L.OutputLayer(n_in=16, n_out=3,
                                          activation="softmax",
                                          loss="mcxent"), "d")
          .set_outputs("out"))
    return ComputationGraph(gb.build()).init()


def test_cg_ragged_parity_fit_output_score():
    rng = np.random.default_rng(5)
    batches = [MultiDataSet([d.features], [d.labels])
               for d in ragged_dense_batches(rng, [7, 5, 8, 3, 6])]
    raw, bucketed = cg_net(False), cg_net(True)
    raw.fit(ListMultiDataSetIterator(list(batches)))
    bucketed.fit(ListMultiDataSetIterator(list(batches)))
    np.testing.assert_allclose(np.asarray(raw.params()),
                               np.asarray(bucketed.params()),
                               rtol=1e-6, atol=1e-7)
    snap = bucketed.compile_telemetry.snapshot()
    assert snap["by_kind"]["train_step"] <= len(snap["bucket_hits"])
    x = batches[0].features[0]
    np.testing.assert_allclose(np.asarray(raw.output(x)[0]),
                               np.asarray(bucketed.output(x)[0]),
                               rtol=1e-6, atol=1e-7)
    assert abs(raw.score(batches[0]) - bucketed.score(batches[0])) < 1e-5
    np.testing.assert_allclose(raw.score_examples(batches[0]),
                               bucketed.score_examples(batches[0]),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# ParallelWrapper + AsyncDataSetIterator integration
# ---------------------------------------------------------------------------
def test_parallel_wrapper_bucketed_parity():
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
    rng = np.random.default_rng(6)
    batches = ragged_dense_batches(rng, [13, 9, 21, 5])
    raw = dense_net(False, seed=11)
    raw.fit(ListDataSetIterator(list(batches)))
    bucketed = dense_net(True, seed=11)
    pw = ParallelWrapper(bucketed)
    pw.fit(ListDataSetIterator(list(batches)))
    np.testing.assert_allclose(np.asarray(raw.params()),
                               np.asarray(bucketed.params()),
                               rtol=2e-4, atol=2e-6)
    snap = bucketed.compile_telemetry.snapshot()
    # buckets are lifted to data-degree multiples; still bounded
    assert snap["by_kind"]["sharded_step"] <= len(snap["bucket_hits"])


def test_async_iterator_buckets_before_device_put():
    import jax
    rng = np.random.default_rng(7)
    batches = ragged_dense_batches(rng, [7, 5, 8, 3])
    g = GlobalConf()
    it = AsyncDataSetIterator(
        ListDataSetIterator(list(batches)), device_put=True,
        transform=lambda d: bucketing.bucket_train_dataset(d, g)[0])
    seen = []
    while it.has_next():
        d = it.next()
        assert isinstance(d.features, jax.Array)  # H2D already done
        assert d.labels_mask is not None          # mask synthesized
        seen.append(d.features.shape[0])
    assert seen == [8, 8, 8, 4]  # bucket-shaped before the engine


# ---------------------------------------------------------------------------
# Telemetry surfaces + fallbacks + conf plumbing
# ---------------------------------------------------------------------------
def test_compile_telemetry_listener_history():
    rng = np.random.default_rng(8)
    net = dense_net(True)
    lst = CompileTelemetryListener()
    net.set_listeners(lst)
    net.fit(ListDataSetIterator(ragged_dense_batches(rng, [7, 5, 8])))
    assert lst.history, "listener collected no snapshots"
    assert lst.snapshot()["retraces"] >= 1
    assert "bucket_hits" in lst.snapshot()


def test_unsupported_conf_falls_back_unbucketed():
    # mini_batch=False (sum reduction): the target/n rescale would be
    # wrong, so bucketing must silently stand down, not mis-train
    rng = np.random.default_rng(9)
    b = (NeuralNetConfiguration.builder().seed(7).learning_rate(0.05)
         .mini_batch(False).shape_bucketing(True))
    conf = (b.list()
            .layer(L.DenseLayer(n_in=8, n_out=16, activation="tanh"))
            .layer(L.OutputLayer(n_in=16, n_out=3, activation="softmax",
                                 loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    ref = dense_net(False)
    ref.conf.global_conf.mini_batch = False
    batches = ragged_dense_batches(rng, [7, 5])
    net.fit(ListDataSetIterator(list(batches)))
    ref.fit(ListDataSetIterator(list(batches)))
    np.testing.assert_allclose(np.asarray(ref.params()),
                               np.asarray(net.params()), rtol=1e-6)
    assert not net.compile_telemetry.snapshot()["bucket_hits"]


def test_globalconf_bucketing_serde_roundtrip():
    b = (NeuralNetConfiguration.builder()
         .shape_bucketing(True, batch_sizes=[8, 32], time_sizes=[16]))
    conf = (b.list()
            .layer(L.DenseLayer(n_in=4, n_out=4))
            .layer(L.OutputLayer(n_in=4, n_out=2))
            .build())
    rt = MultiLayerConfiguration.from_json(conf.to_json())
    assert rt.global_conf.shape_bucketing is True
    assert rt.global_conf.bucket_batch_sizes == [8, 32]
    assert rt.global_conf.bucket_time_sizes == [16]
    # old checkpoints (no bucketing keys) still load, defaulting off
    d = conf.to_dict()
    for k in ("shape_bucketing", "bucket_batch_sizes", "bucket_time_sizes"):
        d["global"].pop(k)
    assert MultiLayerConfiguration.from_dict(d) \
        .global_conf.shape_bucketing is False


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_placed_from_outside(tmp_path, env_set):
    """A fresh process that imports the package: with
    JAX_COMPILATION_CACHE_DIR set JAX keeps that directory and the code
    sets no other; unset, the cache goes to the one fixed path inside
    the checkout."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(root, ".jax_cache")
    if env_set:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla-cache")
    p = subprocess.run(
        [sys.executable, "-c",
         "import deeplearning4j_tpu, jax; "
         "print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, timeout=120, env=env, cwd=root)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip().splitlines()[-1] == want
