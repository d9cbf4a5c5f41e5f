"""Parallel input pipeline (datasets/iterators.AsyncDataSetIterator):
deterministic ordering, sync-vs-async parity, lifecycle/thread hygiene,
staging bounds, vectorized record ETL and streaming normalizer fit."""

import gc
import json
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.datasets.iterators import (
    AsyncDataSetIterator, AsyncMultiDataSetIterator, DataSetIterator,
    ListDataSetIterator, ListMultiDataSetIterator)
from deeplearning4j_tpu.datasets.normalizers import (
    NormalizerMinMaxScaler, NormalizerStandardize)
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.network import (
    MultiLayerConfiguration, NeuralNetConfiguration)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork


def _batches(n=13, rows=6, cols=4, seed=0, masks=False):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        f = rng.normal(size=(rows, cols)).astype(np.float32)
        f[0, 0] = i  # batch identity marker
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, rows)]
        fm = rng.integers(0, 2, (rows,)).astype(np.float32) if masks else None
        out.append(DataSet(f, y, fm, None))
    return out


def _drain(it):
    out = []
    while it.has_next():
        out.append(it.next())
    return out


def _wait_threads(base, timeout=5.0):
    deadline = time.time() + timeout
    while threading.active_count() > base and time.time() < deadline:
        time.sleep(0.02)
    return threading.active_count()


# ---------------------------------------------------------------------------
# Ordering + parity
# ---------------------------------------------------------------------------
def test_async_n_order_byte_identical_to_sync():
    batches = _batches(masks=True)
    sync = _drain(ListDataSetIterator(list(batches)))
    for workers in (1, 3):
        it = AsyncDataSetIterator(ListDataSetIterator(list(batches)),
                                  workers=workers, queue_size=3,
                                  staging_depth=2)
        got = _drain(it)
        it.close()
        assert len(got) == len(sync)
        for a, b in zip(got, sync):
            assert a.features.tobytes() == b.features.tobytes()
            assert a.labels.tobytes() == b.labels.tobytes()
            assert (a.features_mask is None) == (b.features_mask is None)
            if a.features_mask is not None:
                assert a.features_mask.tobytes() == b.features_mask.tobytes()


def test_two_epochs_reset_keeps_order():
    batches = _batches()
    it = AsyncDataSetIterator(ListDataSetIterator(list(batches)), workers=2)
    first = _drain(it)
    it.reset()
    second = _drain(it)
    it.close()
    assert [d.features[0, 0] for d in first] == \
        [d.features[0, 0] for d in second] == list(range(len(batches)))


def _net(workers, seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater("sgd").learning_rate(0.1)
            .input_pipeline(workers=workers, prefetch=3, staging_depth=2)
            .list()
            .layer(L.DenseLayer(n_in=4, n_out=8, activation="relu"))
            .layer(L.OutputLayer(n_in=8, n_out=3, activation="softmax",
                                 loss="negativeloglikelihood"))
            .build())
    return MultiLayerNetwork(conf).init()


def test_fit_score_parity_sync_vs_async_n():
    batches = _batches(n=6)
    scores = {}
    for w in (0, 1, 3):
        net = _net(w)
        net.fit(ListDataSetIterator(list(batches)), epochs=2)
        scores[w] = float(net.score())
    assert scores[0] == scores[1] == scores[3], scores


def test_cg_fit_parity_and_dataset_conversion():
    from deeplearning4j_tpu.nn.conf.graph_conf import GraphBuilder
    from deeplearning4j_tpu.nn.conf.network import GlobalConf
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    batches = _batches(n=4)

    def make(workers):
        g = GlobalConf(seed=7, learning_rate=0.05, updater="adam",
                       pipeline_workers=workers, pipeline_prefetch=3)
        conf = (GraphBuilder(g).add_inputs("in")
                .add_layer("d", L.DenseLayer(n_in=4, n_out=8,
                                             activation="relu"), "in")
                .add_layer("out", L.OutputLayer(n_in=8, n_out=3,
                                                activation="softmax",
                                                loss="mcxent"), "d")
                .set_outputs("out").build())
        return ComputationGraph(conf).init()

    scores = {}
    for w in (0, 2):
        net = make(w)
        net.fit(ListDataSetIterator(list(batches)), epochs=2)
        scores[w] = float(np.asarray(net._score))
    assert scores[0] == scores[2], scores

    mds = [MultiDataSet([d.features], [d.labels], [None], [None])
           for d in batches]
    for w in (0, 2):
        net = make(w)
        net.fit(ListMultiDataSetIterator(list(mds)), epochs=2)
        scores[f"m{w}"] = float(np.asarray(net._score))
    assert scores["m0"] == scores["m2"] == scores[0]


# ---------------------------------------------------------------------------
# Failure + lifecycle
# ---------------------------------------------------------------------------
def test_worker_exception_surfaces_at_position():
    batches = _batches(n=8)

    def boom(d):
        if int(d.features[0, 0]) == 3:
            raise RuntimeError("etl boom @3")
        return d

    it = AsyncDataSetIterator(ListDataSetIterator(list(batches)),
                              workers=3, transform=boom)
    got = []
    with pytest.raises(RuntimeError, match="etl boom"):
        while it.has_next():
            got.append(it.next())
    it.close()
    # batches BEFORE the failed position were delivered, in order
    assert [int(d.features[0, 0]) for d in got] == [0, 1, 2]


def test_feeder_exception_surfaces():
    class ExplodingIterator(DataSetIterator):
        def __init__(self):
            self._i = 0

        def has_next(self):
            return True

        def next(self):
            if self._i == 2:
                raise ValueError("reader died")
            self._i += 1
            return _batches(n=1)[0]

        def reset(self):
            self._i = 0

    it = AsyncDataSetIterator(ExplodingIterator(), workers=2)
    with pytest.raises(ValueError, match="reader died"):
        _drain(it)
    it.close()


def test_close_is_idempotent_and_unblocks_producer():
    base = threading.active_count()

    class InfiniteIterator(DataSetIterator):
        def has_next(self):
            return True

        def next(self):
            return _batches(n=1)[0]

        def reset(self):
            pass

    it = AsyncDataSetIterator(InfiniteIterator(), workers=2, queue_size=2,
                              staging_depth=1)
    assert it.has_next()
    it.next()
    # feeder is now blocked on a full task queue; close() must still
    # unwind everything promptly
    it.close()
    it.close()
    assert _wait_threads(base) <= base
    # reset after close is a no-op (not started) and must not raise
    it.reset()


def test_reset_mid_stream_no_thread_leak():
    base = threading.active_count()
    batches = _batches(n=10)
    it = AsyncDataSetIterator(ListDataSetIterator(list(batches)), workers=3)
    it.next()
    it.reset()
    assert len(_drain(it)) == 10  # full epoch after mid-stream reset
    it.close()
    assert _wait_threads(base) <= base


def test_gc_reclaims_pipeline_threads():
    base = threading.active_count()
    it = AsyncDataSetIterator(ListDataSetIterator(_batches(n=10)), workers=3)
    it.next()
    del it
    gc.collect()
    assert _wait_threads(base) <= base


def test_staging_depth_bounds_resident_batches():
    it = AsyncDataSetIterator(ListDataSetIterator(_batches(n=16)),
                              workers=4, queue_size=8, staging_depth=2)
    while it.has_next():
        it.next()
        time.sleep(0.003)  # slow consumer: workers run ahead to the cap
    hw = it.staging_high_water
    it.close()
    assert 1 <= hw <= 2, hw


def test_pipeline_metrics_populated():
    from deeplearning4j_tpu import monitor
    reg = monitor.get_registry()
    before = reg.counter("dl4j_pipeline_batches_total",
                         labels=("stage",)).labels(stage="consumed").value
    it = AsyncDataSetIterator(ListDataSetIterator(_batches(n=5)), workers=2)
    _drain(it)
    it.close()
    after = reg.counter("dl4j_pipeline_batches_total",
                        labels=("stage",)).labels(stage="consumed").value
    assert after - before == 5
    assert reg.counter("dl4j_pipeline_staged_bytes_total").value > 0
    assert reg.gauge("dl4j_pipeline_workers").value == 2


# ---------------------------------------------------------------------------
# Vectorized record ETL
# ---------------------------------------------------------------------------
def test_record_iterator_vectorized_matches_per_row():
    from deeplearning4j_tpu.records.iterators import (
        RecordReaderDataSetIterator, _record_to_arrays)
    from deeplearning4j_tpu.records.readers import CollectionRecordReader

    rng = np.random.default_rng(4)
    recs = [[str(rng.normal()), rng.normal(), int(rng.integers(0, 4))]
            for _ in range(23)]
    it = RecordReaderDataSetIterator(CollectionRecordReader(recs), 8,
                                     label_index=-1, num_possible_labels=4)
    out = _drain(it)
    assert [d.num_examples() for d in out] == [8, 8, 7]
    for ds, chunk in zip(out, (recs[:8], recs[8:16], recs[16:])):
        fs, ys = zip(*(_record_to_arrays(list(r), -1, 4, False)
                       for r in chunk))
        np.testing.assert_allclose(ds.features, np.stack(fs), rtol=1e-6)
        assert np.array_equal(ds.labels, np.stack(ys))

    reg = RecordReaderDataSetIterator(CollectionRecordReader(recs), 8,
                                      label_index=0, regression=True)
    ds = reg.next()
    assert ds.labels.shape == (8, 1)
    np.testing.assert_allclose(ds.labels[:, 0],
                               [float(r[0]) for r in recs[:8]], rtol=1e-6)


def test_record_iterator_raw_collate_split_through_async():
    from deeplearning4j_tpu.records.iterators import (
        RecordReaderDataSetIterator)
    from deeplearning4j_tpu.records.readers import CollectionRecordReader

    recs = [[float(i), float(i * 2), i % 3] for i in range(40)]

    def make():
        return RecordReaderDataSetIterator(
            CollectionRecordReader(recs), 8, label_index=-1,
            num_possible_labels=3)
    sync = _drain(make())
    it = AsyncDataSetIterator(make(), workers=3)
    got = _drain(it)
    it.close()
    assert len(got) == len(sync) == 5
    for a, b in zip(got, sync):
        assert a.features.tobytes() == b.features.tobytes()
        assert a.labels.tobytes() == b.labels.tobytes()


def test_sequence_iterator_vectorized_one_hot_and_masks():
    from deeplearning4j_tpu.records.iterators import (
        SequenceRecordReaderDataSetIterator)
    from deeplearning4j_tpu.records.readers import (
        CollectionSequenceRecordReader)

    rng = np.random.default_rng(5)
    seqs = [[[float(rng.normal()), float(rng.normal()),
              int(rng.integers(0, 3))] for _ in range(t)]
            for t in (5, 3, 7, 7)]
    it = SequenceRecordReaderDataSetIterator(
        CollectionSequenceRecordReader(seqs), 4, 3, label_index=-1)
    ds = it.next()
    assert ds.features.shape == (4, 7, 2)
    assert ds.labels.shape == (4, 7, 3)
    assert ds.features_mask is not None
    np.testing.assert_array_equal(ds.features_mask.sum(axis=1), [5, 3, 7, 7])
    for i, seq in enumerate(seqs):
        for t, row in enumerate(seq):
            assert ds.labels[i, t, int(row[2])] == 1.0
            np.testing.assert_allclose(ds.features[i, t], row[:2], rtol=1e-6)


def test_multi_record_iterator_vectorized():
    from deeplearning4j_tpu.records.iterators import (
        RecordReaderMultiDataSetIterator)
    from deeplearning4j_tpu.records.readers import CollectionRecordReader

    recs = [[float(i), float(i + 1), i % 4, float(i * 3)] for i in range(10)]
    it = (RecordReaderMultiDataSetIterator.Builder(4)
          .add_reader("r", CollectionRecordReader(recs))
          .add_input("r", 0, 2)
          .add_output_one_hot("r", 2, 4)
          .add_output("r", 3, 4)
          .build())
    m = it.next()
    assert m.features[0].shape == (4, 2)
    np.testing.assert_allclose(m.features[0][:, 1], [1, 2, 3, 4])
    assert m.labels[0].shape == (4, 4)
    assert all(m.labels[0][i, i % 4] == 1.0 for i in range(4))
    np.testing.assert_allclose(m.labels[1][:, 0], [0, 3, 6, 9])


# ---------------------------------------------------------------------------
# Streaming normalizer fit
# ---------------------------------------------------------------------------
def test_normalizer_standardize_iterator_single_pass_parity():
    rng = np.random.default_rng(6)
    X = (rng.normal(size=(500, 7)) * rng.uniform(0.1, 9, 7)
         + rng.normal(size=7)).astype(np.float32)
    full = DataSet(X, np.zeros((500, 1), np.float32))
    a = NormalizerStandardize().fit(full)
    b = NormalizerStandardize().fit(
        ListDataSetIterator(list(full.batch_by(64))))
    np.testing.assert_allclose(a.mean, b.mean, atol=1e-5)
    np.testing.assert_allclose(a.std, b.std, rtol=1e-5)


def test_normalizer_minmax_iterator_parity():
    rng = np.random.default_rng(7)
    X = rng.normal(size=(300, 5)).astype(np.float32)
    full = DataSet(X, np.zeros((300, 1), np.float32))
    a = NormalizerMinMaxScaler().fit(full)
    b = NormalizerMinMaxScaler().fit(
        ListDataSetIterator(list(full.batch_by(32))))
    assert np.array_equal(a.min, b.min)
    assert np.array_equal(a.max, b.max)


def test_normalizer_runs_on_pipeline_worker():
    rng = np.random.default_rng(8)
    X = rng.normal(size=(64, 4)).astype(np.float32)
    full = DataSet(X, np.zeros((64, 1), np.float32))
    norm = NormalizerStandardize().fit(full)
    it = AsyncDataSetIterator(ListDataSetIterator(full.batch_by(16)),
                              workers=2, normalizer=norm)
    got = _drain(it)
    it.close()
    expect = norm.transform(full)
    np.testing.assert_allclose(
        np.concatenate([d.features for d in got]), expect.features,
        rtol=1e-6)


def test_normalizer_fit_leaves_iterator_rewound():
    batches = _batches(n=5)
    it = ListDataSetIterator(list(batches))
    NormalizerStandardize().fit(it)
    assert it.has_next()
    assert len(_drain(it)) == len(batches)


def test_unstarted_reset_rewinds_underlying():
    # reset() before the pipeline ever starts must still rewind a
    # partially-consumed underlying iterator (epoch 1 would otherwise
    # silently train 0 batches)
    batches = _batches(n=5)
    inner = ListDataSetIterator(list(batches))
    _drain(inner)  # exhaust, e.g. by a prior Normalizer.fit
    it = AsyncDataSetIterator(inner, workers=2)
    it.reset()
    got = _drain(it)
    it.close()
    assert [int(d.features[0, 0]) for d in got] == list(range(len(batches)))


def test_fit_trains_epoch1_after_normalizer_fit_on_same_iterator():
    batches = _batches(n=4)
    it = ListDataSetIterator(list(batches))
    NormalizerStandardize().fit(it)
    net = _net(workers=2)
    before = float(net.score(batches[0]))
    net.fit(it, epochs=1)
    assert float(net.score(batches[0])) != before


def test_cg_fit_accepts_plain_iterable():
    from deeplearning4j_tpu.nn.conf.graph_conf import GraphBuilder
    from deeplearning4j_tpu.nn.conf.network import GlobalConf
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    class PlainIterable:  # only __iter__/reset, no has_next/next
        def __init__(self, items):
            self._items = items

        def __iter__(self):
            return iter(self._items)

        def reset(self):
            pass

    batches = _batches(n=3)
    mds = [MultiDataSet([d.features], [d.labels], [None], [None])
           for d in batches]
    g = GlobalConf(seed=7, learning_rate=0.05, updater="adam",
                   pipeline_workers=0)
    conf = (GraphBuilder(g).add_inputs("in")
            .add_layer("d", L.DenseLayer(n_in=4, n_out=8,
                                         activation="relu"), "in")
            .add_layer("out", L.OutputLayer(n_in=8, n_out=3,
                                            activation="softmax",
                                            loss="mcxent"), "d")
            .set_outputs("out").build())
    net = ComputationGraph(conf).init()
    net.fit(PlainIterable(mds), epochs=2)
    assert np.isfinite(float(np.asarray(net._score)))


# ---------------------------------------------------------------------------
# Conf plumbing
# ---------------------------------------------------------------------------
def test_conf_pipeline_settings_roundtrip():
    conf = (NeuralNetConfiguration.builder()
            .input_pipeline(workers=3, prefetch=6, staging_depth=2)
            .list()
            .layer(L.DenseLayer(n_in=2, n_out=2))
            .layer(L.OutputLayer(n_in=2, n_out=2, loss="mse"))
            .build())
    rt = MultiLayerConfiguration.from_json(conf.to_json())
    g = rt.global_conf
    assert (g.pipeline_workers, g.pipeline_prefetch,
            g.pipeline_staging_depth) == (3, 6, 2)
    # old serialized configs (no pipeline keys) still load with defaults
    d = json.loads(conf.to_json())
    for k in ("pipeline_workers", "pipeline_prefetch",
              "pipeline_staging_depth"):
        d["global"].pop(k)
    g2 = MultiLayerConfiguration.from_dict(d).global_conf
    assert g2.pipeline_workers == 1 and g2.pipeline_prefetch == 4
