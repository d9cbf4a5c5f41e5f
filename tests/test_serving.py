"""Serving subsystem: model cache (LRU, mtime invalidation), dynamic
micro-batching (concurrent-vs-serial parity, max_wait timeout), bucket
warmup bounding retraces, predict response shaping (empty input,
top_k/argmax_only), stats/invalidate RPCs, and the debug-gated error
traceback."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.serialization import write_model
from deeplearning4j_tpu.server import (
    DeepLearning4jEntryPoint, MicroBatcher, ModelCache, Server)

F, C = 6, 3


def _mlp(seed=3, bucketing=True):
    b = (NeuralNetConfiguration.builder()
         .seed(seed).learning_rate(0.1).updater("adam"))
    if bucketing:
        b.shape_bucketing(True)
    conf = (b.list()
            .layer(L.DenseLayer(n_in=F, n_out=12, activation="relu"))
            .layer(L.OutputLayer(n_in=12, n_out=C, activation="softmax",
                                 loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _write_mlp(path, seed=3, bucketing=True):
    write_model(_mlp(seed, bucketing), str(path))
    return str(path)


def _post(url, obj):
    req = urllib.request.Request(
        url, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


# ---------------------------------------------------------------------------
# Model cache
# ---------------------------------------------------------------------------
def test_model_cache_hit_stale_reload_lru(tmp_path):
    paths = [_write_mlp(tmp_path / f"m{i}.zip", seed=i) for i in range(3)]
    cache = ModelCache(capacity=2)

    m0 = cache.get(paths[0])
    assert cache.get(paths[0]) is m0          # hit returns same instance
    st = cache.stats()
    assert st["hits"] == 1 and st["misses"] == 1

    # touching the file on disk invalidates the key
    time.sleep(0.01)
    _write_mlp(paths[0], seed=9)
    m0b = cache.get(paths[0])
    assert m0b is not m0
    assert cache.stats()["stale_reloads"] == 1

    # LRU eviction at capacity 2: loading m1 then m2 evicts m0
    cache.get(paths[1])
    cache.get(paths[2])
    st = cache.stats()
    assert st["size"] == 2 and st["evictions"] == 1
    assert cache.peek(paths[0]) is None
    assert cache.peek(paths[2]) is not None

    assert cache.invalidate(paths[2]) == 1
    assert cache.invalidate(paths[2]) == 0
    assert cache.invalidate() == 1            # drops the remaining entry


def test_model_cache_warmup_on_load(tmp_path):
    path = _write_mlp(tmp_path / "m.zip")
    cache = ModelCache()
    model = cache.get(path, warmup_dims=(F,), max_batch=8)
    warm = cache.stats()["models"][list(cache.stats()["models"])[0]]["warmup"]
    assert warm["buckets"] == [1, 2, 4, 8]
    # the warmed ladder means ragged predicts cause no new output traces
    tel = model.compile_telemetry
    before = tel.snapshot()["by_kind"]["output"]
    for n in (1, 2, 3, 5, 7, 8):
        model.output(np.zeros((n, F), np.float32))
    assert tel.snapshot()["by_kind"]["output"] == before


# ---------------------------------------------------------------------------
# Bucket warmup hooks
# ---------------------------------------------------------------------------
def test_warmup_ladder_helper():
    from deeplearning4j_tpu.ops.bucketing import pow2_ladder, warmup_ladder
    assert pow2_ladder(32) == [1, 2, 4, 8, 16, 32]
    assert warmup_ladder(None, 5) == [1, 2, 4, 8]
    assert warmup_ladder([16, 4], 16) == [4, 16]
    # max_batch above the configured ladder falls back to the pow2 rung
    assert warmup_ladder([2, 4], 32) == [2, 4, 32]
    # rungs above the one max_batch lands on are dropped
    assert warmup_ladder([8, 64, 128], 32) == [8, 64]


def test_cg_warmup_inference_bounds_retraces():
    from deeplearning4j_tpu.nn.conf.graph_conf import GraphBuilder
    from deeplearning4j_tpu.nn.conf.network import GlobalConf
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    g = GlobalConf(seed=5, learning_rate=0.1)
    g.shape_bucketing = True
    gb = (GraphBuilder(g)
          .add_inputs("in")
          .add_layer("h", L.DenseLayer(n_in=F, n_out=8, activation="relu"),
                     "in")
          .add_layer("out", L.OutputLayer(n_in=8, n_out=C,
                                          activation="softmax",
                                          loss="mcxent"), "h")
          .set_outputs("out"))
    cg = ComputationGraph(gb.build()).init()
    warm = cg.warmup_inference((F,), max_batch=4)
    assert warm["buckets"] == [1, 2, 4]
    before = cg.compile_telemetry.snapshot()["by_kind"]["output"]
    for n in (1, 3, 4):
        cg.output(np.zeros((n, F), np.float32))
    assert cg.compile_telemetry.snapshot()["by_kind"]["output"] == before


# ---------------------------------------------------------------------------
# Micro-batcher
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bucketing", [True, False])
def test_concurrent_batched_predict_matches_serial(tmp_path, bucketing):
    """N client threads hammering predict through the batcher must match
    serial per-request output, bucketed and unbucketed."""
    path = _write_mlp(tmp_path / "m.zip", bucketing=bucketing)
    ep = DeepLearning4jEntryPoint(max_batch=16, max_wait_ms=10.0)
    rng = np.random.default_rng(0)
    reqs = [rng.normal(size=(int(s), F)).astype(np.float32)
            for s in rng.integers(1, 6, 12)]
    results = {}

    def client(i):
        out = ep.predict(path, features=reqs[i])
        results[i] = np.asarray(out["predictions"], np.float32)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    model = ep.model_cache.peek(path)
    assert model is not None
    hist = next(iter(ep.stats()["serving"].values()))["batch_size_hist"]
    for i, r in enumerate(reqs):
        serial = np.asarray(model.output(r))
        np.testing.assert_allclose(results[i], serial, rtol=1e-6, atol=1e-6)
    assert results[0].shape == (len(reqs[0]), C)
    # the point of the batcher: fewer dispatches than requests
    assert sum(hist.values()) <= len(reqs)
    ep.close()


def test_concurrent_predicts_compile_within_the_ladder(tmp_path):
    """The ladder, not the traffic, bounds the output programs: ragged
    requests from concurrent clients coalesce into batches that land on
    the rungs warmed at load, and compile nothing."""
    path = _write_mlp(tmp_path / "m.zip")
    ep = DeepLearning4jEntryPoint(max_batch=16, max_wait_ms=2.0, min_batch=4)
    rng = np.random.default_rng(5)
    rows = [[rng.normal(size=(int(n), F)).astype(np.float32)
             for n in rng.integers(1, 4, 6)] for _ in range(4)]
    ep.predict(path, features=rows[0][0])     # load + warm the ladder
    model = ep.model_cache.peek(path)
    ladder = next(iter(ep.model_cache.stats()["models"].values()))[
        "warmup"]["buckets"]
    warmed = model.compile_telemetry.snapshot()["by_kind"]["output"]
    assert 0 < warmed <= len(ladder)

    def client(rs):
        for r in rs:
            ep.predict(path, features=r, argmax_only=True)

    threads = [threading.Thread(target=client, args=(rs,)) for rs in rows]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
        assert not t.is_alive()
    s = next(iter(ep.stats()["serving"].values()))
    assert s["requests"] == 4 * 6 + 1
    assert model.compile_telemetry.snapshot()["by_kind"]["output"] == warmed
    ep.close()


def test_lone_request_not_stuck_waiting_for_full_batch():
    """max_wait_ms bounds the coalescing window: with min_batch > 1 a
    single request must be dispatched when the window expires, not wait
    for a batch that will never fill."""
    calls = []

    def infer(x):
        calls.append(len(x))
        return x * 2.0

    b = MicroBatcher(infer, max_batch=64, min_batch=32, max_wait_ms=100.0)
    x = np.ones((2, 4), np.float32)
    t0 = time.perf_counter()
    out = b.predict(x, timeout=10.0)
    elapsed = time.perf_counter() - t0
    np.testing.assert_array_equal(out, x * 2.0)
    assert elapsed < 5.0            # returned via the max_wait timeout,
    assert calls and calls[0] < 32  # not a full min_batch
    b.stop()


def test_batcher_groups_mismatched_shapes():
    """A client sending a different row shape must not fail its
    batch-mates — groups dispatch separately."""
    b = MicroBatcher(lambda x: x.sum(axis=tuple(range(1, x.ndim)),
                                     keepdims=True),
                     max_batch=16, min_batch=8, max_wait_ms=50.0)
    f1 = b.submit(np.ones((2, 3), np.float32))
    f2 = b.submit(np.ones((1, 5), np.float32))
    np.testing.assert_allclose(f1.result(10.0), [[3.0], [3.0]])
    np.testing.assert_allclose(f2.result(10.0), [[5.0]])
    b.stop()


def test_batcher_max_batch_bounds_dispatch():
    sizes = []

    def infer(x):
        sizes.append(len(x))
        return x

    b = MicroBatcher(infer, max_batch=4, min_batch=4, max_wait_ms=200.0,
                     pad_to_bucket=False)
    futs = [b.submit(np.full((2, 2), i, np.float32)) for i in range(4)]
    for i, f in enumerate(futs):
        np.testing.assert_array_equal(f.result(10.0), np.full((2, 2), i))
    assert max(sizes) <= 4
    b.stop()


# ---------------------------------------------------------------------------
# Predict response shaping
# ---------------------------------------------------------------------------
def test_predict_empty_data_dir_keeps_output_rank(tmp_path):
    """Zero minibatches must yield an empty array shaped
    (0, *output_dims), not np.zeros((0,))."""
    path = _write_mlp(tmp_path / "m.zip")
    empty = tmp_path / "data"
    empty.mkdir()
    ep = DeepLearning4jEntryPoint()
    out = ep.predict(path, data_dir=str(empty))
    assert out["shape"] == [0, C]
    assert out["predictions"] == []
    ep.close()


def test_predict_top_k_and_argmax_only(tmp_path):
    path = _write_mlp(tmp_path / "m.zip")
    ep = DeepLearning4jEntryPoint()
    rng = np.random.default_rng(1)
    x = rng.normal(size=(5, F)).astype(np.float32)
    full = np.asarray(ep.predict(path, features=x)["predictions"])
    assert full.shape == (5, C)

    am = ep.predict(path, features=x, argmax_only=True)
    assert am["classes"] == np.argmax(full, axis=-1).tolist()
    assert "predictions" not in am

    tk = ep.predict(path, features=x, top_k=2)
    assert tk["shape"] == [5, 2]
    for row_cls, row_p, row_full in zip(tk["classes"], tk["probabilities"],
                                        full):
        assert row_cls[0] == int(np.argmax(row_full))
        assert row_p[0] >= row_p[1]
    ep.close()


def test_predict_requires_exactly_one_input_source(tmp_path):
    path = _write_mlp(tmp_path / "m.zip")
    ep = DeepLearning4jEntryPoint()
    with pytest.raises(ValueError, match="exactly one"):
        ep.predict(path)
    with pytest.raises(ValueError, match="exactly one"):
        ep.predict(path, data_dir="d", features=[[0.0] * F])
    with pytest.raises(ValueError, match="non-empty"):
        ep.predict(path, features=np.zeros((0, F), np.float32))
    ep.close()


# ---------------------------------------------------------------------------
# Gateway RPCs + error hygiene
# ---------------------------------------------------------------------------
def test_stats_invalidate_rpcs_and_traceback_gating(tmp_path):
    path = _write_mlp(tmp_path / "m.zip")
    srv = Server().start()
    try:
        base = f"http://{srv.host}:{srv.port}/"
        x = np.zeros((2, F), np.float32).tolist()
        code, resp = _post(base, {"method": "predict", "params": {
            "model_path": path, "features": x}})
        assert code == 200, resp
        assert np.asarray(resp["result"]["predictions"]).shape == (2, C)

        code, resp = _post(base, {"method": "stats", "params": {}})
        assert code == 200
        mc = resp["result"]["model_cache"]
        assert mc["size"] == 1 and mc["misses"] == 1
        serving = next(iter(resp["result"]["serving"].values()))
        for field in ("requests", "batches", "batch_size_hist", "queue_ms",
                      "compute_ms", "total_ms", "compile_telemetry"):
            assert field in serving, field
        assert {"p50_ms", "p95_ms", "p99_ms"} <= set(serving["total_ms"])

        code, resp = _post(base, {"method": "invalidate", "params": {
            "model_path": path}})
        assert code == 200 and resp["result"]["invalidated"] == 1
        code, resp = _post(base, {"method": "stats", "params": {}})
        assert resp["result"]["model_cache"]["size"] == 0

        # error payloads: no traceback without debug=True
        code, resp = _post(base, {"method": "predict", "params": {
            "model_path": str(tmp_path / "missing.zip"),
            "features": x}})
        assert code == 500 and "error" in resp
        assert "traceback" not in resp
    finally:
        srv.stop()

    srv = Server(debug=True).start()
    try:
        base = f"http://{srv.host}:{srv.port}/"
        code, resp = _post(base, {"method": "predict", "params": {
            "model_path": str(tmp_path / "missing.zip"),
            "features": [[0.0] * F]}})
        assert code == 500 and "traceback" in resp
    finally:
        srv.stop()


def test_fit_invalidates_mutated_cache_entry(tmp_path):
    """fit() trains the cached instance in-memory; the entry must be
    dropped so a later predict serves the on-disk checkpoint, not a
    silently-diverged object."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.scaleout.data import export_dataset

    path = _write_mlp(tmp_path / "m.zip")
    rng = np.random.default_rng(2)
    x = rng.normal(size=(8, F)).astype(np.float32)
    y = np.eye(C, dtype=np.float32)[rng.integers(0, C, 8)]
    data_dir = tmp_path / "data"
    data_dir.mkdir()
    export_dataset(DataSet(x, y), data_dir / "b0.npz")

    ep = DeepLearning4jEntryPoint()
    save_path = str(tmp_path / "trained.zip")
    out = ep.fit(path, str(data_dir), epochs=2, save_path=save_path)
    assert np.isfinite(out["score"])
    # the mutated instance is gone; the source checkpoint reloads fresh
    assert ep.model_cache.peek(path) is None
    pred = ep.predict(path, features=x)
    from deeplearning4j_tpu.nn.serialization import load_model
    fresh = load_model(path)
    np.testing.assert_allclose(np.asarray(pred["predictions"]),
                               np.asarray(fresh.output(x)),
                               rtol=1e-6, atol=1e-6)
    ep.close()


# ---------------------------------------------------------------------------
# Load generator (slow: excluded from tier-1)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_closed_loop_load_generator_coalesces(tmp_path):
    """8 client threads in a closed loop: coalescing must produce
    multi-request batches and keep the retrace count bounded by the
    warmed bucket ladder (not the request count)."""
    path = _write_mlp(tmp_path / "m.zip")
    ep = DeepLearning4jEntryPoint(max_batch=16, max_wait_ms=2.0, min_batch=8)
    rng = np.random.default_rng(3)
    reqs_per_client = 25
    rows = [[rng.normal(size=(1, F)).astype(np.float32)
             for _ in range(reqs_per_client)] for _ in range(8)]
    ep.predict(path, features=rows[0][0])  # load + warm outside the loop

    def client(rs):
        for r in rs:
            ep.predict(path, features=r, argmax_only=True)

    threads = [threading.Thread(target=client, args=(rs,)) for rs in rows]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    s = next(iter(ep.stats()["serving"].values()))
    assert s["requests"] == 8 * reqs_per_client + 1
    assert s["requests_per_batch_mean"] > 1.5   # coalescing happened
    model = ep.model_cache.peek(path)
    ladder = ep.model_cache.stats()["models"][
        list(ep.model_cache.stats()["models"])[0]]["warmup"]["buckets"]
    output_programs = model.compile_telemetry.snapshot()["by_kind"]["output"]
    assert output_programs <= len(ladder)
    ep.close()
