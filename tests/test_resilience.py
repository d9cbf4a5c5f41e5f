"""Resilience subsystem: retry/backoff determinism, circuit-breaker
state transitions, deterministic fault injection, batcher dead-thread
recovery + deadline shedding, gateway admission control (503 +
Retry-After) and healthz/readyz, corrupt-checkpoint fallback, and the
chaos integration test (crash mid-fit + injected reader faults →
resume=True matches the uninterrupted run)."""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
import zipfile

import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.serialization import write_model
from deeplearning4j_tpu.resilience import (
    CircuitBreaker, CircuitOpenError, FaultPlan, OverloadedError,
    RetryPolicy, TransientError, faults)
from deeplearning4j_tpu.resilience.errors import DeadlineExceededError
from deeplearning4j_tpu.server import (
    DeepLearning4jEntryPoint, MicroBatcher, ModelCache, Server)

F, C = 6, 3


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _mlp(seed=3):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).learning_rate(0.1).updater("adam")
            .list()
            .layer(L.DenseLayer(n_in=F, n_out=12, activation="relu"))
            .layer(L.OutputLayer(n_in=12, n_out=C, activation="softmax",
                                 loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _write_mlp(path, seed=3):
    write_model(_mlp(seed), str(path))
    return str(path)


# ---------------------------------------------------------------------------
# RetryPolicy
# ---------------------------------------------------------------------------
def test_retry_jitter_deterministic_under_fixed_seed():
    a = RetryPolicy(max_attempts=6, base_delay_ms=50, seed=42)
    b = RetryPolicy(max_attempts=6, base_delay_ms=50, seed=42)
    da, db = a.delays(), b.delays()
    assert da == db and len(da) == 5
    # exponential envelope: each delay ≤ base * 2^i, and jitter keeps it
    # within [1 - jitter, 1] of the envelope
    for i, d in enumerate(da):
        env = min(2.0, 0.05 * 2 ** i)
        assert 0.5 * env <= d <= env
    assert RetryPolicy(max_attempts=6, seed=7).delays() != da


def test_retry_retries_transient_then_succeeds():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientError("flake")
        return "ok"
    seen = []
    p = RetryPolicy(max_attempts=5, base_delay_ms=1, seed=0)
    assert p.call(flaky, on_retry=lambda i, e: seen.append(i)) == "ok"
    assert calls["n"] == 3 and seen == [0, 1]


def test_retry_does_not_retry_non_transient():
    calls = {"n": 0}

    def broken():
        calls["n"] += 1
        raise ValueError("a real bug")
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=5, base_delay_ms=1).call(broken)
    assert calls["n"] == 1


def test_retry_exhaustion_raises_last_error():
    def always():
        raise TransientError("always")
    p = RetryPolicy(max_attempts=3, base_delay_ms=1, seed=1)
    with pytest.raises(TransientError):
        p.call(always)


def test_retry_deadline_budget_stops_early():
    calls = {"n": 0}

    def always():
        calls["n"] += 1
        raise TransientError("always")
    # 200 ms backoff against a 50 ms budget: the retry cannot fit, so
    # only the first attempt runs
    p = RetryPolicy(max_attempts=10, base_delay_ms=200, jitter=0.0,
                    deadline_s=0.05)
    with pytest.raises(TransientError):
        p.call(always)
    assert calls["n"] == 1


def test_retry_attempt_timeout_is_retryable():
    calls = {"n": 0}

    def slow_then_fast():
        calls["n"] += 1
        if calls["n"] == 1:
            time.sleep(0.5)
        return calls["n"]
    p = RetryPolicy(max_attempts=3, base_delay_ms=1,
                    attempt_timeout_s=0.1)
    assert p.call(slow_then_fast) == 2


def test_retry_decorator_form():
    state = {"n": 0}

    @RetryPolicy(max_attempts=3, base_delay_ms=1)
    def f():
        state["n"] += 1
        if state["n"] < 2:
            raise TransientError("x")
        return "done"
    assert f() == "done" and state["n"] == 2


# ---------------------------------------------------------------------------
# CircuitBreaker
# ---------------------------------------------------------------------------
def _clocked_breaker(**kw):
    t = {"now": 0.0}
    kw.setdefault("name", f"test-{kw.get('cooldown_s', 0)}-{id(t)}")
    br = CircuitBreaker(clock=lambda: t["now"], **kw)
    return br, t


def test_breaker_closed_open_halfopen_closed():
    br, t = _clocked_breaker(failure_threshold=0.5, window=4, min_calls=2,
                             cooldown_s=10.0)
    boom = lambda: (_ for _ in ()).throw(RuntimeError("x"))  # noqa: E731
    assert br.state == CircuitBreaker.CLOSED
    for _ in range(2):
        with pytest.raises(RuntimeError):
            br.call(boom)
    assert br.state == CircuitBreaker.OPEN
    # open: fail fast with the remaining cooldown as the hint
    with pytest.raises(CircuitOpenError) as e:
        br.call(lambda: 1)
    assert 0 < e.value.retry_after_s <= 10.0
    # cooldown elapses → half-open probe allowed; success closes
    t["now"] = 10.0
    assert br.state == CircuitBreaker.HALF_OPEN
    assert br.call(lambda: 5) == 5
    assert br.state == CircuitBreaker.CLOSED
    # the window was cleared on close: one new failure does not reopen
    with pytest.raises(RuntimeError):
        br.call(boom)
    assert br.state == CircuitBreaker.CLOSED


def test_breaker_halfopen_failure_reopens():
    br, t = _clocked_breaker(failure_threshold=1.0, window=2, min_calls=2,
                             cooldown_s=5.0)
    boom = lambda: (_ for _ in ()).throw(RuntimeError("x"))  # noqa: E731
    for _ in range(2):
        with pytest.raises(RuntimeError):
            br.call(boom)
    assert br.state == CircuitBreaker.OPEN
    t["now"] = 5.0
    with pytest.raises(RuntimeError):
        br.call(boom)          # the probe fails
    assert br.state == CircuitBreaker.OPEN
    # the cooldown restarted at the probe failure
    with pytest.raises(CircuitOpenError):
        br.call(lambda: 1)


def test_breaker_state_metered():
    from deeplearning4j_tpu import monitor
    br, t = _clocked_breaker(failure_threshold=1.0, window=2, min_calls=1,
                             cooldown_s=99.0, name="metered-test")
    with pytest.raises(RuntimeError):
        br.call(lambda: (_ for _ in ()).throw(RuntimeError("x")))
    fam = monitor.get_registry().get("dl4j_resilience_breaker_state")
    val = {tuple(s["labels"].items()): s["value"]
           for s in fam.samples()}[(("breaker", "metered-test"),)]
    assert val == 2  # open


# ---------------------------------------------------------------------------
# Fault injection
# ---------------------------------------------------------------------------
def test_fault_on_call_fires_exactly_once():
    faults.arm({"site": "cache.load", "mode": "fail", "on_call": 2,
                "exc": "RuntimeError"})
    faults.check("cache.load")
    with pytest.raises(RuntimeError):
        faults.check("cache.load")
    faults.check("cache.load")   # call 3: nothing
    assert faults.call_count("cache.load") == 3
    assert faults.armed("cache.load")[0]["injected"] == 1


def test_fault_probability_deterministic_and_bounded():
    def run():
        faults.reset()
        faults.arm({"site": "cache.load", "mode": "fail",
                    "probability": 0.4, "seed": 9, "max_injections": 3})
        seq = []
        for _ in range(30):
            try:
                faults.check("cache.load")
                seq.append(0)
            except TransientError:
                seq.append(1)
        return seq
    s1, s2 = run(), run()
    assert s1 == s2
    assert sum(s1) == 3  # max_injections caps the chaos


def test_fault_latency_mode_delays():
    faults.arm({"site": "gateway.predict", "mode": "latency",
                "latency_ms": 60, "probability": 1.0})
    t0 = time.perf_counter()
    faults.check("gateway.predict")
    assert time.perf_counter() - t0 >= 0.05


def test_fault_env_arming(monkeypatch):
    plan = [{"site": "batcher.compute", "mode": "fail", "on_call": 1,
             "exc": "TransientError"}]
    monkeypatch.setenv(faults.ENV_VAR, json.dumps(plan))
    faults.reset()  # forces the env to be re-read on next check
    with pytest.raises(TransientError):
        faults.check("batcher.compute")


def test_fault_plan_validation():
    with pytest.raises(ValueError):
        FaultPlan("x", mode="explode")
    with pytest.raises(ValueError):
        FaultPlan("x", exc="SegFault")


# ---------------------------------------------------------------------------
# MicroBatcher: dead thread + deadline shedding
# ---------------------------------------------------------------------------
def test_batcher_thread_death_fails_pending_and_restarts():
    """Regression (satellite 1): a batcher thread that dies mid-batch
    used to leave the pending future blocking forever."""
    mb = MicroBatcher(lambda x: x * 2, max_batch=8, name="death-test")
    assert np.allclose(mb.predict(np.ones((2, 3)), timeout=10), 2.0)
    faults.arm({"site": "batcher.compute", "mode": "kill", "on_call": 1})
    fut = mb.submit(np.ones((1, 3)))
    with pytest.raises(RuntimeError, match="died"):
        fut.result(timeout=10)   # fails promptly — no client hang
    assert mb.deaths == 1
    faults.reset()
    # next submit restarts the thread and serves normally
    out = mb.predict(np.ones((3, 3)), timeout=10)
    assert np.allclose(out, 2.0)
    assert mb.restarts == 1 and mb.thread_alive
    mb.stop()


def test_batcher_deadline_shed_before_compute_accounting():
    from deeplearning4j_tpu import monitor

    def slow(x):
        time.sleep(0.15)
        return x
    mb = MicroBatcher(slow, max_batch=4, name="shed-test")
    shed_fam = monitor.get_registry().get("dl4j_resilience_shed_total")

    def shed_count():
        return {tuple(s["labels"].items()): s["value"]
                for s in shed_fam.samples()}.get((("reason", "deadline"),), 0)
    before = shed_count()
    mb.submit(np.ones((1, 3)))             # occupies the thread ~150 ms
    time.sleep(0.03)
    doomed = mb.submit(np.ones((1, 3)), timeout_ms=40)  # expires queued
    ok = mb.submit(np.ones((1, 3)))                     # no deadline
    with pytest.raises(DeadlineExceededError):
        doomed.result(timeout=10)
    assert np.allclose(ok.result(timeout=10), 1.0)      # batch-mates live
    assert mb.metrics.snapshot()["shed"] == {"deadline": 1}
    assert shed_count() == before + 1
    mb.stop()


# ---------------------------------------------------------------------------
# ModelCache: retry + breaker around loads
# ---------------------------------------------------------------------------
def test_model_cache_load_retry_absorbs_transient_flake(tmp_path):
    path = _write_mlp(tmp_path / "m.zip")
    cache = ModelCache(load_retry=RetryPolicy(max_attempts=3,
                                              base_delay_ms=1, seed=0))
    faults.arm({"site": "cache.load", "mode": "fail", "on_call": 1,
                "exc": "TransientError"})
    model = cache.get(path)     # first attempt injected, retry succeeds
    assert model is not None
    assert cache.stats()["misses"] == 1


def test_model_cache_breaker_opens_and_recovers(tmp_path):
    path = _write_mlp(tmp_path / "m.zip")
    br = CircuitBreaker(failure_threshold=1.0, window=3, min_calls=3,
                        cooldown_s=0.05, name="cache-test")
    cache = ModelCache(load_breaker=br)
    faults.arm({"site": "cache.load", "mode": "fail",
                "probability": 1.0, "exc": "TransientError",
                "max_injections": 3})
    for _ in range(3):
        with pytest.raises(TransientError):
            cache.get(path)
    assert br.state == CircuitBreaker.OPEN
    assert cache.stats()["load_breaker"]["state"] == CircuitBreaker.OPEN
    with pytest.raises(CircuitOpenError):
        cache.get(path)          # fail fast, loader not reached
    time.sleep(0.06)             # cooldown → half-open; injections spent
    assert cache.get(path) is not None
    assert br.state == CircuitBreaker.CLOSED


# ---------------------------------------------------------------------------
# Corrupt-checkpoint fallback (satellite 2)
# ---------------------------------------------------------------------------
def _fit_with_checkpoints(tmp_path, every_n=2, iters=6):
    from deeplearning4j_tpu.nn.checkpoint import CheckpointListener
    net = _mlp()
    net.set_listeners(CheckpointListener(tmp_path, keep_last=10,
                                         save_every_n_iterations=every_n))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, F)).astype(np.float32)
    y = np.eye(C, dtype=np.float32)[rng.integers(0, C, 8)]
    for _ in range(iters):
        net.fit(x, y)
    return net


def test_resume_falls_back_past_truncated_checkpoint(tmp_path):
    from deeplearning4j_tpu.nn.checkpoint import (
        CheckpointListener, resume_from_checkpoint)
    _fit_with_checkpoints(tmp_path)
    ckpts = CheckpointListener.checkpoints(tmp_path)
    assert len(ckpts) == 3
    # truncate the newest zip — what a crashed writer without atomic
    # publish produces (and torn storage still can)
    data = ckpts[-1].read_bytes()
    ckpts[-1].write_bytes(data[:len(data) // 2])
    resumed = resume_from_checkpoint(tmp_path)
    assert resumed is not None
    assert resumed.iteration == 4    # fell back to checkpoint_it4
    np.testing.assert_allclose(np.asarray(resumed.params()).size > 0, True)


def test_resume_falls_back_past_corrupt_member(tmp_path):
    from deeplearning4j_tpu.nn.checkpoint import (
        CheckpointListener, resume_from_checkpoint, validate_checkpoint)
    from deeplearning4j_tpu.resilience.errors import CorruptCheckpointError
    _fit_with_checkpoints(tmp_path)
    newest = CheckpointListener.checkpoints(tmp_path)[-1]
    # corrupt the configuration member's bytes in place (CRC mismatch)
    raw = bytearray(newest.read_bytes())
    with zipfile.ZipFile(newest) as zf:
        info = zf.getinfo("configuration.json")
    start = raw.find(b"configuration.json", info.header_offset) \
        + len(b"configuration.json")
    raw[start + 10:start + 20] = b"\x00" * 10
    newest.write_bytes(bytes(raw))
    with pytest.raises(CorruptCheckpointError):
        validate_checkpoint(newest)
    resumed = resume_from_checkpoint(tmp_path)
    assert resumed is not None and resumed.iteration == 4


def test_resume_returns_none_when_all_corrupt(tmp_path):
    from deeplearning4j_tpu.nn.checkpoint import (
        CheckpointListener, resume_from_checkpoint)
    _fit_with_checkpoints(tmp_path)
    for p in CheckpointListener.checkpoints(tmp_path):
        p.write_bytes(b"not a zip at all")
    assert resume_from_checkpoint(tmp_path) is None


def test_manifest_records_epoch_position(tmp_path):
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.nn.checkpoint import (
        CheckpointListener, read_manifest)
    net = _mlp()
    net.set_listeners(CheckpointListener(tmp_path, keep_last=10,
                                         save_every_n_iterations=3))
    rng = np.random.default_rng(0)
    batches = [DataSet(rng.normal(size=(4, F)).astype(np.float32),
                       np.eye(C, dtype=np.float32)[rng.integers(0, C, 4)])
               for _ in range(4)]
    net.fit(ListDataSetIterator(batches), epochs=2)   # 8 iterations
    entries = {e["iteration"]: e for e in read_manifest(tmp_path)}
    assert entries[3]["epoch"] == 0
    assert entries[3]["iteration_in_epoch"] == 3
    assert entries[6]["epoch"] == 1      # batch 2 of epoch 1
    assert entries[6]["iteration_in_epoch"] == 2
    assert not list(tmp_path.glob("*.tmp"))


# ---------------------------------------------------------------------------
# Pipeline reader retries
# ---------------------------------------------------------------------------
def test_pipeline_reader_retry_preserves_order():
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import (
        AsyncDataSetIterator, ListDataSetIterator)
    rng = np.random.default_rng(2)
    batches = [DataSet(rng.normal(size=(4, F)).astype(np.float32),
                       np.eye(C, dtype=np.float32)[rng.integers(0, C, 4)])
               for _ in range(10)]
    faults.arm({"site": "reader.next_raw", "mode": "fail",
                "probability": 0.3, "seed": 4, "exc": "TransientError"})
    it = AsyncDataSetIterator(
        ListDataSetIterator(list(batches)), workers=2,
        reader_retry=RetryPolicy(max_attempts=8, base_delay_ms=1, seed=0))
    got = [it.next() for _ in iter(lambda: it.has_next(), False)]
    it.close()
    assert len(got) == 10
    for g, b in zip(got, batches):
        np.testing.assert_array_equal(np.asarray(g.features), b.features)
    assert faults.armed("reader.next_raw")[0]["injected"] > 0


def test_pipeline_reader_retry_exhaustion_surfaces():
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import (
        AsyncDataSetIterator, ListDataSetIterator)
    x = np.zeros((2, F), np.float32)
    y = np.eye(C, dtype=np.float32)[:1].repeat(2, 0)
    faults.arm({"site": "reader.next_raw", "mode": "fail",
                "probability": 1.0, "exc": "TransientError"})
    it = AsyncDataSetIterator(
        ListDataSetIterator([DataSet(x, y)]), workers=1,
        reader_retry=RetryPolicy(max_attempts=2, base_delay_ms=1, seed=0))
    with pytest.raises(TransientError):
        it.has_next()
    it.close()


# ---------------------------------------------------------------------------
# Chaos integration: crash mid-fit → resume=True → parity
# ---------------------------------------------------------------------------
def _ft_conf():
    return (NeuralNetConfiguration.builder().seed(3).learning_rate(0.05)
            .updater("adam")
            .input_pipeline(workers=1)
            .fault_tolerance(resume=True, reader_retries=4)
            .list()
            .layer(L.DenseLayer(n_in=F, n_out=8, activation="tanh"))
            .layer(L.OutputLayer(n_out=C, activation="softmax",
                                 loss="mcxent"))
            .build())


def _chaos_batches():
    from deeplearning4j_tpu.datasets.dataset import DataSet
    rng = np.random.default_rng(1)
    return [DataSet(rng.normal(size=(8, F)).astype(np.float32),
                    np.eye(C, dtype=np.float32)[rng.integers(0, C, 8)])
            for _ in range(8)]


def _counter_total(name):
    from deeplearning4j_tpu import monitor
    fam = monitor.get_registry().get(name)
    return sum(s["value"] for s in fam.samples()) if fam else 0.0


def test_absorbed_reader_faults_are_counted_as_retries():
    """Chaos at a rate the feeder's retries cover is absorbed, not
    surfaced: fit() takes every step, and each injected reader fault is
    one counted retry and no exhaustion."""
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    batches = _chaos_batches()
    retries = _counter_total("dl4j_resilience_retries_total")
    exhausted = _counter_total("dl4j_resilience_retry_exhausted_total")
    faults.arm({"site": "reader.next_raw", "mode": "fail",
                "probability": 0.25, "seed": 5, "exc": "TransientError"})
    net = MultiLayerNetwork(_ft_conf()).init()
    net.fit(ListDataSetIterator(list(batches)), epochs=2)
    injected = faults.armed("reader.next_raw")[0]["injected"]
    assert net.iteration == 2 * len(batches)
    assert injected > 0
    assert _counter_total("dl4j_resilience_retries_total") \
        == retries + injected
    assert _counter_total("dl4j_resilience_retry_exhausted_total") \
        == exhausted


def test_chaos_crash_resume_parity(tmp_path):
    """Acceptance: with a fault plan crashing fit mid-run and seeded
    transient reader faults, a restart with resume=True completes and
    matches the fault-free run's final score/params."""
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.nn.checkpoint import CheckpointListener
    batches = _chaos_batches()

    ref = MultiLayerNetwork(_ft_conf()).init()
    ref.fit(ListDataSetIterator(list(batches)), epochs=2)
    ref_params = np.asarray(ref.params())

    # crashed run: checkpoint every 3 iterations; the 2nd save (it=6)
    # raises — fit dies at iteration 6 with checkpoint_it3 on disk —
    # while 25%-probability transient reader faults are retried away
    crashed = MultiLayerNetwork(_ft_conf()).init()
    crashed.set_listeners(CheckpointListener(
        tmp_path, save_every_n_iterations=3))
    faults.arm({"site": "checkpoint.write", "mode": "fail", "on_call": 2,
                "exc": "RuntimeError"})
    faults.arm({"site": "reader.next_raw", "mode": "fail",
                "probability": 0.25, "seed": 5, "exc": "TransientError"})
    with pytest.raises(RuntimeError):
        crashed.fit(ListDataSetIterator(list(batches)), epochs=2)
    faults.disarm("checkpoint.write")   # reader chaos stays armed

    # "process restart": a fresh model, same conf/script — fit restores
    # checkpoint_it3, replay-skips 3 batches, and retrains the rest
    resumed = MultiLayerNetwork(_ft_conf()).init()
    resumed.set_listeners(CheckpointListener(
        tmp_path, save_every_n_iterations=3))
    resumed.fit(ListDataSetIterator(list(batches)), epochs=2)

    assert resumed.iteration == ref.iteration == 16
    assert resumed.epoch == ref.epoch == 2
    np.testing.assert_allclose(np.asarray(resumed.params()), ref_params,
                               atol=1e-6)
    assert np.isclose(float(resumed.score()), float(ref.score()),
                      atol=1e-6)
    assert faults.armed("reader.next_raw")[0]["injected"] > 0


def test_resume_skips_whole_epochs(tmp_path):
    """An epoch-end checkpoint resumes at the next epoch boundary."""
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.nn.checkpoint import CheckpointListener
    batches = _chaos_batches()[:4]

    ref = MultiLayerNetwork(_ft_conf()).init()
    ref.fit(ListDataSetIterator(list(batches)), epochs=3)

    crashed = MultiLayerNetwork(_ft_conf()).init()
    crashed.set_listeners(CheckpointListener(tmp_path,
                                             save_every_epoch=True))
    crashed.fit(ListDataSetIterator(list(batches)), epochs=2)

    resumed = MultiLayerNetwork(_ft_conf()).init()
    resumed.set_listeners(CheckpointListener(tmp_path,
                                             save_every_epoch=True))
    resumed.fit(ListDataSetIterator(list(batches)), epochs=3)
    assert resumed.iteration == ref.iteration
    assert resumed.epoch == ref.epoch == 3
    np.testing.assert_allclose(np.asarray(resumed.params()),
                               np.asarray(ref.params()), atol=1e-6)


def test_chaos_crash_resume_parity_computation_graph(tmp_path):
    """Same resume contract on the ComputationGraph fit loop."""
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.nn.checkpoint import CheckpointListener
    from deeplearning4j_tpu.nn.conf.graph_conf import GraphBuilder
    from deeplearning4j_tpu.nn.conf.network import GlobalConf
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    def make():
        g = GlobalConf(seed=1, learning_rate=0.05, updater="adam",
                       ft_resume=True, ft_reader_retries=3)
        conf = (GraphBuilder(g).add_inputs("in")
                .add_layer("d", L.DenseLayer(n_in=F, n_out=8,
                                             activation="tanh"), "in")
                .add_layer("out", L.OutputLayer(n_in=8, n_out=C,
                                                activation="softmax",
                                                loss="mcxent"), "d")
                .set_outputs("out").build())
        return ComputationGraph(conf).init()

    batches = _chaos_batches()[:6]
    ref = make()
    ref.fit(ListDataSetIterator(list(batches)), epochs=2)

    crashed = make()
    crashed.set_listeners(CheckpointListener(
        tmp_path, save_every_n_iterations=4))
    faults.arm({"site": "checkpoint.write", "mode": "fail", "on_call": 2,
                "exc": "RuntimeError"})
    with pytest.raises(RuntimeError):
        crashed.fit(ListDataSetIterator(list(batches)), epochs=2)
    faults.reset()

    resumed = make()
    resumed.set_listeners(CheckpointListener(
        tmp_path, save_every_n_iterations=4))
    resumed.fit(ListDataSetIterator(list(batches)), epochs=2)
    assert resumed.iteration == ref.iteration == 12
    assert resumed.epoch == ref.epoch == 2
    np.testing.assert_allclose(np.asarray(resumed.params()),
                               np.asarray(ref.params()), atol=1e-6)


def test_fault_tolerance_conf_roundtrip():
    from deeplearning4j_tpu.nn.conf.network import (
        GlobalConf, MultiLayerConfiguration)
    conf = _ft_conf()
    again = MultiLayerConfiguration.from_json(conf.to_json())
    assert again.global_conf.ft_resume is True
    assert again.global_conf.ft_reader_retries == 4
    # legacy config dicts (no ft_* keys) still load with defaults
    d = json.loads(conf.to_json())
    for k in ("ft_resume", "ft_reader_retries", "ft_checkpoint_dir"):
        d["global"].pop(k)
    legacy = MultiLayerConfiguration.from_dict(d)
    assert legacy.global_conf.ft_resume is False
    assert GlobalConf().ft_reader_retries == 0


# ---------------------------------------------------------------------------
# Gateway: admission control, healthz/readyz
# ---------------------------------------------------------------------------
def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=30) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _post(url, obj):
    req = urllib.request.Request(
        url, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def test_gateway_overload_sheds_503_with_retry_after(tmp_path):
    """Acceptance: under injected overload the gateway sheds with 503 +
    Retry-After instead of queuing unboundedly, no client hangs, and
    accepted requests complete."""
    path = _write_mlp(tmp_path / "m.zip")
    ep = DeepLearning4jEntryPoint(max_batch=1, max_wait_ms=1.0,
                                  max_queue_rows=2, retry_after_s=2.0)
    server = Server(ep, port=0).start()
    url = f"http://{server.host}:{server.port}/"
    try:
        # prime the cache/warmup outside the overloaded window
        code, body, _ = _post(url, {"method": "predict", "params": {
            "model_path": path, "features": [[0.0] * F]}})
        assert code == 200, body
        # 60 ms of injected compute latency per dispatch → queue builds
        faults.arm({"site": "batcher.compute", "mode": "latency",
                    "latency_ms": 60, "probability": 1.0})
        results = []
        lock = threading.Lock()

        def client():
            t0 = time.perf_counter()
            code, body, headers = _post(url, {
                "method": "predict",
                "params": {"model_path": path, "features": [[0.0] * F]}})
            with lock:
                results.append((code, headers, time.perf_counter() - t0))
        threads = [threading.Thread(target=client) for _ in range(10)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads), "client hang"
        codes = [c for c, _, _ in results]
        assert codes.count(503) >= 1, codes
        assert codes.count(200) >= 1, codes
        for code, headers, _ in results:
            if code == 503:
                assert headers.get("Retry-After") == "2"
        # accepted requests' latency stays bounded (queue cap ≈ 2 rows
        # × 60 ms dispatch, far under the 5 s ceiling)
        accepted = sorted(t for c, _, t in results if c == 200)
        assert accepted[-1] < 5.0
    finally:
        faults.reset()
        server.stop()


def test_healthz_and_readyz_flip(tmp_path):
    path = _write_mlp(tmp_path / "m.zip")
    ep = DeepLearning4jEntryPoint(min_ready_models=1)
    server = Server(ep, port=0).start()
    base = f"http://{server.host}:{server.port}"
    try:
        code, body, _ = _get(base + "/healthz")
        assert code == 200 and body["status"] == "ok"
        # no model resident yet → not ready (models_warm fails)
        code, body, _ = _get(base + "/readyz")
        assert code == 503 and body["ready"] is False
        assert body["checks"]["models_warm"] is False
        # load + warm a model → ready
        code, _, _ = _post(base + "/", {"method": "predict", "params": {
            "model_path": path, "features": [[0.0] * F]}})
        assert code == 200
        code, body, _ = _get(base + "/readyz")
        assert code == 200 and body["ready"] is True
        # open the cache-load breaker → readyz flips unready
        br = ep.model_cache.load_breaker
        for _ in range(br.min_calls):
            br.record(False)
        assert br.state == CircuitBreaker.OPEN
        code, body, _ = _get(base + "/readyz")
        assert code == 503 and body["checks"]["breaker_closed"] is False
        br.reset()
        code, body, _ = _get(base + "/readyz")
        assert code == 200
        # healthz stayed healthy through all of it
        assert _get(base + "/healthz")[0] == 200
    finally:
        server.stop()


def test_readyz_queue_pressure_flips(tmp_path):
    path = _write_mlp(tmp_path / "m.zip")
    ep = DeepLearning4jEntryPoint(max_batch=1, max_wait_ms=1.0,
                                  max_queue_rows=3)
    try:
        ep.predict(model_path=path, features=[[0.0] * F])
        faults.arm({"site": "batcher.compute", "mode": "latency",
                    "latency_ms": 80, "probability": 1.0})
        batcher = next(iter(ep._batchers.values()))[1]
        for _ in range(8):   # direct submits bypass admission control
            batcher.submit(np.zeros((1, F), np.float32))
        deadline = time.monotonic() + 5
        flipped = False
        while time.monotonic() < deadline:
            r = ep.readyz()
            if not r["ready"] and not r["checks"]["queue_below_limit"]:
                flipped = True
                break
            time.sleep(0.01)
        assert flipped, "readyz never reported queue pressure"
        faults.reset()
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and not ep.readyz()["ready"]:
            time.sleep(0.05)
        assert ep.readyz()["ready"]
    finally:
        faults.reset()
        ep.close()


def test_predict_deadline_maps_to_504(tmp_path):
    path = _write_mlp(tmp_path / "m.zip")
    ep = DeepLearning4jEntryPoint(max_batch=1, max_wait_ms=1.0)
    server = Server(ep, port=0).start()
    url = f"http://{server.host}:{server.port}/"
    try:
        code, _, _ = _post(url, {"method": "predict", "params": {
            "model_path": path, "features": [[0.0] * F]}})
        assert code == 200
        faults.arm({"site": "batcher.compute", "mode": "latency",
                    "latency_ms": 100, "probability": 1.0})
        # first request occupies the batcher; the second's 30 ms budget
        # expires while queued → shed → 504
        t = threading.Thread(target=_post, args=(url, {
            "method": "predict",
            "params": {"model_path": path, "features": [[0.0] * F]}}))
        t.start()
        time.sleep(0.03)
        code, body, _ = _post(url, {"method": "predict", "params": {
            "model_path": path, "features": [[0.0] * F],
            "deadline_ms": 30}})
        t.join(timeout=30)
        assert code == 504, body
        assert "DeadlineExceededError" in body["error"]
    finally:
        faults.reset()
        server.stop()


def test_overloaded_error_direct():
    ep = DeepLearning4jEntryPoint(max_queue_rows=1)
    with pytest.raises(OverloadedError) as e:
        ep._admit(5)
    assert e.value.retry_after_s == 1.0
    ep.close()


# ---------------------------------------------------------------------------
# Tier-1 subprocess smoke: fault-armed server still answers /healthz
# ---------------------------------------------------------------------------
_SMOKE = r"""
import json, os, urllib.request, urllib.error
from deeplearning4j_tpu.server import DeepLearning4jEntryPoint, Server
server = Server(DeepLearning4jEntryPoint(), port=0).start()
base = f"http://{server.host}:{server.port}"
out = {}
with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
    out["healthz"] = r.status
try:
    urllib.request.urlopen(base + "/readyz", timeout=10)
    out["readyz"] = 200
except urllib.error.HTTPError as e:
    out["readyz"] = e.code
# the armed gateway.predict fault fires (chaos is live) yet the probe
# surfaces above stayed up
req = urllib.request.Request(base + "/", data=json.dumps(
    {"method": "predict", "params": {"model_path": "x",
                                     "features": [[0.0]]}}).encode())
try:
    urllib.request.urlopen(req, timeout=10)
    out["predict"] = 200
except urllib.error.HTTPError as e:
    out["predict"] = e.code
with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
    out["healthz_after"] = r.status
server.stop()
print(json.dumps(out))
"""


def test_fault_armed_server_answers_healthz_subprocess():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env[faults.ENV_VAR] = json.dumps([
        {"site": "gateway.predict", "mode": "fail", "probability": 1.0,
         "exc": "TransientError"},
        {"site": "cache.load", "mode": "latency", "latency_ms": 50,
         "probability": 1.0}])
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run([sys.executable, "-c", _SMOKE],
                       capture_output=True, text=True, timeout=240,
                       env=env, cwd=root)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["healthz"] == 200
    assert out["healthz_after"] == 200   # chaos didn't take liveness down
    assert out["predict"] == 500         # the injected fault did fire
