"""Unified observability backbone (deeplearning4j_tpu/monitor):
registry thread-safety, histogram bucket/percentile correctness,
Prometheus text-format round-trip, span nesting/timing, the
empty-reservoir percentile fix, and a fit + concurrent-predict
integration test asserting retraces/phase-timings/latencies/cache
counters all appear in one ``metrics`` RPC scrape."""

import json
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.monitor import exposition, tracing
from deeplearning4j_tpu.monitor.registry import MetricsRegistry


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
def test_counter_concurrent_increments():
    reg = MetricsRegistry()
    c = reg.counter("t_work_total", "work", labels=("worker",))
    n_threads, per_thread = 8, 2000

    def work(i):
        child = c.labels(worker=str(i % 3))
        for _ in range(per_thread):
            child.inc()

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    samples = reg.snapshot()["t_work_total"]["samples"]
    assert sum(s["value"] for s in samples) == n_threads * per_thread
    assert {s["labels"]["worker"] for s in samples} == {"0", "1", "2"}


def test_registry_get_or_create_and_type_clash():
    reg = MetricsRegistry()
    assert reg.counter("x_total") is reg.counter("x_total")
    with pytest.raises(ValueError):
        reg.gauge("x_total")
    g = reg.gauge("g")
    g.set(4.0)
    g.inc(1.5)
    assert reg.get("g").value == 5.5
    assert reg.get("missing") is None


def test_gauge_collector_runs_at_snapshot():
    reg = MetricsRegistry()
    calls = []

    def collect(r):
        calls.append(1)
        r.gauge("scrape_time_g").set(len(calls))

    reg.register_collector(collect)
    reg.register_collector(collect)  # dedup
    snap = reg.snapshot()
    assert len(calls) == 1
    assert snap["scrape_time_g"]["samples"][0]["value"] == 1


def test_histogram_buckets_and_percentiles():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "x", buckets=(0.01, 0.1, 1.0))
    for v in [0.005] * 10 + [0.05] * 10 + [0.5] * 10:
        h.observe(v)
    s = reg.snapshot()["lat_seconds"]["samples"][0]
    assert s["count"] == 30
    assert s["sum"] == pytest.approx(0.05 * 10 + 0.5 * 10 + 0.005 * 10)
    assert s["buckets"] == {"0.01": 10, "0.1": 20, "1.0": 30, "+Inf": 30}
    assert 0.005 <= s["p50"] <= 0.5
    assert s["p99"] == 0.5
    assert s["max"] == 0.5


def test_histogram_boundary_lands_in_le_bucket():
    reg = MetricsRegistry()
    h = reg.histogram("b_seconds", buckets=(1.0, 2.0))
    h.observe(1.0)  # le="1.0" means <= 1.0
    h.observe(3.0)  # past the ladder → +Inf only
    s = reg.snapshot()["b_seconds"]["samples"][0]
    assert s["buckets"] == {"1.0": 1, "2.0": 1, "+Inf": 2}


def test_empty_latency_histogram_percentile_is_none():
    from deeplearning4j_tpu.nn.listeners import LatencyHistogram
    lh = LatencyHistogram()
    assert lh.percentile(0.5) is None
    snap = lh.snapshot()
    assert snap["count"] == 0
    assert snap["p50_ms"] is None and snap["p99_ms"] is None
    assert snap["mean_ms"] is None and snap["max_ms"] is None
    lh.record(0.25)
    assert lh.percentile(0.5) == 0.25
    assert lh.snapshot()["p95_ms"] == 250.0


def test_empty_serving_metrics_snapshot_tolerated():
    from deeplearning4j_tpu.server.batcher import ServingMetrics
    s = ServingMetrics("empty-model").snapshot()
    assert s["requests"] == 0
    assert s["total_ms"]["p50_ms"] is None  # no index error, no fake 0.0
    json.dumps(s)  # and it still serializes for the stats RPC


# ---------------------------------------------------------------------------
# Exposition
# ---------------------------------------------------------------------------
def _sample_map(fam):
    return {(name, tuple(sorted(labels.items()))): v
            for name, labels, v in fam["samples"]}


def test_prometheus_round_trip():
    reg = MetricsRegistry()
    reg.counter("rt_total", "a counter", labels=("k",)).labels(k="x").inc(3)
    reg.counter("rt_total", labels=("k",)).labels(k='we"ird\nlabel').inc()
    reg.gauge("rt_gauge", "a gauge").set(2.5)
    h = reg.histogram("rt_seconds", "a histogram", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = exposition.render_prometheus(reg.snapshot())
    fams = exposition.parse_prometheus(text)

    assert fams["rt_total"]["type"] == "counter"
    m = _sample_map(fams["rt_total"])
    assert m[("rt_total", (("k", "x"),))] == 3
    assert m[("rt_total", (("k", 'we"ird\nlabel'),))] == 1

    assert _sample_map(fams["rt_gauge"])[("rt_gauge", ())] == 2.5

    hm = _sample_map(fams["rt_seconds"])
    assert hm[("rt_seconds_bucket", (("le", "0.1"),))] == 1
    assert hm[("rt_seconds_bucket", (("le", "+Inf"),))] == 2
    assert hm[("rt_seconds_count", ())] == 2
    assert hm[("rt_seconds_sum", ())] == pytest.approx(0.55)
    # reservoir percentiles exposed as the sibling _quantile gauge family
    qm = _sample_map(fams["rt_seconds_quantile"])
    assert qm[("rt_seconds_quantile", (("quantile", "0.5"),))] in (0.05, 0.5)


def test_parse_prometheus_rejects_garbage():
    with pytest.raises(ValueError):
        exposition.parse_prometheus("# TYPE x counter\nnot a sample line !")
    with pytest.raises(ValueError):
        exposition.parse_prometheus("orphan_metric 1\n")


def test_render_json_is_valid_json():
    reg = MetricsRegistry()
    reg.counter("j_total").inc()
    parsed = json.loads(exposition.render_json(reg.snapshot()))
    assert parsed["j_total"]["samples"][0]["value"] == 1


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------
def test_span_nesting_and_timing():
    reg = MetricsRegistry()
    assert tracing.current() is None
    with tracing.span("outer", registry=reg) as s_out:
        assert tracing.current() is s_out
        with tracing.span("outer", phase="inner", registry=reg) as s_in:
            assert tracing.current() is s_in
            assert s_in.parent is s_out
            time.sleep(0.01)
        assert tracing.current() is s_out
    assert tracing.current() is None
    assert s_in.duration >= 0.01
    assert s_out.duration >= s_in.duration
    samples = reg.snapshot()[tracing.PHASE_METRIC]["samples"]
    by_phase = {s["labels"]["phase"]: s for s in samples
                if s["labels"]["span"] == "outer"}
    assert by_phase["inner"]["count"] == 1
    assert by_phase[""]["sum"] >= by_phase["inner"]["sum"]


def test_span_records_on_exception_and_disabled():
    reg = MetricsRegistry()
    with pytest.raises(RuntimeError):
        with tracing.span("boom", registry=reg):
            raise RuntimeError("x")
    assert tracing.current() is None
    assert reg.snapshot()[tracing.PHASE_METRIC]["samples"][0]["count"] == 1

    tracing.set_enabled(False)
    try:
        with tracing.span("off", registry=reg) as s:
            pass
        assert s.duration is None  # no timing, no registry write
    finally:
        tracing.set_enabled(None)
    phases = {p["labels"]["span"]
              for p in reg.snapshot()[tracing.PHASE_METRIC]["samples"]}
    assert "off" not in phases


# ---------------------------------------------------------------------------
# Integration: fit + concurrent predict burst → one scrape sees it all
# ---------------------------------------------------------------------------
F, C = 6, 3


def _mlp_model(tmp_path):
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.serialization import write_model
    conf = (NeuralNetConfiguration.builder().seed(5).learning_rate(0.1)
            .updater("adam").shape_bucketing(True).list()
            .layer(L.DenseLayer(n_in=F, n_out=12, activation="relu"))
            .layer(L.OutputLayer(n_in=12, n_out=C, activation="softmax",
                                 loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(16, F)).astype(np.float32)
    y = np.eye(C, dtype=np.float32)[rng.integers(0, C, 16)]
    net.fit(x, y)
    net.fit(x, y)
    path = str(tmp_path / "m.zip")
    write_model(net, path)
    return path


def test_fit_predict_metrics_rpc_scrape(tmp_path):
    from deeplearning4j_tpu.server.gateway import DeepLearning4jEntryPoint
    path = _mlp_model(tmp_path)
    ep = DeepLearning4jEntryPoint(max_batch=16, max_wait_ms=2.0)
    try:
        rng = np.random.default_rng(1)

        def client():
            for _ in range(10):
                ep.predict(path, features=rng.normal(
                    size=(1, F)).astype(np.float32))

        threads = [threading.Thread(target=client) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        m = ep.metrics()
        assert m["content_type"].startswith("text/plain; version=0.0.4")
        fams = exposition.parse_prometheus(m["body"])

        # retrace counts (CompileTelemetry mirror)
        retraces = _sample_map(fams["dl4j_compile_retraces_total"])
        assert sum(retraces.values()) >= 1
        assert any(k == "output" for (_, lbls) in retraces
                   for (_, k) in lbls)
        # per-phase step timings from the fit loop
        phase_counts = {
            lbls: v for (name, lbls), v
            in _sample_map(fams["dl4j_phase_seconds"]).items()
            if name == "dl4j_phase_seconds_count"}
        fit_phases = {dict(lbls)["phase"] for lbls in phase_counts
                      if dict(lbls).get("span") == "fit/step"}
        assert {"jit_call", "block_until_ready", "h2d"} <= fit_phases
        # batcher latency percentiles (quantile gauge family)
        q = _sample_map(fams["dl4j_serving_total_seconds_quantile"])
        assert any(dict(lbls).get("quantile") == "0.95" and v > 0
                   for (_, lbls), v in q.items())
        # cache hit/miss counters
        hits = _sample_map(fams["dl4j_model_cache_hits_total"])
        assert sum(hits.values()) >= 1
        assert sum(_sample_map(
            fams["dl4j_model_cache_misses_total"]).values()) >= 1
        # serving request counters carry the model label
        reqs = _sample_map(fams["dl4j_serving_requests_total"])
        assert any(v >= 40 for v in reqs.values())

        # JSON format returns the raw snapshot
        snap = ep.metrics(format="json")
        assert "dl4j_serving_total_seconds" in snap
        json.dumps(snap)
        with pytest.raises(ValueError):
            ep.metrics(format="xml")

        # stats RPC merges cache + batcher + registry (back-compat keys)
        st = ep.stats()
        assert {"model_cache", "serving", "registry"} <= set(st)
        serving = next(iter(st["serving"].values()))
        assert {"p50_ms", "p95_ms", "p99_ms"} <= set(serving["total_ms"])
    finally:
        ep.close()


def test_http_get_metrics_scrape(tmp_path):
    from deeplearning4j_tpu.server.gateway import Server
    srv = Server().start()
    try:
        url = f"http://{srv.host}:{srv.port}/metrics"
        with urllib.request.urlopen(url, timeout=10) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
        fams = exposition.parse_prometheus(text)
        assert "dl4j_gateway_requests_total" in fams
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://{srv.host}:{srv.port}/nope", timeout=10)
    finally:
        srv.stop()


def test_stats_listener_perf_memory_from_registry():
    """UI reports and /metrics agree: StatsListener's perf/memory come
    from the registry gauges the fit loop set, not a private re-measure."""
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.ui.stats_listener import StatsListener
    from deeplearning4j_tpu.ui.stats_storage import InMemoryStatsStorage

    st = InMemoryStatsStorage()
    conf = (NeuralNetConfiguration.builder().seed(7).learning_rate(0.1)
            .updater("sgd").list()
            .layer(L.DenseLayer(n_in=4, n_out=8, activation="relu"))
            .layer(L.OutputLayer(n_in=8, n_out=3, activation="softmax",
                                 loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    net.set_listeners(StatsListener(st, session_id="mon-sess"))
    rng = np.random.default_rng(2)
    x = rng.normal(size=(8, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)]
    net.fit(x, y)
    net.fit(x, y)

    sid = "mon-sess"
    wid = st.list_worker_ids_for_session(sid)[0]
    upd = st.get_latest_update(sid, "StatsListener", wid)
    reg = monitor.get_registry()
    perf = upd["perf"]
    assert perf["duration_ms"] == reg.get("dl4j_fit_last_step_ms").value
    assert perf["samples_per_sec"] == \
        reg.get("dl4j_fit_examples_per_sec").value
    assert "host_rss_mb" in upd["memory"]
    # and the same gauge is visible in a scrape
    snap = reg.snapshot()
    assert snap["dl4j_host_rss_mb"]["samples"][0]["value"] > 0


# ---------------------------------------------------------------------------
# No dark time in fit(): tiling phases, compile stages, scope names, profile
# ---------------------------------------------------------------------------
OLD_PHASES = {"data_wait", "bucket", "h2d", "jit_call",
              "block_until_ready", "listeners"}   # shard_h2d: sharded fits
NEW_PHASES = {"epoch", "has_next", "dispatch_prep", "score_fetch",
              "bookkeeping", "publish", "glue"}
TAIL_PHASES = {"score_fetch", "bookkeeping", "publish"}
FEATS, HIDDEN, CLASSES, ROWS = 6, 12, 3, 16


def _small_mln(seed=5):
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    conf = (NeuralNetConfiguration.builder().seed(seed).learning_rate(0.05)
            .updater("adam").list()
            .layer(L.DenseLayer(n_in=FEATS, n_out=HIDDEN, activation="relu"))
            .layer(L.BatchNormalization())
            .layer(L.OutputLayer(n_in=HIDDEN, n_out=CLASSES,
                                 activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _small_cg(seed=7):
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.conf.graph_conf import GraphBuilder
    from deeplearning4j_tpu.nn.conf.network import GlobalConf
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    conf = (GraphBuilder(GlobalConf(seed=seed, learning_rate=0.05,
                                    updater="adam"))
            .add_inputs("in")
            .add_layer("d", L.DenseLayer(n_in=FEATS, n_out=HIDDEN,
                                         activation="relu"), "in")
            .add_layer("bn", L.BatchNormalization(), "d")
            .add_layer("out", L.OutputLayer(n_in=HIDDEN, n_out=CLASSES,
                                            activation="softmax",
                                            loss="mcxent"), "bn")
            .set_outputs("out").build())
    return ComputationGraph(conf).init()


ENGINES = {"mln": _small_mln, "cg": _small_cg}


def _step_args(net):
    """Arguments of the engine's raw train step, for lowering it."""
    import jax
    import jax.numpy as jnp
    x, y = jnp.zeros((ROWS, FEATS)), jnp.zeros((ROWS, CLASSES))
    if isinstance(net.net_params, dict):     # graph engine: tuples of heads
        x, y = (x,), (y,)
    return (net.net_params, net.net_state, net.opt_states, x, y, None, None,
            jnp.int32(0), jax.random.PRNGKey(0))


def _batches(n, seed=0):
    from deeplearning4j_tpu.datasets.dataset import DataSet
    rng = np.random.default_rng(seed)
    return [DataSet(rng.normal(size=(ROWS, FEATS)).astype(np.float32),
                    np.eye(CLASSES, dtype=np.float32)[
                        rng.integers(0, CLASSES, ROWS)])
            for _ in range(n)]


def _async_iterator(n):
    from deeplearning4j_tpu.datasets.iterators import (
        AsyncDataSetIterator, ListDataSetIterator)
    return AsyncDataSetIterator(ListDataSetIterator(_batches(n)),
                                device_put=True)


def _phase_totals(span):
    fam = monitor.get_registry().snapshot().get(tracing.PHASE_METRIC, {})
    return {s["labels"]["phase"]: (s["sum"], s["count"])
            for s in fam.get("samples", [])
            if s["labels"]["span"] == span}


def _delta(after, before):
    return {k: (s - before.get(k, (0.0, 0))[0], c - before.get(k, (0.0, 0))[1])
            for k, (s, c) in after.items()}


class _Seen:
    def __init__(self):
        self.iterations = []

    def iteration_done(self, model, iteration):
        self.iterations.append(iteration)


def _events_since(seq, etype):
    return [e for e in monitor.get_journal().tail(etype=etype)
            if e["seq"] > seq]


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_fit_step_phases_tile_the_loop(engine):
    net = ENGINES[engine]()
    seen = _Seen()
    net.set_listeners(seen)
    n = 24
    net.fit(_async_iterator(n))          # compiles; the second is steady
    step0, pipe0 = _phase_totals("fit/step"), _phase_totals("pipeline/batch")
    seq0 = monitor.get_journal().total_emitted
    net.fit(_async_iterator(n))
    step = _delta(_phase_totals("fit/step"), step0)
    pipe = _delta(_phase_totals("pipeline/batch"), pipe0)
    assert len(seen.iterations) == 2 * n
    present = {p for p, (_, c) in step.items() if c}
    assert OLD_PHASES | NEW_PHASES <= present
    for phase in OLD_PHASES | TAIL_PHASES:
        assert step[phase][1] == n, phase
    # the graph engine also asks, a step, whether its step function stands
    assert step["dispatch_prep"][1] == (2 * n if engine == "cg" else n)
    assert step["has_next"][1] == n + 1
    # fit.start follows the loop's first clock reading and fit.end its
    # last phase: between them there is no time in no phase
    (start,) = _events_since(seq0, "fit.start")
    (end,) = _events_since(seq0, "fit.end")
    loop_s = end["ts"] - start["ts"]
    timed_s = sum(s for s, _ in step.values())
    assert abs(timed_s - loop_s) <= 0.02 * loop_s, (timed_s, loop_s)
    # the worker's stages, timed where they run
    assert pipe["transform"][1] == n and pipe["h2d"][1] == n
    assert pipe["h2d"][0] > 0


def test_step_spans_name_the_time_between_phases():
    reg = MetricsRegistry()

    def sums():
        return {x["labels"]["phase"]: (x["sum"], x["count"])
                for x in reg.snapshot()[tracing.PHASE_METRIC]["samples"]}

    t0 = time.perf_counter()
    steps = tracing.StepSpans(registry=reg)
    with steps.span("loop", phase="a"):
        time.sleep(0.005)
    time.sleep(0.01)                      # in no `with`: the loop's glue
    with steps.span("loop", phase="b") as s:
        assert tracing.current() is s and s.name == "loop"
        t1 = time.perf_counter()
    assert tracing.current() is None
    by = sums()
    # a phase is its body alone, as a plain span's is
    assert 0.005 <= by["a"][0] < 0.009 and by["b"][0] < 0.002
    assert by["glue"][1] == 2 and by["glue"][0] >= 0.01
    # and with the glue the phases tile the loop
    total = sum(v for v, _ in by.values())
    assert t1 - t0 - 0.001 < total <= time.perf_counter() - t0
    # code that times itself is left out between close() and restart()
    steps.close()
    time.sleep(0.01)
    steps.restart()
    with steps.span("loop", phase="c"):
        pass
    assert sums()["glue"][0] - by["glue"][0] < 0.005
    # DL4J_SPANS is read once, when the loop takes its helper
    tracing.set_enabled(False)
    try:
        off = tracing.StepSpans(registry=reg)
    finally:
        tracing.set_enabled(None)
    with off.span("loop", phase="d") as s:
        assert s is None
    assert "d" not in sums()


def test_step_spans_annotations_tile_too(monkeypatch):
    """With annotations on, a region <span>/glue closes as the next
    phase's opens: the trace has no hole either."""
    log = []

    class FakeAnnotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("open", self.name))

        def __exit__(self, *exc):
            log.append(("close", self.name))

    import jax
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    steps = tracing.StepSpans(annotate=True, registry=MetricsRegistry())
    with steps.span("fit/step", phase="has_next"):
        pass
    with steps.span("fit/step", phase="data_wait"):
        pass
    steps.close()
    steps.close()                                   # idempotent
    assert log == [
        ("open", "fit/step/has_next"), ("close", "fit/step/has_next"),
        ("open", "fit/step/glue"), ("close", "fit/step/glue"),
        ("open", "fit/step/data_wait"), ("close", "fit/step/data_wait"),
        ("open", "fit/step/glue"), ("close", "fit/step/glue")]
    del log[:]
    quiet = tracing.StepSpans(annotate=False, registry=MetricsRegistry())
    with quiet.span("fit/step", phase="has_next"):
        pass
    quiet.close()
    assert log == []


def _compile_seconds():
    fam = monitor.get_registry().snapshot().get("dl4j_compile_seconds", {})
    return {(s["labels"]["stage"], s["labels"]["span"]): s["count"]
            for s in fam.get("samples", [])}


@pytest.mark.parametrize("engine,fun", [("mln", "mln_train_step"),
                                        ("cg", "cg_train_step")])
def test_compile_stages_are_charged_to_the_step_that_paid(engine, fun):
    import jax
    import jax.numpy as jnp
    net = ENGINES[engine](seed=11)
    before = _compile_seconds()
    seq0 = monitor.get_journal().total_emitted
    net.fit(_async_iterator(4))
    paid = [e for e in _events_since(seq0, "compile.stage")
            if e["span"] == "fit/step" and fun in e["fun_name"]]
    assert {e["stage"] for e in paid} >= {"trace", "lower"}
    assert {e["stage"] for e in paid} & {"backend_compile", "cache_load"}
    assert all(e["iteration"] == 0 and e["fit_id"] and e["seconds"] >= 0
               for e in paid)
    # once per outermost call: the step's own trace, not its nested jits'
    assert sum(e["stage"] == "trace" for e in paid) == 1
    after = _compile_seconds()
    for stage in ("trace", "lower"):
        assert after[(stage, "fit/step")] > before.get((stage, "fit/step"), 0)
    # the same shapes again: nothing compiles, so nothing is recorded
    seq1 = monitor.get_journal().total_emitted
    net.fit(_async_iterator(4))
    assert [e for e in _events_since(seq1, "compile.stage")
            if e["span"] == "fit/step"] == []
    assert {k: v for k, v in _compile_seconds().items()
            if k[1] == "fit/step"} == \
        {k: v for k, v in after.items() if k[1] == "fit/step"}
    # a compile in no program span is nobody's
    seq2 = monitor.get_journal().total_emitted
    assert tracing.current() is None

    salt = jnp.float32(len(fun))         # a new program in each case

    def nobodys_function(x):
        return jnp.tanh(x) * 3.0 + salt

    nobodys = jax.jit(nobodys_function)
    nobodys(jnp.ones((3, 5))).block_until_ready()
    mine = [e for e in _events_since(seq2, "compile.stage")
            if "nobodys_function" in e["fun_name"]]
    assert mine and all(e["span"] == "" for e in mine)
    assert "fit_id" not in mine[0] and "iteration" not in mine[0]
    assert _compile_seconds()[("trace", "")] >= 1


def test_net_init_and_kernel_self_test_have_spans():
    before_init = _phase_totals("net/init")
    _small_mln(seed=13)
    _small_cg(seed=13)
    init = _delta(_phase_totals("net/init"), before_init)
    assert init["default_weights"][1] == 2 and init["given_weights"][1] == 2
    assert init["default_weights"][0] > 0
    before = _phase_totals("fit/setup")
    _small_mln(seed=13).fit(_async_iterator(1))
    setup = _delta(_phase_totals("fit/setup"), before)
    assert setup["kernel_self_test"][1] == 1


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_train_step_carries_scope_names(engine):
    import jax
    net = ENGINES[engine]()
    text = jax.jit(net._build_step_raw()).lower(
        *_step_args(net)).as_text(debug_info=True)
    bn = "1" if engine == "mln" else "bn"
    for scope in (f"jvp(fwd/BatchNormalization/{bn})",
                  f"transpose(jvp(fwd/BatchNormalization/{bn}))",
                  "fwd/DenseLayer/", "fwd/OutputLayer/",
                  "jvp(loss)/", f"jit({engine}_train_step)/update/"):
        assert scope in text, scope


def _instructions(hlo_text):
    """The instruction and computation lines of an HLO module's text,
    without their metadata (the header's file and line tables differ
    with the caller's own lines)."""
    return [re.sub(r",? ?metadata=\{[^}]*\}", "", line)
            for line in hlo_text.splitlines()
            if " = " in line or line.rstrip().endswith("{")]


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_scope_names_change_metadata_only(engine, monkeypatch):
    import contextlib

    import jax

    def compiled():
        net = ENGINES[engine]()
        low = jax.jit(net._build_step_raw()).lower(*_step_args(net))
        return low.as_text(debug_info=True), low.compile().as_text()

    lowered, named = compiled()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    lowered_bare, bare = compiled()
    assert "fwd/BatchNormalization/" in lowered
    assert "fwd/BatchNormalization/" not in lowered_bare
    assert named != bare                       # the names reached the HLO
    assert len(_instructions(named)) > 100
    assert _instructions(named) == _instructions(bare)


def _tier_lowerings():
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    def flash(qkv, km):      # one argument, so that dk and dv are asked for
        return pk.flash_attention(*qkv, km, causal=True).sum()

    def xent(lg, lb):
        return pk.softmax_xent_rows(lg, lb).mean()

    def lstm(zx, h, c, rw, p3):
        c_new, h_new = pk.fused_lstm_step(zx, h, c, rw, p3)
        return c_new.sum() + h_new.sum()

    def dropout(x, key):
        return pk.fused_threshold_dropout(x, 0.8, key).sum()

    return {
        "flash": (flash, ((f32(1, 2, 256, 64),) * 3, f32(1, 256)),
                  ("dl4j_flash_fwd", "dl4j_flash_bwd")),
        "xent": (xent, (f32(256, 512), f32(256, 512)),
                 ("dl4j_softmax_xent",)),
        "lstm": (lstm, (f32(4, 64), f32(4, 16), f32(4, 16), f32(16, 64),
                        f32(3, 16)), ("dl4j_lstm_step",)),
        "dropout": (dropout, (f32(64, 128),
                              jax.ShapeDtypeStruct((2,), jnp.uint32)),
                    ("dl4j_dropout",)),
    }


@pytest.mark.parametrize("tier", ["flash", "xent", "lstm", "dropout"])
def test_pallas_tier_lowering_carries_its_kernel_name(tier):
    import jax
    fn, args, names = _tier_lowerings()[tier]
    text = jax.jit(jax.value_and_grad(fn)).lower(*args).as_text(
        debug_info=True)
    for name in names:      # [transpose(]jvp(<name>)[)]/pallas_call
        assert re.search(rf"\(?{name}\)*/pallas_call", text), name


def test_profile_shares_a_gap_over_the_phases_it_overlaps():
    from deeplearning4j_tpu.monitor import profile
    phases = profile.disjoint([(0.0, 10.0, "block_until_ready"),
                               (10.0, 14.0, "bookkeeping"),
                               (14.0, 30.0, "dispatch_prep"),
                               (40.0, 50.0, "jit_call")])
    # one gap that begins in the wait and ends in the preparation: it is
    # not the wait's alone
    shared = profile.share_gap((8.0, 20.0), phases)
    assert shared == {"block_until_ready": 2.0, "bookkeeping": 4.0,
                      "dispatch_prep": 6.0}
    assert sum(shared.values()) == 12.0
    # what no phase covers is outside the fit, whole or in part
    assert profile.share_gap((31.0, 39.0), phases) == {"outside_fit": 8.0}
    assert profile.share_gap((25.0, 45.0), phases) == {
        "dispatch_prep": 5.0, "jit_call": 5.0, "outside_fit": 10.0}
    assert profile.share_gap((60.0, 61.0), phases) == {"outside_fit": 1.0}
    assert profile.share_gap((3.0, 4.0), []) == {"outside_fit": 1.0}
    # a nested span of another path counts no nanosecond twice
    assert profile.disjoint([(0.0, 10.0, "a"), (2.0, 4.0, "inner"),
                             (8.0, 12.0, "b")]) == \
        [(0.0, 10.0, "a"), (10.0, 12.0, "b")]
    busy, gaps = profile.union([(0.0, 2.0), (1.0, 3.0), (5.0, 6.0)])
    assert busy == 4.0 and gaps == [(3.0, 5.0)]


@pytest.mark.parametrize("op_name,expected", [
    ("jit(cg_train_step)/jvp(fwd/BatchNormalization/bn2a)/reduce_sum:",
     ("fwd", "BatchNormalization", "fwd/BatchNormalization/bn2a")),
    ("jit(cg_train_step)/transpose(jvp(fwd/ConvolutionLayer/res2a))/conv",
     ("bwd", "ConvolutionLayer", "bwd/ConvolutionLayer/res2a")),
    ("jit(mln_train_step)/update/sub", ("update", "update", "update")),
    ("jit(mln_train_step)/transpose(jvp(loss))/mul", ("loss", "loss", "loss")),
    ("jit(mln_train_step)/jvp()/convert_element_type:",
     ("unscoped", "unscoped", "unscoped")),
    ("", ("unscoped", "unscoped", "unscoped")),
])
def test_profile_classifies_an_op_name(op_name, expected):
    from deeplearning4j_tpu.monitor import profile
    assert profile.classify(op_name) == expected


def test_profile_summarizes_hand_made_planes():
    from deeplearning4j_tpu.monitor import profile
    fwd = "jit(mln_train_step)/jvp(fwd/DenseLayer/0)/dot_general:"
    bwd = "jit(mln_train_step)/transpose(jvp(fwd/DenseLayer/0))/dot_general:"
    planes = [
        ("/device:TPU:0", [
            ("XLA Modules", [(0.0, 35.0, "jit_mln_train_step(1)", "")]),
            ("XLA Ops", [(0.0, 10.0, "%fusion.1 = ...", fwd),
                         (20.0, 10.0, "%fusion.2 = ...", bwd),
                         (30.0, 5.0, "%copy.3 = ...", "")])]),
        ("/host:CPU", [("python3", [
            (0.0, 12.0, "fit/step/block_until_ready", ""),
            (12.0, 4.0, "fit/step/glue", ""),
            (16.0, 30.0, "fit/step/dispatch_prep", ""),
            (5.0, 1.0, "PjitFunction(f)", "")])]),
    ]
    summary = profile.summarize(planes)
    chip = summary["chips"]["0"]
    ns = 1e-9
    assert chip["busy_s"] == pytest.approx(25 * ns)
    assert chip["window_s"] == pytest.approx(35 * ns)
    assert chip["device_s"] == {
        "fwd": {"DenseLayer": pytest.approx(10 * ns)},
        "bwd": {"DenseLayer": pytest.approx(10 * ns)},
        "unscoped": {"unscoped": pytest.approx(5 * ns)}}
    assert chip["scoped_share"] == pytest.approx(0.8)
    assert [k for k, _ in chip["top_scopes"]] == [
        "fwd/DenseLayer/0", "bwd/DenseLayer/0", "unscoped"]
    # the one gap, 10 to 20, lies over three phases
    assert chip["idle_s"] == {
        "dispatch_prep": pytest.approx(4 * ns), "glue": pytest.approx(4 * ns),
        "block_until_ready": pytest.approx(2 * ns)}
    assert sum(chip["idle_s"].values()) == pytest.approx(
        chip["window_s"] - chip["busy_s"])
    # the wait returned 2 ns after the last operation that ended in it
    assert chip["wait_lag_s"] == pytest.approx(2 * ns)
    assert summary["host_s"]["glue"] == [pytest.approx(4 * ns), 1]


def _pb(field, value):
    """One protobuf field: a varint for an int, else length-delimited."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    if isinstance(value, int):
        return varint(field << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(field << 3 | 2) + varint(len(value)) + value


def test_profile_reads_an_xplane_file_by_hand(tmp_path):
    """The reader against a hand-encoded XSpace: an operation's op_name
    is the tf_op stat of its event's metadata, as a string or as a
    reference to a stat's name; times are the line's start plus the
    event's offset, in picoseconds."""
    from deeplearning4j_tpu.monitor import profile
    op_name = "jit(cg_train_step)/jvp(fwd/BatchNormalization/bn)/mul:"

    def stat_meta(i, name):
        return _pb(5, _pb(1, i) + _pb(2, _pb(1, i) + _pb(2, name)))

    def event_meta(i, name, *stats):
        return _pb(4, _pb(1, i) + _pb(2, _pb(1, i) + _pb(2, name)
                                       + b"".join(_pb(5, st) for st in stats)))

    line = (_pb(2, "XLA Ops") + _pb(3, 1000)
            + _pb(4, _pb(1, 7) + _pb(2, 5_000_000) + _pb(3, 2_000_000))
            + _pb(4, _pb(1, 8) + _pb(2, 9_000_000) + _pb(3, 1_000_000))
            + _pb(4, _pb(1, 9) + _pb(3, 500_000))
            + _pb(4, _pb(1, 10) + _pb(2, 12_000_000) + _pb(3, 250_000)))
    plane = (_pb(2, "/device:TPU:0") + _pb(3, line)
             + stat_meta(1, "flops") + stat_meta(2, "tf_op")
             + stat_meta(3, op_name)
             + event_meta(7, "%fusion.7 = ...", _pb(1, 1) + _pb(3, 64),
                          _pb(1, 2) + _pb(5, op_name))
             + event_meta(8, "%fusion.8 = ...", _pb(1, 2) + _pb(7, 3))
             + event_meta(9, "%copy.9 = ...")
             + event_meta(10, "%ragged-dot-none.2 = bf16[8,4]{1,0} custom-call(...)",
                          _pb(1, 2) + _pb(5, "ragged-dot-none")))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb(1, plane) + _pb(1, _pb(2, "/host:CPU")))
    planes = profile.load(str(tmp_path))
    assert [name for name, _ in planes] == ["/device:TPU:0", "/host:CPU"]
    assert planes[0][1] == [("XLA Ops", [
        (6000.0, 2000.0, "%fusion.7 = ...", op_name),
        (10000.0, 1000.0, "%fusion.8 = ...", op_name),
        (1000.0, 500.0, "%copy.9 = ...", ""),
        (13000.0, 250.0,
         "%ragged-dot-none.2 = bf16[8,4]{1,0} custom-call(...)",
         "ragged-dot-none")])]
    # a kernel the compiler named itself goes by its instruction's name,
    # which gives it back to the layer that emits it
    assert profile.device_events(planes) == {0: [
        (6000.0, 2000.0, op_name), (10000.0, 1000.0, op_name),
        (1000.0, 500.0, ""), (13000.0, 250.0, "ragged-dot-none.2")]}
    chip = profile.summarize(planes)["chips"]["0"]
    assert chip["device_s"]["kernel"] == {
        "MixtureOfExpertsLayer": pytest.approx(250e-9)}
    assert chip["sub_scope_s"] == {
        "kernel/MixtureOfExpertsLayer/experts": pytest.approx(250e-9)}
    with pytest.raises(FileNotFoundError):
        profile.load(str(tmp_path / "nothing_here"))


def test_dl4j_profile_writes_a_summary(tmp_path, monkeypatch):
    from deeplearning4j_tpu.monitor import profile
    monkeypatch.setenv("DL4J_PROFILE", str(tmp_path))
    net = _small_mln(seed=17)
    net.fit(_async_iterator(3))
    with open(tmp_path / "fit0" / "summary.json") as f:
        summary = json.load(f)
    # the annotations were on for that fit, without the variable
    assert summary["host_s"]["jit_call"][1] == 3
    assert summary["host_s"]["block_until_ready"][0] > 0
    assert summary["chips"] == {}            # no device plane on the CPU
    # the same from the command, on the trace directory
    assert profile.summarize(profile.load(str(tmp_path / "fit0"))) == summary
    assert profile.main([]) == 2
