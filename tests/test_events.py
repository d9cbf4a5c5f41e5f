"""Request-scoped tracing, the structured event journal, and the
flight recorder (monitor/events.py, monitor/flight.py): journal ring /
kill-switch semantics, contextvars scope propagation, span→event
integration, Chrome trace export shape, the gateway E2E pin (ONE
request ID joins admission → batcher queue → coalesced compute →
response in both the journal and the Chrome export), decode step
events with session/slot/tenant, crash-handler dumps (dead batcher,
readyz flip), breaker/fault/checkpoint events, and the two tier-1
subprocess smokes (fault-kill dump with
the failing request's ID; Perfetto-parseable /trace export)."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.monitor import events, flight
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.serialization import write_model
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.server import DeepLearning4jEntryPoint, Server
from deeplearning4j_tpu.server.batcher import MicroBatcher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

F, C = 6, 3


def _write_mlp(path, seed=3):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).learning_rate(0.1).updater("adam")
            .shape_bucketing(True)
            .list()
            .layer(L.DenseLayer(n_in=F, n_out=12, activation="relu"))
            .layer(L.OutputLayer(n_in=12, n_out=C, activation="softmax",
                                 loss="mcxent"))
            .build())
    write_model(MultiLayerNetwork(conf).init(), str(path))
    return str(path)


def _post(url, obj):
    import urllib.error
    import urllib.request
    req = urllib.request.Request(
        url, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _get(url):
    import urllib.error
    import urllib.request
    try:
        with urllib.request.urlopen(url, timeout=30) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


@pytest.fixture(autouse=True)
def _flight_tmp(tmp_path, monkeypatch):
    """Every test gets its own flight dir and no rate limiting, so
    dumps from one test can't hide another's."""
    monkeypatch.setenv("DL4J_FLIGHT_DIR", str(tmp_path / "flight"))
    monkeypatch.setenv("DL4J_FLIGHT_MIN_INTERVAL_S", "0")
    yield
    # monkeypatch restores the env on teardown, but the journal caches
    # its parsed env — resync so no test leaks verbose/kill-switch state
    events.set_enabled(None)


# ---------------------------------------------------------------------------
# Journal basics
# ---------------------------------------------------------------------------
def test_journal_ring_bound_seq_and_filters():
    j = events.EventJournal(capacity=16)
    for i in range(40):
        j.emit("request.done", request_id=f"r{i}",
               severity="warn" if i % 2 else "info")
    tail = j.tail()
    assert len(tail) == 16                      # ring bound
    assert j.total_emitted == 40
    assert j.dropped == 24
    seqs = [e["seq"] for e in tail]
    assert seqs == sorted(seqs)                 # oldest-first
    assert seqs[-1] == 40
    assert j.tail(n=3)[0]["seq"] == 38
    assert [e["request_id"] for e in j.tail(request_id="r39")] == ["r39"]
    assert all(e["severity"] == "warn"
               for e in j.tail(severity="warn"))


def test_journal_kill_switch_is_noop_not_queued(monkeypatch):
    j = events.EventJournal(capacity=16)
    events.set_enabled(False)
    try:
        assert j.emit("request.done") is None
        assert j.total_emitted == 0             # not queued anywhere
    finally:
        events.set_enabled(None)
    # env form: DL4J_JOURNAL=0 with no override (the parsed env is
    # cached for the hot path; set_enabled(None) re-reads it)
    monkeypatch.setenv("DL4J_JOURNAL", "0")
    events.set_enabled(None)
    assert not events.enabled()
    assert j.emit("request.done") is None
    monkeypatch.delenv("DL4J_JOURNAL")
    events.set_enabled(None)
    assert events.enabled()
    assert j.emit("request.done").seq == 1


def test_scope_nesting_merge_and_thread_isolation():
    with events.scope(request_id="outer", tenant="t1"):
        assert events.current_context()["request_id"] == "outer"
        with events.scope(request_id="inner", extra=None):
            ctx = events.current_context()
            assert ctx["request_id"] == "inner"     # inner wins
            assert ctx["tenant"] == "t1"            # outer merges
            assert "extra" not in ctx               # None dropped
        assert events.current_context()["request_id"] == "outer"
        seen = {}

        def worker():
            seen["ctx"] = events.current_context()
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        # fresh threads do NOT inherit context — that's why the
        # batcher captures it per pending request
        assert seen["ctx"] == {}
    assert events.current_context() == {}


def test_request_scope_reuses_existing_id():
    with events.request_scope() as rid:
        assert rid
        with events.request_scope(tenant="t2") as rid2:
            assert rid2 == rid                  # continues, not re-mints
            assert events.current_context()["tenant"] == "t2"


def test_span_close_event_carries_context_and_duration(monkeypatch):
    monkeypatch.setenv("DL4J_JOURNAL_VERBOSE", "1")
    events.set_enabled(None)   # refresh the parsed-env cache
    with events.scope(request_id="spanrid42"):
        with monitor.span("test/evspan", phase="work"):
            pass
    tail = events.get_journal().tail(request_id="spanrid42")
    types = [e["type"] for e in tail]
    # span.open is the verbose-only form; span.close is always on
    assert "span.open" in types and "span.close" in types
    monkeypatch.delenv("DL4J_JOURNAL_VERBOSE")
    events.set_enabled(None)
    with events.scope(request_id="spanrid43"):
        with monitor.span("test/evspan", phase="work"):
            pass
    quiet = [e["type"] for e in
             events.get_journal().tail(request_id="spanrid43")]
    assert "span.close" in quiet and "span.open" not in quiet
    close = [e for e in tail if e["type"] == "span.close"][-1]
    assert close["span"] == "test/evspan"
    assert close["phase"] == "work"
    assert close["duration_s"] >= 0.0
    assert close["request_id"] == "spanrid42"


def test_chrome_trace_export_shape():
    with events.scope(request_id="chromerid"):
        with monitor.span("test/chrome", phase="p"):
            time.sleep(0.002)
        events.emit("request.admitted", rows=1)
    evts = events.get_journal().tail(request_id="chromerid")
    trace = events.chrome_trace(evts)
    te = trace["traceEvents"]
    assert all(e["ph"] in ("X", "i", "M") for e in te)
    slices = [e for e in te if e["ph"] == "X"]
    instants = [e for e in te if e["ph"] == "i"]
    assert slices and instants
    x = [s for s in slices if s["name"] == "test/chrome/p"][-1]
    assert x["dur"] >= 2000                     # µs
    assert x["args"]["request_id"] == "chromerid"
    for e in slices + instants:
        assert isinstance(e["ts"], float) and e["ts"] > 0
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in te)
    json.dumps(trace)                           # serializable end-to-end


# ---------------------------------------------------------------------------
# The acceptance pin: one request ID joins every hop
# ---------------------------------------------------------------------------
def test_gateway_request_id_joins_admission_queue_compute_response(tmp_path):
    path = _write_mlp(tmp_path / "m.zip")
    server = Server(DeepLearning4jEntryPoint(), port=0).start()
    base = f"http://{server.host}:{server.port}"
    try:
        code, body, headers = _post(base + "/", {
            "method": "predict",
            "params": {"model_path": path,
                       "features": [[0.1] * F], "tenant": "acme"}})
        assert code == 200
        rid = body["request_id"]
        assert rid and headers.get("X-DL4J-Request-ID") == rid
        tail = events.get_journal().tail(request_id=rid)
        types = [e["type"] for e in tail]
        # gateway admission → batcher queue → coalesced compute →
        # response, all under ONE id
        for expected in ("rpc.request", "request.admitted",
                         "batch.dispatch", "rpc.response"):
            assert expected in types, (expected, types)
        dispatch = [e for e in tail if e["type"] == "batch.dispatch"][-1]
        assert rid in dispatch["request_ids"]   # compute linked to request
        assert [e for e in tail if e["type"] == "rpc.request"][-1][
            "tenant"] == "acme"
        # the compute span itself is linked to the request set
        compute = [e for e in tail if e["type"] == "span.close"
                   and e.get("phase") == "compute"]
        assert compute and rid in compute[-1]["request_ids"]
        # ... and the same id is findable in the Chrome export
        trace = events.chrome_trace(tail)
        hits = [e for e in trace["traceEvents"]
                if e.get("args", {}).get("request_id") == rid
                or rid in (e.get("args", {}).get("request_ids") or ())]
        assert any(e["ph"] == "X" for e in hits)
        assert any(e["ph"] == "i" for e in hits)
    finally:
        server.stop()


def test_trace_endpoint_and_trace_dump_rpc(tmp_path):
    path = _write_mlp(tmp_path / "m.zip")
    server = Server(DeepLearning4jEntryPoint(), port=0).start()
    base = f"http://{server.host}:{server.port}"
    try:
        code, body, _ = _post(base + "/", {
            "method": "predict",
            "params": {"model_path": path, "features": [[0.0] * F]}})
        rid = body["request_id"]
        # events form, filtered to the request
        code, raw = _get(base + f"/trace?request_id={rid}")
        assert code == 200
        got = json.loads(raw)
        assert got["count"] == len(got["events"]) > 0
        assert all(e.get("request_id") == rid
                   or rid in (e.get("request_ids") or ())
                   for e in got["events"])
        # chrome form: the body IS the Perfetto-loadable object
        code, raw = _get(base + "/trace?format=chrome&last_n=50")
        assert code == 200
        trace = json.loads(raw)
        assert {e["ph"] for e in trace["traceEvents"]} <= {"X", "i", "M"}
        # trace_dump RPC with a server-side flight dump
        code, body, _ = _post(base + "/", {
            "method": "trace_dump",
            "params": {"last_n": 10, "dump": True, "reason": "rpc_test"}})
        assert code == 200
        res = body["result"]
        assert len(res["events"]) <= 10
        assert res["path"] and os.path.exists(res["path"])
        with open(res["path"]) as f:
            dumped = json.load(f)
        assert dumped["schema"] == 1 and dumped["reason"] == "rpc_test"
        assert "registry" in dumped and dumped["n_events"] > 0
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# Decode: step events + tenant label parity
# ---------------------------------------------------------------------------
def test_decode_step_events_and_tenant_labels():
    from deeplearning4j_tpu.server.decode import DecodePool
    Fr, H, Cr = 5, 10, 4
    conf = (NeuralNetConfiguration.builder().seed(7).learning_rate(0.05)
            .shape_bucketing(True)
            .list()
            .layer(L.GravesLSTM(n_in=Fr, n_out=H, activation="tanh"))
            .layer(L.RnnOutputLayer(n_in=H, n_out=Cr, activation="softmax",
                                    loss="mcxent"))
            .build())
    model = MultiLayerNetwork(conf).init()
    pool = DecodePool(model, name="evpool", max_slots=4)
    try:
        with events.request_scope(tenant="acme") as rid:
            sid = pool.open_session(tenant="acme")
            x = np.random.default_rng(0).normal(
                size=(3, Fr)).astype(np.float32)
            pool.step(sid, x, timeout=120)
        opened = [e for e in events.get_journal().tail(
            etype="decode.session_opened") if e.get("session_id") == sid]
        assert opened and opened[-1]["tenant"] == "acme"
        steps = [e for e in events.get_journal().tail(etype="decode.step")
                 if e.get("session_id") == sid]
        assert steps, "every decode step must journal a decode.step"
        s = steps[-1]
        # session ID + slot + tenant on every step event, plus the
        # request id captured at enqueue
        assert s["slot"] == opened[-1]["slot"]
        assert s["tenant"] == "acme"
        assert s["request_id"] == rid
        assert s["tokens"] == 3
        pool.close_session(sid)
        closed = [e for e in events.get_journal().tail(
            etype="decode.session_closed") if e.get("session_id") == sid]
        assert closed and closed[-1]["reason"] == "closed"
        # tenant-labeled request-path counters (label parity satellite)
        reg = monitor.get_registry()
        for name in ("dl4j_decode_sessions_opened_total",
                     "dl4j_decode_tokens_total"):
            fam = reg.get(name)
            assert fam.label_names == ("model", "tenant")
            samples = {tuple(s["labels"].items()): s["value"]
                       for s in fam.samples()}
            key = (("model", "evpool"), ("tenant", "acme"))
            assert samples.get(key, 0) > 0, (name, samples)
    finally:
        pool.stop()


# ---------------------------------------------------------------------------
# Crash handlers: dead batcher dump, readyz flip dump
# ---------------------------------------------------------------------------
def test_batcher_kill_writes_dump_with_request_id(tmp_path):
    faults.reset()
    faults.arm({"site": "batcher.compute", "mode": "kill", "on_call": 1})
    try:
        mb = MicroBatcher(lambda x: x, max_wait_ms=1.0, name="killme")
        with events.request_scope() as rid:
            fut = mb.submit(np.ones((2, 3), np.float32))
        with pytest.raises(RuntimeError, match="thread died"):
            fut.result(timeout=30)
        deadline = time.time() + 30
        while mb.thread_alive and time.time() < deadline:
            time.sleep(0.01)
        died = [e for e in events.get_journal().tail(etype="batcher.died")
                if rid in (e.get("request_ids") or ())]
        assert died and died[-1]["severity"] == "error"
        # the injected fault journaled with the victim's correlation set
        injected = [e for e in events.get_journal().tail(
            etype="fault.injected")
            if rid in (e.get("request_ids") or ())]
        assert injected and injected[-1]["site"] == "batcher.compute"
        # the flight recorder captured both, named by reason
        dumps = flight.list_dumps()
        assert dumps, "batcher death must write a flight dump"
        with open(dumps[-1]) as f:
            payload = json.load(f)
        assert payload["reason"] == "batcher_died"
        assert rid in payload["extra"]["stranded_request_ids"]
        dumped_types = {e["type"] for e in payload["events"]}
        assert "fault.injected" in dumped_types
        assert "batcher.died" in dumped_types
        mb.stop()
    finally:
        faults.reset()


def test_readyz_flip_to_not_ready_dumps(tmp_path):
    ep = DeepLearning4jEntryPoint()
    try:
        assert ep.readyz()["ready"] is True
        before = len(flight.list_dumps())
        ep.min_ready_models = 5                 # force unready
        r = ep.readyz()
        assert r["ready"] is False
        flips = events.get_journal().tail(etype="readyz.flip")
        assert flips and flips[-1]["ready"] is False
        assert "models_warm" in flips[-1]["failing"]
        assert len(flight.list_dumps()) == before + 1
        ep.min_ready_models = 0                 # flip back: event, no dump
        assert ep.readyz()["ready"] is True
        flips = events.get_journal().tail(etype="readyz.flip")
        assert flips[-1]["ready"] is True
        assert len(flight.list_dumps()) == before + 1
    finally:
        ep.close()


def test_flight_dump_rate_limit_and_kill_switch(monkeypatch):
    monkeypatch.setenv("DL4J_FLIGHT_MIN_INTERVAL_S", "3600")
    p1 = flight.dump("ratelimited_reason")
    assert p1 is not None
    assert flight.dump("ratelimited_reason") is None   # limited
    assert flight.dump("ratelimited_reason", force=True) is not None
    monkeypatch.setenv("DL4J_FLIGHT", "0")
    assert flight.dump("ratelimited_reason", force=True) is None


# ---------------------------------------------------------------------------
# Resilience / train events
# ---------------------------------------------------------------------------
def test_breaker_transition_events():
    from deeplearning4j_tpu.resilience import CircuitBreaker
    clk = [0.0]
    br = CircuitBreaker(failure_threshold=0.5, window=4, min_calls=2,
                        cooldown_s=10.0, name="evbreaker",
                        clock=lambda: clk[0])

    def boom():
        raise RuntimeError("down")
    for _ in range(2):
        with pytest.raises(RuntimeError):
            br.call(boom)
    assert br.state == CircuitBreaker.OPEN
    trans = [e for e in events.get_journal().tail(
        etype="breaker.transition") if e.get("breaker") == "evbreaker"]
    assert trans and trans[-1]["to"] == "open"
    assert trans[-1]["severity"] == "warn"


def test_checkpoint_write_event(tmp_path):
    from deeplearning4j_tpu.nn.checkpoint import CheckpointListener
    conf = (NeuralNetConfiguration.builder().seed(1).learning_rate(0.1)
            .list()
            .layer(L.DenseLayer(n_in=F, n_out=8, activation="relu"))
            .layer(L.OutputLayer(n_in=8, n_out=C, activation="softmax",
                                 loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    lst = CheckpointListener(tmp_path / "ckpt", save_every_n_iterations=1)
    net.add_listener(lst)
    x = np.random.default_rng(0).normal(size=(8, F)).astype(np.float32)
    y = np.eye(C, dtype=np.float32)[
        np.random.default_rng(1).integers(0, C, 8)]
    from deeplearning4j_tpu.datasets.dataset import DataSet
    net.fit(DataSet(x, y), epochs=1)
    writes = events.get_journal().tail(etype="checkpoint.write")
    assert writes and writes[-1]["path"].startswith("checkpoint_it")
    # the fit scope correlated the checkpoint event with its fit
    assert writes[-1].get("fit_id")
    fits = [e for e in events.get_journal().tail(etype="fit.start")
            if e.get("fit_id") == writes[-1]["fit_id"]]
    assert fits and fits[-1]["model"] == "MultiLayerNetwork"
    ends = [e for e in events.get_journal().tail(etype="fit.end")
            if e.get("fit_id") == writes[-1]["fit_id"]]
    assert ends


# ---------------------------------------------------------------------------
# Tier-1 subprocess smokes
# ---------------------------------------------------------------------------
_KILL_SMOKE = r"""
import json, os, sys, urllib.request, urllib.error
import numpy as np
from deeplearning4j_tpu.monitor import flight
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.serialization import write_model
from deeplearning4j_tpu.server import DeepLearning4jEntryPoint, Server

conf = (NeuralNetConfiguration.builder().seed(3).learning_rate(0.1)
        .shape_bucketing(True).list()
        .layer(L.DenseLayer(n_in=6, n_out=8, activation="relu"))
        .layer(L.OutputLayer(n_in=8, n_out=3, activation="softmax",
                             loss="mcxent"))
        .build())
path = os.path.join(os.environ["SMOKE_TMP"], "m.zip")
write_model(MultiLayerNetwork(conf).init(), path)
server = Server(DeepLearning4jEntryPoint(), port=0).start()
base = f"http://{server.host}:{server.port}"
req = urllib.request.Request(base + "/", data=json.dumps(
    {"method": "predict",
     "params": {"model_path": path, "features": [[0.0] * 6]}}).encode())
out = {}
try:
    urllib.request.urlopen(req, timeout=60)
    out["predict"] = 200
except urllib.error.HTTPError as e:
    out["predict"] = e.code
    out["request_id"] = json.loads(e.read()).get("request_id")
import time
deadline = time.time() + 30
while not flight.list_dumps() and time.time() < deadline:
    time.sleep(0.05)
out["dumps"] = flight.list_dumps()
server.stop()
print(json.dumps(out))
"""


def test_fault_kill_writes_flight_dump_subprocess(tmp_path):
    """A fault-armed server (DL4J_FAULT_PLAN kill on batcher.compute)
    writes a flight-recorder dump containing the injected fault event
    AND the failing request's ID — the black box survives the thread
    it describes."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SMOKE_TMP"] = str(tmp_path)
    env["DL4J_FLIGHT_DIR"] = str(tmp_path / "flight")
    env[faults.ENV_VAR] = json.dumps(
        [{"site": "batcher.compute", "mode": "kill", "on_call": 1}])
    p = subprocess.run([sys.executable, "-c", _KILL_SMOKE],
                       capture_output=True, text=True, timeout=240,
                       env=env, cwd=REPO)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["predict"] == 500
    rid = out["request_id"]
    assert rid and out["dumps"]
    with open(out["dumps"][-1]) as f:
        payload = json.load(f)
    assert payload["reason"] == "batcher_died"
    assert rid in payload["extra"]["stranded_request_ids"]
    by_type = {}
    for e in payload["events"]:
        by_type.setdefault(e["type"], []).append(e)
    # the injected fault event is in the dump, correlated to the victim
    assert any(rid in (e.get("request_ids") or ())
               for e in by_type.get("fault.injected", []))
    assert any(rid in (e.get("request_ids") or ())
               for e in by_type.get("batcher.died", []))
    # and the request's own lifecycle events made it in too
    assert any(e.get("request_id") == rid
               for e in by_type.get("rpc.request", []))


_CHROME_SMOKE = r"""
import json, os, urllib.request
import numpy as np
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.serialization import write_model
from deeplearning4j_tpu.server import DeepLearning4jEntryPoint, Server

conf = (NeuralNetConfiguration.builder().seed(3).learning_rate(0.1)
        .shape_bucketing(True).list()
        .layer(L.DenseLayer(n_in=6, n_out=8, activation="relu"))
        .layer(L.OutputLayer(n_in=8, n_out=3, activation="softmax",
                             loss="mcxent"))
        .build())
path = os.path.join(os.environ["SMOKE_TMP"], "m.zip")
write_model(MultiLayerNetwork(conf).init(), path)
server = Server(DeepLearning4jEntryPoint(), port=0).start()
base = f"http://{server.host}:{server.port}"
for i in range(3):
    req = urllib.request.Request(base + "/", data=json.dumps(
        {"method": "predict",
         "params": {"model_path": path,
                    "features": [[float(i)] * 6]}}).encode())
    urllib.request.urlopen(req, timeout=60)
with urllib.request.urlopen(base + "/trace?format=chrome",
                            timeout=30) as r:
    body = r.read().decode()
server.stop()
print(body)
"""


def test_chrome_trace_export_parses_subprocess(tmp_path):
    """GET /trace?format=chrome from a live server parses as JSON with
    well-formed ph/ts fields — the Perfetto contract."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SMOKE_TMP"] = str(tmp_path)
    p = subprocess.run([sys.executable, "-c", _CHROME_SMOKE],
                       capture_output=True, text=True, timeout=240,
                       env=env, cwd=REPO)
    assert p.returncode == 0, p.stderr[-2000:]
    trace = json.loads(p.stdout.strip())
    te = trace["traceEvents"]
    assert len(te) > 10
    for e in te:
        assert e["ph"] in ("X", "i", "M"), e
        assert isinstance(e["pid"], int)
        if e["ph"] != "M":
            assert isinstance(e["ts"], (int, float)) and e["ts"] > 0
        if e["ph"] == "X":
            assert e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "t"
    # serving spans made it into the export as real slices
    assert any(e["ph"] == "X" and e["name"].startswith("serve/batch")
               for e in te)
