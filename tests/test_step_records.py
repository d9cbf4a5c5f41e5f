"""Every step of a ``fit()`` leaves a record and a stall names itself
(PR 37): ``StepSpans.step_done``, the ``fit.step`` and ``fit.stall``
events, the stall counters, the trace's ``stalls`` and
``clock_bounds_s``, and the three benchmark readers that read them.
"""

import json

import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.monitor import events, profile, tracing
from deeplearning4j_tpu.monitor.registry import MetricsRegistry

FEATS, CLASSES, ROWS = 6, 3, 16


class FakeClock:
    """Stands in for the ``time`` module inside ``tracing``: the clock
    moves when a test says so, and the thread's CPU time with it while
    ``on_cpu``."""

    def __init__(self):
        self.t = 100.0
        self.cpu = 5.0
        self.on_cpu = True

    def advance(self, seconds):
        self.t += seconds
        if self.on_cpu:
            self.cpu += seconds

    def perf_counter(self):
        return self.t

    def thread_time(self):
        return self.cpu


@pytest.fixture
def clock(monkeypatch):
    c = FakeClock()
    monkeypatch.setattr(tracing, "time", c)
    return c


def _events_since(seq, etype):
    return [e for e in monitor.get_journal().tail(etype=etype)
            if e["seq"] > seq]


def _step(steps, clock, iteration, phases, compiling=False, charge=0.0):
    """One step of a hand-made loop: ``phases`` is [(name, seconds)]."""
    for name, seconds in phases:
        with steps.span("loop", phase=name) as s:
            clock.advance(seconds)
            s.compile_s += charge
            charge = 0.0
    steps.step_done(iteration, compiling=compiling)


def _stall_counts(reg):
    snap = reg.snapshot()
    return tuple({s["labels"]["phase"]: s["value"]
                  for s in snap[name]["samples"]}
                 for name in (tracing.STALLS_METRIC,
                              tracing.STALL_SECONDS_METRIC))


STEADY = [("a", 0.010), ("b", 0.050)]


def test_a_records_phases_sum_to_the_steps_wall_time(clock):
    reg = MetricsRegistry()
    seq0 = monitor.get_journal().total_emitted
    steps = tracing.StepSpans(registry=reg)
    clock.advance(0.003)                  # before the first phase: glue
    with steps.span("loop", phase="a"):
        clock.advance(0.010)
    clock.advance(0.002)                  # between two phases: glue
    with steps.span("loop", phase="b"):
        clock.advance(0.050)
    with steps.span("loop", phase="a"):   # a phase may come twice a step
        clock.advance(0.001)
    clock.on_cpu = False
    clock.advance(0.004)                  # after the last, off the CPU: glue
    steps.step_done(7, k=2)
    (ev,) = _events_since(seq0, "fit.step")
    assert ev["phases"] == pytest.approx(
        {"glue": 0.009, "a": 0.011, "b": 0.050})
    assert list(ev["phases"]) == ["glue", "a", "b"]         # loop order
    assert sum(ev["phases"].values()) == pytest.approx(ev["step_s"])
    assert ev["step_s"] == pytest.approx(0.070)
    assert (ev["iteration"], ev["k"], ev["span"]) == (7, 2, "loop")
    assert ev["compiling"] is False and ev["compile_s"] == 0.0
    # what the host thread did meanwhile: on the CPU but for the last 4 ms
    assert ev["cpu_s"] == pytest.approx(0.066)
    for key in ("switches_voluntary", "switches_involuntary",
                "major_faults", "gc_s", "gc_collections"):
        assert ev[key] >= 0, key
    # the next step starts where this one ended
    _step(steps, clock, 8, STEADY)
    second = _events_since(seq0, "fit.step")[1]
    assert second["step_s"] == pytest.approx(0.060)
    assert second["cpu_s"] == pytest.approx(0.0)
    # the histograms hold what the records hold
    sums = {x["labels"]["phase"]: x["sum"]
            for x in reg.snapshot()[tracing.PHASE_METRIC]["samples"]}
    assert sums == pytest.approx({"glue": 0.009, "a": 0.021, "b": 0.100})


@pytest.mark.parametrize("how", ["late", "flagged_as_compiling",
                                 "charged_compile_seconds"])
def test_one_late_step_of_twenty_is_one_stall_unless_it_compiled(clock, how):
    reg = MetricsRegistry()
    seq0 = monitor.get_journal().total_emitted
    steps = tracing.StepSpans(registry=reg)
    for i in range(20):
        if i == 13:
            _step(steps, clock, i, [("a", 0.011), ("b", 0.080)],
                  compiling=how == "flagged_as_compiling",
                  charge=0.02 if how == "charged_compile_seconds" else 0.0)
        else:
            _step(steps, clock, i, STEADY)
    assert len(_events_since(seq0, "fit.step")) == 20
    stalls = _events_since(seq0, "fit.stall")
    if how != "late":
        assert stalls == []
        assert _stall_counts(reg) == ({}, {})
        late = _events_since(seq0, "fit.step")[13]
        assert late["compiling"] is True
        assert late["compile_s"] == (0.02 if how.startswith("charged") else 0)
        return
    (ev,) = stalls
    assert ev["severity"] == "warn" and ev["iteration"] == 13
    assert ev["holder"] == "b" and ev["first_of_fit"] is False
    assert ev["median_s"] == pytest.approx(0.060)
    assert ev["excess_s"] == pytest.approx(0.031)
    assert ev["step_s"] == pytest.approx(0.091)
    assert ev["phase_excess_s"] == pytest.approx(
        {"glue": 0.0, "a": 0.001, "b": 0.030})
    assert ev["phases"] == pytest.approx({"glue": 0, "a": 0.011, "b": 0.080})
    assert ev["cpu_s"] == pytest.approx(0.091)       # on the CPU all through
    counts, seconds = _stall_counts(reg)
    assert counts == {"b": 1.0}
    assert seconds == pytest.approx({"b": 0.031})


def test_the_rule_is_the_larger_of_4_ms_and_2_percent():
    assert tracing.stall_excess(0.0639, 0.060) == 0.0     # under 4 ms
    assert tracing.stall_excess(0.0641, 0.060) == pytest.approx(0.0041)
    assert tracing.stall_excess(0.509, 0.500) == 0.0      # 4 ms, under 2%
    assert tracing.stall_excess(0.511, 0.500) == pytest.approx(0.011)
    assert tracing.stall_excess(0.050, 0.060) == 0.0      # early is no stall


def test_the_first_step_of_a_fit_is_judged_once_eight_are_there(clock):
    reg = MetricsRegistry()
    seq0 = monitor.get_journal().total_emitted
    steps = tracing.StepSpans(registry=reg)
    # a fit's first step starts its pipeline: 40 ms in `epoch`
    _step(steps, clock, 0, [("epoch", 0.040)] + STEADY)
    for i in range(1, 7):
        _step(steps, clock, i, STEADY)
    assert _events_since(seq0, "fit.stall") == []     # seven: not yet
    _step(steps, clock, 7, STEADY)
    (ev,) = _events_since(seq0, "fit.stall")
    assert ev["first_of_fit"] is True and ev["iteration"] == 0
    assert ev["holder"] == "epoch"
    assert ev["excess_s"] == pytest.approx(0.040)
    # later steps are judged as they close
    _step(steps, clock, 8, [("a", 0.010), ("b", 0.056)])
    late = _events_since(seq0, "fit.stall")[1]
    assert (late["iteration"], late["holder"]) == (8, "b")
    assert late["first_of_fit"] is False
    assert _stall_counts(reg)[0] == {"epoch": 1.0, "b": 1.0}


def test_both_counter_families_read_zero_without_a_stall(clock):
    reg = MetricsRegistry()
    steps = tracing.StepSpans(registry=reg)
    for i in range(12):
        _step(steps, clock, i, STEADY)
    snap = reg.snapshot()
    for name in (tracing.STALLS_METRIC, tracing.STALL_SECONDS_METRIC):
        assert snap[name]["type"] == "counter"
        assert snap[name]["label_names"] == ["phase"]
        assert sum(s["value"] for s in snap[name]["samples"]) == 0
    text = monitor.render_prometheus(snap)
    assert "# TYPE dl4j_fit_stalls_total counter" in text


def _small_net(seed=5):
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    conf = (NeuralNetConfiguration.builder().seed(seed).learning_rate(0.05)
            .updater("adam").list()
            .layer(L.DenseLayer(n_in=FEATS, n_out=12, activation="relu"))
            .layer(L.OutputLayer(n_in=12, n_out=CLASSES,
                                 activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _iterator(n, async_=True):
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import (
        AsyncDataSetIterator, ListDataSetIterator)
    rng = np.random.default_rng(0)
    it = ListDataSetIterator([
        DataSet(rng.normal(size=(ROWS, FEATS)).astype(np.float32),
                np.eye(CLASSES, dtype=np.float32)[
                    rng.integers(0, CLASSES, ROWS)]) for _ in range(n)])
    return AsyncDataSetIterator(it, device_put=True) if async_ else it


def test_a_fit_of_300_steps_keeps_its_fit_start_in_the_journal():
    net = _small_net()
    net.fit(_iterator(4))                     # compiles
    seq0 = monitor.get_journal().total_emitted
    net.fit(_iterator(300))
    tail = [e for e in monitor.get_journal().tail() if e["seq"] > seq0]
    (start,) = [e for e in tail if e["type"] == "fit.start"]
    own = [e for e in tail if e.get("fit_id") == start["fit_id"]]
    by_type = {}
    for e in own:
        by_type[e["type"]] = by_type.get(e["type"], 0) + 1
    # one event a step and the fit's two marks; a stall adds its own (on
    # a quiet machine a handful: the fit leaves at most 310 events)
    stalled = by_type.pop("fit.stall", 0)
    assert by_type == {"fit.start": 1, "fit.step": 300, "fit.end": 1}
    assert stalled <= 300
    assert not [e for e in tail if e["type"] == "span.close"
                and e["span"] == "fit/step"]
    # a plain span outside the loop's helper still journals its close
    assert [e for e in tail if e["type"] == "span.close"
            and e["span"] == "pipeline/batch"]
    steps = [e for e in own if e["type"] == "fit.step"]
    assert [e["iteration"] for e in steps] == list(range(5, 305))
    for e in steps[:3] + steps[-3:]:
        assert sum(e["phases"].values()) == pytest.approx(e["step_s"])
        assert {"data_wait", "jit_call", "block_until_ready", "score_fetch",
                "bookkeeping", "publish", "listeners"} <= set(e["phases"])
    assert "epoch" in steps[0]["phases"] and "epoch" not in steps[1]["phases"]
    assert len(net._steps._ring) == tracing.RING_STEPS
    json.dumps(own)                           # a flight dump can write them


def test_the_step_that_compiles_says_so():
    net = _small_net(seed=9)
    seq0 = monitor.get_journal().total_emitted
    net.fit(_iterator(3, async_=False))
    first, *rest = _events_since(seq0, "fit.step")
    assert first["compiling"] is True and first["compile_s"] > 0
    assert not any(e["compiling"] for e in rest)


def test_spans_off_leaves_no_record_and_no_event():
    net = _small_net(seed=11)
    net.fit(_iterator(3, async_=False))
    seq0 = monitor.get_journal().total_emitted
    tracing.set_enabled(False)
    try:
        net.fit(_iterator(12, async_=False))
    finally:
        tracing.set_enabled(None)
    assert _events_since(seq0, "fit.step") == []
    assert _events_since(seq0, "fit.stall") == []
    assert len(net._steps._ring) == 0 and net._steps._stalls is None
    assert net.iteration == 15


def test_chrome_trace_lays_a_fit_step_out_as_slices_that_tile_it():
    ev = {"type": "fit.step", "severity": "info", "ts": 1000.0, "tid": 3,
          "seq": 9, "fit_id": "f1", "span": "fit/step", "iteration": 4,
          "step_s": 0.070,
          "phases": {"glue": 0.009, "jit_call": 0.011,
                     "block_until_ready": 0.050}}
    mark = {"type": "fit.start", "severity": "info", "ts": 999.0, "tid": 3,
            "seq": 8, "fit_id": "f1"}
    trace = events.chrome_trace([mark, ev])
    slices = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert [s["name"] for s in slices] == [
        "fit/step/glue", "fit/step/jit_call", "fit/step/block_until_ready"]
    assert slices[0]["ts"] == pytest.approx(1000.0e6 - 0.070e6)
    for before, after in zip(slices, slices[1:]):
        assert after["ts"] == pytest.approx(before["ts"] + before["dur"])
    assert slices[-1]["ts"] + slices[-1]["dur"] == pytest.approx(1000.0e6)
    assert [s["dur"] for s in slices] == pytest.approx([9e3, 11e3, 50e3])
    assert all(s["args"]["fit_id"] == "f1" and s["args"]["iteration"] == 4
               and "phases" not in s["args"] for s in slices)
    assert [e["name"] for e in trace["traceEvents"] if e["ph"] == "i"] == [
        "fit.start"]
    json.dumps(trace)


# ---------------------------------------------------------------------------
# The device's side: steps, stalls and clock bounds of a trace
# ---------------------------------------------------------------------------
STEP_NS, BUSY_NS, DELAY_NS, LAG_NS = 100e6, 60e6, 0.3e6, 1.2e6
LATE_STEP, LATE_NS, MORE_NS = 6, 30e6, 0.2e6


def _planes(late):
    """Twelve steps of 100 ms: the host enters ``jit_call``, the device
    starts 0.3 ms later (0.5 in odd steps) and is busy for 60 ms, the
    wait ends 1.2 ms after the device's last operation (1.4 in every
    third step).  Step 6 takes 30 ms more: on the device
    (``device``), between the device's end and the wait's (``host``), or
    between the call and the device's start (``runtime``)."""
    host, ops, launches = [], [], []
    t = 1_000_000.0
    for i in range(12):
        extra = LATE_NS if i == LATE_STEP else 0.0
        delay = DELAY_NS + (MORE_NS if i % 2 else 0.0) \
            + (extra if late.startswith("runtime") else 0.0)
        busy = BUSY_NS + (extra if late == "device" else 0.0)
        lag = LAG_NS + (0.0 if i % 3 else MORE_NS) \
            + (extra if late == "host" else 0.0)
        call_end = t + delay + 1e6           # the call returns mid-launch
        if late == "runtime_in_wait":
            call_end -= extra
        dev0, dev1 = t + delay, t + delay + busy
        wait_end = dev1 + lag
        step_end = t + STEP_NS + extra
        host += [(t, call_end - t, "fit/step/jit_call", ""),
                 (call_end, wait_end - call_end,
                  "fit/step/block_until_ready", ""),
                 (wait_end, 5e6, "fit/step/score_fetch", ""),
                 (wait_end + 5e6, step_end - wait_end - 5e6,
                  "fit/step/data_wait", "")]
        ops += [(dev0, busy / 2, "%fusion.1 = ...", ""),
                (dev0 + busy / 2, busy / 2, "%fusion.2 = ...", "")]
        launches.append((dev0, busy, "jit_mln_train_step(7)", ""))
        # a small program of the host's preparation, not the step's
        launches.append((step_end - 1e6, 1e4, "jit_convert(3)", ""))
        ops.append((step_end - 1e6, 1e4, "%convert.9 = ...", ""))
        t = step_end
    return [("/device:TPU:0", [("XLA Modules", launches), ("XLA Ops", ops)]),
            ("/host:CPU", [("python3", host)])]


@pytest.mark.parametrize("late", ["device", "host", "runtime",
                                  "runtime_in_wait"])
def test_summarize_tells_who_was_late_in_the_one_late_step(late):
    summary = profile.summarize(_planes(late))
    assert summary["steps"] == 12
    (stall,) = summary["stalls"]
    assert stall["step"] == LATE_STEP
    assert stall["median_s"] == pytest.approx(STEP_NS / 1e9)
    assert stall["excess_s"] == pytest.approx(LATE_NS / 1e9)
    assert sum(stall["phases_s"].values()) == pytest.approx(stall["wall_s"])
    chip = stall["chips"]["0"]
    assert chip["late"] == late.split("_")[0]
    busy_over = LATE_NS if late == "device" else 0.0
    assert chip["busy_excess_s"] == pytest.approx(busy_over / 1e9, abs=1e-12)
    lag_over = LATE_NS if late == "host" else 0.0
    # step 6 is a third step: its own lag is 1.4 ms, the median's 1.2
    assert chip["wait_lag_excess_s"] == pytest.approx(
        (lag_over + MORE_NS) / 1e9)
    held = {"device": None, "host": "block_until_ready",
            "runtime": "jit_call",
            "runtime_in_wait": "block_until_ready"}[late]
    if held:
        assert max(chip["idle_excess_s"], key=chip["idle_excess_s"].get) \
            == held
        assert chip["idle_excess_s"][held] >= 0.9 * LATE_NS / 1e9
    assert chip["busy_s"] + sum(chip["idle_s"].values()) == pytest.approx(
        stall["wall_s"])
    # the planted offsets: the least launch delay, the least lag
    assert summary["chips"]["0"]["clock_bounds_s"] == pytest.approx(
        [-LAG_NS / 1e9, DELAY_NS / 1e9])
    json.dumps(summary)


def test_a_trace_without_steps_or_chips_has_no_stalls():
    host_only = [("/host:CPU", [("python3", [
        (0.0, 10.0, "fit/step/jit_call", ""),
        (10.0, 90.0, "fit/step/block_until_ready", "")])])]
    summary = profile.summarize(host_only)
    assert (summary["steps"], summary["stalls"], summary["chips"]) == \
        (1, [], {})
    assert profile.summarize([])["steps"] == 0


# ---------------------------------------------------------------------------
# The benchmark's three readers
# ---------------------------------------------------------------------------
@pytest.fixture
def registry(monkeypatch):
    """An empty registry in the place of the program's."""
    reg = MetricsRegistry()
    monkeypatch.setattr(monitor, "get_registry", lambda: reg)
    return reg


def _reader(name):
    import importlib
    return importlib.import_module("benchmark.metrics." + name)


@pytest.mark.parametrize("name,expected", [
    ("step_stall_ms_per_step", 0.35), ("steps_stalled_share", 1.5)])
def test_the_stall_readers_divide_by_the_iterations(registry, name, expected):
    reader = _reader(name)
    assert reader.read({}) is None                   # the parent's program
    registry.counter("dl4j_fit_iterations_total", "").inc(200)
    assert reader.read({}) is None
    stalls = registry.counter(tracing.STALLS_METRIC, "", labels=("phase",))
    seconds = registry.counter(tracing.STALL_SECONDS_METRIC, "",
                               labels=("phase",))
    assert reader.read({}) == 0.0                    # registered, no stall
    stalls.labels(phase="block_until_ready").inc(2)
    stalls.labels(phase="epoch").inc()
    seconds.labels(phase="block_until_ready").inc(0.030)
    seconds.labels(phase="epoch").inc(0.040)
    assert reader.read({}) == pytest.approx(expected)


def test_the_tail_fetch_reader_sums_the_two_phases_of_the_window():
    reader = _reader("step_tail_fetch_ms_per_step")
    window = {"steps": 10, "seconds": 1.0,
              "spans": {"bookkeeping": (0.002, 10), "jit_call": (0.02, 10)}}
    assert reader.read({"window": window}) is None   # the parent's phases
    window["spans"].update(score_fetch=(0.004, 10), publish=(0.021, 10))
    assert reader.read({"window": window}) == pytest.approx(2.5)
    window["steps"] = 0
    assert reader.read({"window": window}) is None


def test_the_new_metrics_are_declared_for_every_cell():
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    new = {m["name"]: m for m in bench["per_layer"][-3:]}
    assert list(new) == ["step_stall_ms_per_step", "steps_stalled_share",
                         "step_tail_fetch_ms_per_step"]
    for m in new.values():
        assert "workloads" not in m and m["layer"] == "entry points"
        assert m["source"] == "program_span" and m["better"] == "lower"
