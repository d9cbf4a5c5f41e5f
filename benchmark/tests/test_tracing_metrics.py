"""The readers of the per-layer metrics that read the program's own
tracing (PR 27), each on a hand-made ``ctx`` and registry.  Run by hand,
on the CPU, like ``test_benchmark.py``:

    python -m pytest benchmark/tests/test_tracing_metrics.py -q -p no:cacheprovider
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark.metrics import (  # noqa: E402
    fit_loop_untimed_ms_per_step, idle_unattributed_share,
    pipeline_h2d_ms_per_batch, setup_compile_or_load_s, setup_init_s,
    setup_trace_lower_s)


@pytest.fixture
def registry(monkeypatch):
    """An empty registry in the place of the program's."""
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.monitor.registry import MetricsRegistry
    reg = MetricsRegistry()
    monkeypatch.setattr(monitor, "get_registry", lambda: reg)
    return reg


def _span(reg, span, phase, *seconds):
    h = reg.histogram("dl4j_phase_seconds", "", labels=("span", "phase"))
    for s in seconds:
        h.labels(span=span, phase=phase).observe(s)


def _compile(reg, stage, span, *seconds):
    h = reg.histogram("dl4j_compile_seconds", "", labels=("stage", "span"))
    for s in seconds:
        h.labels(stage=stage, span=span).observe(s)


def _window(steps, seconds, spans):
    return {"window": {"steps": steps, "seconds": seconds, "spans": spans},
            "trace": None}


# --- from the window's spans ------------------------------------------------
def test_untimed_is_the_window_less_every_phase():
    # the parent's shape: seven phases, and time in none of them
    ctx = _window(10, 1.0, {"data_wait": (0.01, 10), "jit_call": (0.02, 10),
                            "block_until_ready": (0.93, 10),
                            "listeners": (0.01, 10)})
    assert fit_loop_untimed_ms_per_step.read(ctx) == pytest.approx(3.0)
    # phases that tile the loop leave nothing
    ctx["window"]["spans"]["dispatch_prep"] = (0.03, 10)
    assert fit_loop_untimed_ms_per_step.read(ctx) == pytest.approx(0.0, abs=1e-9)


@pytest.mark.parametrize("ctx", [_window(0, 1.0, {"jit_call": (0.5, 1)}),
                                 _window(10, 1.0, {})])
def test_untimed_reads_nothing_without_steps_or_spans(ctx):
    assert fit_loop_untimed_ms_per_step.read(ctx) is None


# --- from the trace's reduction ---------------------------------------------
def test_unattributed_is_between_spans_over_all_idle_seconds():
    ctx = {"trace": {"idle_gaps": [["between_spans", 0.07],
                                   ["block_until_ready", 0.055],
                                   ["jit_call", 0.0]]}}
    assert idle_unattributed_share.read(ctx) == pytest.approx(56.0)
    ctx = {"trace": {"idle_gaps": [["block_until_ready", 0.06],
                                   ["dispatch_prep", 0.04]]}}
    assert idle_unattributed_share.read(ctx) == 0.0


@pytest.mark.parametrize("trace", [None, {}, {"idle_gaps": []},
                                   {"idle_gaps": [["jit_call", 0.0]]}])
def test_unattributed_reads_nothing_without_idle_time(trace):
    assert idle_unattributed_share.read({"trace": trace}) is None


# --- from the program's registry --------------------------------------------
def test_pipeline_h2d_is_the_mean_of_the_workers_phase(registry):
    assert pipeline_h2d_ms_per_batch.read({}) is None       # the parent
    _span(registry, "pipeline/batch", "transform", 0.5)
    assert pipeline_h2d_ms_per_batch.read({}) is None       # part 4 dropped
    _span(registry, "pipeline/batch", "h2d", 0.010, 0.020, 0.030)
    _span(registry, "fit/step", "h2d", 9.0)                 # another span's
    assert pipeline_h2d_ms_per_batch.read({}) == pytest.approx(20.0)


def test_setup_init_sums_the_phases_of_net_init(registry):
    assert setup_init_s.read({}) is None
    _span(registry, "fit/step", "jit_call", 5.0)
    assert setup_init_s.read({}) is None
    _span(registry, "net/init", "default_weights", 3.0)
    _span(registry, "net/init", "given_weights", 0.5, 0.25)
    assert setup_init_s.read({}) == pytest.approx(3.75)
    assert setup_init_s.phase_totals("net/init")["given_weights"] == (0.75, 2)


def test_compile_stages_count_program_spans_only_and_no_second_twice(registry):
    assert setup_trace_lower_s.read({}) is None
    assert setup_compile_or_load_s.read({}) is None
    # the reference's compiles, after the window: in no span
    for stage in ("trace", "lower", "backend_compile", "cache_load"):
        _compile(registry, stage, "", 100.0)
    assert setup_trace_lower_s.read({}) is None
    assert setup_compile_or_load_s.read({}) is None
    _compile(registry, "trace", "fit/step", 1.0, 0.5)
    _compile(registry, "lower", "fit/step", 2.0)
    _compile(registry, "trace", "net/init", 0.25)
    assert setup_trace_lower_s.read({}) == pytest.approx(3.75)
    assert setup_compile_or_load_s.read({}) is None
    _compile(registry, "backend_compile", "fit/step", 4.0)
    _compile(registry, "cache_load", "fit/setup", 0.5)
    assert setup_compile_or_load_s.read({}) == pytest.approx(4.5)
    assert setup_trace_lower_s.read({}) == pytest.approx(3.75)


def test_the_program_splits_the_backend_timer_at_a_cache_hit(registry, monkeypatch):
    """JAX 0.9.0's backend timer contains the cache's retrieval: the
    program files the retrieval under cache_load and the rest under
    backend_compile, so the reader's sum is the timer's."""
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.monitor import compile_stages as cs
    monkeypatch.setattr(cs, "get_registry", lambda: registry)
    backend = "/jax/core/compile/backend_compile_duration"
    with monitor.span("fit/step", phase="jit_call"):
        cs._on_start(backend, 0.0)
        cs._on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 0.3)
        cs._on_duration(backend, 1.0, fun_name="jit(step)")
    assert setup_compile_or_load_s.read({}) == pytest.approx(1.0)
    fam = registry.snapshot()["dl4j_compile_seconds"]["samples"]
    assert {s["labels"]["stage"]: round(s["sum"], 6) for s in fam} == {
        "cache_load": 0.3, "backend_compile": 0.7}
