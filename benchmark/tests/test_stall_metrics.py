"""The three readers of the step records (PR 37), each on a hand-made
``ctx`` and registry, beside ``test_tracing_metrics.py``'s (a file the
benchmark already had, which a PR that may only add leaves as it is).
Run by hand, on the CPU:

    python -m pytest benchmark/tests/test_stall_metrics.py -q -p no:cacheprovider
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark.metrics import (  # noqa: E402
    step_stall_ms_per_step, step_tail_fetch_ms_per_step, steps_stalled_share)


@pytest.fixture
def registry(monkeypatch):
    """An empty registry in the place of the program's."""
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.monitor.registry import MetricsRegistry
    reg = MetricsRegistry()
    monkeypatch.setattr(monitor, "get_registry", lambda: reg)
    return reg


def test_stalls_are_read_over_the_iterations_of_the_process(registry):
    for reader in (step_stall_ms_per_step, steps_stalled_share):
        assert reader.read({}) is None                  # the parent
    registry.counter("dl4j_fit_iterations_total", "").inc(400)
    stalls = registry.counter("dl4j_fit_stalls_total", "", labels=("phase",))
    seconds = registry.counter("dl4j_fit_stall_seconds_total", "",
                               labels=("phase",))
    assert step_stall_ms_per_step.read({}) == 0.0       # no stall: 0, not None
    assert steps_stalled_share.read({}) == 0.0
    for phase, n, s in (("epoch", 2, 0.080), ("block_until_ready", 1, 0.012),
                        ("publish", 1, 2.1)):
        stalls.labels(phase=phase).inc(n)
        seconds.labels(phase=phase).inc(s)
    assert step_stall_ms_per_step.read({}) == pytest.approx(5.48)
    assert steps_stalled_share.read({}) == pytest.approx(1.0)


def test_the_tail_fetch_is_two_phases_of_the_window():
    w = {"steps": 100, "seconds": 25.0,
         "spans": {"bookkeeping": (0.58, 100), "listeners": (0.01, 100)}}
    assert step_tail_fetch_ms_per_step.read({"window": w}) is None  # parent
    w["spans"].update(score_fetch=(0.03, 100), publish=(0.25, 100))
    assert step_tail_fetch_ms_per_step.read({"window": w}) == pytest.approx(2.8)
    assert step_tail_fetch_ms_per_step.read(
        {"window": dict(w, steps=0)}) is None
