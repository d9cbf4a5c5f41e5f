"""Unified observability backbone: metrics registry + step-phase
tracing + Prometheus/JSON exposition.

The reproduction's telemetry surfaces — ``CompileTelemetry`` retrace
counts (ops/bucketing.py), serving latency reservoirs
(server/batcher.py), model-cache counters (server/model_cache.py), the
UI's per-iteration stats (ui/stats_listener.py) — all meter into ONE
process-wide :class:`~deeplearning4j_tpu.monitor.registry.MetricsRegistry`,
and the training/serving hot paths are phase-annotated with
:func:`~deeplearning4j_tpu.monitor.tracing.span`, so a single scrape
(the gateway's ``metrics`` RPC / ``GET /metrics``) answers both "what is
the system doing" and "where does a step spend its time".

    from deeplearning4j_tpu import monitor

    with monitor.span("fit/step", phase="h2d"):
        x = jax.device_put(x)

    text = monitor.render_prometheus(monitor.get_registry().snapshot())

A third surface rides the same package: the **structured event
journal** (``monitor/events.py`` — a bounded ring of typed events with
request/session correlation IDs carried by contextvars) and the
**flight recorder** (``monitor/flight.py`` — crash handlers dump the
journal tail plus a registry snapshot to a timestamped JSON file;
``GET /trace`` / the ``trace_dump`` RPC serve the live journal and its
Chrome trace-event export).  A plain ``monitor.span`` journals a
``span.close`` when it ends; the phases of a training loop
(``monitor.StepSpans``) journal nothing one by one: every step is ONE
``fit.step`` event that holds its phases and what the host thread did
meanwhile, and a step that ran late adds a ``fit.stall`` that names the
phase that held it.

Env knobs: ``DL4J_PROFILE=<dir>`` wraps every fit in
``jax.profiler.start_trace`` and writes its ``summary.json``
(monitor/profile.py); ``DL4J_TRACE_ANNOTATIONS=1`` mirrors
spans into XLA profiler dumps; ``DL4J_SPANS=0`` disables span timing;
``DL4J_JOURNAL=0`` disables the event journal; ``DL4J_FLIGHT_DIR``
places flight-recorder dumps.  Full catalog: docs/OBSERVABILITY.md.
"""

from deeplearning4j_tpu.monitor import (  # noqa: F401
    compile_stages, events, flight, tracing)
from deeplearning4j_tpu.monitor.events import (  # noqa: F401
    EventJournal, chrome_trace, chrome_trace_fleet, get_journal,
    new_request_id, request_scope)
from deeplearning4j_tpu.monitor.registry import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, get_registry)
from deeplearning4j_tpu.monitor.tracing import (  # noqa: F401
    Span, StepSpans, current, enable_jax_annotations,
    profile_if_configured, span)
from deeplearning4j_tpu.monitor.exposition import (  # noqa: F401
    CONTENT_TYPE, merge_snapshots, parse_prometheus, render_json,
    render_prometheus, snapshot_from_parsed)
from deeplearning4j_tpu.monitor.system import (  # noqa: F401
    memory_collector, memory_snapshot)

# Device/host memory is only knowable at scrape time — refresh it on
# every snapshot of the process registry.
get_registry().register_collector(memory_collector)
# JAX's compile-stage timers land in dl4j_compile_seconds{stage,span}
compile_stages.install()
# the collector's pauses, for the step records of a StepSpans
tracing.install_gc_hook()


def record_fit_step(batch_size: int, seconds: float,
                    score=None, registry=None) -> None:
    """Per-step training gauges shared by MultiLayerNetwork and
    ComputationGraph (and read back by ui/stats_listener.py, so the UI
    and ``/metrics`` report the same numbers)."""
    reg = registry if registry is not None else get_registry()
    reg.counter("dl4j_fit_iterations_total",
                "training iterations completed").inc()
    reg.histogram("dl4j_fit_step_seconds",
                  "full train-step wall time (seconds)").observe(seconds)
    if seconds > 0:
        reg.gauge("dl4j_fit_examples_per_sec",
                  "training throughput, last step").set(batch_size / seconds)
    reg.gauge("dl4j_fit_last_step_ms",
              "last train-step wall time (ms)").set(seconds * 1e3)
    if score is not None:
        try:
            reg.gauge("dl4j_fit_score", "last training score").set(
                float(score))
        except (TypeError, ValueError):
            pass
