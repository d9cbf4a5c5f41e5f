"""Compile stages, charged to the span that paid them.

JAX reports each stage of a compilation through ``jax.monitoring``:
tracing a function to a jaxpr, lowering the jaxpr to an MLIR module,
and the backend compile — which, in JAX 0.9.0, *contains* the
persistent cache's lookup (``compile_or_get_cached`` runs inside the
``backend_compile_duration`` timer, and on a hit additionally reports
``cache_retrieval_time_sec``).  :func:`install` registers listeners,
once, that land every stage in

* ``dl4j_compile_seconds{stage,span}`` — ``stage`` one of ``trace``,
  ``lower``, ``backend_compile``, ``cache_load``; ``span`` the name of
  the innermost program span open on the compiling thread
  (``fit/step``, ``fit/setup``, ``net/init``, ...) or empty when the
  compile was nobody's (a user's own ``jax.jit``, a benchmark's
  reference).  ``cache_load`` is the retrieval time of a hit and
  ``backend_compile`` the rest of the backend timer, so the two never
  hold the same second;
* ``dl4j_compile_cache_total{outcome}`` — persistent-cache ``hit`` and
  ``miss`` events;
* the journal event ``compile.stage`` (function name, stage, seconds,
  span, and the iteration of a ``fit/step`` span; the ``fit_id`` rides
  on the trace context): which step recompiled, and what it cost.  The
  open span also takes the seconds (``Span.compile_s``), from which a
  ``StepSpans`` charges them to the step's record: a step that paid for
  a compilation is no stall.

A stage is counted once per outermost call: every ``jit`` traced inside
the step fires its own trace event, and an eager operation inside a
trace fires all three, but their time is already inside the outer
event.  JAX announces each timer's start (a scalar event of the same
name), so a per-thread depth tells inner from outer.
"""

from __future__ import annotations

import threading

from deeplearning4j_tpu.monitor import events, tracing
from deeplearning4j_tpu.monitor.registry import get_registry

COMPILE_METRIC = "dl4j_compile_seconds"
CACHE_METRIC = "dl4j_compile_cache_total"

_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_OUTCOMES = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}

_local = threading.local()
_installed = False


def _record(stage: str, seconds: float, fun_name: str) -> None:
    cur = tracing.current()
    span = cur.name if cur is not None else ""
    if cur is not None:
        cur.compile_s += seconds    # a StepSpans charges it to the step
    get_registry().histogram(
        COMPILE_METRIC, "compile time by stage and paying span (seconds)",
        labels=("stage", "span"),
    ).labels(stage=stage, span=span).observe(seconds)
    events.emit("compile.stage", fun_name=fun_name, stage=stage,
                seconds=seconds, span=span,
                iteration=cur.iteration if cur is not None else None)


def _on_start(event: str, _value, **_kw) -> None:
    if event in _STAGES:
        _local.depth = getattr(_local, "depth", 0) + 1


def _on_duration(event: str, seconds: float, fun_name: str = "",
                 **_kw) -> None:
    if event == _CACHE_LOAD:
        # fires inside the backend timer of the call that hit
        if getattr(_local, "depth", 0) == 1:
            _local.cache_load = seconds
        return
    stage = _STAGES.get(event)
    if stage is None:
        return
    depth = _local.depth = max(0, getattr(_local, "depth", 0) - 1)
    if depth:
        return
    if stage == "backend_compile":
        load = getattr(_local, "cache_load", None)
        _local.cache_load = None
        if load is not None:
            _record("cache_load", load, fun_name)
            seconds = max(0.0, seconds - load)
    _record(stage, seconds, fun_name)


def _count_cache(outcome: str) -> None:
    get_registry().counter(
        CACHE_METRIC, "persistent compile cache lookups by outcome",
        labels=("outcome",)).labels(outcome=outcome).inc()


def _on_event(event: str, **_kw) -> None:
    outcome = _CACHE_OUTCOMES.get(event)
    if outcome is not None:
        _count_cache(outcome)


def install() -> None:
    """Register the listeners with ``jax.monitoring``; idempotent."""
    global _installed
    if _installed:
        return
    _installed = True
    import jax.monitoring as jm
    jm.register_scalar_listener(_on_start)
    jm.register_event_duration_secs_listener(_on_duration)
    jm.register_event_listener(_on_event)
