"""Step-phase tracing — lightweight spans over the hot paths.

``with span("fit/step", phase="h2d"):`` times a phase of work against a
per-thread span stack and lands the duration in the registry histogram
``dl4j_phase_seconds{span=...,phase=...}`` — so "where does a training
step spend its time" (data wait vs bucketing vs host-to-device vs the
jitted call vs blocking on the device) is a scrape away instead of a
profiler session ("Array Languages Make Neural Networks Fast":
whole-framework speedups start from knowing which phase dominates).

A loop whose phases must leave no time unnamed owns a
:class:`StepSpans` for its length: ``with steps.span("fit/step",
phase="jit_call"):`` is the same span, and whatever runs between two
phases is the phase ``glue``, so the phases tile the loop and there is
no "between".

Two optional bridges into JAX's own profiler:

* ``DL4J_TRACE_ANNOTATIONS=1`` (or :func:`enable_jax_annotations`)
  wraps every span in ``jax.profiler.TraceAnnotation`` so spans appear
  as named regions inside XLA profiler dumps;
* ``DL4J_PROFILE=<dir>`` makes :func:`profile_if_configured` (which
  ``MultiLayerNetwork.fit``/``ComputationGraph.fit`` enter) wrap the
  whole fit call in ``jax.profiler.start_trace(<dir>/fitN)`` with the
  annotations on, and write ``<dir>/fitN/summary.json`` on exit
  (monitor/profile.py: device time by scope, idle time by host phase).

``DL4J_SPANS=0`` turns span timing into a no-op (the A/B lever for
measuring span overhead).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from contextlib import contextmanager
from typing import Iterator, List, Optional

from deeplearning4j_tpu.monitor import events
from deeplearning4j_tpu.monitor.registry import (
    MetricsRegistry, get_registry)

PHASE_METRIC = "dl4j_phase_seconds"

log = logging.getLogger(__name__)

_local = threading.local()
_flags = {"jax_annotations": None, "enabled": None}
_profile = {"active": False, "count": 0, "lock": threading.Lock()}


class Span:
    __slots__ = ("name", "phase", "parent", "iteration", "duration")

    def __init__(self, name: str, phase: Optional[str],
                 parent: Optional["Span"], iteration: Optional[int] = None):
        self.name = name
        self.phase = phase
        self.parent = parent
        self.iteration = iteration
        self.duration: Optional[float] = None

    def __repr__(self):
        return (f"Span({self.name!r}, phase={self.phase!r}, "
                f"duration={self.duration})")


def _stack() -> List[Span]:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def current() -> Optional[Span]:
    """The innermost open span on this thread, or None."""
    st = _stack()
    return st[-1] if st else None


def set_enabled(on: Optional[bool]) -> None:
    """Force span timing on/off; ``None`` restores the env default
    (``DL4J_SPANS``)."""
    _flags["enabled"] = None if on is None else bool(on)


def enabled() -> bool:
    if _flags["enabled"] is not None:
        return _flags["enabled"]
    return os.environ.get("DL4J_SPANS", "1") != "0"


def enable_jax_annotations(on: bool = True) -> None:
    _flags["jax_annotations"] = bool(on)


def _annotations_enabled() -> bool:
    if _flags["jax_annotations"] is not None:
        return _flags["jax_annotations"]
    return os.environ.get("DL4J_TRACE_ANNOTATIONS") == "1"


def _annotate(name: str, phase: Optional[str]):
    """Open a region named ``name/phase`` in the profiler's trace."""
    try:
        import jax
        ann = jax.profiler.TraceAnnotation(
            f"{name}/{phase}" if phase else name)
        ann.__enter__()
        return ann
    except Exception:
        return None


def _annotate_end(ann) -> None:
    if ann is not None:
        try:
            ann.__exit__(None, None, None)
        except Exception:
            pass


def _begin(name: str, phase: Optional[str],
           iteration: Optional[int] = None) -> Span:
    """Open a span on this thread's stack.  The caller reads the clock
    (and opens the trace annotation, last, so that it covers the body
    alone)."""
    st = _stack()
    s = Span(name, phase, st[-1] if st else None, iteration)
    st.append(s)
    # the journal sees every span close with its trace context
    # (request_id / session_id / fit_id ride on the contextvars scope) —
    # this is what lets "why was THIS predict slow" be answered from the
    # event log.  Open events are verbose-only: close carries the
    # duration, and doubling hot-path emits breaks the ≤5% budget.
    if events.verbose():
        events.emit("span.open", span=name, phase=phase or "")
    return s


def _series(registry: Optional[MetricsRegistry], name: str,
            phase: Optional[str]):
    """The histogram series of one (span, phase)."""
    reg = registry if registry is not None else get_registry()
    return reg.histogram(
        PHASE_METRIC, "span phase wall time (seconds)",
        labels=("span", "phase"),
    ).labels(span=name, phase=phase or "")


def _end(s: Span, duration: float, series) -> None:
    """Close a span opened by :func:`_begin`: histogram, journal."""
    s.duration = duration
    st = _stack()
    if st and st[-1] is s:
        st.pop()
    series.observe(duration)
    events.emit("span.close", span=s.name, phase=s.phase or "",
                duration_s=duration)


@contextmanager
def span(name: str, phase: Optional[str] = None,
         registry: Optional[MetricsRegistry] = None) -> Iterator[Span]:
    """Time a phase of work.  Nested spans stack per-thread (``current()``
    sees the innermost); the duration lands in
    ``dl4j_phase_seconds{span=name, phase=phase}`` on exit — exceptions
    included, a failing step still accounts for its time."""
    if not enabled():
        yield Span(name, phase, None)
        return
    s = _begin(name, phase)
    ann = _annotate(name, phase) if _annotations_enabled() else None
    t0 = time.perf_counter()
    try:
        yield s
    finally:
        duration = time.perf_counter() - t0
        _annotate_end(ann)
        _end(s, duration, _series(registry, name, phase))


GLUE = "glue"


class StepSpans:
    """The phases of one loop, with no time between them.

    ``with steps.span(name, phase):`` is :func:`span`: the same
    :class:`Span`, histogram series, journal event and trace annotation,
    around the body alone.  What runs between one phase's end and the
    next one's start (the loop's own statements, the spans' own
    bookkeeping) is the phase ``glue`` of the same span: a histogram
    observation at every start, and, when annotating, a region
    ``<name>/glue`` that closes as the next phase's opens.  So the
    phases and their glue tile the loop from this object's construction
    to its last phase, in the registry and in the trace alike.  The loop
    owns one for the length of a ``fit()``: ``DL4J_SPANS`` and
    ``DL4J_TRACE_ANNOTATIONS`` are read once, here, and each series is
    looked up once, not at every span.  Not thread-safe: one loop, one
    thread."""

    __slots__ = ("_on", "_annotating", "_registry", "_t", "_series",
                 "_glue")

    def __init__(self, annotate: Optional[bool] = None,
                 registry: Optional[MetricsRegistry] = None):
        self._on = enabled()
        self._annotating = self._on and (
            _annotations_enabled() if annotate is None else annotate)
        self._registry = registry
        self._series: dict = {}
        self._glue = None                 # the open glue annotation
        self._t = time.perf_counter()     # where the last phase ended

    def span(self, name: str, phase: str,
             iteration: Optional[int] = None) -> "_Phase":
        return _Phase(self, name, phase, iteration)

    def close(self) -> None:
        """The loop is over, or hands over to code that times itself:
        end the open glue region."""
        _annotate_end(self._glue)
        self._glue = None

    def restart(self) -> None:
        """Back from code that timed itself (after :meth:`close`): the
        glue of the next phase starts now."""
        self._t = time.perf_counter()

    def _get(self, name: str, phase: str):
        series = self._series.get((name, phase))
        if series is None:
            series = self._series[name, phase] = _series(
                self._registry, name, phase)
        return series


class _Phase:
    """One ``with steps.span(...)``: yields the open :class:`Span`, or
    None when span timing is off."""

    __slots__ = ("_steps", "_args", "_span", "_ann")

    def __init__(self, steps: StepSpans, name: str, phase: str,
                 iteration: Optional[int]):
        self._steps = steps
        self._args = (name, phase, iteration)
        self._span: Optional[Span] = None
        self._ann = None

    def __enter__(self) -> Optional[Span]:
        steps = self._steps
        if steps._on:
            name, phase, iteration = self._args
            self._span = _begin(name, phase, iteration)
            glue = steps._get(name, GLUE)
            if steps._annotating:
                steps.close()
                self._ann = _annotate(name, phase)
            t = time.perf_counter()
            glue.observe(t - steps._t)
            steps._t = t
        return self._span

    def __exit__(self, *exc) -> bool:
        s = self._span
        if s is not None:
            steps = self._steps
            t = time.perf_counter()
            if steps._annotating:
                _annotate_end(self._ann)
                steps._glue = _annotate(s.name, GLUE)
            _end(s, t - steps._t, steps._get(s.name, s.phase))
            steps._t = t
        return False


@contextmanager
def profile_if_configured(tag: str = "fit") -> Iterator[bool]:
    """Yields False unless ``DL4J_PROFILE=<dir>`` is set; then the body
    runs under ``jax.profiler.start_trace(<dir>/<tag><N>)`` (Python
    tracer off: it slows the host it measures), yields True so that the
    caller mirrors its spans into the trace, and on exit
    ``<dir>/<tag><N>/summary.json`` is written (monitor/profile.py).
    Re-entrant calls (fit inside fit, concurrent fits) skip — JAX
    allows one live trace per process."""
    d = os.environ.get("DL4J_PROFILE")
    if not d:
        yield False
        return
    with _profile["lock"]:
        started = not _profile["active"]
        if started:
            _profile["active"] = True
            path = os.path.join(d, f"{tag}{_profile['count']}")
            _profile["count"] += 1
    if not started:
        yield False
        return
    try:
        import jax
        os.makedirs(path, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(path, profiler_options=opts)
    except Exception:
        with _profile["lock"]:
            _profile["active"] = False
        yield False
        return
    try:
        yield True
    finally:
        try:
            jax.profiler.stop_trace()
            from deeplearning4j_tpu.monitor import profile
            profile.write_summary(path)
        except Exception:
            log.warning("DL4J_PROFILE: no summary for %s", path,
                        exc_info=True)
        finally:
            with _profile["lock"]:
                _profile["active"] = False
