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
no "between".  Such a phase journals nothing of its own: the loop closes
every step with ``steps.step_done(iteration)``, and the step is ONE
``fit.step`` event that holds its phases beside what the host thread did
meanwhile; a step that ran late by :func:`stall_excess` names the phase
that held it (``fit.stall``, ``dl4j_fit_stalls_total{phase}``).  A plain
:func:`span` (serving, ``pipeline/batch``, ``net/init``) journals its
``span.close`` as ever.

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

import gc
import logging
import os
import resource
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Iterator, List, Optional

from deeplearning4j_tpu.monitor import events
from deeplearning4j_tpu.monitor.registry import (
    MetricsRegistry, get_registry)

PHASE_METRIC = "dl4j_phase_seconds"
STALLS_METRIC = "dl4j_fit_stalls_total"
STALL_SECONDS_METRIC = "dl4j_fit_stall_seconds_total"

#: A step that did not compile is a stall when its wall time is over the
#: median of the steps around it by more than the larger of these two:
#: 2% is ``step_ms_p95``'s bound (one such step among the places the
#: percentile reads breaches it alone), and 4 ms clears the 1 to 1.5 ms
#: of standard deviation that a sparse-expert step's routing gives the
#: steps proper.
STALL_SHARE = 0.02
STALL_FLOOR_S = 0.004
#: steps a :class:`StepSpans` keeps, and how many of them must not have
#: compiled before the first is judged
RING_STEPS = 64
JUDGED_FROM = 8


def median(values) -> float:
    ordered = sorted(values)
    n = len(ordered)
    return (ordered[(n - 1) // 2] + ordered[n // 2]) / 2


def stall_excess(step_s: float, median_s: float) -> float:
    """Seconds by which a step ran over the median of its neighbours,
    or 0.0 where that is no stall.  The one rule: the loop applies it to
    its ring of steps, ``monitor/profile_steps.py`` to a trace's."""
    excess = step_s - median_s
    return excess if excess > max(STALL_FLOOR_S,
                                  STALL_SHARE * median_s) else 0.0


log = logging.getLogger(__name__)

_local = threading.local()
_flags = {"jax_annotations": None, "enabled": None}
_profile = {"active": False, "count": 0, "lock": threading.Lock()}


class Span:
    __slots__ = ("name", "phase", "parent", "iteration", "duration",
                 "compile_s")

    def __init__(self, name: str, phase: Optional[str],
                 parent: Optional["Span"], iteration: Optional[int] = None):
        self.name = name
        self.phase = phase
        self.parent = parent
        self.iteration = iteration
        self.duration: Optional[float] = None
        #: seconds monitor/compile_stages.py charged to this span
        self.compile_s = 0.0

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


def _close(s: Span, duration: float, series) -> None:
    """Close a span opened by :func:`_begin`: off the stack, into its
    histogram."""
    s.duration = duration
    st = _stack()
    if st and st[-1] is s:
        st.pop()
    series.observe(duration)


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
        _close(s, duration, _series(registry, name, phase))
        events.emit("span.close", span=name, phase=phase or "",
                    duration_s=duration)


GLUE = "glue"

# the collector's pauses, process-wide: a running sum that every
# StepSpans reads the difference of at its steps' ends
_gc = {"t0": 0.0, "pause_s": 0.0, "collections": 0}


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _gc["t0"] = time.perf_counter()
    else:
        _gc["pause_s"] += time.perf_counter() - _gc["t0"]
        _gc["collections"] += 1


def install_gc_hook() -> None:
    """Time the collector's pauses through ``gc.callbacks``; idempotent
    (``monitor`` installs it once at import)."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


#: what the host did, as a record names it: this thread's, then the
#: process's collector
_HOST_KEYS = ("cpu_s", "switches_voluntary", "switches_involuntary",
              "major_faults", "gc_s", "gc_collections")


def _host_now() -> tuple:
    ru = resource.getrusage(resource.RUSAGE_THREAD)
    return (time.thread_time(), ru.ru_nvcsw, ru.ru_nivcsw, ru.ru_majflt,
            _gc["pause_s"], _gc["collections"])


class StepSpans:
    """The phases of one loop, with no time between them, and a record
    of every step.

    ``with steps.span(name, phase):`` is :func:`span` around the body
    alone: the same :class:`Span` on the thread's stack, histogram
    series and trace annotation, but no journal event of its own.  What
    runs between one phase's end and the next one's start (the loop's
    own statements, the spans' own bookkeeping) is the phase ``glue`` of
    the same span: a histogram observation at every start, and, when
    annotating, a region ``<name>/glue`` that closes as the next phase's
    opens.  So the phases and their glue tile the loop from this
    object's construction to its last phase, in the registry and in the
    trace alike.

    :meth:`step_done` closes the open step's record: its wall time since
    the last close, its phases in loop order (they sum to the wall
    time), and what the host did meanwhile as differences since the last
    close (``cpu_s``, ``switches_voluntary``, ``switches_involuntary``,
    ``major_faults`` of this thread; ``gc_s``, ``gc_collections`` of the
    process; ``compile_s`` charged by ``monitor/compile_stages.py``).
    The record is journalled as one ``fit.step`` event and kept among
    the last ``RING_STEPS``.  A step that did not compile is judged by
    :func:`stall_excess` against the median of the last ``RING_STEPS``
    such steps, once ``JUDGED_FROM`` of them are there (the first ones
    are judged then, together; the record closed first says
    ``first_of_fit``).  For a stall, the phase furthest over its own
    median in the ring is the holder: counters
    ``dl4j_fit_stalls_total{phase}`` and
    ``dl4j_fit_stall_seconds_total{phase}`` (registered here, so a loop
    without a stall reads 0), and the event ``fit.stall``.

    The loop owns one for the length of a ``fit()``: ``DL4J_SPANS`` and
    ``DL4J_TRACE_ANNOTATIONS`` are read once, here, and each series is
    looked up once, not at every span.  With ``DL4J_SPANS=0`` nothing of
    this runs.  Not thread-safe: one loop, one thread."""

    __slots__ = ("_on", "_annotating", "_registry", "_t", "_series",
                 "_glue", "_name", "_phases", "_compile_s", "_t0", "_host",
                 "_ring", "_walls", "_unjudged", "_first", "_stalls")

    def __init__(self, annotate: Optional[bool] = None,
                 registry: Optional[MetricsRegistry] = None):
        self._on = enabled()
        self._annotating = self._on and (
            _annotations_enabled() if annotate is None else annotate)
        self._registry = registry
        self._series: dict = {}
        self._glue = None                 # the open glue annotation
        self._name = ""                   # the loop's span name
        self._phases: dict = {}           # the open step's {phase: seconds}
        self._compile_s = 0.0
        self._ring: deque = deque(maxlen=RING_STEPS)
        self._walls: deque = deque(maxlen=RING_STEPS)   # of quiet steps
        self._unjudged: list = []         # closed before JUDGED_FROM were
        self._first: Optional[dict] = None
        self._stalls = None
        if self._on:
            reg = registry if registry is not None else get_registry()
            self._stalls = (
                reg.counter(STALLS_METRIC, "steps that ran over the median "
                            "of the steps around them by more than 4 ms "
                            "and 2%, by the phase that held them",
                            labels=("phase",)),
                reg.counter(STALL_SECONDS_METRIC, "seconds by which they "
                            "ran over that median", labels=("phase",)))
            self._host = _host_now()
        # where the last phase ended, and the last step
        self._t = self._t0 = time.perf_counter()

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
        glue of the next phase starts now, and the stretch is no part of
        the open step's wall time."""
        t = time.perf_counter()
        self._t0 += t - self._t
        self._t = t

    def _get(self, name: str, phase: str):
        series = self._series.get((name, phase))
        if series is None:
            series = self._series[name, phase] = _series(
                self._registry, name, phase)
        return series

    def _add_glue(self, name: str, t: float) -> None:
        """What ran since the last phase ended, up to ``t``."""
        glue = t - self._t
        self._get(name, GLUE).observe(glue)
        phases = self._phases
        phases[GLUE] = phases.get(GLUE, 0.0) + glue
        self._t = t

    def step_done(self, iteration: int, compiling: bool = False,
                  k: int = 1) -> None:
        """The step is over (``k`` iterations of one launch, ending at
        ``iteration``): close its record, journal it, judge it."""
        if not self._on:
            return
        t = time.perf_counter()
        self._add_glue(self._name, t)
        host, last = _host_now(), self._host
        rec = {"span": self._name, "iteration": iteration, "k": k,
               "compiling": bool(compiling) or self._compile_s > 0.0,
               "step_s": t - self._t0, "phases": self._phases,
               "compile_s": self._compile_s}
        for key, now, then in zip(_HOST_KEYS, host, last):
            rec[key] = now - then
        self._t0, self._host = t, host
        self._phases, self._compile_s = {}, 0.0
        if self._first is None:
            self._first = rec
        self._ring.append(rec)
        events.emit("fit.step", **rec)
        if rec["compiling"]:
            return
        self._walls.append(rec["step_s"])
        if len(self._walls) < JUDGED_FROM:
            self._unjudged.append(rec)
            return
        middle = median(self._walls)
        held, self._unjudged = self._unjudged, []
        for r in (*held, rec):
            if stall_excess(r["step_s"], middle):
                self._stall(r, middle)

    def _stall(self, rec: dict, middle: float) -> None:
        quiet = [r["phases"] for r in self._ring if not r["compiling"]]
        over = {p: s - median([q.get(p, 0.0) for q in quiet])
                for p, s in rec["phases"].items()}
        holder = max(over, key=over.get)
        excess = rec["step_s"] - middle
        stalls, seconds = self._stalls
        stalls.labels(phase=holder).inc()
        seconds.labels(phase=holder).inc(excess)
        events.emit("fit.stall", "warn", holder=holder, excess_s=excess,
                    median_s=middle, phase_excess_s=over,
                    first_of_fit=rec is self._first, **rec)


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
            if steps._annotating:
                steps.close()
                self._ann = _annotate(name, phase)
            steps._add_glue(name, time.perf_counter())
        return self._span

    def __exit__(self, *exc) -> bool:
        s = self._span
        if s is not None:
            steps = self._steps
            t = time.perf_counter()
            if steps._annotating:
                _annotate_end(self._ann)
                steps._glue = _annotate(s.name, GLUE)
            duration = t - steps._t
            _close(s, duration, steps._get(s.name, s.phase))
            phases = steps._phases
            phases[s.phase] = phases.get(s.phase, 0.0) + duration
            steps._compile_s += s.compile_s
            steps._name = s.name
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
