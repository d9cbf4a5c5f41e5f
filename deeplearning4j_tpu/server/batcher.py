"""Dynamic micro-batching for the serving path.

Concurrent ``predict`` requests arrive one or a few rows at a time; a
jitted XLA ``output`` call costs nearly the same to dispatch for 1 row
as for 32 — so answering requests one-at-a-time leaves most of the
hardware idle ("Array Languages Make Neural Networks Fast": batched,
compile-cached execution is where array frameworks win).  The
:class:`MicroBatcher` coalesces: requests enqueue rows with a future, a
batcher thread gathers up to ``max_batch`` rows (waiting at most
``max_wait_ms`` after the batch's first request), pads the gathered
batch up to the bucket ladder (``ops/bucketing.py``) so the jitted
callable compiles once per bucket instead of once per row-count, runs
ONE ``output`` call, and scatters per-request slices back.

Correctness: rows are independent at inference (no batch statistics —
BatchNorm uses running stats), so zero-row padding and slicing back is
exact, and a request's rows produce the same values whether they ran
alone or co-batched (the concurrent-vs-serial parity test pins this).
Requests whose row shape/dtype differs from their batch-mates are
grouped and run separately rather than failing the whole batch.

Telemetry: per-request queue/compute/total latency
(``nn/listeners.LatencyHistogram`` percentile snapshots) and a
batch-size histogram, surfaced through the gateway's ``stats`` RPC.
"""

from __future__ import annotations

import logging
import threading
import time
from concurrent.futures import Future
from typing import Callable, List, Optional, Sequence

import jax
import numpy as np

log = logging.getLogger(__name__)

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.analysis import sanitizer
from deeplearning4j_tpu.monitor import events, flight
from deeplearning4j_tpu.ops import bucketing
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.resilience.errors import DeadlineExceededError


class _Pending:
    __slots__ = ("x", "future", "t_enqueue", "deadline", "tenant", "ctx")

    def __init__(self, x, future, t_enqueue, deadline=None, tenant=None,
                 ctx=None):
        self.x = x
        self.future = future
        self.t_enqueue = t_enqueue
        self.deadline = deadline  # absolute time.monotonic(), or None
        self.tenant = tenant      # fair-share admission attribution
        # trace context captured at enqueue: the batcher thread re-
        # attaches it to the events it emits on this request's behalf
        self.ctx = ctx or {}

    @property
    def request_id(self):
        return self.ctx.get("request_id")


class ServingMetrics:
    """Per-batcher serving telemetry: request latency split into queue
    (enqueue → batch dispatch), compute (the jitted call), and total
    (enqueue → result), plus how well coalescing is working (batch-size
    histogram, rows per batch).

    The latency recorders are registry histograms
    (``dl4j_serving_{queue,compute,total}_seconds{model=...}``, each a
    ``LatencyHistogram`` reservoir plus Prometheus buckets) so one
    ``/metrics`` scrape sees every batcher; ``snapshot()`` keeps the
    stats RPC's legacy ``*_ms`` dict shape on top of the same data."""

    def __init__(self, name: str = ""):
        reg = monitor.get_registry()
        self._lock = threading.Lock()
        lbl = {"model": name or "default"}
        self.queue = reg.histogram(
            "dl4j_serving_queue_seconds",
            "request enqueue → batch dispatch", ("model",)).labels(**lbl)
        self.compute = reg.histogram(
            "dl4j_serving_compute_seconds",
            "batched jitted inference call", ("model",)).labels(**lbl)
        self.total = reg.histogram(
            "dl4j_serving_total_seconds",
            "request enqueue → result", ("model",)).labels(**lbl)
        # requests carry a tenant label for fair-share attribution; the
        # family is incremented per request at dispatch (not per batch)
        # so per-tenant series sum to the model's total without double
        # counting
        self._f_requests = reg.counter(
            "dl4j_serving_requests_total", "predict requests served",
            ("model", "tenant"))
        self._model = lbl["model"]
        self._c_rows = reg.counter(
            "dl4j_serving_rows_total", "rows served", ("model",)).labels(**lbl)
        self._c_batches = reg.counter(
            "dl4j_serving_batches_total", "coalesced batches dispatched",
            ("model",)).labels(**lbl)
        self.requests = 0
        self.rows = 0
        self.batches = 0
        self.shed = {}
        self.batch_size_hist = {}

    def record_shed(self, reason: str) -> None:
        with self._lock:
            self.shed[reason] = self.shed.get(reason, 0) + 1

    def record_request(self, tenant=None) -> None:
        self._f_requests.labels(model=self._model,
                                tenant=tenant or "-").inc()

    def record_batch(self, n_requests: int, n_rows: int) -> None:
        with self._lock:
            self.requests += n_requests
            self.rows += n_rows
            self.batches += 1
            self.batch_size_hist[n_rows] = \
                self.batch_size_hist.get(n_rows, 0) + 1
        self._c_rows.inc(n_rows)
        self._c_batches.inc()

    def snapshot(self) -> dict:
        # one acquisition for the whole snapshot: two sequential locked
        # reads could interleave with a record_batch/record_shed and
        # return counters from two different instants
        with self._lock:
            requests, rows, batches = self.requests, self.rows, self.batches
            hist = {str(k): v for k, v in
                    sorted(self.batch_size_hist.items())}
            shed = dict(self.shed)
        return {
            "requests": requests,
            "rows": rows,
            "batches": batches,
            "shed": shed,
            "rows_per_batch_mean": round(rows / batches, 2) if batches else 0.0,
            "requests_per_batch_mean":
                round(requests / batches, 2) if batches else 0.0,
            "batch_size_hist": hist,
            "queue_ms": self.queue.latency_snapshot(),
            "compute_ms": self.compute.latency_snapshot(),
            "total_ms": self.total.latency_snapshot(),
        }


class MicroBatcher:
    """Coalesce concurrent few-row ``predict`` calls into one jitted
    ``output`` call.

    ``infer_fn(x: np.ndarray[B, ...]) -> np.ndarray[B, ...]`` must be
    row-aligned (row i of the output belongs to row i of the input).
    ``max_batch`` bounds gathered rows per dispatch (a single oversized
    request still runs, alone).  Dispatch is backpressure-driven: the
    batcher takes everything queued and runs it immediately — while the
    jitted call executes, new requests pile up and form the next batch,
    so coalescing emerges from load without adding idle wait to the
    request path.  ``min_batch > 1`` opts into explicit coalescing
    windows: the batch is held until it has ``min_batch`` rows or
    ``max_wait_ms`` has passed since its first request — ``max_wait_ms``
    bounds how long a lone request can wait for company, it is never
    stuck waiting for a full batch.  ``pad_to_bucket`` zero-pads the
    gathered batch up to the ``bucket_sizes`` ladder (powers of two when
    None) and slices the padding back off; turn it off when the model
    already buckets internally (``conf.shape_bucketing``)."""

    def __init__(self, infer_fn: Callable[[np.ndarray], np.ndarray],
                 max_batch: int = 32, max_wait_ms: float = 5.0,
                 min_batch: int = 1,
                 bucket_sizes: Optional[Sequence[int]] = None,
                 pad_to_bucket: bool = True, name: str = ""):
        self._infer_fn = infer_fn
        self.max_batch = max(1, int(max_batch))
        self.min_batch = max(1, min(int(min_batch), self.max_batch))
        self.max_wait_s = max(0.0, float(max_wait_ms)) / 1e3
        self._bucket_sizes = (list(bucket_sizes) if bucket_sizes else None)
        self._pad = bool(pad_to_bucket)
        self.metrics = ServingMetrics(name)
        self._queue: List[_Pending] = []
        self._cond = threading.Condition()
        self._running = True
        self._name = name
        self._inflight: List[_Pending] = []
        self._dead = False  # set by the crash handler BEFORE the dying
        # thread's is_alive() goes False — submit() keys restarts off it
        self.deaths = 0
        self.restarts = 0
        reg = monitor.get_registry()
        self._c_shed = reg.counter(
            "dl4j_resilience_shed_total",
            "requests shed instead of served", labels=("reason",))
        self._c_deaths = reg.counter(
            "dl4j_resilience_batcher_deaths_total",
            "micro-batcher threads that died unexpectedly")
        self._c_restarts = reg.counter(
            "dl4j_resilience_batcher_restarts_total",
            "micro-batcher threads restarted after a death")
        self._thread = self._spawn_thread()

    def _spawn_thread(self) -> threading.Thread:
        t = threading.Thread(
            target=self._loop_guarded, daemon=True,
            name=f"micro-batcher:{self._name or hex(id(self))}")
        t.start()
        return t

    # ------------------------------------------------------------------
    # Client side
    # ------------------------------------------------------------------
    def submit(self, features, timeout_ms: Optional[float] = None,
               tenant: Optional[str] = None) -> Future:
        """Enqueue a ``[k, ...]`` row batch; the future resolves to the
        ``[k, ...]`` output slice for exactly those rows.

        ``timeout_ms`` is the request's deadline budget: if it expires
        while the request is still queued, the request is SHED before
        compute (the future fails with :class:`DeadlineExceededError`)
        instead of burning a jitted call on an answer nobody is waiting
        for.  ``tenant`` attributes the queued rows (and the served
        request counter) for the gateway's fair-share admission."""
        x = np.asarray(features)
        if x.ndim < 1 or x.shape[0] == 0:
            raise ValueError("submit() needs a non-empty [k, ...] row batch")
        deadline = (None if timeout_ms is None
                    else time.monotonic() + float(timeout_ms) / 1e3)
        t_enqueue = time.perf_counter()
        ctx = events.current_context()
        restarted = False
        with self._cond:
            if not self._running:
                raise RuntimeError("MicroBatcher is stopped")
            # dead-thread detection: a batcher thread killed by a crash
            # must not strand clients — restart it on the next request
            if self._dead or not self._thread.is_alive():
                self._dead = False
                self.restarts += 1
                self._c_restarts.inc()
                self._thread = self._spawn_thread()
                restarted = True
            # the future is only born once the request is admitted — a
            # rejected submit must not mint one (dl4j-check's resolved-
            # on-all-schedules obligation counts every future)
            fut = Future()
            self._queue.append(_Pending(x, fut, t_enqueue, deadline,
                                        tenant, ctx=ctx))
            self._cond.notify_all()
        if restarted:
            events.emit("batcher.restarted", model=self._name)
        # verbose-only: request.admitted (gateway) already witnessed
        # this request microseconds ago on the same thread, and
        # batch.dispatch's request_ids prove queue membership — a third
        # always-on per-request emit breaks the ≤5% serving budget
        if events.verbose():
            events.emit("request.enqueued", rows=len(x), model=self._name)
        return fut

    def predict(self, features, timeout: Optional[float] = None,
                timeout_ms: Optional[float] = None,
                tenant: Optional[str] = None):
        """Blocking convenience wrapper around :meth:`submit`.
        ``timeout`` (seconds) bounds the client-side wait; ``timeout_ms``
        is the server-side deadline budget (queued past it = shed)."""
        return self.submit(features, timeout_ms=timeout_ms,
                           tenant=tenant).result(timeout)

    def queue_rows(self) -> int:
        """Rows currently waiting for dispatch — the admission-control
        signal the gateway checks against its queue limit."""
        with self._cond:
            return sum(len(p.x) for p in self._queue)

    def queue_rows_by_tenant(self) -> dict:
        """Queued rows attributed per tenant — the fair-share admission
        signal (requests without a tenant pool under ``"-"``)."""
        with self._cond:
            out: dict = {}
            for p in self._queue:
                t = p.tenant or "-"
                out[t] = out.get(t, 0) + len(p.x)
            return out

    @property
    def thread_alive(self) -> bool:
        return self._thread.is_alive()

    def stop(self, timeout: float = 5.0) -> None:
        """Drain in-flight work, stop the batcher thread, and fail any
        requests that could not be drained."""
        with self._cond:
            self._running = False
            self._cond.notify_all()
        self._thread.join(timeout)
        with self._cond:
            leftovers, self._queue = self._queue, []
        for p in leftovers:
            if not p.future.done():
                p.future.set_exception(RuntimeError("MicroBatcher stopped"))

    # ------------------------------------------------------------------
    # Batcher thread
    # ------------------------------------------------------------------
    def _take_batch(self) -> List[_Pending]:
        """Block until work exists, then drain everything queued up to
        ``max_batch`` rows.  A request that would overflow the batch is
        left for the next one (keeps dispatched row counts — and
        therefore compiled bucket shapes — bounded by ``max_batch``),
        unless it would be alone anyway.  With ``min_batch > 1`` the
        drain keeps waiting for more rows until ``min_batch`` is reached
        or ``max_wait_s`` has passed since the batch's first request."""
        with self._cond:
            while self._running and not self._queue:
                self._cond.wait(0.1)
            if not self._queue:
                return []
            deadline = time.perf_counter() + self.max_wait_s
            taken: List[_Pending] = []
            rows = 0
            while True:
                while self._queue:
                    nxt = len(self._queue[0].x)
                    if taken and rows + nxt > self.max_batch:
                        break
                    p = self._queue.pop(0)
                    taken.append(p)
                    rows += nxt
                if rows >= self.min_batch or not self._running:
                    break
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                self._cond.wait(remaining)
            return taken

    def _shed_expired(self, taken: List[_Pending]) -> List[_Pending]:
        """Drop requests whose deadline budget expired while queued —
        BEFORE compute, so the jitted call never runs for a client that
        has already given up."""
        now = time.monotonic()
        keep: List[_Pending] = []
        for p in taken:
            if p.deadline is not None and now >= p.deadline:
                self.metrics.record_shed("deadline")
                self._c_shed.labels(reason="deadline").inc()
                events.emit("request.shed", severity="warn",
                            reason="deadline", model=self._name,
                            request_id=p.request_id, tenant=p.tenant)
                if not p.future.done():
                    p.future.set_exception(DeadlineExceededError(
                        "request deadline expired while queued "
                        f"({(now - p.deadline) * 1e3:.1f} ms past budget)"))
            else:
                keep.append(p)
        return keep

    def _run_group(self, group: List[_Pending]) -> None:
        t_dispatch = time.perf_counter()
        # the ONE compute span for this batch is linked to the N
        # coalesced request spans by carrying every joined request ID in
        # the batcher thread's trace context — the journal answers
        # "which requests rode the batch that failed/was slow"
        rids = [p.request_id for p in group if p.request_id]
        try:
            with events.scope(model=self._name or None,
                              request_ids=rids or None):
                faults.check("batcher.compute")
                with monitor.span("serve/batch", phase="concat_pad"):
                    xs = [p.x for p in group]
                    x = np.concatenate(xs) if len(xs) > 1 else xs[0]
                    n = len(x)
                    if self._pad:
                        nb = bucketing.bucket_size(n, self._bucket_sizes)
                        if nb != n:
                            x = np.concatenate(
                                [x, np.zeros((nb - n,) + x.shape[1:],
                                             x.dtype)])
                events.emit("batch.dispatch", requests=len(group), rows=n)
                t0 = time.perf_counter()
                with monitor.span("serve/batch", phase="compute"), \
                        sanitizer.guard_step():
                    # explicit device->host pull (jax.device_get), not an
                    # implicit np.asarray sync: the sanitizer's transfer
                    # guard allows explicit transfers, and a non-jax
                    # output (plain numpy infer_fn) passes through
                    # unchanged
                    out = np.asarray(jax.device_get(self._infer_fn(x)))[:n]
                t1 = time.perf_counter()
            i = 0
            for p in group:
                k = len(p.x)
                p.future.set_result(out[i:i + k])
                i += k
            verbose = events.verbose()
            for p in group:
                self.metrics.queue.record(t_dispatch - p.t_enqueue)
                self.metrics.compute.record(t1 - t0)
                self.metrics.total.record(t1 - p.t_enqueue)
                self.metrics.record_request(p.tenant)
                # per-request completion events are verbose-only: the
                # response hop is already witnessed per request by
                # rpc.response (HTTP) and per batch by the compute
                # span.close carrying request_ids — a per-request emit
                # on the batcher's critical path breaks the ≤5% budget
                if verbose:
                    events.emit("request.done", model=self._name,
                                request_id=p.request_id, tenant=p.tenant,
                                rows=len(p.x),
                                total_s=round(t1 - p.t_enqueue, 6))
            self.metrics.record_batch(len(group), n)
        except Exception as e:
            for p in group:
                if not p.future.done():
                    p.future.set_exception(e)

    def _loop_guarded(self) -> None:
        """The batcher thread body plus its crash handler.  A
        ``BaseException`` escaping the loop (a killed thread — e.g. an
        armed ``mode="kill"`` fault, or a fatal interpreter error) used
        to strand every pending future in a forever-block; now the
        handler fails in-flight and queued requests with an error result
        and the next :meth:`submit` restarts the thread."""
        death_err = None
        try:
            self._loop()
        except BaseException as e:
            # recorded here (not re-raised): the death is fully handled
            # below, and a daemon thread's unhandled-exception spew
            # would just double-report it
            death_err = e
            log.error("micro-batcher %r thread died: %s: %s",
                      self._name, type(e).__name__, e)
        finally:
            with self._cond:
                died = self._running  # normal stop() exits are not deaths
                stranded = self._inflight + self._queue
                self._inflight = []
                if died:
                    self._queue = []
                    self.deaths += 1
                    self._dead = True
            if died:
                self._c_deaths.inc()
                for p in stranded:
                    if not p.future.done():
                        p.future.set_exception(RuntimeError(
                            "MicroBatcher thread died; request failed "
                            "(the batcher restarts on the next submit)"))
                # black box: journal the death with the stranded request
                # IDs, then dump the last-N events + registry snapshot
                # so "what happened in the 2s before the batcher died"
                # survives the thread
                rids = [p.request_id for p in stranded if p.request_id]
                events.emit(
                    "batcher.died", severity="error", model=self._name,
                    error=(f"{type(death_err).__name__}: {death_err}"
                           if death_err is not None else "unknown"),
                    stranded=len(stranded), request_ids=rids or None)
                flight.dump("batcher_died", extra={
                    "batcher": self._name,
                    "stranded_request_ids": rids,
                    "error": repr(death_err)})

    def _loop(self) -> None:
        while True:
            taken = self._take_batch()
            if not taken:
                if not self._running:
                    return
                continue
            taken = self._shed_expired(taken)
            if not taken:
                continue
            # one dispatch per (row-shape, dtype) group: a client sending
            # mismatched rows must not fail its batch-mates
            groups: dict = {}
            for p in taken:
                groups.setdefault(
                    (p.x.shape[1:], str(p.x.dtype)), []).append(p)
            for group in groups.values():
                with self._cond:
                    self._inflight = list(group)
                self._run_group(group)
                with self._cond:
                    self._inflight = []
