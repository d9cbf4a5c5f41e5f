"""DataSet iterators, including the parallel async input pipeline.

The reference wraps every training iterator in an
``AsyncDataSetIterator`` — a background thread filling a BlockingQueue
(ref: datasets/iterator/AsyncDataSetIterator.java:39-127).  Here that
design is generalized into a multi-worker ETL pipeline:

    feeder ──▶ task queue ──▶ N workers ──▶ reorder buffer ──▶ consumer
    (serial raw pull,          (collate → normalize →          (ordered,
     order = sync iterator)     transform → device_put)         bounded)

The feeder pulls *raw* batches serially (readers are stateful, so this
is what keeps batch order deterministic and identical to the sync
iterator); workers run the ETL chain in parallel and stage finished,
already-``device_put`` batches into an order-preserving reorder buffer
bounded by ``staging_depth``, so H2D transfer overlaps the jitted step
and the device never waits on ETL.  Iterators that can split "pull raw
records" from "assemble arrays" expose ``next_raw()``/``collate()``
(records/iterators.py does) so the expensive vectorized assembly also
runs on the workers.

Everything meters into the ``dl4j_pipeline_*`` registry families
(docs/OBSERVABILITY.md); the consumer-side wait is the fit loops'
``data_wait`` phase.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
import weakref
from typing import Iterator, List, Optional

import numpy as np

log = logging.getLogger(__name__)

from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet


class DataSetIterator:
    """Iterator contract (ref: nd4j DataSetIterator consumed throughout)."""

    def __iter__(self) -> Iterator[DataSet]:
        self.reset()
        return self

    def __next__(self) -> DataSet:
        if not self.has_next():
            raise StopIteration
        return self.next()

    def next(self) -> DataSet:
        raise NotImplementedError

    def has_next(self) -> bool:
        raise NotImplementedError

    def reset(self) -> None:
        raise NotImplementedError

    def batch_size(self) -> int:
        raise NotImplementedError

    def async_supported(self) -> bool:
        return True


class ListDataSetIterator(DataSetIterator):
    """Iterate a list of pre-built minibatches (ref: ListDataSetIterator)."""

    def __init__(self, data, batch: Optional[int] = None):
        if isinstance(data, DataSet):
            data = data.batch_by(batch) if batch else [data]
        self._data: List[DataSet] = list(data)
        self._i = 0

    def next(self):
        d = self._data[self._i]
        self._i += 1
        return d

    def has_next(self):
        return self._i < len(self._data)

    def reset(self):
        self._i = 0

    def batch_size(self):
        return self._data[0].num_examples() if self._data else 0


class ExistingDataSetIterator(DataSetIterator):
    """Wrap any python iterable of DataSets (ref: ExistingDataSetIterator)."""

    def __init__(self, iterable_factory):
        self._factory = iterable_factory
        self._it = iter(iterable_factory())
        self._peek = None
        self._advance()

    def _advance(self):
        try:
            self._peek = next(self._it)
        except StopIteration:
            self._peek = None

    def next(self):
        d = self._peek
        self._advance()
        return d

    def has_next(self):
        return self._peek is not None

    def reset(self):
        self._it = iter(self._factory())
        self._advance()

    def batch_size(self):
        return self._peek.num_examples() if self._peek else 0


class MultipleEpochsIterator(DataSetIterator):
    """Repeat an underlying iterator N epochs (ref: MultipleEpochsIterator)."""

    def __init__(self, epochs: int, underlying: DataSetIterator):
        self.epochs = epochs
        self.underlying = underlying
        self._epoch = 0

    def next(self):
        if not self.underlying.has_next():
            self.underlying.reset()
            self._epoch += 1
        return self.underlying.next()

    def has_next(self):
        return self.underlying.has_next() or self._epoch < self.epochs - 1

    def reset(self):
        self.underlying.reset()
        self._epoch = 0

    def batch_size(self):
        return self.underlying.batch_size()


class SamplingDataSetIterator(DataSetIterator):
    """Sample minibatches with replacement from one DataSet
    (ref: SamplingDataSetIterator)."""

    def __init__(self, dataset: DataSet, batch: int, total_batches: int, seed: int = 0):
        self.dataset = dataset
        self.batch = batch
        self.total = total_batches
        self._rng = np.random.default_rng(seed)
        self._count = 0

    def next(self):
        idx = self._rng.integers(0, self.dataset.num_examples(), self.batch)
        self._count += 1
        d = self.dataset
        return DataSet(d.features[idx], d.labels[idx],
                       None if d.features_mask is None else d.features_mask[idx],
                       None if d.labels_mask is None else d.labels_mask[idx])

    def has_next(self):
        return self._count < self.total

    def reset(self):
        self._count = 0

    def batch_size(self):
        return self.batch


def _pipeline_metrics():
    """dl4j_pipeline_* instruments (lazy import: datasets must stay
    importable before the monitor package finishes initializing)."""
    global _METRICS
    if _METRICS is None:
        from deeplearning4j_tpu import monitor
        reg = monitor.get_registry()
        _METRICS = {
            "batches": reg.counter(
                "dl4j_pipeline_batches_total",
                "input-pipeline batches by stage "
                "(produced=raw pull, transformed=ETL done, consumed=handed"
                " to the training loop)", labels=("stage",)),
            "queue_depth": reg.gauge(
                "dl4j_pipeline_queue_depth",
                "current depth of the pipeline queues "
                "(task=raw batches awaiting ETL, ready=staged batches "
                "awaiting the consumer)", labels=("queue",)),
            "busy": reg.counter(
                "dl4j_pipeline_worker_busy_seconds_total",
                "cumulative wall time ETL workers spent transforming"),
            "staged_bytes": reg.counter(
                "dl4j_pipeline_staged_bytes_total",
                "bytes of batches staged through the reorder buffer"),
            "workers": reg.gauge(
                "dl4j_pipeline_workers",
                "worker threads of the most recently started pipeline"),
        }
    return _METRICS


_METRICS = None


def _batch_arrays(d) -> list:
    """Every array of a DataSet or MultiDataSet (or the bare array)."""
    if isinstance(d, MultiDataSet):
        arrs = list(d.features) + list(d.labels)
        for ms in (d.features_masks, d.labels_masks):
            if ms is not None:
                arrs.extend(ms)
    elif isinstance(d, DataSet):
        arrs = [d.features, d.labels, d.features_mask, d.labels_mask]
    else:
        arrs = [d]
    return [a for a in arrs if a is not None]


def _batch_nbytes(d) -> int:
    return sum(int(getattr(a, "nbytes", 0) or 0) for a in _batch_arrays(d))


def _device_put_batch(d):
    """Stage a DataSet or MultiDataSet onto the default device."""
    import jax
    if isinstance(d, MultiDataSet):
        def put_list(arrs):
            if arrs is None:
                return None
            return [None if a is None else jax.device_put(a) for a in arrs]
        return MultiDataSet(put_list(d.features), put_list(d.labels),
                            put_list(d.features_masks),
                            put_list(d.labels_masks))
    if isinstance(d, DataSet):
        return DataSet(jax.device_put(d.features), jax.device_put(d.labels),
                       None if d.features_mask is None
                       else jax.device_put(d.features_mask),
                       None if d.labels_mask is None
                       else jax.device_put(d.labels_mask))
    return jax.device_put(d)


def _make_etl(collate, normalizer, transform, device_put):
    """The worker-side ETL chain as a closure over plain values — it
    must NOT capture the iterator (running threads would pin it and the
    GC-finalizer shutdown path could never fire).  Its two stages are
    timed where they run, on the worker: span ``pipeline/batch``, phase
    ``transform`` and, when staging to the device, ``h2d``."""
    import jax
    from deeplearning4j_tpu import monitor

    def etl(raw):
        with monitor.span("pipeline/batch", phase="transform"):
            d = collate(raw) if collate is not None else raw
            if normalizer is not None:
                d = normalizer.transform(d)
            if transform is not None:
                d = transform(d)
        if device_put:
            with monitor.span("pipeline/batch", phase="h2d"):
                d = _device_put_batch(d)
                # device_put returns before the bytes have moved: wait
                # here, on the worker, so that the phase times the
                # transfer and a staged batch is one that has arrived
                jax.block_until_ready(_batch_arrays(d))
        return d
    return etl


class _PipelineRun:
    """One started generation of the pipeline: feeder + worker threads,
    the bounded task queue and the order-preserving reorder buffer.

    Holds no reference to the owning iterator: thread targets are bound
    methods of THIS object, so when the iterator is dropped without
    close(), its ``weakref.finalize`` can still fire and ``request_stop``
    unwinds the threads (a producer blocked on a full queue checks the
    stop event instead of leaking)."""

    def __init__(self, underlying, etl, workers: int, queue_size: int,
                 staging_depth: int, reader_retry=None):
        self.underlying = underlying
        self.next_raw, _ = _etl_split(underlying)
        self.etl = etl
        self.reader_retry = reader_retry
        self.workers = workers
        self.staging_depth = staging_depth
        self.stop = threading.Event()
        self.task_q: queue.Queue = queue.Queue(maxsize=queue_size)
        self.cond = threading.Condition()
        self.ready: dict = {}
        self.ready_high_water = 0
        self.next_seq = 0
        self.total: Optional[int] = None
        self.errors: List[tuple] = []
        self.live_workers = workers
        self.threads = [threading.Thread(target=self._feed, daemon=True,
                                         name="dl4j-pipe-feeder")]
        self.threads += [
            threading.Thread(target=self._work, daemon=True,
                             name=f"dl4j-pipe-worker-{i}")
            for i in range(workers)]

    def start(self):
        _pipeline_metrics()["workers"].set(self.workers)
        for t in self.threads:
            t.start()

    # -- bounded-queue helpers that never block past a stop ------------
    def _q_put(self, item) -> bool:
        while not self.stop.is_set():
            try:
                self.task_q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _q_get(self):
        while not self.stop.is_set():
            try:
                return self.task_q.get(timeout=0.05)
            except queue.Empty:
                continue
        return None

    def _pull_raw(self):
        """One raw pull through the resilience stack: the
        ``reader.next_raw`` fault site, then the optional retry policy
        — a transient reader flake (or injected chaos) is retried with
        backoff on THIS thread instead of surfacing on the consumer.
        The fault check fires before the stateful reader advances, so a
        retried pull re-reads nothing and batch order is unchanged."""
        from deeplearning4j_tpu.resilience import faults

        def pull():
            faults.check("reader.next_raw")
            return self.next_raw()
        if self.reader_retry is None:
            return pull()
        return self.reader_retry.call(pull)

    def _feed(self):
        m = _pipeline_metrics()
        seq = 0
        try:
            while not self.stop.is_set() and self.underlying.has_next():
                raw = self._pull_raw()
                if not self._q_put((seq, raw)):
                    return
                seq += 1
                m["batches"].labels(stage="produced").inc()
                m["queue_depth"].labels(queue="task").set(
                    self.task_q.qsize())
        except BaseException as e:  # surfaced on the consumer thread at
            with self.cond:         # this batch position — a dead feeder
                self.errors.append((seq, e))  # must not look like EOF
                self.cond.notify_all()
        finally:
            with self.cond:
                self.total = seq
                self.cond.notify_all()
            for _ in range(self.workers):
                self._q_put(AsyncDataSetIterator._SENTINEL)

    def _work(self):
        m = _pipeline_metrics()
        try:
            while not self.stop.is_set():
                task = self._q_get()
                if task is None or task is AsyncDataSetIterator._SENTINEL:
                    return
                seq, raw = task
                m["queue_depth"].labels(queue="task").set(
                    self.task_q.qsize())
                t0 = time.perf_counter()
                try:
                    item = self.etl(raw)
                except BaseException as e:
                    with self.cond:
                        self.errors.append((seq, e))
                        self.cond.notify_all()
                    continue
                m["busy"].inc(time.perf_counter() - t0)
                m["batches"].labels(stage="transformed").inc()
                m["staged_bytes"].inc(_batch_nbytes(item))
                with self.cond:
                    # staging bound: at most staging_depth finished
                    # batches resident ahead of the consumer
                    while (not self.stop.is_set()
                           and seq >= self.next_seq + self.staging_depth):
                        self.cond.wait(0.05)
                    if self.stop.is_set():
                        return
                    self.ready[seq] = item
                    self.ready_high_water = max(self.ready_high_water,
                                                len(self.ready))
                    m["queue_depth"].labels(queue="ready").set(
                        len(self.ready))
                    self.cond.notify_all()
        finally:
            with self.cond:
                self.live_workers -= 1
                self.cond.notify_all()

    def get_next(self):
        """Block until the next in-order batch is staged.  Returns
        ``(item, True)`` or ``(None, False)`` at EOF; re-raises a
        feeder/worker exception at the failed batch's position."""
        m = _pipeline_metrics()
        with self.cond:
            while True:
                if self.next_seq in self.ready:
                    item = self.ready.pop(self.next_seq)
                    self.next_seq += 1
                    m["queue_depth"].labels(queue="ready").set(
                        len(self.ready))
                    m["batches"].labels(stage="consumed").inc()
                    self.cond.notify_all()
                    return item, True
                if self.errors:
                    err_seq = min(s for s, _ in self.errors)
                    if err_seq <= self.next_seq:
                        exc = next(e for s, e in self.errors
                                   if s == err_seq)
                        self.stop.set()
                        self.cond.notify_all()
                        raise exc
                if (self.total is not None
                        and self.next_seq >= self.total
                        and self.live_workers == 0):
                    return None, False
                if self.stop.is_set():  # close() raced us
                    return None, False
                self.cond.wait(0.05)

    def request_stop(self):
        """Signal-only shutdown — safe from a GC finalizer."""
        self.stop.set()
        with self.cond:
            self.cond.notify_all()

    def shutdown(self):
        self.request_stop()
        for t in self.threads:
            t.join(timeout=5)
        # A thread still alive here is mid-flight in user ETL or
        # next_raw (every queue wait checks `stop`).  Block until it
        # drains: callers touch the shared stateful reader right after
        # shutdown(), and a feeder still inside next_raw would mutate
        # it concurrently.
        stuck = [t for t in self.threads if t.is_alive()]
        if stuck:
            log.warning(
                "pipeline shutdown: %d thread(s) still in ETL after 5s; "
                "waiting for in-flight work to finish", len(stuck))
            for t in stuck:
                t.join()  # dl4j: noqa[DL4J204] callers touch the shared stateful reader right after shutdown() — in-flight ETL must fully drain
        self.threads = []


def reader_retry_from_conf(g):
    """The feeder-side RetryPolicy for ``conf.fault_tolerance(
    reader_retries=N)``, or None when retries are off.  Seeded from the
    conf seed so the backoff schedule is reproducible run-to-run."""
    if getattr(g, "ft_reader_retries", 0) <= 0:
        return None
    from deeplearning4j_tpu.resilience import RetryPolicy
    return RetryPolicy(max_attempts=int(g.ft_reader_retries) + 1,
                       base_delay_ms=25, max_delay_ms=1000,
                       seed=g.seed, name="reader.next_raw")


def _etl_split(underlying):
    """(next_raw, collate) when the underlying iterator supports the
    raw-pull/assembly split, else (next, None) — the two must pair: raw
    records without the matching collate are not a batch."""
    raw = getattr(underlying, "next_raw", None)
    collate = getattr(underlying, "collate", None)
    if raw is not None and collate is not None:
        return raw, collate
    return underlying.next, None


class AsyncDataSetIterator(DataSetIterator):
    """Multi-worker, order-preserving prefetch pipeline
    (ref: AsyncDataSetIterator.java:39-127 — generalized from one
    thread + BlockingQueue to a feeder + N ETL workers + a bounded
    reorder buffer).

    The feeder pulls raw batches from ``underlying`` serially — batch
    order out of this iterator is therefore deterministic and exactly
    matches the sync iterator.  Workers run collate → normalize →
    transform → ``device_put`` concurrently; finished batches wait in a
    reorder buffer holding at most ``staging_depth`` device-resident
    batches ahead of the consumer.  A worker exception surfaces on the
    consumer thread at the failed batch's position (batches before it
    are still delivered, in order)."""

    _SENTINEL = object()

    def __init__(self, underlying: DataSetIterator, queue_size: int = 4,
                 device_put: bool = False, transform=None,
                 workers: int = 1, staging_depth: Optional[int] = None,
                 normalizer=None, reader_retry=None):
        """``transform`` runs on a worker thread BEFORE device_put —
        the shape-bucketing hook (ops/bucketing.py): batches are padded
        up to their bucket off the critical path, so the H2D transfer
        is already bucket-shaped.  ``normalizer`` (datasets/normalizers)
        is applied before ``transform``.  ``staging_depth`` bounds how
        many finished (device-resident) batches may sit ahead of the
        consumer; default = ``queue_size``.  ``reader_retry`` (a
        ``resilience.RetryPolicy``) retries transient raw-pull failures
        on the feeder thread — ``conf.fault_tolerance(reader_retries=N)``
        plumbs it in."""
        self.underlying = underlying
        self.reader_retry = reader_retry
        self.queue_size = max(1, int(queue_size))
        self.device_put = device_put
        self.transform_fn = transform
        self.normalizer = normalizer
        self.workers = max(1, int(workers))
        self.staging_depth = (self.queue_size if staging_depth is None
                              else max(1, int(staging_depth)))
        self._peek = None
        self._exhausted = False
        self._pending_exc: Optional[BaseException] = None
        self._run: Optional[_PipelineRun] = None
        self._finalizer = None
        self._started = False  # threads start lazily on first use, so a
        # reset() right after construction doesn't drain a prefetch pass

    # -- consumer side ---------------------------------------------------
    def _start(self):
        self._exhausted = False
        self._peek = None
        self._pending_exc = None
        self._started = True
        etl = _make_etl(_etl_split(self.underlying)[1],
                        self.normalizer, self.transform_fn,
                        self.device_put)
        self._run = _PipelineRun(self.underlying, etl, self.workers,
                                 self.queue_size, self.staging_depth,
                                 reader_retry=self.reader_retry)
        # GC safety net: a dropped-without-close() iterator must not
        # leak its threads.  The run holds no reference back to self,
        # so collection of self is possible while threads still spin —
        # the finalizer stops them.
        self._finalizer = weakref.finalize(self, _PipelineRun.request_stop,
                                           self._run)
        self._run.start()
        self._advance()

    def _ensure_started(self):
        if not self._started:
            self._start()

    def _advance(self):
        if self._exhausted:
            self._peek = None
            return
        try:
            self._peek, ok = self._run.get_next()
        except BaseException as e:
            # deferred: every batch staged BEFORE the failure is still
            # delivered in order; the exception surfaces on the consumer
            # right after the last good batch
            self._exhausted = True
            self._peek = None
            self._pending_exc = e
            return
        if not ok:
            self._exhausted = True

    def _raise_pending(self):
        e = self._pending_exc
        if e is not None:
            self._pending_exc = None
            raise e

    def next(self):
        self._ensure_started()
        if self._peek is None:
            self._raise_pending()
        d = self._peek
        self._advance()
        return d

    def has_next(self):
        self._ensure_started()
        if self._peek is not None:
            return True
        self._raise_pending()
        return False

    @property
    def staging_high_water(self) -> int:
        """Max finished batches ever resident in the reorder buffer
        (bounded by ``staging_depth``); survives close()."""
        if self._run is not None:
            return self._run.ready_high_water
        return getattr(self, "_last_high_water", 0)

    def close(self):
        """Stop feeder + workers and release the queues.  Idempotent;
        safe to call mid-stream (a producer blocked on a full queue sees
        the stop event instead of leaking).  The iterator restarts
        lazily on next use from wherever ``underlying`` stands."""
        if not self._started:
            return
        if self._finalizer is not None:
            self._finalizer.detach()
            self._finalizer = None
        if self._run is not None:
            self._last_high_water = self._run.ready_high_water
            self._run.shutdown()
            self._run = None
        self._started = False
        self._peek = None
        self._exhausted = False
        self._pending_exc = None

    def reset(self):
        # Rewind the underlying iterator even when the pipeline never
        # started: threads haven't spun up, but the caller may hand us a
        # partially-consumed iterator (e.g. one a Normalizer.fit just
        # drained) and expects reset() to mean "epoch starts from 0".
        if self._started:
            self.close()
        self.underlying.reset()

    def batch_size(self):
        return self.underlying.batch_size()


class MultiDataSetIterator:
    """Iterator contract for multi-input/output batches
    (ref: nd4j MultiDataSetIterator consumed by ComputationGraph.fit)."""

    def has_next(self) -> bool:
        raise NotImplementedError

    def next(self):
        raise NotImplementedError

    def reset(self) -> None:
        raise NotImplementedError

    def __iter__(self):
        self.reset()
        while self.has_next():
            yield self.next()


class ListMultiDataSetIterator(MultiDataSetIterator):
    """Pre-built MultiDataSet minibatches."""

    def __init__(self, batches):
        self._data = list(batches)
        self._i = 0

    def has_next(self):
        return self._i < len(self._data)

    def next(self):
        d = self._data[self._i]
        self._i += 1
        return d

    def reset(self):
        self._i = 0


class AsyncMultiDataSetIterator(AsyncDataSetIterator):
    """Multi-worker prefetch wrapper for MultiDataSet iterators
    (ref: datasets/iterator/AsyncMultiDataSetIterator.java).  Shares the
    whole feeder/worker/reorder machinery with AsyncDataSetIterator —
    only the device staging differs (every array in the features/labels
    lists moves, None masks pass through)."""

    def __init__(self, underlying: MultiDataSetIterator,
                 queue_size: int = 4, transform=None,
                 device_put: bool = False, workers: int = 1,
                 staging_depth: Optional[int] = None, reader_retry=None):
        super().__init__(underlying, queue_size=queue_size,
                         device_put=device_put, transform=transform,
                         workers=workers, staging_depth=staging_depth,
                         reader_retry=reader_retry)

    def batch_size(self):  # MultiDataSet iterators need not expose this
        fn = getattr(self.underlying, "batch_size", None)
        return fn() if fn else 0
