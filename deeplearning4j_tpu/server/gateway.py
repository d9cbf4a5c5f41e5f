"""Gateway entry point for external (non-framework) processes
(ref: deeplearning4j-keras — keras/Server.java:15-18 starts a py4j
GatewayServer around DeepLearning4jEntryPoint;
DeepLearning4jEntryPoint.fit() :21-33 trains a Keras-saved model on
batches streamed from disk; HDF5MiniBatchDataSetIterator reads them).

The reference's wire tech (py4j JVM gateway) is replaced by a JSON-RPC
HTTP endpoint — the natural cross-process seam for a Python-hosted
runtime.  The entry-point surface is preserved (``fit`` takes a saved
model plus a directory of exported minibatches, trains, and writes the
result checkpoint) and extended into a real inference server:

* **model cache** (``server/model_cache.py``): models load and jit-warm
  once, keyed by ``(path, mtime)``, with LRU eviction and an
  ``invalidate`` RPC;
* **dynamic micro-batching** (``server/batcher.py``): concurrent
  ``predict`` requests with inline ``features`` coalesce into one
  jitted ``output`` call, padded to the bucket ladder;
* **bucket warmup**: the first predict for a model pre-compiles the
  serving ladder (``warmup_inference``), so cold compiles happen once
  at load, not on the request path;
* **serving metrics** (``stats`` RPC): latency percentiles, batch-size
  histogram, model-cache counters, each model's ``CompileTelemetry``
  snapshot, and the process-wide metrics registry;
* **Prometheus exposition** (``metrics`` RPC / ``GET /metrics``): the
  unified registry (monitor/) as text-format v0.0.4 or JSON — one
  scrape sees retraces, step-phase timings, serving latencies, cache
  hit rates and device memory (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import json
import os
import threading
import time
import traceback
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path
from typing import Optional

import numpy as np

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.monitor import events, flight
from deeplearning4j_tpu.resilience import (
    CircuitBreaker, CircuitOpenError, OverloadedError, RetryPolicy, faults)
from deeplearning4j_tpu.resilience.errors import DeadlineExceededError
from deeplearning4j_tpu.server.batcher import MicroBatcher
from deeplearning4j_tpu.server.model_cache import ModelCache


class DeepLearning4jEntryPoint:
    """(ref: keras/DeepLearning4jEntryPoint.java:21-33 — the object the
    gateway exposes; one method per remote operation).

    ``max_batch``/``max_wait_ms`` configure the per-model micro-batcher;
    ``coalesce`` is the default for ``predict(features=...)`` requests
    (overridable per request).

    Overload posture (docs/RESILIENCE.md): ``max_queue_rows`` bounds the
    rows queued across batchers — a ``predict`` that would push past it
    is rejected with :class:`OverloadedError` (HTTP 503 +
    ``Retry-After: retry_after_s``) instead of queuing without bound;
    per-request ``deadline_ms`` propagates into the batcher so requests
    that expire while queued are shed before compute.  When this entry
    point builds its own :class:`ModelCache`, checkpoint loads get a
    retry policy and a circuit breaker (``/readyz`` goes unready while
    that breaker is open)."""

    def __init__(self, model_cache: Optional[ModelCache] = None,
                 max_batch: int = 32, max_wait_ms: float = 5.0,
                 min_batch: int = 1, coalesce: bool = True,
                 max_queue_rows: int = 1024, retry_after_s: float = 1.0,
                 min_ready_models: int = 0,
                 tenant_quota_rows: Optional[int] = None,
                 decode_slots: int = 32, decode_ttl_s: float = 600.0,
                 decode_max_wait_ms: float = 2.0,
                 blue_green: bool = False,
                 slo=None, slo_interval_s: float = 5.0):
        if model_cache is None:
            model_cache = ModelCache(
                load_retry=RetryPolicy(max_attempts=3, base_delay_ms=25,
                                       name="cache.load"),
                load_breaker=CircuitBreaker(cooldown_s=10.0,
                                            name="cache.load"),
                blue_green=blue_green)
        self.model_cache = model_cache
        self.max_batch = max(1, int(max_batch))
        self.max_wait_ms = float(max_wait_ms)
        self.min_batch = max(1, int(min_batch))
        self.coalesce = bool(coalesce)
        self.max_queue_rows = max(1, int(max_queue_rows))
        self.retry_after_s = max(0.0, float(retry_after_s))
        self.min_ready_models = max(0, int(min_ready_models))
        # per-tenant fair share: one tenant may hold at most this many
        # queued rows (predict + decode) — None disables the per-tenant
        # check, the global max_queue_rows bound always applies
        self.tenant_quota_rows = (None if tenant_quota_rows is None
                                  else max(1, int(tenant_quota_rows)))
        from deeplearning4j_tpu.server.decode import DecodeManager
        self.decode = DecodeManager(
            self.model_cache, max_slots=decode_slots, ttl_s=decode_ttl_s,
            max_wait_ms=decode_max_wait_ms, retry_after_s=self.retry_after_s)
        self._t_start = time.time()
        self._batchers: dict = {}
        self._batcher_lock = threading.Lock()
        # speculative decoders, one per (vocab, k, draft config) — the
        # per-session drafting state lives inside them
        self._spec_decoders: dict = {}
        self._spec_lock = threading.Lock()
        self._last_ready: Optional[bool] = None
        self._c_shed = monitor.get_registry().counter(
            "dl4j_resilience_shed_total",
            "requests shed instead of served", labels=("reason",))
        # SLO monitoring (docs/OBSERVABILITY.md "Fleet federation &
        # SLOs"): slo=True arms the stock serving objectives, a list of
        # Objectives (or a ready SloTracker) customizes them; the
        # evaluator thread watches this process's registry and meters
        # dl4j_slo_* / journals slo.state_changed / flight-dumps on a
        # fast-burn flip
        self.slo = None
        if slo:
            from deeplearning4j_tpu.monitor.slo import SloTracker
            self.slo = (slo if isinstance(slo, SloTracker)
                        else SloTracker(None if slo is True else slo))
            self.slo.start(interval_s=slo_interval_s)

    def _load_model(self, model_path: str):
        return self.model_cache.get(model_path)

    @staticmethod
    def _data_iterator(data_dir: str):
        """Minibatch source for a data directory, by layout:

        * ``features/`` + ``labels/`` subdirs of ``batch_%d.h5`` — the
          reference's HDF5 layout (HDF5MiniBatchDataSetIterator.java:24);
        * ``batch_%d.h5`` files carrying features+labels datasets;
        * ``.npz`` exports (scaleout.data.PathDataSetIterator).
        """
        from deeplearning4j_tpu.scaleout.data import PathDataSetIterator
        from deeplearning4j_tpu.keras_import.hdf5_data import (
            _BATCH_RE, HDF5MiniBatchDataSetIterator)
        d = Path(data_dir)
        if (d / "features").is_dir() and (d / "labels").is_dir():
            return HDF5MiniBatchDataSetIterator(d / "features", d / "labels")
        # the iterator's own strict batch_%d.h5 pattern decides — a stray
        # non-conforming .h5 must not hijack a directory of .npz exports
        if any(_BATCH_RE.match(p.name) for p in d.iterdir()):
            return HDF5MiniBatchDataSetIterator(d)
        return PathDataSetIterator.from_dir(data_dir)

    def fit(self, model_path: str, data_dir: str, epochs: int = 1,
            save_path: Optional[str] = None,
            shape_bucketing: Optional[bool] = None) -> dict:
        """Train ``model_path`` on the minibatches in ``data_dir``
        (HDF5 ``batch_%d.h5`` layouts or .npz exports —
        :meth:`_data_iterator`).  Exported minibatch directories are the
        canonical ragged stream (the last shard is short), so
        ``shape_bucketing=True`` pads every batch up to its bucket and
        the step compiles once per bucket (ops/bucketing.py); retrace
        telemetry is returned alongside the score."""
        from deeplearning4j_tpu.nn.serialization import write_model
        model = self.model_cache.get(model_path)
        if shape_bucketing is not None:
            model.conf.global_conf.shape_bucketing = bool(shape_bucketing)
        it = self._data_iterator(data_dir)
        for _ in range(int(epochs)):
            it.reset()
            while it.has_next():
                model.fit(it.next())
        out = save_path or model_path
        if not out.endswith(".zip"):
            out = str(Path(out).with_suffix(".zip"))
        write_model(model, out)
        # training mutated the in-memory instance away from the on-disk
        # file its cache key names — drop it (the written checkpoint
        # re-caches on next use; same-path saves also changed the mtime)
        self.invalidate(model_path)
        result = {"score": float(model.score()), "model_path": out}
        tel = getattr(model, "compile_telemetry", None)
        if tel is not None:
            result["compile_telemetry"] = tel.snapshot()
        return result

    def evaluate(self, model_path: str, data_dir: str) -> dict:
        model = self.model_cache.get(model_path)
        ev = model.evaluate(self._data_iterator(data_dir))
        return {"accuracy": ev.accuracy(), "f1": ev.f1()}

    # ------------------------------------------------------------------
    # Inference
    # ------------------------------------------------------------------
    def predict(self, model_path: str, data_dir: Optional[str] = None,
                features=None, top_k: Optional[int] = None,
                argmax_only: bool = False,
                coalesce: Optional[bool] = None,
                deadline_ms: Optional[float] = None,
                tenant: Optional[str] = None) -> dict:
        """Run inference with the cached, bucket-warmed model.

        Exactly one input source: ``data_dir`` (exported minibatch
        directory — already batched, runs batch-at-a-time) or
        ``features`` (an inline ``[k, ...]`` row batch — the serving
        path; concurrent requests coalesce through the micro-batcher
        unless ``coalesce=False``).

        ``deadline_ms`` is the request's total budget: a request still
        queued in the batcher when it expires is shed before compute
        (``DeadlineExceededError`` → HTTP 504); admission control may
        reject it up front (``OverloadedError`` → HTTP 503 +
        ``Retry-After``) when queued rows exceed ``max_queue_rows``.

        Response shaping for classification clients: ``argmax_only``
        returns class ids; ``top_k=K`` returns the K best class ids +
        probabilities per row — both avoid serializing the full
        ``[n, n_classes]`` probability matrix to JSON."""
        # request-scoped tracing: reuse the request ID the HTTP server
        # minted for this RPC (or mint one for direct in-process calls)
        # so admission, the batcher queue and the coalesced compute all
        # journal under the same correlation ID
        with events.request_scope(tenant=tenant,
                                  model=os.path.basename(str(model_path))):
            return self._predict(model_path, data_dir, features, top_k,
                                 argmax_only, coalesce, deadline_ms, tenant)

    def _predict(self, model_path, data_dir, features, top_k,
                 argmax_only, coalesce, deadline_ms, tenant) -> dict:
        faults.check("gateway.predict")
        if (data_dir is None) == (features is None):
            raise ValueError(
                "predict needs exactly one of data_dir= or features=")
        if features is not None:
            x = np.asarray(features, dtype=np.float32)
            if x.ndim < 1 or x.shape[0] == 0:
                raise ValueError("features must be a non-empty [k, ...] "
                                 "row batch")
            use_batcher = self.coalesce if coalesce is None else bool(coalesce)
            if use_batcher:
                # admission BEFORE the (possibly breaker-guarded) model
                # load: an overloaded server sheds cheap and early
                self._admit(len(x), tenant=tenant)
            model = self.model_cache.get(
                model_path, warmup_dims=tuple(x.shape[1:]),
                max_batch=self.max_batch)
            if use_batcher:
                out = self._batcher_for(model_path, model).predict(
                    x, timeout_ms=deadline_ms, tenant=tenant)
            else:
                out = self._infer_fn(model)(x)
            return self._format_predictions(out, top_k, argmax_only)

        model = self.model_cache.get(model_path)
        it = self._data_iterator(data_dir)
        infer = self._infer_fn(model)
        outs = []
        while it.has_next():
            outs.append(infer(it.next().features))
        if outs:
            stacked = np.concatenate(outs)
        else:
            # keep output rank even with zero minibatches: (0, *out_dims)
            stacked = np.zeros((0,) + self._output_dims(model), np.float32)
        return self._format_predictions(stacked, top_k, argmax_only)

    def warmup(self, model_path: str, feature_dims,
               max_batch: Optional[int] = None,
               spec_k: Optional[int] = None) -> dict:
        """Explicitly pre-compile the serving bucket ladder for
        ``model_path`` (``feature_dims`` is the per-example feature
        shape) — what the first ``features=`` predict does implicitly.
        ``spec_k=K`` additionally warms the decode pool's fused
        speculative-verify program per slot-ladder rung
        (``DecodePool.warmup_spec``) so the first
        ``decode_step(spec=...)`` never pays a cold compile."""
        model = self.model_cache.get(model_path)
        out = model.warmup_inference(
            feature_dims, max_batch=int(max_batch or self.max_batch))
        if spec_k is not None:
            out["spec"] = self.decode.warmup_spec(
                model_path, feature_dims, k=int(spec_k))
        return out

    def invalidate(self, model_path: Optional[str] = None) -> dict:
        """Drop cached model(s) — and their batchers and decode pools
        (open sessions fail) — so the next request reloads from disk
        (explicit cache-invalidation RPC; a changed file mtime
        invalidates implicitly)."""
        n = self.model_cache.invalidate(model_path)
        self.decode.invalidate(model_path)
        with self._batcher_lock:
            keys = ([os.path.abspath(str(model_path))]
                    if model_path is not None else list(self._batchers))
            dropped = [self._batchers.pop(k) for k in keys
                       if k in self._batchers]
        for _, batcher in dropped:
            batcher.stop()
        return {"invalidated": n}

    # ------------------------------------------------------------------
    # Stateful decode sessions (server/decode.py — ROADMAP 3b)
    # ------------------------------------------------------------------
    def open_session(self, model_path: str,
                     tenant: Optional[str] = None) -> dict:
        """Open a stateful decode session: the model's recurrent carry
        for this stream lives on device in the model's slot pool, so
        every subsequent :meth:`decode_step` is O(1) in how much of the
        stream has already been consumed.  503 + Retry-After when every
        slot is held by a live session."""
        with events.request_scope(
                tenant=tenant, model=os.path.basename(str(model_path))):
            return self.decode.open_session(model_path, tenant=tenant)

    def decode_step(self, session_id: str, features,
                    mask=None, tenant: Optional[str] = None,
                    deadline_ms: Optional[float] = None,
                    top_k: Optional[int] = None,
                    argmax_only: bool = False,
                    spec=None, draft=None) -> dict:
        """Feed one ``[T, C]`` chunk (``T=1`` token-by-token; longer
        chunks are the prefill path) to a session and return the
        ``[T, ...]`` outputs.  Concurrent sessions' steps coalesce into
        one jitted slot-pool dispatch (continuous batching); admission
        control and per-tenant fair share apply exactly as for
        ``predict`` (one step = one queue row, matching the decode
        queue's accounting).

        ``spec=`` turns on speculative continuation AFTER the chunk:
        ``spec=N`` (or ``{"tokens": N, "k": K}``) greedily generates N
        more tokens via the fused verify program — draft proposals
        (``draft=`` — ``"ngram"`` by default, see
        ``server/speculative.py``) are scored K at a time in ONE
        compiled dispatch each, with exact greedy parity.  The response
        gains ``spec``: the generated token ids, the pending next
        token, and dispatch/acceptance counts."""
        with events.request_scope(tenant=tenant, session_id=session_id):
            self._admit(1, tenant=tenant)
            outs = self.decode.decode_step(
                session_id, features, mask=mask, timeout_ms=deadline_ms,
                tenant=tenant)
            spec_out = None
            if spec:
                spec_out = self._spec_continue(
                    session_id, outs, spec, draft, tenant=tenant,
                    deadline_ms=deadline_ms)
        result = self._format_predictions(outs[0], top_k, argmax_only)
        if len(outs) > 1:
            result["outputs"] = [np.asarray(o).tolist() for o in outs]
        result["session_id"] = session_id
        if spec_out is not None:
            result["spec"] = spec_out
        return result

    def _spec_continue(self, session_id: str, outs, spec, draft,
                       tenant=None, deadline_ms=None) -> dict:
        """Run the speculative greedy continuation for ``decode_step``'s
        ``spec=`` knob (one :class:`SpeculativeDecoder` per
        vocab/k/draft config, session state keyed inside it)."""
        from deeplearning4j_tpu.server import speculative
        cfg = {"tokens": int(spec)} if not isinstance(spec, dict) else spec
        n_tokens = int(cfg.get("tokens", 0))
        if n_tokens <= 0:
            return {"tokens": [], "dispatches": 0}
        k = int(cfg.get("k", 4))
        last = np.asarray(outs[0])[-1]
        vocab = int(last.shape[-1])
        key = (vocab, k, json.dumps(draft, sort_keys=True)
               if isinstance(draft, dict) else str(draft))
        with self._spec_lock:
            dec = self._spec_decoders.get(key)
            if dec is None:
                dec = speculative.SpeculativeDecoder(
                    self.decode, vocab=vocab, k=k, draft=draft)
                self._spec_decoders[key] = dec
        first = int(np.argmax(last))
        return dec.generate(session_id, first, n_tokens, tenant=tenant,
                            timeout_ms=deadline_ms)

    def close_session(self, session_id: str) -> dict:
        """Release a decode session's slot (its device carry is
        reclaimed for the next session)."""
        with self._spec_lock:
            decoders = list(self._spec_decoders.values())
        for dec in decoders:
            dec.forget(session_id)
        return {"closed": self.decode.close_session(session_id)}

    # ------------------------------------------------------------------
    # Cross-replica session migration (fleet/ tier — docs/FLEET.md)
    # ------------------------------------------------------------------
    def export_session(self, session_id: str) -> dict:
        """Phase one of a migration: snapshot the session's device
        carry as a JSON payload and hold its slot in exported limbo
        (excluded from stats/active counts) until ``finish_export``."""
        return self.decode.export_session(session_id)

    def finish_export(self, session_id: str, ok: bool = True) -> dict:
        """Phase two: ``ok=True`` releases the migrated session's slot;
        ``ok=False`` reinstates it (the import failed — the carry never
        left this replica's device pool)."""
        return {"finished": self.decode.finish_export(session_id,
                                                      ok=bool(ok))}

    def import_session(self, model_path: str, payload: dict,
                       session_id: Optional[str] = None,
                       tenant: Optional[str] = None) -> dict:
        """Restore an exported session onto THIS replica (the target
        half of a migration) — the stream continues from the imported
        carry with next-token parity against the source."""
        return self.decode.import_session(model_path, payload,
                                          session_id=session_id,
                                          tenant=tenant)

    def drain(self, deadline_ms: Optional[float] = None) -> dict:
        """Stop admitting decode session joins (opens and imports shed
        503) and report remaining sessions per pool — the rollout
        forcing function.  ``/readyz`` goes unready while draining so a
        load balancer shifts traffic; ``undrain`` re-admits."""
        deadline_s = None if deadline_ms is None \
            else max(0.0, float(deadline_ms)) / 1e3
        return {"pools": self.decode.drain(deadline_s),
                "draining": True}

    def undrain(self) -> dict:
        """Re-admit decode session joins after a drain (rollout done or
        aborted)."""
        self.decode.resume()
        return {"draining": False}

    def decode_stats(self) -> dict:
        """Per-model decode-pool observability: slots, sessions, step
        counts, the continuous-batching histogram and the bounded
        compiled-program count."""
        return self.decode.stats()

    # ------------------------------------------------------------------
    # Health / readiness (docs/RESILIENCE.md)
    # ------------------------------------------------------------------
    def _admit(self, n_rows: int, tenant: Optional[str] = None) -> None:
        """Bounded-queue admission control: reject (don't queue) when
        the rows already waiting across batchers and decode pools plus
        this request exceed ``max_queue_rows`` — and, with
        ``tenant_quota_rows`` set, when THIS tenant's queued rows would
        exceed its fair share (one tenant flooding the queue gets 503 +
        Retry-After while everyone else keeps being served)."""
        depth = self._queued_rows()
        if depth + n_rows > self.max_queue_rows:
            self._c_shed.labels(reason="queue_full").inc()
            events.emit("request.shed", severity="warn",
                        reason="queue_full", rows=n_rows, queued=depth)
            raise OverloadedError(
                f"queue full ({depth} rows waiting, limit "
                f"{self.max_queue_rows})", retry_after_s=self.retry_after_s)
        if self.tenant_quota_rows is not None:
            t = tenant or "-"
            held = self._tenant_queued_rows().get(t, 0)
            if held + n_rows > self.tenant_quota_rows:
                self._c_shed.labels(reason="tenant_quota").inc()
                events.emit("request.shed", severity="warn",
                            reason="tenant_quota", rows=n_rows, queued=held)
                raise OverloadedError(
                    f"tenant {t!r} over fair-share quota ({held} rows "
                    f"queued, limit {self.tenant_quota_rows})",
                    retry_after_s=self.retry_after_s)
        events.emit("request.admitted", rows=n_rows, queued=depth)

    def _queued_rows(self) -> int:
        with self._batcher_lock:
            batchers = [b for _, b in self._batchers.values()]
        return sum(b.queue_rows() for b in batchers) \
            + self.decode.queue_rows()

    def _tenant_queued_rows(self) -> dict:
        with self._batcher_lock:
            batchers = [b for _, b in self._batchers.values()]
        out: dict = {}
        for b in batchers:
            for t, n in b.queue_rows_by_tenant().items():
                out[t] = out.get(t, 0) + n
        for t, n in self.decode.queue_rows_by_tenant().items():
            out[t] = out.get(t, 0) + n
        return out

    def healthz(self) -> dict:
        """Liveness: the process is up and the RPC loop answers.  Stays
        200 even under injected faults or overload — unhealthy-vs-busy
        is ``readyz``'s distinction, not this one's."""
        return {"status": "ok", "uptime_s": round(time.time() -
                                                  self._t_start, 1)}

    def readyz(self) -> dict:
        """Readiness: should a load balancer send traffic here NOW?
        Ready iff every batcher thread is alive, queued rows are under
        the admission limit, the model-load breaker (if any) is not
        open, and at least ``min_ready_models`` models are resident and
        warm."""
        with self._batcher_lock:
            batchers = list(self._batchers.values())
        queued = sum(b.queue_rows() for _, b in batchers)
        breaker = getattr(self.model_cache, "load_breaker", None)
        cache_stats = self.model_cache.stats()
        warm = sum(1 for m in cache_stats["models"].values()
                   if m.get("warmup") is not None)
        checks = {
            "batchers_alive": all(b.thread_alive for _, b in batchers),
            # decode pools with live sessions must have a live dispatch
            # thread too — a dead decode batcher strands every open
            # session, which is exactly what an LB should drain over
            "decode_alive": self.decode.batchers_alive(),
            # a draining replica is mid-rollout/migration: an LB (or
            # the fleet router) should place sessions elsewhere
            "not_draining": not self.decode.draining,
            "queue_below_limit": queued < self.max_queue_rows,
            "breaker_closed": (breaker is None
                               or breaker.state != CircuitBreaker.OPEN),
            "models_warm": len(cache_stats["models"])
                           >= self.min_ready_models,
        }
        ready = all(checks.values())
        # a flip to not-ready is a crash-adjacent moment: journal it and
        # snapshot the black box while the evidence is still in the ring
        if self._last_ready is not None and ready != self._last_ready:
            failing = sorted(k for k, v in checks.items() if not v)
            events.emit("readyz.flip", severity="warn" if not ready
                        else "info", ready=ready, failing=failing)
            if not ready:
                flight.dump("readyz_not_ready",
                            extra={"checks": checks, "queued_rows": queued})
        self._last_ready = ready
        return {"ready": ready, "checks": checks,
                "queued_rows": queued,
                "models_resident": cache_stats["size"],
                "models_warmed": warm}

    def stats(self) -> dict:
        """Serving observability: model-cache counters, per-model
        batcher metrics (queue/compute/total latency percentiles,
        batch-size histogram), each resident model's
        ``CompileTelemetry`` snapshot, AND the process-wide metrics
        registry — one RPC sees retraces, latencies, phase timings and
        memory together (keys ``model_cache``/``serving`` are unchanged
        for existing clients; ``registry`` is additive)."""
        out = {"model_cache": self.model_cache.stats(), "serving": {}}
        with self._batcher_lock:
            items = list(self._batchers.items())
        for key, (model, batcher) in items:
            s = batcher.metrics.snapshot()
            tel = getattr(model, "compile_telemetry", None)
            if tel is not None:
                s["compile_telemetry"] = tel.snapshot()
            out["serving"][key] = s
        out["decode"] = self.decode.stats()
        if self.slo is not None:
            out["slo"] = self.slo.states()
        out["registry"] = monitor.get_registry().snapshot()
        return out

    def metrics(self, format: str = "prometheus",
                scope: str = "process"):
        """The scrape endpoint as an RPC.  ``format="prometheus"``
        (default) returns ``{"content_type", "body"}`` with text-format
        v0.0.4 (also served raw at ``GET /metrics`` for a stock
        Prometheus scraper / ``curl``); ``format="json"`` returns the
        registry snapshot dict itself.  ``scope`` is accepted for
        surface parity with the fleet router — a single gateway only
        has ``"process"`` scope (``"fleet"`` is served by
        ``fleet.SessionRouter``)."""
        fmt = str(format).lower()
        if str(scope).lower() != "process":
            raise ValueError(
                f"scope {scope!r} is not served by a single gateway — "
                "fleet scope is the fleet router's surface "
                "(fleet/router.py)")
        snap = monitor.get_registry().snapshot()
        if fmt == "json":
            return snap
        if fmt != "prometheus":
            raise ValueError(f"format must be prometheus or json, "
                             f"got {format!r}")
        return {"content_type": monitor.CONTENT_TYPE,
                "body": monitor.render_prometheus(snap)}

    def trace_dump(self, last_n: Optional[int] = None,
                   format: str = "events", request_id: Optional[str] = None,
                   dump: bool = False, reason: str = "manual",
                   scope: str = "local") -> dict:
        """Live access to the structured event journal (the flight
        recorder's source).  ``format="events"`` (default) returns the
        newest ``last_n`` journal events (optionally filtered to one
        ``request_id`` — "what happened to THIS request");
        ``format="chrome"`` returns the Chrome trace-event export under
        ``trace`` (save ``.trace`` to a file and open it in Perfetto /
        ``chrome://tracing`` to see a serving burst or a slow fit epoch
        as real slices).  ``dump=True`` also writes a flight-recorder
        file and returns its path.  ``scope`` is accepted for surface
        parity with the fleet router (which assembles every replica's
        journal); a single gateway only serves its ``"local"``
        journal."""
        fmt = str(format).lower()
        if fmt not in ("events", "chrome"):
            raise ValueError(f"format must be events or chrome, got "
                             f"{format!r}")
        if str(scope).lower() not in ("local", "process"):
            raise ValueError(
                f"scope {scope!r} is not served by a single gateway — "
                "fleet trace assembly is the fleet router's surface "
                "(fleet/router.py)")
        journal = events.get_journal()
        evts = journal.tail(n=last_n, request_id=request_id)
        out: dict = {"count": len(evts),
                     "total_emitted": journal.total_emitted,
                     "dropped": journal.dropped}
        if dump:
            out["path"] = flight.dump(reason, force=True)
        if fmt == "chrome":
            out["trace"] = events.chrome_trace(evts)
        else:
            out["events"] = evts
        return out

    def close(self) -> None:
        """Stop all batcher threads and decode pools (server
        shutdown; open decode sessions fail cleanly)."""
        if self.slo is not None:
            self.slo.stop()
        with self._batcher_lock:
            dropped = list(self._batchers.values())
            self._batchers.clear()
        for _, batcher in dropped:
            batcher.stop()
        self.decode.close()

    # ------------------------------------------------------------------
    # Internals
    # ------------------------------------------------------------------
    @staticmethod
    def _infer_fn(model):
        """Row-aligned numpy inference callable over a model's jitted
        ``output`` (first output for multi-output graphs)."""
        def infer(x):
            out = model.output(x)
            if isinstance(out, tuple):
                out = out[0]
            return np.asarray(out)
        return infer

    def _batcher_for(self, model_path: str, model) -> MicroBatcher:
        """The micro-batcher bound to this model instance; a reloaded
        model (stale mtime / invalidate) gets a fresh batcher."""
        key = os.path.abspath(str(model_path))
        with self._batcher_lock:
            entry = self._batchers.get(key)
            if entry is not None and entry[0] is model:
                return entry[1]
            old = entry[1] if entry is not None else None
            g = model.conf.global_conf
            batcher = MicroBatcher(
                self._infer_fn(model),
                max_batch=self.max_batch, max_wait_ms=self.max_wait_ms,
                min_batch=self.min_batch,
                bucket_sizes=g.bucket_batch_sizes,
                # the model pads internally when bucketing is on — don't
                # pad twice (idempotent, but wasted host work)
                pad_to_bucket=not g.shape_bucketing,
                name=os.path.basename(key))
            self._batchers[key] = (model, batcher)
        if old is not None:
            old.stop()
        return batcher

    @staticmethod
    def _output_dims(model):
        """Per-example output shape when there is no data to infer it
        from (the zero-minibatch fallback must keep output rank)."""
        if hasattr(model, "_output_layer_confs"):  # ComputationGraph
            confs = list(model._output_layer_confs().values())
            n_out = int(getattr(confs[0], "n_out", 0) or 0) if confs else 0
        else:
            n_out = int(getattr(model.layers[-1], "n_out", 0) or 0)
        return (n_out,) if n_out else ()

    @staticmethod
    def _format_predictions(out, top_k=None, argmax_only=False) -> dict:
        out = np.asarray(out)
        if argmax_only:
            cls = np.argmax(out, axis=-1)
            return {"classes": cls.tolist(), "shape": list(cls.shape)}
        if top_k:
            k = max(1, min(int(top_k), out.shape[-1]))
            idx = np.argsort(out, axis=-1)[..., ::-1][..., :k]
            vals = np.take_along_axis(out, idx, axis=-1)
            return {"top_k": k, "classes": idx.tolist(),
                    "probabilities": vals.tolist(), "shape": list(idx.shape)}
        return {"predictions": out.tolist(), "shape": list(out.shape)}


class Server:
    """(ref: keras/Server.java — `new GatewayServer(new
    DeepLearning4jEntryPoint()).start()`).  JSON-RPC over HTTP:

    POST / {"method": "fit", "params": {...}} →
        {"result": {...}} or {"error": "..."}

    ``debug=True`` includes the full traceback in error payloads;
    by default clients only see the exception type and message
    (tracebacks leak host paths and internals).
    """

    def __init__(self, entry_point: Optional[DeepLearning4jEntryPoint] = None,
                 host: str = "127.0.0.1", port: int = 0,
                 debug: bool = False):
        ep = entry_point or DeepLearning4jEntryPoint()
        self.entry_point = ep
        self.debug = bool(debug)
        server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):
                pass

            def _respond(self, code, payload, content_type,
                         headers=None):
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(payload)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(payload)

            def do_GET(self):
                """The probe surfaces a stock scraper / load balancer /
                ``curl`` hits without JSON-RPC framing: ``/metrics``
                (Prometheus text), ``/healthz`` (liveness, always 200
                while the process answers), ``/readyz`` (readiness —
                503 while shedding/unwarm/breaker-open, so an LB drains
                this replica instead of feeding it) and ``/trace`` (the
                live event journal; ``?format=chrome`` returns the
                Perfetto-loadable Chrome trace-event export directly,
                ``?request_id=`` filters to one request's events)."""
                path, _, query = self.path.partition("?")
                try:
                    from urllib.parse import parse_qs
                    q = {k: v[-1] for k, v in parse_qs(query).items()}
                    if path == "/trace":
                        fmt = q.get("format", "events")
                        last_n = (int(q["last_n"]) if "last_n" in q
                                  else None)
                        kw = ({"scope": q["scope"]} if "scope" in q
                              else {})
                        r = ep.trace_dump(last_n=last_n, format=fmt,
                                          request_id=q.get("request_id"),
                                          **kw)
                        # chrome format serves the bare trace object so
                        # the response body IS a Perfetto-loadable file
                        body = r["trace"] if fmt == "chrome" else r
                        server._count_request("GET /trace", 200)
                        self._respond(
                            200, json.dumps(body, default=str).encode(),
                            "application/json")
                    elif path == "/metrics":
                        # ?scope=fleet on a fleet router serves the
                        # federated merge; a single gateway only has
                        # process scope
                        kw = ({"scope": q["scope"]} if "scope" in q
                              else {})
                        m = ep.metrics(**kw)
                        server._count_request("GET /metrics", 200)
                        self._respond(200, m["body"].encode(),
                                      m["content_type"])
                    elif path == "/healthz":
                        server._count_request("GET /healthz", 200)
                        self._respond(200, json.dumps(ep.healthz()).encode(),
                                      "application/json")
                    elif path == "/readyz":
                        r = ep.readyz()
                        code = 200 if r["ready"] else 503
                        server._count_request("GET /readyz", code)
                        self._respond(code, json.dumps(r).encode(),
                                      "application/json")
                    else:
                        self._respond(404, b'{"error": "not found"}',
                                      "application/json")
                except Exception as e:
                    server._count_request(f"GET {path}", 500)
                    self._respond(500, json.dumps(
                        {"error": f"{type(e).__name__}: {e}"}).encode(),
                        "application/json")

            def do_POST(self):
                method = ""
                headers = {}
                # the gateway ADOPTS an upstream trace/request ID when
                # the caller sends one (the fleet router's hop header —
                # one request_scope then correlates the full
                # router→replica flow in GET /trace) and mints one
                # otherwise; every event this RPC produces (admission,
                # batcher queue, coalesced compute, decode step)
                # journals under it, and the client gets it back for
                # support-ticket correlation
                rid = (self.headers.get("X-DL4J-Request-ID") or "").strip() \
                    or events.new_request_id()
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    req = json.loads(self.rfile.read(n) or b"{}")
                    method = req.get("method", "")
                    params = req.get("params", {})
                    if not isinstance(params, dict):
                        raise ValueError("params must be an object")
                    if method.startswith("_") or not hasattr(ep, method):
                        raise AttributeError(f"no method {method!r}")
                    with events.scope(request_id=rid, method=method,
                                      tenant=params.get("tenant")):
                        events.emit("rpc.request")
                        result = getattr(ep, method)(**params)
                        events.emit("rpc.response", code=200)
                    payload = json.dumps({"result": result,
                                          "request_id": rid},
                                         default=str).encode()
                    code = 200
                except Exception as e:
                    err = {"error": f"{type(e).__name__}: {e}",
                           "request_id": rid}
                    # resilience errors carry their HTTP semantics:
                    # shed/short-circuited → 503 + Retry-After (back
                    # off, come back), expired deadline → 504
                    if isinstance(e, (OverloadedError, CircuitOpenError)):
                        code = 503
                        headers["Retry-After"] = str(max(
                            1, int(round(e.retry_after_s or 1.0))))
                        err["retry_after_s"] = e.retry_after_s
                    elif isinstance(e, DeadlineExceededError):
                        code = 504
                    else:
                        code = 500
                        if server.debug:
                            err["traceback"] = traceback.format_exc()
                    with events.scope(request_id=rid, method=method or "?"):
                        events.emit("rpc.response", severity="warn",
                                    code=code, error=type(e).__name__)
                    payload = json.dumps(err).encode()
                headers["X-DL4J-Request-ID"] = rid
                server._count_request(method or "?", code)
                self._respond(code, payload, "application/json", headers)

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self.host, self.port = self._httpd.server_address
        self._thread: Optional[threading.Thread] = None
        self._requests_c = monitor.get_registry().counter(
            "dl4j_gateway_requests_total", "gateway RPC calls",
            labels=("method", "code"))

    def _count_request(self, method: str, code: int) -> None:
        self._requests_c.labels(method=method, code=str(code)).inc()

    def start(self) -> "Server":
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        close = getattr(self.entry_point, "close", None)
        if close is not None:
            close()
