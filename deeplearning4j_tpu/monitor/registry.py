"""Process-wide, thread-safe metrics registry.

The framework's telemetry used to be fragmented — ``CompileTelemetry``
(ops/bucketing.py), ``LatencyHistogram`` (nn/listeners.py) and
``ui/stats_listener.py`` each kept private counters with no shared
surface and no exposition endpoint.  This registry is the one place all
of them land (the observability analog of the reference's StatsStorage
feeding the UI, ref: ui/stats/BaseStatsListener.java): ``Counter``,
``Gauge`` and ``Histogram`` families with labels, a ``snapshot()`` dict
any renderer can walk (``monitor/exposition.py`` turns it into
Prometheus text-format v0.0.4 or JSON), and scrape-time collectors for
values that are only known at read time (device memory).

Histograms are fixed log-bucket counts PLUS reservoir percentiles:
the bucket counts make the metric a real Prometheus histogram
(aggregatable across processes), while the embedded
``nn/listeners.LatencyHistogram`` reservoir gives exact-ish p50/p95/p99
without a scrape-side quantile engine — the same estimator the serving
stats RPC always reported.
"""

from __future__ import annotations

import threading
from bisect import bisect_left
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

# Log-ish ladder from 0.5 ms to 30 s — the latency range a training step
# or serving request plausibly spans (Prometheus-default style).
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)

_NO_LABELS: Tuple[str, ...] = ()


def _label_values(label_names: Sequence[str], kv: Dict[str, str]) -> Tuple:
    if set(kv) != set(label_names):
        raise ValueError(f"labels {sorted(kv)} != declared "
                         f"{sorted(label_names)}")
    return tuple(str(kv[k]) for k in label_names)


class _CounterChild:
    __slots__ = ("_lock", "value")

    def __init__(self):
        self._lock = threading.Lock()
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        if n < 0:
            raise ValueError("counters only go up")
        with self._lock:
            self.value += n

    def sample(self) -> dict:
        return {"value": self.value}


class _GaugeChild:
    __slots__ = ("_lock", "value")

    def __init__(self):
        self._lock = threading.Lock()
        self.value: Optional[float] = None

    def set(self, v: float) -> None:
        with self._lock:
            self.value = float(v)

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self.value = (self.value or 0.0) + n

    def dec(self, n: float = 1.0) -> None:
        self.inc(-n)

    def sample(self) -> dict:
        return {"value": self.value if self.value is not None else 0.0}


class _HistogramChild:
    """Fixed-bucket counts + a LatencyHistogram reservoir for
    percentiles.  ``observe``/``record`` are synonyms so the serving
    stack's existing ``LatencyHistogram.record`` call sites drop in."""

    __slots__ = ("_lock", "buckets", "_counts", "reservoir")

    def __init__(self, buckets: Sequence[float]):
        # lazy import: monitor must stay importable mid-way through the
        # package __init__ chain (ops/bucketing imports monitor while
        # deeplearning4j_tpu/__init__ is still importing nn.multilayer)
        from deeplearning4j_tpu.nn.listeners import LatencyHistogram
        self._lock = threading.Lock()
        self.buckets = tuple(sorted(float(b) for b in buckets))
        self._counts = [0] * (len(self.buckets) + 1)  # last = +Inf overflow
        self.reservoir = LatencyHistogram()

    def observe(self, v: float) -> None:
        v = float(v)
        self.reservoir.record(v)
        with self._lock:
            self._counts[bisect_left(self.buckets, v)] += 1

    record = observe  # LatencyHistogram call-site compatibility

    def sample(self) -> dict:
        res = self.reservoir
        with self._lock:
            counts = list(self._counts)
        with res._lock:
            count, total, mx = res.count, res.total, res.max
        cum, buckets = 0, {}
        for b, c in zip(self.buckets, counts):
            cum += c
            buckets[repr(b)] = cum
        buckets["+Inf"] = count
        return {
            "count": count,
            "sum": total,
            "max": mx if count else None,
            "buckets": buckets,
            "p50": res.percentile(0.50),
            "p95": res.percentile(0.95),
            "p99": res.percentile(0.99),
        }

    def latency_snapshot(self) -> dict:
        """The serving stats RPC's legacy ``*_ms`` dict shape."""
        return self.reservoir.snapshot()


class _Family:
    """One metric family: name + help + label names + children keyed by
    label values.  ``labels(**kv)`` get-or-creates a child; the no-label
    convenience methods (inc/set/observe) proxy to the unlabeled child."""

    kind = "untyped"
    _child_cls: Any = None

    def __init__(self, name: str, help: str,
                 label_names: Sequence[str] = _NO_LABELS, **opts):
        self.name = name
        self.help = help
        self.label_names = tuple(label_names)
        self._opts = opts
        self._lock = threading.Lock()
        self._children: Dict[Tuple, Any] = {}

    def _make_child(self):
        return self._child_cls()

    def labels(self, **kv):
        key = _label_values(self.label_names, kv)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = self._make_child()
                self._children[key] = child
            return child

    def _default_child(self):
        if self.label_names:
            raise ValueError(f"{self.name} has labels "
                             f"{self.label_names}; use .labels(...)")
        return self.labels()

    def samples(self) -> List[dict]:
        with self._lock:
            items = list(self._children.items())
        return [{"labels": dict(zip(self.label_names, key)),
                 **child.sample()} for key, child in items]

    def describe(self) -> dict:
        return {"type": self.kind, "help": self.help,
                "label_names": list(self.label_names),
                "samples": self.samples()}


class Counter(_Family):
    kind = "counter"
    _child_cls = _CounterChild

    def inc(self, n: float = 1.0) -> None:
        self._default_child().inc(n)

    @property
    def value(self) -> float:
        return self._default_child().value


class Gauge(_Family):
    kind = "gauge"
    _child_cls = _GaugeChild

    def set(self, v: float) -> None:
        self._default_child().set(v)

    def inc(self, n: float = 1.0) -> None:
        self._default_child().inc(n)

    @property
    def value(self) -> Optional[float]:
        return self._default_child().value


class Histogram(_Family):
    kind = "histogram"

    def _make_child(self):
        return _HistogramChild(self._opts.get("buckets") or DEFAULT_BUCKETS)

    def observe(self, v: float) -> None:
        self._default_child().observe(v)


class MetricsRegistry:
    """Thread-safe family store.  ``counter``/``gauge``/``histogram``
    get-or-create (re-declaring with a different type raises — the usual
    copy-paste bug); collectors run at ``snapshot()`` time for values
    only known at scrape (device memory, cache residency)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._families: Dict[str, _Family] = {}
        self._collectors: List[Callable[["MetricsRegistry"], None]] = []

    def _get_or_create(self, cls, name: str, help: str,
                       labels: Sequence[str], **opts):
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = cls(name, help, labels, **opts)
                self._families[name] = fam
            elif not isinstance(fam, cls):
                raise ValueError(f"{name} already registered as {fam.kind}")
            return fam

    def counter(self, name: str, help: str = "",
                labels: Sequence[str] = _NO_LABELS) -> Counter:
        return self._get_or_create(Counter, name, help, labels)

    def gauge(self, name: str, help: str = "",
              labels: Sequence[str] = _NO_LABELS) -> Gauge:
        return self._get_or_create(Gauge, name, help, labels)

    def histogram(self, name: str, help: str = "",
                  labels: Sequence[str] = _NO_LABELS,
                  buckets: Optional[Sequence[float]] = None) -> Histogram:
        return self._get_or_create(Histogram, name, help, labels,
                                   buckets=buckets)

    def get(self, name: str) -> Optional[_Family]:
        with self._lock:
            return self._families.get(name)

    def register_collector(
            self, fn: Callable[["MetricsRegistry"], None]) -> None:
        with self._lock:
            if fn not in self._collectors:
                self._collectors.append(fn)

    def snapshot(self) -> Dict[str, dict]:
        """{family_name: {type, help, label_names, samples: [...]}} —
        the contract of every renderer (exposition.py) and the gateway
        stats RPC.  Collector failures are swallowed: a scrape must never
        take the server down."""
        with self._lock:
            collectors = list(self._collectors)
        for fn in collectors:
            try:
                fn(self)
            except Exception:
                pass
        with self._lock:
            families = sorted(self._families.items())
        return {name: fam.describe() for name, fam in families}


_REGISTRY = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """THE process-wide registry — train, serving and UI all
    meter into this one instance so a single scrape sees everything."""
    return _REGISTRY
