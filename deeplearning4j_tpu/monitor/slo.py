"""SLO monitoring — declarative objectives, multi-window burn rates,
and an ``ok → warning → burning`` alert state machine
(docs/OBSERVABILITY.md "Fleet federation & SLOs").

Metrics say what the system is doing; an **objective** says what it is
SUPPOSED to be doing: "99% of predicts under 250 ms", "99.9% of
requests served, not shed".  This module evaluates objectives from
registry snapshots — the local process registry, or a federated fleet
snapshot (``monitor/federation.py``) — so the same tracker watches one
gateway or a whole fleet.

**Burn rate** is the SRE-workbook quantity: over a rolling window,
``(bad / total) / error_budget`` where ``error_budget = 1 - target``.
Burn 1.0 consumes exactly the budget the objective allots; burn 14.4
over a 5-minute window is the classic "page now" fast-burn signal.
Each objective evaluates TWO windows — fast (default 5 m) and slow
(default 1 h) — and the state machine is:

* ``burning``  — fast-window burn ≥ ``burning_burn`` (default 14.4)
  AND the slow window confirms budget is actually being consumed
  (slow burn ≥ 1.0) — a blip after an idle hour does not page;
* ``warning``  — either window's burn ≥ ``warn_burn`` (default 2.0);
* ``ok``       — otherwise.

Every state change journals ``slo.state_changed``; a flip INTO
``burning`` also writes a flight-recorder dump (``slo_fast_burn``) so
the journal tail around the regression is preserved before it rotates
out.  States/burns/budgets are metered as
``dl4j_slo_{burn_rate,budget_remaining,state}`` with ``objective`` and
``series`` labels (``series`` is the label-set key, e.g.
``model=lstm.zip|tenant=acme``; the fleet tier prefixes it with the
scope, e.g. ``replica=r0|``).

``DL4J_SLO=0`` (or :func:`set_enabled`) is the kill switch and the
overhead A/B lever.

**Alert delivery**: burn states that only live in ``/metrics`` page
nobody.  ``SloTracker(alert_sink=...)`` delivers every
``slo.state_changed`` flip to a sink — a callable (the in-process
pager hook), an ``http(s)://`` webhook URL (JSON POST), or a
``cmd:<shell command>`` (payload JSON on stdin).  With no explicit
sink, the ``DL4J_SLO_WEBHOOK`` env var supplies one.  Delivery runs
through a :class:`~deeplearning4j_tpu.resilience.policy.RetryPolicy`
(transient webhook failures retry with backoff inside a small
deadline) and is metered ``dl4j_slo_alerts_total{outcome=}``
(``delivered`` / ``failed``).
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from deeplearning4j_tpu.monitor import events, flight
from deeplearning4j_tpu.monitor.registry import get_registry

OK, WARNING, BURNING = "ok", "warning", "burning"
STATE_VALUES = {OK: 0, WARNING: 1, BURNING: 2}

_flags = {"enabled": None}


def set_enabled(on: Optional[bool]) -> None:
    """Force SLO evaluation on/off; ``None`` restores the env default
    (``DL4J_SLO``) — the overhead A/B lever, mirroring
    ``events.set_enabled``."""
    _flags["enabled"] = None if on is None else bool(on)


def enabled() -> bool:
    on = _flags["enabled"]
    if on is not None:
        return on
    return os.environ.get("DL4J_SLO", "1") != "0"


ENV_WEBHOOK = "DL4J_SLO_WEBHOOK"


def _webhook_sink(url: str):
    """JSON-POST alert sink.  Non-2xx and transport failures raise a
    retryable error so the tracker's RetryPolicy engages."""
    import urllib.error
    import urllib.request

    from deeplearning4j_tpu.resilience.errors import TransientError

    def deliver(payload: dict) -> None:
        body = json.dumps(payload).encode()
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=5.0) as r:
                r.read()
        except (urllib.error.URLError, ConnectionError, TimeoutError,
                OSError) as e:
            raise TransientError(
                f"slo webhook {url} failed: "
                f"{getattr(e, 'reason', e)}") from None
    return deliver


def _command_sink(command: str):
    """Shell-command alert sink (``cmd:<command>``): the payload JSON
    arrives on stdin — the pager/runbook integration hook."""
    import subprocess

    from deeplearning4j_tpu.resilience.errors import TransientError

    def deliver(payload: dict) -> None:
        proc = subprocess.run(command, shell=True,
                              input=json.dumps(payload).encode(),
                              capture_output=True, timeout=10.0)
        if proc.returncode != 0:
            raise TransientError(
                f"slo alert command exited {proc.returncode}: "
                f"{proc.stderr[-200:]!r}")
    return deliver


def resolve_alert_sink(sink):
    """callable → itself; ``http(s)://`` → webhook; ``cmd:`` → command;
    None → the ``DL4J_SLO_WEBHOOK`` env var (or no sink)."""
    if sink is None:
        sink = os.environ.get(ENV_WEBHOOK) or None
    if sink is None or callable(sink):
        return sink
    s = str(sink)
    if s.startswith("cmd:"):
        return _command_sink(s[4:].strip())
    return _webhook_sink(s)


def _le_value(le: str) -> float:
    return float("inf") if le == "+Inf" else float(le)


def _series_key(labels: Dict[str, str]) -> str:
    if not labels:
        return "-"
    return "|".join(f"{k}={v}" for k, v in sorted(labels.items()))


class Objective:
    """One declarative objective.  Two kinds:

    * ``kind="latency"`` — ``family`` names a histogram;
      ``threshold_s`` is the latency bound (align it to a bucket
      boundary of the family's ladder — good counts come from the
      cumulative bucket at the smallest ``le ≥ threshold``); ``target``
      is the fraction that must land under it (0.99 = p99).  One series
      per label set of the family (e.g. per ``model``).

    * ``kind="availability"`` — ``good_family`` / ``bad_family`` name
      counters; ``target`` is the good fraction (0.999 = three nines).
      When the two families share label keys, series group on the
      shared keys (per model/tenant attribution); with disjoint label
      sets both sides aggregate into one ``-`` series.
    """

    def __init__(self, name: str, kind: str, target: float,
                 family: Optional[str] = None,
                 threshold_s: Optional[float] = None,
                 good_family: Optional[str] = None,
                 bad_family: Optional[str] = None,
                 fast_window_s: float = 300.0,
                 slow_window_s: float = 3600.0,
                 warn_burn: float = 2.0, burning_burn: float = 14.4):
        if kind not in ("latency", "availability"):
            raise ValueError(f"objective kind must be latency or "
                             f"availability, got {kind!r}")
        if not 0.0 < float(target) < 1.0:
            raise ValueError("target must be a fraction in (0, 1)")
        if kind == "latency" and (family is None or threshold_s is None):
            raise ValueError("latency objectives need family= and "
                             "threshold_s=")
        if kind == "availability" and (good_family is None
                                       or bad_family is None):
            raise ValueError("availability objectives need good_family= "
                             "and bad_family=")
        self.name = str(name)
        self.kind = kind
        self.target = float(target)
        self.error_budget = 1.0 - self.target
        self.family = family
        self.threshold_s = None if threshold_s is None else float(threshold_s)
        self.good_family = good_family
        self.bad_family = bad_family
        self.fast_window_s = float(fast_window_s)
        self.slow_window_s = max(float(slow_window_s), self.fast_window_s)
        self.warn_burn = float(warn_burn)
        self.burning_burn = float(burning_burn)

    def to_dict(self) -> dict:
        return {k: v for k, v in {
            "name": self.name, "kind": self.kind, "target": self.target,
            "family": self.family, "threshold_s": self.threshold_s,
            "good_family": self.good_family, "bad_family": self.bad_family,
            "fast_window_s": self.fast_window_s,
            "slow_window_s": self.slow_window_s,
            "warn_burn": self.warn_burn,
            "burning_burn": self.burning_burn}.items() if v is not None}

    # -- cumulative (bad, total) extraction ----------------------------
    def series(self, snapshot: Dict[str, dict]
               ) -> Dict[str, Tuple[float, float]]:
        """``{series_key: (bad, total)}`` — CUMULATIVE counts from one
        registry/federated snapshot; the tracker turns consecutive
        extractions into windowed rates."""
        if self.kind == "latency":
            return self._latency_series(snapshot)
        return self._availability_series(snapshot)

    def _latency_series(self, snapshot) -> Dict[str, Tuple[float, float]]:
        fam = snapshot.get(self.family)
        out: Dict[str, Tuple[float, float]] = {}
        if not fam or fam.get("type") != "histogram":
            return out
        for s in fam.get("samples", ()):
            labels = {k: v for k, v in (s.get("labels") or {}).items()
                      if k != "replica"}
            total = float(s.get("count") or 0.0)
            good = 0.0
            buckets = s.get("buckets") or {}
            eligible = [(_le_value(le), c) for le, c in buckets.items()
                        if _le_value(le) >= self.threshold_s]
            if eligible:
                good = float(min(eligible)[1])
            key = _series_key(labels)
            prev = out.get(key, (0.0, 0.0))
            out[key] = (prev[0] + max(0.0, total - good), prev[1] + total)
        return out

    def _availability_series(self, snapshot
                             ) -> Dict[str, Tuple[float, float]]:
        good_fam = snapshot.get(self.good_family) or {}
        bad_fam = snapshot.get(self.bad_family) or {}
        good_keys = {k for s in good_fam.get("samples", ())
                     for k in (s.get("labels") or {})} - {"replica"}
        bad_keys = {k for s in bad_fam.get("samples", ())
                    for k in (s.get("labels") or {})} - {"replica"}
        shared = sorted(good_keys & bad_keys)

        def project(s) -> str:
            labels = s.get("labels") or {}
            return _series_key({k: labels[k] for k in shared
                                if k in labels})

        goods: Dict[str, float] = {}
        bads: Dict[str, float] = {}
        for s in good_fam.get("samples", ()):
            k = project(s)
            goods[k] = goods.get(k, 0.0) + float(s.get("value") or 0.0)
        for s in bad_fam.get("samples", ()):
            k = project(s)
            bads[k] = bads.get(k, 0.0) + float(s.get("value") or 0.0)
        out: Dict[str, Tuple[float, float]] = {}
        for k in set(goods) | set(bads):
            g, b = goods.get(k, 0.0), bads.get(k, 0.0)
            out[k] = (b, g + b)
        return out


def default_objectives() -> List[Objective]:
    """The stock serving objectives (docs/OBSERVABILITY.md): predict
    p99 latency, decode-dispatch p99 latency, and availability =
    1 − shed rate."""
    return [
        Objective("predict_p99", "latency", 0.99,
                  family="dl4j_serving_total_seconds", threshold_s=0.25),
        Objective("decode_step_p99", "latency", 0.99,
                  family="dl4j_decode_step_seconds", threshold_s=0.1),
        Objective("availability", "availability", 0.999,
                  good_family="dl4j_serving_requests_total",
                  bad_family="dl4j_resilience_shed_total"),
    ]


class SloTracker:
    """Rolling evaluator for a set of objectives against registry (or
    federated) snapshots.  Stateless objectives + per-series history in
    the tracker, so one objective list can drive the process tracker,
    per-replica trackers AND the fleet-wide tracker without shared
    state (``series_prefix`` keeps their metric series apart)."""

    def __init__(self, objectives: Optional[List[Objective]] = None,
                 registry=None, series_prefix: str = "",
                 on_state_change: Optional[Callable] = None,
                 flight_dump: bool = True, alert_sink=None,
                 alert_retry=None):
        self.objectives = (list(objectives) if objectives is not None
                           else default_objectives())
        self._reg = registry if registry is not None else get_registry()
        self.series_prefix = str(series_prefix)
        self.on_state_change = on_state_change
        self.flight_dump = bool(flight_dump)
        self.alert_sink = resolve_alert_sink(alert_sink)
        if alert_retry is None and self.alert_sink is not None:
            from deeplearning4j_tpu.resilience.policy import RetryPolicy
            alert_retry = RetryPolicy(max_attempts=3, base_delay_ms=100,
                                      max_delay_ms=1000, deadline_s=10.0,
                                      name="slo-alert")
        self.alert_retry = alert_retry
        self._c_alerts = self._reg.counter(
            "dl4j_slo_alerts_total",
            "SLO state-change alerts by delivery outcome "
            "(delivered / failed)", ("outcome",))
        self._lock = threading.Lock()
        self._hist: Dict[Tuple[str, str], deque] = {}
        self._state: Dict[Tuple[str, str], str] = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._g_burn = self._reg.gauge(
            "dl4j_slo_burn_rate",
            "error-budget burn rate per objective/series/window (1.0 = "
            "consuming exactly the allotted budget)",
            ("objective", "series", "window"))
        self._g_budget = self._reg.gauge(
            "dl4j_slo_budget_remaining",
            "fraction of the slow-window error budget still unspent "
            "(1.0 = untouched, ≤ 0 = blown)", ("objective", "series"))
        self._g_state = self._reg.gauge(
            "dl4j_slo_state",
            "SLO alert state per objective/series: 0 ok, 1 warning, "
            "2 burning", ("objective", "series"))

    # ------------------------------------------------------------------
    # Evaluation
    # ------------------------------------------------------------------
    def evaluate(self, snapshot: Optional[Dict[str, dict]] = None,
                 now: Optional[float] = None) -> Dict[str, dict]:
        """One evaluation pass: extract cumulative counts, append to
        each series' history, compute fast/slow burns, run the state
        machine.  ``snapshot``/``now`` are injectable for determinism
        (tests, federated evaluation); defaults read the process
        registry and the wall clock.  No-op when disabled."""
        if not enabled():
            return {}
        now = time.time() if now is None else float(now)
        snap = (snapshot if snapshot is not None
                else self._partial_snapshot())
        out: Dict[str, dict] = {}
        for obj in self.objectives:
            for key, (bad, total) in sorted(obj.series(snap).items()):
                series = self.series_prefix + key
                skey = (obj.name, series)
                with self._lock:
                    hist = self._hist.setdefault(skey, deque())
                    hist.append((now, bad, total))
                    horizon = now - obj.slow_window_s
                    # keep exactly one sample at/before the slow-window
                    # start so the slow delta spans the full window
                    while len(hist) > 2 and hist[1][0] <= horizon:
                        hist.popleft()
                    samples = tuple(hist)
                    old = self._state.get(skey, OK)
                burn_fast = self._burn(samples, now, obj.fast_window_s,
                                       obj.error_budget)
                burn_slow = self._burn(samples, now, obj.slow_window_s,
                                       obj.error_budget)
                budget = self._budget_remaining(samples, now, obj)
                if burn_fast >= obj.burning_burn and burn_slow >= 1.0:
                    state = BURNING
                elif max(burn_fast, burn_slow) >= obj.warn_burn:
                    state = WARNING
                else:
                    state = OK
                self._g_burn.labels(objective=obj.name, series=series,
                                    window="fast").set(round(burn_fast, 4))
                self._g_burn.labels(objective=obj.name, series=series,
                                    window="slow").set(round(burn_slow, 4))
                self._g_budget.labels(objective=obj.name,
                                      series=series).set(round(budget, 4))
                self._g_state.labels(objective=obj.name,
                                     series=series).set(STATE_VALUES[state])
                if state != old:
                    with self._lock:
                        self._state[skey] = state
                    self._on_flip(obj, series, old, state,
                                  burn_fast, burn_slow)
                elif skey not in self._state:
                    with self._lock:
                        self._state.setdefault(skey, state)
                out.setdefault(obj.name, {})[series] = {
                    "state": state, "burn_fast": round(burn_fast, 4),
                    "burn_slow": round(burn_slow, 4),
                    "budget_remaining": round(budget, 4),
                    "bad": bad, "total": total}
        return out

    def _partial_snapshot(self) -> Dict[str, dict]:
        """Snapshot ONLY the families the objectives read — a full
        ``registry.snapshot()`` runs every scrape-time collector (host
        RSS, device memory) and walks every family, which at a tight
        evaluation cadence measurably taxes a busy serving box."""
        needed = set()
        for obj in self.objectives:
            for fam in (obj.family, obj.good_family, obj.bad_family):
                if fam:
                    needed.add(fam)
        out: Dict[str, dict] = {}
        for name in needed:
            fam = self._reg.get(name)
            if fam is not None:
                out[name] = fam.describe()
        return out

    @staticmethod
    def _window_delta(samples, now: float, window_s: float
                      ) -> Tuple[float, float]:
        """(d_bad, d_total) between now's sample and the last sample
        at-or-before the window start (falling back to the oldest)."""
        if len(samples) < 2:
            return 0.0, 0.0
        start = now - window_s
        base = samples[0]
        for s in samples:
            if s[0] <= start:
                base = s
            else:
                break
        latest = samples[-1]
        return (max(0.0, latest[1] - base[1]),
                max(0.0, latest[2] - base[2]))

    @classmethod
    def _burn(cls, samples, now: float, window_s: float,
              error_budget: float) -> float:
        d_bad, d_total = cls._window_delta(samples, now, window_s)
        if d_total <= 0 or error_budget <= 0:
            return 0.0
        return (d_bad / d_total) / error_budget

    @classmethod
    def _budget_remaining(cls, samples, now: float,
                          obj: Objective) -> float:
        d_bad, d_total = cls._window_delta(samples, now,
                                           obj.slow_window_s)
        allowed = obj.error_budget * d_total
        if allowed <= 0:
            return 1.0
        return max(-10.0, 1.0 - d_bad / allowed)

    def _on_flip(self, obj: Objective, series: str, old: str, new: str,
                 burn_fast: float, burn_slow: float) -> None:
        sev = ("error" if new == BURNING
               else "warn" if new == WARNING else "info")
        events.emit("slo.state_changed", severity=sev,
                    objective=obj.name, series=series, old=old, new=new,
                    burn_fast=round(burn_fast, 3),
                    burn_slow=round(burn_slow, 3))
        if new == BURNING and self.flight_dump:
            # the fast-burn flip is the crash-adjacent moment: preserve
            # the journal around the regression before it rotates out
            flight.dump("slo_fast_burn", extra={
                "objective": obj.to_dict(), "series": series,
                "burn_fast": round(burn_fast, 3),
                "burn_slow": round(burn_slow, 3)})
        cb = self.on_state_change
        if cb is not None:
            try:
                cb(obj, series, old, new)
            except Exception:
                pass   # a hook failure must not break evaluation
        self._deliver_alert(obj, series, old, new, burn_fast, burn_slow)

    def _deliver_alert(self, obj: Objective, series: str, old: str,
                       new: str, burn_fast: float,
                       burn_slow: float) -> None:
        """Push the flip to the configured sink through the retry
        policy; outcomes land in ``dl4j_slo_alerts_total``.  A sink
        that stays broken past the retries is counted and dropped — the
        evaluator never wedges on a dead pager."""
        sink = self.alert_sink
        if sink is None:
            return
        payload = {"kind": "slo.state_changed", "objective": obj.name,
                   "series": series, "old": old, "new": new,
                   "burn_fast": round(burn_fast, 3),
                   "burn_slow": round(burn_slow, 3),
                   "target": obj.target, "ts": time.time()}
        try:
            if self.alert_retry is not None:
                self.alert_retry.call(sink, payload)
            else:
                sink(payload)
        except Exception as e:
            self._c_alerts.labels(outcome="failed").inc()
            events.emit("slo.alert_delivered", severity="error",
                        objective=obj.name, series=series, new=new,
                        outcome="failed",
                        error=f"{type(e).__name__}: {e}")
            return
        self._c_alerts.labels(outcome="delivered").inc()
        events.emit("slo.alert_delivered", objective=obj.name,
                    series=series, new=new, outcome="delivered")

    # ------------------------------------------------------------------
    # State surface
    # ------------------------------------------------------------------
    def states(self) -> Dict[str, Dict[str, str]]:
        with self._lock:
            out: Dict[str, Dict[str, str]] = {}
            for (obj, series), state in self._state.items():
                out.setdefault(obj, {})[series] = state
            return out

    def burning_objectives(self) -> set:
        """Objective names with ANY series currently burning."""
        with self._lock:
            return {obj for (obj, _), s in self._state.items()
                    if s == BURNING}

    def healthy(self, objective: str) -> bool:
        """True when NO series of ``objective`` is burning."""
        with self._lock:
            return not any(s == BURNING
                           for (obj, _), s in self._state.items()
                           if obj == objective)

    # ------------------------------------------------------------------
    # Background evaluation
    # ------------------------------------------------------------------
    def start(self, interval_s: float = 5.0) -> "SloTracker":
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return self
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, args=(max(0.01, float(interval_s)),),
                daemon=True, name="slo-eval")
            self._thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout)

    def _loop(self, interval_s: float) -> None:
        while not self._stop.is_set():
            try:
                self.evaluate()
            except Exception:
                pass   # the evaluator must outlive any scrape surprise
            self._stop.wait(interval_s)
