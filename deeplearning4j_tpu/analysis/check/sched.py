"""dl4j-check core: a cooperative deterministic scheduler for the
serving stack's thread protocols.

The serving path (server/batcher.py, server/decode.py, fleet/) is a
multi-threaded protocol machine whose correctness claims — "no client
hang", "exported slots can't double-count", "kill-mid-migration fails
loudly" — are ordering properties.  Example-based tests exercise one
lucky interleaving each; this module makes the interleaving a CHOICE:

* Production threads run unmodified, but every synchronization
  primitive they touch (``threading.Lock``/``RLock``/``Condition``/
  ``Event``/``Thread``, ``queue.Queue``, the ``Future`` used by the
  batcher and the decode pool) is shimmed while a :class:`Harness` is
  active, serializing all managed threads onto ONE runnable-at-a-time
  token.  At every primitive operation the thread yields to the
  scheduler, which picks who runs next — so a whole schedule is just a
  sequence of choices, recorded as the run's decision vector.

* Time is logical: ``time.monotonic``/``perf_counter``/``sleep`` are
  patched to a scheduler clock.  A timed wait registers a wake-up time
  and fires ONLY when no thread is runnable (the clock jumps to the
  earliest timer) — poll loops like the batcher's ``cond.wait(0.1)``
  stay finite, and a deadline expires exactly when the system would
  otherwise be idle waiting for it.

* Exploration policies plug in: :class:`RandomPolicy` (seeded, with
  preemption bounding a la CHESS), :class:`DFSPolicy` (bounded-
  exhaustive over decision prefixes), :class:`ReplayPolicy` (re-run a
  recorded decision vector byte-for-byte).  Same policy decisions ⇒
  byte-identical trace — every failing schedule is replayable.

* Between any two scheduling points the system is QUIESCENT (exactly
  one thread runs at a time), so invariant probes registered on the
  scheduler can read shared protocol state (slot tables, free lists)
  without synchronization and without perturbing the schedule.

Activation is scoped to the harness: outside it (or on threads the
scheduler does not manage) every shim degrades to the real primitive,
so production code paths are unchanged and objects that outlive a run
(metric registry families created during a run) keep working.

Known limits, by design: a managed thread that blocks in a non-shimmed
primitive (real socket I/O, a pre-existing real lock held across a
yield) stalls the harness — scenarios stick to the in-process protocol
surface; CPU-bound loops with no primitive ops in them cannot be
preempted (there is no yield point to preempt at).
"""

from __future__ import annotations

import _thread
import random
import threading as _rt
import time as _time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

# Real primitives, captured before any Harness ever patches the module
# attributes.  The scheduler's OWN synchronization must be built on
# raw ``_thread`` primitives: the stdlib's Thread/Semaphore/Event
# classes resolve Condition/Event from the ``threading`` module
# NAMESPACE at construction time, so instantiating them while the
# harness has that namespace patched would hand the scheduler its own
# shims back (infinite recursion).
_REAL_THREAD = _rt.Thread
_REAL_LOCK = _rt.Lock          # _thread.allocate_lock: namespace-free
_REAL_RLOCK = _rt.RLock        # _thread.RLock: namespace-free
_real_get_ident = _rt.get_ident
_real_monotonic = _time.monotonic


class _Token:
    """A binary handoff token on a raw ``_thread`` lock (born taken).
    The scheduler's run-permit protocol is strictly alternating —
    exactly one release per acquire — so a binary token is enough and
    stays clear of every patched class."""

    __slots__ = ("_lk",)

    def __init__(self):
        self._lk = _thread.allocate_lock()
        self._lk.acquire()

    def acquire(self) -> None:
        self._lk.acquire()

    def release(self) -> None:
        self._lk.release()

RUNNABLE, BLOCKED, DONE = "runnable", "blocked", "done"

#: the active (scheduler, monitor) pair; shims and patched factories
#: consult this instead of binding a scheduler at construction so that
#: shim objects surviving a run degrade to real primitives afterwards
ACTIVE: Dict[str, object] = {"sched": None, "monitor": None}


class Violation:
    """One checker finding: an invariant/spec breach, a deadlock, or a
    suspected hang, tagged with where in the schedule it fired."""

    __slots__ = ("kind", "message", "thread", "step")

    def __init__(self, kind: str, message: str, thread: str = "",
                 step: int = 0):
        self.kind = kind
        self.message = message
        self.thread = thread
        self.step = step

    def to_dict(self) -> dict:
        return {"kind": self.kind, "message": self.message,
                "thread": self.thread, "step": self.step}

    def __repr__(self):
        return f"Violation({self.kind}: {self.message!r} @{self.step})"


class _TState:
    """Scheduler bookkeeping for one managed thread."""

    __slots__ = ("name", "index", "os_thread", "permit", "state",
                 "waiting_on", "wake_at", "wake_reason", "error",
                 "fastpath_yields")

    def __init__(self, name: str, index: int):
        self.name = name
        self.index = index
        self.os_thread = None
        self.permit = _Token()
        self.state = RUNNABLE
        self.waiting_on: Optional[Tuple[object, str]] = None
        self.wake_at: Optional[float] = None
        self.wake_reason: Optional[str] = None
        self.error: Optional[BaseException] = None
        self.fastpath_yields = 0


# ----------------------------------------------------------------------
# Policies
# ----------------------------------------------------------------------
class RandomPolicy:
    """Seeded-random exploration with preemption bounding: at a branch
    point where the current thread could keep running, switching away
    is a preemption and at most ``max_preemptions`` happen per schedule
    (the CHESS result: most concurrency bugs need very few)."""

    def __init__(self, seed: int = 0, max_preemptions: int = 4,
                 p_preempt: float = 0.4):
        self.seed = seed
        self._rng = random.Random(seed)
        self.max_preemptions = max_preemptions
        self.p_preempt = p_preempt
        self.preemptions = 0

    def choose(self, cands: Sequence[_TState],
               cur: Optional[_TState]) -> int:
        if cur is not None and cur in cands:
            others = [i for i, c in enumerate(cands) if c is not cur]
            if others and self.preemptions < self.max_preemptions \
                    and self._rng.random() < self.p_preempt:
                self.preemptions += 1
                return self._rng.choice(others)
            return cands.index(cur)
        return self._rng.randrange(len(cands))


class DFSPolicy:
    """Bounded-exhaustive driver: follow ``prefix`` decisions, then the
    deterministic default (keep the current thread; else the oldest
    runnable).  The explorer enumerates alternatives off the recorded
    branch list."""

    def __init__(self, prefix: Sequence[int] = ()):
        self.prefix = list(prefix)
        self._i = 0
        self.preemptions = 0
        self.diverged = False

    def choose(self, cands: Sequence[_TState],
               cur: Optional[_TState]) -> int:
        default = cands.index(cur) if (cur is not None and cur in cands) \
            else 0
        if self._i < len(self.prefix):
            pick = self.prefix[self._i]
            self._i += 1
            if pick >= len(cands):
                # the scenario's branch structure shifted under this
                # prefix (can only happen for a buggy, schedule-
                # dependent scenario) — fall back to the default
                self.diverged = True
                pick = default
        else:
            pick = default
        if cur is not None and cur in cands and pick != cands.index(cur):
            self.preemptions += 1
        return pick


class ReplayPolicy(DFSPolicy):
    """Replay a recorded decision vector exactly (the trace-replay
    workflow: every violation carries its decisions)."""


# ----------------------------------------------------------------------
# The scheduler
# ----------------------------------------------------------------------
class Scheduler:
    """One scheduler = one schedule = one run of a scenario."""

    #: a thread spinning through this many consecutive yield points
    #: with no other runnable thread is forced through the slow path so
    #: the step counter (and the overrun detector) advances
    _FASTPATH_LIMIT = 128

    def __init__(self, policy=None, max_steps: int = 50000,
                 clock0: float = 1000.0):
        self.policy = policy or RandomPolicy(0)
        self.max_steps = int(max_steps)
        self.clock = float(clock0)
        self.trace: List[str] = []
        #: (n_candidates, chosen_index, current_index_or_None) at every
        #: true branch point — the schedule's identity and replay key
        self.branches: List[Tuple[int, int, Optional[int]]] = []
        self.violations: List[Violation] = []
        #: (name, fn) pairs; fn() -> Optional[str], run at every
        #: scheduling point while the system is quiescent
        self.probes: List[Tuple[str, Callable[[], Optional[str]]]] = []
        self.futures: List[object] = []
        self._threads: List[_TState] = []
        self._by_ident: Dict[int, _TState] = {}
        self._current: Optional[_TState] = None
        self._sched_sem = _Token()
        self._steps = 0
        self._labels: Dict[str, int] = {}
        self._probe_seen: set = set()
        self._active = False
        self._root: Optional[_TState] = None

    # -- identity helpers ----------------------------------------------
    def next_label(self, kind: str) -> str:
        n = self._labels.get(kind, 0) + 1
        self._labels[kind] = n
        return f"{kind}-{n}"

    def label(self, obj, kind: str) -> str:
        """A run-local label for a shim object, assigned at FIRST USE
        within this run: objects that outlive a run (metric-registry
        child locks are cached process-wide) get a fresh label in the
        next run's sequence, so identical schedules produce
        byte-identical traces regardless of what earlier runs
        created."""
        if getattr(obj, "_label_gen", None) is not self:
            obj._label_gen = self
            obj._label = self.next_label(kind)
        return obj._label

    def managed_current(self) -> Optional[_TState]:
        if not self._active:
            return None
        return self._by_ident.get(_real_get_ident())

    @property
    def decisions(self) -> List[int]:
        return [b[1] for b in self.branches]

    @property
    def steps(self) -> int:
        return self._steps

    @property
    def preemptions(self) -> int:
        return getattr(self.policy, "preemptions", 0)

    def trace_text(self) -> str:
        return "\n".join(self.trace)

    def violation(self, kind: str, message: str) -> None:
        """Record a violation (deduped per run) from specs/probes."""
        key = (kind, message)
        if key in self._probe_seen:
            return
        self._probe_seen.add(key)
        ts = self.managed_current()
        self.violations.append(Violation(
            kind, message, ts.name if ts else "", self._steps))

    # -- spawn / finish ------------------------------------------------
    def _spawn(self, name: str, body: Callable[[], None],
               is_root: bool = False) -> _TState:
        ts = _TState(name, len(self._threads))
        self._threads.append(ts)
        if is_root:
            self._root = ts

        def run_body():
            self._by_ident[_real_get_ident()] = ts
            ts.permit.acquire()  # dl4j: noqa[DL4J203] scheduler handoff token: released by the run loop, never paired with a release here
            err = None
            try:
                body()
            except BaseException as e:
                err = e
            self._finish(ts, err)

        # raw _thread spawn: threading.Thread would build its started-
        # Event from the (patched) threading namespace
        ts.os_thread = _thread.start_new_thread(run_body, ())
        return ts

    def _finish(self, ts: _TState, err: Optional[BaseException]) -> None:
        ts.state = DONE
        ts.error = err
        self.trace.append(f"{self._steps:05d} {ts.name} thread.done"
                          + (f" error={type(err).__name__}" if err else ""))
        self._sched_sem.release()

    # -- yield / block / wake (called from managed threads) ------------
    def _record(self, ts: _TState, op: str, detail: str = "") -> None:
        self.trace.append(f"{self._steps:05d} {ts.name} {op}"
                          + (f" {detail}" if detail else ""))

    def _run_probes(self) -> None:
        for name, fn in self.probes:
            try:
                msg = fn()
            except Exception as e:
                msg = f"probe crashed: {type(e).__name__}: {e}"
            if msg:
                self.violation("invariant", f"[{name}] {msg}")

    def yield_point(self, op: str, detail: str = "") -> None:
        """A scheduling point: record, probe, and hand the token back
        unless this thread is the only runnable one (fast path)."""
        ts = self.managed_current()
        if ts is None:
            return
        self._record(ts, op, detail)
        self._run_probes()
        others = any(o is not ts and o.state == RUNNABLE
                     for o in self._threads)
        if not others and ts.fastpath_yields < self._FASTPATH_LIMIT:
            ts.fastpath_yields += 1
            return
        ts.fastpath_yields = 0
        self._sched_sem.release()
        ts.permit.acquire()  # dl4j: noqa[DL4J203] scheduler handoff token, released by the run loop

    def block(self, obj: object, op: str,
              timeout: Optional[float] = None, detail: str = "") -> str:
        """Block the current thread on ``obj`` until woken (or until the
        logical timer fires, when ``timeout`` is given).  Returns the
        wake reason: ``"wake"`` or ``"timeout"``."""
        ts = self.managed_current()
        if ts is None:
            raise RuntimeError("block() outside a managed thread")
        self._record(ts, op, detail)
        self._run_probes()
        ts.state = BLOCKED
        ts.waiting_on = (obj, op)
        ts.wake_at = (self.clock + max(0.0, float(timeout))
                      if timeout is not None else None)
        ts.wake_reason = None
        ts.fastpath_yields = 0
        self._sched_sem.release()
        ts.permit.acquire()  # dl4j: noqa[DL4J203] scheduler handoff token, released by the run loop
        ts.waiting_on = None
        ts.wake_at = None
        return ts.wake_reason or "wake"

    def wake(self, ts: _TState, reason: str = "wake") -> None:
        if ts.state == BLOCKED:
            ts.state = RUNNABLE
            ts.wake_reason = reason

    # -- the run loop (controlling thread) -----------------------------
    def run(self, root_fn: Callable[[], None],
            name: str = "root") -> None:
        """Execute ``root_fn`` (and every thread it spawns) to
        completion under this scheduler.  Must be called with the
        matching :class:`Harness` active."""
        self._active = True
        try:
            self._spawn(name, root_fn, is_root=True)
            while True:
                self._steps += 1
                if self._steps > self.max_steps:
                    blocked = ", ".join(
                        f"{t.name}({t.waiting_on[1] if t.waiting_on else t.state})"
                        for t in self._threads if t.state != DONE)
                    self.violations.append(Violation(
                        "overrun",
                        f"schedule exceeded {self.max_steps} steps — "
                        f"suspected hang/livelock; live: {blocked}",
                        step=self._steps))
                    break
                cands = [t for t in self._threads if t.state == RUNNABLE]
                if not cands:
                    blocked = [t for t in self._threads
                               if t.state == BLOCKED]
                    timers = [t for t in blocked if t.wake_at is not None]
                    if timers:
                        nxt = min(timers,
                                  key=lambda s: (s.wake_at, s.index))
                        self.clock = max(self.clock, nxt.wake_at)
                        nxt.wake_reason = "timeout"
                        nxt.state = RUNNABLE
                        continue
                    if blocked:
                        waits = "; ".join(
                            f"{t.name} waiting on "
                            f"{t.waiting_on[1] if t.waiting_on else '?'}"
                            for t in blocked)
                        self.violations.append(Violation(
                            "deadlock",
                            f"all threads blocked with no timers: {waits}",
                            step=self._steps))
                    break
                choice = self._choose(cands)
                self._run_slice(choice)
            root = self._root
            if root is not None and root.error is not None:
                err = root.error
                kind = ("scenario-assert"
                        if isinstance(err, AssertionError)
                        else "scenario-error")
                self.violations.append(Violation(
                    kind, f"{type(err).__name__}: {err}", root.name,
                    self._steps))
            for t in self._threads:
                if t is not root and t.error is not None:
                    self.violations.append(Violation(
                        "thread-crash",
                        f"unhandled {type(t.error).__name__} in "
                        f"{t.name}: {t.error}", t.name, self._steps))
        finally:
            self._active = False

    def _choose(self, cands: List[_TState]) -> _TState:
        cur = self._current
        if len(cands) == 1:
            return cands[0]
        idx = self.policy.choose(cands, cur)
        cur_idx = cands.index(cur) if (cur is not None and cur in cands) \
            else None
        self.branches.append((len(cands), idx, cur_idx))
        return cands[idx]

    def _run_slice(self, ts: _TState) -> None:
        self._current = ts
        self.clock += 1e-6
        ts.permit.release()
        self._sched_sem.acquire()  # dl4j: noqa[DL4J203] scheduler handoff token: released by whichever managed thread yields next


# ----------------------------------------------------------------------
# Primitive shims.  Every shim is dual-mode: cooperative when called
# from a managed thread of the ACTIVE scheduler, a plain real primitive
# otherwise — so shim objects that outlive a run degrade gracefully.
# ----------------------------------------------------------------------
def _sched_for(obj) -> Optional[Scheduler]:
    s = ACTIVE.get("sched")
    if s is None or s.managed_current() is None:
        return None
    return s


class SLock:
    """Cooperative ``threading.Lock``."""

    _reentrant = False

    def __init__(self, label: Optional[str] = None):
        self._fixed_label = label
        self._owner: Optional[_TState] = None
        self._count = 0
        self._waiters: List[_TState] = []
        self._real = _REAL_RLOCK() if self._reentrant else _REAL_LOCK()

    @classmethod
    def _kind(cls) -> str:
        return "rlock" if cls._reentrant else "lock"

    def _lbl(self, s: Scheduler) -> str:
        return self._fixed_label or s.label(self, self._kind())

    def acquire(self, blocking: bool = True, timeout: float = -1):
        s = _sched_for(self)
        if s is None:
            if timeout is not None and timeout > 0:
                return self._real.acquire(blocking, timeout)  # dl4j: noqa[DL4J203] fallback delegate: the caller owns the release pairing
            return self._real.acquire(blocking)  # dl4j: noqa[DL4J203] fallback delegate: the caller owns the release pairing
        ts = s.managed_current()
        label = self._lbl(s)
        s.yield_point("lock.acquire", label)
        while self._owner is not None and self._owner is not ts:
            if not blocking:
                return False
            self._waiters.append(ts)
            reason = s.block(
                self, "lock.blocked", detail=label,
                timeout=timeout if (timeout is not None and timeout > 0)
                else None)
            if ts in self._waiters:
                self._waiters.remove(ts)
            if reason == "timeout" and self._owner is not None \
                    and self._owner is not ts:
                return False
        if self._owner is ts:
            if not self._reentrant:
                raise RuntimeError(
                    f"non-reentrant {self._lbl(s)} re-acquired by "
                    f"{ts.name} (self-deadlock in real execution)")
            self._count += 1
        else:
            self._owner = ts
            self._count = 1
        return True

    def release(self):
        s = _sched_for(self)
        if s is None:
            return self._real.release()
        ts = s.managed_current()
        if self._owner is not ts:
            raise RuntimeError(f"release of {self._lbl(s)} not held by "
                               f"{ts.name}")
        self._count -= 1
        if self._count > 0:
            return
        self._owner = None
        for w in list(self._waiters):
            s.wake(w)
        self._waiters.clear()
        s.yield_point("lock.release", self._lbl(s))

    def locked(self) -> bool:
        if self._owner is not None:
            return True
        got = self._real.acquire(False)  # dl4j: noqa[DL4J203] probe-acquire released on the next line
        if got:
            self._real.release()
        return not got

    # Condition integration (mirrors the private threading contract)
    def _is_owned(self) -> bool:
        s = _sched_for(self)
        return s is not None and self._owner is s.managed_current()

    def _release_save(self):
        owner, count = self._owner, self._count
        self._owner, self._count = None, 0
        s = _sched_for(self)
        if s is not None:
            for w in list(self._waiters):
                s.wake(w)
            self._waiters.clear()
        return owner, count

    def _acquire_restore(self, saved):
        self.acquire()
        _owner, count = saved
        self._count = count

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False


class SRLock(SLock):
    """Cooperative ``threading.RLock``."""

    _reentrant = True


class SCondition:
    """Cooperative ``threading.Condition`` over an :class:`SLock`/
    :class:`SRLock` (a fresh SRLock when none is given)."""

    def __init__(self, lock=None):
        self._lock = lock if lock is not None else SRLock()
        self._waiters: List[_TState] = []

    def _lbl(self, s: Scheduler) -> str:
        return s.label(self, "cond")

    # lock surface
    def acquire(self, *a, **k):
        return self._lock.acquire(*a, **k)  # dl4j: noqa[DL4J203] delegate: the caller owns the acquire/release pairing (Condition surface)

    def release(self):
        return self._lock.release()

    def __enter__(self):
        self._lock.acquire()  # dl4j: noqa[DL4J203] released in __exit__ — this IS the with-statement implementation
        return self

    def __exit__(self, *exc):
        self._lock.release()
        return False

    def wait(self, timeout: Optional[float] = None) -> bool:
        s = _sched_for(self)
        if s is None:
            raise RuntimeError(
                "SCondition waited on outside the harness "
                "(a checker-built object escaped its run)")
        ts = s.managed_current()
        if not self._lock._is_owned():
            raise RuntimeError("cannot wait on un-acquired condition")
        saved = self._lock._release_save()
        self._waiters.append(ts)
        reason = s.block(self, "cond.wait", timeout=timeout,
                         detail=self._lbl(s))
        if ts in self._waiters:
            self._waiters.remove(ts)
        self._lock._acquire_restore(saved)
        return reason != "timeout"

    def wait_for(self, predicate, timeout: Optional[float] = None):
        result = predicate()
        while not result:
            if not self.wait(timeout):
                return predicate()
            result = predicate()
        return result

    def notify(self, n: int = 1) -> None:
        s = _sched_for(self)
        if s is None:
            return
        for w in list(self._waiters[:n]):
            self._waiters.remove(w)
            s.wake(w)
        s.yield_point("cond.notify", self._lbl(s))

    def notify_all(self) -> None:
        self.notify(len(self._waiters) or 1)


class SEvent:
    """Cooperative ``threading.Event``."""

    def __init__(self):
        self._flag = False
        self._waiters: List[_TState] = []

    def _lbl(self, s: Scheduler) -> str:
        return s.label(self, "event")

    def is_set(self) -> bool:
        return self._flag

    def set(self) -> None:
        self._flag = True
        s = _sched_for(self)
        if s is not None:
            for w in list(self._waiters):
                s.wake(w)
            self._waiters.clear()
            s.yield_point("event.set", self._lbl(s))

    def clear(self) -> None:
        self._flag = False

    def wait(self, timeout: Optional[float] = None) -> bool:
        s = _sched_for(self)
        if s is None:
            # degraded mode: a set flag is still visible
            return self._flag
        ts = s.managed_current()
        s.yield_point("event.wait", self._lbl(s))
        while not self._flag:
            self._waiters.append(ts)
            reason = s.block(self, "event.blocked", timeout=timeout,
                             detail=self._lbl(s))
            if ts in self._waiters:
                self._waiters.remove(ts)
            if reason == "timeout" and not self._flag:
                return False
        return True


class SQueue:
    """Cooperative ``queue.Queue`` (FIFO, optional maxsize)."""

    def __init__(self, maxsize: int = 0):
        self.maxsize = int(maxsize)
        self._items: List[object] = []
        self._getters: List[_TState] = []
        self._putters: List[_TState] = []
        self._unfinished = 0

    def _lbl(self, s: Scheduler) -> str:
        return s.label(self, "queue")

    def qsize(self) -> int:
        return len(self._items)

    def empty(self) -> bool:
        return not self._items

    def full(self) -> bool:
        return 0 < self.maxsize <= len(self._items)

    def put(self, item, block: bool = True,
            timeout: Optional[float] = None) -> None:
        import queue as _q
        s = _sched_for(self)
        if s is None:
            if self.full():
                raise _q.Full
            self._items.append(item)
            self._unfinished += 1
            return
        ts = s.managed_current()
        s.yield_point("queue.put", self._lbl(s))
        while self.full():
            if not block:
                raise _q.Full
            self._putters.append(ts)
            reason = s.block(self, "queue.put_blocked", timeout=timeout,
                             detail=self._lbl(s))
            if ts in self._putters:
                self._putters.remove(ts)
            if reason == "timeout" and self.full():
                raise _q.Full
        self._items.append(item)
        self._unfinished += 1
        for w in list(self._getters):
            s.wake(w)
        self._getters.clear()

    def put_nowait(self, item) -> None:
        self.put(item, block=False)

    def get(self, block: bool = True, timeout: Optional[float] = None):
        import queue as _q
        s = _sched_for(self)
        if s is None:
            if not self._items:
                raise _q.Empty
            return self._items.pop(0)
        ts = s.managed_current()
        s.yield_point("queue.get", self._lbl(s))
        while not self._items:
            if not block:
                raise _q.Empty
            self._getters.append(ts)
            reason = s.block(self, "queue.get_blocked", timeout=timeout,
                             detail=self._lbl(s))
            if ts in self._getters:
                self._getters.remove(ts)
            if reason == "timeout" and not self._items:
                raise _q.Empty
        item = self._items.pop(0)
        for w in list(self._putters):
            s.wake(w)
        self._putters.clear()
        return item

    def get_nowait(self):
        return self.get(block=False)

    def task_done(self) -> None:
        self._unfinished = max(0, self._unfinished - 1)

    def join(self) -> None:
        s = _sched_for(self)
        while self._unfinished > 0 and s is not None:
            s.block(self, "queue.join", timeout=0.01,
                    detail=self._lbl(s))


class SThread:
    """Cooperative ``threading.Thread``: the spawned thread becomes a
    managed thread of the active scheduler; outside a harness it
    degrades to a plain real thread."""

    def __init__(self, group=None, target=None, name=None, args=(),
                 kwargs=None, daemon=None):
        s = ACTIVE.get("sched")
        self._target = target
        self._args = tuple(args or ())
        self._kwargs = dict(kwargs or {})
        self.name = name or (s.next_label("thread") if s else "thread")
        self.daemon = True if daemon is None else bool(daemon)
        self._ts: Optional[_TState] = None
        self._real: Optional[_rt.Thread] = None
        self._started = False
        self._joiners: List[_TState] = []

    def _run(self):
        if self._target is not None:
            self._target(*self._args, **self._kwargs)

    def start(self) -> None:
        if self._started:
            raise RuntimeError("threads can only be started once")
        self._started = True
        s = ACTIVE.get("sched")
        if s is None or not s._active:
            self._real = _REAL_THREAD(target=self._run, daemon=self.daemon,
                                      name=self.name)
            self._real.start()
            return
        sthread = self

        def body():
            try:
                sthread._run()
            finally:
                scur = ACTIVE.get("sched")
                if scur is s:
                    for w in list(sthread._joiners):
                        s.wake(w)
                    sthread._joiners.clear()

        self._ts = s._spawn(self.name, body)
        s.yield_point("thread.start", self.name)

    def is_alive(self) -> bool:
        if self._real is not None:
            return self._real.is_alive()
        return self._started and self._ts is not None \
            and self._ts.state != DONE

    def join(self, timeout: Optional[float] = None) -> None:
        if self._real is not None:
            return self._real.join(timeout)
        s = _sched_for(self)
        if s is None:
            deadline = _real_monotonic() + (timeout or 5.0)
            while self.is_alive() and _real_monotonic() < deadline:
                _time.sleep(0.002)
            return
        ts = s.managed_current()
        s.yield_point("thread.join", self.name)
        while self.is_alive():
            self._joiners.append(ts)
            reason = s.block(self, "thread.join_blocked", timeout=timeout,
                             detail=self.name)
            if ts in self._joiners:
                self._joiners.remove(ts)
            if reason == "timeout" and self.is_alive():
                return


def make_future_class():
    """Build the cooperative Future class lazily (keeps the
    concurrent.futures import off this module's import path)."""
    import concurrent.futures as _cf

    class SFuture(_cf.Future):
        """Cooperative ``concurrent.futures.Future``: ``result()``
        blocks through the scheduler; resolution wakes waiters at a
        yield point.  Registered with the scheduler so the explorer can
        assert every future was resolved on every schedule."""

        def __init__(self):
            super().__init__()
            self._swaiters: List[_TState] = []
            s = ACTIVE.get("sched")
            if s is not None:
                s.futures.append(self)

        def result(self, timeout=None):
            s = _sched_for(self)
            if s is None:
                return super().result(timeout)
            ts = s.managed_current()
            s.yield_point("future.result")
            while not self.done():
                self._swaiters.append(ts)
                reason = s.block(self, "future.blocked", timeout=timeout)
                if ts in self._swaiters:
                    self._swaiters.remove(ts)
                if reason == "timeout" and not self.done():
                    raise _cf.TimeoutError()
            return super().result(timeout=0)

        def _wake_all(self, op: str) -> None:
            s = _sched_for(self)
            if s is None:
                return
            for w in list(self._swaiters):
                s.wake(w)
            self._swaiters.clear()
            s.yield_point(op)

        def set_result(self, result):
            super().set_result(result)
            self._wake_all("future.set_result")

        def set_exception(self, exc):
            super().set_exception(exc)
            self._wake_all("future.set_exception")

    return SFuture


def schedule_point(op: str = "schedule_point") -> None:
    """An explicit yield point for scenario code (and for synthetic
    racy fixtures): a no-op outside a managed thread."""
    s = ACTIVE.get("sched")
    if s is not None:
        s.yield_point(op)


# ----------------------------------------------------------------------
# The harness: scoped activation + monkey-patching
# ----------------------------------------------------------------------
class Harness:
    """Patch the serving stack's synchronization primitives onto the
    scheduler for the duration of a ``with`` block.  One harness at a
    time per process; production code paths outside the block are
    untouched (every patch is restored on exit)."""

    _guard = _REAL_LOCK()

    def __init__(self, sched: Scheduler, monitor=None):
        self.sched = sched
        self.monitor = monitor
        self._saved: List[Tuple[object, str, object]] = []
        self.flight_dumps = 0

    def _patch(self, obj, attr, value) -> None:
        self._saved.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, value)

    def __enter__(self) -> "Harness":
        if not Harness._guard.acquire(blocking=False):  # dl4j: noqa[DL4J203] released in __exit__ — the harness IS the with-statement
            raise RuntimeError("another dl4j-check Harness is active")
        try:
            self._install()
        except BaseException:
            Harness._guard.release()
            raise
        return self

    def _install(self) -> None:
        import queue as queue_mod

        from deeplearning4j_tpu.monitor import events as ev_mod
        from deeplearning4j_tpu.monitor import compile_stages as compile_mod
        from deeplearning4j_tpu.monitor import flight as flight_mod
        from deeplearning4j_tpu.resilience import faults
        from deeplearning4j_tpu.server import batcher as batcher_mod
        from deeplearning4j_tpu.server import decode as decode_mod

        sched = self.sched
        monitor = self.monitor
        ACTIVE["sched"] = sched
        ACTIVE["monitor"] = monitor

        self._patch(_rt, "Thread", SThread)
        self._patch(_rt, "Lock", SLock)
        self._patch(_rt, "RLock", SRLock)
        self._patch(_rt, "Condition", SCondition)
        self._patch(_rt, "Event", SEvent)
        self._patch(queue_mod, "Queue", SQueue)

        # managed threads are raw _thread spawns; threading.current_
        # thread() would try to mint a _DummyThread for them, and with
        # the namespace patched the real Thread.__init__ builds its
        # started-Event from OUR shims and breaks (logging reads
        # current_thread().name on every record)
        real_current = _rt.current_thread

        class _ManagedThreadView:
            __slots__ = ("name", "daemon", "ident")

            def __init__(self, name, ident):
                self.name = name
                self.daemon = True
                self.ident = ident

            def is_alive(self):
                return True

        def fake_current_thread():
            s = ACTIVE.get("sched")
            ts = s.managed_current() if s is not None else None
            if ts is not None:
                return _ManagedThreadView(f"dl4j-check:{ts.name}",
                                          _real_get_ident())
            return real_current()

        self._patch(_rt, "current_thread", fake_current_thread)
        sfuture = make_future_class()
        self._patch(batcher_mod, "Future", sfuture)
        self._patch(decode_mod, "Future", sfuture)

        real_monotonic = _time.monotonic
        real_perf = _time.perf_counter
        real_sleep = _time.sleep

        def fake_clock():
            s = ACTIVE.get("sched")
            if s is not None and s.managed_current() is not None:
                return s.clock
            return real_monotonic()

        def fake_perf():
            s = ACTIVE.get("sched")
            if s is not None and s.managed_current() is not None:
                return s.clock
            return real_perf()

        def fake_sleep(secs):
            s = ACTIVE.get("sched")
            if s is not None and s.managed_current() is not None:
                s.block(fake_sleep, "time.sleep", timeout=max(1e-9, secs))
                return
            real_sleep(secs)

        self._patch(_time, "monotonic", fake_clock)
        self._patch(_time, "perf_counter", fake_perf)
        self._patch(_time, "sleep", fake_sleep)

        real_emit = ev_mod.emit

        def emit_hook(etype, severity="info", **fields):
            s = ACTIVE.get("sched")
            m = ACTIVE.get("monitor")
            if m is not None and s is not None \
                    and s.managed_current() is not None:
                try:
                    m.on_event(etype, severity, fields)
                except Exception as e:
                    s.violation("monitor-error",
                                f"spec monitor crashed on {etype}: "
                                f"{type(e).__name__}: {e}")
            return real_emit(etype, severity=severity, **fields)

        self._patch(ev_mod, "emit", emit_hook)
        # which call compiles is the process's history, not the
        # schedule's (a replay finds the program compiled): the compile
        # listeners' registry writes must add no yield points to a run
        self._patch(compile_mod, "_record", lambda *a, **k: None)
        self._patch(compile_mod, "_count_cache", lambda *a, **k: None)

        harness = self

        def flight_stub(reason, extra=None):
            harness.flight_dumps += 1
            return None

        self._patch(flight_mod, "dump", flight_stub)
        faults.reset()

    def __exit__(self, *exc) -> bool:
        try:
            for obj, attr, value in reversed(self._saved):
                setattr(obj, attr, value)
            self._saved.clear()
            ACTIVE["sched"] = None
            ACTIVE["monitor"] = None
            from deeplearning4j_tpu.resilience import faults
            faults.reset()
        finally:
            Harness._guard.release()
        return False
