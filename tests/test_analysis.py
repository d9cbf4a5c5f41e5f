"""dl4j-lint (deeplearning4j_tpu/analysis/) tests: one positive and one
negative fixture per rule, pragma/baseline suppression semantics, JSON
output schema, lock-order cycle detection on a synthetic 3-lock
inversion, the tier-1 self-lint smoke (the real package must lint
clean), and the runtime sanitizer smokes (transfer-guard-armed fit on
both engines, poisoned step caught, retrace budget).

Rule fixtures are SOURCE STRINGS written into a temp project — the
linter runs on tests/ too, so positives must not live in this file as
real code.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from deeplearning4j_tpu.analysis import core
import deeplearning4j_tpu.analysis.rules  # noqa: F401 — registers rules

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_lint(tmp_path, sources, docs=None, rules=None, baseline=None):
    """Write {relpath: source} into tmp_path and lint it."""
    for rel, src in sources.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    docs_path = None
    if docs is not None:
        d = tmp_path / "docs"
        d.mkdir(exist_ok=True)
        (d / "OBSERVABILITY.md").write_text(textwrap.dedent(docs))
        docs_path = str(d / "OBSERVABILITY.md")
    findings, project = core.lint(
        [str(tmp_path / rel) for rel in sources], root=str(tmp_path),
        docs_path=docs_path, rule_ids=rules, baseline_path=baseline)
    return findings, project


def rules_of(findings, gating_only=True):
    return sorted({f.rule for f in findings
                   if f.gates() or not gating_only})


# ----------------------------------------------------------------------
# Tracer rules
# ----------------------------------------------------------------------
def test_host_sync_in_jit_positive_and_negative(tmp_path):
    findings, _ = run_lint(tmp_path, {"m.py": """
        import jax
        import jax.numpy as jnp

        def step(p, x):
            y = jnp.dot(p, x)
            y.item()                 # positive
            v = float(y)             # positive
            n = float(x.shape[0])    # negative: static shape math
            return y * v * n

        fast = jax.jit(step)

        def host_only(y):
            return float(y)          # negative: not jit-reachable
    """}, rules=["DL4J101"])
    assert [f.line for f in findings] == [7, 8]
    assert all(f.rule == "DL4J101" for f in findings)


def test_host_transfer_in_jit_positive_and_negative(tmp_path):
    findings, _ = run_lint(tmp_path, {"m.py": """
        import jax
        import jax.numpy as jnp
        import numpy as np

        def step(p, x):
            z = np.asarray(x)        # positive
            good = jnp.asarray(x)    # negative: stays on device
            return z.sum() + good.sum()

        fast = jax.jit(step)
    """}, rules=["DL4J102"])
    assert [f.line for f in findings] == [7]


def test_impure_in_jit_positive_and_negative(tmp_path):
    findings, _ = run_lint(tmp_path, {"m.py": """
        import time
        import jax

        def step(x):
            print(x)                 # positive
            t = time.time()          # positive
            return x + t

        fast = jax.jit(step)

        def etl(x):
            print(x)                 # negative: host-side helper
            return x
    """}, rules=["DL4J103"])
    assert [f.line for f in findings] == [6, 7]


def test_retrace_risk_immediate_loop_and_closure(tmp_path):
    findings, _ = run_lint(tmp_path, {"m.py": """
        import jax

        def hammer(xs):
            out = []
            for x in xs:
                f = jax.jit(lambda a: a + 1)     # positive: jit in loop
                out.append(f(x))
            return jax.jit(sum)(out)             # positive: immediate

        def build(k):
            def inner(x):
                return x.reshape(k, -1)
            return jax.jit(inner)                # positive: closes over k

        def build_static(k):
            def inner(x, kk):
                return x.reshape(kk, -1)
            return jax.jit(inner, static_argnums=(1,))   # negative
    """}, rules=["DL4J104"])
    msgs = " | ".join(f.message for f in findings)
    assert "inside a loop" in msgs
    assert "immediately invoked" in msgs
    assert "closes over enclosing parameter `k`" in msgs
    assert len(findings) == 3


def test_hot_span_transfer_positive_and_negative(tmp_path):
    findings, _ = run_lint(tmp_path, {"m.py": """
        import numpy as np
        import jax
        from deeplearning4j_tpu import monitor

        def serve(fn, x):
            with monitor.span("serve/batch", phase="compute"):
                out = np.asarray(fn(x))          # positive: implicit sync
            with monitor.span("serve/batch", phase="compute"):
                ok = np.asarray(jax.device_get(fn(x)))   # negative
            with monitor.span("etl/decode", phase="jpeg"):
                cold = np.asarray(fn(x))         # negative: not a hot span
            return out, ok, cold
    """}, rules=["DL4J105"])
    assert [f.line for f in findings] == [8]


def test_hot_span_transfer_sees_the_step_helpers_phases(tmp_path):
    """fit()'s phases are entered through a StepSpans object: the rule
    must go on looking at them."""
    findings, _ = run_lint(tmp_path, {"m.py": """
        import numpy as np
        import jax

        def loop(net, steps, score):
            with steps.span("fit/step", phase="bookkeeping"):
                net.score = np.asarray(score)    # positive: implicit sync
            with net._steps.span("fit/step", phase="listeners",
                                 iteration=3):
                bad = score.item()               # positive
            with steps.span("fit/step", phase="bookkeeping"):
                ok = np.asarray(jax.device_get(score))   # negative
            with steps.span("pipeline/batch", phase="h2d"):
                cold = np.asarray(score)         # negative: not a hot span
            return bad, ok, cold
    """}, rules=["DL4J105"])
    assert [f.line for f in findings] == [7, 10]


def test_hot_span_transfer_sees_the_tails_two_fetch_phases(tmp_path):
    """``score_fetch`` and ``publish`` exist to hold the step's fetches:
    an explicit ``jax.device_get`` there is the point, an implicit sync
    still a finding."""
    findings, _ = run_lint(tmp_path, {"m.py": """
        import numpy as np
        import jax

        def tail(net, steps, score, counts):
            with steps.span("fit/step", phase="score_fetch"):
                bad = np.asarray(score)          # positive: implicit sync
            with steps.span("fit/step", phase="score_fetch"):
                ok = float(jax.device_get(score))        # negative
            with steps.span("fit/step", phase="publish"):
                worse = counts.item()            # positive
            with steps.span("fit/step", phase="publish"):
                fine = np.asarray(jax.device_get(counts))    # negative
            return bad, ok, worse, fine
    """}, rules=["DL4J105"])
    assert [f.line for f in findings] == [7, 11]


def test_fp64_promotion_positive_and_negative(tmp_path):
    findings, _ = run_lint(tmp_path, {"m.py": """
        import jax
        import jax.numpy as jnp
        import numpy as np

        def step(p, x):
            m = np.zeros((4, 4))                    # positive: f64 default
            w = np.ones(4, dtype=np.float32)        # negative: pinned
            g = jnp.zeros((4, 4))                   # negative: jnp is f32
            h = x.astype(np.float64)                # positive
            s = np.float64(0.0)                     # positive
            a = jnp.asarray(x, dtype=jnp.float64)   # positive: dtype kwarg
            e = np.eye(3, dtype="float32")          # negative
            b = np.zeros((2, 2), np.float32)        # negative: positional
            return p + m + w + g + h + s + a + e + b

        fast = jax.jit(step)

        def host_side(n):
            return np.zeros(n)                      # negative: host-side
    """}, rules=["DL4J106"])
    assert [f.line for f in findings] == [7, 10, 11, 12]
    assert all(f.rule == "DL4J106" for f in findings)


# ----------------------------------------------------------------------
# Concurrency rules
# ----------------------------------------------------------------------
def test_blocking_under_lock_positive_and_negative(tmp_path):
    findings, _ = run_lint(tmp_path, {"m.py": """
        import queue
        import threading

        _lock = threading.Lock()
        _q = queue.Queue()

        def bad():
            with _lock:
                return _q.get()              # positive: no timeout

        def good():
            with _lock:
                return _q.get(timeout=0.1)   # negative
    """}, rules=["DL4J201"])
    assert len(findings) == 1
    assert "without timeout" in findings[0].message


def test_lock_order_cycle_three_lock_inversion(tmp_path):
    findings, _ = run_lint(tmp_path, {"m.py": """
        import threading

        lock_a = threading.Lock()
        lock_b = threading.Lock()
        lock_c = threading.Lock()

        def ab():
            with lock_a:
                with lock_b:
                    pass

        def bc():
            with lock_b:
                with lock_c:
                    pass

        def ca():
            with lock_c:
                with lock_a:     # closes the 3-lock cycle
                    pass
    """}, rules=["DL4J202"])
    assert len(findings) == 1
    assert "lock-order cycle" in findings[0].message
    for name in ("lock_a", "lock_b", "lock_c"):
        assert name in findings[0].message


def test_lock_order_consistent_is_clean(tmp_path):
    findings, _ = run_lint(tmp_path, {"m.py": """
        import threading

        lock_a = threading.Lock()
        lock_b = threading.Lock()

        def one():
            with lock_a:
                with lock_b:
                    pass

        def two():
            with lock_a:
                with lock_b:
                    pass
    """}, rules=["DL4J202"])
    assert findings == []


def test_lock_order_cycle_across_files_and_classes(tmp_path):
    findings, _ = run_lint(tmp_path, {
        "pkg/a.py": """
            import threading

            class Batcher:
                def __init__(self):
                    self._lock = threading.Lock()

                def dispatch(self, pipe):
                    with self._lock:
                        pipe.drain()
        """,
        "pkg/b.py": """
            import threading
            from pkg.a import Batcher

            class Pipe:
                def __init__(self, batcher):
                    self._lock = threading.Lock()
                    self.batcher = batcher

                def feed(self):
                    with self._lock:
                        with self.batcher._lock:
                            pass

                def drain(self):
                    with self._lock:
                        pass
        """}, rules=["DL4J202"])
    # Batcher._lock -> (via pipe.drain? unresolvable) … the resolvable
    # inversion here is Pipe._lock -> Batcher._lock only, so no cycle:
    # the rule must NOT hallucinate one from unresolvable calls
    assert findings == []


def test_unbounded_join_positive_and_negative(tmp_path):
    findings, _ = run_lint(tmp_path, {"m.py": """
        def stop(t, parts):
            t.join()                 # positive
            t.join(5.0)              # negative: bounded
            return ", ".join(parts)  # negative: str.join
    """}, rules=["DL4J204"])
    assert [f.line for f in findings] == [3]


def test_bare_acquire_positive_and_negative(tmp_path):
    findings, _ = run_lint(tmp_path, {"m.py": """
        import threading

        _lock = threading.Lock()

        def bad():
            _lock.acquire()          # positive: no finally release
            work()
            _lock.release()

        def good():
            _lock.acquire()          # negative: released in finally
            try:
                work()
            finally:
                _lock.release()

        def best():
            with _lock:              # negative: with-statement
                work()

        def work():
            pass
    """}, rules=["DL4J203"])
    assert [f.line for f in findings] == [7]


def test_blocking_under_lock_ctor_typed_queue_and_future(tmp_path):
    # DL4J201 extension: receivers recognized by their CONSTRUCTOR
    # (queue.Queue() / submit()) even when the name says neither
    findings, _ = run_lint(tmp_path, {"m.py": """
        import queue
        import threading

        _lock = threading.Lock()
        _work = queue.Queue()

        def bad_get():
            with _lock:
                return _work.get()           # positive: ctor-typed

        def bad_result(pool):
            item = pool.submit(job)
            with _lock:
                return item.result()         # positive: submit-typed

        def good_result(pool):
            item = pool.submit(job)
            with _lock:
                return item.result(5.0)      # negative: bounded

        def job():
            return 1
    """}, rules=["DL4J201"])
    msgs = sorted(f.message for f in findings)
    assert len(findings) == 2, msgs
    assert any("_work.get() without timeout" in m for m in msgs)
    assert any("item.result() without timeout" in m for m in msgs)


# ----------------------------------------------------------------------
# Thread-protocol rules (DL4J205–208)
# ----------------------------------------------------------------------
def test_future_success_path_only(tmp_path):
    findings, _ = run_lint(tmp_path, {"m.py": """
        import threading

        class BadWorker:
            def __init__(self):
                self._pending = []
                self._t = threading.Thread(target=self._loop)

            def _loop(self):
                for item, fut in self._pending:
                    fut.set_result(item)     # positive: success only

        class GoodWorker:
            def __init__(self):
                self._pending = []
                self._t = threading.Thread(target=self._loop)

            def _loop(self):
                for item, fut in self._pending:
                    try:
                        fut.set_result(work(item))
                    except Exception as e:
                        fut.set_exception(e)  # resolved on error too

        def work(item):
            return item
    """}, rules=["DL4J205"])
    assert len(findings) == 1
    assert "success path" in findings[0].message
    assert "BadWorker._loop" in findings[0].symbol


def test_unbounded_wait_on_device_thread(tmp_path):
    findings, _ = run_lint(tmp_path, {"m.py": """
        import queue
        import threading

        import jax.numpy as jnp

        class DeviceOwner:
            def __init__(self):
                self._work = queue.Queue()
                self._buf = jnp.zeros((4,))
                self._t = threading.Thread(target=self._loop)

            def _loop(self):
                try:
                    while True:
                        item = self._work.get()      # positive
                except Exception:
                    pass

        class HostOnly:
            def __init__(self):
                self._work = queue.Queue()
                self._t = threading.Thread(target=self._loop)

            def _loop(self):
                try:
                    while True:
                        item = self._work.get()      # negative: no device
                except Exception:
                    pass

        class BoundedOwner:
            def __init__(self):
                self._work = queue.Queue()
                self._buf = jnp.zeros((4,))
                self._t = threading.Thread(target=self._loop)

            def _loop(self):
                try:
                    while True:
                        item = self._work.get(timeout=1.0)   # negative
                except Exception:
                    pass
    """}, rules=["DL4J206"])
    assert len(findings) == 1
    assert "owns device" in findings[0].message
    assert "DeviceOwner._loop" in findings[0].symbol


def test_shared_write_outside_lock(tmp_path):
    findings, _ = run_lint(tmp_path, {"m.py": """
        import threading

        class Counter:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0

            def inc(self):
                with self._lock:
                    self.n += 1

            def dec(self):
                with self._lock:
                    self.n -= 1

            def reset(self):
                self.n = 0        # positive: lock-free minority write

        class Disciplined:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0

            def inc(self):
                with self._lock:
                    self.n += 1

            def dec(self):
                with self._lock:
                    self.n -= 1

            def reset(self):
                with self._lock:
                    self._reset_locked()

            def _reset_locked(self):
                self.n = 0        # negative: _locked convention
    """}, rules=["DL4J207"])
    assert len(findings) == 1
    assert "self.n" in findings[0].message
    assert findings[0].symbol == "Counter.reset"


def test_shared_write_majority_unguarded_is_owner_thread_style(tmp_path):
    # a single-owner-thread attribute (most writes lock-free, the
    # locked ones being crash paths) must NOT be flagged
    findings, _ = run_lint(tmp_path, {"m.py": """
        import threading

        class Owner:
            def __init__(self):
                self._lock = threading.Lock()
                self.buf = None

            def step_a(self):
                self.buf = 1

            def step_b(self):
                self.buf = 2

            def step_c(self):
                self.buf = 3

            def crash_a(self):
                with self._lock:
                    self.buf = None

            def crash_b(self):
                with self._lock:
                    self.buf = None
    """}, rules=["DL4J207"])
    assert findings == []


def test_thread_without_crash_handler(tmp_path):
    findings, _ = run_lint(tmp_path, {"m.py": """
        import threading

        def fragile():
            work()                   # positive: no handler

        def sturdy():
            try:
                work()
            except Exception:
                pass

        def spawn():
            threading.Thread(target=fragile).start()
            threading.Thread(target=sturdy).start()

        def work():
            return 1
    """}, rules=["DL4J208"])
    assert len(findings) == 1
    assert "fragile" in findings[0].message


def test_thread_rules_exempt_test_files(tmp_path):
    findings, _ = run_lint(tmp_path, {"test_m.py": """
        import threading

        def fragile():
            return 1

        def spawn():
            threading.Thread(target=fragile).start()
    """}, rules=["DL4J205", "DL4J206", "DL4J207", "DL4J208"])
    assert findings == []


# ----------------------------------------------------------------------
# Observability drift rules
# ----------------------------------------------------------------------
_DOCS = """
    # Observability

    | Metric | Type | Labels | Meaning |
    |---|---|---|---|
    | `dl4j_good_total` | counter | — | documented and registered |
    | `dl4j_model_cache_{hits,misses}_total` | counter | — | brace row |
    | `dl4j_ghost_total` | counter | — | documented, never registered |
"""


def test_metric_drift_both_directions(tmp_path):
    findings, _ = run_lint(tmp_path, {"m.py": """
        def wire(reg):
            reg.counter("dl4j_good_total", "ok")
            reg.counter("dl4j_rogue_total", "undocumented")
            for k in ("hits", "misses"):
                reg.counter(f"dl4j_model_cache_{k}_total", "pattern ok")
    """}, docs=_DOCS, rules=["DL4J301", "DL4J302"])
    by_rule = {f.rule: f for f in findings}
    assert set(by_rule) == {"DL4J301", "DL4J302"}
    assert "dl4j_rogue_total" in by_rule["DL4J301"].message
    assert "dl4j_ghost_total" in by_rule["DL4J302"].message


def test_metric_drift_test_files_exempt_from_301(tmp_path):
    findings, _ = run_lint(tmp_path, {"test_m.py": """
        def wire(reg):
            reg.counter("dl4j_adhoc_test_total", "test-only metric")
    """}, docs=_DOCS, rules=["DL4J301"])
    assert findings == []


_EVENT_DOCS = """
    # Observability

    | Metric | Type | Labels | Meaning |
    |---|---|---|---|
    | `dl4j_good_total` | counter | — | unrelated metric row |

    ## Tracing & flight recorder

    ### Event taxonomy

    | Event | Severity | Key fields | Emitted when |
    |---|---|---|---|
    | `request.done` | info | `request_id` | a request completed |
    | `batcher.died` | error | `error` | declared-only, still valid |
    | `ghost.event` | info | — | documented, never emitted |

    ## Next section

    Dotted names outside the taxonomy section — prose like
    `conf.shape_bucketing` or this table — must NOT count as rows:

    | `prose.outside_section` | not a taxonomy row |
"""


def test_event_drift_both_directions(tmp_path):
    findings, _ = run_lint(tmp_path, {"m.py": """
        EVENT_TYPES = ("request.done", "batcher.died")

        def wire(journal):
            journal.emit("request.done", request_id="r1")
            journal.emit("rogue.event", oops=True)
    """}, docs=_EVENT_DOCS, rules=["DL4J303", "DL4J304"])
    by_rule = {f.rule: f for f in findings}
    assert set(by_rule) == {"DL4J303", "DL4J304"}
    assert "rogue.event" in by_rule["DL4J303"].message
    assert "ghost.event" in by_rule["DL4J304"].message
    # prose outside the taxonomy section never reaches the stale check
    assert "prose.outside_section" not in by_rule["DL4J304"].message


def test_event_drift_declared_but_unemitted_type_must_be_documented(
        tmp_path):
    # batcher.died is declared in EVENT_TYPES (not emitted) and
    # documented — no finding in either direction for it; an
    # UNdocumented declared type is a DL4J303 hit
    findings, _ = run_lint(tmp_path, {"m.py": """
        EVENT_TYPES = ("request.done", "batcher.died", "secret.type")

        def wire(journal):
            journal.emit("request.done")
    """}, docs=_EVENT_DOCS, rules=["DL4J303"])
    assert len(findings) == 1
    assert "secret.type" in findings[0].message


def test_event_drift_test_files_and_plain_strings_exempt(tmp_path):
    findings, _ = run_lint(tmp_path, {
        "test_m.py": """
            def probe(journal):
                journal.emit("adhoc.test_event")
        """,
        "m.py": """
            def other(queue):
                # non-dotted first args are not event emits
                queue.emit("not_an_event_name")
                queue.emit(123)
        """}, docs=_EVENT_DOCS, rules=["DL4J303"])
    assert findings == []


def test_event_doc_rule_silent_without_journal_code(tmp_path):
    # a project with no emits and no EVENT_TYPES has nothing to drift:
    # the taxonomy table alone must not fail DL4J304
    findings, _ = run_lint(tmp_path, {"m.py": """
        def plain():
            return 1
    """}, docs=_EVENT_DOCS, rules=["DL4J304"])
    assert findings == []


# ----------------------------------------------------------------------
# Pragmas, baseline, CLI
# ----------------------------------------------------------------------
_PRAGMA_SRC = """
    import jax
    import jax.numpy as jnp

    def step(p):
        a = float(jnp.sum(p))  # dl4j: noqa[DL4J101] intentional: reason text
        b = float(jnp.max(p))  # dl4j: noqa[DL4J999] wrong rule id
        c = float(jnp.min(p))  # dl4j: noqa
        return a + b + c

    fast = jax.jit(step)
"""


def test_pragma_suppression_semantics(tmp_path):
    findings, _ = run_lint(tmp_path, {"m.py": _PRAGMA_SRC},
                           rules=["DL4J101"])
    by_line = {f.line: f for f in findings}
    assert by_line[6].suppressed                     # matching rule id
    assert by_line[6].noqa_reason.startswith("intentional")
    assert not by_line[7].suppressed                 # wrong rule id
    assert by_line[8].suppressed                     # bare noqa = all
    assert not by_line[6].gates() and by_line[7].gates()


def test_baseline_roundtrip_and_new_finding(tmp_path):
    src = {"m.py": """
        import jax
        import jax.numpy as jnp

        def step(p):
            return float(jnp.sum(p))

        fast = jax.jit(step)
    """}
    findings, _ = run_lint(tmp_path, src, rules=["DL4J101"])
    assert len(findings) == 1 and findings[0].gates()
    bl = tmp_path / "baseline.json"
    core.Baseline.write(str(bl), findings)
    findings2, _ = run_lint(tmp_path, src, rules=["DL4J101"],
                            baseline=str(bl))
    assert len(findings2) == 1 and findings2[0].baselined \
        and not findings2[0].gates()
    # a NEW finding is not covered by the old baseline (indentation
    # matches the block above — run_lint dedents the whole source)
    src["m.py"] += ("\n"
                    "        def step2(p):\n"
                    "            return float(jnp.max(p))\n"
                    "\n"
                    "        fast2 = jax.jit(step2)\n")
    findings3, _ = run_lint(tmp_path, src, rules=["DL4J101"],
                            baseline=str(bl))
    assert sorted(f.gates() for f in findings3) == [False, True]


def test_baseline_fingerprint_survives_line_shift(tmp_path):
    src = """
        import jax
        import jax.numpy as jnp

        def step(p):
            return float(jnp.sum(p))

        fast = jax.jit(step)
    """
    findings, _ = run_lint(tmp_path, {"m.py": src}, rules=["DL4J101"])
    bl = tmp_path / "baseline.json"
    core.Baseline.write(str(bl), findings)
    shifted = "# a new leading comment line\n" + textwrap.dedent(src)
    findings2, _ = run_lint(tmp_path, {"m.py": shifted}, rules=["DL4J101"],
                            baseline=str(bl))
    assert len(findings2) == 1 and findings2[0].baselined


def test_cli_json_schema_and_exit_codes(tmp_path):
    (tmp_path / "m.py").write_text(textwrap.dedent("""
        import jax
        import jax.numpy as jnp

        def step(p):
            return float(jnp.sum(p))

        fast = jax.jit(step)
    """))
    env = dict(os.environ, PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.analysis", "m.py",
         "--format", "json", "--no-baseline"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == 1, proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["version"] == 1
    assert set(doc) == {"version", "findings", "summary"}
    f = doc["findings"][0]
    for key in ("rule", "severity", "path", "line", "col", "message",
                "symbol", "suppressed", "baselined", "fingerprint"):
        assert key in f
    assert f["rule"] == "DL4J101" and f["severity"] == "error"
    assert doc["summary"]["gating"] == 1
    assert doc["summary"]["by_rule"] == {"DL4J101": 1}
    # clean file exits 0
    (tmp_path / "m.py").write_text("x = 1\n")
    proc = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.analysis", "m.py",
         "--no-baseline"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_stale_baseline_warned_and_pruned(tmp_path):
    findings, _ = run_lint(tmp_path, {"m.py": """
        import jax
        import jax.numpy as jnp

        def step(p):
            return float(jnp.sum(p))

        fast = jax.jit(step)
    """}, rules=["DL4J101"])
    bl = tmp_path / "baseline.json"
    core.Baseline.write(str(bl), findings)
    # poison the baseline with an entry that fires nowhere
    doc = json.loads(bl.read_text())
    doc["findings"].append({
        "rule": "DL4J101", "path": "gone.py", "symbol": "ghost",
        "message": "host sync that no longer exists",
        "fingerprint": "DL4J101::gone.py::ghost::stale"})
    bl.write_text(json.dumps(doc))

    env = dict(os.environ, PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.analysis", "m.py",
         "--baseline", str(bl), "--rules", "DL4J101",
         "--format", "json"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout)
    assert out["summary"]["stale_baseline"] == \
        ["DL4J101::gone.py::ghost::stale"]
    # text mode prints the warning
    proc_t = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.analysis", "m.py",
         "--baseline", str(bl), "--rules", "DL4J101"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=120)
    assert "stale baseline entry" in proc_t.stdout

    # --prune-baseline drops exactly the stale entry
    proc2 = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.analysis", "m.py",
         "--baseline", str(bl), "--rules", "DL4J101",
         "--prune-baseline"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=120)
    assert proc2.returncode == 0, proc2.stdout + proc2.stderr
    assert "1 stale entry dropped" in proc2.stdout
    kept = json.loads(bl.read_text())["findings"]
    assert len(kept) == 1 and kept[0]["path"] == "m.py"
    # pruned baseline still suppresses the live finding
    proc3 = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.analysis", "m.py",
         "--baseline", str(bl), "--rules", "DL4J101",
         "--format", "json"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=120)
    out3 = json.loads(proc3.stdout)
    assert proc3.returncode == 0
    assert out3["summary"]["stale_baseline"] == []
    assert out3["summary"]["baselined"] == 1


def test_parse_error_is_a_finding(tmp_path):
    findings, _ = run_lint(tmp_path, {"m.py": "def broken(:\n"})
    assert findings and findings[0].rule == "DL4J000"


def test_rule_registry_has_at_least_eight_distinct_rules():
    assert len(core.RULES) >= 8
    assert {r.severity for r in core.RULES.values()} <= {
        core.ERROR, core.WARNING, core.INFO}
    assert len({r.name for r in core.RULES.values()}) == len(core.RULES)


# ----------------------------------------------------------------------
# Tier-1 smoke: the real package lints clean
# ----------------------------------------------------------------------
def test_repo_lints_clean_with_checked_in_baseline():
    env = dict(os.environ, PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "-m", "deeplearning4j_tpu.analysis",
         "deeplearning4j_tpu", "tests", "--format", "json"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    doc = json.loads(proc.stdout)
    assert doc["summary"]["gating"] == 0
    # every suppression in the repo carries a reason string
    for f in doc["findings"]:
        if f["suppressed"]:
            assert f["noqa_reason"], f


# ----------------------------------------------------------------------
# Sanitizer smokes
# ----------------------------------------------------------------------
def _mln(bucketing=True):
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    conf = (NeuralNetConfiguration.builder().seed(7).learning_rate(0.05)
            .shape_bucketing(bucketing)
            .list()
            .layer(L.DenseLayer(n_in=6, n_out=8, activation="relu"))
            .layer(L.OutputLayer(n_in=8, n_out=3, activation="softmax",
                                 loss="negativeloglikelihood"))
            .build())
    return MultiLayerNetwork(conf).init()


def _cg(bucketing=True):
    from deeplearning4j_tpu.nn.conf.graph_conf import GraphBuilder
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.conf.network import GlobalConf
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    g = GlobalConf(seed=7, learning_rate=0.05, updater="sgd",
                   shape_bucketing=bucketing)
    conf = (GraphBuilder(g)
            .add_inputs("in").set_input_types(InputType.feed_forward(6))
            .add_layer("d", DenseLayer(n_in=6, n_out=8,
                                       activation="relu"), "in")
            .add_layer("out", OutputLayer(
                n_in=8, n_out=3, activation="softmax",
                loss="negativeloglikelihood"), "d")
            .set_outputs("out").build())
    return ComputationGraph(conf).init()


def _xy(n=37):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return x, y


def test_sanitized_fit_mln_completes(monkeypatch):
    monkeypatch.setenv("DL4J_SANITIZE", "1")
    from deeplearning4j_tpu import monitor
    net = _mln(bucketing=True)
    x, y = _xy()
    from deeplearning4j_tpu.datasets.dataset import DataSet
    net.fit(DataSet(x, y), epochs=3)
    assert np.isfinite(net.score())
    fam = monitor.get_registry().get("dl4j_sanitizer_violations_total")
    before = sum(s["value"] for s in fam.describe()["samples"]) \
        if fam else 0.0
    assert before == pytest.approx(before)  # no crash reading telemetry


def test_sanitized_fit_cg_completes(dl4j_sanitize):
    from deeplearning4j_tpu.datasets.dataset import MultiDataSet
    net = _cg(bucketing=True)
    x, y = _xy()
    net.fit(MultiDataSet([x], [y]), epochs=3)
    assert np.isfinite(float(net._score))


def test_poisoned_step_is_caught(monkeypatch):
    monkeypatch.setenv("DL4J_SANITIZE", "1")
    from deeplearning4j_tpu.datasets.dataset import DataSet
    net = _mln()
    x, y = _xy()
    ds = DataSet(x, y)
    net.fit(ds, epochs=1)  # steady state: step compiled
    orig = net._step_fn

    def poisoned(params, state, opts, f, l, fm, lm, it, rng):
        f = np.asarray(f)  # host round-trip: pull + implicit re-upload
        return orig(params, state, opts, f, l, fm, lm, it, rng)

    net._step_fn = poisoned
    with pytest.raises(Exception, match="[Tt]ransfer"):
        net.fit(ds, epochs=1)
    net._step_fn = orig
    from deeplearning4j_tpu import monitor
    fam = monitor.get_registry().get("dl4j_sanitizer_violations_total")
    assert fam is not None
    modes = {s["labels"].get("mode"): s["value"]
             for s in fam.describe()["samples"]}
    assert modes.get("transfer", 0) >= 1


def test_unsanitized_poisoned_step_passes(monkeypatch):
    monkeypatch.delenv("DL4J_SANITIZE", raising=False)
    from deeplearning4j_tpu.datasets.dataset import DataSet
    net = _mln()
    x, y = _xy()
    ds = DataSet(x, y)
    net.fit(ds, epochs=1)
    orig = net._step_fn

    def poisoned(params, state, opts, f, l, fm, lm, it, rng):
        f = np.asarray(f)
        return orig(params, state, opts, f, l, fm, lm, it, rng)

    net._step_fn = poisoned
    net.fit(ds, epochs=1)  # the silent host round-trip the guard exists for
    assert np.isfinite(net.score())


def test_retrace_budget_enforced():
    from deeplearning4j_tpu.analysis import sanitizer
    from deeplearning4j_tpu.datasets.dataset import DataSet
    net = _mln()
    x, y = _xy()
    with pytest.raises(sanitizer.SanitizerError, match="retrace budget"):
        with sanitizer.sanitize(modes=("retrace",), retrace_budget=0):
            net.fit(DataSet(x, y), epochs=1)


def test_retrace_budget_env_override(monkeypatch):
    monkeypatch.setenv("DL4J_SANITIZE", "retrace")
    monkeypatch.setenv("DL4J_SANITIZE_RETRACE_BUDGET", "50")
    from deeplearning4j_tpu.datasets.dataset import DataSet
    net = _mln()
    x, y = _xy()
    net.fit(DataSet(x, y), epochs=2)  # 1 retrace, well under 50


def test_sanitize_mode_validation():
    from deeplearning4j_tpu.analysis import sanitizer
    with pytest.raises(ValueError):
        with sanitizer.sanitize(modes=("bogus",)):
            pass
    assert not sanitizer.enabled("transfer")
    with sanitizer.sanitize(modes=("transfer",)):
        assert sanitizer.enabled("transfer")
        assert not sanitizer.enabled("rank")
