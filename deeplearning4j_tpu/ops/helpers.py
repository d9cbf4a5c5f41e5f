"""Per-layer helper selection: fused Pallas kernels behind predicates,
kill switches and warm validation — the TPU-native equivalent of the
reference's cuDNN helper tier (``CudnnConvolutionHelper`` /
``CudnnLSTMHelper`` et al. with a builtin fallback, ref:
nn/layers/convolution/ConvolutionLayer.java:157-212 and the cuDNN paper
the pattern comes from, PAPERS.md arXiv 1410.0759).

Every op with a fused implementation registers a :class:`Helper` here:

==============  ======  =============================  =====================
op              tier    fused kernel (pallas_kernels)  dense XLA fallback
==============  ======  =============================  =====================
``lstm_step``   lstm    fused_lstm_step                ops/recurrent._lstm_cell_pre
``dropout``     dropout fused_threshold_dropout        ops/normalization.dropout
``softmax_xent`` xent   softmax_xent_rows              stable logsumexp form in ops/losses
``attention``   flash   flash_attention                dense softmax attention
``gmm``         gmm     gmm, tgmm                      ``lax.ragged_dot``
==============  ======  =============================  =====================

Selection happens automatically AT TRACE TIME, per call site: each
helper's support predicate (shape/dtype/platform) decides between the
parity-tested Pallas kernel and the dense fallback, and the decision is
metered (``dl4j_pallas_selected_total`` / ``dl4j_pallas_fallback_total``
by op).  Off-TPU nothing fuses by default — the fallback IS the
pre-helper code path, byte-identical — but each tier can be forced for
testing (the kernels then run under ``interpret=True``).

Convolutions have no entry: XLA's own convolution beat a fused conv + bias +
activation kernel wherever both were measured (the trial is PERF.md section
6, PR 28), so ``ConvolutionLayer`` calls ``ops/convolution.conv2d`` itself.

Kill switches, most-specific wins:

* ``DL4J_PALLAS=0`` — global: every tier falls back.
* ``DL4J_PALLAS_{LSTM,DROPOUT,XENT,FLASH,GMM}=0|1`` — per tier:
  ``0`` forces the fallback, ``1`` forces the fused path even off-TPU
  (interpret mode; how the parity tests exercise the kernels through
  the public ``fit``/``output`` path).
* :func:`deeplearning4j_tpu.ops.pallas_kernels.disable_kernels` — the
  runtime per-tier switch :func:`kernel_self_test` flips when a Mosaic
  compile fails on the real chip, so one bad kernel degrades to XLA
  without taking down the healthy tiers.  The flip is logged at
  WARNING with the compiler's message; ``chip_smoke.py`` treats
  any disabled tier as a failed run.

:func:`ensure_validated` is the warm-validation hook both engines call
at the top of ``fit()``: the first time any fused tier could engage it
runs :func:`kernel_self_test` so a kernel rejection surfaces (and
disables that tier) BEFORE the first real training step compiles.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Callable, Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops import pallas_kernels as pk

log = logging.getLogger(__name__)


class Helper(NamedTuple):
    """One fused-implementation registration."""
    op: str                      # registry key (lstm_step, dropout, ...)
    tier: str                    # kill-switch tier name (lstm, dropout, ...)
    test_name: str               # key in the kernel_self_test() report
    self_test: Callable[[], None]  # small-shape compile+run validation
    # validated when a trace first selects it, not at the top of every
    # fit(): a tier that few networks use costs the others nothing
    on_first_use: bool = False


_ENV_TIER = {"lstm": "DL4J_PALLAS_LSTM", "dropout": "DL4J_PALLAS_DROPOUT",
             "xent": "DL4J_PALLAS_XENT", "flash": "DL4J_PALLAS_FLASH",
             "gmm": "DL4J_PALLAS_GMM"}


def _registry():
    from deeplearning4j_tpu import monitor
    return monitor.get_registry()


def record_selection(op: str, fused: bool) -> None:
    """Meter one trace-time selection decision.  Counts move on TRACES
    (and un-jitted calls), not steps — a retrace-heavy run shows up here
    next to dl4j_compile_retraces_total."""
    try:
        if fused:
            c = _registry().counter(
                "dl4j_pallas_selected_total",
                "ops routed to a fused Pallas helper at trace time",
                labels=("op",))
        else:
            c = _registry().counter(
                "dl4j_pallas_fallback_total",
                "ops that took the dense XLA fallback at trace time",
                labels=("op",))
        c.labels(op=op).inc()
    except Exception:
        pass  # metering must never break a forward pass


def available(op: str) -> bool:
    """Is the fused tier for ``op`` eligible at all (before the per-call
    shape/dtype predicate)?  Order: global kill → runtime kill switch →
    per-tier env force → platform, outside a step that GSPMD partitions
    (pallas_kernels.partitioned_trace)."""
    tier = _HELPERS[op].tier
    if os.environ.get("DL4J_PALLAS") == "0":  # dl4j: noqa[DL4J103] env kill switch read at trace time by design (fixed per process)
        return False
    if tier in pk._disabled:
        return False
    env = os.environ.get(_ENV_TIER[tier])  # dl4j: noqa[DL4J103] env kill switch read at trace time by design (fixed per process)
    if env == "0":
        return False
    if env == "1":
        return True
    return pk._on_tpu() and not pk.partitioned_trace_active()


# ---------------------------------------------------------------------------
# Per-op selection wrappers — the call sites layers/ops route through.
# ---------------------------------------------------------------------------

def dropout(x, rate: float, rng):
    """Inverted dropout for Layer._maybe_dropout: in-kernel threshold
    mask when the dropout tier selects (no HBM mask tensor), else
    ops/normalization.dropout (jax.random.bernoulli).  Same keep
    distribution either way; the streams differ — see
    pallas_kernels.fused_threshold_dropout."""
    if available("dropout") and pk.dropout_fused_supported(x.shape, x.dtype):
        record_selection("dropout", True)
        return pk.fused_threshold_dropout(x, float(rate), rng)
    record_selection("dropout", False)
    from deeplearning4j_tpu.ops import normalization as norm_ops
    return norm_ops.dropout(x, rate, rng)


def _lstm_default_acts():
    from deeplearning4j_tpu.ops import activations as act_ops
    sig = {jax.nn.sigmoid, act_ops.sigmoid, act_ops.get("sigmoid")}
    tanh = {jnp.tanh, act_ops.tanh, act_ops.get("tanh")}
    return sig, tanh


def lstm_step_wanted(params: dict, x, gate_act, cell_act,
                     peephole: bool = True) -> bool:
    """Trace-time decision for ops/recurrent.lstm_scan: True routes the
    scan body through pallas_kernels.fused_lstm_step.  Fused supports
    the standard sigmoid/tanh peephole cell only — exotic gate
    activations keep the composable XLA cell."""
    sig, tanh = _lstm_default_acts()
    # every conjunct is a STATIC Python bool (shape/env/identity checks,
    # nothing traced) — selection is a trace-time decision by design
    ok = (peephole
          and all(k in params for k in ("pI", "pF", "pO", "RW"))
          and gate_act in sig and cell_act in tanh
          and available("lstm_step")
          and pk.lstm_fused_supported(x.shape[0], params["RW"].shape[0],
                                      x.dtype))
    record_selection("lstm_step", ok)
    return ok


def softmax_xent_wanted(n_rows: int, vocab: int) -> bool:
    """Trace-time decision for ops/losses.mcxent (shape/mask legality is
    the caller's check): fused pays off for wide-vocab row blocks where
    the saved HBM round-trips beat the kernel launch.
    ``DL4J_FUSED_XENT=1|0`` keeps its historical force-override role."""
    env = os.environ.get("DL4J_FUSED_XENT")  # dl4j: noqa[DL4J103] env flag read at trace time by design (fixed per process)
    if env == "0":
        ok = False
    elif env == "1":
        ok = True
    else:
        # static Python ints (shapes), nothing traced
        ok = (available("softmax_xent") and vocab >= 128
              and n_rows * vocab >= (1 << 16))
    record_selection("softmax_xent", ok)
    return ok


def attention_wanted(q) -> bool:
    """Trace-time decision for parallel/sequence.dense_attention: True
    routes the [B,H,T,D] core through the flash kernel (O(T·D) HBM both
    directions); the dense softmax path otherwise."""
    # static Python bools (env + shape-tuple comparisons), nothing traced
    ok = available("attention") and pk.flash_attention_supported(q)
    record_selection("attention", ok)
    return ok


_FIRST_USE: Dict[str, bool] = {}
_STARTED: Dict[str, tuple] = {}
_FIRST_USE_LOCK = threading.Lock()
# a self-test compiles a few small kernels: seconds, a minute when cold
_FIRST_USE_TIMEOUT_S = 600.0


def _start_self_test(op: str):
    """(thread, verdict list) of ``op``'s self-test started on a thread of
    its own, whose JAX state holds no trace."""
    verdict = []

    def run():
        try:
            verdict.append(_self_test_one(op, True))
        except BaseException as e:  # the test's own are caught
            verdict.append(f"error: {type(e).__name__}: {e}")

    runner = threading.Thread(target=run, daemon=True,
                              name=f"dl4j-selftest-{op}")
    runner.start()
    return runner, verdict


def prevalidate(op: str) -> None:
    """Start ``op``'s first-use self-test now, if its tier could engage,
    so that it runs while the caller goes on (a layer that will select
    the tier calls this when it is initialised, beside the weights' own
    set-up); the first selection waits for its verdict."""
    with _FIRST_USE_LOCK:
        if op not in _FIRST_USE and op not in _STARTED and available(op):
            _STARTED[op] = _start_self_test(op)


def _validated_on_first_use(op: str) -> bool:
    """Run ``op``'s self-test the first time a trace selects it (or take
    the verdict of the one :func:`prevalidate` started), once a process,
    and say whether the tier is still available after it.  The test runs
    on a thread of its own: the caller is in the middle of a trace.  A
    test that does not finish in time disables the tier as a failing one
    does."""
    with _FIRST_USE_LOCK:
        if op not in _FIRST_USE:
            runner, verdict = _STARTED.pop(op, None) or _start_self_test(op)
            runner.join(_FIRST_USE_TIMEOUT_S)
            _FIRST_USE[op] = verdict == ["ok"]
            tier = _HELPERS[op].tier
            if not _FIRST_USE[op] and tier not in pk._disabled:
                log.warning("Pallas tier '%s' is DISABLED for this process: "
                            "its self-test %s", tier,
                            verdict[0] if verdict else
                            f"did not finish in {_FIRST_USE_TIMEOUT_S} s")
                pk.disable_kernels(f"{op} self-test: {verdict[:1]}",
                                   tier=tier)
    return available(op)


def gmm_wanted(rows: int, dtype, stacks) -> bool:
    """Trace-time decision for ops/row_segments.routed_sum: True runs an
    expert layer's grouped products over segments of ``rows`` sorted rows
    in ``dtype`` through the gmm tier's kernels (``stacks``: the layer's
    [G, a, b] weight stacks, all in one dtype, ``dtype`` or float32
    masters, every one a transpose of the first's shape or the first's
    own); ``lax.ragged_dot`` otherwise.  One decision a layer a trace."""
    G, k, n = stacks[0].shape
    w_dtype = stacks[0].dtype
    ok = (available("gmm")
          and all(w.dtype == w_dtype and sorted(w.shape[1:]) == sorted((k, n))
                  for w in stacks)
          and pk.gmm_supported(rows, k, n, G, dtype, w_dtype)
          and _validated_on_first_use("gmm"))
    record_selection("gmm", ok)
    return ok


# ---------------------------------------------------------------------------
# Warm validation — compile-check every registered helper once, through
# the real dispatch path, BEFORE anything perf-critical traces it cold.
# ---------------------------------------------------------------------------

def _selftest_flash():
    # what the chip's policy runs: bfloat16 operands, a head of 64,
    # causal, forward and all three gradients
    import numpy as np
    rng = np.random.default_rng(0)
    B, H, T, D = 1, 2, 256, 64
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.bfloat16)
    km = jnp.ones((B, T), jnp.float32)

    def loss(q, k, v):
        return pk.flash_attention(q, k, v, km, causal=True).astype(
            jnp.float32).sum()
    vg = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    out, grads = vg(q, k, v)
    jax.block_until_ready(grads)
    if not bool(jnp.isfinite(out)):
        raise FloatingPointError("non-finite flash attention loss")


def _selftest_xent():
    import numpy as np
    rng = np.random.default_rng(0)
    N, V = 256, 512
    logits = jnp.asarray(rng.normal(size=(N, V)), jnp.float32)
    labels = jnp.asarray(np.eye(V, dtype=np.float32)[
        rng.integers(0, V, N)])

    def loss(lg, lab):
        return pk.softmax_xent_rows(lg, lab).mean()
    vg = jax.jit(jax.value_and_grad(loss))
    # both label forms: rows of class weights, and integer class ids
    for lab in (labels, jnp.argmax(labels, axis=1).astype(jnp.int32)):
        out, g = vg(logits, lab)
        jax.block_until_ready(g)
        if not bool(jnp.isfinite(out)):
            raise FloatingPointError("non-finite fused xent loss")


def _selftest_lstm():
    import numpy as np
    rng = np.random.default_rng(0)
    N, H = 4, 16
    zx = jnp.asarray(rng.normal(size=(N, 4 * H)), jnp.float32)
    h = jnp.asarray(rng.normal(size=(N, H)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(N, H)), jnp.float32)
    rw = jnp.asarray(rng.normal(size=(H, 4 * H)) * 0.3, jnp.float32)
    p3 = jnp.asarray(rng.normal(size=(3, H)) * 0.1, jnp.float32)

    def loss(zx, h, c, rw, p3):
        c_new, h_new = pk.fused_lstm_step(zx, h, c, rw, p3)
        return jnp.sum(c_new ** 2) + jnp.sum(h_new ** 2)
    vg = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))
    out, grads = vg(zx, h, c, rw, p3)
    jax.block_until_ready(grads)
    if not bool(jnp.isfinite(out)):
        raise FloatingPointError("non-finite fused lstm loss")


def _selftest_dropout():
    import numpy as np
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    key = jax.random.PRNGKey(7)

    def loss(x):
        return jnp.sum(pk.fused_threshold_dropout(x, 0.8, key) ** 2)
    vg = jax.jit(jax.value_and_grad(loss))
    out, g = vg(x)
    jax.block_until_ready(g)
    if not bool(jnp.isfinite(out)):
        raise FloatingPointError("non-finite fused dropout loss")


def _selftest_gmm():
    # both kernels, a float32 stack under bfloat16 rows, with an empty
    # group, a group that ends inside a row tile and rows behind the last
    # group, against XLA's grouped product: one program, two kernels (a
    # set-up pays each kernel's trace and lowering; the transposed read
    # is the first kernel's with its block index turned)
    import numpy as np
    rng = np.random.default_rng(0)
    m, k, n = 256, 256, 128
    x = rng.normal(size=(m, k)).astype(jnp.bfloat16)
    w = (rng.normal(size=(3, k, n)) * 0.1).astype(np.float32)
    sizes = np.asarray([100, 0, 120], np.int32)

    def run(x, w, sizes):
        y = pk.gmm(x, w, sizes)
        want = jax.lax.ragged_dot(x, w.astype(x.dtype), sizes)
        dw = pk.tgmm(x, y, sizes, out_dtype=jnp.float32)
        held = (jnp.arange(m) < jnp.sum(sizes))[:, None]
        gap = jnp.max(jnp.where(held, jnp.abs(
            y.astype(jnp.float32) - want.astype(jnp.float32)), 0))
        return gap, jnp.max(jnp.abs(want.astype(jnp.float32))), jnp.all(
            jnp.isfinite(dw))
    products = jax.jit(run)
    gap, scale, finite = (float(v) for v in jax.device_get(
        products(x, w, sizes)))
    if not gap <= 0.02 * scale:
        raise FloatingPointError(
            f"dl4j_gmm disagrees with ragged_dot by {gap} of {scale}")
    if not finite:
        raise FloatingPointError("non-finite grouped product")


_HELPERS: Dict[str, Helper] = {
    "lstm_step": Helper("lstm_step", "lstm", "lstm_step", _selftest_lstm),
    "dropout": Helper("dropout", "dropout", "dropout", _selftest_dropout),
    "softmax_xent": Helper("softmax_xent", "xent", "softmax_xent",
                           _selftest_xent),
    "attention": Helper("attention", "flash", "flash_attention",
                        _selftest_flash),
    "gmm": Helper("gmm", "gmm", "gmm", _selftest_gmm, on_first_use=True),
}

OPS = tuple(_HELPERS)


def helper_for(op: str) -> Helper:
    return _HELPERS[op]


def kernel_self_test(disable_on_error: bool = True,
                     ops: Optional[Sequence[str]] = None) -> dict:
    """Compile+run every registered helper once on small shapes through
    the REAL dispatch path (interpret only off-TPU).  On error the
    offending TIER is disabled via pallas_kernels.disable_kernels, so
    library callers keep training on dense XLA, and the switch-off is
    logged once at WARNING with the compiler's message.  Every verdict
    lands in ``dl4j_pallas_selftest_ok{op=}`` (1 passed / 0 failed) plus
    the per-tier ``dl4j_pallas_tier_disabled`` gauge."""
    results: dict = {}
    # snapshot BEFORE any test can flip a kill switch: the mode the
    # tests actually ran under, not the post-disable state
    interp = pk._interpret()
    for op in (ops if ops is not None else OPS):
        results[_HELPERS[op].test_name] = _self_test_one(op, disable_on_error)
    results["interpret_mode"] = interp
    if pk._disabled:
        results["disabled"] = {t: r[:300] for t, r in pk._disabled.items()}
    with _WARM_LOCK:
        _WARM["done"] = True
        _WARM["result"] = results
    return results


def _self_test_one(op: str, disable_on_error: bool) -> str:
    """One helper's self-test: its verdict ("ok" or the error), metered,
    its tier disabled on error if so asked."""
    h = _HELPERS[op]
    try:
        h.self_test()
        verdict, ok = "ok", 1
    except Exception as e:  # Mosaic/XLA compile or runtime failure
        verdict, ok = f"error: {type(e).__name__}: {e}"[:300], 0
        if disable_on_error and h.tier not in pk._disabled:
            log.warning(
                "Pallas tier '%s' failed its self-test and is DISABLED "
                "for this process; '%s' runs on dense XLA. %s: %s",
                h.tier, op, type(e).__name__, e)
            pk.disable_kernels(
                f"{h.test_name} self-test failed: {e}", tier=h.tier)
    try:
        _registry().gauge(
            "dl4j_pallas_selftest_ok",
            "last kernel_self_test verdict per helper (1 ok, 0 failed)",
            labels=("op",)).labels(op=op).set(ok)
        _registry().gauge(
            "dl4j_pallas_tier_disabled",
            "kernel-tier kill switch (1 = disabled)",
            labels=("tier",)).labels(tier=h.tier).set(
                1 if h.tier in pk._disabled else 0)
    except Exception:
        pass  # metering must never break validation
    return verdict


_WARM: dict = {"done": False, "result": None}
_WARM_LOCK = threading.Lock()


def ensure_validated() -> dict:
    """Once-per-process warm validation, called at the top of both
    engines' ``fit()``: when any fused tier could engage (on TPU, or a
    tier force env is set) run :func:`kernel_self_test` over the
    ELIGIBLE helpers so a bad kernel flips its kill switch before the
    first real step compiles (a helper validated ``on_first_use`` runs
    its self-test when a trace first selects it instead).  Off-TPU with
    nothing forced this is a cheap no-op — the fallback paths need no
    validation."""
    if _WARM["done"]:
        return _WARM["result"]
    with _WARM_LOCK:
        if _WARM["done"]:
            return _WARM["result"]
    eligible = [op for op in OPS
                if available(op) and not _HELPERS[op].on_first_use]
    if not eligible:
        with _WARM_LOCK:
            _WARM["done"] = True
            _WARM["result"] = {
                "skipped": "no fused tier eligible (off-TPU, nothing forced)"}
        return _WARM["result"]
    return kernel_self_test(ops=eligible)


def reset_validation() -> None:
    """Forget the cached warm-validation verdict (tests; or after
    flipping tier env switches mid-process)."""
    with _WARM_LOCK:
        _WARM["done"] = False
        _WARM["result"] = None
    with _FIRST_USE_LOCK:
        _FIRST_USE.clear()
        _STARTED.clear()


# ---------------------------------------------------------------------------
# Precision tiers (ISSUE 19) — reduced-precision compute/wire paths
# behind the SAME selection contract as the kernel tiers: conf opts in,
# env kill switches override, a failed parity self-test flips a runtime
# kill, and every decision is metered.  Call sites ask this registry
# (``precision_enabled``) instead of reading conf/env themselves.
# ---------------------------------------------------------------------------

class PrecisionTier(NamedTuple):
    tier: str                       # registry key
    env: str                        # kill-switch env var
    self_test: Callable[[], None]   # bounded-ε parity validation


def _precision_tiers() -> Dict[str, "PrecisionTier"]:
    from deeplearning4j_tpu.ops import quantize as q
    return {
        "bf16_train": PrecisionTier("bf16_train", "DL4J_PRECISION_BF16",
                                    lambda: None),  # ops/dtypes casts; no
        # quantization parity to validate — tests pin the ε-bound
        "int8_infer": PrecisionTier("int8_infer", "DL4J_PRECISION_INT8",
                                    q._selftest_int8_weights),
        "fp8_infer": PrecisionTier("fp8_infer", "DL4J_PRECISION_FP8",
                                   q._selftest_fp8_weights),
        "grad_quant": PrecisionTier("grad_quant", "DL4J_DIST_QUANT",
                                    q._selftest_grad_blocks),
    }


PRECISION_TIERS = ("bf16_train", "int8_infer", "fp8_infer", "grad_quant")


def precision_enabled(tier: str, configured: bool) -> bool:
    """Trace-time tier selection: does ``tier`` engage for a call site
    whose conf asks for ``configured``?  Order mirrors :func:`available`:
    global kill → runtime (self-test) kill → per-tier env (0 forces off,
    1 forces on) → the conf's word.  The decision is metered under
    ``dl4j_precision_selected_total{tier,on}``."""
    from deeplearning4j_tpu.ops import quantize as q
    tiers = _precision_tiers()
    if tier not in tiers:
        raise KeyError(f"unknown precision tier '{tier}' "
                       f"(known: {PRECISION_TIERS})")
    if os.environ.get("DL4J_PRECISION") == "0":  # dl4j: noqa[DL4J103] env kill switch read at trace time by design (fixed per process)
        on = False
    elif q.tier_disabled(tier):
        on = False
    else:
        env = os.environ.get(tiers[tier].env)  # dl4j: noqa[DL4J103] env kill switch read at trace time by design (fixed per process)
        if env is not None and env.lower() in ("0", "off", "false"):
            on = False
        elif env is not None and env.lower() in ("1", "on", "true"):
            on = True
        else:
            on = bool(configured)
    q.record_tier(tier, on)
    return on


_PRECISION_WARM: dict = {}


def ensure_precision_validated(tier: str) -> bool:
    """Once-per-process parity validation for one precision tier,
    called the first time that tier would engage: the tier's bounded-ε
    self-test runs, and a failure flips the runtime kill (the call site
    silently serves the fp32 path) instead of corrupting numerics.
    Returns True when the tier is usable."""
    from deeplearning4j_tpu.ops import quantize as q
    with _WARM_LOCK:
        if tier in _PRECISION_WARM:
            return _PRECISION_WARM[tier]
    info = _precision_tiers()[tier]
    ok = True
    try:
        info.self_test()
    except Exception as e:
        ok = False
        q.disable_tier(tier, f"self-test failed: {type(e).__name__}: {e}")
    try:
        _registry().gauge(
            "dl4j_precision_selftest_ok",
            "last precision-tier self-test verdict (1 ok, 0 failed)",
            labels=("tier",)).labels(tier=tier).set(1 if ok else 0)
    except Exception:
        pass
    with _WARM_LOCK:
        _PRECISION_WARM[tier] = ok
    return ok


def reset_precision_validation() -> None:
    """Tests only: forget cached tier verdicts and runtime kills."""
    from deeplearning4j_tpu.ops import quantize as q
    with _WARM_LOCK:
        _PRECISION_WARM.clear()
    q.reset_disabled()
