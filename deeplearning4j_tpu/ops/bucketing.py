"""Shape-bucketing compile cache + retrace telemetry.

Every jitted entry point (the fused train step, ``_output_fn``,
``_score_fn``, the ``k_steps`` scan) specializes on exact input shapes,
so a ragged minibatch stream — variable batch sizes, variable RNN time
lengths — silently retraces and recompiles per shape.  On real data
streams that compile time dominates wall-clock, and the fused
``fit(fused_steps=K)`` scan path degrades to per-step whenever shapes
differ.  "Array Languages Make Neural Networks Fast" (PAPERS.md)
identifies compile-once/run-many shape discipline as the prerequisite
for hardware-limit throughput; this module enforces it:

* **Bucketing** (:func:`bucket_train_dataset` /
  :func:`bucket_train_multidataset` / :func:`bucket_inference_features`):
  pad the batch dimension (and the time dimension of ``[N, T, C]``
  sequences) up to a small set of buckets — powers of two by default,
  user-configured via ``GlobalConf.bucket_batch_sizes`` /
  ``bucket_time_sizes``.  Training batches are padded with CYCLED real
  rows and a rescaled labels mask (the exact pad-and-mask semantics of
  ``parallel/wrapper.py``: valid rows carry ``target/n``, padded rows 0,
  so the step's ``mean(per_ex)`` over the padded batch equals the
  unpadded mean for every mask-linear loss).  Inference batches are
  zero-padded and the outputs un-padded (:func:`unpad_outputs`), so
  results match the unpadded run.

* **Retrace telemetry** (:class:`CompileTelemetry`): each network counts
  distinct jit-entry signatures (shape/dtype/mask-presence — exactly
  what XLA keys its trace cache on) and per-bucket hit counts, surfaced
  through ``nn/listeners.CompileTelemetryListener``, so compile-behavior
  regressions are measurable instead of anecdotal.

* **Persistent compilation cache** (:func:`configure_compile_cache`):
  JAX's on-disk compilation cache at ``$JAX_COMPILATION_CACHE_DIR`` or,
  unset, at the fixed ``<checkout>/.jax_cache``, configured once at
  package import so repeated runs skip cold compiles.
"""

from __future__ import annotations

import math
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

# Losses where the labels mask does not scale the per-example loss
# linearly (ops/losses.py: cosine_proximity normalizes the masked
# vectors) — exact pad-and-mask is impossible there.  Shared with
# ParallelWrapper (this set used to live there).
MASK_NONLINEAR_LOSSES = frozenset({"cosine_proximity"})


# ---------------------------------------------------------------------------
# Bucket ladders
# ---------------------------------------------------------------------------
def next_pow2(n: int) -> int:
    """Smallest power of two >= n."""
    n = int(n)
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def bucket_size(n: int, sizes: Optional[Sequence[int]] = None) -> int:
    """Smallest configured bucket >= n; powers of two when no ladder is
    configured, and past the ladder's top rung (padding down is
    impossible)."""
    if sizes:
        for s in sorted(int(s) for s in sizes):
            if s >= n:
                return s
    return next_pow2(n)


def pow2_ladder(max_n: int) -> List[int]:
    """Power-of-two bucket ladder covering batch sizes ``1..max_n``:
    ``[1, 2, 4, ..., next_pow2(max_n)]`` — the default bucket set when
    no explicit ladder is configured."""
    top = next_pow2(max(1, int(max_n)))
    out, n = [], 1
    while n <= top:
        out.append(n)
        n <<= 1
    return out


def warmup_ladder(sizes: Optional[Sequence[int]] = None,
                  max_batch: int = 32) -> List[int]:
    """The bucket ladder a serving path should pre-compile so first
    requests never eat a cold XLA compile: the configured ladder when
    one exists — truncated at the rung a ``max_batch``-row batch lands
    on (the micro-batcher never builds a bigger batch, so higher rungs
    would be compiled for nothing) — else the power-of-two ladder up to
    ``max_batch``."""
    max_batch = max(1, int(max_batch))
    if sizes:
        ladder = sorted({int(s) for s in sizes})
        top = bucket_size(max_batch, ladder)
        out = [s for s in ladder if s < top]
        out.append(top)
        return out
    return pow2_ladder(max_batch)


def bucket_key(bucket) -> str:
    """Human/JSON key for a bucket tuple: ``b64``, ``b64t32``,
    ``b64t32/16`` (multi-input graphs)."""
    nb, tb = bucket
    if tb is None:
        return f"b{nb}"
    if isinstance(tb, tuple):
        ts = "/".join("-" if t is None else str(t) for t in tb)
        return f"b{nb}t{ts}"
    return f"b{nb}t{tb}"


# ---------------------------------------------------------------------------
# Pad/mask primitives (the parallel/wrapper.py semantics, now shared)
# ---------------------------------------------------------------------------
def cycle_rows(a, target: int):
    """Pad rows up to ``target`` by cycling REAL examples (not zeros:
    replicated real rows keep batch statistics — e.g. BatchNorm —
    well-conditioned; their loss contribution is removed by the mask)."""
    a = np.asarray(a)
    if len(a) >= target:
        return a[:target]
    reps = -(-target // len(a))
    return np.concatenate([a] * reps)[:target]


def scaled_mask(lm, y, n: int, target: int, scale: Optional[float] = None):
    """Labels mask over the PADDED batch making the step's
    ``mean(per_ex)`` over ``target`` rows equal the unpadded mean over
    ``n`` rows: valid rows carry ``target/n`` (losses are linear in the
    mask — see MASK_NONLINEAR_LOSSES), padded rows carry 0.  ``scale``
    overrides the ``target/n`` factor (``1.0`` for per-example scoring,
    where no minibatch mean is taken)."""
    scale = np.float32(target / n if scale is None else scale)
    if lm is None:
        m = np.zeros((target,) + (1,) * (np.asarray(y).ndim - 1),
                     np.float32)
        m[:n] = scale
    else:
        lm = np.asarray(lm, np.float32)
        m = np.zeros((target,) + lm.shape[1:], np.float32)
        m[:n] = lm * scale
    return m


def _pad_time(a: np.ndarray, tb: int) -> np.ndarray:
    """Zero-pad axis 1 (time) up to ``tb``."""
    if a.shape[1] >= tb:
        return a
    pad = [(0, 0)] * a.ndim
    pad[1] = (0, tb - a.shape[1])
    return np.pad(a, pad)


def pad_supported(model, require_mean: bool = True) -> bool:
    """Exact pad-and-mask needs (a) every output loss linear in the
    labels mask (CenterLoss adds an unmasked center term), (b) no
    batch-coupled aux losses (MoE load balancing sees the padded rows)
    and — for paths that reduce to a minibatch mean
    (``require_mean=True``) — (c) mean loss reduction: the ``target/n``
    mask rescale assumes division by the padded row count, so
    ``mini_batch=False`` sum-reduced nets are excluded.  BatchNorm IS
    allowed: cycled real rows keep the batch statistics
    well-conditioned, a documented approximation preferred over
    dropping examples."""
    if require_mean and not model.conf.global_conf.mini_batch:
        return False
    if type(model).__name__ == "ComputationGraph":
        outs = list(model._output_layer_confs().values())
        all_layers = [v.layer_conf() for v in model.conf.vertices.values()
                      if hasattr(v, "layer_conf")]
    else:
        outs = [model.layers[-1]]
        all_layers = model.layers
    for lc in outs:
        if getattr(lc, "requires_features_for_score", False):
            return False
        if (getattr(lc, "loss", None) or "") in MASK_NONLINEAR_LOSSES:
            return False
    for lc in all_layers:
        if "MixtureOfExperts" in type(lc).__name__:
            return False
    return True


# ---------------------------------------------------------------------------
# Training-batch bucketing
# ---------------------------------------------------------------------------
def _resolve_lm_base(lm, fm, y, t):
    """Labels-mask base for the synthesized scaled mask — the
    mask-entry resolution fixed in parallel/wrapper.py: an existing
    labels mask wins; a features mask becomes the base only when its
    shape provably matches the labels' time layout (the step's loss
    resolves the propagated time mask exactly this way); a 3-D label
    with a padded time axis needs an explicit all-ones time base so the
    padded timesteps are excluded.  Returns (base, ok)."""
    y = np.asarray(y)
    if lm is not None:
        return np.asarray(lm), True
    if fm is not None:
        fm_arr = np.asarray(fm)
        if fm_arr.ndim == y.ndim - 1 and fm_arr.shape == y.shape[:-1]:
            return fm_arr, True
        if y.ndim == 2:
            # per-example mask suffices: the step resolves a [N,T] mask
            # against a 2-D preout to None, so no time weighting to match
            return None, True
        return None, False  # mask routing ambiguous: don't guess
    if t is not None and y.ndim == 3:
        return np.ones(y.shape[:-1], np.float32), True
    return None, True


def bucket_train_dataset(ds, g, min_multiple: int = 1,
                         scale_loss: bool = True):
    """Pad a DataSet up to its (batch, time) bucket: rows are cycled
    real examples, the time axis is zero-padded, a features mask is
    synthesized/extended for sequence data and the labels mask is the
    scaled mask making the padded mean loss exactly equal the unpadded
    one.  ``min_multiple`` additionally lifts the batch bucket to a
    multiple (ParallelWrapper's data degree).  ``scale_loss=False``
    keeps valid-row mask entries at their original values (per-example
    scoring, where results are sliced back instead of averaged).

    Returns ``(padded_ds, bucket)``; ``bucket is None`` means the batch
    could not be bucketed (ambiguous mask routing) and ``ds`` is
    returned unchanged.  Idempotent: re-bucketing a bucket-shaped batch
    is a no-op fast path (the AsyncDataSetIterator pre-buckets before
    device_put; the engine must not pull the arrays back to host)."""
    from deeplearning4j_tpu.datasets.dataset import DataSet

    f, y = ds.features, ds.labels
    n = int(f.shape[0])
    nb = bucket_size(n, g.bucket_batch_sizes)
    if min_multiple > 1:
        nb = -(-nb // min_multiple) * min_multiple
    t = int(f.shape[1]) if f.ndim == 3 else None
    tb = bucket_size(t, g.bucket_time_sizes) if t is not None else None
    fm, lm = ds.features_mask, ds.labels_mask
    if nb == n and (tb is None or tb == t) and lm is not None \
            and (t is None or fm is not None):
        return ds, (nb, tb)  # already bucket-shaped (e.g. pre-bucketed)

    y = np.asarray(y)
    lm_base, ok = _resolve_lm_base(lm, fm, y, t)
    if not ok:
        return ds, None

    f_p = cycle_rows(f, nb)
    if tb is not None and tb != t:
        f_p = _pad_time(f_p, tb)
    y_p = cycle_rows(y, nb)
    if y.ndim == 3 and tb is not None and y.shape[1] == t and tb != t:
        y_p = _pad_time(y_p, tb)

    if t is not None:
        # sequence features always carry a mask once bucketed — mask
        # PRESENCE is part of the jit signature, and a batch landing
        # exactly on a bucket must not trace separately from a padded one
        fm_arr = (np.asarray(fm, np.float32) if fm is not None
                  else np.ones((n, t), np.float32))
        fm_p = cycle_rows(fm_arr, nb)
        if tb != t:
            fm_p = _pad_time(fm_p, tb)
    else:
        fm_p = None if fm is None else cycle_rows(fm, nb)

    scale = None if scale_loss else 1.0
    if lm_base is None:
        lm_p = scaled_mask(None, y, n, nb, scale)
    else:
        base = np.zeros((nb,) + tuple(
            tb if (i == 1 and t is not None and s == t and tb != t) else s
            for i, s in enumerate(lm_base.shape))[1:], np.float32)
        sl = (slice(0, n),) + tuple(slice(0, s) for s in lm_base.shape[1:])
        base[sl] = lm_base * np.float32(nb / n if scale is None else scale)
        lm_p = base
    return DataSet(f_p, y_p, fm_p, lm_p), (nb, tb)


def bucket_train_multidataset(mds, g, min_multiple: int = 1,
                              scale_loss: bool = True):
    """MultiDataSet (ComputationGraph) analog of
    :func:`bucket_train_dataset`.  Per-ENTRY mask semantics (the
    wrapper's fix: a missing mask arrives as ``[None]``, so container-
    level checks are not enough): a features mask without any labels
    mask makes multi-input→output routing ambiguous — refuse rather
    than guess.  Every 3-D entry gets its own time bucket."""
    from deeplearning4j_tpu.datasets.dataset import MultiDataSet

    def _all_none(tup):
        return tup is None or all(m is None for m in tup)

    fms = mds.features_masks
    lms = mds.labels_masks
    if not _all_none(fms) and _all_none(lms):
        return mds, None
    n = mds.num_examples()
    nb = bucket_size(n, g.bucket_batch_sizes)
    if min_multiple > 1:
        nb = -(-nb // min_multiple) * min_multiple

    def t_of(a):
        shape = getattr(a, "shape", None)
        if shape is None:
            shape = np.asarray(a).shape
        return int(shape[1]) if len(shape) == 3 else None

    f_ts = [t_of(f) for f in mds.features]
    f_tbs = [None if t is None else bucket_size(t, g.bucket_time_sizes)
             for t in f_ts]
    bucket = (nb, tuple(f_tbs))

    fm_list = list(fms) if fms is not None else [None] * len(mds.features)
    lm_list = list(lms) if lms is not None else [None] * len(mds.labels)

    # Idempotence fast path (mirrors bucket_train_dataset): a batch that
    # is already bucket-shaped with all masks in place passes through
    # untouched — the async pipeline pre-buckets on a worker BEFORE
    # device_put, and the engine's re-bucket must not pull the staged
    # arrays back to host.
    if nb == n and all(tb is None or tb == t
                       for t, tb in zip(f_ts, f_tbs)) \
            and all(m is not None for m in lm_list) \
            and all(t is None or m is not None
                    for t, m in zip(f_ts, fm_list)) \
            and all(t_of(y) is None
                    or bucket_size(t_of(y), g.bucket_time_sizes) == t_of(y)
                    for y in mds.labels):
        return mds, bucket

    def pad_entry(a, tb):
        a_p = cycle_rows(a, nb)
        if tb is not None and tb != a_p.shape[1]:
            a_p = _pad_time(a_p, tb)
        return a_p

    feats, new_fms = [], []
    for f, fm, t, tb in zip(mds.features, fm_list, f_ts, f_tbs):
        feats.append(pad_entry(np.asarray(f), tb))
        if t is not None:
            fm_arr = (np.asarray(fm, np.float32) if fm is not None
                      else np.ones((n, t), np.float32))
            fm_p = cycle_rows(fm_arr, nb)
            if tb != t:
                fm_p = _pad_time(fm_p, tb)
            new_fms.append(fm_p)
        else:
            new_fms.append(None if fm is None else cycle_rows(fm, nb))

    labels, new_lms = [], []
    for y, lm in zip(mds.labels, lm_list):
        y = np.asarray(y)
        t = t_of(y)
        tb = bucket_size(t, g.bucket_time_sizes) if t is not None else None
        y_p = pad_entry(y, tb)
        lm_base = (np.asarray(lm) if lm is not None
                   else (np.ones(y.shape[:-1], np.float32)
                         if y.ndim == 3 else None))
        scale = np.float32(nb / n if scale_loss else 1.0)
        if lm_base is None:
            m = np.zeros((nb,) + (1,) * (y.ndim - 1), np.float32)
            m[:n] = scale
        else:
            tgt = [nb] + list(lm_base.shape[1:])
            if t is not None and lm_base.ndim >= 2 \
                    and lm_base.shape[1] == t and tb != t:
                tgt[1] = tb
            m = np.zeros(tuple(tgt), np.float32)
            sl = (slice(0, n),) + tuple(slice(0, s)
                                        for s in lm_base.shape[1:])
            m[sl] = lm_base * scale
        labels.append(y_p)
        new_lms.append(m)

    return MultiDataSet(feats, labels, tuple(new_fms), tuple(new_lms)), bucket


# ---------------------------------------------------------------------------
# Inference bucketing
# ---------------------------------------------------------------------------
def bucket_inference_features(x, mask, g):
    """Zero-pad a feature batch (rows are independent at inference — no
    batch statistics are computed — so zeros are exact) up to its
    bucket, synthesizing/extending the time mask for sequences so
    recurrent state carries through padded timesteps unchanged (exact
    for bidirectional RNNs too: lstm_scan's masked steps are identity
    carries).  Returns ``(x_p, mask_p, n, t, bucket)``."""
    x = np.asarray(x)
    n = int(x.shape[0])
    nb = bucket_size(n, g.bucket_batch_sizes)
    t = int(x.shape[1]) if x.ndim == 3 else None
    tb = bucket_size(t, g.bucket_time_sizes) if t is not None else None

    x_p = x
    if nb != n:
        pad = [(0, nb - n)] + [(0, 0)] * (x.ndim - 1)
        x_p = np.pad(x_p, pad)
    if tb is not None and tb != t:
        x_p = _pad_time(x_p, tb)

    if t is not None:
        m = (np.asarray(mask, np.float32) if mask is not None
             else np.ones((n, t), np.float32))
        m_p = np.zeros((nb, tb) + m.shape[2:], np.float32)
        m_p[:n, :t] = m
    elif mask is not None:
        m = np.asarray(mask, np.float32)
        m_p = np.zeros((nb,) + m.shape[1:], np.float32)
        m_p[:n] = m
    else:
        m_p = None
    return x_p, m_p, n, t, (nb, tb)


def unpad_outputs(out, n: int, t: Optional[int], tb: Optional[int]):
    """Slice a padded output back to the real batch (and time) extent."""
    out = out[:n]
    if t is not None and tb is not None and t != tb and out.ndim >= 3 \
            and out.shape[1] == tb:
        out = out[:, :t]
    return out


# ---------------------------------------------------------------------------
# Retrace telemetry
# ---------------------------------------------------------------------------
def signature_of(tree) -> Tuple:
    """Hashable (structure, shapes, dtypes) signature of a pytree of
    arrays — the same information jax.jit keys its trace cache on, so a
    NEW signature on a given entry point is (up to jit-cache eviction)
    an XLA retrace."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (str(treedef),
            tuple((tuple(getattr(l, "shape", ())),
                   str(getattr(l, "dtype", type(l).__name__)))
                  for l in leaves))


class CompileTelemetry:
    """Retrace counter + per-bucket hit counts for one network.

    ``record(kind, args, bucket=)`` is called by every jitted entry
    point (train_step, fused_step_k*, output, score, score_examples)
    with the arrays about to cross into jit; a signature not seen on
    that entry point counts as a retrace.  ``invalidate()`` mirrors the
    engines' trace-token invalidation (the jitted callables are dropped,
    so the same shapes genuinely recompile)."""

    def __init__(self):
        self.retraces = 0
        self.calls = 0
        self.bucket_hits: Dict[str, int] = {}
        self.trace_log: List[Tuple[str, Tuple]] = []
        self._seen: Dict[str, set] = {}

    def record(self, kind: str, args, bucket=None) -> bool:
        """Returns True when this (kind, signature) is new — a retrace."""
        sig = signature_of(args)
        self.calls += 1
        seen = self._seen.setdefault(kind, set())
        new = sig not in seen
        if new:
            seen.add(sig)
            self.retraces += 1
            self.trace_log.append((kind, sig))
        if bucket is not None:
            key = f"{kind}:{bucket_key(bucket)}"
            self.bucket_hits[key] = self.bucket_hits.get(key, 0) + 1
        # mirror into the process-wide registry (monitor/) so retraces
        # show up in the same scrape as latencies and memory; aggregated
        # across networks — per-instance detail stays on this object
        from deeplearning4j_tpu.monitor import get_registry
        reg = get_registry()
        reg.counter("dl4j_compile_calls_total", "jit-entry calls",
                    labels=("kind",)).labels(kind=kind).inc()
        if new:
            reg.counter("dl4j_compile_retraces_total",
                        "new jit-entry signatures (XLA retraces)",
                        labels=("kind",)).labels(kind=kind).inc()
            # journal the retrace with the trace context (fit_id /
            # request_id): a jit_call-dominated step can be attributed
            # to the exact request/fit that paid the compile
            from deeplearning4j_tpu.monitor import events
            events.emit("compile.retrace", kind=kind,
                        retraces=self.retraces)
        if bucket is not None:
            reg.counter("dl4j_bucket_hits_total",
                        "bucketed batches dispatched",
                        labels=("kind", "bucket")).labels(
                kind=kind, bucket=bucket_key(bucket)).inc()
        return new

    def invalidate(self) -> None:
        """Ambient trace state changed (precision policy, sequence mesh):
        the engines drop their jitted fns, so seen signatures WILL
        recompile — forget them (cumulative counters keep counting)."""
        self._seen.clear()

    def reset(self) -> None:
        self.__init__()

    def snapshot(self) -> Dict[str, Any]:
        return {
            "retraces": self.retraces,
            "calls": self.calls,
            "by_kind": {k: len(v) for k, v in self._seen.items()},
            "bucket_hits": dict(self.bucket_hits),
        }


# ---------------------------------------------------------------------------
# Persistent compilation cache
# ---------------------------------------------------------------------------
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> None:
    """Place JAX's on-disk compilation cache; the package calls this
    once at import, before anything compiles (JAX latches the cache off
    at the first compile that finds no directory).  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing
    is set here; otherwise the cache goes to the one fixed path
    ``<checkout>/.jax_cache`` — the path is part of the cache key, so it
    must never carry a pid, a time or a temporary name.  JAX's own
    thresholds decide which programs are worth an entry."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE_DIR)
