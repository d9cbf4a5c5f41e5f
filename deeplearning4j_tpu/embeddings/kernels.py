"""Fused word2vec update kernels — chunked-scan XLA scatter/gather programs.

The reference's inner loop queues one ``AggregateSkipGram``/``AggregateCBOW``
native op per training pair and flushes batches of 4096 into libnd4j,
where they execute sequentially (ref: models/embeddings/learning/impl/
elements/SkipGram.java:224-272, CBOW.java).  The TPU-first equivalent:
the host assembles fixed-shape integer batches (context indices, Huffman
points/codes, negative samples, per-pair learning rates) and ONE jitted
XLA computation per batch runs a ``lax.scan`` over sub-chunks:

    per chunk: gather rows → batched dot (MXU) → sigmoid → weighted
    outer-product gradients → scatter-add into syn0/syn1/syn1neg

Chunking matters for fidelity: a fully-batched scatter-add would apply
every duplicate-row update from one stale snapshot (divergent on
Zipf-heavy rows); the scan re-reads fresh rows every ``CHUNK`` pairs,
approximating the reference's sequential hogwild dynamics while staying
a single compiled program.  Within a chunk, duplicate-row contributions
are averaged (not summed) for stability.  All three weight tables are
donated so XLA updates them in place.

This module is the portable XLA path and the reference semantics.

All gathers use mode="clip": placeholder tables (e.g. the 1-row syn1neg
when negative sampling is off) are indexed by masked-out entries, and the
default out-of-bounds fill is NaN, which survives multiplication by a
zero mask (0·NaN = NaN) and poisons the whole update.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

# Gradient clip matching word2vec's expTable domain [-6, 6]
# (ref: InMemoryLookupTable builds expTable over MAX_EXP=6).
MAX_EXP = 6.0

# Pairs per scan step.  Small enough that duplicate-row staleness is
# negligible even for tiny vocabs, large enough to keep the MXU busy.
# DL4J_W2V_CHUNK overrides for on-chip throughput tuning.
import os as _os
try:
    CHUNK = max(1, int(_os.environ.get("DL4J_W2V_CHUNK", "64")))
except ValueError:
    CHUNK = 64


def _sigmoid_clipped(x):
    # Outside [-MAX_EXP, MAX_EXP] word2vec skips the update (sigmoid
    # saturates); clipping the input gives the same fixed endpoint values.
    return jax.nn.sigmoid(jnp.clip(x, -MAX_EXP, MAX_EXP))


def _inv_row_counts(n_rows, idx, weight):
    """1/count over rows touched in this chunk — duplicate contributions
    are averaged so a row's step never exceeds the sequential magnitude."""
    counts = jnp.zeros((n_rows,), weight.dtype).at[idx].add(
        weight, mode="drop")
    inv = 1.0 / jnp.maximum(counts, 1.0)
    return jnp.take(inv, idx, axis=0, mode="clip")


def _chunked(arr, chunk):
    b = arr.shape[0]
    pad = (-b) % chunk
    if pad:
        # padded tail rows carry zero masks/alpha, so they are no-ops
        arr = jnp.concatenate(
            [arr, jnp.zeros((pad,) + arr.shape[1:], arr.dtype)])
    return arr.reshape(((b + pad) // chunk, chunk) + arr.shape[1:])


def _hs_ns_grads(l1, syn1, syn1neg, points, code_targets, code_mask,
                 neg_idx, neg_label, neg_mask, alpha):
    """Shared HS + NS math: returns (neu1e, syn1', syn1neg')."""
    dt = l1.dtype
    neu1e = jnp.zeros_like(l1)

    l2 = jnp.take(syn1, points, axis=0, mode="clip")                     # (B, C, D)
    f = _sigmoid_clipped(jnp.einsum("bd,bcd->bc", l1, l2))
    g = ((code_targets - f) * code_mask * alpha[:, None]).astype(dt)
    neu1e = neu1e + jnp.einsum("bc,bcd->bd", g, l2)
    inv1 = _inv_row_counts(syn1.shape[0], points, code_mask).astype(dt)
    syn1 = syn1.at[points].add((g * inv1)[..., None] * l1[:, None, :],
                               mode="drop")

    l2n = jnp.take(syn1neg, neg_idx, axis=0, mode="clip")                # (B, K, D)
    fn = _sigmoid_clipped(jnp.einsum("bd,bkd->bk", l1, l2n))
    gn = ((neg_label - fn) * neg_mask * alpha[:, None]).astype(dt)
    neu1e = neu1e + jnp.einsum("bk,bkd->bd", gn, l2n)
    invn = _inv_row_counts(syn1neg.shape[0], neg_idx, neg_mask).astype(dt)
    syn1neg = syn1neg.at[neg_idx].add(
        (gn * invn)[..., None] * l1[:, None, :], mode="drop")
    return neu1e, syn1, syn1neg


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def skipgram_step(syn0, syn1, syn1neg,
                  ctx_idx, points, code_targets, code_mask,
                  neg_idx, neg_label, neg_mask, alpha):
    """One batched skip-gram update.

    syn0:      (V, D) input vectors        — donated
    syn1:      (Vi, D) HS inner-node table — donated (Vi may be 1 if unused)
    syn1neg:   (Vn, D) NS output table     — donated (Vn may be 1 if unused)
    ctx_idx:   (B,)   int32 — row of syn0 being trained (the "lastWord")
    points:    (B, C) int32 — Huffman inner-node rows of the center word
    code_targets: (B, C) f32 — 1-code (what sigmoid should produce)
    code_mask: (B, C) f32 — 1 for valid code positions, 0 padding
    neg_idx:   (B, K) int32 — target + negative sample rows
    neg_label: (B, K) f32 — 1 for the true target column, 0 for negatives
    neg_mask:  (B, K) f32 — validity mask (0 also kills pad pairs)
    alpha:     (B,)   f32 — per-pair learning rate
    """
    chunk = min(CHUNK, ctx_idx.shape[0])

    def body(carry, xs):
        syn0, syn1, syn1neg = carry
        ctx, pts, ct, cm, ni, nl, nm, al = xs
        dt = syn0.dtype
        l1 = jnp.take(syn0, ctx, axis=0, mode="clip")
        valid = (al > 0).astype(jnp.float32)
        neu1e, syn1, syn1neg = _hs_ns_grads(
            l1, syn1, syn1neg, pts, ct, cm, ni, nl, nm, al)
        inv0 = _inv_row_counts(syn0.shape[0], ctx, valid).astype(dt)
        syn0 = syn0.at[ctx].add(neu1e * inv0[:, None], mode="drop")
        return (syn0, syn1, syn1neg), ()

    xs = tuple(_chunked(a, chunk) for a in
               (ctx_idx, points, code_targets, code_mask,
                neg_idx, neg_label, neg_mask, alpha))
    (syn0, syn1, syn1neg), _ = lax.scan(body, (syn0, syn1, syn1neg), xs)
    return syn0, syn1, syn1neg


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def cbow_step(syn0, syn1, syn1neg,
              win_idx, win_mask, points, code_targets, code_mask,
              neg_idx, neg_label, neg_mask, alpha):
    """One batched CBOW update (ref: learning/impl/elements/CBOW.java).

    win_idx:  (B, W) int32 — context-window rows (incl. PV-DM labels)
    win_mask: (B, W) f32 — 1 for real context positions
    Other args as in :func:`skipgram_step`; l1 is the masked mean of the
    window vectors and the gradient is applied to every window row.
    """
    chunk = min(CHUNK, win_idx.shape[0])

    def body(carry, xs):
        syn0, syn1, syn1neg = carry
        win, wm, pts, ct, cm, ni, nl, nm, al = xs
        dt = syn0.dtype
        vecs = jnp.take(syn0, win, axis=0, mode="clip")                  # (b, W, D)
        counts = jnp.maximum(wm.sum(-1, keepdims=True), 1.0).astype(dt)
        l1 = (vecs * wm[..., None].astype(dt)).sum(1) / counts
        neu1e, syn1, syn1neg = _hs_ns_grads(
            l1, syn1, syn1neg, pts, ct, cm, ni, nl, nm, al)
        # Apply neu1e to every context row (word2vec convention:
        # undivided), averaging duplicate rows within the chunk.
        inv0 = _inv_row_counts(syn0.shape[0], win, wm).astype(dt)
        upd = neu1e[:, None, :] * (wm.astype(dt) * inv0)[..., None]
        syn0 = syn0.at[win].add(upd, mode="drop")
        return (syn0, syn1, syn1neg), ()

    xs = tuple(_chunked(a, chunk) for a in
               (win_idx, win_mask, points, code_targets, code_mask,
                neg_idx, neg_label, neg_mask, alpha))
    (syn0, syn1, syn1neg), _ = lax.scan(body, (syn0, syn1, syn1neg), xs)
    return syn0, syn1, syn1neg


@functools.partial(jax.jit, donate_argnums=(0,))
def infer_step(vec, syn1, syn1neg,
               points, code_targets, code_mask,
               neg_idx, neg_label, neg_mask, alpha):
    """PV inference: train ONLY a floating vector against frozen tables
    (ref: SkipGram.iterateSample isInference branch — updates the
    inferenceVector instead of syn0).

    vec: (B, D) — donated; one inference vector per row.
    """
    dt = vec.dtype
    l2 = jnp.take(syn1, points, axis=0, mode="clip")
    f = _sigmoid_clipped(jnp.einsum("bd,bcd->bc", vec, l2))
    g = ((code_targets - f) * code_mask * alpha[:, None]).astype(dt)
    neu1e = jnp.einsum("bc,bcd->bd", g, l2)

    l2n = jnp.take(syn1neg, neg_idx, axis=0, mode="clip")
    fn = _sigmoid_clipped(jnp.einsum("bd,bkd->bk", vec, l2n))
    gn = ((neg_label - fn) * neg_mask * alpha[:, None]).astype(dt)
    neu1e = neu1e + jnp.einsum("bk,bkd->bd", gn, l2n)
    return vec + neu1e
