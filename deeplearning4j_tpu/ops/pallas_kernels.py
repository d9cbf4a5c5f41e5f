"""Pallas TPU kernels for the hot ops where HLO fusion isn't enough
(SURVEY.md §7: the native-kernel tier; the reference's analog is the
fused libnd4j Aggregate ops + cuDNN helpers, §2.3/§2.10).

This module holds the KERNELS and their shape/dtype support predicates;
per-layer selection between a kernel and its dense XLA fallback lives in
``ops/helpers.py`` (the cuDNN-helper-selection tier: registry, per-tier
kill switches, warm validation, ``dl4j_pallas_*`` selection metrics).

Five tiers:

* **flash_attention** — block-wise online-softmax attention.  The dense
  XLA path materializes the [B, H, T, T] score matrix in HBM; this
  kernel streams K/V blocks through VMEM with running max/denominator
  accumulation, so memory is O(T·D) and the MXU sees back-to-back
  (BQ×D)·(D×BK) tiles.  Used by parallel/sequence.dense_attention (and
  therefore the per-shard core of Ulysses sequence parallelism; the
  ring path keeps its own block-streaming body) on TPU.  Backward is
  blockwise too (FlashAttention-2 recomputation from the saved per-row
  logsumexp): one kernel rebuilds each [BQ, BK] probability tile on
  the fly, once, and takes dq, dk and dv from it, so TRAINING memory
  is O(T·D) as well — no dense [T, T] rematerialization.  Products
  run in the operands' dtype (bfloat16 on the chip) with float32
  accumulation; head dims that aren't multiples of the 128-lane width
  are zero-padded outside the custom_vjp.

* **fused_softmax_xent** — softmax + cross-entropy + gradient in one
  VMEM pass per row block.  The char-RNN/output-layer hot op: avoids
  writing the [N, V] probability matrix to HBM twice (once for loss,
  once for grad).

* **fused_lstm_step** — one peephole-LSTM timestep (the scan body of
  ``ops/recurrent.lstm_scan``) in one VMEM pass: the [N, H]·[H, 4H]
  recurrent matmul plus ALL the elementwise gate math (2 peephole
  muls, 3 sigmoids, 2 tanhs, the cell/hidden updates) that XLA:TPU
  otherwise schedules as separate HLO ops per timestep.  Backward
  recomputes through the XLA reference cell.

* **fused_threshold_dropout** — inverted dropout whose mask is a
  counter-hash THRESHOLD test computed inside the kernel (the
  libnd4j-style threshold dropout): no [N, ...] mask tensor is ever
  materialized in HBM, and the backward pass re-derives the same mask
  from the seed instead of saving it.

* **gmm** — the expert layer's grouped matrix products (``dl4j_gmm``
  for rows by a stack of matrices, either way round, ``dl4j_tgmm`` for
  the stack's gradient), over rows sorted by group, with the live tiles
  planned on the device from the group sizes; each entry point is a
  module-level ``jax.jit``, so a program traces and lowers each kernel
  signature once however many call sites share it.

All run under ``interpret=True`` off-TPU so the same code is testable
on the CPU mesh (the reference's cuDNN-vs-builtin cross-check pattern,
SURVEY.md §4)."""

from __future__ import annotations

import contextlib
import functools
from typing import NamedTuple, Optional

import jax
import jax.extend as jex
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.interpreters import mlir

from deeplearning4j_tpu.ops import mask_rules, recompute

NEG_INF = -1e30


# Runtime kill switches, PER KERNEL TIER: set by kernel_self_test() when
# a Mosaic compile fails on the real chip, so one bad kernel degrades to
# the dense XLA path without disabling the other, healthy ones (the
# cuDNN-helper-with-builtin-fallback pattern, ref
# ConvolutionLayer.java:157-212).  DL4J_PALLAS=0 disables everything;
# per-tier state is read by ops/helpers.available().
ALL_TIERS = ("flash", "xent", "lstm", "dropout", "gmm")
_disabled: dict = {}  # tier -> reason


def disable_kernels(reason: str, tier: Optional[str] = None) -> None:
    tiers = (tier,) if tier else ALL_TIERS
    for t in tiers:
        _disabled[t] = reason
    try:  # mirror the kill-switch state into the monitor registry
        from deeplearning4j_tpu import monitor
        g = monitor.get_registry().gauge(
            "dl4j_pallas_tier_disabled",
            "kernel-tier kill switch (1 = disabled)", labels=("tier",))
        for t in tiers:
            g.labels(tier=t).set(1)
    except Exception:
        pass  # metering must never break kernel dispatch


# Trace-time regime flag (the kv_decode_scope idiom): True while a step
# that GSPMD partitions over a mesh is being traced (parallel/fsdp.py).
_PARTITIONED = False


@contextlib.contextmanager
def partitioned_trace():
    """Scope under which the fused tiers leave AUTOMATIC selection on
    the chip — all five, by name: flash, xent, lstm, dropout, gmm.  A
    Mosaic kernel cannot be partitioned automatically (the TPU lowering
    raises "Mosaic kernels cannot be automatically partitioned. Please
    wrap the call in a shard_map"), so a step jitted with mesh shardings
    traces the dense XLA ops, which GSPMD can partition.  The decision
    is metered like any other fallback (``dl4j_pallas_fallback_total``).
    An explicit ``DL4J_PALLAS_<TIER>=1`` still forces the kernel."""
    global _PARTITIONED  # dl4j: noqa[DL4J103] trace-time regime flag like kv_decode_scope: flipped once around a trace, never per step
    prev = _PARTITIONED
    _PARTITIONED = True
    try:
        yield
    finally:
        _PARTITIONED = prev


def partitioned_trace_active() -> bool:
    return _PARTITIONED


def _on_tpu() -> bool:
    # the device probe (and its DL4J_TPU test hook) lives in ops/platform.py
    import os
    if os.environ.get("DL4J_PALLAS") == "0":  # dl4j: noqa[DL4J103] env flag read at trace time by design (fixed per process)
        return False
    from deeplearning4j_tpu.ops import platform
    return platform.is_tpu()


def flash_available() -> bool:
    """Dispatch gate for callers of flash_attention (parallel/sequence)."""
    return "flash" not in _disabled and _on_tpu()


def xent_available() -> bool:
    """Dispatch gate for callers of softmax_xent_rows (ops/losses)."""
    return "xent" not in _disabled and _on_tpu()


def _interpret() -> bool:
    return not _on_tpu()


# ===========================================================================
# Flash attention — forward AND blockwise backward (O(T) HBM both ways).
#
# Forward saves per-row logsumexp; backward recomputes attention weights
# block-by-block from (q, k, lse) — the FlashAttention-2 recomputation
# scheme — so training never materializes the [T, T] score matrix.
#
# The kernels take the operands as the layer has them.  DTYPE: every
# product multiplies q, k, v, dO tiles in their own dtype (bfloat16
# under the chip's policy: one MXU pass; float32 in, float32 products)
# and accumulates in float32; the scores, the softmax, its statistics
# and the dq/dk/dv accumulators are float32 until the final store, and
# p and ds are cast to the dtype of the operand they meet.  HEAD WIDTH:
# D is the blocks' last dimension whatever it is; flash_attention says
# why it still pads it to 128 lanes.  TILES: square, see _flash_block.
# MASK: a rule fixed at trace time (ops/mask_rules.py: causal, block
# diffusion; None lets every pair live) says which key tiles a q block
# visits and which of those need a comparison inside the tile; the two
# kernels run its steps as they stand (_run_visits) and touch no other
# tile.  Under the causal rule that is every tile before the diagonal,
# then the one on it with the comparison.
# ===========================================================================

LANE = 128
# contract the last dimension of both operands: A · Bᵀ
_NT = (((1,), (1,)), ((), ()))
# the per-row logsumexp of a row with no live key: large and POSITIVE,
# so that the backward's exp(s − lse) is an exact 0 for the whole row
# with no test per element (T-pad and fully-masked query rows)
_LSE_DEAD = 1e30
# q, k, v, dO whole-sequence blocks are double-buffered in VMEM (1 MB an
# array at T 4,096 x D 64 bfloat16, 4 MB at T 8,192 x D 128 float32) next
# to a few [block_q, block_k] float32 temporaries; v5e has 128 MiB
_FLASH_VMEM_LIMIT = 64 << 20
_FLASH_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"),
    vmem_limit_bytes=_FLASH_VMEM_LIMIT)
# the backward's key tiles run in order: dq gathers over them in a
# float32 [T, D] of VMEM (4 MB at T 8,192 x D 128) beside the head's dq
# block and the whole-sequence q and dO
_FLASH_BWD_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=_FLASH_VMEM_LIMIT)

# The square tile's cap: what one v5e measured best, forward and
# forward + backward, at B 2, H 32 over 8, T 4,096, D 64, bfloat16,
# causal, among ten tilings from 128 x 128 to 2,048 x 512, and no loss
# against 128 x 128 at T 384 to 2,048 (PERF.md 6, PR 32).
_FLASH_BLOCK_CAP = 512


def _flash_block(T: int, rule=None) -> int:
    """Rows of q a program holds and keys a tile holds, for a sequence of
    T (a multiple of 128, as flash_attention pads it): the largest
    multiple of 128 that divides T and is at most the cap, so no
    sequence computes rows beyond its own padding; under a rule whose
    tiles must not straddle a boundary of its own (block diffusion's
    halves), the largest that the rule accepts too."""
    return mask_rules.tile_for(rule, T, _FLASH_BLOCK_CAP)


def _tile_positions(block: int, transposed: bool = False):
    """(query positions, key positions) of a square tile, counted from
    its corner: queries along the rows [block, 1] and keys along the
    columns [1, block], or the other way round if ``transposed``."""
    rows = lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    cols = lax.broadcasted_iota(jnp.int32, (1, block), 1)
    return (cols, rows) if transposed else (rows, cols)


def _run_visits(steps, tile, carry):
    """Run a rule's steps (``mask_rules``) in their order: ``tile(s,
    carry)`` over each range of whole tiles, ``tile(s, carry,
    live_in_tile=...)`` on each boundary tile, once or as often (0 or 1)
    as the device reads."""
    for step in steps:
        if step[0] == "range":
            carry = lax.fori_loop(step[1], step[2], tile, carry)
            continue
        _, index, live_in_tile, trips = step
        if trips is None:
            carry = tile(index, carry, live_in_tile=live_in_tile)
        else:
            carry = lax.fori_loop(
                0, trips, lambda _, c, index=index, live_in_tile=live_in_tile:
                tile(index, c, live_in_tile=live_in_tile), carry)
    return carry


def _flash_fwd_kernel(q_ref, k_ref, v_ref, mask_ref, out_ref, lse_ref, *,
                      rule, scale: float):
    """One (batch*head, q-block) program: stream K/V tiles with online
    softmax.  Block shapes: q [BQ, D], k/v [T, D], mask [1, T]; outputs
    out [BQ, D] and the per-row logsumexp lse [1, BQ], a lane-dense ROW
    (a [BQ, 1] column would spend a 128-lane tile on every number in
    HBM)."""
    q = q_ref[...]                                        # [BQ, D]
    BQ, D = q.shape
    qi = pl.program_id(1)

    def tile(s, carry, *, live_in_tile=None):
        m, l, acc = carry
        start = pl.multiple_of(s * BQ, BQ)
        k_blk = k_ref[pl.ds(start, BQ), :]
        v_blk = v_ref[pl.ds(start, BQ), :]
        live = mask_ref[:, pl.ds(start, BQ)] > 0          # [1, BK]
        if live_in_tile is not None:
            live = jnp.logical_and(live,
                                   live_in_tile(*_tile_positions(BQ)))
        scores = jax.lax.dot_general(
            q, k_blk, _NT, preferred_element_type=jnp.float32) * scale
        scores = jnp.where(live, scores, NEG_INF)         # [BQ, BK]
        m_new = jnp.maximum(m, scores.max(axis=1, keepdims=True))
        alpha = jnp.exp(jnp.maximum(m - m_new, NEG_INF * 0.5))
        p = jnp.exp(scores - m_new)
        l_new = l * alpha + p.sum(axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    carry = (jnp.full((BQ, 1), NEG_INF, jnp.float32),
             jnp.zeros((BQ, 1), jnp.float32),
             jnp.zeros((BQ, D), jnp.float32))
    n_tiles = k_ref.shape[0] // BQ
    if rule is None:
        carry = lax.fori_loop(0, n_tiles, tile, carry)
    else:
        carry = _run_visits(rule.q_visits(qi, n_tiles, BQ), tile, carry)
    m, l, acc = carry
    out_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(out_ref.dtype)
    # lse for backward recomputation; a row with no live key (fully
    # masked, or a T-pad row) is marked dead: the backward drops it
    lse = jnp.where(m > NEG_INF * 0.5,
                    m + jnp.log(jnp.maximum(l, 1e-30)), _LSE_DEAD)
    lse_ref[...] = lse.reshape(1, BQ)


def _flash_fwd(q, k, v, key_mask, *, rule, scale: float):
    B, H, T, D = q.shape
    rule = mask_rules.resolve(rule)     # a caller's plain True is CAUSAL
    block = _flash_block(T, rule)
    qf = q.reshape(B * H, T, D)
    kf = k.reshape(B * H, T, D)
    vf = v.reshape(B * H, T, D)
    mask = key_mask.astype(jnp.float32).reshape(B, 1, T)
    whole = pl.BlockSpec((None, T, D), lambda b, i: (b, 0, 0))
    block_rows = pl.BlockSpec((None, block, D), lambda b, i: (b, i, 0))

    out, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, rule=rule, scale=scale),
        grid=(B * H, T // block),
        in_specs=[
            block_rows,                                             # q
            whole,                                                  # k
            whole,                                                  # v
            pl.BlockSpec((None, 1, T), lambda b, i: (b // H, 0, 0)),  # mask
        ],
        out_specs=[
            block_rows,
            pl.BlockSpec((None, 1, block), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, T), jnp.float32),
        ],
        name="dl4j_flash_fwd",
        compiler_params=_FLASH_COMPILER_PARAMS,
        interpret=_interpret(),
    )(qf, kf, vf, mask)
    return out.reshape(B, H, T, D), lse


def _flash_bwd_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                      delta_ref, dq_ref, dk_ref, dv_ref, dq_acc, *,
                      rule, scale: float):
    """dQ, dK and dV from one build of each tile.  One (batch*head, k
    block) program streams the Q/dO tiles that see this key tile and
    recomputes the TRANSPOSED tile pᵀ [BK, BQ], so that the per-query
    statistics are [1, BQ] rows and dk's and dv's products plain ones:
    dv += pᵀ·dO, dk += dsᵀ·Q · scale with dsᵀ = pᵀ ∘ (V·dOᵀ − δ), and
    dq[q tile] += ds·K · scale from the transpose of the dsᵀ already
    cast for dk's product: five products and one exp a tile.  dq gathers
    in ``dq_acc``, a float32 [T, D] that stays in VMEM over the head's
    key tiles (the grid's second dimension runs in order): zeroed at the
    first, written to the head's dq block at the last, so a q tile takes
    its key tiles' terms in rising key order."""
    k_blk = k_ref[...]                                    # [BK, D]
    v_blk = v_ref[...]                                    # [BK, D]
    key_live = mask_ref[...] > 0                          # [BK, 1]
    BK, D = k_blk.shape
    ki = pl.program_id(1)
    n_blocks = q_ref.shape[0] // BK

    @pl.when(ki == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def tile(s, carry, *, live_in_tile=None):
        dk, dv = carry
        rows = pl.ds(pl.multiple_of(s * BK, BK), BK)
        q_blk = q_ref[rows, :]
        do_blk = do_ref[rows, :]
        lse_row = lse_ref[:, rows]                        # [1, BQ]
        delta_row = delta_ref[:, rows]                    # [1, BQ]
        live = key_live
        if live_in_tile is not None:
            live = jnp.logical_and(
                live, live_in_tile(*_tile_positions(BK, transposed=True)))
        st = jax.lax.dot_general(
            k_blk, q_blk, _NT, preferred_element_type=jnp.float32) * scale
        # the EXPONENT is clamped, not the result: a dead tile gives an
        # exact 0, and a dead row's lse (_LSE_DEAD) does by itself
        pt = jnp.exp(jnp.where(live, st - lse_row, NEG_INF))  # [BK, BQ]
        dv = dv + jax.lax.dot_general(
            pt.astype(do_blk.dtype), do_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [BK, D]
        dpt = jax.lax.dot_general(v_blk, do_blk, _NT,
                                  preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta_row)).astype(q_blk.dtype)
        dk = dk + jax.lax.dot_general(
            dst, q_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [BK, D]
        dq_acc[rows, :] += jax.lax.dot_general(
            dst.T, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [BQ, D]
        return dk, dv

    carry = (jnp.zeros((BK, D), jnp.float32), jnp.zeros((BK, D), jnp.float32))
    if rule is None:
        carry = lax.fori_loop(0, n_blocks, tile, carry)
    else:
        carry = _run_visits(rule.k_visits(ki, n_blocks, BK), tile, carry)
    dk, dv = carry
    dk_ref[...] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)

    @pl.when(ki == n_blocks - 1)
    def _():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _flash_bwd(q, k, v, key_mask, out, lse, g, *, rule, scale: float):
    B, H, T, D = q.shape
    rule = mask_rules.resolve(rule)
    block = _flash_block(T, rule)
    qf = q.reshape(B * H, T, D)
    kf = k.reshape(B * H, T, D)
    vf = v.reshape(B * H, T, D)
    dof = g.reshape(B * H, T, D)
    mask = key_mask.astype(jnp.float32).reshape(B, T, 1)
    # δ_i = Σ_d dO·O — a cheap elementwise reduction XLA fuses on its own
    delta = jnp.sum(dof.astype(jnp.float32) *
                    out.reshape(B * H, T, D).astype(jnp.float32),
                    axis=-1)[:, None, :]                  # [BH, 1, T]

    whole = pl.BlockSpec((None, T, D), lambda b, i: (b, 0, 0))
    whole_row = pl.BlockSpec((None, 1, T), lambda b, i: (b, 0, 0))
    block_rows = pl.BlockSpec((None, block, D), lambda b, i: (b, i, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, rule=rule, scale=scale),
        grid=(B * H, T // block),
        in_specs=[
            whole,                                                  # q
            block_rows,                                             # k
            block_rows,                                             # v
            pl.BlockSpec((None, block, 1),
                         lambda b, i: (b // H, i, 0)),              # mask
            whole,                                                  # do
            whole_row,                                              # lse
            whole_row,                                              # delta
        ],
        # dq's block is the head's whole [T, D]: it leaves VMEM once, when
        # the head's last key tile has written it
        out_specs=[whole, block_rows, block_rows],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, T, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, T, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((T, D), jnp.float32)],
        name="dl4j_flash_bwd",
        compiler_params=_FLASH_BWD_COMPILER_PARAMS,
        interpret=_interpret(),
    )(qf, kf, vf, mask, dof, lse, delta)
    return (dq.reshape(B, H, T, D), dk.reshape(B, H, T, D),
            dv.reshape(B, H, T, D))


def _dense_reference(q, k, v, key_mask, causal, scale):
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    rule = mask_rules.resolve(causal)
    if rule is not None:
        T = q.shape[2]
        qi = jnp.arange(T)[:, None]
        ki = jnp.arange(T)[None, :]
        scores = jnp.where(rule.live(qi, ki), scores, NEG_INF)
    scores = jnp.where(key_mask[:, None, None, :] > 0, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_core(q, k, v, key_mask, rule, scale: float):
    out, _ = _flash_fwd(q, k, v, key_mask, rule=rule, scale=scale)
    return out


def _flash_vjp_fwd(q, k, v, key_mask, rule, scale):
    out, lse = _flash_fwd(q, k, v, key_mask, rule=rule, scale=scale)
    # offered here, inside the rule: the backward reads these residuals,
    # and a name on the layer's output would mark another variable
    out, lse = recompute.offer(out), recompute.offer(lse)
    return out, (q, k, v, key_mask, out, lse)


def _flash_vjp_bwd(rule, scale, res, g):
    q, k, v, key_mask, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, key_mask, out, lse, g,
                            rule=rule, scale=scale)
    return dq, dk, dv, None


_flash_core.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, key_mask, causal=False,
                    scale: Optional[float] = None):
    """Memory-efficient exact attention, differentiable with O(T) HBM in
    both directions.  q,k,v: [B,H,T,D]; key_mask [B,T] (1=keep);
    ``causal``: False, True or any rule ``mask_rules.resolve`` knows.
    Products run in the operands' dtype with float32 accumulation
    (bfloat16 in: one MXU pass; float32 in: float32 products), softmax
    in float32.  scale defaults to 1/sqrt(D) of the ORIGINAL head dim
    and multiplies the float32 scores.  Head dims that are not a
    multiple of 128 (64, 96, ...) are zero-padded to the next one: the
    kernels take any width as it is (the chip's compiler took, and the
    chip computed, every D tried from 33 to 256), and alone they are
    faster so, but in the language-model cell the step was 0.5 to 0.8%
    faster with the pad, twice (PERF.md 6, PR 32), so it stays.
    Sequence lengths that are not a multiple of 128 (ragged/bucketed
    ladders) are zero-padded along T with a ZEROED key mask — masked
    keys change no real row, and pad query rows' cotangents are zero —
    and the tile follows the padded T (``_flash_block``).  Both
    pad/slice pairs sit outside the custom_vjp so gradients pass
    through.  A rule that fixes the row count (block diffusion's 2L)
    takes no padded rows: a length it cannot tile is refused, with the
    reason.

    The core's output and its row statistics (logsumexp) are offered to
    a recomputed run (``ops/recompute.py``), inside the forward rule
    where they become the backward kernel's residuals: a run that keeps
    them (``[B, H, T, D]`` in the operands' dtype and ``[B*H, 1, T]``
    float32) does not launch the forward kernel a second time.  q, k
    and v are not offered: three times the bytes for projections that
    are cheap to run again."""
    D = q.shape[-1]
    T = q.shape[2]
    s = scale if scale is not None else 1.0 / (D ** 0.5)
    rule = mask_rules.resolve(causal)
    pad_d = (-D) % LANE
    pad_t = (-T) % LANE
    if rule is not None:
        rule.check(T)
        if pad_t and rule.tile_span(T) != T:
            raise ValueError(
                f"flash attention under {rule!r}: {T} rows are not a "
                f"multiple of {LANE}, and padding would move the boundary "
                "its tiles meet on")
    if pad_d or pad_t:
        widths = [(0, 0), (0, 0), (0, pad_t), (0, pad_d)]
        q = jnp.pad(q, widths)
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
        key_mask = jnp.pad(key_mask, [(0, 0), (0, pad_t)])  # pads masked out
    out = _flash_core(q, k, v, key_mask, rule, s)
    return out[:, :, :T, :D] if (pad_d or pad_t) else out


def flash_attention_supported(q, block: int = 128) -> bool:
    """Shape gate: any T >= 128 works (a shorter sequence would be
    padded to one 128-row tile, mostly dead, and dense attention is
    cheap there); lengths that aren't 128-multiples are zero-padded
    inside flash_attention, like the head dim to 128 lanes.  Any head
    dim works, but under 32 three quarters of the padded products are
    zeros — fall back to dense."""
    B, H, T, D = q.shape
    return T >= block and D >= 32


# ===========================================================================
# Fused softmax cross-entropy
# ===========================================================================

def _softmax_xent_kernel(logits_ref, labels_ref, loss_ref, grad_ref):
    """One row-block: max-sub softmax, CE loss, (p·Σy − y) gradient — one
    HBM read of logits, one write of grad.  The Σy factor keeps the
    gradient exact for soft/unnormalized label rows (d/dx of Σy·logZ)."""
    x = logits_ref[...].astype(jnp.float32)
    y = labels_ref[...].astype(jnp.float32)
    m = x.max(axis=1, keepdims=True)
    e = jnp.exp(x - m)
    z = e.sum(axis=1, keepdims=True)
    p = e / z
    logp = (x - m) - jnp.log(z)
    loss_ref[...] = -(y * logp).sum(axis=1, keepdims=True).astype(
        loss_ref.dtype)
    grad_ref[...] = (p * y.sum(axis=1, keepdims=True) - y).astype(
        grad_ref.dtype)


def _softmax_xent_ids_kernel(logits_ref, ids_ref, loss_ref, grad_ref):
    """The same pass on integer class ids [br, 1]: the one-hot row is a
    comparison with the lane index and never exists in HBM.  An id
    outside [0, V) matches no lane: loss 0, gradient 0, as a zero label
    row gives above."""
    x = logits_ref[...].astype(jnp.float32)
    ids = ids_ref[...]
    hit = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) == ids
    m = x.max(axis=1, keepdims=True)
    e = jnp.exp(x - m)
    z = e.sum(axis=1, keepdims=True)
    logp = (x - m) - jnp.log(z)
    labelled = ((ids >= 0) & (ids < x.shape[1])).astype(jnp.float32)
    loss_ref[...] = -jnp.where(hit, logp, 0.0).sum(
        axis=1, keepdims=True).astype(loss_ref.dtype)
    grad_ref[...] = (e / z * labelled - hit.astype(jnp.float32)).astype(
        grad_ref.dtype)


def fused_softmax_xent(logits, labels, block_rows: Optional[int] = None):
    """Returns (per_row_loss [N], dlogits [N, V]) in one fused pass;
    ``labels`` are rows of class weights [N, V] or integer class ids [N].
    Rows are padded to the block size; the block height adapts to V so
    ~8 live br×V fp32 buffers (2 in, 1 out, temps) stay under the ~10 MB
    scoped-VMEM budget."""
    N, V = logits.shape
    by_id = labels.ndim == 1
    if by_id:
        labels = labels.astype(jnp.int32)[:, None]
    if block_rows is None:
        budget = 10 << 20  # observed ~8 live br x V buffers in-kernel
        block_rows = max(8, min(256, budget // (V * 4 * 8) // 8 * 8))
    br = min(block_rows, max(8, N))
    pad = (-N) % br
    if pad:
        logits = jnp.concatenate(
            [logits, jnp.zeros((pad, V), logits.dtype)])
        # a padded row has no label: zero weights, or an id no lane has
        labels = jnp.concatenate(
            [labels, jnp.full((pad, labels.shape[1]), -1 if by_id else 0,
                              labels.dtype)])
    Np = logits.shape[0]
    loss, grad = pl.pallas_call(
        _softmax_xent_ids_kernel if by_id else _softmax_xent_kernel,
        grid=(Np // br,),
        in_specs=[
            pl.BlockSpec((br, V), lambda i: (i, 0)),
            pl.BlockSpec((br, labels.shape[1]), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, V), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Np, 1), logits.dtype),
            jax.ShapeDtypeStruct((Np, V), logits.dtype),
        ],
        name="dl4j_softmax_xent",
        interpret=_interpret(),
    )(logits, labels)
    return loss[:N, 0], grad[:N]


@jax.custom_vjp
def softmax_xent_rows(logits, labels):
    """Differentiable fused softmax+CE: per-row loss [N] whose VJP reuses
    the gradient the forward kernel already produced — one VMEM pass
    total, vs softmax→log→mul→sum + their transposes on the dense path.
    Called from ops/losses.mcxent above the dispatch threshold."""
    loss, _ = fused_softmax_xent(logits, labels)
    return loss


def _sxr_fwd(logits, labels):
    loss, grad = fused_softmax_xent(logits, labels)
    return loss, (grad, labels.shape if labels.ndim == 1 else None)


def _sxr_bwd(res, g):
    grad, ids_shape = res
    # labels cotangent is never consumed (labels are data); zeros keeps the
    # vjp signature total and XLA dead-code-eliminates it (integer ids
    # take the float0 zero JAX gives integers)
    zero = (jnp.zeros_like(grad) if ids_shape is None
            else np.zeros(ids_shape, jax.dtypes.float0))
    return grad * g[:, None], zero


softmax_xent_rows.defvjp(_sxr_fwd, _sxr_bwd)


# ===========================================================================
# Fused LSTM cell — one VMEM pass for the recurrent matmul + gate math
# inside the lax.scan of ops/recurrent.lstm_scan (the cudnnRNN analog).
# ===========================================================================

def _lstm_step_kernel(zx_ref, h_ref, c_ref, rw_ref, p_ref, c_out_ref,
                      h_out_ref):
    """zx [N, 4H] (pre-projected input), h/c [N, H], rw [H, 4H],
    p [3, H] (peephole pI/pF/pO rows) → (c_new, h_new) [N, H].  Gate
    layout [i, f, o, c] matches GravesLSTMParamInitializer."""
    zx = zx_ref[...].astype(jnp.float32)
    h = h_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    z = zx + jax.lax.dot_general(
        h, rw_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # [N, 4H]
    H = c.shape[1]
    pI = p_ref[0, :].astype(jnp.float32)[None, :]
    pF = p_ref[1, :].astype(jnp.float32)[None, :]
    pO = p_ref[2, :].astype(jnp.float32)[None, :]
    i = jax.nn.sigmoid(z[:, :H] + c * pI)
    f = jax.nn.sigmoid(z[:, H:2 * H] + c * pF)
    g = jnp.tanh(z[:, 3 * H:])
    c_new = f * c + i * g
    o = jax.nn.sigmoid(z[:, 2 * H:3 * H] + c_new * pO)
    h_new = o * jnp.tanh(c_new)
    c_out_ref[...] = c_new.astype(c_out_ref.dtype)
    h_out_ref[...] = h_new.astype(h_out_ref.dtype)


def _lstm_forward(zx, h, c, rw, p3):
    N, H = c.shape
    return pl.pallas_call(
        _lstm_step_kernel,
        out_shape=[jax.ShapeDtypeStruct((N, H), c.dtype),
                   jax.ShapeDtypeStruct((N, H), h.dtype)],
        name="dl4j_lstm_step",
        interpret=_interpret(),
    )(zx, h, c, rw, p3)


def _lstm_step_reference(zx, h, c, rw, p3):
    """XLA reference of the same cell math (matches
    ops/recurrent._lstm_cell_pre with sigmoid/tanh + peephole) — the
    backward pass differentiates this."""
    z = zx + h @ rw
    zi, zf, zo, zc = jnp.split(z, 4, axis=-1)
    i = jax.nn.sigmoid(zi + c * p3[0])
    f = jax.nn.sigmoid(zf + c * p3[1])
    g = jnp.tanh(zc)
    c_new = f * c + i * g
    o = jax.nn.sigmoid(zo + c_new * p3[2])
    h_new = o * jnp.tanh(c_new)
    return c_new, h_new


@jax.custom_vjp
def fused_lstm_step(zx, h, c, rw, p3):
    """One fused peephole-LSTM step: (c_new, h_new).  zx is the
    pre-projected input row (x_t·W + b hoisted outside the scan)."""
    return _lstm_forward(zx, h, c, rw, p3)


def _lstm_vjp_fwd(zx, h, c, rw, p3):
    return _lstm_forward(zx, h, c, rw, p3), (zx, h, c, rw, p3)


def _lstm_vjp_bwd(res, g):
    _, vjp = jax.vjp(_lstm_step_reference, *res)
    return vjp(g)


fused_lstm_step.defvjp(_lstm_vjp_fwd, _lstm_vjp_bwd)


_VMEM_BUDGET = 10 << 20  # bytes of live f32 buffers one program may hold


def lstm_fused_supported(n: int, h: int, dtype) -> bool:
    """Support predicate for the lstm tier: f32/bf16, lane-friendly H,
    whole step (z + recurrent weights + states) within the VMEM
    budget.  The scan body is ONE program — no grid — so the batch must
    fit too."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)):
        return False
    if h < 8 or h % 8:
        return False
    live = (2 * n * 4 * h + h * 4 * h + 3 * h + 4 * n * h) * 4
    return live <= _VMEM_BUDGET


# ===========================================================================
# In-kernel threshold dropout — mask generated from a counter hash inside
# the kernel; the [shape]-sized mask tensor never exists in HBM, and the
# backward pass regenerates it from the seed (same kernel applied to the
# cotangent) instead of saving it.
# ===========================================================================

_DROPOUT_WIDTH = 128     # lane width of the flattened 2-D view
_DROPOUT_ROWS = 1024     # row-block per program (512 KB f32)


def _mix32(idx, s0, s1):
    """xxhash-style avalanche over a uint32 element counter + two seed
    words.  Plain integer jnp ops, so the SAME function runs inside the
    Pallas kernel and on the XLA reference path — bit-identical masks."""
    h = idx * jnp.uint32(2654435761)
    h = h ^ s0
    h = h * jnp.uint32(2246822519)
    h = h ^ (h >> jnp.uint32(13))
    h = h ^ s1
    h = h * jnp.uint32(3266489917)
    h = h ^ (h >> jnp.uint32(16))
    return h


def _threshold_dropout_math(x, idx, s0, s1, rate: float):
    """keep iff the top-24 hash bits fall under round(rate·2²⁴) — an
    integer threshold test (P(keep) = rate to 2⁻²⁴), then inverted
    scaling, matching ops/normalization.dropout semantics (rate is the
    RETAIN probability)."""
    bits = _mix32(idx, s0, s1)
    thresh = jnp.uint32(int(round(rate * float(1 << 24))))  # dl4j: noqa[DL4J101] rate is a static Python float by contract (layer config), never traced
    keep = (bits >> jnp.uint32(8)) < thresh
    # multiply by the host-computed reciprocal (not x/rate): XLA folds a
    # divide-by-constant differently inside vs outside the kernel, and
    # the kernel-vs-reference parity contract is BIT-identical
    inv = jnp.float32(1.0 / float(rate))  # dl4j: noqa[DL4J101] rate is a static Python float by contract, never traced
    return jnp.where(keep, x.astype(jnp.float32) * inv,
                     jnp.float32(0.0)).astype(x.dtype)


def _dropout_kernel(x_ref, seed_ref, out_ref, *, rate: float):
    R, W = x_ref.shape
    r0 = pl.program_id(0) * R
    rows = (r0 + lax.broadcasted_iota(jnp.int32, (R, W), 0)).astype(
        jnp.uint32)
    cols = lax.broadcasted_iota(jnp.int32, (R, W), 1).astype(jnp.uint32)
    idx = rows * jnp.uint32(W) + cols                     # global element id
    out_ref[...] = _threshold_dropout_math(
        x_ref[...], idx, seed_ref[0, 0], seed_ref[0, 1], rate)


def _dropout_forward(x2d, seed, rate: float):
    R = x2d.shape[0]
    br = min(_DROPOUT_ROWS, R)
    return pl.pallas_call(
        functools.partial(_dropout_kernel, rate=rate),
        grid=(R // br,),
        in_specs=[
            pl.BlockSpec((br, _DROPOUT_WIDTH), lambda i: (i, 0)),
            pl.BlockSpec((1, 2), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, _DROPOUT_WIDTH), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        name="dl4j_dropout",
        interpret=_interpret(),
    )(x2d, seed)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _dropout_core(x2d, seed, rate: float):
    return _dropout_forward(x2d, seed, rate)


def _dropout_vjp_fwd(x2d, seed, rate):
    # residual is the SEED alone — the mask is recomputed, never stored
    return _dropout_forward(x2d, seed, rate), seed


def _dropout_vjp_bwd(rate, seed, g):
    # d/dx of (mask ∘ x / rate) is the same masked scaling applied to g
    return _dropout_forward(g, seed, rate), None


_dropout_core.defvjp(_dropout_vjp_fwd, _dropout_vjp_bwd)


def _dropout_seed(rng):
    """Two uint32 seed words from a PRNG key (old-style uint32[2] raw
    keys and new typed keys both)."""
    kd = rng
    try:
        if jnp.issubdtype(rng.dtype, jax.dtypes.prng_key):
            kd = jax.random.key_data(rng)
    except (AttributeError, TypeError):
        pass
    kd = jnp.asarray(kd, jnp.uint32).reshape(-1)
    return jnp.stack([kd[0], kd[-1]]).reshape(1, 2)


def fused_threshold_dropout(x, rate: float, rng):
    """Inverted dropout with the mask THRESHOLD test fused into the
    kernel.  rate is the RETAIN probability (ops/normalization.dropout
    parity).  NOTE: draws from a different (hash-counter) stream than
    jax.random.bernoulli — same distribution, different masks — so the
    dense fallback is distribution-equivalent, not mask-identical;
    threshold_dropout_reference() is the bit-exact XLA reference."""
    if rate >= 1.0 or rate <= 0.0:
        return x
    n = x.size
    rows = -(-n // _DROPOUT_WIDTH)
    br = min(_DROPOUT_ROWS, max(8, rows))
    rows_p = -(-rows // br) * br
    flat = x.reshape(-1)
    pad = rows_p * _DROPOUT_WIDTH - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), x.dtype)])
    out = _dropout_core(flat.reshape(rows_p, _DROPOUT_WIDTH),
                        _dropout_seed(rng), float(rate))  # dl4j: noqa[DL4J101] rate is a static Python float (nondiff custom_vjp arg), never traced
    return out.reshape(-1)[:n].reshape(x.shape)


def threshold_dropout_reference(x, rate: float, rng):
    """Same math on the dense XLA path (global element counter = the
    kernel's row·width+col) — bit-identical to the kernel output; the
    parity tests pin this."""
    if rate >= 1.0 or rate <= 0.0:
        return x
    seed = _dropout_seed(rng)
    idx = jnp.arange(x.size, dtype=jnp.uint32).reshape(x.shape)
    return _threshold_dropout_math(x, idx, seed[0, 0], seed[0, 1],
                                   float(rate))  # dl4j: noqa[DL4J101] rate is a static Python float by contract, never traced


def dropout_fused_supported(shape, dtype) -> bool:
    """Support predicate for the dropout tier: float tensors big enough
    that skipping the HBM mask round-trip beats the kernel launch."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)):
        return False
    n = 1
    for d in shape:
        n *= int(d)
    return n >= (1 << 12)


# ===========================================================================
# Grouped matrix products — the expert layer's products over rows sorted by
# group (ops/row_segments.py): rows [m, k] of which the first sizes[0] belong
# to group 0, the next sizes[1] to group 1, and so on; rows behind the last
# group are never read into a result and come out undefined.
#
# A kernel runs a grid of STEPS, one for each (row tile, group) pair that
# holds a row of the group: a tile that two groups share runs once for each,
# and no tile behind the last group runs at all.  The steps are planned on
# the device from the group sizes (_tile_plan) and handed to the kernels by
# scalar prefetch, with their count as the grid's dynamic extent, so a step
# neither retraces nor waits for the host whatever the routing.  Products
# multiply in the operands' dtype and accumulate in float32.
#
# dl4j_gmm holds a group's whole [k, n-tile] matrix while the steps of the
# group run over its row tiles (the n tiles are the grid's outer dimension),
# so the stack is read once a product whatever the rows; ``transpose_w``
# reads w[g] as [n, k] through its own block index (x · w[g]ᵀ, the rows'
# cotangent) without a transposed copy of the stack.  dl4j_tgmm gathers
# x[rows of g]ᵀ · y[rows of g] over the group's row tiles in a float32
# block of VMEM and stores it when the group ends.  An empty group has one
# step, in which dl4j_gmm stores nothing and dl4j_tgmm stores zeros.  Rows
# of a tile outside the step's group are left as they are by dl4j_gmm's
# store and selected out of both of dl4j_tgmm's operands, so whatever an
# undefined row holds never reaches a sum.
#
# Every tile follows from the shapes alone (_gmm_tiles, _tgmm_tiles), and
# the plan of the steps is made once for all the products over the same
# rows (group_plan).  Each kernel is a primitive of its own whose
# parameters are hashable and whose lowering is emitted out of line: a call
# site binds it (no kernel is traced in the caller's trace), and a program
# traces, lowers and Mosaic-compiles each distinct (shapes, dtypes, tiles,
# transpose flag) once, in a function every site with that signature calls.
# A jax.jit entry would share its trace, but a loop body's dead-code pass
# makes a copy of the callee that the lowering then takes for another
# function: one more kernel a loop body, and a recomputed layer's more.
# ===========================================================================

# elements of a stack's block, or of the weights' gradient's float32
# accumulator (a block is double-buffered: 16 MB of VMEM at float32)
_GMM_BLOCK_ELEMS = 2 << 20
_GMM_VMEM_LIMIT = 64 << 20
# the row tile: a step's product holds at most this many rows, and a
# segment whose groups are short takes fewer (a boundary tile runs once
# for each of its groups)
_GMM_ROWS_CAP = 512
_GMM_ROWS_MIN = 128


def _lane_tile(n: int, cap_elems: int) -> int:
    """The widest tile of n that a block may have: n itself if it is no
    multiple of 128 lanes or fits ``cap_elems``, else the largest multiple
    of 128 that divides n and fits."""
    if n % LANE or n <= cap_elems:
        return n
    best = LANE
    for t in range(LANE, n + 1, LANE):
        if n % t == 0 and t <= cap_elems:
            best = t
    return best


def _row_tile(m: int, groups: int) -> int:
    """Rows a step multiplies: the largest power of two from the cap down
    whose boundary tiles (one a group, half dead on average) stay within
    an eighth of the segment, and that divides it; a segment of fewer
    rows than the smallest such tile is one tile."""
    tm = _GMM_ROWS_CAP
    while tm > _GMM_ROWS_MIN and groups * tm > m // 8:
        tm //= 2
    while tm > 8 and m % tm:
        tm //= 2
    return tm if m % tm == 0 else m


def _gmm_tiles(m: int, k: int, n: int, groups: int):
    """(row tile, column tile) of dl4j_gmm for x [m, k] by a stack of
    groups x [k, n]: the column tile keeps a group's [k, tile] block in
    the block budget."""
    return (_row_tile(m, groups),
            _lane_tile(n, max(LANE, _GMM_BLOCK_ELEMS // k)))


def _tgmm_tiles(m: int, a: int, b: int, groups: int):
    """(row tile, a tile, b tile) of dl4j_tgmm for x [m, a], y [m, b]: the
    float32 accumulator [a tile, b tile] within half the block budget."""
    ta = _lane_tile(a, 1024)
    return (_row_tile(m, groups), ta,
            _lane_tile(b, max(LANE, _GMM_BLOCK_ELEMS // (2 * ta))))


def _tile_plan(sizes, m: int, tm: int):
    """The steps of a grouped kernel over m rows in tiles of tm, from the
    group sizes on the device: (offsets [G + 1], the group of each step,
    the row tile of each step, the number of steps).  The step arrays are
    m / tm + G - 1 long, the most any sizes need; a group's steps are its
    tiles in order and the groups follow each other, so a tile that two
    groups share is visited twice in a row; an empty group has one step,
    whose rows all lie outside it.  Written in lax calls alone, a few
    dozen operations over [G] and [steps, G] integers: an operator or a
    jnp function is a jitted wrapper traced anew at each call, and a
    jitted helper a function of its own in every loop body and
    recomputed layer, and each plans once a segment a direction."""
    i32 = jnp.int32
    G = sizes.shape[0]
    length = m // tm + G - 1
    per_group = functools.partial(lax.full, (G,), dtype=i32)
    sizes = lax.convert_element_type(sizes, i32)
    ends = lax.cumsum(sizes, axis=0)
    first = lax.div(lax.sub(ends, sizes), per_group(tm))
    last = lax.div(lax.max(lax.sub(ends, per_group(1)), per_group(0)),
                   per_group(tm))
    tiles = lax.select(lax.gt(sizes, per_group(0)),
                       lax.add(lax.sub(last, first), per_group(1)),
                       per_group(1))
    after = lax.cumsum(tiles, axis=0)
    # a step's group: the groups whose steps all lie before it (one
    # comparison of [length, G], where a repeat would be a scatter)
    steps = lax.broadcasted_iota(i32, (length, G), 0)
    group_of = lax.min(
        lax.reduce_sum(lax.convert_element_type(lax.le(
            lax.broadcast_in_dim(after, (length, G), (1,)), steps), i32),
            (1,)),
        lax.full((length,), G - 1, i32))
    one_hot = lax.eq(lax.broadcast_in_dim(group_of, (length, G), (0,)),
                     lax.broadcasted_iota(i32, (length, G), 1))

    def of_group(v):
        return lax.reduce_sum(lax.select(
            one_hot, lax.broadcast_in_dim(v, (length, G), (1,)),
            lax.full((length, G), 0, i32)), (1,))
    tile_of = lax.min(
        lax.add(of_group(first), lax.sub(lax.iota(i32, length),
                                         of_group(lax.sub(after, tiles)))),
        lax.full((length,), m // tm - 1, i32))
    offsets = lax.concatenate([lax.full((1,), 0, i32), ends], 0)
    return (offsets, group_of, tile_of,
            lax.index_in_dim(after, G - 1, keepdims=False))


class GroupPlan(NamedTuple):
    """The steps of both kernels over m rows sorted into groups, planned
    once for all the products over the same rows (:func:`group_plan`)."""
    steps: jax.Array        # int32 []: how many steps run
    offsets: jax.Array      # int32 [G + 1]: where each group starts
    group_of: jax.Array     # int32 [m / tm + G - 1]: each step's group
    tile_of: jax.Array      # int32 [m / tm + G - 1]: each step's row tile
    tm: int                 # rows a step multiplies


def group_plan(sizes, m: int) -> GroupPlan:
    """The plan of the kernels' steps over m rows whose groups are
    ``sizes`` [G] long, in the row tile the segment and the groups give
    (every group, an empty one too, has a step)."""
    tm = _row_tile(m, sizes.shape[0])
    offsets, group_of, tile_of, steps = _tile_plan(sizes, m, tm)
    return GroupPlan(steps, offsets, group_of, tile_of, tm)


def _plan(groups, m: int) -> GroupPlan:
    return groups if isinstance(groups, GroupPlan) else group_plan(groups, m)


def _rows_in_group(offsets_ref, g, tile, tm: int):
    """[tm, 1]: which rows of row tile ``tile`` belong to group g."""
    rows = tile * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return lax.bitwise_and(rows >= offsets_ref[g], rows < offsets_ref[g + 1])


def _group_starts(group_ref, step):
    """Whether grid step ``step`` is its group's first."""
    return lax.bitwise_or(step == 0,
                          group_ref[lax.max(step, 1) - 1] != group_ref[step])


def _select_rows(live, block):
    """``block`` with the rows outside ``live`` [tm, 1] zero."""
    return lax.select(lax.broadcast_in_dim(live, block.shape, (0, 1)),
                      block, lax.full_like(block, 0))


def _gmm_kernel(offsets_ref, group_ref, tile_ref, x_ref, w_ref, out_ref,
                *cast_ref, transpose_w: bool):
    """One step: the row tile's product with its group's block, stored in
    the rows of the group (another group's rows of the tile keep what
    that group's step stores).  A block in another dtype than the rows'
    is cast into ``cast_ref`` once, at the first step that holds it."""
    step = pl.program_id(1)
    w = w_ref
    if cast_ref:
        w, = cast_ref

        @pl.when(_group_starts(group_ref, step))
        def _():
            w[...] = lax.convert_element_type(w_ref[...], w.dtype)
    dims = _NT if transpose_w else (((1,), (0,)), ((), ()))
    y = lax.convert_element_type(
        lax.dot_general(x_ref[...], w[...], dims,
                        preferred_element_type=jnp.float32), out_ref.dtype)
    live = _rows_in_group(offsets_ref, group_ref[step], tile_ref[step],
                          x_ref.shape[0])
    out_ref[...] = lax.select(lax.broadcast_in_dim(live, y.shape, (0, 1)),
                              y, out_ref[...])


def _grouped_primitive(name: str, impl, out_aval):
    """A primitive that runs ``impl(steps, *plan, x, w_or_y, **params)``
    (the steps and the plan of :func:`_tile_plan`, planned by the caller
    under its own scope) and lowers it as one out-of-line function a
    program for each distinct operand shapes and params;
    ``out_aval(*avals, **params)`` is its (shape, dtype)."""
    prim = jex.core.Primitive(name)
    prim.def_impl(impl)
    prim.def_abstract_eval(
        lambda *avals, **params: jax.core.ShapedArray(
            *out_aval(*avals, **params)))
    mlir.register_lowering(prim, mlir.lower_fun(impl, multiple_results=False),
                           inline=False)
    return prim


def _gmm_call(steps, offsets, group_of, tile_of, x, w, *, transpose_w: bool,
              tiles, interpret: bool):
    m, k = x.shape
    n = w.shape[1] if transpose_w else w.shape[2]
    tm, tn = tiles
    if transpose_w:
        w_block = (tn, k)
        w_spec = pl.BlockSpec((None, tn, k),
                              lambda j, s, o, g, t: (g[s], j, 0))
    else:
        w_block = (k, tn)
        w_spec = pl.BlockSpec((None, k, tn),
                              lambda j, s, o, g, t: (g[s], 0, j))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_w=transpose_w),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, steps),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, s, o, g, t: (t[s], 0)),
                w_spec,
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, s, o, g, t: (t[s], j)),
            scratch_shapes=([] if w.dtype == x.dtype
                            else [pltpu.VMEM(w_block, x.dtype)]),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_GMM_VMEM_LIMIT),
        name="dl4j_gmm",
        interpret=interpret,
    )(offsets, group_of, tile_of, x, w)


_gmm_p = _grouped_primitive(
    "grouped_matmul", _gmm_call,
    lambda *plan_x_w, transpose_w, **_: (
        (plan_x_w[4].shape[0],
         plan_x_w[5].shape[1 if transpose_w else 2]), plan_x_w[4].dtype))


def gmm(x, w, groups, transpose_w: bool = False):
    """[m, n]: row r of group g times w[g] ([G, k, n]), or times w[g]ᵀ if
    ``transpose_w`` (w [G, n, k]); ``groups`` the rows of each group in
    order ([G] sizes) or their :func:`group_plan`.  Rows behind the last
    group are undefined.  The product runs in x's dtype, which the result
    has, and accumulates in float32; a stack in a wider dtype (float32
    masters under bfloat16 rows) is cast a block at a time in VMEM, once
    a block, and never in HBM."""
    plan = _plan(groups, x.shape[0])
    n = w.shape[1] if transpose_w else w.shape[2]
    tiles = (plan.tm, _gmm_tiles(x.shape[0], x.shape[1], n, w.shape[0])[1])
    return _gmm_p.bind(*plan[:4], x, w, transpose_w=transpose_w,
                       tiles=tiles, interpret=_interpret())


def _tgmm_kernel(offsets_ref, group_ref, tile_ref, x_ref, y_ref, out_ref,
                 acc_ref):
    """One step: the group's rows of the tile, xᵀ · y, added to the
    group's float32 sum; zeroed at the group's first step, stored at its
    last."""
    step = pl.program_id(2)
    last = pl.num_programs(2) - 1
    g = group_ref[step]

    @pl.when(_group_starts(group_ref, step))
    def _():
        acc_ref[...] = lax.full(acc_ref.shape, 0, acc_ref.dtype)

    live = _rows_in_group(offsets_ref, g, tile_ref[step], x_ref.shape[0])
    acc_ref[...] += lax.dot_general(
        _select_rows(live, x_ref[...]), _select_rows(live, y_ref[...]),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(lax.bitwise_or(step == last,
                            group_ref[lax.min(step + 1, last)] != g))
    def _():
        out_ref[...] = lax.convert_element_type(acc_ref[...], out_ref.dtype)


def _tgmm_call(steps, offsets, group_of, tile_of, x, y, *, out_dtype, tiles,
               interpret: bool):
    m, a = x.shape
    b = y.shape[1]
    G = offsets.shape[0] - 1
    tm, ta, tb = tiles
    return pl.pallas_call(
        _tgmm_kernel,
        out_shape=jax.ShapeDtypeStruct((G, a, b), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(a // ta, b // tb, steps),
            in_specs=[
                pl.BlockSpec((tm, ta), lambda i, j, s, o, g, t: (t[s], i)),
                pl.BlockSpec((tm, tb), lambda i, j, s, o, g, t: (t[s], j)),
            ],
            out_specs=pl.BlockSpec((None, ta, tb),
                                   lambda i, j, s, o, g, t: (g[s], i, j)),
            scratch_shapes=[pltpu.VMEM((ta, tb), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_GMM_VMEM_LIMIT),
        name="dl4j_tgmm",
        interpret=interpret,
    )(offsets, group_of, tile_of, x, y)


_tgmm_p = _grouped_primitive(
    "grouped_matmul_t", _tgmm_call,
    lambda steps, offsets, g, t, x, y, *, out_dtype, **_: (
        (offsets.shape[0] - 1, x.shape[1], y.shape[1]), out_dtype))


def tgmm(x, y, groups, out_dtype=None):
    """[G, a, b]: for each group g, x[rows of g]ᵀ · y[rows of g] with x [m,
    a], y [m, b] and ``groups`` the rows of each group in order ([G]
    sizes) or their :func:`group_plan` (the weights' gradient of
    :func:`gmm`); summed in float32 and stored in ``out_dtype`` (x's by
    default).  An empty group gives zeros; rows behind the last group are
    never read into a sum."""
    plan = _plan(groups, x.shape[0])
    G = plan.offsets.shape[0] - 1
    tiles = (plan.tm,) + _tgmm_tiles(x.shape[0], x.shape[1], y.shape[1],
                                     G)[1:]
    return _tgmm_p.bind(*plan[:4], x, y,
                        out_dtype=jnp.dtype(out_dtype or x.dtype),
                        tiles=tiles, interpret=_interpret())


def gmm_supported(m: int, k: int, n: int, groups: int, dtype,
                  w_dtype=None) -> bool:
    """Shape gate of the gmm tier for rows [m, k] in ``dtype`` by a stack
    of groups x [k, n] in ``w_dtype`` (``dtype`` by default; float32
    masters under bfloat16 rows are cast in the kernels), both
    directions: float32 or bfloat16, rows in tiles of 8, and every block
    of both kernels within the VMEM limit (a width that is no multiple of
    128 lanes is one block whole)."""
    floats = (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))
    w_dtype = jnp.dtype(w_dtype or dtype)
    if jnp.dtype(dtype) not in floats or w_dtype not in floats:
        return False
    if m % 8:
        return False
    size, w_size = jnp.dtype(dtype).itemsize, w_dtype.itemsize
    need = 0
    for kk, nn in ((k, n), (n, k)):
        tm, tn = _gmm_tiles(m, kk, nn, groups)
        need = max(need, 2 * (tm * kk * size + kk * tn * w_size
                              + tm * tn * size)
                   + kk * tn * size + 4 * tm * tn)
    for aa, bb in ((k, n), (n, k)):
        tm, ta, tb = _tgmm_tiles(m, aa, bb, groups)
        need = max(need, 2 * (tm * ta + tm * tb) * size
                   + 2 * ta * tb * w_size + 4 * ta * tb)
    return need <= _GMM_VMEM_LIMIT // 2

