"""Pallas TPU kernels for the hot ops where HLO fusion isn't enough
(SURVEY.md §7: the native-kernel tier; the reference's analog is the
fused libnd4j Aggregate ops + cuDNN helpers, §2.3/§2.10).

This module holds the KERNELS and their shape/dtype support predicates;
per-layer selection between a kernel and its dense XLA fallback lives in
``ops/helpers.py`` (the cuDNN-helper-selection tier: registry, per-tier
kill switches, warm validation, ``dl4j_pallas_*`` selection metrics).

Four kernels:

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

All run under ``interpret=True`` off-TPU so the same code is testable
on the CPU mesh (the reference's cuDNN-vs-builtin cross-check pattern,
SURVEY.md §4)."""

from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import mask_rules, recompute

NEG_INF = -1e30


# Runtime kill switches, PER KERNEL TIER: set by kernel_self_test() when
# a Mosaic compile fails on the real chip, so one bad kernel degrades to
# the dense XLA path without disabling the other, healthy ones (the
# cuDNN-helper-with-builtin-fallback pattern, ref
# ConvolutionLayer.java:157-212).  DL4J_PALLAS=0 disables everything;
# per-tier state is read by ops/helpers.available().
ALL_TIERS = ("flash", "xent", "lstm", "dropout")
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
    the chip — all four, by name: flash, xent, lstm, dropout.  A
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

