"""Sequence/context parallelism — long-context attention over the mesh.

The reference's only long-sequence mechanism is truncated BPTT
(ref: nn/multilayer/MultiLayerNetwork.java:1227); it predates ring
attention.  This module is the capability-parity *extension* SURVEY.md §5
prescribes: shard the time dimension over the mesh's 'seq' axis and keep
attention exact with ring / all-to-all communication over ICI.

Two strategies, both exact (bitwise-comparable to dense attention up to
float reassociation):

* **Ring attention** (``ring_attention``): K/V blocks rotate around the
  'seq' ring via ``lax.ppermute`` while each device streams them into a
  numerically-stable online softmax (flash-attention accumulation:
  running max / running sum / weighted accumulator).  Communication is
  neighbor-to-neighbor → rides ICI links; memory is O(T_local) per chip,
  so global context length scales linearly with the ring size.

* **Ulysses / all-to-all** (``ulysses_attention``): ``lax.all_to_all``
  re-shards [B, H, T/S, D] → [B, H/S, T, D] (heads scattered, sequence
  gathered), runs ordinary dense attention per head group, and transposes
  back.  Requires n_heads % seq_size == 0; two collectives instead of
  S-1 permutes.

Both run inside ``shard_map`` over just the attention core — projections
and the rest of the network stay plain GSPMD ops, so XLA still fuses and
partitions them automatically from the input shardings.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30

# ---------------------------------------------------------------------------
# Active-mesh context: layers query this to decide whether their attention
# core should be sequence-parallel (the analog of the reference's implicit
# "which device am I on" AffinityManager state, made explicit and scoped).
_ACTIVE_MESH: Optional[Mesh] = None
_SEQ_AXIS = "seq"


@contextlib.contextmanager
def sequence_mesh(mesh: Optional[Mesh]):
    """Scope under which attention layers shard their time dimension over
    the mesh's 'seq' axis (no-op if mesh is None or seq size is 1)."""
    global _ACTIVE_MESH
    prev = _ACTIVE_MESH
    _ACTIVE_MESH = mesh
    try:
        yield mesh
    finally:
        _ACTIVE_MESH = prev


def active_seq_size() -> int:
    if _ACTIVE_MESH is None:
        return 1
    return int(_ACTIVE_MESH.shape.get(_SEQ_AXIS, 1))


def cache_token():
    """Identity of the active sequence-parallel regime.  Models key their
    cached jitted step/score/output functions on this: entering or
    leaving ``sequence_mesh`` (or switching meshes) must retrace, since
    the collectives are baked into the traced program."""
    if _ACTIVE_MESH is None or active_seq_size() == 1:
        return None
    return id(_ACTIVE_MESH)


# ---------------------------------------------------------------------------
# KV-cache decode scope: the engines' carried decode step (`_rnn_step_raw`,
# shared by rnn_time_step and the serving decode pool) traces its forward
# under this scope, which switches SelfAttentionLayer from "re-run the whole
# window" to the incremental ring-cached path (`attend_cached`).  Training,
# TBPTT and plain output() never enter the scope, so their numerics are
# untouched.  The flag is read at TRACE time — it is baked into the compiled
# step, exactly like `cache_token()` bakes the sequence-parallel regime.
_KV_DECODE = False


@contextlib.contextmanager
def kv_decode_scope(enabled: bool = True):
    """Scope under which attention layers decode incrementally against a
    per-stream KV ring carried in ``rnn_state`` (the compiled-carry
    contract: the ring is an explicit, relocatable carry leaf, so it
    rides the decode pool's device-resident slot buffer and the fleet
    tier's migration payload)."""
    global _KV_DECODE  # dl4j: noqa[DL4J103] trace-time regime flag like sequence_mesh: flipped once around a trace, never per step
    prev = _KV_DECODE
    _KV_DECODE = bool(enabled)  # dl4j: noqa[DL4J101] `enabled` is a host-side Python bool (a trace-time mode switch), never a tracer
    try:
        yield
    finally:
        _KV_DECODE = prev


def kv_decode_active() -> bool:
    return _KV_DECODE


def kv_ring_init(batch: int, n_heads: int, window: int, head_dim: int,
                 dtype=jnp.float32):
    """Zero KV ring for ``batch`` streams: ``k``/``v`` are ``[B, H, W,
    D]`` circular buffers, ``pos`` is the per-stream count of real
    tokens ever written (monotone; write index = ``pos % W``, valid
    length = ``min(pos, W)``) — so a freshly-zeroed ring (``pos == 0``)
    is self-describing as empty, which is what lets the decode pool
    reuse a slot by zeroing its gathered carry in-trace."""
    return {
        "k": jnp.zeros((batch, n_heads, window, head_dim), dtype),
        "v": jnp.zeros((batch, n_heads, window, head_dim), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# Paged KV arena (vLLM-style block tables — the serving tier's shared
# KV pool).  Instead of every stream owning a dense [H, W, D] ring, one
# pooled [num_blocks, H, block_size, D] arena per attention layer holds
# ALL streams' K/V pages; each stream carries only an int32 block table
# mapping its logical ring slots to physical blocks.  Effective decode
# capacity becomes total tokens RESIDENT across streams instead of the
# `max_streams x worst-case window` rectangle.
#
# The arena is shared state and therefore cannot ride the per-stream
# carry pytree the way the dense ring does — it is threaded through the
# compiled step as an explicit (donated) argument.  `PagedTape` is the
# trace-time conduit between the pool step (which owns the arena
# arguments) and the attention layers (which discover them mid-forward):
# the pool step activates a tape via `paged_scope`, each attention layer
# draws its arena + block-table input from it in encounter order and
# deposits the updated arena back.  Like `kv_decode_scope`, the tape is
# read at TRACE time only — it is baked into the compiled program and
# never consulted per step.
_PAGED_TAPE = None


def block_geometry(window: int, block_size: int):
    """Round a logical window up to whole blocks: returns ``(w_eff,
    n_blocks)`` with ``w_eff = n_blocks * block_size >= window``.  The
    ring arithmetic runs mod ``w_eff`` (every ring slot maps to a fixed
    offset of a fixed table entry); validity still masks to the logical
    ``window``."""
    bs = max(1, int(block_size))
    nbs = max(1, -(-int(window) // bs))
    return nbs * bs, nbs


class PagedTape:
    """Trace-time conduit handing attention layers their shared paged-KV
    arena.  Two modes:

    * **template** (``arenas is None``): active while the pool builds
      its carry template via ``eval_shape`` — records each layer's arena
      geometry in ``specs`` (encounter order == arena id) and hands back
      a dummy 1-block arena so the trace shapes resolve.
    * **run** (``arenas``/``tables`` given): hands layer ``i`` the real
      arena tracer ``arenas[i]`` and its block-table input
      ``tables[i]``; the layer deposits the written arena via
      :meth:`put` and the pool step collects them with :meth:`collect`.
    """

    def __init__(self, block_size: int = 16, arenas=None, tables=None,
                 dtype=None, record_undo: bool = False):
        self.block_size = max(1, int(block_size))
        self.dtype = dtype          # storage override (e.g. bf16 arena)
        self.arenas = None if arenas is None else tuple(arenas)
        self.tables = None if tables is None else tuple(tables)
        # speculative verify needs to roll REJECTED writes back out of
        # the shared arena (it cannot stack the whole arena per step the
        # way the per-stream carry is stacked) — when set, layers record
        # each token's overwritten slot contents via put_undo
        self.record_undo = bool(record_undo)
        self.specs = []
        self._out = {}
        self._undo = {}
        self._i = 0

    @property
    def template(self) -> bool:
        return self.arenas is None

    def next_layer(self, n_heads: int, head_dim: int, window: int,
                   ref_dtype):
        """Claim the next arena id (layer encounter order).  Returns
        ``(aid, arena, tbl)``; in template mode ``tbl`` is ``None`` (the
        layer zero-fills) and the arena is a dummy."""
        i = self._i
        self._i += 1
        w_eff, nbs = block_geometry(window, self.block_size)
        dt = self.dtype if self.dtype is not None else ref_dtype
        if self.template:
            self.specs.append({
                "heads": int(n_heads), "head_dim": int(head_dim),
                "window": int(window), "window_eff": int(w_eff),
                "blocks_per_slot": int(nbs),
                "dtype": str(jnp.zeros((), dt).dtype)})
            dummy = jnp.zeros((2, n_heads, self.block_size, head_dim), dt)
            return i, {"k": dummy, "v": dummy}, None
        return i, self.arenas[i], self.tables[i]

    def put(self, aid: int, arena) -> None:
        if not self.template:
            self._out[aid] = arena

    def put_undo(self, aid: int, undo) -> None:
        if not self.template:
            self._undo[aid] = undo

    def collect(self):
        """Updated arenas in arena-id order (the pool step's return)."""
        return tuple(self._out[i] for i in range(self._i))

    def collect_undo(self):
        """Per-layer undo journals in arena-id order (spec verify)."""
        return tuple(self._undo[i] for i in range(self._i))


@contextlib.contextmanager
def paged_scope(tape: PagedTape):
    """Activate ``tape`` for the duration of one trace (the paged
    analog of ``kv_decode_scope`` — a trace-time regime, never a
    per-step branch)."""
    global _PAGED_TAPE  # dl4j: noqa[DL4J103] trace-time regime flag like _KV_DECODE: flipped once around a trace, never per step
    prev = _PAGED_TAPE
    _PAGED_TAPE = tape
    try:
        yield tape
    finally:
        _PAGED_TAPE = prev


def paged_tape() -> Optional[PagedTape]:
    return _PAGED_TAPE


def attend_paged(q, k_new, v_new, pos, tbl, arena, *, window: int,
                 key_mask=None, scale: Optional[float] = None,
                 undo: bool = False):
    """Incremental sliding-window attention through a block table — the
    paged twin of :func:`attend_cached` (same streaming-causal
    semantics, same masked-pad exactness, same >= f32 accumulation).

    ``pos``: ``[B]`` int32 monotone token count per stream; ``tbl``:
    ``[B, n_blocks_per_slot]`` int32 physical block ids (entries beyond
    the allocated prefix point at the arena's scratch block — they are
    never valid-attendable); ``arena``: ``{"k","v"}`` of
    ``[num_blocks, H, block_size, D]``.  Token ``t`` writes its K/V at
    ring slot ``pos % w_eff`` → physical ``(tbl[slot // bs], slot %
    bs)``, then attends over the gathered ``[H, w_eff, D]`` view with
    validity masked to the logical ``window``.  Writes are
    delta-scatter-adds (``old + (new - old) * mask``): masked pad
    tokens write exactly nothing, and duplicate scratch-block rows
    (pad/warmup) stay bounded.  Returns ``(out, new_pos, new_arena)``;
    the arena is storage-dtype (bf16 arenas attend with f32
    accumulation via ``preferred_element_type``).

    With ``undo=True`` additionally returns a journal of every token's
    overwritten slot — ``{"pb","o": [Tc,B], "k","v": [Tc,B,H,D]}`` (the
    pre-write contents) — so speculative verify can restore the shared
    arena for rejected tokens after acceptance is known."""
    B, H, Tc, D = q.shape
    ak, av = arena["k"], arena["v"]
    bs = ak.shape[2]
    nbs = tbl.shape[1]
    w_eff = nbs * bs
    W = min(int(window), w_eff)
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    acc_dt = jnp.promote_types(q.dtype, jnp.float32)
    if key_mask is None:
        key_mask = jnp.ones((B, Tc), q.dtype)
    slots = jnp.arange(w_eff)
    rows = jnp.arange(B)

    def body(carry, inp):
        ka, va, p = carry
        q_t, k_t, v_t, m_t = inp          # [B,H,D] x3, [B]
        w = p % w_eff                      # [B] ring slot
        pb = tbl[rows, w // bs]            # [B] physical block
        o = w % bs                         # [B] offset within block
        m = m_t.astype(ka.dtype)[:, None, None]
        old_k = ka[pb, :, o, :]            # [B, H, D] pre-write contents
        old_v = va[pb, :, o, :]
        # masked delta-write: .add of (new - old) * m is a set for
        # unique (pb, o) pairs (live streams hold disjoint blocks), a
        # no-op for masked pads, and bounded for duplicated scratch
        # rows (whose contents are never valid-attendable)
        ka = ka.at[pb, :, o, :].add((k_t.astype(ka.dtype) - old_k) * m)
        va = va.at[pb, :, o, :].add((v_t.astype(va.dtype) - old_v) * m)
        count = p + m_t.astype(p.dtype)
        # gather AFTER the write: [B, nbs, H, bs, D] -> [B, H, w_eff, D]
        kg = jnp.moveaxis(ka[tbl], 2, 1).reshape(B, H, w_eff, D)
        vg = jnp.moveaxis(va[tbl], 2, 1).reshape(B, H, w_eff, D)
        # slot s holds logical position `last` = the largest p' < count
        # with p' ≡ s (mod w_eff); valid iff it exists and is within
        # the logical window (w_eff > window only pads to whole blocks)
        c1 = count[:, None] - 1
        last = c1 - ((c1 - slots[None, :]) % w_eff)       # [B, w_eff]
        valid = (last >= 0) & (last >= count[:, None] - W)
        # zero INVALID values before the weighted sum: invalid slots may
        # alias the scratch block (unallocated table tail entries) whose
        # contents are arbitrary — a 0-weight x garbage product must
        # never poison the output (0 * inf/nan is nan)
        vg = jnp.where(valid[:, None, :, None], vg, 0)
        scores = jnp.einsum("bhd,bhwd->bhw", q_t, kg,
                            preferred_element_type=acc_dt) * scale
        scores = jnp.where(valid[:, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        o_t = jnp.einsum("bhw,bhwd->bhd", probs, vg,
                         preferred_element_type=acc_dt)
        u_t = {"pb": pb, "o": o, "k": old_k, "v": old_v}
        return (ka, va, count), (o_t.astype(q.dtype), u_t)

    xs = (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k_new, 2, 0),
          jnp.moveaxis(v_new, 2, 0), jnp.moveaxis(key_mask, 1, 0))
    (ak, av, pos), (outs, journal) = lax.scan(body, (ak, av, pos), xs)
    if undo:
        return jnp.moveaxis(outs, 0, 2), pos, {"k": ak, "v": av}, journal
    return jnp.moveaxis(outs, 0, 2), pos, {"k": ak, "v": av}


def attend_cached(q, k_new, v_new, ring, *, key_mask=None,
                  scale: Optional[float] = None):
    """Incremental sliding-window attention over a per-stream KV ring —
    the O(window)/token decode path (vs ``dense_attention``'s
    O(T)/token re-run of the whole stream).

    ``q, k_new, v_new``: the NEW chunk's projections ``[B, H, Tc, D]``;
    ``ring``: ``kv_ring_init``-shaped pytree; ``key_mask``: ``[B, Tc]``
    with 1 = real token.  Semantics are streaming-causal: chunk token
    ``t`` first appends its K/V at ``pos % W`` (masked pad tokens write
    nothing and advance nothing — a bucketed pad chunk carries the ring
    through unchanged, exact), then attends over the ``min(pos+1, W)``
    valid entries; entries older than ``window`` are overwritten and
    masked out (ring wraparound).  For ``window >= stream length`` the
    step-by-step outputs match full causal ``dense_attention`` to float
    reassociation (the parity the tests pin at 1e-5).

    Cost per token is O(window) flat in stream length — the lax.scan
    over the chunk keeps the HLO O(1) in chunk length, and per-step
    statistics accumulate at >= f32 like the ring-attention core."""
    B, H, Tc, D = q.shape
    W = ring["k"].shape[2]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    acc_dt = jnp.promote_types(q.dtype, jnp.float32)
    if key_mask is None:
        key_mask = jnp.ones((B, Tc), q.dtype)
    slots = jnp.arange(W)

    def body(carry, inp):
        kr, vr, pos = carry
        q_t, k_t, v_t, m_t = inp          # [B,H,D] x3, [B]
        m_t = m_t.astype(kr.dtype)
        # append: one-hot write at pos % W, gated by the token mask
        write = ((slots[None, :] == (pos % W)[:, None]).astype(kr.dtype)
                 * m_t[:, None])          # [B, W]
        wr = write[:, None, :, None]      # [B, 1, W, 1]
        kr = kr * (1.0 - wr) + k_t[:, :, None, :] * wr
        vr = vr * (1.0 - wr) + v_t[:, :, None, :] * wr
        count = pos + m_t.astype(pos.dtype)
        # ring wraparound masking: only the min(count, W) most-recent
        # entries are attendable (slot indices fill 0..W-1 then wrap,
        # so validity is a plain length test against the write count)
        valid = slots[None, :] < jnp.minimum(count, W)[:, None]   # [B, W]
        scores = jnp.einsum("bhd,bhwd->bhw", q_t, kr,
                            preferred_element_type=acc_dt) * scale
        scores = jnp.where(valid[:, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        o_t = jnp.einsum("bhw,bhwd->bhd", probs, vr,
                         preferred_element_type=acc_dt)
        return (kr, vr, count), o_t.astype(q.dtype)

    xs = (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k_new, 2, 0),
          jnp.moveaxis(v_new, 2, 0), jnp.moveaxis(key_mask, 1, 0))
    (kr, vr, pos), outs = lax.scan(
        body, (ring["k"], ring["v"], ring["pos"]), xs)
    return (jnp.moveaxis(outs, 0, 2),
            {"k": kr, "v": vr, "pos": pos})


# ---------------------------------------------------------------------------
# Dense reference core (single device / no 'seq' axis).


def dense_attention(q, k, v, *, causal=False, key_mask=None,
                    scale: Optional[float] = None, allow_flash: bool = True):
    """Plain softmax attention.  q,k,v: [B, H, T, D]; key_mask: [B, Tk]
    with 1=keep (the reference's feedForwardMaskArray convention,
    ref: nn/api/Layer.java:309).  ``causal`` is False, True or a mask
    rule (``ops/mask_rules.py``: which pairs live, by position).  On
    TPU, tile-friendly shapes route to the Pallas flash-attention kernel
    (ops/pallas_kernels.py) — O(T·D) memory instead of the [T, T] score
    matrix in HBM — which visits only the tiles the rule leaves live."""
    from deeplearning4j_tpu.ops import mask_rules
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    rule = mask_rules.resolve(causal)
    if allow_flash and q.shape[2] == k.shape[2]:
        # helper selection (ops/helpers.py): the attention tier routes
        # tile-friendly shapes to the flash kernel and meters the choice
        from deeplearning4j_tpu.ops import helpers
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        if helpers.attention_wanted(q):
            km = (key_mask if key_mask is not None
                  else jnp.ones((q.shape[0], k.shape[2]), q.dtype))
            return pk.flash_attention(q, k, v, km.astype(q.dtype), rule,
                                      scale)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if rule is not None:
        Tq, Tk = scores.shape[-2], scores.shape[-1]
        if Tq == Tk:
            rule.check(Tq)
        qi = jnp.arange(Tq)[:, None]
        ki = jnp.arange(Tk)[None, :]
        scores = jnp.where(rule.live(qi, ki), scores, NEG_INF)
    if key_mask is not None:
        scores = jnp.where(key_mask[:, None, None, :].astype(bool),
                           scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


# ---------------------------------------------------------------------------
# Ring attention (per-shard body; run under shard_map over 'seq').


def _ring_attention_sharded(q, k, v, key_mask, *, axis_name: str,
                            causal: bool, scale: Optional[float]):
    """Online-softmax ring scan.  Per-shard shapes: q,k,v [B, H, Tl, D],
    key_mask [B, Tl] or None.  The device's global block index comes from
    ``lax.axis_index`` so causal masking uses *global* positions."""
    S = lax.axis_size(axis_name)
    B, H, Tl, D = q.shape
    scale = scale if scale is not None else 1.0 / (D ** 0.5)
    idx = lax.axis_index(axis_name)
    q_pos = idx * Tl + jnp.arange(Tl)                      # global q positions

    # Online-softmax statistics accumulate at >=f32 regardless of the
    # compute dtype: bf16 running max/denominator drifts visibly vs the
    # dense/Pallas paths (which accumulate f32), and the f64 gradient-check
    # path keeps its width (advisor round-1 finding).
    acc_dt = jnp.promote_types(q.dtype, jnp.float32)
    m = jnp.full((B, H, Tl), NEG_INF, acc_dt)              # running row max
    l = jnp.zeros((B, H, Tl), acc_dt)                      # running denom
    o = jnp.zeros((B, H, Tl, D), acc_dt)                   # weighted accum
    if key_mask is None:
        key_mask = jnp.ones((B, Tl), q.dtype)

    # after s hops each device holds the block originally on (idx - s) % S
    perm = [(i, (i + 1) % S) for i in range(S)]

    # lax.scan (not a Python loop) so the HLO stays O(1) in ring size —
    # one block-update body compiled once, S trips; the extra ppermute on
    # the last trip completes the cycle (blocks return to their owners).
    def body(carry, s):
        m, l, o, k, v, mask = carry
        src = (idx - s) % S
        k_pos = src * Tl + jnp.arange(Tl)                  # global k positions

        def attend(m, l, o):
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                                preferred_element_type=acc_dt) * scale
            if causal:
                scores = jnp.where(q_pos[:, None] >= k_pos[None, :],
                                   scores, NEG_INF)
            scores = jnp.where(mask[:, None, None, :].astype(bool),
                               scores, NEG_INF)
            m_new = jnp.maximum(m, scores.max(axis=-1))
            # guard fully-masked rows: keep exp argument finite
            alpha = jnp.exp(jnp.maximum(m - m_new, NEG_INF * 0.5))
            p = jnp.exp(scores - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            o_new = o * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, v, preferred_element_type=acc_dt)
            return m_new, l_new, o_new

        # NB: a causal block-skip (cond on "all k in this shard's future")
        # cannot shorten the ring's critical path — every hop ends in a
        # ppermute all S devices must join, and the last shard attends on
        # every hop, so step time stays S x attend either way.  The real
        # causal win is zigzag/striped query partitioning (balance low+high
        # positions per shard); until that layout lands, unconditional
        # compute keeps the body simple and vmap-safe.
        m, l, o = attend(m, l, o)
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        mask = lax.ppermute(mask, axis_name, perm)
        return (m, l, o, k, v, mask), None

    (m, l, o, _, _, _), _ = lax.scan(
        body, (m, l, o, k, v, key_mask), jnp.arange(S))
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def ring_attention(q, k, v, *, mesh: Mesh, causal: bool = False,
                   key_mask=None, scale: Optional[float] = None,
                   axis_name: str = _SEQ_AXIS):
    """shard_map-wrapped exact ring attention; q,k,v are full arrays whose
    time dim is (to be) sharded over ``axis_name``."""
    spec = P(None, None, axis_name, None)
    mask_spec = P(None, axis_name)
    if key_mask is None:
        key_mask = jnp.ones((q.shape[0], q.shape[2]), q.dtype)
    fn = shard_map(
        partial(_ring_attention_sharded, axis_name=axis_name,
                causal=causal, scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec, mask_spec),
        out_specs=spec,
        check_vma=False)
    return fn(q, k, v, key_mask)


# ---------------------------------------------------------------------------
# Ulysses (all-to-all) sequence parallelism.


def _ulysses_sharded(q, k, v, key_mask, *, axis_name: str, causal: bool,
                     scale: Optional[float]):
    """Per-shard: [B, H, Tl, D] → all_to_all → [B, H/S, T, D] → dense
    attention → all_to_all back."""
    S = lax.axis_size(axis_name)
    a2a = partial(lax.all_to_all, axis_name=axis_name, split_axis=1,
                  concat_axis=2, tiled=True)
    qg, kg, vg = a2a(q), a2a(k), a2a(v)                  # [B, H/S, T, D]
    mask_g = lax.all_gather(key_mask, axis_name, axis=1, tiled=True)  # [B, T]
    out = dense_attention(qg, kg, vg, causal=causal, key_mask=mask_g,
                          scale=scale)
    return lax.all_to_all(out, axis_name=axis_name, split_axis=2,
                          concat_axis=1, tiled=True)     # [B, H, Tl, D]


def ulysses_attention(q, k, v, *, mesh: Mesh, causal: bool = False,
                      key_mask=None, scale: Optional[float] = None,
                      axis_name: str = _SEQ_AXIS):
    S = int(mesh.shape[axis_name])
    if q.shape[1] % S:
        raise ValueError(f"n_heads={q.shape[1]} not divisible by seq={S}")
    spec = P(None, None, axis_name, None)
    mask_spec = P(None, axis_name)
    if key_mask is None:
        key_mask = jnp.ones((q.shape[0], q.shape[2]), q.dtype)
    fn = shard_map(
        partial(_ulysses_sharded, axis_name=axis_name, causal=causal,
                scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec, mask_spec),
        out_specs=spec,
        check_vma=False)
    return fn(q, k, v, key_mask)


# ---------------------------------------------------------------------------
# Strategy dispatch used by SelfAttentionLayer.


def attention(q, k, v, *, causal=False, key_mask=None,
              scale: Optional[float] = None, strategy: str = "auto"):
    """Attention core that is sequence-parallel whenever a mesh with a
    non-trivial 'seq' axis is active (see ``sequence_mesh``), dense
    otherwise.  strategy: 'auto' | 'ring' | 'ulysses' | 'dense'.
    ``causal``: False, True or a mask rule (``ops/mask_rules.py``); the
    sequence-parallel strategies know False and True only and refuse
    any other rule."""
    if strategy not in ("auto", "ring", "ulysses", "dense"):
        raise ValueError(f"unknown attention strategy {strategy!r} "
                         "(expected auto|ring|ulysses|dense)")
    mesh = _ACTIVE_MESH
    seq = active_seq_size()
    if strategy == "dense" or seq == 1 or mesh is None:
        return dense_attention(q, k, v, causal=causal, key_mask=key_mask,
                               scale=scale)
    from deeplearning4j_tpu.ops import mask_rules
    rule = mask_rules.resolve(causal)
    causal = rule is mask_rules.CAUSAL
    if rule is not None and not causal:
        raise NotImplementedError(
            f"attention under the mask rule {rule!r} over a 'seq' mesh "
            f"axis of {seq}: the ring and all-to-all strategies shard the "
            "sequence by position and know causal masks only; run it with "
            "strategy='dense' or without the axis")
    if q.shape[2] % seq:
        raise ValueError(
            f"sequence length {q.shape[2]} not divisible by the mesh 'seq' "
            f"axis ({seq}); pad/bucket the time dimension to a multiple")
    if strategy == "ulysses":
        # explicit request: let ulysses_attention raise on head/seq mismatch
        return ulysses_attention(q, k, v, mesh=mesh, causal=causal,
                                 key_mask=key_mask, scale=scale)
    if strategy == "auto" and q.shape[1] % seq == 0 and seq <= 4:
        return ulysses_attention(q, k, v, mesh=mesh, causal=causal,
                                 key_mask=key_mask, scale=scale)
    return ring_attention(q, k, v, mesh=mesh, causal=causal,
                          key_mask=key_mask, scale=scale)
