"""Row buffers cut into segments, the empty ones skipped on the device.

An expert layer that holds G of E experts sorts its N·k assignments by
held expert: the rows that hold work are the first ``held`` of the sorted
order, and ``held`` is data.  The functions here work on such a buffer in
``n_seg`` segments of ``seg`` rows (both static) and run only the first
``ceil(held / seg)`` of them, in a loop whose trip count the device reads
(``lax.fori_loop`` with a traced bound: no retrace, no host round trip,
no bound that truncates: with every row held every segment runs).  Rows
of a segment that did not run read zero: a buffer is zeroed whole before
the loop fills it, which costs the chip less than zeros written segment
by segment after it (``PERF.md`` 6, PR 30).  A buffer of one segment, as
of a layer that holds every expert, takes the loop's body once, with no
loop and no zeros.

What runs by segment, over the rows that hold work: the gather of the
tokens' rows into the sorted order, the grouped products forward and to
the rows backward with their gate and masks, and the gather of the
cotangents on the weighted sum's way back.  What runs once over the
whole buffer: the weights' gradients (one grouped product each; rows
beyond the groups are in no group, and a sum carried through the
segments would read and write the weight stacks once a segment) and the
way back from the sorted order to the tokens, a gather over every
assignment (a row costs the chip several times as much to scatter as
to gather: ``PERF.md`` 6, PR 30).  Each has its backward written out
(``custom_vjp``), so that a loop's backward is such a loop and not a
sum of whole buffers over the segments.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

#: rows of a segment are a multiple of this (a bf16 tile's sublanes)
ROW_TILE = 16


def segment_rows(rows: int, held: int, experts: int):
    """(rows of a segment, segments) for a sorted buffer of ``rows``
    assignments of which ``held`` of ``experts`` experts' are here:
    twice the even-load share ``rows * held / experts``, rounded up to
    the row tile.  An even load then lies in the middle of the first
    segment and not on its edge, where a row more or less from step to
    step decides whether a second one runs (``PERF.md`` 6, PR 35); a
    second segment runs when the load passes twice its even share, and
    a layer that holds half the experts or more runs its one."""
    if held >= experts:
        return rows, 1
    share = -(-rows * held // experts)
    seg = -(-2 * share // ROW_TILE) * ROW_TILE
    return (rows, 1) if seg >= rows else (seg, -(-rows // seg))


def segments_run(held_rows, seg: int):
    """Segments that hold a row with work, of a count on the host or on
    the device; the first runs even with none."""
    ceil = -(-held_rows // seg)
    return max(1, ceil) if isinstance(ceil, int) else jnp.maximum(1, ceil)


def _rows_of(buf, s, seg):
    return lax.dynamic_slice_in_dim(buf, s * seg, seg, axis=0)


def _put(buf, s, seg, rows):
    return lax.dynamic_update_slice_in_dim(buf, rows.astype(buf.dtype),
                                           s * seg, axis=0)


def _valid(s, seg, held_rows):
    return (s * seg + jnp.arange(seg) < held_rows)[:, None]


def _by_segment(held_rows, seg, body, bufs):
    """The buffers ``bufs`` (shapes and dtypes, ``seg`` rows a segment)
    filled with ``body(s)``'s rows of each over the segments s that hold
    work, in order; the other segments read zero."""
    if bufs[0].shape[0] == seg:
        return tuple(v.astype(b.dtype) for v, b in zip(body(0), bufs))

    def live(s, carry):
        return tuple(_put(c, s, seg, v) for c, v in zip(carry, body(s)))

    return lax.fori_loop(0, segments_run(held_rows, seg), live,
                         tuple(jnp.zeros(b.shape, b.dtype) for b in bufs))


def _like(rows, *tail, dtype):
    return jax.ShapeDtypeStruct((rows,) + tail, dtype)


# --- the rows out to the sorted order and back --------------------------------
# ``perm[r]`` is the assignment (token perm[r] // k, choice perm[r] % k) that
# sorted row r holds, ``back`` [n, k] its inverse: the row of each assignment.
# Either way round a row moves by a gather: out along ``perm`` over the rows
# that hold work, back along ``back`` over every assignment, whose rows
# without work read zero.
def _sum_over_choices(buf, back, w=None):
    """[n, ...]: every token's sum over the sorted buffer's rows of its k
    assignments, each times its weight ``w[n, j]`` if given; summed in
    float32 and rounded once.  One gather of n rows a choice: gathered
    as [n k, ...] and viewed as [n, k, ...] the rows would be laid out
    anew on the chip, which costs as much as the gather."""
    wide = jnp.promote_types(buf.dtype, jnp.float32)
    total = 0
    for j in range(back.shape[1]):
        got = buf.at[back[:, j]].get(unique_indices=True,
                                     mode="promise_in_bounds").astype(wide)
        total = total + (got if w is None else got * w[:, j, None].astype(wide))
    return total.astype(buf.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def gather_rows(tokens, perm, back, held_rows, seg, k):
    """Sorted row r is token ``perm[r] // k`` for r < held_rows, else 0."""
    n = tokens.shape[0]

    def body(s):
        index = jnp.minimum(_rows_of(perm, s, seg) // k, n - 1)
        got = tokens.at[index].get(mode="promise_in_bounds")
        return jnp.where(_valid(s, seg, held_rows), got, 0),

    return _by_segment(held_rows, seg, body, (
        _like(perm.shape[0], *tokens.shape[1:], dtype=tokens.dtype),))[0]


def _gather_rows_fwd(tokens, perm, back, held_rows, seg, k):
    return gather_rows(tokens, perm, back, held_rows, seg, k), back


def _gather_rows_bwd(seg, k, res, d_rows):
    # a token's cotangent is the sum over its k assignments' rows
    return _sum_over_choices(d_rows, res), None, None, None


gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def weighted_sum(y, w, perm, back, held_rows, seg):
    """``sum_j w[n, j] * y[back[n, j]]``: every token's weighted sum
    over the sorted rows of its k assignments."""
    return _sum_over_choices(y, back, w)


def _weighted_sum_fwd(y, w, perm, back, held_rows, seg):
    return weighted_sum(y, w, perm, back, held_rows, seg), (
        y, w, perm, back, held_rows)


def _weighted_sum_bwd(seg, res, d_out):
    y, w, perm, back, held_rows = res
    n, k = w.shape
    flat_w = w.reshape(-1)
    wide = jnp.promote_types(y.dtype, jnp.float32)

    def body(s):
        a = jnp.minimum(_rows_of(perm, s, seg), n * k - 1)
        valid = _valid(s, seg, held_rows)
        # the token's cotangent row: times the assignment's weight it is
        # the sorted row's cotangent, along the row's values the weight's
        got = d_out.at[a // k].get(mode="promise_in_bounds")
        return (jnp.where(valid, got * flat_w[a][:, None], 0),
                jnp.sum(_rows_of(y, s, seg).astype(wide) * got.astype(wide),
                        axis=-1, where=valid))

    d_y, d_w = _by_segment(held_rows, seg, body,
                           (y, _like(y.shape[0], dtype=wide)))
    return d_y, d_w[back].astype(w.dtype), None, None, None


weighted_sum.defvjp(_weighted_sum_fwd, _weighted_sum_bwd)


# --- the grouped products ----------------------------------------------------
def _segment_sizes(group_sizes, s, seg):
    """The part of each group that lies in segment s of the sorted
    order: the global sizes clipped to the segment."""
    ends = jnp.cumsum(group_sizes)
    lo = s * seg
    return (jnp.clip(ends, lo, lo + seg)
            - jnp.clip(ends - group_sizes, lo, lo + seg)).astype(
                group_sizes.dtype)


def gated_silu(h1, h3):
    return jax.nn.silu(h1) * h3


def _to_rows(ct, like, w, sizes):
    """JAX's transpose of ``rows -> ragged_dot(rows, w, sizes)``."""
    return jax.linear_transpose(
        lambda r: lax.ragged_dot(r, w, sizes), like)(ct)[0]


def _to_weights(ct, rows, w, sizes):
    """JAX's transpose of ``w -> ragged_dot(rows, w, sizes)``."""
    return jax.linear_transpose(
        lambda w: lax.ragged_dot(rows, w, sizes), w)(ct)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def expert_products(rows, w_in, w2, group_sizes, seg, act):
    """``act(rows W_in...) W2`` by group over the rows that hold work:
    ``w_in`` is (W1,) or (W1, W3), ``act`` their elementwise gate."""
    return _expert_products_fwd(rows, w_in, w2, group_sizes, seg, act)[0]


def _expert_products_fwd(rows, w_in, w2, group_sizes, seg, act):
    held_rows = jnp.sum(group_sizes)

    def body(s):
        sizes = _segment_sizes(group_sizes, s, seg)
        r = _rows_of(rows, s, seg)
        h = tuple(lax.ragged_dot(r, w, sizes) for w in w_in)
        # rows beyond the groups belong to other holders: the grouped
        # products leave them undefined, in both directions
        y = lax.ragged_dot(act(*h), w2, sizes)
        return (jnp.where(_valid(s, seg, held_rows), y, 0),) + h

    def like(w):
        return _like(rows.shape[0], w.shape[-1], dtype=rows.dtype)
    y, *hidden = _by_segment(
        held_rows, seg, body, (like(w2),) + tuple(like(w) for w in w_in))
    return y, (rows, tuple(hidden), w_in, w2, group_sizes)


def _expert_products_bwd(seg, act, res, d_y):
    """The rows' cotangents segment by segment, from the kept hidden
    rows; the weights' in one grouped product each over the whole
    buffer, whose rows beyond the groups are in no group: nothing is
    summed over the segments."""
    rows, hidden, w_in, w2, group_sizes = res
    held_rows = jnp.sum(group_sizes)

    def body(s):
        sizes = _segment_sizes(group_sizes, s, seg)
        valid = _valid(s, seg, held_rows)
        h = tuple(_rows_of(b, s, seg) for b in hidden)
        a, gate_back = jax.vjp(act, *h)
        d_h = gate_back(_to_rows(jnp.where(valid, _rows_of(d_y, s, seg), 0),
                                 a, w2, sizes))
        r = _rows_of(rows, s, seg)
        d_r = sum(_to_rows(d, r, w, sizes) for d, w in zip(d_h, w_in))
        return (jnp.where(valid, d_r, 0), a) + tuple(d_h)

    d_rows, gated, *d_hidden = _by_segment(
        held_rows, seg, body, (rows, hidden[0]) + tuple(hidden))
    d_w_in = tuple(_to_weights(d, rows, w, group_sizes)
                   for d, w in zip(d_hidden, w_in))
    return d_rows, d_w_in, _to_weights(d_y, gated, w2, group_sizes), None


expert_products.defvjp(_expert_products_fwd, _expert_products_bwd)
