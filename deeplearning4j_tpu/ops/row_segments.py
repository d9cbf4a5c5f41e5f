"""An expert layer's sorted rows taken a segment at a time, from the
tokens to the tokens' sum, the empty segments skipped on the device.

An expert layer that holds G of E experts sorts its N·k assignments by
held expert: the rows that hold work are the first ``held`` of the sorted
order, and ``held`` is data.  The order is cut into ``n_seg`` segments of
``seg`` rows (both static), and **no array here that is as wide as the
model or as an expert has more than one segment's rows**: only the index
arrays (``perm``, ``back``: N·k int32) are whole.  :func:`routed_sum`
takes one segment at a time through all of the layer: the gather of its
tokens' rows, the grouped products with the global group sizes clipped
to the segment, the gate, and the way back, k gathers of N rows out of
the segment's output summed in float32 over the assignments whose row
lies in it.  What is carried from one segment to the next is shaped like
the tokens (float32 until the one rounding) and, backward, like the
weights.

Segment 0 runs once, outside any loop (it runs even with no row held):
its arrays are written whole, nothing is zeroed first, and its rows and
hidden rows are what the backward keeps.  Segments 1 to ``ceil(held /
seg)`` - 1 run in a loop whose trip count the device reads
(``lax.fori_loop`` with a traced bound: no retrace, no host round trip,
no bound that truncates: with every row held every segment runs), adding
into the tokens' sum; backward their hidden rows are computed again from
the gathered rows and their weights' gradients added to segment 0's.
That path costs a second forward of the segment and one read and write
of the weight stacks a segment, and runs when a holder's load passes
twice its even share (``segment_rows``).  A layer that holds every expert
has one segment of N·k rows: the same body once, no loop.

The backward is written out (``custom_vjp``), so that a loop's backward
is such a loop and a gather's a gather (a row costs the chip several
times as much to scatter as to gather: ``PERF.md`` 6, PR 30).  Rows of a
segment beyond the held ones belong to other holders: the grouped
products leave them undefined in both directions, and every sum that
could read them selects them out.
"""

from __future__ import annotations

import functools
import re

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


def tall_arrays(program: str, seg: int, widths, stacks=()):
    """The array shapes in a lowered or compiled program's text (StableHLO
    ``tensor<8x4xf32>`` or HLO ``f32[8,4]``) that break this module's
    rule: one dimension is a width of ``widths`` (the model's, an
    expert's) and the others multiply to more than ``seg`` rows.  Shapes
    in ``stacks`` (the experts' weight stacks, in any order of
    dimensions) are no rows."""
    allowed = {tuple(sorted(shape)) for shape in stacks}
    tall = set()
    for dims in re.findall(r"(?:tensor<|\w\[)(\d+(?:[x,]\d+)+)", program):
        shape = tuple(int(d) for d in re.split("[x,]", dims))
        size = 1
        for d in shape:
            size *= d
        if tuple(sorted(shape)) not in allowed and any(
                d in widths and size // d > seg for d in shape):
            tall.add(shape)
    return sorted(tall)


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


def _transposed(w):
    """The stack ``w`` [G, a, b] with each expert's matrix transposed:
    ``ragged_dot(ct, _transposed(w), sizes)`` is the transpose of ``rows
    -> ragged_dot(rows, w, sizes)``.  Made once a layer and handed to
    every segment: the chip lays a transposed stack out anew, and a
    transposition written inside the later segments' loop is moved out
    of it by the compiler and paid whether the loop runs or not."""
    return jnp.swapaxes(w, 1, 2)


def _to_weights(ct, rows, w, sizes):
    """JAX's transpose of ``w -> ragged_dot(rows, w, sizes)``."""
    return jax.linear_transpose(
        lambda w: lax.ragged_dot(rows, w, sizes), w)(ct)[0]


# ``perm[r]`` is the assignment (token perm[r] // k, choice perm[r] % k) that
# sorted row r holds, ``back`` [n, k] its inverse: the row of each assignment.
# Either way round a row moves by a gather: out along ``perm`` over a
# segment's rows, back along ``back`` over every assignment, of which those
# whose row lies in another segment or holds no work are selected out.
def _assignments(perm, s, seg, n_k):
    """The assignment of each row of segment s (the filling behind the
    last row reads the last assignment: no held row is filling)."""
    return jnp.minimum(lax.dynamic_slice_in_dim(perm, s * seg, seg), n_k - 1)


def _place(back, s, seg, held_rows):
    """([n, k] index into segment s, [n, k] whether it is there and holds
    work) of every assignment's sorted row."""
    lo = s * seg
    there = (back >= lo) & (back < jnp.minimum(lo + seg, held_rows))
    return jnp.clip(back - lo, 0, seg - 1), there


def _sum_over_choices(buf, index, there, w=None):
    """[n, ...] float32: every token's sum over the rows ``index[n, j]``
    of ``buf`` for the choices j that are ``there``, each times its weight
    ``w[n, j]`` if given.  One gather of n rows a choice: gathered as
    [n k, ...] and viewed as [n, k, ...] the rows would be laid out anew
    on the chip, which costs as much as the gather."""
    wide = jnp.promote_types(buf.dtype, jnp.float32)
    total = 0
    for j in range(index.shape[1]):
        got = buf.at[index[:, j]].get(mode="promise_in_bounds").astype(wide)
        got = jnp.where(there[:, j, None], got, 0)
        total = total + (got if w is None else got * w[:, j, None].astype(wide))
    return total


def _hidden(s, seg, k, tokens, w_in, perm, sizes):
    """Segment s up to its experts' hidden rows: (the tokens' rows in the
    sorted order, the hidden rows of each of ``w_in``), what the backward
    reads of a segment whose groups are ``sizes`` long."""
    with jax.named_scope("dispatch"):
        token_of = _assignments(perm, s, seg, tokens.shape[0] * k) // k
        rows = tokens.at[token_of].get(mode="promise_in_bounds")
    with jax.named_scope("experts"):
        return rows, tuple(lax.ragged_dot(rows, w, sizes) for w in w_in)


def _segment(s, seg, k, act, tokens, w, w_in, w2, perm, back, group_sizes):
    """Segment s from the tokens to the tokens' sum: ([n, D] float32, its
    rows' part of every token's weighted sum; what :func:`_hidden` gave)."""
    sizes = _segment_sizes(group_sizes, s, seg)
    kept = _hidden(s, seg, k, tokens, w_in, perm, sizes)
    with jax.named_scope("experts"):
        y = lax.ragged_dot(act(*kept[1]), w2, sizes)
    with jax.named_scope("combine"):
        part = _sum_over_choices(
            y, *_place(back, s, seg, jnp.sum(group_sizes)), w)
    return part, kept


def _segment_back(s, seg, k, act, kept, d_out, w_in_t, w2_t,
                  tokens, w, w_in, w2, perm, back, group_sizes):
    """Segment s's part of the cotangents of ``(tokens, w, w_in, w2)``,
    from its rows and hidden rows ``kept`` and the transposed stacks:
    the first two float32 and shaped like ``tokens`` and ``w``."""
    rows, hidden = kept
    held_rows = jnp.sum(group_sizes)
    sizes = _segment_sizes(group_sizes, s, seg)
    wide = jnp.promote_types(rows.dtype, jnp.float32)
    valid = (s * seg + jnp.arange(seg) < held_rows)[:, None]
    index, there = _place(back, s, seg, held_rows)
    with jax.named_scope("combine"):
        # the token's cotangent row: times the assignment's weight it is
        # the sorted row's cotangent
        a = _assignments(perm, s, seg, w.size)
        got = d_out.at[a // k].get(mode="promise_in_bounds")
        w_row = w.reshape(-1)[a][:, None]
    with jax.named_scope("experts"):
        gated, gate_back = jax.vjp(act, *hidden)
        # a row's weight is a scalar, so it commutes with the row's
        # product: the unweighted cotangent of the gated rows serves the
        # weight's own (below) and, times the weight, the way on
        d_gated = lax.ragged_dot(got, w2_t, sizes)
        d_hidden = gate_back(d_gated * w_row)
        d_w2 = _to_weights(got * w_row, gated, w2, sizes)
        d_w_in = tuple(_to_weights(d, rows, w_, sizes)
                       for d, w_ in zip(d_hidden, w_in))
        d_rows = sum(lax.ragged_dot(d, w_t, sizes)
                     for d, w_t in zip(d_hidden, w_in_t))
    with jax.named_scope("combine"):
        # along the row's values the weight's cotangent: y . got, which
        # is gated . (got W2^T) by group, without y
        d_w_row = jnp.sum(gated.astype(wide) * d_gated.astype(wide),
                          axis=-1, where=valid)
        d_w = jnp.where(there, d_w_row[index], 0)
    with jax.named_scope("dispatch"):
        # a token's cotangent is the sum over its k assignments' rows
        d_tokens = _sum_over_choices(d_rows, index, there)
    return d_tokens, d_w, d_w_in, d_w2


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def routed_sum(tokens, w, w_in, w2, perm, back, group_sizes, seg, k, act):
    """``sum_j w[n, j] * (act(x W_in[e]...) W2[e])`` with x = tokens[n]
    and e the held expert of assignment (n, j), over the assignments on
    held experts: [n, D] in the tokens' dtype, summed in float32 and
    rounded once.  ``w_in`` is (W1,) or (W1, W3), ``act`` their
    elementwise gate; ``perm`` (whole segments long) sorts the
    assignments by held expert, ``back`` [n, k] is its inverse and
    ``group_sizes`` the rows of each held expert."""
    return _routed_sum_fwd(tokens, w, w_in, w2, perm, back, group_sizes,
                           seg, k, act)[0]


def _later_segments(perm, group_sizes, seg, body, first):
    """``first`` plus ``body(s)`` over the segments s >= 1 that hold
    work, leaf by leaf."""
    if perm.shape[0] == seg:
        return first

    def add(s, total):
        return jax.tree_util.tree_map(
            lambda t, part: t + part.astype(t.dtype), total, body(s))

    return lax.fori_loop(
        1, segments_run(jnp.sum(group_sizes), seg), add, first)


def _routed_sum_fwd(tokens, w, w_in, w2, perm, back, group_sizes,
                    seg, k, act):
    args = (tokens, w, w_in, w2, perm, back, group_sizes)
    first, kept = _segment(0, seg, k, act, *args)
    total = _later_segments(
        perm, group_sizes, seg,
        lambda s: _segment(s, seg, k, act, *args)[0], first)
    return total.astype(tokens.dtype), args + (kept,)


def _routed_sum_bwd(seg, k, act, res, d_out):
    """Segment 0's cotangents from the rows and hidden rows it kept; a
    later segment's from its own computed again, added to them."""
    *args, kept = res
    tokens, w, w_in, w2, perm, _, group_sizes = args
    with jax.named_scope("experts"):
        turned = tuple(_transposed(w_) for w_ in w_in), _transposed(w2)

    def later(s):
        again = _hidden(s, seg, k, tokens, w_in, perm,
                        _segment_sizes(group_sizes, s, seg))
        return _segment_back(s, seg, k, act, again, d_out, *turned, *args)

    d_tokens, d_w, d_w_in, d_w2 = _later_segments(
        perm, group_sizes, seg, later,
        _segment_back(0, seg, k, act, kept, d_out, *turned, *args))
    return (d_tokens.astype(tokens.dtype), d_w.astype(w.dtype), d_w_in,
            d_w2, None, None, None)


routed_sum.defvjp(_routed_sum_fwd, _routed_sum_bwd)
