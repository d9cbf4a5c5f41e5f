"""Attention mask rules: which (query, key) pairs an attention core lets
live, fixed at trace time.

A rule is a frozen value (hashable: it rides a ``custom_vjp`` as a
static argument) that answers three questions, each from the same
definition, so that the dense core, the flash kernels and the tile
counter cannot drift apart:

* ``live(q_pos, k_pos)``: the pairs, elementwise over broadcastable
  int32 positions.  ``parallel.sequence.dense_attention`` masks its
  scores with it; the tests hold the kernels to it.
* ``q_visits(qi, n, block)`` / ``k_visits(ki, n, block)``: the key tiles
  a q block visits (the q tiles that visit a key tile), as an ordered
  tuple of steps the flash kernels run as they stand:
  ``("range", lo, hi)`` for whole tiles (every pair live, no comparison)
  and ``("tile", index, live_in_tile, trips)`` for a boundary tile, whose
  pairs are compared inside the tile by ``live_in_tile(q_local,
  k_local)`` over positions counted from the tile's corner; ``trips`` is
  None (once) or a 0/1 count the device reads.  ``qi``/``ki`` are Python
  ints (the counter) or traced scalars (a kernel's ``program_id``): the
  plans are written in arithmetic both understand.  No other tile is
  touched.
* ``positions(T)``: the position of each of the T rows, for the rotary
  embedding.

``causal`` is one rule (``CAUSAL``) and block diffusion another
(``BlockDiffusion(seq_len, block_length)``).  Sliding windows and
segment ids are rules still to be written.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
from jax import lax

LANE = 128


def _floordiv(x, b: int):
    """``x // b`` of non-negative int32 positions, as a shift where b is
    a power of two."""
    if b == 1:
        return x
    if b & (b - 1) == 0:
        return lax.shift_right_logical(
            x, jnp.asarray(b.bit_length() - 1, x.dtype))
    return lax.div(x, jnp.asarray(b, x.dtype))


@dataclasses.dataclass(frozen=True)
class Causal:
    """Row i sees columns 0..i."""

    def check(self, T: int) -> None:
        pass

    def positions(self, T: int):
        return jnp.arange(T)

    def live(self, q_pos, k_pos):
        return q_pos >= k_pos

    # -- tiles ----------------------------------------------------------
    def tile_span(self, T: int) -> int:
        return T

    def tile_ok(self, block: int) -> bool:
        return True

    def q_visits(self, qi, n: int, block: int):
        # tiles before the diagonal lie wholly below it; tile qi crosses it
        return (("range", 0, qi), ("tile", qi, self.live, None))

    def k_visits(self, ki, n: int, block: int):
        # q tiles before this key tile see none of it
        return (("tile", ki, self.live, None), ("range", ki + 1, n))


CAUSAL = Causal()


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """Block diffusion over ``[x_t ; x0]`` (arXiv:2503.09573): 2L rows, a
    noised copy then a clean copy of one sequence of L tokens, both at
    positions 0..L-1, in blocks of b consecutive tokens.  With j the
    block of a row or column inside its half:

        noisy row  sees noisy columns with j_c == j_i
                   and  clean columns with j_c <  j_i
        clean row  sees clean columns with j_c <= j_i

    L^2 + L b live pairs of the (2L)^2.  Tiles: with L a multiple of the
    tile and b a divisor of it (``check``/``tile_ok`` refuse anything
    else), a noisy q block i of h = L / tile visits its own tile
    (block-diagonal inside), the whole clean tiles h..h+i-1 and the
    boundary tile h+i (strictly earlier blocks); a clean q block h+i
    visits the whole clean tiles h..h+i-1 and the boundary tile h+i
    (its own block and earlier)."""

    seq_len: int
    block_length: int

    def __post_init__(self):
        L, b = self.seq_len, self.block_length
        if L < 1 or b < 1 or L % b:
            raise ValueError(f"block diffusion: sequence length {L} is not "
                             f"a whole number of blocks of {b}")

    def check(self, T: int) -> None:
        if T != 2 * self.seq_len:
            raise ValueError(
                f"block diffusion over sequences of {self.seq_len} runs on "
                f"2 x {self.seq_len} rows (noised copy, then clean copy); "
                f"got {T}")

    def positions(self, T: int):
        self.check(T)
        return jnp.arange(T) % self.seq_len

    def live(self, q_pos, k_pos):
        L, b = self.seq_len, self.block_length
        q_noisy, k_noisy = q_pos < L, k_pos < L
        jq = _floordiv(jnp.where(q_noisy, q_pos, q_pos - L), b)
        jk = _floordiv(jnp.where(k_noisy, k_pos, k_pos - L), b)
        return jnp.where(
            q_noisy,
            jnp.where(k_noisy, jk == jq, jk < jq),
            jnp.logical_and(jnp.logical_not(k_noisy), jk <= jq))

    # -- tiles ----------------------------------------------------------
    def tile_span(self, T: int) -> int:
        self.check(T)
        return self.seq_len     # the halves meet on a tile boundary

    def tile_ok(self, block: int) -> bool:
        return block % self.block_length == 0

    def _band(self, lowest, highest=None):
        """Pairs inside a tile whose block distance d = j_q - j_k (both
        counted from the tile's corner, which is a block's corner) lies
        in [lowest, highest]; the bounds may be scalars the device
        reads."""
        b = self.block_length

        def live_in_tile(q_local, k_local):
            d = _floordiv(q_local, b) - _floordiv(k_local, b)
            ok = d >= lowest
            return ok if highest is None \
                else jnp.logical_and(ok, d <= highest)
        return live_in_tile

    def q_visits(self, qi, n: int, block: int):
        h = n // 2
        clean = (qi >= h) * 1           # 0: a q block of the noisy half
        i = qi - clean * h
        return (("range", h, h + i),
                # the clean copy of its own tile: blocks strictly before
                # a noisy row's own, up to and with a clean row's own
                ("tile", h + i, self._band(1 - clean), None),
                # a noisy q block's own tile: its own block alone
                ("tile", qi, self._band(0, 0), 1 - clean))

    def k_visits(self, ki, n: int, block: int):
        h = n // 2
        clean = (ki >= h) * 1           # 0: a key tile of the noisy half
        j = ki - clean * h
        far = block                     # no block distance reaches a tile's width
        return (
            # noisy q block j: a noisy key tile is its own (d == 0), a
            # clean one its boundary (d >= 1)
            ("tile", j, self._band(clean, clean * far), None),
            # clean q block h+j, of a clean key tile alone
            ("tile", h + j, self._band(0), clean),
            # q blocks after j, of either half, see a clean key tile whole
            ("range", j + 1 + (1 - clean) * h, h),
            ("range", h + j + 1 + (1 - clean) * n, n))


def resolve(causal) -> Optional[object]:
    """The rule a layer's or a core's ``causal`` argument names: False or
    None (every pair lives, no rule), True (``CAUSAL``), a rule, or
    ``("block_diffusion", seq_len, block_length)`` as a configuration
    serialises one."""
    if causal is None or causal is False:
        return None
    if causal is True:
        return CAUSAL
    if isinstance(causal, (Causal, BlockDiffusion)):
        return causal
    if isinstance(causal, (list, tuple)) and len(causal) == 3 \
            and causal[0] == "block_diffusion":
        return BlockDiffusion(int(causal[1]), int(causal[2]))  # dl4j: noqa[DL4J101] a configuration's own numbers, never tracers
    raise ValueError(f"unknown attention mask rule {causal!r} (False | True "
                     "| ('block_diffusion', seq_len, block_length))")


def tile_for(rule, T: int, cap: int) -> int:
    """Rows of q a flash program holds and keys a tile holds, for T rows
    (a multiple of 128) under ``rule``: the largest multiple of 128 that
    is at most ``cap``, divides the span the rule's tiles must not
    straddle (T itself without a rule) and that the rule accepts."""
    span = T if rule is None else rule.tile_span(T)
    fits = [b for b in range(LANE, min(cap, span) + 1, LANE)
            if span % b == 0 and (rule is None or rule.tile_ok(b))]
    if not fits:
        raise ValueError(
            f"attention over {T} rows under {rule!r}: no tile of a multiple "
            f"of {LANE} rows (at most {cap}) divides {span} and holds whole "
            "blocks; the halves must meet on a tile boundary and a block "
            "must divide the tile")
    return max(fits)


def tile_counts(rule, T: int, block: int) -> dict:
    """{"visited", "boundary", "skipped"}: of the (T / block)^2 tiles a
    head has, those a flash kernel visits whole (no comparison), those
    it visits with the comparison inside, and those it never touches;
    from the same plan the kernels run."""
    n = T // block
    if rule is None:
        return {"visited": n * n, "boundary": 0, "skipped": 0}
    whole = boundary = 0
    for qi in range(n):
        for step in rule.q_visits(qi, n, block):
            if step[0] == "range":
                whole += max(0, step[2] - step[1])
            else:
                boundary += 1 if step[3] is None else int(step[3])  # dl4j: noqa[DL4J101] the plan over Python ints: the counter, not a kernel
    return {"visited": whole, "boundary": boundary,
            "skipped": n * n - whole - boundary}
