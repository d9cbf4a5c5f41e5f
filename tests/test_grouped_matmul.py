"""The gmm tier's kernels (``dl4j_gmm``, ``dl4j_tgmm`` in
ops/pallas_kernels.py) against XLA's grouped product, ``lax.ragged_dot``,
and its transposes, on the CPU in Pallas interpret mode.  The expert
layer's ``routed_sum`` with the tier on and off is
tests/test_routed_sum_tiers.py.

Rows are sorted by group; the rows behind the last group belong to no
group and are filled here with NaN, which no result may read.  float32
operands agree to round-off (the kernels and XLA sum in other orders);
bfloat16 ones are compared with XLA's bfloat16 product in float32, to the
rounding of one bfloat16 output."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deeplearning4j_tpu.ops import helpers
from deeplearning4j_tpu.ops import pallas_kernels as pk

F32, BF16 = jnp.float32, jnp.bfloat16
TOL = {F32: 1e-5, BF16: 2e-2}     # of the largest value compared


def _close(got, want, dtype):
    got = np.asarray(jnp.asarray(got, F32), np.float64)
    want = np.asarray(jnp.asarray(want, F32), np.float64)
    assert got.shape == want.shape
    if not want.size:
        return
    scale = max(float(np.abs(want).max()), 1e-30)
    assert float(np.abs(got - want).max()) <= TOL[dtype] * scale, \
        (float(np.abs(got - want).max()), scale)


def _operands(m, k, n, sizes, dtype, w_dtype=None, seed=0):
    """Rows [m, k] with NaN behind the held ones, a stack [G, k, n], a
    cotangent [m, n] likewise, the sizes."""
    rng = np.random.default_rng(seed)
    held = int(sum(sizes))
    x = rng.normal(size=(m, k)).astype(np.float32)
    ct = rng.normal(size=(m, n)).astype(np.float32)
    x[held:] = np.nan
    ct[held:] = np.nan
    w = (rng.normal(size=(len(sizes), k, n)) / np.sqrt(k)).astype(np.float32)
    return (jnp.asarray(x, dtype), jnp.asarray(w, w_dtype or dtype),
            jnp.asarray(ct, dtype), jnp.asarray(sizes, jnp.int32), held)


# the three expert cells' (groups, model width, expert width), cut down
# with their ratios: 8 x 2048 x 1792, 16 x 2048 x 768, 16 x 2048 x 1024
CELLS = {"lfm2": (8, 256, 224), "sdar": (16, 256, 96), "trinity": (16, 256, 128)}

# 96 rows in 4 groups (one program each kernel serves every case): the
# row tile is 32
CASES = {
    "uneven": [7, 0, 50, 1],
    "leading_and_trailing_empty": [0, 0, 41, 0],
    "no_held_rows": [0, 0, 0, 0],
    "every_row_held": [16, 32, 16, 32],
    "one_group": [0, 0, 0, 33],
}
M = 96
_gmm = jax.jit(pk.gmm, static_argnames="transpose_w")
_tgmm = jax.jit(pk.tgmm, static_argnames="out_dtype")


def _ragged(x, w, sizes, held):
    """XLA's product over the held rows (NaN rows zeroed, never read)."""
    x = jnp.where(jnp.arange(x.shape[0])[:, None] < held, x, 0)
    return lax.ragged_dot(x.astype(F32), w.astype(F32), sizes)[:held]


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_gmm_agrees_with_ragged_dot(case, dtype):
    x, w, _, s, held = _operands(M, 32, 48, CASES[case], dtype)
    got = _gmm(x, w, s)
    assert got.shape == (M, 48) and got.dtype == dtype
    _close(got[:held], _ragged(x, w, s, held), dtype)


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_gmm_reads_the_stack_transposed(case, dtype):
    """x · w[g]ᵀ from the stack as it is: the rows' cotangent."""
    _, w, ct, s, held = _operands(M, 32, 48, CASES[case], dtype)
    got = _gmm(ct, w, s, transpose_w=True)
    assert got.shape == (M, 32)
    _close(got[:held], _ragged(ct, jnp.swapaxes(w, 1, 2), s, held), dtype)


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_tgmm_is_the_transpose_of_ragged_dot(case, dtype):
    """Each group's x[rows]ᵀ · ct[rows]; an empty group gives zeros, and
    the NaN rows behind the last group reach no sum."""
    x, w, ct, s, held = _operands(M, 32, 48, CASES[case], dtype)
    got = _tgmm(x, ct, s, out_dtype=F32)
    assert got.shape == w.shape and got.dtype == F32
    live = (jnp.arange(M) < held)[:, None]
    want = jax.linear_transpose(
        lambda w_: lax.ragged_dot(jnp.where(live, x, 0).astype(F32), w_, s),
        w.astype(F32))(jnp.where(live, ct, 0).astype(F32))[0]
    _close(got, want, dtype)
    for g in np.flatnonzero(np.asarray(s) == 0):
        assert not np.asarray(got[g]).any()


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_cells_widths_cut_down(cell):
    """The three cells' widths at their ratios, bfloat16 rows by float32
    stacks (the masters, cast a block at a time in the kernel), both
    reads and the weights' gradient: the tiles the rule picks at these
    widths take a block of 128 lanes or the whole width."""
    G, D, H = CELLS[cell]
    sizes = np.random.default_rng(G + H).multinomial(
        200, np.full(G, 1.0 / G))
    sizes[1] = 0
    x, w, ct, s, held = _operands(256, D, H, sizes, BF16, w_dtype=F32, seed=1)
    w_bf = w.astype(BF16)
    _close(_gmm(x, w, s)[:held], _ragged(x, w_bf, s, held), BF16)
    _close(_gmm(ct, w, s, transpose_w=True)[:held],
           _ragged(ct, jnp.swapaxes(w_bf, 1, 2), s, held), BF16)
    live = (jnp.arange(256) < held)[:, None]
    want = jax.linear_transpose(
        lambda w_: lax.ragged_dot(jnp.where(live, x, 0).astype(F32), w_, s),
        w)(jnp.where(live, ct, 0).astype(F32))[0]
    _close(_tgmm(x, ct, s, out_dtype=F32), want, BF16)


@pytest.mark.parametrize("m,groups,want", [
    (16384, 8, 256), (16384, 16, 128), (65536, 16, 512), (96, 5, 32),
    (40, 1, 8), (12, 3, 12)])
def test_the_row_tile_follows_the_segment(m, groups, want):
    """The rows a step multiplies: the largest power of two up to 512
    whose boundary tiles (one a group) stay within an eighth of the
    segment, and that divides it; a segment too short is one tile."""
    assert pk._row_tile(m, groups) == want


@pytest.mark.parametrize("sizes,tm,want", [
    # (group, row tile) of each step, in order; an empty group has one
    ([7, 0, 30, 19, 1], 16,
     [(0, 0), (1, 0), (2, 0), (2, 1), (2, 2), (3, 2), (3, 3), (4, 3)]),
    ([0, 0, 0], 16, [(0, 0), (1, 0), (2, 0)]),
    ([16, 32, 16], 16, [(0, 0), (1, 1), (1, 2), (2, 3)]),
    ([64, 0], 16, [(0, 0), (0, 1), (0, 2), (0, 3), (1, 3)]),
    ([3, 5], 32, [(0, 0), (1, 0)]),
])
def test_the_plan_visits_each_groups_tiles_in_order(sizes, tm, want):
    offsets, group_of, tile_of, steps = pk._tile_plan(
        jnp.asarray(sizes, jnp.int32), 64, tm)
    n = int(steps)
    assert list(zip(np.asarray(group_of)[:n].tolist(),
                    np.asarray(tile_of)[:n].tolist())) == want
    assert group_of.shape == (64 // tm + len(sizes) - 1,)
    assert np.asarray(offsets).tolist() == [0] + np.cumsum(sizes).tolist()


def test_the_support_gate():
    assert pk.gmm_supported(16384, 2048, 1792, 8, BF16, F32)
    assert pk.gmm_supported(16384, 2048, 768, 16, BF16)
    assert not pk.gmm_supported(16384, 2048, 768, 16, jnp.float16)
    assert not pk.gmm_supported(100, 32, 48, 4, F32)        # rows not in 8s
    # a width no multiple of 128 is one block whole: too wide for VMEM
    assert not pk.gmm_supported(16384, 20000, 9000, 8, F32)


def test_the_tier_is_a_helper_with_a_self_test():
    assert "gmm" in helpers.OPS and "gmm" in pk.ALL_TIERS
    helpers.helper_for("gmm").self_test()
