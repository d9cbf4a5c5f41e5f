"""Pipeline parallelism — GPipe-style stage partitioning over a mesh
axis (scaling-book pipelining recipe; no reference analog — DL4J's
distribution tiers are data-parallel only, SURVEY.md §2.4-2.6 — this is
part of the TPU-native multi-chip story alongside dp/fsdp/tp/sp/ep).

The model is a stack of S *identical* blocks (the practical pipeline
case: repeated transformer/dense blocks).  Block parameters are stacked
on a leading stage dimension and sharded over the pipeline axis, so each
device holds exactly its stage's weights.  The schedule runs
``M + S - 1`` ticks; each tick every stage applies its block to its
current microbatch and ``lax.ppermute``s the activation to the next
stage (neighbor transfer → rides ICI).  Outputs are collected on the
last stage and broadcast with a ``psum``.  Bubble fraction is
``(S-1)/(M+S-1)`` — raise the microbatch count M to amortize.

Everything is differentiable (scan + ppermute + psum), so ``jax.grad``
through ``pipeline_apply`` gives pipeline-parallel training for free.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

PIPELINE_AXIS = "model"  # default: reuse the mesh's 'model' axis for stages


def stack_block_params(params_list):
    """[per-stage pytree, ...] → stacked pytree with leading stage dim
    (shard this dim over the pipeline axis)."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *params_list)


def _pipeline_sharded(params, xs, *, block_fn, axis: str, n_stages: int):
    """Per-shard body.  params: this stage's block params (leading stage
    dim of size 1, squeezed); xs: full microbatch stack [M, mb, ...]
    (replicated — only stage 0 reads it)."""
    params = jax.tree_util.tree_map(lambda a: a[0], params)
    idx = lax.axis_index(axis)
    S = n_stages
    M = xs.shape[0]
    mb_shape = xs.shape[1:]

    # one extra row absorbs not-yet-valid writes (t < S-1 → slot M)
    outs0 = jnp.zeros((M + 1,) + mb_shape, xs.dtype)
    buf0 = jnp.zeros(mb_shape, xs.dtype)
    perm = [(i, (i + 1) % S) for i in range(S)]

    def tick(carry, t):
        buf, outs = carry
        feed = jnp.where(t < M, t, 0)
        inp = jnp.where(idx == 0, xs[feed], buf)
        y = block_fn(params, inp)
        out_slot = jnp.where(t >= S - 1, t - (S - 1), M)
        outs = lax.dynamic_update_index_in_dim(
            outs, jnp.where(idx == S - 1, y, jnp.zeros_like(y)),
            out_slot, axis=0)
        buf = lax.ppermute(y, axis, perm)
        return (buf, outs), None

    (_, outs), _ = lax.scan(tick, (buf0, outs0), jnp.arange(M + S - 1))
    # last stage holds the real outputs; everyone else contributed zeros
    return lax.psum(outs[:M], axis)


def pipeline_apply(block_fn: Callable, stacked_params, microbatches,
                   *, mesh: Mesh, axis: str = PIPELINE_AXIS):
    """Run the pipeline.  stacked_params: pytree with leading stage dim
    S == mesh.shape[axis]; microbatches: [M, mb, ...] array."""
    S = int(mesh.shape[axis])
    leading = {a.shape[0] for a in jax.tree_util.tree_leaves(stacked_params)}
    if leading != {S}:
        raise ValueError(
            f"stacked params leading dim {leading} != pipeline axis size {S}")
    param_specs = jax.tree_util.tree_map(
        lambda a: P(axis, *([None] * (a.ndim - 1))), stacked_params)
    fn = shard_map(
        partial(_pipeline_sharded, block_fn=block_fn, axis=axis,
                n_stages=S),
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        check_vma=False)
    return fn(stacked_params, microbatches)


def pipeline_loss_fn(block_fn: Callable, loss_fn: Callable, *, mesh: Mesh,
                     axis: str = PIPELINE_AXIS):
    """Convenience: (stacked_params, microbatches, labels) → scalar loss
    through the pipeline — differentiate with jax.grad for
    pipeline-parallel training."""

    def f(stacked_params, microbatches, labels):
        outs = pipeline_apply(block_fn, stacked_params, microbatches,
                              mesh=mesh, axis=axis)
        return loss_fn(outs, labels)

    return f
