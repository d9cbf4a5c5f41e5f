"""What a recomputed run keeps beside its inputs.

``jax.checkpoint`` with its default policy keeps a run's inputs and
runs everything inside again in the backward pass.  Some values cost
far more to compute again than to hold: a layer *offers* such a value
(:func:`offer`), and a run wrapped by :func:`keeping_offers` keeps it
wherever its backward pass reads it.  An offer nobody reads costs
nothing (the value is pruned with whatever made it), and outside a
``jax.checkpoint`` an offer is the identity and lowers to no
operation, so a layer offers without knowing who runs it.

Two values are offered today: the flash attention core's output with
its row statistics (``ops/pallas_kernels.py``), and a gated MLP's
output (``GatedDenseLayer``).  ``LoopVertex`` is the one taker.
"""

from __future__ import annotations

import jax
from jax.ad_checkpoint import checkpoint_name

#: the one name every offer carries
OFFERED = "dl4j_offered"


def offer(x):
    """``x``, marked as worth keeping to a recomputed run that reads it
    in its backward pass."""
    return checkpoint_name(x, OFFERED)


def keeping_offers(fn):
    """``jax.checkpoint(fn)`` that keeps, beside ``fn``'s inputs, the
    offered values its backward pass reads."""
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(OFFERED))
