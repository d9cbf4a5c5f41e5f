"""Flat parameter view adapter.

The reference stores ALL params as one 1xN row vector with per-layer
views into it (ref: nn/api/Model.java:128 setParamsViewArray,
MultiLayerNetwork.java:102 flattenedParams).  The native representation
here is a pytree (list of per-layer dicts), but checkpoints, parameter
averaging compat, and `params()`/`setParams()` parity need a canonical
flattening order.  Order: layer index ascending, then within a layer the
canonical key order below (W before b, matching
DefaultParamInitializer / GravesLSTMParamInitializer orderings).
"""

from __future__ import annotations

from typing import List

import jax.numpy as jnp
import numpy as np

# Canonical within-layer ordering; unknown keys go last, alphabetically.
PARAM_ORDER = ["W", "RW", "b", "pI", "pF", "pO", "gamma", "beta",
               "f_W", "f_RW", "f_b", "f_pI", "f_pF", "f_pO",
               "b_W", "b_RW", "b_b", "b_pI", "b_pF", "b_pO"]


def ordered_keys(layer_params: dict) -> List[str]:
    known = [k for k in PARAM_ORDER if k in layer_params]
    rest = sorted(k for k in layer_params if k not in PARAM_ORDER)
    return known + rest


def num_params(params: List[dict]) -> int:
    return sum(int(np.prod(v.shape)) for lp in params for v in lp.values())


def flatten(params: List[dict]) -> jnp.ndarray:
    """→ 1-D flat vector in canonical order (the reference's params()).

    Concrete arrays are gathered on the HOST: the leaves of an
    FSDP-trained model carry heterogeneous NamedShardings, and op-by-op
    ``jnp.concatenate`` over mixed committed shardings miscomputes on
    multi-axis meshes (observed on jax 0.4.37, CPU 2x4 data×fsdp mesh —
    values silently wrong, not an error; a 20-trial re-check on jax
    0.9.0 did not reproduce it, which does not prove it gone).
    Per-leaf ``np.asarray`` is
    the always-correct gather, and the flat vector is the portable
    cross-mesh checkpoint format anyway (parallel/fsdp.py).  Under a
    jit trace (the line-search solvers flatten inside their value-and-
    grad closures) leaves are tracers — there the compiled concatenate
    is both required and correct."""
    import jax
    leaves = [lp[k] for lp in params for k in ordered_keys(lp)]
    if not leaves:
        return jnp.zeros((0,), jnp.float32)
    if any(isinstance(l, jax.core.Tracer) for l in leaves):
        return jnp.concatenate([jnp.ravel(l) for l in leaves])
    return jnp.asarray(np.concatenate(
        [np.ravel(np.asarray(l)) for l in leaves]))  # dl4j: noqa[DL4J102] tracer-guarded host gather — the traced branch above uses jnp


def unflatten(flat, template: List[dict]) -> List[dict]:
    """Inverse of flatten, shaped like `template` (the reference's setParams())."""
    out = []
    off = 0
    flat = jnp.asarray(flat).reshape(-1)
    for lp in template:
        new = {}
        for k in ordered_keys(lp):
            n = int(np.prod(lp[k].shape))
            new[k] = flat[off:off + n].reshape(lp[k].shape).astype(lp[k].dtype)
            off += n
        out.append(new)
    if off != flat.shape[0]:
        raise ValueError(f"Param count mismatch: template {off} vs flat {flat.shape[0]}")
    return out
