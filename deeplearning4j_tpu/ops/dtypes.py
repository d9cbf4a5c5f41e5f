"""Precision policy for TPU execution.

The reference runs float32 (or double for gradient checks,
ref: gradientcheck/GradientCheckUtil.java:87-92).  On TPU the idiomatic
policy is: parameters and activations bfloat16-capable with float32
accumulation on the MXU (``preferred_element_type``), float32 master
params/updater state, and float64 only on the CPU backend for gradient
checks.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Policy:
    """Mixed-precision policy applied by the training engine.

    ``cast_to_compute`` only downcasts float32 leaves: float64 (gradient
    checks) and integer leaves (embedding indices) pass through untouched,
    so the same jitted step serves f64-on-CPU numeric checks unchanged.
    """

    param_dtype: jnp.dtype = jnp.float32   # master copy of params
    compute_dtype: jnp.dtype = jnp.float32  # activations / matmul inputs
    accum_dtype: jnp.dtype = jnp.float32    # MXU accumulation / reductions

    @property
    def is_mixed(self) -> bool:
        return self.compute_dtype != self.param_dtype

    def cast_to_compute(self, tree):
        import jax
        if not self.is_mixed:
            return tree

        def cast(x):
            if hasattr(x, "dtype") and x.dtype == jnp.float32:
                return x.astype(self.compute_dtype)
            return x

        return jax.tree_util.tree_map(cast, tree)

    def cast_params_to_compute(self, params, keep):
        """:meth:`cast_to_compute` of a network's parameters (a dict or a
        list of per-layer dicts of leaves) but for the leaves ``keep``
        names ({key or position: leaf names}, ``Layer.master_leaves``):
        those reach their layer in the master dtype."""
        if not keep or not self.is_mixed:
            return self.cast_to_compute(params)

        def layer(key, leaves):
            kept = keep.get(key, ())
            return {k: v if k in kept else self.cast_to_compute(v)
                    for k, v in leaves.items()}
        if isinstance(params, dict):
            return {key: layer(key, v) for key, v in params.items()}
        return type(params)(layer(i, v) for i, v in enumerate(params))

    def cast_to_param(self, tree):
        """Upcast compute-dtype leaves back to the master dtype (carried
        state: BN running stats, RNN carries, MoE aux loss)."""
        import jax
        if not self.is_mixed:
            return tree

        def cast(x):
            if hasattr(x, "dtype") and x.dtype == self.compute_dtype:
                return x.astype(self.param_dtype)
            return x

        return jax.tree_util.tree_map(cast, tree)

    def cast_to_accum(self, x):
        if hasattr(x, "dtype") and x.dtype != self.accum_dtype \
                and jnp.issubdtype(x.dtype, jnp.floating) \
                and jnp.finfo(x.dtype).bits <= jnp.finfo(self.accum_dtype).bits:
            return x.astype(self.accum_dtype)
        return x


FLOAT32 = Policy()
# bfloat16 compute with f32 accumulation: the TPU-native fast path.
BF16 = Policy(param_dtype=jnp.float32, compute_dtype=jnp.bfloat16, accum_dtype=jnp.float32)
# float64 compute over f32 master storage: numeric-check precision,
# CPU backend only (TPU f64 is emulated).
FLOAT64 = Policy(param_dtype=jnp.float32, compute_dtype=jnp.float64, accum_dtype=jnp.float64)

_NAMED = {
    "float32": FLOAT32, "f32": FLOAT32, "fp32": FLOAT32, "float": FLOAT32,
    "bfloat16": BF16, "bf16": BF16, "mixed_bfloat16": BF16,
    # TPU has no fp16 compute path — 'half' maps to bf16 (same width,
    # wider exponent; the MXU-native low-precision format).
    "half": BF16, "float16": BF16, "f16": BF16,
    "float64": FLOAT64, "f64": FLOAT64, "double": FLOAT64,
}


def accum_dtype_for(dtype):
    """Output/accumulation dtype for a matmul/conv with inputs of `dtype`.

    bf16 inputs keep a bf16 result dtype: the TPU MXU accumulates bf16
    contractions in f32 internally, and widening the result via
    ``preferred_element_type`` breaks conv/dot transpose (VJP) rules'
    operand-dtype agreement (f32 cotangent × bf16 operand).  Wider floats
    (f32, f64 gradient checks) accumulate at their own width.
    """
    if dtype == jnp.bfloat16:
        return dtype
    return jnp.promote_types(dtype, jnp.float32)

# None = auto: bf16 compute on TPU (the MXU's native fast path), f32 elsewhere.
_default_policy: Policy | None = None


def set_default_policy(policy: Policy | None) -> None:
    """Override the ambient policy (None restores backend-auto selection)."""
    global _default_policy
    _default_policy = policy


def default_policy() -> Policy:
    if _default_policy is not None:
        return _default_policy
    from deeplearning4j_tpu.ops import platform
    return BF16 if platform.is_tpu() else FLOAT32


def resolve(name: str | None) -> Policy:
    """Map a config string ('float32' | 'bfloat16' | 'float64' | None=auto)
    to a Policy.  The engine calls this at trace-build time."""
    if name is None or name == "auto":
        policy = default_policy()
    else:
        try:
            policy = _NAMED[name.lower()]
        except KeyError:
            raise ValueError(f"Unknown precision '{name}'. "
                             f"Known: {sorted(_NAMED)} or 'auto'") from None
    if policy.is_mixed:
        # the bf16_train precision tier gates HERE, the single boundary
        # every engine resolves policies through: DL4J_PRECISION=0 /
        # DL4J_PRECISION_BF16=0 force the f32 path byte-identically to
        # an untiered conf (explicit bf16 AND the TPU auto default)
        from deeplearning4j_tpu.ops import helpers as _prec_helpers
        if not _prec_helpers.precision_enabled("bf16_train", True):
            return FLOAT32
    return policy
