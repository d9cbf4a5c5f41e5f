"""TPU capability detection + chip peak-FLOPs table.

Everything that keys behavior off "are we on TPU" (auto mixed precision
in ops/dtypes.py, Pallas interpret mode in ops/pallas_kernels.py) goes
through :func:`is_tpu`, which reads the *devices* JAX reports (platform
+ device_kind) and honors an explicit ``DL4J_TPU=0|1`` env override —
the hook the CPU tests use to steer code onto the chip's branches.
A backend that fails to initialise is an error here, never "not a TPU":
answering False would quietly turn a broken chip into f32 + interpret
mode.
"""

from __future__ import annotations

import functools
import os


def is_tpu() -> bool:
    """True when the default JAX backend is TPU hardware."""
    env = os.environ.get("DL4J_TPU")  # dl4j: noqa[DL4J103] env flag read at trace time by design (fixed per process)
    if env is not None and env != "":
        return env not in ("0", "false", "False")
    return _probe_is_tpu()


@functools.lru_cache(maxsize=1)
def _probe_is_tpu() -> bool:
    import jax
    # a backend init error propagates out of jax.devices()
    return any("tpu" in d.platform.lower() or "tpu" in d.device_kind.lower()
               for d in jax.devices())


def device_kind() -> str:
    """Device-kind string of the first device."""
    import jax
    return jax.devices()[0].device_kind


# Dense per-chip peak FLOP/s with bf16 inputs / f32 MXU accumulation
# (published cloud specs).  Keys are matched as substrings of the
# lower-cased device_kind.
_BF16_PEAK = {
    "v6": 918e12,       # Trillium / v6e
    "v5p": 459e12,
    "v5 lite": 197e12,  # v5e reports device_kind "TPU v5 lite"
    "v5e": 197e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
}


def peak_flops_bf16(kind: str | None = None) -> float | None:
    """Per-chip dense bf16 peak FLOP/s for MFU math; None when the chip
    is unknown (callers must then report MFU as unavailable rather than
    inventing a denominator)."""
    k = (kind if kind is not None else device_kind()).lower()
    # longest-key-first so "v5p"/"v5 lite" win over any shorter alias
    for name in sorted(_BF16_PEAK, key=len, reverse=True):
        if name in k:
            return _BF16_PEAK[name]
    return None
