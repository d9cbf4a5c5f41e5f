"""TPU capability detection.

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
