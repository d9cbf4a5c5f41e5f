#!/usr/bin/env python3
"""The yardstick of the attention core's mask rules: one core alone at
``[1, 32, 8192, 128]`` bfloat16, forward + ``jax.grad``, under the
block-diffusion rule (L 4,096, b 4) and under the causal rule over the
same 2L rows; host clock around a compiled call, median of 40.  Run by
hand through the chip tool:

    python3 benchmark/tests/yardstick_mask_rules.py
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.ops import mask_rules
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("yardstick: no TPU")
    B, H, L, D, b = 1, 32, 4096, 128, 4
    T = 2 * L
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.bfloat16)
               for _ in range(3))
    km = jnp.ones((B, T), jnp.bfloat16)
    out = {"shape": [B, H, T, D], "dtype": "bfloat16"}
    for name, rule in (("causal_2L", mask_rules.CAUSAL),
                       ("block_diffusion", mask_rules.BlockDiffusion(L, b)),
                       ("causal_2L_again", mask_rules.CAUSAL)):
        tile = pk._flash_block(T, rule)
        step = jax.jit(jax.value_and_grad(
            lambda q, k, v, rule=rule: jnp.sum(pk.flash_attention(
                q, k, v, km, rule).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2)))
        fwd = jax.jit(lambda q, k, v, rule=rule: pk.flash_attention(
            q, k, v, km, rule))
        for fn, what in ((step, "fwd_bwd_ms"), (fwd, "fwd_ms")):
            jax.block_until_ready(fn(q, k, v))
            times = []
            for _ in range(40):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(q, k, v))
                times.append((time.perf_counter() - t0) * 1e3)
            out.setdefault(name, {})[what] = statistics.median(times)
        out[name]["tile"] = tile
        out[name]["tiles"] = mask_rules.tile_counts(rule, T, tile)
    out["ratio_fwd_bwd"] = (out["block_diffusion"]["fwd_bwd_ms"]
                            / min(out["causal_2L"]["fwd_bwd_ms"],
                                  out["causal_2L_again"]["fwd_bwd_ms"]))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
