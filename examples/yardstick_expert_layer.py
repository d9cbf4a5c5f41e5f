"""One expert layer alone, forward and ``jax.grad``: the yardstick of
``MixtureOfExpertsLayer(top_k=k, experts_held=...)``.

Times one layer at the two shapes the benchmark's expert cells run
(SDAR-30B-A3B: 8,192 tokens of 2,048, softmax top-8 of 128, 16 held,
hidden 768, with and without ``recompute``; LFM2-8B-A1B: 8,192 tokens,
sigmoid top-4 of 32 with a bias, 8 held, hidden 1,792), each at an even
load, at the load its cell measures and at a load that runs two of the
layer's row segments.  The load is steered through the router alone: the
tokens' first feature is 1 and the router's first row lifts the held
experts' logits by a boost found by bisection on the layer's own counts,
so nothing of the layer is bypassed.  A reading is the median of
``--reps`` calls of one jitted ``value_and_grad`` (every leaf and the
tokens), each waited for.

It uses the layer's public surface only, so the same file times any
tree: copy it into a checkout of the parent and run it there.

    python examples/yardstick_expert_layer.py                 # on the chip
    python examples/yardstick_expert_layer.py --tiny --reps 3 # a rehearsal

Lines of JSON go to stdout and to ``--out``; a time from a CPU says
nothing about the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deeplearning4j_tpu.nn.conf import layers as L  # noqa: E402
from deeplearning4j_tpu.nn.conf.inputs import InputType  # noqa: E402

#: tokens, width, experts, held, top k, hidden, scoring, bias, the held
#: rows a layer of the cell reads at its fullest (``PERF.md`` 4)
SHAPES = {
    "sdar": dict(tokens=8192, width=2048, experts=128, held=16, k=8,
                 hidden=768, scoring="softmax", bias=False, cell_rows=9800),
    "lfm2": dict(tokens=8192, width=2048, experts=32, held=8, k=4,
                 hidden=1792, scoring="sigmoid", bias=True, cell_rows=13700),
}
TINY = dict(tokens=256, width=128, hidden=64)


def build(shape, recompute, dtype, key):
    layer = L.MixtureOfExpertsLayer(
        n_out=shape["width"], n_experts=shape["experts"],
        hidden=shape["hidden"], top_k=shape["k"], scoring=shape["scoring"],
        expert_bias=shape["bias"], gated=True, residual=False,
        activation="identity", experts_held=tuple(range(shape["held"])),
        recompute=recompute)
    kp, kx = jax.random.split(key)
    params, state, _ = layer.initialize(
        kp, InputType.recurrent(shape["width"], shape["tokens"]), dtype)
    if shape["bias"]:
        state["expert_bias"] = jnp.zeros_like(state["expert_bias"])
    x = jax.random.normal(kx, (1, shape["tokens"], shape["width"]), dtype)
    return layer, params, state, x.at[:, :, 0].set(1.0)


def steered(params, held, boost):
    """The router with the held experts' logits lifted by ``boost`` on
    every token (whose first feature is 1)."""
    row = jnp.zeros((params["Wg"].shape[1],), jnp.float32).at[:held].set(boost)
    return {**params, "Wg": params["Wg"].at[0].set(
        row.astype(params["Wg"].dtype))}


def boost_for(layer, params, state, x, held, want):
    """The boost at which the layer counts about ``want`` rows on its held
    experts, by bisection on the layer's own counts; and that count."""
    @jax.jit
    def held_rows(boost):
        _, st, _ = layer.forward(steered(params, held, boost), state, x,
                                 train=False, rng=None)
        return jnp.sum(st["moe_expert_counts"][:held])

    lo, hi = -8.0, 16.0
    for _ in range(24):
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if int(held_rows(mid)) < want else (lo, mid)
    return hi, int(held_rows(hi))


def reading(layer, params, state, x, reps):
    cot = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(
        x.shape).astype(x.dtype)

    @jax.jit
    def step(params, x):
        def loss(params, x):
            y, st, _ = layer.forward(params, state, x, train=True, rng=None)
            return jnp.sum((y * cot).astype(jnp.float32)), st
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            params, x)

    (_, st), _ = jax.block_until_ready(step(params, x))
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(step(params, x))
        ms.append(1e3 * (time.perf_counter() - t0))
    q1, q2, q3 = statistics.quantiles(ms, n=4)
    return {"median_ms": q2, "q1_ms": q1, "q3_ms": q3,
            "segments": [int(v) for v in st["moe_row_segments"]]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="sdar,lfm2")
    ap.add_argument("--reps", type=int, default=40)
    ap.add_argument("--tiny", action="store_true",
                    help="small widths and few tokens: a rehearsal off the chip")
    ap.add_argument("--out", default="chiprun_out/yardstick_expert_layer.jsonl")
    ap.add_argument("--tag", default="", help="copied into every line")
    args = ap.parse_args(argv)
    device = jax.devices()[0]
    dtype = jnp.bfloat16 if device.platform == "tpu" else jnp.float32
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as out:
        for name in args.shapes.split(","):
            shape = dict(SHAPES[name], **(TINY if args.tiny else {}))
            rows = shape["tokens"] * shape["k"]
            share = rows * shape["held"] // shape["experts"]
            loads = {"even": share,
                     "cell": share * shape["cell_rows"] // 8192,
                     "two-segments": 3 * share}
            for recompute in ((False, True) if name == "sdar" else (False,)):
                layer, params, state, x = build(
                    shape, recompute, dtype, jax.random.PRNGKey(0))
                for load, want in loads.items():
                    boost, rows_held = boost_for(
                        layer, params, state, x, shape["held"], want)
                    line = {
                        "tag": args.tag, "shape": name, "load": load,
                        "recompute": recompute, "held_rows": rows_held,
                        "segment_shape": list(layer.segment_shape(rows)),
                        **reading(layer, steered(params, shape["held"], boost),
                                  state, x, args.reps),
                        "reps": args.reps, "device": device.device_kind,
                        "platform": device.platform}
                    print(json.dumps(line), flush=True)
                    out.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
