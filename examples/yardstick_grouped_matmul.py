"""The expert layers' grouped products alone: the gmm tier's kernels
(``dl4j_gmm``, ``dl4j_tgmm``) against XLA's ``lax.ragged_dot``.

Times each product a gated expert layer runs over one segment of 16,384
sorted rows at the three expert cells' shapes (LFM2-8B-A1B: 8 held
experts of 2,048 x 1,792, 13,000 held rows; SDAR-30B-A3B: 16 of 2,048 x
768 and Trinity-Mini: 16 of 2,048 x 1,024, 9,000 held rows each, split
among the groups by a seeded draw): the rows by a stack, the rows'
cotangent through the stack transposed, and the stack's gradient, with
bfloat16 rows and float32 stacks as the training step hands them over.
A reading is the median of ``--reps`` calls of one jitted program that
runs the product on ``--batch`` distinct inputs, each call waited for;
TFLOP/s counts the held rows only.

    python examples/yardstick_grouped_matmul.py                 # on the chip
    python examples/yardstick_grouped_matmul.py --tiny --reps 2 # a rehearsal

Lines of JSON go to stdout, and to the file ``--out`` names; a time from
a CPU says nothing about the chip.
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
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from deeplearning4j_tpu.ops import pallas_kernels as pk  # noqa: E402

#: groups, model width, expert width, held rows of a 16,384-row segment
SHAPES = {"lfm2": (8, 2048, 1792, 13000), "sdar": (16, 2048, 768, 9000),
          "trinity": (16, 2048, 1024, 9000)}
TINY = (4, 256, 128, 200)


def products(sizes, hidden):
    """{name: (kernel's, XLA's), each a function of (xs, w) with xs a
    [batch, rows, a] stack of inputs and w a float32 [groups, width,
    hidden]}."""
    def each(f):
        return lambda xs, w: [f(xs[i], w) for i in range(xs.shape[0])]

    bf = jnp.bfloat16
    return {
        # x [rows, width] by W1 [groups, width, hidden]
        "rows_by_stack": (each(lambda x, w: pk.gmm(x, w, sizes)),
                          each(lambda x, w: lax.ragged_dot(
                              x, w.astype(bf), sizes))),
        # the rows' cotangent [rows, hidden] through W1 transposed
        "rows_cotangent": (
            each(lambda x, w: pk.gmm(x, w, sizes, transpose_w=True)),
            each(lambda x, w: lax.ragged_dot(
                x, jnp.swapaxes(w, 1, 2).astype(bf), sizes))),
        # x [rows, width] against a cotangent [rows, hidden]: W1's gradient
        "stack_gradient": (
            each(lambda x, w: pk.tgmm(x, x[:, :hidden], sizes,
                                      out_dtype=jnp.float32)),
            each(lambda x, w: jax.linear_transpose(
                lambda w_: lax.ragged_dot(x, w_, sizes),
                w.astype(bf))(x[:, :hidden])[0].astype(jnp.float32))),
    }


def reading(f, xs, w, reps):
    step = jax.jit(f)
    jax.block_until_ready(step(xs, w))
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(step(xs, w))
        ms.append(1e3 * (time.perf_counter() - t0) / xs.shape[0])
    q1, q2, q3 = statistics.quantiles(ms, n=4)
    return {"median_ms": q2, "q1_ms": q1, "q3_ms": q3}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="lfm2,sdar,trinity")
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--tiny", action="store_true",
                    help="small widths and few rows: a rehearsal off the chip")
    ap.add_argument("--out", help="a file to append the lines to")
    args = ap.parse_args(argv)
    device = jax.devices()[0]
    rows = 256 if args.tiny else 16384
    lines = []
    for name in args.shapes.split(","):
        groups, width, hidden, held = TINY if args.tiny else SHAPES[name]
        rng = np.random.default_rng(groups + hidden)
        sizes = jnp.asarray(rng.multinomial(
            held, rng.dirichlet(np.full(groups, 4.0))), jnp.int32)
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (groups, width, hidden)) * 0.02
        inputs = {"rows_by_stack": width, "rows_cotangent": hidden,
                  "stack_gradient": width}
        for what, (kernel, xla) in products(sizes, hidden).items():
            xs = jax.random.normal(jax.random.fold_in(key, 1),
                                   (args.batch, rows, inputs[what]),
                                   jnp.bfloat16)
            for impl, f in (("kernel", kernel), ("ragged_dot", xla)):
                r = reading(f, xs, w, args.reps)
                line = json.dumps({
                    "shape": name, "product": what, "impl": impl,
                    "held_rows": held, **r,
                    "tflops": 2e-9 * held * width * hidden / r["median_ms"],
                    "device": device.device_kind,
                    "platform": device.platform})
                print(line, flush=True)
                lines.append(line)
    if args.out:
        with open(args.out, "a") as out:
            out.write("".join(line + "\n" for line in lines))


if __name__ == "__main__":
    main()
