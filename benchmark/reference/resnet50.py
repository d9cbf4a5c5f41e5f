"""ResNet-50 of He et al. 2015 (table 1, 50-layer), version 1 with the
stride on the first 1x1 of a stage's first block, as a plain reference:
7x7/2 stem, 3x3/2 max pool, bottleneck stages 3-4-6-3 of widths 64 to
512 (outputs four times as wide), every convolution followed by batch
normalisation (batch statistics, eps 1e-5), ReLU after the first two of
a block and after the residual add, a projection shortcut in each
stage's first block, global average pool and a softmax classifier under
cross-entropy.  Parameters are a dict by the vertex names the program's
graph engine uses.  Each block is under ``jax.checkpoint`` so that float32
at the whole batch fits the chip: batch normalisation mixes rows, so the
rows cannot be taken in blocks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.harness import flops
from benchmark.reference import common as C

ROW_BLOCKS = 1
EPS = 1e-5


def _convs(cfg):
    """[(name, cin, cout, k, stride, pad, input size)] in forward order."""
    size = cfg["image_size"]
    out = [("stem", cfg["channels"], cfg["stem_width"], 7, 2, 3, size)]
    size = (size + 6 - 7) // 2 + 1      # stem
    size = (size + 2 - 3) // 2 + 1      # max pool
    cin = cfg["stem_width"]
    for si, (n_blocks, ch) in enumerate(cfg["stages"]):
        for bi in range(n_blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            name = f"s{si}b{bi}"
            out.append((f"{name}_a", cin, ch, 1, stride, 0, size))
            mid = (size - 1) // stride + 1
            out.append((f"{name}_b", ch, ch, 3, 1, 1, mid))
            out.append((f"{name}_c", ch, 4 * ch, 1, 1, 0, mid))
            if bi == 0:
                out.append((f"{name}_proj", cin, 4 * ch, 1, stride, 0, size))
            cin, size = 4 * ch, mid
    return out


def layers(cfg):
    out = [flops.conv(cin, cout, k, s, size, size, pad)
           for _, cin, cout, k, s, pad, size in _convs(cfg)]
    out.append(flops.dense(4 * cfg["stages"][-1][1], cfg["num_classes"]))
    return out


def init_params(cfg, key):
    params = {}
    for name, cin, cout, k, _, _, _ in _convs(cfg):
        key, kw, kb, kg, kbeta = jax.random.split(key, 5)
        params[f"{name}_conv"] = {
            "W": C.he_normal(kw, (cout, cin, k, k), cin * k * k),
            "b": C.small_normal(kb, (cout,), 0.01)}
        # the last batch norm of a block starts its branch small (Goyal et
        # al. 2017 start it at zero), the others near one
        last = name.endswith("_c")
        params[f"{name}_bn"] = {
            "gamma": C.small_normal(kg, (cout,), 0.1,
                                    mean=cfg["init"]["branch_gamma"] if last else 1.0),
            "beta": C.small_normal(kbeta, (cout,), 0.1)}
    key, kw, kb = jax.random.split(key, 3)
    nin = 4 * cfg["stages"][-1][1]
    params["fc"] = {"W": C.he_normal(kw, (nin, cfg["num_classes"]), nin),
                    "b": C.small_normal(kb, (cfg["num_classes"],), 0.01)}
    return params


def loss_fn(cfg, numerics="float32"):
    rnd = C.rounder(numerics)

    def conv_bn(params, name, x, stride, pad, relu):
        c, b = params[f"{name}_conv"], params[f"{name}_bn"]
        x = C.conv2d(x, c["W"], c["b"], stride, pad, rnd)
        x = C.batch_norm(x, b["gamma"], b["beta"], EPS)
        return jax.nn.relu(x) if relu else x

    def block(params, name, x, stride, project):
        y = conv_bn(params, f"{name}_a", x, stride, 0, True)
        y = conv_bn(params, f"{name}_b", y, 1, 1, True)
        y = conv_bn(params, f"{name}_c", y, 1, 0, False)
        if project:
            x = conv_bn(params, f"{name}_proj", x, stride, 0, False)
        return jax.nn.relu(x + y)

    def loss(params, x, y):
        x = conv_bn(params, "stem", x, 2, 3, True)
        x = C.max_pool(x, 3, 2, 1)
        for si, (n_blocks, _) in enumerate(cfg["stages"]):
            for bi in range(n_blocks):
                stride = 2 if (bi == 0 and si > 0) else 1
                name = f"s{si}b{bi}"
                sub = {k: v for k, v in params.items()
                       if k.startswith(name + "_")}
                x = jax.checkpoint(
                    lambda p, a, name=name, stride=stride, bi=bi:
                    block(p, name, a, stride, bi == 0))(sub, x)
        x = jnp.mean(x, axis=(2, 3))
        return C.softmax_xent(C.dense(x, params["fc"]["W"],
                                      params["fc"]["b"], rnd), y)

    return loss
