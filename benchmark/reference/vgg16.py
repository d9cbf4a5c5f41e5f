"""VGG16, configuration D of Simonyan & Zisserman 2014, as a plain
reference: thirteen 3x3 convolutions (stride 1, padding 1, ReLU) in five
blocks, a 2x2 max pool after each block, two ReLU dense layers and a
softmax classifier under cross-entropy.  No dropout (the configuration
sets none).  Parameters are a list with one entry a layer, pools
included as ``{}``, which is how the program's list engine takes them.
"""

from __future__ import annotations

import jax

from benchmark.harness import flops
from benchmark.reference import common as C

ROW_BLOCKS = 4  # no layer mixes rows, so block gradients average exactly


def _plan(cfg):
    """[('conv', cin, cout) | ('pool',) | ('dense', nin, nout)]"""
    plan, cin, size = [], cfg["channels"], cfg["image_size"]
    for n_convs, ch in cfg["blocks"]:
        for _ in range(n_convs):
            plan.append(("conv", cin, ch, size))
            cin = ch
        plan.append(("pool",))
        size //= 2
    nin = cin * size * size
    for nout in (cfg["fc_size"], cfg["fc_size"], cfg["num_classes"]):
        plan.append(("dense", nin, nout))
        nin = nout
    return plan


def layers(cfg):
    out = []
    for p in _plan(cfg):
        if p[0] == "conv":
            out.append(flops.conv(p[1], p[2], 3, 1, p[3], p[3], 1))
        elif p[0] == "dense":
            out.append(flops.dense(p[1], p[2]))
    return out


def init_params(cfg, key):
    params = []
    for p in _plan(cfg):
        key, kw, kb = jax.random.split(key, 3)
        if p[0] == "conv":
            params.append({"W": C.he_normal(kw, (p[2], p[1], 3, 3), p[1] * 9),
                           "b": C.small_normal(kb, (p[2],), 0.01)})
        elif p[0] == "dense":
            params.append({"W": C.he_normal(kw, (p[1], p[2]), p[1]),
                           "b": C.small_normal(kb, (p[2],), 0.01)})
        else:
            params.append({})
    return params


def loss_fn(cfg, numerics="float32"):
    rnd = C.rounder(numerics)
    plan = _plan(cfg)
    last = len(plan) - 1

    def loss(params, x, y):
        for i, (p, w) in enumerate(zip(plan, params)):
            if p[0] == "conv":
                x = jax.nn.relu(C.conv2d(x, w["W"], w["b"], 1, 1, rnd))
            elif p[0] == "pool":
                x = C.max_pool(x, 2, 2)
            else:
                x = x.reshape(x.shape[0], -1)  # NCHW rows, channel-major
                x = C.dense(x, w["W"], w["b"], rnd)
                if i != last:
                    x = jax.nn.relu(x)
        return C.softmax_xent(x, y)

    return loss
