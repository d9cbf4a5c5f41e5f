"""Operations and bytes of a training step, from the configuration's shapes.

A configuration's reference module lists its weighted layers
(``layers(cfg)``): convolutions and dense layers with their shapes.  This
file turns that list into FLOPs (2 per multiply-add) and into the least
time a chip could take, so the same work is counted whatever implements
it.  Pooling, normalisation, activations and the loss are not counted:
they are under 1% of either model and counting nothing for them can only
make a share read lower.
"""

from __future__ import annotations

BF16 = 2  # bytes an element of the operands and results as the step computes them


def conv(cin, cout, k, stride, hin, win, pad):
    hout = (hin + 2 * pad - k) // stride + 1
    wout = (win + 2 * pad - k) // stride + 1
    return {"kind": "conv", "cin": cin, "cout": cout, "k": k, "stride": stride,
            "hin": hin, "win": win, "hout": hout, "wout": wout}


def dense(nin, nout):
    return {"kind": "dense", "nin": nin, "nout": nout}


def forward_flops(layer) -> int:
    """FLOPs of one layer's forward pass for ONE row."""
    if layer["kind"] == "conv":
        return (2 * layer["cin"] * layer["cout"] * layer["k"] ** 2
                * layer["hout"] * layer["wout"])
    return 2 * layer["nin"] * layer["nout"]


def _sizes(layer, batch):
    """(input, weight, output) element counts of one layer at ``batch``."""
    if layer["kind"] == "conv":
        return (batch * layer["cin"] * layer["hin"] * layer["win"],
                layer["cout"] * layer["cin"] * layer["k"] ** 2,
                batch * layer["cout"] * layer["hout"] * layer["wout"])
    return batch * layer["nin"], layer["nin"] * layer["nout"], batch * layer["nout"]


def passes(layer, batch, first: bool):
    """The matrix passes a training step needs of one layer, each as
    (name, flops, bytes of operands and result in bf16).  The first layer
    needs no gradient with respect to its input."""
    f = forward_flops(layer) * batch
    x, w, y = _sizes(layer, batch)
    out = [("forward", f, BF16 * (x + w + y)),
           ("weight_grad", f, BF16 * (x + y + w))]
    if not first:
        out.append(("input_grad", f, BF16 * (y + w + x)))
    return out


def forward_flops_per_row(layers) -> int:
    return sum(forward_flops(l) for l in layers)


def step_flops(layers, batch) -> int:
    """FLOPs one training step requires: forward, weight gradient, and
    input gradient of every weighted layer but the first."""
    return sum(f for i, l in enumerate(layers)
               for _, f, _ in passes(l, batch, first=(i == 0)))


def least_seconds(layers, batch, peaks, kind="conv"):
    """Least time the chip could take for all passes of the layers of
    ``kind``: for each pass the larger of FLOPs over the FLOP peak and
    bytes over the bandwidth peak.  Returns (seconds, seconds bound by
    compute, seconds bound by bandwidth)."""
    total = by_flops = by_bytes = 0.0
    for i, l in enumerate(layers):
        if l["kind"] != kind:
            continue
        for _, f, b in passes(l, batch, first=(i == 0)):
            tf, tb = f / peaks["flops_bf16"], b / peaks["hbm_bytes_per_s"]
            total += max(tf, tb)
            if tf >= tb:
                by_flops += tf
            else:
                by_bytes += tb
    return total, by_flops, by_bytes
