"""The convolutions' share of their roofline: the least time the chip
could take for every convolution pass of the traced steps (per pass the
larger of FLOPs over the bf16 peak and bf16 bytes of operands and result
over the bandwidth peak, from the configuration's shapes), over the
device time of the trace's convolution events."""

from benchmark.harness import flops


def read(ctx):
    tr = ctx["trace"]
    if tr is None or ctx["peaks"] is None or not tr.get("conv_s"):
        return None
    least, _, _ = flops.least_seconds(ctx["layers"], ctx["window"]["batch"],
                                      ctx["peaks"], kind="conv")
    return 100.0 * least * tr["steps"] / tr["conv_s"]
