"""The whole step's share of the chip's peak: the FLOPs a training step
requires by the benchmark's own count from the configuration's shapes
(forward, weight gradient, and input gradient of every weighted layer but
the first; nothing recomputed), times the steps of the window, over the
window's seconds x chips x the bf16 peak of the device."""

from benchmark.harness import flops


def read(ctx):
    w = ctx["window"]
    if not w["steps"] or ctx["peaks"] is None:
        return None
    need = flops.step_flops(ctx["layers"], w["batch"]) * w["steps"]
    return 100.0 * need / (w["seconds"] * ctx["chips"]
                           * ctx["peaks"]["flops_bf16"])
