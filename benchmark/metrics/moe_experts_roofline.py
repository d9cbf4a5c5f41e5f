"""The held experts' share of their roofline: the least time the chip
could take for the three grouped products of every expert layer (forward
and both gradients; per product the larger of FLOPs over the bf16 peak
and bf16 bytes of operands and result over the bandwidth peak, at a
traced step's mean of the assignments the program's counter read, times
the steps: each step reads the weights anew), over the device time
under the layers' ``experts`` scopes, forward and backward
(``harness/moe_scopes.py``)."""

from benchmark.harness import moe_scopes


def read(ctx):
    tr = moe_scopes.traced(ctx)
    if tr is None or ctx["peaks"] is None or not tr["steps"] \
            or not tr["part_s"].get("experts"):
        return None
    cfg, steps = ctx["cfg"], tr["steps"]
    least = steps * sum(moe_scopes.least_seconds(
        a / steps, len(cfg["experts_held"]), cfg["hidden_size"],
        cfg["moe_intermediate_size"], ctx["peaks"])
        for a in tr["held_assignments"].values())
    return 100.0 * least / tr["part_s"]["experts"]
