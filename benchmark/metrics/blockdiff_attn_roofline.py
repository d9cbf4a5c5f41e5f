"""The block-diffusion attention core's share of its roofline: the least
time the chip could take for the live pairs' products of every attention
layer, forward and backward (``harness/blockdiff.py``: seven products
over the L^2 + L b pairs the mask leaves live; FLOPs over the bf16 peak
against bf16 bytes of q, k, v, o and their gradients over the bandwidth
peak), times the traced steps, over the device time under the layers'
``attn_core`` scopes, forward and backward."""

from benchmark.harness import blockdiff


def read(ctx):
    tr = blockdiff.traced(ctx)
    if tr is None or ctx["peaks"] is None:
        return None
    least = tr["steps"] * blockdiff.least_seconds(
        ctx["cfg"] | {"seq_len": ctx["traffic"]["seq_len"],
                      "block_length": ctx["traffic"]["block_length"]},
        ctx["window"]["batch"], ctx["peaks"])
    return 100.0 * least / tr["core_s"]
