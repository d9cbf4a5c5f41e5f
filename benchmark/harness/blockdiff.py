"""What the block-diffusion attention core's metrics read and count.

COUNT.  The least work an attention core under the block-diffusion mask
needs, whatever implements it and under whatever kernel names: the mask
leaves L^2 + L b of the (2L)^2 pairs live (a noisy row sees its own
block and the clean blocks before it, a clean row the clean blocks up to
its own), and a training step needs seven products over them a head:
q k^T and p v forward; in the backward q k^T again (the probabilities
are rebuilt from the saved row statistics, as every memory-efficient
core does), dO v^T, p^T dO, ds k and ds^T q.  Each is 2 x pairs x
head_dim FLOPs a head.  The bytes are q, o and their gradients at every
query head and k, v and theirs at the key/value heads, once each, in
bf16.  The least time is the larger of FLOPs over the bf16 peak and
bytes over the bandwidth peak: the share cannot pass 100% unless the
core skips live pairs.

READ.  The device seconds of a traced run under the part
``attn_core`` that ``SelfAttentionLayer`` names inside its scope
(``fwd/SelfAttentionLayer/<vertex>/attn_core``, forward and backward;
``monitor/profile.py`` sums them as ``sub_scope_s``).  ``run.py``
reduces the trace to ``ctx["trace"]`` without scopes and deletes it;
mode ``fit_block_diffusion`` has it kept (``BENCHMARK_KEEP_TRACE``) with
the steps beside it, and this file reads that copy.  A program without
the scope (the parent of the PR that brought it) gives ``None`` here and
the metrics are left out of the line.
"""

from __future__ import annotations

import json
import os

BF16 = 2
LAYER, PART = "SelfAttentionLayer", "attn_core"
COUNTERS_FILE = "blockdiff_counters.json"
PRODUCTS = 7        # 2 forward, 5 backward
COUNTERS = ("dl4j_diffusion_tokens_total", "dl4j_diffusion_loss_weight_sum",
            "dl4j_attention_tiles", "dl4j_moe_row_segments_total",
            "dl4j_moe_assignments_total")


def live_pairs(seq_len: int, block_length: int) -> int:
    return seq_len * seq_len + seq_len * block_length


def core_flops(seq_len, block_length, heads, head_dim) -> float:
    """FLOPs of one layer's core, forward and backward, one sequence."""
    return PRODUCTS * 2.0 * live_pairs(seq_len, block_length) * heads * head_dim


def core_bytes(seq_len, heads, kv_heads, head_dim) -> float:
    """bf16 bytes of q, k, v, o and their gradients, once each."""
    rows = 2 * seq_len
    return BF16 * 2.0 * rows * head_dim * (2 * heads + 2 * kv_heads)


def least_seconds(cfg, batch, peaks) -> float:
    """Least time a step's attention cores could take on the chip."""
    L, b = cfg["seq_len"], cfg["block_length"]
    H, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    one = max(core_flops(L, b, H, Dh) / peaks["flops_bf16"],
              core_bytes(L, H, Hkv, Dh) / peaks["hbm_bytes_per_s"])
    return one * batch * len(cfg["layers_run"])


def program_counters() -> dict:
    """{family: [{labels, value}]} of the program's diffusion and tile
    counters so far; {} where the program has none."""
    from deeplearning4j_tpu import monitor
    snap = monitor.get_registry().snapshot()
    return {name: [{"labels": s["labels"], "value": s["value"]}
                   for s in snap[name].get("samples", [])]
            for name in COUNTERS if name in snap}


def traced(ctx):
    """{"steps", "core_s" (device seconds under ``attn_core``, forward and
    backward, all chips)} of the kept trace, read once a run; None where
    there is nothing to read."""
    if "_blockdiff" not in ctx:
        ctx["_blockdiff"] = _read(os.environ.get("BENCHMARK_KEEP_TRACE"))
    return ctx["_blockdiff"]


def _read(kept):
    if not kept or not os.path.isdir(kept):
        return None
    try:
        with open(os.path.join(kept, COUNTERS_FILE)) as f:
            counters = json.load(f)
        from deeplearning4j_tpu.monitor import profile
        chips = profile.summarize(profile.load(kept))["chips"]
    except (OSError, ValueError, KeyError, ImportError):
        return None
    return reduce(chips, counters)


def reduce(chips, counters):
    """The same from a profile's ``chips`` and the counters."""
    core_s = 0.0
    for chip in chips.values():
        for name, s in chip.get("sub_scope_s", {}).items():
            _, kind, part = name.split("/")
            if kind == LAYER and part == PART:
                core_s += s
    if not core_s or not counters.get("steps"):
        return None
    return {"steps": counters["steps"], "core_s": core_s}
