"""What the expert layer's two metrics read: the device seconds of a
traced run under the parts the layer names inside its scope (``route``,
``dispatch``, ``experts``, ``combine``; ``monitor/profile.py`` sums them
by direction), the held assignments the program's counter read over the
traced steps, and the operations and bytes of the held experts' products.

``run.py`` reduces the trace to ``ctx["trace"]`` without scopes and
deletes it; mode ``fit_tokens`` has it kept (``BENCHMARK_KEEP_TRACE``)
with the counters beside it, and this file reads that copy.  A program
without the scopes or the counter (the parent of the PR that brought
them) gives ``None`` here and the metrics are left out of the line.
"""

from __future__ import annotations

import json
import os

BF16 = 2
LAYER = "MixtureOfExpertsLayer"
COUNTERS_FILE = "moe_counters.json"
ROUTING_PARTS = ("route", "dispatch", "combine")


def traced(ctx):
    """{"steps", "held_assignments": {vertex: over the traced steps},
    "part_s": {part: device seconds, forward and backward}} of the kept
    trace, read once a run; None where there is nothing to read."""
    if "_moe_scopes" in ctx:
        return ctx["_moe_scopes"]
    ctx["_moe_scopes"] = out = _read(os.environ.get("BENCHMARK_KEEP_TRACE"))
    return out


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
    part_s = {}
    for chip in chips.values():
        for name, s in chip.get("sub_scope_s", {}).items():
            _, kind, part = name.split("/")
            if kind == LAYER:
                part_s[part] = part_s.get(part, 0.0) + s
    if not part_s or not counters.get("held_assignments"):
        return None
    return {"steps": counters["steps"], "part_s": part_s,
            "held_assignments": counters["held_assignments"]}


def expert_passes(assignments, held, d_model, d_expert):
    """The matrix passes a training step needs of one expert layer's held
    experts at ``assignments`` token x expert rows, each as (name, FLOPs,
    bytes of operands and result in bf16): the three products W1, W3
    (d_model x d_expert) and W2 (d_expert x d_model), each forward, for
    its weights' gradient and for its input's."""
    f = 2.0 * assignments * d_model * d_expert
    rows_in, rows_mid = assignments * d_model, assignments * d_expert
    w = held * d_model * d_expert
    out = []
    for name, x, y in (("W1", rows_in, rows_mid), ("W3", rows_in, rows_mid),
                       ("W2", rows_mid, rows_in)):
        b = BF16 * (x + w + y)
        out += [(name + "/forward", f, b), (name + "/weight_grad", f, b),
                (name + "/input_grad", f, b)]
    return out


def least_seconds(assignments, held, d_model, d_expert, peaks):
    """Least time the chip could take for :func:`expert_passes`: per pass
    the larger of FLOPs over the bf16 peak and bytes over the bandwidth."""
    return sum(max(f / peaks["flops_bf16"], b / peaks["hbm_bytes_per_s"])
               for _, f, b in expert_passes(assignments, held, d_model,
                                            d_expert))
