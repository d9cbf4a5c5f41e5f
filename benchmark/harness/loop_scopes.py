"""What the loop's three metrics read: the device seconds of a traced run
under the parts a looped stack and the head that scores its exits name
inside their scopes (``body``; ``head``, ``gate``), the part of ``body``
that is the blocks' second forward (``jax.checkpoint`` names it
``rematted_computation``), all summed by ``monitor/profile.py``, and the
passes the program's counter counted over the traced steps.

``run.py`` reduces the trace to ``ctx["trace"]`` without scopes and
deletes it; mode ``fit_looped`` has it kept (``BENCHMARK_KEEP_TRACE``)
with the counter beside it, and this file reads that copy.  A program
without the scopes or the counter gives ``None`` here and the metrics are
left out of the line.
"""

from __future__ import annotations

import json
import os

LOOP, HEAD = "LoopVertex", "LoopExitOutputLayer"
COUNTERS_FILE = "loop_counters.json"
PASSES = "dl4j_loop_passes_total"


def passes_run():
    """{loop vertex: passes run} so far, from the program's counter; {}
    where the program has none."""
    from deeplearning4j_tpu import monitor
    fam = monitor.get_registry().snapshot().get(PASSES, {})
    return {s["labels"]["vertex"]: float(s["value"])
            for s in fam.get("samples", [])}


def traced(ctx):
    """{"steps", "passes" (over the traced steps, all loops), "busy_s",
    "part_s": {layer type: {part: device seconds, forward and backward}},
    "recomputed_s": {layer type: seconds} or None} of the kept trace,
    read once a run; None where there is nothing to read."""
    if "_loop_scopes" not in ctx:
        ctx["_loop_scopes"] = _read(os.environ.get("BENCHMARK_KEEP_TRACE"))
    return ctx["_loop_scopes"]


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
    part_s, recomputed, busy = {}, None, 0.0
    for chip in chips.values():
        busy += chip.get("busy_s", 0.0)
        for name, s in chip.get("sub_scope_s", {}).items():
            _, kind, part = name.split("/")
            if kind in (LOOP, HEAD):
                by = part_s.setdefault(kind, {})
                by[part] = by.get(part, 0.0) + s
        if "recomputed_s" in chip:
            recomputed = recomputed or {}
            for kind, s in chip["recomputed_s"].items():
                recomputed[kind] = recomputed.get(kind, 0.0) + s
    passes = sum(counters.get("passes", {}).values())
    if not part_s or not passes or not counters.get("steps"):
        return None
    return {"steps": counters["steps"], "passes": passes, "busy_s": busy,
            "part_s": part_s, "recomputed_s": recomputed}
