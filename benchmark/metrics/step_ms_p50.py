"""Median time between consecutive ``iteration_done`` calls over the
window: the steadier companion of the end-to-end ``step_ms_p95``."""

from benchmark.harness import stats


def read(ctx):
    w = ctx["window"]
    return stats.percentile(w["step_ms"], 50) if w["step_ms"] else None
