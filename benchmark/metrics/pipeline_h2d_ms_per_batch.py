"""Time the input pipeline's worker takes to move one batch to the
device: ``dl4j_phase_seconds{span="pipeline/batch",phase="h2d"}``, sum
over count, over the process (``device_put`` and the wait until the
arrays are ready, on the worker's thread, where the transfer happens)."""

from benchmark.metrics.setup_init_s import phase_totals


def read(ctx):
    seconds, count = phase_totals("pipeline/batch").get("h2d", (0.0, 0))
    if not count:
        return None
    return seconds / count * 1e3
