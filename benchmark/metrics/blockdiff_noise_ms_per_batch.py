"""Host time the noising pre-processor takes a batch:
``dl4j_phase_seconds{span="pipeline/batch",phase="noise"}``, sum over
count, over the process (the span ``BlockDiffusionNoiser.pre_process``
opens, on the input pipeline's feeder thread)."""

from benchmark.metrics.setup_init_s import phase_totals


def read(ctx):
    seconds, count = phase_totals("pipeline/batch").get("noise", (0.0, 0))
    if not count:
        return None
    return seconds / count * 1e3
