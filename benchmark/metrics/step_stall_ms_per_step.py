"""Time a step of ``fit()`` loses to stalls: the seconds by which the
steps that ran late ran over the median of the steps around them
(program counter ``dl4j_fit_stall_seconds_total{phase}``, all phases),
over the steps run (``dl4j_fit_iterations_total``).  From the registry
as it stands, so over every ``fit()`` of the process: warm-up, window
and traced stretch; a step that compiled is no stall.  0 where no step
stalled, nothing where the program has no such counter."""

STALL_SECONDS = "dl4j_fit_stall_seconds_total"
ITERATIONS = "dl4j_fit_iterations_total"


def counter_per_iteration(name):
    """The sum of one counter family over the iterations run, or None
    where the program's registry lacks either."""
    from deeplearning4j_tpu import monitor
    snap = monitor.get_registry().snapshot()
    if name not in snap or ITERATIONS not in snap:
        return None
    steps = sum(s["value"] for s in snap[ITERATIONS]["samples"])
    if not steps:
        return None
    return sum(s["value"] for s in snap[name]["samples"]) / steps


def read(ctx):
    per_step = counter_per_iteration(STALL_SECONDS)
    return None if per_step is None else per_step * 1e3
