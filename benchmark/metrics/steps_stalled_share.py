"""Share of the steps of ``fit()`` that stalled: program counter
``dl4j_fit_stalls_total{phase}``, all phases, over
``dl4j_fit_iterations_total``, from the registry as it stands (every
``fit()`` of the process).  ``step_ms_p95`` leaves the steps proper once
this passes 5%.  Nothing where the program has no such counter."""

from benchmark.metrics.step_stall_ms_per_step import counter_per_iteration

STALLS = "dl4j_fit_stalls_total"


def read(ctx):
    share = counter_per_iteration(STALLS)
    return None if share is None else share * 100.0
