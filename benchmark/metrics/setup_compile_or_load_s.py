"""Seconds of set-up the program spent in the backend's compile or in
loading the compiled program from the persistent cache:
``dl4j_compile_seconds``, every series whose ``span`` is not empty,
stages ``backend_compile`` + ``cache_load``.  In JAX 0.9.0 the
``backend_compile_duration`` timer contains the cache's lookup, and a
hit reports its ``cache_retrieval_time_sec`` besides; the program takes
that time out of ``backend_compile`` and files it under ``cache_load``,
so the two stages hold no second twice and their sum is the backend
timer's."""

from benchmark.metrics.setup_trace_lower_s import stage_seconds


def read(ctx):
    return stage_seconds(("backend_compile", "cache_load"))
