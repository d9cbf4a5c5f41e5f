"""Device time one pass of a looped stack takes: under the loop vertex's
``body`` scope (the blocks of all passes: forward, the recomputed forward
and backward) over the traced steps, over the passes the program's
counter ``dl4j_loop_passes_total`` counted there
(``harness/loop_scopes.py``)."""

from benchmark.harness import loop_scopes


def read(ctx):
    tr = loop_scopes.traced(ctx)
    if tr is None or not tr["part_s"].get(loop_scopes.LOOP, {}).get("body"):
        return None
    return tr["part_s"][loop_scopes.LOOP]["body"] / tr["passes"] * 1e3
