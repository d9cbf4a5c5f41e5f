"""Device time a traced step spends routing: under the expert layers'
``route``, ``dispatch`` and ``combine`` scopes, forward and backward (the
router's product, the selection, the sort, the gathers and scatters;
``harness/moe_scopes.py``)."""

from benchmark.harness import moe_scopes


def read(ctx):
    tr = moe_scopes.traced(ctx)
    if tr is None or not tr["steps"]:
        return None
    return sum(tr["part_s"].get(p, 0.0)
               for p in moe_scopes.ROUTING_PARTS) / tr["steps"] * 1e3
