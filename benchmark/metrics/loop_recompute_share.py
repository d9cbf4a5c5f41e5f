"""Share of the device's busy time that a looped stack spends running its
blocks' forward a second time (recomputation per block: the operations
``jax.checkpoint`` names ``rematted_computation`` under the loop vertex's
scope, as ``monitor/profile.py`` sums them in ``recomputed_s``); ``None``
where the profile does not tell them apart."""

from benchmark.harness import loop_scopes


def read(ctx):
    tr = loop_scopes.traced(ctx)
    if tr is None or tr["recomputed_s"] is None or not tr["busy_s"]:
        return None
    return 100.0 * tr["recomputed_s"].get(loop_scopes.LOOP, 0.0) / tr["busy_s"]
