"""Device time a traced step spends in the head that scores a loop's
exits: under its ``head`` scope (every pass's product with the head's
matrix and its cross-entropy, forward, recomputed and backward) and its
``gate`` scope (the gate, the exit distribution, its entropy), both
directions (``harness/loop_scopes.py``)."""

from benchmark.harness import loop_scopes


def read(ctx):
    tr = loop_scopes.traced(ctx)
    parts = tr and tr["part_s"].get(loop_scopes.HEAD)
    if not parts:
        return None
    return (parts.get("head", 0.0) + parts.get("gate", 0.0)) \
        / tr["steps"] * 1e3
