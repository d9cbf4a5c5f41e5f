"""Device time a traced step spends in the attention cores: under the
attention layers' ``attn_core`` scopes, forward and backward (the three
flash kernels and what the backward rule computes beside them;
``harness/blockdiff.py``)."""

from benchmark.harness import blockdiff


def read(ctx):
    tr = blockdiff.traced(ctx)
    if tr is None:
        return None
    return tr["core_s"] / tr["steps"] * 1e3
