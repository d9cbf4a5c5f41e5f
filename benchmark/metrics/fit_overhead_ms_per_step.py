"""Host time a step of ``fit()`` spends outside the jitted call: the
window's wall time a step, less the mean ``jit_call`` +
``block_until_ready`` spans a step (program spans
``dl4j_phase_seconds{span="fit/step"}``, taken before and after the
window)."""


def read(ctx):
    w = ctx["window"]
    if not w["steps"]:
        return None
    s = w["spans"]
    if "jit_call" not in s or "block_until_ready" not in s:
        return None
    device_side = s["jit_call"][0] + s["block_until_ready"][0]
    return (w["seconds"] - device_side) / w["steps"] * 1e3
