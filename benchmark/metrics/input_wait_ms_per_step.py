"""Time a step waits for its input: the mean ``data_wait`` + ``h2d`` spans
a step over the window (program spans ``dl4j_phase_seconds``)."""


def read(ctx):
    w = ctx["window"]
    s = w["spans"]
    if not w["steps"] or "data_wait" not in s:
        return None
    return (s["data_wait"][0] + s.get("h2d", (0.0, 0))[0]) / w["steps"] * 1e3
