"""Host time a step of ``fit()`` spends in no span at all: the window's
wall time a step, less the sum of ALL ``fit/step`` phases a step
(program spans ``dl4j_phase_seconds{span="fit/step"}``, taken before
and after the window).  Near zero when the phases tile the loop."""


def read(ctx):
    w = ctx["window"]
    if not w["steps"] or not w["spans"]:
        return None
    timed = sum(seconds for seconds, _ in w["spans"].values())
    return (w["seconds"] - timed) / w["steps"] * 1e3
