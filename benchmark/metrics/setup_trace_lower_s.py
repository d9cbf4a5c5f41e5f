"""Seconds of set-up the program spent tracing its functions to jaxprs
and lowering them to MLIR (the Pallas kernels' lowering among it):
``dl4j_compile_seconds``, every series whose ``span`` is not empty (a
compile inside a program span; the reference's compiles, after the
window, are in no span), stages ``trace`` + ``lower``.  The program
counts a stage once per outermost jitted call."""

COMPILE_METRIC = "dl4j_compile_seconds"


def stage_seconds(stages):
    """Sum of the program's compile seconds in ``stages`` over every
    non-empty span, or None where the program has no such counter."""
    from deeplearning4j_tpu import monitor
    fam = monitor.get_registry().snapshot().get(COMPILE_METRIC)
    if not fam:
        return None
    found = [float(s["sum"]) for s in fam.get("samples", [])
             if s["labels"].get("span") and s["labels"].get("stage") in stages]
    return sum(found) if found else None


def read(ctx):
    return stage_seconds(("trace", "lower"))
