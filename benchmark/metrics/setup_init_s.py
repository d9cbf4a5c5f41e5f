"""Seconds of set-up inside ``net.init()``: the sum of the program's
``dl4j_phase_seconds{span="net/init"}`` (drawing the default weights,
then taking the given ones and building the updater state), over the
process.  The benchmark builds one network."""

SPAN_METRIC = "dl4j_phase_seconds"


def phase_totals(span):
    """{phase: (sum of seconds, count)} of one span name in the
    program's registry; empty where the program has no such span."""
    from deeplearning4j_tpu import monitor
    fam = monitor.get_registry().snapshot().get(SPAN_METRIC, {})
    return {s["labels"]["phase"]: (float(s["sum"]), int(s["count"]))
            for s in fam.get("samples", [])
            if s["labels"].get("span") == span}


def read(ctx):
    phases = phase_totals("net/init")
    if not phases:
        return None
    return sum(seconds for seconds, _ in phases.values())
