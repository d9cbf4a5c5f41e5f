"""Share of the traced stretch's device idle time that began while the
host was in no ``fit/step`` span: ``between_spans`` seconds over all
seconds of the reduction's ``idle_gaps``."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    gaps = dict(tr.get("idle_gaps") or [])
    total = sum(gaps.values())
    if not total:
        return None
    return 100.0 * gaps.get("between_spans", 0.0) / total
