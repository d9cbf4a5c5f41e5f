"""Time a step of ``fit()`` spends fetching from the device after the
wait: the mean ``score_fetch`` + ``publish`` spans a step over the
window (program spans ``dl4j_phase_seconds{span="fit/step"}``, taken
before and after the window): the score's transfer, and the publishers'
fetches of what the layers left in state.  Nothing where the program
has no such phases."""


def read(ctx):
    w = ctx["window"]
    s = w["spans"]
    if not w["steps"] or "score_fetch" not in s or "publish" not in s:
        return None
    return (s["score_fetch"][0] + s["publish"][0]) / w["steps"] * 1e3
