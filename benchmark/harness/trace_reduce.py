"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.  Kept with the benchmark so that every PR computes them
the same way.

What a v5e trace holds (read by hand from the first chip run of PR 25;
``describe()`` below prints the same): the chip is the plane
``/device:TPU:0``.  Its line ``XLA Ops`` has one event for every HLO
instruction that ran; an event's name is the instruction's whole text
(``%fusion.188 = (bf16[64], bf16[128,64,224,224]) fusion(f32[64,64,3,3]
%copy-done.28, ...), kind=kOutput, calls=%fused_computation.253``), and no
stat names its category.  Its line ``XLA Modules`` has one event a program
launched (``jit_step(...)``).  The host is the plane ``/host:CPU``; the
program's spans appear on its ``python3`` lines by name
(``fit/step/block_until_ready``) when its annotations are on.  Device and
host events share one clock, in nanoseconds.

Which events are convolution work.  XLA:TPU puts every convolution into
an output fusion (``kind=kOutput``), together with whatever it fused
around it: the bias and ReLU, the bias gradient, batch-norm statistics,
the updater's arithmetic on a weight gradient.  Pooling can be an output
fusion too, so the kind alone does not decide.  The Pallas conv tier is a
``custom-call`` with ``custom_call_target="tpu_custom_call"``.  An event
counts as convolution work when it is one of those two AND one of the
arrays in its text has the dimensions of a convolution kernel of the
configuration (cout, cin, k, k in any order): forward and input-gradient
passes take the kernel as an operand, the weight-gradient pass yields it.
The time counted is the whole event's, fused neighbours included.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN = re.compile(r"^fit/step/(\w+)$")
ARRAY = re.compile(r"\b(?:bf16|f16|f32|s8|u8|f8\w*)\[(\d+(?:,\d+)*)\]")


def _xplane(path):
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise SystemExit(f"benchmark: no .xplane.pb under {path}")
    return found[-1]


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path if path.endswith(".pb") else _xplane(path))


def kernel_dims(layers):
    """The sorted (cout, cin, k, k) of every convolution of a layer list."""
    return {tuple(sorted((l["cout"], l["cin"], l["k"], l["k"])))
            for l in layers if l["kind"] == "conv"}


def is_convolution(text: str, kernels) -> bool:
    if "kind=kOutput" not in text and "tpu_custom_call" not in text:
        return False
    return any(tuple(sorted(int(d) for d in m.group(1).split(","))) in kernels
               for m in ARRAY.finditer(text))


def short_name(text: str) -> str:
    """``%fusion.188 = (...) fusion(...)`` -> ``fusion``: the instruction's
    name without its number, which changes from compile to compile."""
    name = text.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", name) or name


def device_events(profile, chips=1):
    """{chip: [(start_ns, duration_ns, text)]} of the ops line."""
    out = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) >= chips:
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            out[int(m.group(1))] = [
                (float(e.start_ns), float(e.duration_ns), e.name)
                for e in line.events]
    return out


def host_spans(profile):
    """[(start_ns, end_ns, phase)] of the program's fit/step spans."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                m = SPAN.match(e.name)
                if m:
                    out.append((float(e.start_ns),
                                float(e.start_ns + e.duration_ns), m.group(1)))
    return sorted(out)


def union(intervals):
    """Total length, and the gaps, of a set of (start, end) intervals."""
    busy, gaps, end = 0.0, [], None
    for s, e in sorted(intervals):
        if end is None:
            busy, end = e - s, e
        elif s > end:
            gaps.append((end, s))
            busy, end = busy + (e - s), e
        elif e > end:
            busy, end = busy + (e - end), e
    return busy, gaps


def _phase_at(spans, t):
    for s, e, phase in spans:
        if s <= t < e:
            return phase
    return "between_spans"


def reduce(profile, layers=(), chips=1):
    """busy, window and convolution seconds (means over the chips), the
    device operations that took most time and the idle gaps by the
    ``fit/step`` phase the host was in when each began."""
    per_chip = device_events(profile, chips)
    if not per_chip:
        raise SystemExit("benchmark: the trace holds no device ops line")
    kernels = kernel_dims(layers)
    spans = host_spans(profile)
    busy_s = window_s = conv_s = 0.0
    by_op, by_gap = {}, {}
    for evs in per_chip.values():
        if not evs:
            raise SystemExit("benchmark: no operation ran on the device")
        t0 = min(s for s, *_ in evs)
        t1 = max(s + d for s, d, *_ in evs)
        busy, gaps = union([(s, s + d) for s, d, *_ in evs])
        busy_s += busy / 1e9
        window_s += (t1 - t0) / 1e9
        for s, d, text in evs:
            key = short_name(text)
            if is_convolution(text, kernels):
                conv_s += d / 1e9
                key = "conv:" + key
            by_op[key] = by_op.get(key, 0.0) + d / 1e9
        for a, b in gaps:
            ph = _phase_at(spans, a)
            by_gap[ph] = by_gap.get(ph, 0.0) + (b - a) / 1e9
    n = len(per_chip)
    top = sorted(by_op.items(), key=lambda kv: -kv[1])
    return {"busy_s": busy_s / n, "window_s": window_s / n,
            "conv_s": conv_s / n,
            "device_ops": [[k, v / n] for k, v in top],
            "idle_gaps": [[k, v / n] for k, v in
                          sorted(by_gap.items(), key=lambda kv: -kv[1])]}


def reduce_dir(path, layers=(), chips=1):
    return reduce(load(path), layers, chips)


def describe(path, top=40):
    """Print what a trace holds: planes, lines, and the longest events of
    each device line with their stats.  For reading a trace by hand."""
    prof = load(path)
    for plane in prof.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs), "events")
            if not (plane.name.startswith("/device:") or "fit/step" in
                    " ".join(e.name for e in evs[:2000])):
                continue
            agg = {}
            for e in evs:
                a = agg.setdefault(e.name, [0, 0.0, None])
                a[0] += 1
                a[1] += e.duration_ns
                if a[2] is None:
                    a[2] = dict(e.stats)
            for name, (n, ns, st) in sorted(agg.items(),
                                            key=lambda kv: -kv[1][1])[:top]:
                short = {k: (str(v)[:120]) for k, v in st.items()}
                print(f"    {ns / 1e6:10.3f} ms  x{n:<5} {name}  {short}")


if __name__ == "__main__":
    import sys
    describe(sys.argv[1])
