"""A profiled ``fit()`` step by step: which steps ran late, and whether
the device, the runtime or the host was late in them.

``profile.summarize`` adds what this module reads from the same planes:

* ``steps``: the steps of the trace.  A step runs from the start of its
  ``fit/step/jit_call`` annotation to the start of the next one's (the
  last one to the end of the last phase), so it ends with the phases in
  which the host fetched the next batch;
* ``stalls``: the steps whose wall time is over the median of the
  trace's steps by ``tracing.stall_excess``, the loop's own rule (the
  trace cannot tell that a step compiled: such a step reads as a stall
  held in ``jit_call``).  Each with its wall time, the median, the
  excess, its phases' seconds, and for every chip the device's busy
  seconds in the step, its idle seconds by the phase they fell in, the
  time from the device's last operation to the end of
  ``block_until_ready``, each also as the excess over its median over
  the steps, and ``late``: ``device`` where the busy time grew most,
  ``host`` where the idle time inside ``block_until_ready`` did and the
  lag with it (the device was done and the wait returned late),
  ``runtime`` where the idle time inside ``jit_call`` or
  ``dispatch_prep`` did, or inside ``block_until_ready`` ahead of the
  device's work (the launch reached the device late), else the phase
  whose idle time grew most (``data_wait``: the input was late);
* ``clock_bounds_s``, per chip: ``[low, high]``, the bounds that
  causality sets on the offset of the device's clock against the
  host's over all steps.  No launch of the step's program starts on the
  device before the host entered ``jit_call`` (the smallest such delay
  is ``high``: the device's timestamps are late by no more), and no
  ``block_until_ready`` ends before the device's last operation did
  (the smallest such lag is ``-low``: they are early by no more).
  ``high - low`` is the error bar on every idle gap attributed to a
  phase, and of ``wait_lag_s`` no more than ``-low`` can be clock.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Sequence, Tuple

from deeplearning4j_tpu.monitor import profile, tracing

#: the phase whose start cuts the trace into steps
LAUNCH = "jit_call"
#: the device line with one event a program launched
LAUNCHES_LINE = "XLA Modules"
#: the phases in which a launch is on its way to the device
RUNTIME_PHASES = ("jit_call", "dispatch_prep")

Phase = Tuple[float, float, str]


def cut(phases: Sequence[Phase]) -> List[Tuple[float, float]]:
    """[(start_ns, end_ns)] of the steps of the (disjoint, sorted) host
    phases."""
    starts = [s for s, _, name in phases if name == LAUNCH]
    return list(zip(starts, starts[1:] + [phases[-1][1]])) if starts else []


def step_launches(planes) -> Dict[int, List[float]]:
    """{chip: sorted start_ns of the launches of the program that took
    most of the device's time}: the train step's."""
    out = {}
    for name, lines in planes:
        m = profile.DEVICE_PLANE.match(name)
        if not m:
            continue
        by_program: Dict[str, List[float]] = {}
        seconds: Dict[str, float] = {}
        for line_name, events in lines:
            if line_name != LAUNCHES_LINE:
                continue
            for start, duration, text, _ in events:
                program = re.sub(r"\(.*$", "", text)
                by_program.setdefault(program, []).append(start)
                seconds[program] = seconds.get(program, 0.0) + duration
        if seconds:
            out[int(m.group(1))] = sorted(
                by_program[max(seconds, key=seconds.get)])
    return out


def _device_side(steps, phases, starts, evs, launches) -> List[dict]:
    """One row a step: the chip's busy ns, idle ns by phase, the wait's
    lag and the launch's delay (None where the step shows none)."""
    ops = sorted((s, s + d) for s, d, _ in evs)
    op_starts = [s for s, _ in ops]
    ends = sorted(e for _, e in ops)
    rows = []
    for a, b in steps:
        mine = [(s, min(e, b)) for s, e in
                ops[bisect.bisect_left(op_starts, a):
                    bisect.bisect_left(op_starts, b)]]
        busy, gaps = profile.union(mine)
        if mine:
            gaps = [(a, mine[0][0]), *gaps,
                    (max(e for _, e in mine), b)]
        else:
            gaps = [(a, b)]
        idle: Dict[str, float] = {}
        for gap in gaps:
            if gap[1] > gap[0]:
                for name, ns in profile.share_gap(gap, phases,
                                                  starts).items():
                    idle[name] = idle.get(name, 0.0) + ns
        lag = None
        for s, e, name in phases[bisect.bisect_left(starts, a):
                                 bisect.bisect_left(starts, b)]:
            if name == profile.WAIT:
                i = bisect.bisect_right(ends, e) - 1
                if i >= 0 and ends[i] >= s:
                    lag = e - ends[i]
        i = bisect.bisect_left(launches, a)
        delay = launches[i] - a if i < len(launches) and launches[i] < b \
            else None
        rows.append({"busy": busy, "idle": idle, "lag": lag,
                     "delay": delay})
    return rows


def _late(busy_excess: float, idle_excess: Dict[str, float],
          lag_excess: Optional[float]) -> str:
    phase = max(idle_excess, key=idle_excess.get, default=None)
    if phase is None or busy_excess >= idle_excess[phase]:
        return "device"
    if phase == profile.WAIT:
        # idle while the host waits: after the device's last operation
        # (the wait returned late), or before its first (the launch was
        # still on its way when the call had returned)
        return "host" if (lag_excess or 0.0) >= idle_excess[phase] / 2 \
            else "runtime"
    return "runtime" if phase in RUNTIME_PHASES else phase


def _median(values) -> float:
    values = [v for v in values if v is not None]
    return tracing.median(values) if values else 0.0


def steps_summary(planes, phases: Sequence[Phase],
                  device_events: Dict[int, list]) -> dict:
    """``steps``, ``stalls`` and each chip's ``clock_bounds_s`` (see the
    module's docstring) from the planes, their disjoint host phases and
    the chips' operations as ``profile.device_events`` gives them."""
    steps = cut(phases)
    starts = [s for s, _, _ in phases]
    walls = [(b - a) / 1e9 for a, b in steps]
    median = _median(walls)
    late = [i for i, w in enumerate(walls)
            if tracing.stall_excess(w, median)]
    stalls = [{"step": i, "wall_s": walls[i], "median_s": median,
               "excess_s": walls[i] - median,
               "phases_s": {p: ns / 1e9 for p, ns in profile.share_gap(
                   steps[i], phases, starts).items()},
               "chips": {}} for i in late]
    launches = step_launches(planes)
    bounds: Dict[str, Optional[List[float]]] = {}
    for chip, evs in sorted(device_events.items()):
        if not evs or not steps:
            continue
        rows = _device_side(steps, phases, starts, evs,
                            launches.get(chip, []))
        lags = [r["lag"] for r in rows if r["lag"] is not None]
        delays = [r["delay"] for r in rows if r["delay"] is not None]
        bounds[str(chip)] = [-min(lags) / 1e9, min(delays) / 1e9] \
            if lags and delays else None
        busy_m = _median(r["busy"] for r in rows)
        lag_m = _median(r["lag"] for r in rows)
        idle_m = {p: _median(r["idle"].get(p, 0.0) for r in rows)
                  for p in {p for r in rows for p in r["idle"]}}
        for stall in stalls:
            r = rows[stall["step"]]
            idle_excess = {p: (ns - idle_m[p]) / 1e9
                           for p, ns in r["idle"].items()}
            busy_excess = (r["busy"] - busy_m) / 1e9
            lag_excess = None if r["lag"] is None \
                else (r["lag"] - lag_m) / 1e9
            stall["chips"][str(chip)] = {
                "busy_s": r["busy"] / 1e9, "busy_excess_s": busy_excess,
                "idle_s": {p: ns / 1e9 for p, ns in r["idle"].items()},
                "idle_excess_s": idle_excess,
                "wait_lag_s": None if r["lag"] is None else r["lag"] / 1e9,
                "wait_lag_excess_s": lag_excess,
                "late": _late(busy_excess, idle_excess, lag_excess)}
    return {"steps": len(steps), "stalls": stalls, "clock_bounds_s": bounds}
