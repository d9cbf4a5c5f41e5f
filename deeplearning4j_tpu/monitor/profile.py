"""From a profiler trace to where a ``fit()``'s time went.

    python -m deeplearning4j_tpu.monitor.profile <trace dir or .xplane.pb>

reads a trace that ``DL4J_PROFILE=<dir>`` (or any ``jax.profiler`` run
with ``DL4J_TRACE_ANNOTATIONS=1``) wrote and prints one JSON summary;
``DL4J_PROFILE`` writes the same as ``<dir>/fitN/summary.json`` when
the ``fit()`` returns.  Per chip it holds

* ``busy_s`` / ``window_s``: the union of the device's operations, and
  the stretch from the first to the last;
* ``device_s``: device seconds by direction (``fwd``, ``bwd``,
  ``update``, ``loss``, ``kernel``, ``unscoped``) and layer type, read from the
  ``jax.named_scope`` names the engines put on the step
  (``fwd/<LayerType>/<index or vertex>``, ``loss``, ``update``; JAX
  wraps the backward's operations in ``transpose(jvp(...))`` of the
  forward's name), ``top_scopes``, the ten longest scopes, and
  ``sub_scope_s``, the seconds of the parts a layer names inside its
  own scope (``<direction>/<LayerType>/<part>``), over all vertices of
  that type.  A part's scope stands outside the control flow the part
  contains (``.../experts/while/body/...`` reads ``experts``); the
  ``while``, ``conditional`` or ``call`` event itself spans the events
  of the instructions it runs, which are on the same line, so it counts
  as busy time and is left out of every sum.  An operation the compiler
  expands into kernels of its own name loses its scope (XLA's grouped
  matrix product, ``ragged-dot-*``).  ``recomputed_s`` is the part of
  ``device_s["bwd"]``, by layer type, that is a forward pass run again
  under ``jax.checkpoint`` (``rematted_computation`` in the name), and
  ``recomputed_kernels_s`` the same by Mosaic kernel (a Pallas kernel,
  or one the compiler emits itself, ``ragged-dot-*``), with an entry
  for every kernel that ran: 0.0 says a kernel's launches were all
  first ones (what a recomputed run keeps of a kernel's outputs,
  ``ops/recompute.py``, it does not launch the kernel for again).
  The layer classes declare both, their parts and the kernels they
  claim for a part (:func:`layer_tables`); a claimed kernel reads under
  the direction ``kernel``, since forward and backward cannot be told
  apart;
* ``idle_s``: the device's idle seconds by the host phase they fell in.
  Device and host events share the trace's clock, so each gap between
  device operations is shared out over the ``fit/step`` phases it
  overlaps, by intersection of intervals; what no phase covers goes to
  ``outside_fit``;
* ``wait_lag_s``: the median time from the device's last operation
  inside a ``block_until_ready`` phase to that phase's end: how late
  the host learns that a step is done;
* ``clock_bounds_s``: the bounds causality sets on the offset of the
  device's clock against the host's, the error bar on every idle gap
  above; and, for the whole trace, ``steps`` and ``stalls``: the steps
  that ran late by the fit loop's own rule, each with the device's busy
  and idle time inside it and whether the device, the runtime or the
  host was late (monitor/profile_steps.py);
* ``host_s``: seconds and count of every ``fit/step`` phase.

An operation's scope is its HLO ``op_name`` metadata.  The TPU runtime
writes it into the trace as the ``tf_op`` stat of the event's
*metadata* (one record an instruction, which ``jax.profiler.ProfileData``
does not show), so this module reads the ``.xplane.pb`` itself: the file
is one protobuf message whose few fields it needs are walked by hand
(:func:`_fields`; field numbers from tsl's ``xplane.proto``).  A fusion
carries the ``op_name`` of its root instruction; copies the compiler
inserted carry none and read ``unscoped``.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import statistics
import sys
from typing import (
    Dict, Iterable, Iterator, List, Optional, Sequence, Tuple)

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
PHASE = re.compile(r"^fit/step/(\w+)$")
#: the stat of a device event's metadata that holds the HLO op_name
SCOPE_STATS = ("tf_op",)
# JAX closes jvp( and transpose( right after the vertex's name: a part's
# name follows the parentheses (``jvp(fwd/<Type>/<vertex>)/<part>/<op>``),
# or the body of a loop that runs the layer's parts once a trip
# (``.../<vertex>)/while/body/<part>/<op>``: the expert layer's later
# row segments)
LAYER_SCOPE = re.compile(
    r"fwd/(\w+)/([^/()]+)\)*(?:/while/body)*(?:/(\w+))?")
UPDATE_SCOPE = re.compile(r"(?:^|[/(])update(?:[/)]|$)")
LOSS_SCOPE = re.compile(r"(?:^|[/(])loss(?:[/)]|$)")
#: an instruction that runs other instructions: its event spans theirs
CONTAINER = re.compile(r"\s(?:while|conditional|call)\(")
#: how ``jax.checkpoint`` names the forward it runs again in the backward
#: pass (the backward's own operations stand beside it, not under it)
REMATTED = "rematted_computation"
#: a Mosaic kernel's event: a custom call whose instruction is named as
#: the kernel is (``%dl4j_flash_fwd.3 = ... custom-call(...)``)
PALLAS_CALL = "tpu_custom_call"
#: the op_name such an event goes by: counted as busy, summed nowhere
SPANS_OTHERS = "<spans others>"
OUTSIDE = "outside_fit"
#: the phase in which the host waits for the device
WAIT = "block_until_ready"

Interval = Tuple[float, float]


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of every field of one protobuf message:
    an int for a varint, a memoryview for a length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:                       # fixed64 / fixed32
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _map_entry(buf):
    key = value = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane(buf) -> Tuple[str, list]:
    """(name, [(line name, [(start_ns, duration_ns, event name,
    op_name)])]) of one XPlane."""
    name, lines, metadata, stat_names = "", [], {}, {}
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:                # map<int64, XEventMetadata>
            key, value = _map_entry(v)
            metadata[key] = value
        elif f == 5:                # map<int64, XStatMetadata>
            key, value = _map_entry(v)
            stat_names[key] = next(
                (_text(x) for g, x in _fields(value) if g == 2), "")
    scope_ids = {i for i, n in stat_names.items() if n in SCOPE_STATS}
    named = {}
    for key, value in metadata.items():
        ev_name = op_name = ""
        for f, v in _fields(value):
            if f == 2:
                ev_name = _text(v)
            elif f == 5 and not op_name:            # XStat
                stat = dict(_fields(v))
                if stat.get(1) in scope_ids:
                    op_name = (_text(stat[5]) if 5 in stat
                               else stat_names.get(stat.get(7), ""))
        named[key] = (ev_name, op_name)
    out = []
    for line in lines:
        line_name, t0, events = "", 0, []
        for f, v in _fields(line):
            if f == 2:
                line_name = _text(v)
            elif f == 3:
                t0 = v
            elif f == 4:
                events.append(v)
        rows = []
        for ev in events:
            e = dict(_fields(ev))
            rows.append((t0 + e.get(2, 0) / 1e3, e.get(3, 0) / 1e3)
                        + named.get(e.get(1), ("", "")))
        out.append((line_name, rows))
    return name, out


def load(path: str) -> List[Tuple[str, list]]:
    """The planes of the newest ``.xplane.pb`` under ``path`` (or of
    ``path`` itself), as :func:`_plane` gives them."""
    if not path.endswith(".pb"):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    with open(path, "rb") as f:
        space = memoryview(f.read())
    return [_plane(v) for f, v in _fields(space) if f == 1]


Tables = Tuple[Dict[str, frozenset], Dict[str, Tuple[str, str]]]


def layer_tables() -> Tables:
    """({layer type: the parts it names inside its scope}, {prefix of a
    kernel's name: (layer type, part) that claims it}), as the registered
    layer and vertex classes declare them (``scope_parts``,
    ``scope_kernels``)."""
    from deeplearning4j_tpu.nn.conf.graph_conf import VERTEX_REGISTRY
    from deeplearning4j_tpu.nn.conf.layers import LAYER_REGISTRY
    parts, kernels = {}, {}
    for kind, cls in {**LAYER_REGISTRY, **VERTEX_REGISTRY}.items():
        if cls.scope_parts:
            parts[kind] = frozenset(cls.scope_parts)
        for prefix, part in cls.scope_kernels.items():
            if prefix in kernels:
                raise ValueError(f"kernels {prefix}* are claimed by "
                                 f"{kernels[prefix][0]} and by {kind}")
            kernels[prefix] = (kind, part)
    return parts, kernels


def classify(op_name: str, tables: Optional[Tables] = None
             ) -> Tuple[str, str, str]:
    """(direction, layer type, scope) of an HLO ``op_name``."""
    owner = _claimed(op_name, (tables or layer_tables())[1])
    if owner:
        return "kernel", owner[0], f"kernel/{owner[0]}"
    if UPDATE_SCOPE.search(op_name):
        return "update", "update", "update"
    m = LAYER_SCOPE.search(op_name)
    if m:
        direction = "bwd" if "transpose(" in op_name else "fwd"
        return direction, m.group(1), f"{direction}/{m.group(1)}/{m.group(2)}"
    if LOSS_SCOPE.search(op_name):
        return "loss", "loss", "loss"
    return "unscoped", "unscoped", "unscoped"


def _instruction(event_name: str) -> str:
    """``%ragged-dot-none.3 = bf16[...] custom-call(...)`` ->
    ``ragged-dot-none.3``"""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _claimed(op_name: str, kernels) -> Optional[Tuple[str, str]]:
    """(layer type, part) of a kernel the compiler named itself."""
    for prefix, owner in kernels.items():
        if op_name.startswith(prefix):
            return owner
    return None


def sub_scope(op_name: str, tables: Optional[Tables] = None
              ) -> Optional[str]:
    """``<direction>/<LayerType>/<part>`` of an operation inside a part
    its layer names (anything else that follows a vertex's name is an
    operation's own name), else None."""
    parts, kernels = tables or layer_tables()
    owner = _claimed(op_name, kernels)
    if owner:
        return f"kernel/{owner[0]}/{owner[1]}"
    m = LAYER_SCOPE.search(op_name)
    if not m or m.group(3) not in parts.get(m.group(1), ()):
        return None
    direction = "bwd" if "transpose(" in op_name else "fwd"
    return f"{direction}/{m.group(1)}/{m.group(3)}"


def union(intervals: Iterable[Interval]) -> Tuple[float, List[Interval]]:
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


def disjoint(phases: Sequence[Tuple[float, float, str]]):
    """The phases sorted by start, each clipped to begin no earlier than
    the one before it ended (spans of one loop tile; a nested span of
    another path must not count a nanosecond twice)."""
    out, end = [], float("-inf")
    for s, e, name in sorted(phases):
        s = max(s, end)
        if e > s:
            out.append((s, e, name))
            end = e
    return out


def share_gap(gap: Interval, phases: Sequence[Tuple[float, float, str]],
              starts: Optional[List[float]] = None) -> Dict[str, float]:
    """Share one idle gap out over the (disjoint, sorted) host phases
    it overlaps; the remainder goes to ``outside_fit``.  The values sum
    to the gap's length."""
    a, b = gap
    if starts is None:
        starts = [s for s, _, _ in phases]
    out: Dict[str, float] = {}
    covered = 0.0
    i = max(0, bisect.bisect_right(starts, a) - 1)
    while i < len(phases) and phases[i][0] < b:
        s, e, name = phases[i]
        overlap = min(b, e) - max(a, s)
        if overlap > 0:
            out[name] = out.get(name, 0.0) + overlap
            covered += overlap
        i += 1
    if b - a - covered > 0:
        out[OUTSIDE] = b - a - covered
    return out


def host_phases(planes) -> List[Tuple[float, float, str]]:
    """[(start_ns, end_ns, phase)] of the ``fit/step`` annotations."""
    out = []
    for name, lines in planes:
        if not name.startswith("/host:"):
            continue
        for _, events in lines:
            for start, duration, ev_name, _ in events:
                m = PHASE.match(ev_name)
                if m:
                    out.append((start, start + duration, m.group(1)))
    return out


def _ops_lines(planes) -> Dict[int, list]:
    """{chip: its ops line's events as :func:`_plane` gives them}."""
    out = {}
    for name, lines in planes:
        m = DEVICE_PLANE.match(name)
        if not m:
            continue
        for line_name, events in lines:
            if line_name == OPS_LINE:
                out[int(m.group(1))] = events
    return out


def device_events(planes, tables: Optional[Tables] = None
                  ) -> Dict[int, List[Tuple[float, float, str]]]:
    """{chip: [(start_ns, duration_ns, op_name)]} of the ops lines."""
    kernels = (tables or layer_tables())[1]
    return {chip: [(s, d, _goes_by(text, op, kernels))
                   for s, d, text, op in events]
            for chip, events in _ops_lines(planes).items()}


def recomputed_kernels(events) -> Dict[str, float]:
    """{Mosaic kernel: seconds of its launches inside a forward pass run
    again} over one ops line's events.  Every kernel that ran has an
    entry; the instruction's number, which changes from compile to
    compile, is cut from the name."""
    out: Dict[str, float] = {}
    for _, d, text, op_name in events:
        if PALLAS_CALL not in text:
            continue
        name = re.sub(r"[.\d]+$", "", _instruction(text))
        out.setdefault(name, 0.0)
        if REMATTED in op_name:
            out[name] += d / 1e9
    return out


def _goes_by(text: str, op_name: str, kernels) -> str:
    """What an event of the ops line is summed under: an instruction
    that runs others under no scope at all, a kernel the compiler named
    itself under its instruction's name (the event's name is the HLO's
    text), anything else under its ``op_name``."""
    if CONTAINER.search(text):
        return SPANS_OTHERS
    ins = _instruction(text)
    return ins if _claimed(ins, kernels) else op_name


def summarize(planes) -> dict:
    """The summary of one trace (see the module's docstring).  A trace
    with no device plane (a CPU run) has no ``chips``."""
    tables = layer_tables()
    phases = disjoint(host_phases(planes))
    starts = [s for s, _, _ in phases]
    host: Dict[str, List[float]] = {}
    for s, e, name in phases:
        h = host.setdefault(name, [0.0, 0])
        h[0] += (e - s) / 1e9
        h[1] += 1
    chips = {}
    ops_lines = _ops_lines(planes)
    by_chip = device_events(planes, tables)
    # imported here: profile_steps builds on this module's helpers
    from deeplearning4j_tpu.monitor import profile_steps
    by_step = profile_steps.steps_summary(planes, phases, by_chip)
    clock_bounds = by_step.pop("clock_bounds_s")
    for chip, evs in sorted(by_chip.items()):
        if not evs:
            continue
        busy, gaps = union((s, s + d) for s, d, _ in evs)
        by_dir: Dict[str, Dict[str, float]] = {}
        by_scope: Dict[str, float] = {}
        by_sub: Dict[str, float] = {}
        by_remat: Dict[str, float] = {}
        timed = [(d, op) for _, d, op in evs if op != SPANS_OTHERS]
        for d, op_name in timed:
            direction, kind, scope = classify(op_name, tables)
            by_kind = by_dir.setdefault(direction, {})
            by_kind[kind] = by_kind.get(kind, 0.0) + d / 1e9
            by_scope[scope] = by_scope.get(scope, 0.0) + d / 1e9
            if REMATTED in op_name:
                by_remat[kind] = by_remat.get(kind, 0.0) + d / 1e9
            sub = (sub_scope(op_name, tables)
                   if direction in ("fwd", "bwd", "kernel") else None)
            if sub:
                by_sub[sub] = by_sub.get(sub, 0.0) + d / 1e9
        idle: Dict[str, float] = {}
        for gap in gaps:
            for name, ns in share_gap(gap, phases, starts).items():
                idle[name] = idle.get(name, 0.0) + ns / 1e9
        ops_s = sum(d for d, _ in timed) / 1e9
        ends = sorted(s + d for s, d, _ in evs)
        lags = []
        for s, e, name in phases:
            if name == WAIT:
                i = bisect.bisect_right(ends, e) - 1
                if i >= 0 and ends[i] >= s:
                    lags.append((e - ends[i]) / 1e9)
        chips[str(chip)] = {
            "busy_s": busy / 1e9,
            "window_s": (max(s + d for s, d, _ in evs)
                         - min(s for s, _, _ in evs)) / 1e9,
            "scoped_share": 1.0 - by_scope.get("unscoped", 0.0) / ops_s
            if ops_s else 0.0,
            "device_s": by_dir,
            "sub_scope_s": by_sub,
            "recomputed_s": by_remat,
            "recomputed_kernels_s": recomputed_kernels(ops_lines[chip]),
            "top_scopes": sorted(by_scope.items(),
                                 key=lambda kv: -kv[1])[:10],
            "idle_s": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
            "wait_lag_s": statistics.median(lags) if lags else None,
            "clock_bounds_s": clock_bounds.get(str(chip)),
        }
    return {"chips": chips, "host_s": host, **by_step}


def write_summary(trace_dir: str) -> str:
    """Summarize the trace under ``trace_dir`` into its
    ``summary.json``; returns the file's path."""
    out = os.path.join(trace_dir, "summary.json")
    with open(out, "w") as f:
        json.dump(summarize(load(trace_dir)), f, indent=1)
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1].strip(), file=sys.stderr)
        return 2
    json.dump(summarize(load(argv[0])), sys.stdout, indent=1)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
