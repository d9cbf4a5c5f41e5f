"""Exposition: render a registry snapshot as Prometheus text-format
v0.0.4 or JSON, and parse the text format back (the round-trip check
``tests/test_monitor.py`` pins, and a debugging convenience).

Histogram families render as real Prometheus histograms
(``_bucket``/``_sum``/``_count``) plus a sibling gauge family
``<name>_quantile{quantile="0.5|0.95|0.99"}`` carrying the reservoir
percentiles — scrape-side systems get aggregatable buckets AND the
exact-ish percentiles the serving stats RPC always reported, without
bending the text format (a histogram family may not carry quantile
lines itself).
"""

from __future__ import annotations

import json
import math
import re
from typing import Dict, List, Optional, Tuple

_QUANTILES = ("0.5", "0.95", "0.99")
_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)\s*$")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')

CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"


def _escape(v: str) -> str:
    return (str(v).replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _unescape(v: str) -> str:
    return (v.replace("\\n", "\n").replace('\\"', '"')
            .replace("\\\\", "\\"))


def _fmt(v) -> str:
    if v is None:
        return "NaN"
    v = float(v)
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(v)


def _label_str(labels: Dict[str, str], extra: Optional[Tuple[str, str]] = None
               ) -> str:
    items = list(labels.items())
    if extra is not None:
        items.append(extra)
    if not items:
        return ""
    return "{" + ",".join(f'{k}="{_escape(v)}"' for k, v in items) + "}"


def render_prometheus(snapshot: Dict[str, dict]) -> str:
    """Prometheus text-format v0.0.4 over a
    ``MetricsRegistry.snapshot()`` dict."""
    lines: List[str] = []
    for name, fam in sorted(snapshot.items()):
        if not _NAME_RE.match(name):
            continue
        kind = fam.get("type", "untyped")
        if fam.get("help"):
            lines.append(f"# HELP {name} {_escape(fam['help'])}")
        lines.append(f"# TYPE {name} {kind}")
        quantile_lines: List[str] = []
        for s in fam.get("samples", []):
            labels = s.get("labels", {})
            if kind == "histogram":
                for le, c in s.get("buckets", {}).items():
                    lines.append(
                        f"{name}_bucket{_label_str(labels, ('le', le))} "
                        f"{_fmt(c)}")
                lines.append(f"{name}_sum{_label_str(labels)} "
                             f"{_fmt(s.get('sum', 0.0))}")
                lines.append(f"{name}_count{_label_str(labels)} "
                             f"{_fmt(s.get('count', 0))}")
                for q, key in zip(_QUANTILES, ("p50", "p95", "p99")):
                    if s.get(key) is not None:
                        quantile_lines.append(
                            f"{name}_quantile"
                            f"{_label_str(labels, ('quantile', q))} "
                            f"{_fmt(s[key])}")
            else:
                lines.append(f"{name}{_label_str(labels)} "
                             f"{_fmt(s.get('value', 0.0))}")
        if quantile_lines:
            lines.append(f"# TYPE {name}_quantile gauge")
            lines.extend(quantile_lines)
    return "\n".join(lines) + "\n"


def render_json(snapshot: Dict[str, dict], indent: Optional[int] = None
                ) -> str:
    return json.dumps(snapshot, indent=indent, sort_keys=True)


def parse_prometheus(text: str) -> Dict[str, dict]:
    """Parse Prometheus text format back into
    ``{family: {"type": ..., "samples": [(name, labels, value), ...]}}``.
    Raises ValueError on malformed lines or samples outside any declared
    family — the validity check the test suite round-trips through."""
    families: Dict[str, dict] = {}
    current: Optional[str] = None
    for lineno, raw in enumerate(text.splitlines(), 1):
        line = raw.strip()
        if not line:
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) != 4 or parts[3] not in (
                    "counter", "gauge", "histogram", "summary", "untyped"):
                raise ValueError(f"line {lineno}: bad TYPE line {raw!r}")
            current = parts[2]
            families[current] = {"type": parts[3], "samples": []}
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            raise ValueError(f"line {lineno}: unparseable sample {raw!r}")
        name, label_blob, value = m.group(1), m.group(2), m.group(3)
        fam = None
        for suffix in ("", "_bucket", "_sum", "_count"):
            base = name[:-len(suffix)] if suffix and name.endswith(suffix) \
                else (name if not suffix else None)
            if base and base in families:
                fam = base
                break
        if fam is None:
            raise ValueError(f"line {lineno}: sample {name!r} outside any "
                             "declared family")
        labels: Dict[str, str] = {}
        if label_blob:
            matched = _LABEL_RE.findall(label_blob)
            rebuilt = ",".join(f'{k}="{v}"' for k, v in matched)
            if rebuilt != label_blob:
                raise ValueError(f"line {lineno}: bad labels {label_blob!r}")
            labels = {k: _unescape(v) for k, v in matched}
        try:
            val = float(value)
        except ValueError:
            raise ValueError(f"line {lineno}: bad value {value!r}")
        families[fam]["samples"].append((name, labels, val))
    return families


# ---------------------------------------------------------------------------
# Federation merge helpers (monitor/federation.py builds on these): turn a
# parsed text scrape back into the snapshot shape every renderer walks, and
# merge N snapshot-shaped sources into ONE federated snapshot.
# ---------------------------------------------------------------------------
def _le_key(le: str) -> float:
    return math.inf if le == "+Inf" else float(le)


def snapshot_from_parsed(parsed: Dict[str, dict]) -> Dict[str, dict]:
    """Reconstruct a ``MetricsRegistry.snapshot()``-shaped dict from
    :func:`parse_prometheus` output, so a scraped replica's families can
    be merged and re-rendered with the same code that serves the local
    registry.  Histogram ``_bucket``/``_sum``/``_count`` samples regroup
    by their non-``le`` label set; reservoir percentiles are not carried
    by the text format, so rebuilt histogram samples omit them (the
    renderer skips absent quantiles)."""
    out: Dict[str, dict] = {}
    for fam, doc in parsed.items():
        kind = doc.get("type", "untyped")
        if kind != "histogram":
            samples = [{"labels": dict(labels), "value": value}
                       for _name, labels, value in doc.get("samples", ())]
            out[fam] = {
                "type": kind, "help": "",
                "label_names": sorted({k for s in samples
                                       for k in s["labels"]}),
                "samples": samples}
            continue
        groups: Dict[Tuple, dict] = {}
        for name, labels, value in doc.get("samples", ()):
            key_labels = {k: v for k, v in labels.items() if k != "le"}
            key = tuple(sorted(key_labels.items()))
            g = groups.setdefault(key, {"labels": key_labels, "buckets": {},
                                        "sum": 0.0, "count": 0.0})
            if name.endswith("_bucket"):
                g["buckets"][labels.get("le", "+Inf")] = value
            elif name.endswith("_sum"):
                g["sum"] = value
            elif name.endswith("_count"):
                g["count"] = value
        samples = []
        for key in sorted(groups):
            g = groups[key]
            g["buckets"] = dict(sorted(g["buckets"].items(),
                                       key=lambda kv: _le_key(kv[0])))
            samples.append(g)
        out[fam] = {
            "type": kind, "help": "",
            "label_names": sorted({k for s in samples
                                   for k in s["labels"]}),
            "samples": samples}
    return out


def _merged_buckets(srcs: List[dict]) -> Dict[str, float]:
    """Sum cumulative bucket counts over the union of each source's
    ``le`` ladder: a source missing an ``le`` contributes its count at
    its greatest bucket at-or-below it (buckets are cumulative, so that
    carry-forward is exact for its own ladder)."""
    les: set = set()
    per_src: List[List[Tuple[float, float]]] = []
    for s in srcs:
        b = s.get("buckets") or {}
        les.update(b)
        per_src.append(sorted(((_le_key(le), v) for le, v in b.items())))
    out: Dict[str, float] = {}
    for le in sorted(les, key=_le_key):
        lv, total = _le_key(le), 0.0
        for pairs in per_src:
            cum = 0.0
            for sle, v in pairs:
                if sle <= lv:
                    cum = v
                else:
                    break
            total += cum
        out[le] = total
    return out


def merge_snapshots(sources: Dict[str, Dict[str, dict]]) -> Dict[str, dict]:
    """Merge snapshot-shaped sources (replica name → snapshot) into one
    federated snapshot (docs/OBSERVABILITY.md "Fleet federation & SLOs"):

    * **counters** sum across sources per label set — fleet totals;
    * **histograms** sum bucket counts (cumulative, union ladder),
      ``sum`` and ``count`` per label set — fleet-aggregatable;
    * **gauges** (and untyped/summary samples) keep one sample per
      source under an added ``replica`` label — a gauge is a per-process
      reading, summing it would fabricate a meaningless number.  A
      sample that ALREADY carries a ``replica`` label keeps it (the
      federation's own per-replica staleness gauges).

    A family whose type disagrees across sources keeps the first type
    seen and drops conflicting sources' samples (re-declaration bug,
    surfaced by the missing series rather than a crash)."""
    merged: Dict[str, dict] = {}
    for src in sorted(sources):
        snap = sources[src]
        for fam, doc in snap.items():
            kind = doc.get("type", "untyped")
            m = merged.setdefault(fam, {"type": kind,
                                        "help": doc.get("help", ""),
                                        "_names": set(), "_acc": {}})
            if m["type"] != kind:
                continue
            if not m["help"] and doc.get("help"):
                m["help"] = doc["help"]
            for s in doc.get("samples", ()):
                labels = dict(s.get("labels") or {})
                if kind not in ("counter", "histogram"):
                    labels.setdefault("replica", src)
                key = tuple(sorted(labels.items()))
                m["_names"].update(labels)
                acc = m["_acc"].get(key)
                if kind == "histogram":
                    if acc is None:
                        acc = m["_acc"][key] = {
                            "labels": labels, "sum": 0.0, "count": 0.0,
                            "_srcs": []}
                    acc["sum"] += float(s.get("sum") or 0.0)
                    acc["count"] += float(s.get("count") or 0.0)
                    acc["_srcs"].append(s)
                else:
                    if acc is None:
                        acc = m["_acc"][key] = {"labels": labels,
                                                "value": 0.0}
                    if kind == "counter":
                        acc["value"] += float(s.get("value") or 0.0)
                    else:
                        acc["value"] = float(s.get("value") or 0.0)
    out: Dict[str, dict] = {}
    for fam, m in merged.items():
        samples = []
        for key in sorted(m["_acc"]):
            acc = m["_acc"][key]
            srcs = acc.pop("_srcs", None)
            if srcs is not None:
                acc["buckets"] = _merged_buckets(srcs)
            samples.append(acc)
        out[fam] = {"type": m["type"], "help": m["help"],
                    "label_names": sorted(m["_names"]),
                    "samples": samples}
    return out
