#!/usr/bin/env python3
"""The benchmark's one command: one process, one cell, once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names its
configuration (``benchmark/configs/``), its traffic (``benchmark/traffic/``,
whose ``mode`` names the module under ``benchmark/modes/`` that drives the
entry point), its limits (``benchmark/limits/<cell>.json``) and the
per-layer metrics, each read by ``benchmark/metrics/<name>.py``.  The last
line of standard output is one JSON object to the driver's contract.
Without a TPU the command exits non-zero and prints no result;
``--rehearse`` is the only way it runs elsewhere (tiny sizes from the
traffic file, the line marked as no measurement).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import importlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def say(**info):
    print(json.dumps(info, default=str), flush=True)


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json")


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def reports(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run off-chip at the traffic file's tiny sizes; "
                         "the line is marked and is no measurement")
    args = ap.parse_args(argv)

    bench = load_json("BENCHMARK.json")
    cell = find(bench["workloads"], args.workload, "workload")
    config = find(bench["configs"], cell["config"], "configuration")
    cfg = load_json(config["file"])
    traffic = load_json("benchmark", "traffic", cell["traffic"] + ".json")
    limits = load_json("benchmark", "limits", cell["name"] + ".json")["limits"]

    # one compile cache, at a fixed place inside the checkout unless the
    # machine names one; the program takes the same variable
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(time.perf_counter())
        if name == "/jax/core/compile/backend_compile_duration" else None)

    devices = jax.devices()
    d0 = devices[0]
    T_IMPORTED = time.perf_counter()
    if not args.rehearse:
        if d0.platform != "tpu":
            raise SystemExit(f"benchmark: no TPU: jax.devices()[0].platform "
                             f"is {d0.platform!r}")
        if len(devices) < cell["chips"]:
            raise SystemExit(f"benchmark: {cell['name']} needs "
                             f"{cell['chips']} chip(s), JAX reports {len(devices)}")
        from benchmark.harness.peaks import peaks_for
        peaks = peaks_for(d0.device_kind)
    else:
        peaks = None

    mode = importlib.import_module("benchmark.modes." + traffic["mode"]).Mode(
        cfg, traffic, args.seed, cell["chips"], args.rehearse)
    mode.setup()
    # set-up is the system's: from the device being there to the start of
    # the window.  The interpreter's, JAX's and the TPU runtime's own start
    # (T_START to T_IMPORTED, 10 to 14 s on a v5e, in spells of either) is
    # neither the program's nor steady, and goes on the information line.
    setup_s = time.perf_counter() - T_IMPORTED

    res = mode.window(args.seconds)
    w = res["window"]
    in_window = sum(1 for t in compiles if w["t0"] <= t <= w["t1"])
    # the fullest chip's peak: its arrays' peak plus what the runtime
    # reserved for the programs' own scratch (on the TPU the activations
    # of a step live there and peak_bytes_in_use does not count them)
    peak = max((int(m.get("peak_bytes_in_use", 0))
                + int(m.get("peak_bytes_reserved", 0)))
               for m in ((d.memory_stats() or {})
                         for d in devices[:cell["chips"]]))
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": cell["chips"] if not args.rehearse else len(devices),
              "memory_peak_bytes": peak}
    say(info="window", steps=w["steps"], seconds=w["seconds"],
        setup_s=setup_s, setup_marks=getattr(mode, "setup_marks", {}),
        runtime_start_s=T_IMPORTED - T_START, compiles_in_window=in_window,
        first_score=w["scores"][:1], last_score=w["scores"][-1:],
        spans={k: [round(s, 4), c] for k, (s, c) in w["spans"].items()})

    ctx = {"cell": cell, "cfg": cfg, "traffic": traffic, "window": w,
           "layers": mode.layers, "peaks": peaks, "chips": cell["chips"],
           "trace": None}
    breakdown = None
    if args.trace:
        from benchmark.harness import trace_reduce
        tdir = os.path.join(ROOT, ".bench_trace", cell["name"])
        shutil.rmtree(tdir, ignore_errors=True)
        os.makedirs(tdir)
        os.environ["DL4J_TRACE_ANNOTATIONS"] = "1"   # the program's spans, in the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # the Python tracer slows the host it measures
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            stretch = mode.traced()
        finally:
            jax.profiler.stop_trace()
            os.environ.pop("DL4J_TRACE_ANNOTATIONS", None)
        red = trace_reduce.reduce_dir(tdir, mode.layers, chips=cell["chips"])
        red["steps"] = stretch["steps"]
        ctx["trace"] = red
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        breakdown = {"device_ops": red["device_ops"][:10],
                     "idle_gaps": red["idle_gaps"][:10]}
        keep = os.environ.get("BENCHMARK_KEEP_TRACE")
        if keep:
            shutil.copytree(tdir, keep, dirs_exist_ok=True)
        shutil.rmtree(tdir, ignore_errors=True)

    rows = mode.check(limits)
    rows.append(("window_compiles", in_window, 0, in_window == 0))
    rows.append(("failed", res["failed"], 0, res["failed"] == 0))
    correct = all(ok for *_, ok in rows)

    metrics = {}
    if args.trace:
        for m in bench["per_layer"]:
            if not reports(m, cell["name"]):
                continue
            reader = importlib.import_module("benchmark.metrics." + m["name"])
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = dict(res["end_to_end"], setup_s=setup_s)
        for m in bench["end_to_end"]:
            if reports(m, cell["name"]):
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}

    compared = {n: {"value": v, "limit": lim} for n, v, lim, _ in rows}
    for n, v, lim, ok in rows:
        print(f"compared {n}: {v!r} limit {lim!r} {'ok' if ok else 'NOT OK'}"
              f"{' at ' + mode.where[n] if n in getattr(mode, 'where', {}) else ''}",
              file=sys.stderr, flush=True)
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if args.rehearse:
        line["rehearsal"] = "off-chip at tiny sizes: no measurement"
    line["compared"] = compared
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
