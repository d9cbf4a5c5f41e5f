#!/usr/bin/env python3
"""Long windows of one cell, every stall tabled (run by hand through the
chip tool, never by the benchmark's own runs):

    python3 benchmark/tests/readings_stalls.py --workload <cell> --windows 4 --seconds 20

Set-up once, as the cell's own run makes it, then N windows through the
mode's own ``window()``.  For each window one JSON line, on standard
output and in ``chiprun_out/readings_stalls_<cell>.jsonl``: its
``train_samples_per_s`` and ``step_ms_p95``, the median and standard
deviation of its steps proper (the program's ``fit.step`` records but the
first), the first step's phases, and every ``fit.stall`` event of the
window: the holder phase, the excess, every phase's excess and what the
host thread did meanwhile.  A last line sums the stalls by holder and
reads the program's two counters.  With ``--profile <dir>`` the windows
run under ``DL4J_PROFILE`` and each line also holds the ``steps``,
``stalls``, ``clock_bounds_s`` and ``wait_lag_s`` of that fit's
``summary.json`` (kept as ``chiprun_out/stalls_summary_<cell>_<n>.json``),
and the steps' rate by the records, which leaves out the time the trace
takes to stop and to be read.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

def _ms(seconds):
    return round(seconds * 1e3, 3)


def _stall_row(e):
    return {"iteration": e["iteration"], "holder": e["holder"],
            "first_of_fit": e["first_of_fit"], "excess_ms": _ms(e["excess_s"]),
            "step_ms": _ms(e["step_s"]), "median_ms": _ms(e["median_s"]),
            "phase_excess_ms": {p: _ms(s) for p, s in sorted(
                e["phase_excess_s"].items(), key=lambda kv: -kv[1])[:4]},
            "cpu_ms": _ms(e["cpu_s"]), "gc_ms": _ms(e["gc_s"]),
            **{k: e[k] for k in ("gc_collections", "switches_voluntary",
                                 "switches_involuntary", "major_faults")}}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2024100437)
    ap.add_argument("--windows", type=int, default=4)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--profile", default=None,
                    help="run the windows under DL4J_PROFILE=<dir>")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    # a window's events stay in the ring until they are read
    os.environ.setdefault("DL4J_JOURNAL_CAPACITY", "32768")
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from benchmark import run
    bench = run.load_json("BENCHMARK.json")
    cell = run.find(bench["workloads"], args.workload, "workload")
    cfg = run.load_json(run.find(bench["configs"], cell["config"],
                                 "configuration")["file"])
    traffic = run.load_json("benchmark", "traffic", cell["traffic"] + ".json")
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("readings: no TPU")
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, f"readings_stalls_{cell['name']}.jsonl"), "a")

    def say(row):
        line = json.dumps(row)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    mode = importlib.import_module("benchmark.modes." + traffic["mode"]).Mode(
        cfg, traffic, args.seed, cell["chips"], args.rehearse)
    mode.setup()
    from deeplearning4j_tpu import monitor
    journal = monitor.get_journal()
    if args.profile:
        os.environ["DL4J_PROFILE"] = args.profile
    by_holder, steps_all = {}, 0
    for n in range(args.windows):
        seq0 = journal.total_emitted
        res = mode.window(args.seconds)
        evs = [e for e in journal.tail() if e["seq"] > seq0]
        records = [e for e in evs if e["type"] == "fit.step"]
        stalls = [e for e in evs if e["type"] == "fit.stall"]
        proper = [e["step_s"] for e in records[1:] if not e["compiling"]]
        row = {"cell": cell["name"], "seed": args.seed, "window": n,
               "steps": res["window"]["steps"], "records": len(records),
               **res["end_to_end"],
               "step_ms_median": _ms(statistics.median(proper)) if proper else None,
               "step_ms_stdev": _ms(statistics.pstdev(proper)) if proper else None,
               "step_ms_max": _ms(max(proper)) if proper else None,
               "records_samples_per_s":
                   len(records) * mode.batch / sum(e["step_s"] for e in records)
                   if records else None,
               "first_step_ms": {p: _ms(s) for p, s in
                                 records[0]["phases"].items()} if records else None,
               "stalls": [_stall_row(e) for e in stalls]}
        if args.profile:
            src = os.path.join(args.profile, f"fit{n}", "summary.json")
            with open(src) as f:
                summary = json.load(f)
            shutil.copy(src, os.path.join(
                out_dir, f"stalls_summary_{cell['name']}_{n}.json"))
            row["profile"] = {
                "steps": summary["steps"], "stalls": summary["stalls"],
                "chips": {c: {k: v[k] for k in ("clock_bounds_s", "wait_lag_s",
                                                "busy_s", "window_s", "idle_s")}
                          for c, v in summary["chips"].items()}}
            shutil.rmtree(os.path.join(args.profile, f"fit{n}"),
                          ignore_errors=True)
        say(row)
        steps_all += len(records)
        for e in stalls:
            h = by_holder.setdefault(e["holder"], [0, 0.0, 0])
            h[0] += 1
            h[1] += e["excess_s"]
            h[2] += bool(e["first_of_fit"])
    snap = monitor.get_registry().snapshot()
    say({"cell": cell["name"], "seed": args.seed, "windows": args.windows,
         "steps": steps_all,
         "stalls_by_holder": {h: {"stalls": n, "excess_ms": _ms(s),
                                  "first_of_fit": f}
                              for h, (n, s, f) in sorted(by_holder.items())},
         "counters": {name: {s["labels"]["phase"]: s["value"]
                             for s in snap.get(name, {}).get("samples", [])}
                      for name in ("dl4j_fit_stalls_total",
                                   "dl4j_fit_stall_seconds_total")},
         "iterations_total": sum(s["value"] for s in snap.get(
             "dl4j_fit_iterations_total", {}).get("samples", []))})


if __name__ == "__main__":
    main()
