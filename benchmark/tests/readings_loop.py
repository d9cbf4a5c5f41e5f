#!/usr/bin/env python3
"""``tests/readings.py`` for a cell of mode ``fit_looped``, whose batch of
one sequence has no half to leave out: the upper readings come from the
control's precision and from two faults of the mechanism put in the
program's place, a pass left out (``passes`` one fewer) and the stack's
leaves taking their gradient from the last pass alone (``grad_passes``).
Run by hand through the chip tool, never by the benchmark's own runs:

    python3 benchmark/tests/readings_loop.py --workload <cell> --seeds 3 --controls 2

One JSON line a seed, on standard output and in
``chiprun_out/readings_<cell>.jsonl``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1000)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from benchmark import run
    from benchmark.harness import compare
    bench = run.load_json("BENCHMARK.json")
    cell = run.find(bench["workloads"], args.workload, "workload")
    cfg = run.load_json(run.find(bench["configs"], cell["config"],
                                 "configuration")["file"])
    traffic = run.load_json("benchmark", "traffic", cell["traffic"] + ".json")
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("readings: no TPU")
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = open(os.path.join(out_dir, f"readings_{cell['name']}.jsonl"), "a")
    Mode = importlib.import_module("benchmark.modes." + traffic["mode"]).Mode
    for i in range(args.seeds):
        # seeds spread over the whole range the driver may draw from
        seed = args.first_seed + i * 178956971
        t0 = time.perf_counter()
        mode = Mode(cfg, traffic, seed, cell["chips"], args.rehearse)
        mode.setup()
        t1 = time.perf_counter()
        mode.release()
        ref = mode.reference_readings()
        values, where = compare.gaps(mode.readings, ref)
        row = {"cell": cell["name"], "seed": seed,
               "program": values, "program_where": where,
               "losses": {"program": mode.readings["losses"],
                          "reference": ref["losses"]},
               "setup_s": t1 - t0}
        if i < args.controls:
            control = cfg["precision"]["control"]
            passes = mode.cfg["total_ut_steps"]
            for name, how in (
                    ("control_" + control, {"numerics": control}),
                    ("fault_a_pass_left_out", {"passes": passes - 1}),
                    ("fault_gradient_from_the_last_pass_alone",
                     {"grad_passes": (passes - 1,)})):
                row[name] = compare.gaps(mode.reference_readings(**how),
                                         ref)[0]
        row["seconds"] = time.perf_counter() - t0
        line = json.dumps(row)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()
        del mode, ref


if __name__ == "__main__":
    main()
