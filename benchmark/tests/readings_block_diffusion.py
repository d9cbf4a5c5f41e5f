#!/usr/bin/env python3
"""``tests/readings.py`` for a cell of mode ``fit_block_diffusion``, whose
batch of one sequence has no half to leave out: the upper readings come
from controls of the precision the configuration states (the reference
with every product's operands in float8; with the router alone in
bfloat16; with the parameters held in bfloat16) and from four faults of
the mechanism put in the program's place (``reference/sdar_moe.py``: a
causal mask over the 2L rows; the noisy half at positions L..2L-1; the
weight 1 / t left out; a noisy row that sees the clean copy of its own
block).  Every one goes through the mode's own comparison
(``Mode.gaps``, then ``compare.verdict`` under the cell's committed
limits) and is printed with its verdict: ``correct`` false is what each
is read for.  A control or a fault whose verdict the first loss and the
first gradient decide follows one step and is held to the limits of
those; the parameters' control follows all of them.  Run by hand through
the chip tool, never by the benchmark's own runs:

    python3 benchmark/tests/readings_block_diffusion.py --workload <cell> --seeds 2 --controls 2

One JSON line a seed, on standard output and in
``chiprun_out/readings_<cell>.jsonl``.
"""

from __future__ import annotations

import argparse
import concurrent.futures
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
    ap.add_argument("--only", default="", help="comma-separated names of the variants to read")
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
    from benchmark.reference.sdar_moe import FAULTS
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
    limits = run.load_json("benchmark", "limits", cell["name"] + ".json")["limits"]
    first_step = {k: v for k, v in limits.items()
                  if k.startswith(("loss1_", "grad_", "early_rows_"))}
    control = cfg["precision"]["control"]
    # (name, numerics, fault, steps followed, limits held to)
    variants = [("control_" + control, control, None, 1, first_step),
                ("control_router_bfloat16", "router_bfloat16", None, 1, first_step),
                ("control_params_bfloat16", "params_bfloat16", None, None, limits)] \
        + [("fault_" + f, "float32", f, 1, first_step) for f in FAULTS]
    if args.only:
        variants = [v for v in variants if v[0] in args.only.split(",")]
    pool = concurrent.futures.ThreadPoolExecutor(3)    # the comparisons, beside the device

    def judged(mode, readings, ref, held_to):
        values, _ = mode.gaps(readings, ref)
        values = {k: v for k, v in values.items()
                  if len(readings["losses"]) == len(ref["losses"])
                  or not k.startswith(("change_", "loss2_", "loss3_"))}
        rows = compare.verdict(values, {k: v for k, v in held_to.items()
                                        if k in values})
        return {"values": values, "correct": all(ok for *_, ok in rows),
                "failed": [n for n, _, _, ok in rows if not ok]}

    for i in range(args.seeds):
        # seeds spread over the whole range the driver may draw from
        seed = args.first_seed + i * 178956971
        t0 = time.perf_counter()
        mode = Mode(cfg, traffic, seed, cell["chips"], args.rehearse)
        mode.setup()
        t1 = time.perf_counter()
        mode.release()
        ref = mode.reference_readings()
        jobs = {"program": pool.submit(judged, mode, mode.readings, ref, limits)}
        if i < args.controls:
            for name, numerics, fault, steps, held_to in variants:
                jobs[name] = pool.submit(
                    judged, mode, mode.reference_readings(
                        numerics=numerics, fault=fault, steps=steps),
                    ref, held_to)
        row = {"cell": cell["name"], "seed": seed,
               "losses": {"program": mode.readings["losses"],
                          "reference": ref["losses"]},
               "setup_s": t1 - t0, **{k: j.result() for k, j in jobs.items()}}
        row["seconds"] = time.perf_counter() - t0
        line = json.dumps(row)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()
        del mode, ref, jobs


if __name__ == "__main__":
    main()
