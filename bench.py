#!/usr/bin/env python
"""Benchmark entry point — run by the driver on real TPU hardware.

Prints ONE JSON line on stdout:
  {"metric", "value", "unit", "vs_baseline", "configs": {...}}

The headline metric stays LeNet-MNIST ``MultiLayerNetwork.fit()``
samples/sec/chip; ``configs`` carries
all five BASELINE.md north-star configs:

  lenet        LeNet MNIST, MultiLayerNetwork       samples/sec/chip
  vgg16        VGG16 CIFAR-10                       samples/sec/chip + MFU
  charrnn      GravesLSTM char-RNN (TBPTT segment)  chars/sec/chip
  word2vec     skip-gram NS, fused kernel path      words/sec
  resnet50     ResNet-50 ImageNet-shape, DP mesh    samples/sec/chip + MFU

Measurement protocol (advisor round-2 finding: one 30-step window is
noise): every config runs WINDOWS repeated timed windows after warmup
and reports the median (plus min/max) — the median window is the value.
MFU is measured FLOPs/s over the chip's published dense-bf16 peak
(ops/platform.peak_flops_bf16; the peak used is recorded in the output).
FLOPs per step come from XLA's own cost model on the exact compiled
step (compiled.cost_analysis()['flops']) — no hand-counted estimates.

Reference measurement analog: PerformanceListener samples/sec
(/root/reference/deeplearning4j-nn/src/main/java/org/deeplearning4j/
optimize/listeners/PerformanceListener.java:119-122).
"""

import json
import os
import statistics
import sys
import threading
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

# Rough DL4J 0.8 LeNet-MNIST CPU throughput (the reference publishes no
# numbers — BASELINE.json published:{}).  Kept only so vs_baseline is
# comparable across rounds.
BASELINE_SAMPLES_SEC = 1500.0

WINDOWS = 5
MFU_TARGET = 0.35


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def timed_windows(run_step, block, steps, windows=WINDOWS, warmup=8):
    """Run `warmup` steps, then `windows` timed windows of `steps` steps.
    Returns per-window seconds (list)."""
    for _ in range(warmup):
        run_step()
    block()
    times = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            run_step()
        block()
        times.append(time.perf_counter() - t0)
    return times


def window_stats(times, items_per_step, steps):
    """Best-of-N summary WITH variance: a headline number whose window
    spread is recorded next to it is attributable; one that isn't is
    noise you can't distinguish from a regression (ROADMAP item 5 — the
    r01→r02 1.40M→511k swing had no spread recorded, so nobody could
    tell machine noise from a real change)."""
    med = statistics.median(times)
    rates = [items_per_step * steps / t for t in times]
    return {
        "items_per_sec_median": items_per_step * steps / med,
        "items_per_sec_max": items_per_step * steps / min(times),
        "items_per_sec_min": items_per_step * steps / max(times),
        "items_per_sec_stdev": round(statistics.stdev(rates), 2)
                               if len(rates) > 1 else 0.0,
        "window_rel_spread": round((max(times) - min(times)) / med, 4),
        "best_of": len(times),
        "step_time_ms_median": med / steps * 1e3,
        "window_sec": [round(t, 4) for t in times],
        "steps_per_window": steps,
    }


def machine_fingerprint(devices=None):
    """Where this record was measured: without the fingerprint, two
    BENCH records are not comparable at all (a v5e number vs a CPU
    number looks like a 100x regression)."""
    import platform as pyplat
    import socket
    fp = {"host": socket.gethostname(), "os": pyplat.platform(),
          "python": pyplat.python_version(), "cpu_count": os.cpu_count()}
    try:
        import jax
        fp["jax_version"] = jax.__version__
        fp["platform"] = jax.default_backend()
        if devices:
            fp["device_kind"] = devices[0].device_kind
            fp["device_count"] = len(devices)
    except Exception:
        pass
    return fp


GATE_THRESHOLD = 0.15   # >15% below the stored best-of-N = regression
NEAR_MISS_THRESHOLD = 0.10   # drops past this (but under the gate)
# are recorded as near-misses — the tuning signal for the 15% line


def _fingerprint_key(fp):
    """The comparability key for regression gating: two records gate
    against each other only when they ran on the same host/backend
    shape.  Volatile fields (kernel build, jax patch level) stay out so
    a routine image bump doesn't orphan the whole history."""
    parts = (fp.get("host", "?"), fp.get("platform", "?"),
             fp.get("device_kind", "?"), str(fp.get("device_count", 1)),
             str(fp.get("cpu_count", "?")))
    return "|".join(parts)


def gate_regressions(result, history_dir):
    """Bench regression gating (ROADMAP item 5): persist each config's
    best-of-N value history under ``bench_history/`` keyed by machine
    fingerprint, and FAIL LOUDLY — record flag here, nonzero exit in
    ``main()`` — when a config lands >15% below its stored baseline on
    the SAME fingerprint (different machine = different entry, no
    cross-machine noise).  ``DL4J_BENCH_NO_GATE=1`` records but never
    fails (the escape hatch for a known slowdown or machine change);
    dry-run configs are all skipped so the gate is a recorded no-op."""
    disabled = os.environ.get("DL4J_BENCH_NO_GATE") == "1"
    keep_n = 10
    gate = {"dir": history_dir, "threshold_pct": int(GATE_THRESHOLD * 100),
            "near_miss_threshold_pct": int(NEAR_MISS_THRESHOLD * 100),
            "keep_n": keep_n, "disabled": disabled, "checked": 0,
            "regressions": [], "margins": [], "near_misses": [],
            "threshold_overrides": {}, "failed": False}
    fp_key = _fingerprint_key(result.get("machine", {}))
    try:
        os.makedirs(history_dir, exist_ok=True)
        for name, cfg in (result.get("configs") or {}).items():
            value = cfg.get("value") if isinstance(cfg, dict) else None
            unit = cfg.get("unit") if isinstance(cfg, dict) else None
            if not isinstance(value, (int, float)) or value <= 0 or not unit:
                continue   # skipped / errored / dry-run configs don't gate
            path = os.path.join(history_dir, f"{name}.json")
            hist = {"entries": {}}
            if os.path.exists(path):
                try:
                    with open(path) as f:
                        hist = json.load(f)
                except Exception:
                    hist = {"entries": {}}   # corrupt history never blocks
            # per-config threshold override: a noisy config (CPU
            # legs, allocation-bound micro-benches) can carry
            # its own gate line as top-level metadata in its history
            # file — {"threshold_pct": 25, "entries": {...}} — tuned
            # from the recorded pct_vs_best margin distribution
            threshold = GATE_THRESHOLD
            t_over = hist.get("threshold_pct")
            if isinstance(t_over, (int, float)) and 0 < t_over < 100:
                threshold = float(t_over) / 100.0
                gate["threshold_overrides"][name] = float(t_over)
            entry = hist["entries"].get(fp_key)
            if entry is not None and entry.get("unit") == unit \
                    and entry.get("values"):
                baseline = max(entry["values"])
                gate["checked"] += 1
                # the margin is recorded on EVERY checked config — pass
                # or fail — so the threshold can be tuned from the
                # distribution of real runs instead of anecdotes
                # (ROADMAP 5: does CPU noise crowd the line?)
                pct_vs_best = round((value / baseline - 1.0) * 100, 1)
                gate["margins"].append({
                    "config": name, "value": value, "unit": unit,
                    "baseline_best_of_n": baseline,
                    "pct_vs_best": pct_vs_best,
                    "threshold_pct": int(round(threshold * 100)),
                    "history_len": len(entry["values"]),
                    "fingerprint": fp_key,
                })
                if value < baseline * (1.0 - threshold):
                    gate["regressions"].append({
                        "config": name, "value": value,
                        "baseline_best_of_n": baseline, "unit": unit,
                        "drop_pct": round((1 - value / baseline) * 100, 1),
                        "threshold_pct": int(round(threshold * 100)),
                        "fingerprint": fp_key,
                    })
                elif value < baseline * (1.0 - NEAR_MISS_THRESHOLD):
                    # inside the gate but close to it: the population
                    # that decides whether 15% is too tight or too loose
                    gate["near_misses"].append({
                        "config": name,
                        "drop_pct": round((1 - value / baseline) * 100, 1),
                        "gate_headroom_pct": round(
                            threshold * 100
                            - (1 - value / baseline) * 100, 1),
                    })
            elif entry is not None and entry.get("unit") != unit:
                # a config changed what it measures: restart its history
                entry = None
            if entry is None:
                entry = {"unit": unit, "values": []}
            entry["values"] = (entry["values"] + [value])[-keep_n:]
            entry["unit"] = unit
            entry["updated"] = time.strftime("%Y-%m-%dT%H:%M:%S")
            hist["entries"][fp_key] = entry
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(hist, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
    except Exception as e:   # the gate must never kill the record itself
        gate["error"] = f"{type(e).__name__}: {e}"
    # compact pct_vs_best roll-up: the record's headline noise picture
    # (what the threshold tuning reads) without digging through the
    # full per-config margin entries
    pcts = sorted(m["pct_vs_best"] for m in gate["margins"])
    if pcts:
        gate["margin_summary"] = {
            "checked": len(pcts),
            "worst_pct_vs_best": pcts[0],
            "median_pct_vs_best": pcts[len(pcts) // 2],
            "best_pct_vs_best": pcts[-1],
            "by_config": {m["config"]: m["pct_vs_best"]
                          for m in gate["margins"]},
        }
        result["margins"] = gate["margin_summary"]
    gate["failed"] = bool(gate["regressions"]) and not disabled
    result["bench_gate"] = gate
    if gate["regressions"]:
        log(f"bench gate: {len(gate['regressions'])} regression(s) "
            f"{'(gate disabled)' if disabled else '— FAILING'}: "
            + ", ".join(f"{r['config']} -{r['drop_pct']}%"
                        for r in gate["regressions"]))
    return gate


def compiled_step(raw_step, args):
    """AOT-compile a train step once; returns (callable, flops or None).
    Compile wall-time is recorded in ``compiled_step.last_compile_sec``
    (diagnosing where the bench budget goes on a fresh chip)."""
    import jax
    jitted = jax.jit(raw_step, donate_argnums=(0, 1, 2))
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    compiled_step.last_compile_sec = round(time.perf_counter() - t0, 2)
    flops = None
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        f = float(ca.get("flops", 0.0))
        flops = f if f > 0 else None
    except Exception:
        pass
    return compiled, flops


compiled_step.last_compile_sec = None


def _step_bench(net, x, y, steps, key_seed=0, warmup=8, tuple_args=False):
    """Measure a network's full fit step (donated buffers) on ONE device.
    tuple_args wraps x/y for the ComputationGraph step signature.
    Returns (window_times, flops_per_step)."""
    import jax
    import jax.numpy as jnp
    net.init()
    xa, ya = ((x,), (y,)) if tuple_args else (x, y)
    step, flops = compiled_step(
        net._build_step_raw(),
        (net.net_params, net.net_state, net.opt_states, xa, ya, None, None,
         jnp.asarray(0, jnp.int32), jax.random.PRNGKey(key_seed)))
    carry = [net.net_params, net.net_state, net.opt_states]
    key = jax.random.PRNGKey(key_seed)
    it = jnp.asarray(0, jnp.int32)

    def strip_rnn(state):
        # TBPTT models return carried rnn_state; the AOT-compiled step
        # was lowered for the carry-free structure, so drop it between
        # calls (matches the engines' per-batch _strip_rnn_state)
        if isinstance(state, dict):
            return {n: {k: v for k, v in s.items() if k != "rnn_state"}
                    for n, s in state.items()}
        return [{k: v for k, v in s.items() if k != "rnn_state"}
                for s in state]

    def run():
        carry[0], st, carry[2], _ = step(
            carry[0], carry[1], carry[2], xa, ya, None, None, it, key)
        carry[1] = strip_rnn(st)

    times = timed_windows(run, lambda: jax.block_until_ready(carry[0]),
                          steps, warmup=warmup)
    return times, flops


def bench_lenet(precision):
    """Single-device step → per-chip number IS the measured device's
    throughput (dividing by the host's total chip count would understate
    it n_chips-fold on a multi-chip host)."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.models.lenet import lenet

    BATCH = 256
    net = lenet()
    net.conf.global_conf.precision = precision
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(BATCH, 1, 28, 28)).astype(np.float32))
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rng.integers(0, 10, BATCH)])
    times, flops = _step_bench(net, x, y, steps=50)
    st = window_stats(times, BATCH, 50)
    return {
        "metric": f"LeNet-MNIST fit() samples/sec/chip ({precision})",
        "value": round(st["items_per_sec_median"], 1),
        "unit": "samples/sec/chip",
        "chips_used": 1,
        **st,
    }


def bench_lenet_etl():
    """LeNet fed from FILES, not in-memory arrays: npz shards on disk →
    native threaded prefetcher (native/dl4j_io.cc) → AsyncDataSetIterator
    (background decode + device_put) → fit step.  Reports etl_ms per
    step next to step time so input-pipeline overlap is measured, not
    assumed (ref: AsyncDataSetIterator.java:39-127; PerformanceListener's
    ETL-ms column, PerformanceListener.java:119-122)."""
    import pathlib
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models.lenet import lenet
    from deeplearning4j_tpu.datasets.fetchers import load_mnist, CACHE_DIR
    from deeplearning4j_tpu.datasets.iterators import (
        AsyncDataSetIterator, ExistingDataSetIterator)
    from deeplearning4j_tpu.native.io import (
        NativeFilePrefetcher, load_npz_dataset_bytes)
    from deeplearning4j_tpu.native import available as native_available

    BATCH = 256
    real_idx = (CACHE_DIR / "mnist").exists()
    # cache keyed by data source: a run after the MNIST cache appears
    # must not silently reuse synthetic shards under a "real" label
    cache = (pathlib.Path(__file__).parent / ".bench_cache" /
             f"lenet_etl_{'idx' if real_idx else 'synth'}")
    cache.mkdir(parents=True, exist_ok=True)
    ds = load_mnist(train=True)
    n_shards = min(40, ds.features.shape[0] // BATCH)
    paths = [cache / f"shard_{i:03d}.npz" for i in range(n_shards)]
    for i, p in enumerate(paths):
        if not p.exists():
            s = slice(i * BATCH, (i + 1) * BATCH)
            tmp = p.with_suffix(".tmp.npz")
            np.savez(tmp, features=ds.features[s], labels=ds.labels[s])
            os.replace(tmp, p)  # atomic: a killed run can't leave a
            # truncated shard that poisons every later bench

    def gen():
        for _, blob in NativeFilePrefetcher(paths, capacity=4, n_threads=2):
            yield load_npz_dataset_bytes(blob)

    it = AsyncDataSetIterator(ExistingDataSetIterator(gen),
                              queue_size=4, device_put=True)
    net = lenet()
    net.conf.global_conf.precision = "bf16"
    net.init()
    first = np.load(paths[0])
    step, flops = compiled_step(
        net._build_step_raw(),
        (net.net_params, net.net_state, net.opt_states,
         jnp.asarray(first["features"]), jnp.asarray(first["labels"]),
         None, None, jnp.asarray(0, jnp.int32), jax.random.PRNGKey(0)))
    carry = [net.net_params, net.net_state, net.opt_states]
    key = jax.random.PRNGKey(0)
    it0 = jnp.asarray(0, jnp.int32)
    etl_wait = [0.0]

    def run():
        t0 = time.perf_counter()
        if not it.has_next():
            it.reset()
        d = it.next()
        etl_wait[0] += time.perf_counter() - t0
        carry[0], carry[1], carry[2], _ = step(
            carry[0], carry[1], carry[2], d.features, d.labels,
            None, None, it0, key)

    STEPS = 30
    for _ in range(8):
        run()
    jax.block_until_ready(carry[0])
    times, etls = [], []
    for _ in range(WINDOWS):
        etl_wait[0] = 0.0
        t0 = time.perf_counter()
        for _ in range(STEPS):
            run()
        jax.block_until_ready(carry[0])
        times.append(time.perf_counter() - t0)
        etls.append(etl_wait[0])
    st = window_stats(times, BATCH, STEPS)
    return {
        "metric": "LeNet-MNIST fit() from disk via native prefetch + async "
                  "iterator, samples/sec/chip (bf16)",
        "value": round(st["items_per_sec_median"], 1),
        "unit": "samples/sec/chip",
        "chips_used": 1,
        "etl_ms_per_step_median": round(
            statistics.median(etls) / STEPS * 1e3, 3),
        "etl_fraction_of_step": round(
            statistics.median(etls) / statistics.median(times), 4),
        "native_prefetcher": native_available(),
        "data_source": "cached MNIST IDX" if real_idx
                       else "synthetic fallback (zero egress)",
        "n_shards": n_shards,
        **({"flops_per_step": flops} if flops else {}),
        **st,
    }


def bench_pipeline():
    """Input-pipeline A/B on an ETL-bound workload: the same fit() run
    sync (pipeline_workers=0), async-1 and async-N.  Each batch's ETL is
    a simulated storage fetch (latency the workers overlap) plus a
    GIL-releasing numpy decode — the shape of any real disk/network
    ingest path.  Reports batches/sec per leg and the registry-measured
    ``data_wait`` share of wall time, which is the tentpole's claim: the
    parallel pipeline shrinks the device's wait on ETL."""
    import jax
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import DataSetIterator
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    BATCH, FEAT, BATCHES = 256, 784, 40
    FETCH_MS = 5.0      # simulated storage latency per batch
    DECODE_ROUNDS = 3   # numpy elementwise decode passes per batch
    rng = np.random.default_rng(0)
    base = rng.normal(size=(BATCH, FEAT)).astype(np.float32)
    labels = np.eye(10, dtype=np.float32)[rng.integers(0, 10, BATCH)]

    class EtlBoundIterator(DataSetIterator):
        """next_raw = shard index (cheap, serial); collate = fetch +
        decode (expensive, runs on pipeline workers)."""

        def __init__(self):
            self._i = 0

        def has_next(self):
            return self._i < BATCHES

        def next_raw(self):
            i = self._i
            self._i += 1
            return i

        def collate(self, i):
            time.sleep(FETCH_MS / 1e3)          # storage fetch
            x = base + np.float32(i)
            for _ in range(DECODE_ROUNDS):      # decode/augment
                x = np.tanh(x * np.float32(1.0001))
            return DataSet(x, labels)

        def next(self):
            return self.collate(self.next_raw())

        def reset(self):
            self._i = 0

        def batch_size(self):
            return BATCH

    def make_net(workers):
        conf = (NeuralNetConfiguration.builder().seed(7)
                .updater("adam").learning_rate(1e-3)
                .input_pipeline(workers=workers, prefetch=8,
                                staging_depth=4)
                .list()
                .layer(L.DenseLayer(n_in=FEAT, n_out=32,
                                    activation="relu"))
                .layer(L.OutputLayer(n_in=32, n_out=10,
                                     activation="softmax",
                                     loss="negativeloglikelihood"))
                .build())
        return MultiLayerNetwork(conf).init()

    def phase_sum(phase):
        snap = monitor.get_registry().snapshot()
        fam = snap.get("dl4j_phase_seconds") or {"samples": []}
        return sum(s.get("sum") or 0.0 for s in fam["samples"]
                   if s["labels"].get("span") == "fit/step"
                   and s["labels"].get("phase") == phase)

    n_workers = max(2, min(4, os.cpu_count() or 1))
    legs = {}
    for name, workers in (("sync", 0), ("async_1", 1),
                          (f"async_{n_workers}", n_workers)):
        net = make_net(workers)
        warm = EtlBoundIterator()
        warm._i = BATCHES - 4   # compile off the clock, 4 batches
        net.fit(warm)
        it = EtlBoundIterator()
        walls, shares = [], []
        for _ in range(3):
            it.reset()
            w0 = phase_sum("data_wait")
            t0 = time.perf_counter()
            net.fit(it)
            wall = time.perf_counter() - t0
            walls.append(wall)
            shares.append((phase_sum("data_wait") - w0) / max(wall, 1e-9))
        wall = statistics.median(walls)
        legs[name] = {
            "batches_per_sec": round(BATCHES / wall, 2),
            "wall_sec_median": round(wall, 4),
            "data_wait_share": round(statistics.median(shares), 4),
        }
    sync_rate = legs["sync"]["batches_per_sec"]
    async_n = legs[f"async_{n_workers}"]
    speedup_n = async_n["batches_per_sec"] / max(sync_rate, 1e-9)
    return {
        "metric": "ETL-bound fit() batches/sec, sync vs async input "
                  "pipeline",
        "value": round(speedup_n, 2),
        "unit": "x (async-N vs sync)",
        "n_workers": n_workers,
        "etl_ms_simulated_fetch": FETCH_MS,
        "speedup_async_1": round(
            legs["async_1"]["batches_per_sec"] / max(sync_rate, 1e-9), 2),
        f"speedup_async_{n_workers}": round(speedup_n, 2),
        "meets_1_5x_target": speedup_n >= 1.5,
        "data_wait_share_sync": legs["sync"]["data_wait_share"],
        "data_wait_share_async":
            async_n["data_wait_share"],
        **legs,
    }


def bench_resilience():
    """Resilience A/B: the same training+serving workload run clean vs
    under an armed chaos plan — 1%-probability transient reader faults
    (retried by the feeder with backoff, ``fault_tolerance(
    reader_retries=3)``) and injected cache-load latency shaped to a
    ~50 ms p99 (1% of loads).  Reports the throughput delta the
    resilience machinery costs when absorbing that fault rate, plus the
    shed/retry/injection counters — the claim under test is "chaos at
    this rate is absorbed, not surfaced" (docs/RESILIENCE.md)."""
    import tempfile
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.serialization import write_model
    from deeplearning4j_tpu.resilience import faults
    from deeplearning4j_tpu.server.gateway import DeepLearning4jEntryPoint

    BATCH, FEAT, BATCHES, CLASSES = 128, 256, 30, 10
    rng = np.random.default_rng(3)
    batches = [DataSet(rng.normal(size=(BATCH, FEAT)).astype(np.float32),
                       np.eye(CLASSES, dtype=np.float32)[
                           rng.integers(0, CLASSES, BATCH)])
               for _ in range(BATCHES)]

    def make_net():
        conf = (NeuralNetConfiguration.builder().seed(7)
                .updater("adam").learning_rate(1e-3)
                .input_pipeline(workers=1, prefetch=4)
                .fault_tolerance(reader_retries=3)
                .list()
                .layer(L.DenseLayer(n_in=FEAT, n_out=64,
                                    activation="relu"))
                .layer(L.OutputLayer(n_in=64, n_out=CLASSES,
                                     activation="softmax",
                                     loss="negativeloglikelihood"))
                .build())
        return MultiLayerNetwork(conf).init()

    tmp = tempfile.mkdtemp(prefix="dl4j_resilience_bench_")
    model_path = os.path.join(tmp, "model.zip")
    write_model(make_net(), model_path)
    SERVE_REQS, INVALIDATE_EVERY = 40, 5
    rows = rng.normal(size=(SERVE_REQS, 1, FEAT)).astype(np.float32)

    def counter_value(name, **labels):
        fam = monitor.get_registry().get(name)
        if fam is None:
            return 0.0
        return sum(s["value"] for s in fam.samples()
                   if all(s["labels"].get(k) == v
                          for k, v in labels.items()))

    TRAIN_EPOCHS = 3   # ~100 raw pulls: enough traffic for a 1% plan

    def run_leg(chaos):
        faults.reset()
        if chaos:
            # seeds chosen so the 1% plans deterministically fire at
            # least once inside this workload's call window — a chaos
            # leg that injects nothing measures nothing
            faults.arm({"site": "reader.next_raw", "mode": "fail",
                        "probability": 0.01, "seed": 0,
                        "exc": "TransientError"})
            # ~50 ms p99: 1% of cache loads eat an injected 50 ms stall
            faults.arm({"site": "cache.load", "mode": "latency",
                        "latency_ms": 50.0, "probability": 0.01,
                        "seed": 6})
        retries0 = counter_value("dl4j_resilience_retries_total")
        shed0 = counter_value("dl4j_resilience_shed_total")
        net = make_net()
        net.fit(ListDataSetIterator(list(batches[:4])))  # compile off-clock
        t0 = time.perf_counter()
        net.fit(ListDataSetIterator(list(batches)), epochs=TRAIN_EPOCHS)
        train_wall = time.perf_counter() - t0
        # serving side: BOTH legs pay the same periodic invalidate (so
        # reload cost cancels in the A/B); the chaos leg's reloads run
        # through the latency-injected cache.load site
        ep = DeepLearning4jEntryPoint(max_batch=32, max_wait_ms=1.0)
        ep.predict(model_path, features=rows[0])  # load+warm off-clock
        t0 = time.perf_counter()
        for i in range(SERVE_REQS):
            if i % INVALIDATE_EVERY == 0 and i > 0:
                ep.invalidate(model_path)
            ep.predict(model_path, features=rows[i])
        serve_wall = time.perf_counter() - t0
        ep.close()
        leg = {
            "train_samples_per_sec": round(
                BATCH * BATCHES * TRAIN_EPOCHS / train_wall, 1),
            "serve_requests_per_sec": round(SERVE_REQS / serve_wall, 1),
            "retries": counter_value(
                "dl4j_resilience_retries_total") - retries0,
            "shed": counter_value("dl4j_resilience_shed_total") - shed0,
            "faults_injected": {p["site"]: p["injected"]
                                for p in faults.armed()},
        }
        faults.reset()
        return leg

    legs = {"baseline": run_leg(False), "chaos": run_leg(True)}
    base_t = legs["baseline"]["train_samples_per_sec"]
    chaos_t = legs["chaos"]["train_samples_per_sec"]
    delta = (chaos_t - base_t) / max(base_t, 1e-9)
    return {
        "metric": "fit() samples/sec under 1% injected reader faults + "
                  "50ms p99 cache-load latency, vs clean",
        "value": round(chaos_t, 1),
        "unit": "samples/sec (chaos leg)",
        "throughput_delta_pct": round(delta * 100, 1),
        "serve_delta_pct": round(
            (legs["chaos"]["serve_requests_per_sec"]
             - legs["baseline"]["serve_requests_per_sec"])
            / max(legs["baseline"]["serve_requests_per_sec"], 1e-9) * 100,
            1),
        "chaos_absorbed": legs["chaos"]["retries"] > 0,
        **legs,
    }


def bench_sharded(n_chips, peak):
    """FSDP A/B (ROADMAP item 1): the same wide-MLP fit() run
    replica-style vs ``conf.sharding(data=1, fsdp=n_chips)`` — the
    production sharded path, not a dry-run.  Reports samples/sec per
    leg, the per-device param/updater bytes from the ``dl4j_sharding_*``
    gauges (the ZeRO claim: updater state shrinks ~1/fsdp), and an MFU
    estimate computed from the per-layer flops model ×
    ``dl4j_phase_seconds{phase=jit_call}`` step spans — derivable from
    the record alone, no compiled-step cost model needed.  On one
    device the sharded conf degrades to replica-style and the record
    says so."""
    import jax
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.ops import flops as flops_model

    BATCH, FEAT, HID, CLASSES, BATCHES = 256, 512, 512, 64, 12
    fsdp_degree = max(1, n_chips)
    rng = np.random.default_rng(8)
    batches = [DataSet(rng.normal(size=(BATCH, FEAT)).astype(np.float32),
                       np.eye(CLASSES, dtype=np.float32)[
                           rng.integers(0, CLASSES, BATCH)])
               for _ in range(BATCHES)]

    def make_net(shard):
        b = (NeuralNetConfiguration.builder().seed(3)
             .updater("adam").learning_rate(1e-3)
             .input_pipeline(workers=0))
        if shard:
            b.sharding(data=1, fsdp=fsdp_degree)
        conf = (b.list()
                .layer(L.DenseLayer(n_in=FEAT, n_out=HID,
                                    activation="relu"))
                .layer(L.DenseLayer(n_in=HID, n_out=HID,
                                    activation="relu"))
                .layer(L.OutputLayer(n_in=HID, n_out=CLASSES,
                                     activation="softmax", loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    def phase_totals(phase):
        snap = monitor.get_registry().snapshot()
        fam = snap.get("dl4j_phase_seconds") or {"samples": []}
        tot = cnt = 0.0
        for s in fam["samples"]:
            if s["labels"].get("span") == "fit/step" \
                    and s["labels"].get("phase") == phase:
                tot += s.get("sum") or 0.0
                cnt += s.get("count") or 0
        return tot, cnt

    def gauge(name):
        fam = monitor.get_registry().get(name)
        if fam is None:
            return None
        samples = fam.samples()
        return samples[0]["value"] if samples else None

    legs = {}
    for name, shard in (("replica", False), ("sharded", True)):
        net = make_net(shard)
        net.fit(ListDataSetIterator(list(batches[:2])))  # compile off-clock
        walls = []
        jit_s0, jit_c0 = phase_totals("jit_call")
        for _ in range(3):
            it = ListDataSetIterator(list(batches))
            t0 = time.perf_counter()
            net.fit(it)
            jax.block_until_ready(net.net_params)
            walls.append(time.perf_counter() - t0)
        jit_s1, jit_c1 = phase_totals("jit_call")
        steps = max(1.0, jit_c1 - jit_c0)
        step_s = (jit_s1 - jit_s0) / steps
        wall = min(walls)
        leg = {
            "samples_per_sec": round(BATCH * BATCHES / wall, 1),
            "wall_sec_best_of_3": round(wall, 4),
            "wall_sec_all": [round(w, 4) for w in walls],
            "wall_sec_stdev": round(statistics.stdev(walls), 4),
            "jit_call_ms_per_step": round(step_s * 1e3, 3),
        }
        est = flops_model.mfu(net, BATCH, step_s, peak)
        if est:
            leg.update(est)
        if shard:
            leg["sharding_active"] = net._sharding_plan is not None
            for gname in ("dl4j_sharding_param_bytes_total",
                          "dl4j_sharding_param_bytes_per_device",
                          "dl4j_sharding_updater_bytes_total",
                          "dl4j_sharding_updater_bytes_per_device",
                          "dl4j_sharding_allgather_bytes_per_step",
                          "dl4j_sharding_reducescatter_bytes_per_step"):
                v = gauge(gname)
                if v is not None:
                    leg[gname.replace("dl4j_sharding_", "")] = v
        legs[name] = leg
    sh = legs["sharded"]
    upd_total = sh.get("updater_bytes_total")
    upd_dev = sh.get("updater_bytes_per_device")
    shrink = (round(upd_dev / upd_total, 4)
              if upd_total and upd_dev else None)
    return {
        "metric": f"wide-MLP fit() samples/sec, replica vs FSDP "
                  f"(fsdp={fsdp_degree})",
        "value": sh["samples_per_sec"],
        "unit": "samples/sec (sharded leg)",
        "fsdp_degree": fsdp_degree,
        "sharding_active": sh.get("sharding_active", False),
        "speedup_vs_replica": round(
            sh["samples_per_sec"]
            / max(legs["replica"]["samples_per_sec"], 1e-9), 3),
        "updater_bytes_per_device_over_total": shrink,
        "updater_shrink_near_1_over_fsdp":
            (shrink is not None
             and shrink <= 1.0 / fsdp_degree * 1.5) if fsdp_degree > 1
            else None,
        **legs,
    }


def bench_lenet_scan(precision="bf16", k_steps=50):
    """Device-bound ceiling through the PRODUCT path:
    ``fit(it, fused_steps=K)`` fuses K train steps into one compiled
    lax.scan launch (nn/multilayer.py _build_fused_step) — no per-step
    host dispatch.  The gap between this and the per-step `lenet` number
    is pure host/dispatch overhead.

    Auto-enabled on TPU only (DL4J_BENCH_SCAN=1 to force elsewhere): on
    XLA:CPU, scan bodies miss fusion/layout optimizations and the number
    is meaningless."""
    import jax
    from deeplearning4j_tpu.models.lenet import lenet
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator

    BATCH = 256
    net = lenet()
    net.conf.global_conf.precision = precision
    net.init()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(BATCH, 1, 28, 28)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, BATCH)]
    batches = [DataSet(x, y) for _ in range(k_steps)]

    def run():
        net.fit(ListDataSetIterator(list(batches)), fused_steps=k_steps)

    times = timed_windows(run, lambda: jax.block_until_ready(net.net_params),
                          steps=4, warmup=2)
    st = window_stats(times, BATCH * k_steps, 4)
    # normalize units to TRAIN steps so the fields recompute consistently
    # with every other config (window covers 4 launches x k_steps steps)
    st["launch_time_ms_median"] = st["step_time_ms_median"]
    st["step_time_ms_median"] = st["launch_time_ms_median"] / k_steps
    st["steps_per_window"] = 4 * k_steps
    return {
        "metric": f"LeNet-MNIST fit(fused_steps={k_steps}) steady-state "
                  f"samples/sec/chip ({precision})",
        "value": round(st["items_per_sec_median"], 1),
        "unit": "samples/sec/chip",
        "chips_used": 1,
        **st,
    }


def bench_vgg16(peak, conv_layout=None, batch=256):
    """conv_layout='nhwc' re-traces every conv in channels-last internal
    layout (ops/convolution._nhwc_internal) — the vgg16 vs vgg16_nhwc
    A/B answers whether XLA:TPU's layout assignment already absorbs the
    logical-NCHW cost (round-3 verdict weak #4 / next #3).  ``batch``
    parameterizes the vgg16 vs vgg16_b512 ladder: if doubling the batch
    raises MFU materially, per-layer overheads (small early convs, step
    dispatch) are the limiter rather than the conv kernels themselves."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.models.vgg import vgg16_cifar10

    # pin the env BOTH ways: a user-exported DL4J_CONV_LAYOUT must not
    # silently turn the baseline leg into NHWC (that would answer the
    # A/B "no difference" by construction)
    prev = os.environ.pop("DL4J_CONV_LAYOUT", None)
    if conv_layout:
        os.environ["DL4J_CONV_LAYOUT"] = conv_layout
    try:
        BATCH = batch
        net = vgg16_cifar10()
        net.conf.global_conf.precision = "bf16"
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(BATCH, 3, 32, 32)).astype(np.float32))
        y = jnp.asarray(np.eye(10, dtype=np.float32)[rng.integers(0, 10, BATCH)])
        times, flops = _step_bench(net, x, y, steps=30)
    finally:
        if prev is None:
            os.environ.pop("DL4J_CONV_LAYOUT", None)
        else:
            os.environ["DL4J_CONV_LAYOUT"] = prev
    st = window_stats(times, BATCH, 30)
    out = {
        "metric": "VGG16-CIFAR10 fit() samples/sec/chip (bf16"
                  f"{', nhwc-internal' if conv_layout else ''}"
                  f"{f', batch={batch}' if batch != 256 else ''})",
        "value": round(st["items_per_sec_median"], 1),
        "unit": "samples/sec/chip",
        "chips_used": 1,
        "batch": BATCH,
        "conv_internal_layout": conv_layout or "nchw",
        **st,
    }
    if flops and peak:
        step_s = st["step_time_ms_median"] / 1e3
        out["flops_per_step"] = flops
        out["mfu"] = round(flops / step_s / peak, 4)
        out["mfu_peak_used_tflops"] = peak / 1e12
        out["mfu_target"] = MFU_TARGET
    return out


def bench_charrnn():
    import jax.numpy as jnp
    from deeplearning4j_tpu.models.charrnn import char_rnn

    BATCH, T, V = 64, 50, 84
    net = char_rnn(vocab_size=V)
    net.conf.global_conf.precision = "bf16"
    rng = np.random.default_rng(2)
    eye = np.eye(V, dtype=np.float32)
    x = jnp.asarray(eye[rng.integers(0, V, (BATCH, T))])
    y = jnp.asarray(eye[rng.integers(0, V, (BATCH, T))])
    times, flops = _step_bench(net, x, y, steps=30)
    st = window_stats(times, BATCH * T, 30)
    st["chars_per_sec_median"] = st.pop("items_per_sec_median")
    return {
        "metric": "GravesLSTM char-RNN TBPTT-segment chars/sec/chip (bf16)",
        "value": round(st["chars_per_sec_median"], 1),
        "unit": "chars/sec/chip",
        "chips_used": 1,
        **st,
    }


def bench_charrnn_scan(k_steps=20):
    """charrnn through ``fit(fused_steps=K)``: K TBPTT segments per
    compiled lax.scan launch.  The per-step charrnn config runs small
    [64,H]x[H,4H] recurrent gemms and is the most dispatch-exposed
    north-star — the gap to this number is host overhead, the same
    diagnosis lenet vs lenet_scan makes for the conv path."""
    import jax
    from deeplearning4j_tpu.models.charrnn import char_rnn
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator

    BATCH, T, V = 64, 50, 84
    net = char_rnn(vocab_size=V)
    net.conf.global_conf.precision = "bf16"
    net.init()
    rng = np.random.default_rng(2)
    eye = np.eye(V, dtype=np.float32)
    batches = [DataSet(eye[rng.integers(0, V, (BATCH, T))],
                       eye[rng.integers(0, V, (BATCH, T))])
               for _ in range(k_steps)]

    def run():
        net.fit(ListDataSetIterator(list(batches)), fused_steps=k_steps)

    times = timed_windows(run, lambda: jax.block_until_ready(net.net_params),
                          steps=4, warmup=2)
    st = window_stats(times, BATCH * T * k_steps, 4)
    st["chars_per_sec_median"] = st.pop("items_per_sec_median")
    st["launch_time_ms_median"] = st["step_time_ms_median"]
    st["step_time_ms_median"] = st["launch_time_ms_median"] / k_steps
    st["steps_per_window"] = 4 * k_steps
    return {
        "metric": f"GravesLSTM char-RNN fit(fused_steps={k_steps}) "
                  "chars/sec/chip (bf16)",
        "value": round(st["chars_per_sec_median"], 1),
        "unit": "chars/sec/chip",
        "chips_used": 1,
        **st,
    }


def bench_word2vec():
    """End-to-end Word2Vec.fit() on a synthetic zipf corpus (text8 is not
    fetchable offline; the fused skip-gram NS kernel path is what's
    measured, embeddings/kernels.py skipgram_step)."""
    from deeplearning4j_tpu.embeddings.word2vec import Word2Vec
    from deeplearning4j_tpu.text.sentence_iterators import (
        CollectionSentenceIterator)

    rng = np.random.default_rng(3)
    VOCAB, TOKENS, SENT = 2000, 220_000, 20
    words = np.array([f"w{i}" for i in range(VOCAB)])
    zipf = 1.0 / np.arange(1, VOCAB + 1)
    zipf /= zipf.sum()
    tokens = rng.choice(words, size=TOKENS, p=zipf)
    sents = [" ".join(tokens[i:i + SENT]) for i in range(0, TOKENS, SENT)]

    w2v = (Word2Vec.Builder()
           .iterate(CollectionSentenceIterator(sents))
           .layer_size(128)
           .window_size(5)
           .negative_sample(5)
           .use_hierarchic_softmax(False)
           .min_word_frequency(1)
           .epochs(1)
           .seed(7)
           .build())
    w2v.build_vocab()
    t0 = time.perf_counter()
    w2v.fit()
    dt = time.perf_counter() - t0
    from deeplearning4j_tpu.embeddings import kernels as w2v_kernels
    return {
        "metric": "Word2Vec skip-gram NS words/sec (end-to-end fit, synthetic text8-like corpus)",
        "value": round(TOKENS / dt, 1),
        "unit": "words/sec",
        "corpus_tokens": TOKENS,
        "fit_sec": round(dt, 3),
        "chunk": w2v_kernels.CHUNK,  # DL4J_W2V_CHUNK tunes; vs 55k/s CPU
        "note": "single epoch incl. host-side windowing; fused skipgram_step kernel",
    }


def bench_resnet50(n_chips, peak):
    """ResNet-50 at ImageNet shapes, data-parallel over all chips via
    ParallelWrapper when >1 chip is present, plain CG step on one."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models.resnet import resnet50

    BATCH = 64 * max(1, n_chips)
    net = resnet50()
    net.conf.global_conf.precision = "bf16"
    net.init()
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(BATCH, 3, 224, 224)).astype(np.float32))
    y = jnp.asarray(np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, BATCH)])

    if n_chips > 1:
        from deeplearning4j_tpu.datasets.dataset import MultiDataSet
        from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
        pw = ParallelWrapper(net)
        data = ListDataSetIterator(
            [MultiDataSet([np.asarray(x)], [np.asarray(y)])])

        def run():
            pw.fit(data)
        run()  # compile
        times = timed_windows(run, lambda: jax.block_until_ready(net.net_params),
                              steps=10)
        st = window_stats(times, BATCH, 10)
        # per-chip FLOPs from the per-chip-batch step (data parallelism
        # replicates the model, shards the batch) so DP MFU is reported
        # too, not silently omitted
        per = BATCH // n_chips
        sub = resnet50()
        sub.conf.global_conf.precision = "bf16"
        sub.init()
        _, flops = compiled_step(
            sub._build_step_raw(),
            (sub.net_params, sub.net_state, sub.opt_states,
             (x[:per],), (y[:per],), None, None,
             jnp.asarray(0, jnp.int32), jax.random.PRNGKey(4)))
    else:
        times, flops = _step_bench(net, x, y, steps=10, warmup=5,
                                   tuple_args=True)
        st = window_stats(times, BATCH, 10)
    out = {
        "metric": "ResNet-50 ImageNet-shape data-parallel samples/sec/chip (bf16)",
        "value": round(st["items_per_sec_median"] / n_chips, 1),
        "unit": "samples/sec/chip",
        "global_batch": BATCH,
        "chips_used": n_chips,
        **st,
    }
    if flops and peak:
        # flops is per-chip per-step either way (single-chip full batch,
        # or the per-chip-shard step under DP)
        step_s = st["step_time_ms_median"] / 1e3
        out["flops_per_step_per_chip"] = flops
        out["mfu"] = round(flops / step_s / peak, 4)
        out["mfu_peak_used_tflops"] = peak / 1e12
    return out


def bench_ragged():
    """Ragged-minibatch micro-workload: the same stream of
    variable-batch-size minibatches trained twice — with shape bucketing
    (ops/bucketing.py pads each batch up to its power-of-two bucket, the
    jitted step compiles once per bucket) and without (every distinct
    shape is an XLA retrace).  Emits the CompileTelemetry retrace counts
    so compile-behavior regressions show up in the bench JSON, not just
    in wall-clock noise."""
    import jax
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator

    rng = np.random.default_rng(5)
    N_BATCHES, F, C = 40, 64, 10
    sizes = [int(s) for s in rng.integers(3, 65, size=N_BATCHES)]
    batches = [DataSet(rng.normal(size=(s, F)).astype(np.float32),
                       np.eye(C, dtype=np.float32)[rng.integers(0, C, s)])
               for s in sizes]

    def make_net(bucketed):
        b = (NeuralNetConfiguration.builder().seed(7).learning_rate(0.05)
             .updater("sgd"))
        if bucketed:
            b.shape_bucketing(True)
        conf = (b.list()
                .layer(L.DenseLayer(n_in=F, n_out=64, activation="relu"))
                .layer(L.OutputLayer(n_in=64, n_out=C, activation="softmax",
                                     loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    legs = {}
    for label, bucketed in (("bucketed", True), ("raw", False)):
        net = make_net(bucketed)
        t0 = time.perf_counter()
        net.fit(ListDataSetIterator(list(batches)))
        jax.block_until_ready(net.net_params)
        snap = net.compile_telemetry.snapshot()
        legs[label] = {
            "wall_sec": round(time.perf_counter() - t0, 3),
            "retraces": snap["retraces"],
            "step_calls": snap["calls"],
            "bucket_hits": snap["bucket_hits"],
        }
    buckets_hit = len(legs["bucketed"]["bucket_hits"])
    return {
        "metric": f"ragged stream ({N_BATCHES} variable-size batches) "
                  "train-step retraces, bucketed",
        "value": legs["bucketed"]["retraces"],
        "unit": "retraces",
        "distinct_batch_shapes": len(set(sizes)),
        "buckets_hit": buckets_hit,
        "retraces_bounded_by_buckets":
            legs["bucketed"]["retraces"] <= max(1, buckets_hit),
        **legs,
    }


def bench_kernels():
    """Fused-vs-dense helper-tier A/B (ops/helpers.py): for each op with
    a registered Pallas helper (conv2d+bias+act, the fused LSTM cell
    inside lstm_scan, in-kernel threshold dropout, fused softmax-xent),
    run the same jitted fwd+bwd workload with the tier forced FUSED and
    forced DENSE and report both throughputs, window variance and the
    speedup.  On CPU the fused legs execute under interpret=True — they
    prove the A/B harness and measure dispatch overhead, not the win
    (same caveat as bench_sharded's CPU-mesh legs); chip numbers are the
    evidence.  The flash-attention tier is exercised by the model
    configs (charrnn/attention paths), not re-benched here."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops import helpers
    from deeplearning4j_tpu.ops import losses
    from deeplearning4j_tpu.ops import platform
    from deeplearning4j_tpu.ops import recurrent as rnn_ops

    on_tpu = platform.is_tpu()
    if on_tpu:
        conv_n, conv_cin, conv_hw, conv_cout = 64, 64, 32, 64
        lstm_n, lstm_t, lstm_in, lstm_h = 32, 64, 128, 256
        xent_n, xent_v = 8192, 4096
        drop_shape = (4096, 1024)
        steps, windows, warmup = 10, 3, 3
    else:  # interpret-mode legs: keep the working set tiny
        conv_n, conv_cin, conv_hw, conv_cout = 4, 4, 12, 12
        lstm_n, lstm_t, lstm_in, lstm_h = 4, 8, 8, 32
        xent_n, xent_v = 256, 512
        drop_shape = (256, 256)
        steps, windows, warmup = 2, 2, 1
    rng = np.random.default_rng(0)

    def _time(build, items_per_step):
        fn, args = build()
        out = fn(*args)
        jax.block_until_ready(out)
        holder = [out]

        def run():
            holder[0] = fn(*args)
        times = timed_windows(run, lambda: jax.block_until_ready(holder[0]),
                              steps, windows=windows, warmup=warmup)
        return window_stats(times, items_per_step, steps)

    def conv_build():
        x = jnp.asarray(rng.normal(
            size=(conv_n, conv_cin, conv_hw, conv_hw)), jnp.float32)
        w = jnp.asarray(rng.normal(
            size=(conv_cout, conv_cin, 3, 3)) * 0.2, jnp.float32)
        b = jnp.zeros((conv_cout,), jnp.float32)

        def loss(x, w, b):
            return jnp.sum(helpers.conv2d_bias_act(
                x, w, b, border_mode="same", activation="relu") ** 2)
        return jax.jit(jax.value_and_grad(loss, argnums=(1, 2))), (x, w, b)

    def lstm_build():
        p = {"W": jnp.asarray(rng.normal(
                 size=(lstm_in, 4 * lstm_h)) * 0.2, jnp.float32),
             "RW": jnp.asarray(rng.normal(
                 size=(lstm_h, 4 * lstm_h)) * 0.2, jnp.float32),
             "b": jnp.zeros((4 * lstm_h,), jnp.float32),
             "pI": jnp.zeros((lstm_h,), jnp.float32),
             "pF": jnp.zeros((lstm_h,), jnp.float32),
             "pO": jnp.zeros((lstm_h,), jnp.float32)}
        x = jnp.asarray(rng.normal(
            size=(lstm_n, lstm_t, lstm_in)), jnp.float32)

        def loss(p, x):
            hs, _ = rnn_ops.lstm_scan(p, x)
            return jnp.sum(hs ** 2)
        return jax.jit(jax.grad(loss)), (p, x)

    def xent_build():
        logits = jnp.asarray(rng.normal(size=(xent_n, xent_v)), jnp.float32)
        y = jnp.asarray(np.eye(xent_v, dtype=np.float32)[
            rng.integers(0, xent_v, xent_n)])

        def loss(lg):
            return jnp.sum(losses.mcxent(y, lg, "softmax"))
        return jax.jit(jax.value_and_grad(loss)), (logits,)

    def drop_build():
        x = jnp.asarray(rng.normal(size=drop_shape), jnp.float32)
        key = jax.random.PRNGKey(3)

        def loss(x):
            return jnp.sum(helpers.dropout(x, 0.8, key) ** 2)
        return jax.jit(jax.grad(loss)), (x,)

    workloads = {
        "conv2d": ("DL4J_PALLAS_CONV", conv_build, conv_n),
        "lstm_step": ("DL4J_PALLAS_LSTM", lstm_build, lstm_n * lstm_t),
        "softmax_xent": ("DL4J_FUSED_XENT", xent_build, xent_n),
        "dropout": ("DL4J_PALLAS_DROPOUT", drop_build,
                    drop_shape[0] * drop_shape[1]),
    }
    ops = {}
    speedups = []
    for op, (env_key, build, items) in workloads.items():
        saved = os.environ.get(env_key)
        try:
            os.environ[env_key] = "1"   # selection reads env at trace time
            fused = _time(build, items)
            os.environ[env_key] = "0"
            dense = _time(build, items)
        finally:
            if saved is None:
                os.environ.pop(env_key, None)
            else:
                os.environ[env_key] = saved
        sp = (fused["items_per_sec_median"]
              / max(dense["items_per_sec_median"], 1e-9))
        speedups.append(sp)
        ops[op] = {"fused": fused, "dense": dense,
                   "speedup_fused_vs_dense": round(sp, 3)}
    geomean = float(np.prod(speedups) ** (1.0 / len(speedups)))
    return {
        "metric": "fused-kernel helper tier, fused/dense throughput "
                  "(geomean over ops)",
        "value": round(geomean, 3),
        "unit": "x",
        "emulated_interpret_mode": not on_tpu,
        "self_test": pk_self_test_summary(),
        **ops,
    }


def pk_self_test_summary():
    """One-line helper verdicts for the bench record (full report lands
    in result['pallas_kernels'])."""
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    return {t: ("disabled: " + r[:80]) for t, r in pk._disabled.items()} \
        or "all tiers healthy"


def bench_serving():
    """Closed-loop serving A/B: 8 client threads issue small
    ``predict(features=...)`` requests against the gateway entry point —
    per-request (``coalesce=False``, one jitted output call per request)
    vs dynamic micro-batching (``coalesce=True``,
    server/batcher.py) — on the same cached, bucket-warmed model.
    Reports requests/sec and latency percentiles per leg, the coalesced
    leg's batch-size histogram, and the output-path retrace count, which
    must stay bounded by the warmed bucket ladder (not grow with
    request count)."""
    import tempfile
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.serialization import write_model
    from deeplearning4j_tpu.server.gateway import DeepLearning4jEntryPoint

    F, H, C = 64, 256, 10
    conf = (NeuralNetConfiguration.builder().seed(11).learning_rate(0.01)
            .updater("sgd")
            .shape_bucketing(True)
            .list()
            .layer(L.DenseLayer(n_in=F, n_out=H, activation="relu"))
            .layer(L.DenseLayer(n_in=H, n_out=H, activation="relu"))
            .layer(L.OutputLayer(n_in=H, n_out=C, activation="softmax",
                                 loss="mcxent"))
            .build())
    tmp = tempfile.mkdtemp(prefix="dl4j_serving_bench_")
    model_path = os.path.join(tmp, "model.zip")
    write_model(MultiLayerNetwork(conf).init(), model_path)

    CONCURRENCY, REQS = 8, 60
    MAX_BATCH = 32
    rng = np.random.default_rng(6)
    # single-row requests — the canonical serving shape; coalescing (not
    # request-side batching) must supply the batch.  The bucket ladder,
    # not the request count, bounds the retraces: coalesced batches land
    # on the warmed pow2 rungs, ragged tails included.
    client_rows = [
        [rng.normal(size=(1, F)).astype(np.float32) for _ in range(REQS)]
        for _ in range(CONCURRENCY)]

    def run_leg(coalesce):
        # min_batch == concurrency: hold each batch until every in-flight
        # client has joined (or 2 ms passed) — the throughput-tuned
        # configuration; per-request clients see min_batch-free latency
        ep = DeepLearning4jEntryPoint(max_batch=MAX_BATCH, max_wait_ms=2.0,
                                      min_batch=CONCURRENCY)
        # prime: model load + bucket-ladder warmup outside the timed window
        ep.predict(model_path, features=client_rows[0][0], coalesce=coalesce)
        lat, lat_lock = [], threading.Lock()

        def client(rows):
            ts = []
            for r in rows:
                t0 = time.perf_counter()
                ep.predict(model_path, features=r, coalesce=coalesce)
                ts.append(time.perf_counter() - t0)
            with lat_lock:
                lat.extend(ts)

        # best-of-3 bursts: one timed window is ~0.1 s wall, so a single
        # scheduler hiccup swamps any real effect (the span-overhead A/B
        # needs better than ±20% noise); latencies pool across bursts
        wall = float("inf")
        for _ in range(3):
            threads = [threading.Thread(target=client, args=(rows,))
                       for rows in client_rows]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = min(wall, time.perf_counter() - t0)
        lat.sort()

        def pct(q):
            return round(lat[min(len(lat) - 1, int(q * len(lat)))] * 1e3, 3)

        model = ep.model_cache.peek(model_path)
        tel = model.compile_telemetry.snapshot()
        warm = ep.model_cache.stats()["models"][
            os.path.abspath(model_path)]["warmup"]
        leg = {
            "requests_per_sec": round(CONCURRENCY * REQS / wall, 1),
            "wall_sec": round(wall, 3),
            "latency_ms_p50": pct(0.50),
            "latency_ms_p95": pct(0.95),
            "latency_ms_p99": pct(0.99),
            "output_programs": tel["by_kind"].get("output", 0),
            "warmed_buckets": warm["buckets"] if warm else [],
        }
        if coalesce:
            serving = ep.stats()["serving"]
            if serving:
                s = next(iter(serving.values()))
                leg["rows_per_batch_mean"] = s["rows_per_batch_mean"]
                leg["requests_per_batch_mean"] = s["requests_per_batch_mean"]
                leg["batch_size_hist"] = s["batch_size_hist"]
        qs = getattr(model, "_q_stats", None)
        if qs:
            # the int8 leg's resident-weight story, from the engine's
            # own quantization stats (ops/quantize)
            leg["weight_bytes_quantized"] = qs["quantized_bytes"]
            leg["weight_bytes_dense"] = qs["dense_bytes"]
        ep.close()
        return leg

    legs = {"per_request": run_leg(False), "coalesced": run_leg(True)}
    # precision-tier A/B: the coalesced workload served from int8
    # weight-only quantized params (DL4J_SERVE_QUANT routes through
    # ModelCache → quantize_inference; dequant fuses into the traced
    # output), vs the dense leg above.  Records the throughput ratio
    # and the ~4x resident-weight reduction.
    os.environ["DL4J_SERVE_QUANT"] = "int8"
    try:
        legs["coalesced_int8"] = run_leg(True)
    finally:
        os.environ.pop("DL4J_SERVE_QUANT", None)
    # instrumentation-overhead A/Bs: the coalesced workload with (a)
    # span timing and (b) the event journal hard-disabled (the
    # DL4J_SPANS=0 / DL4J_JOURNAL=0 kill-switch paths — journal emits
    # become no-ops, not queued).  Each must cost ≤ 5% of serving
    # throughput or it can't stay always-on.  Methodology: PAIRED
    # adjacent on/off bursts (order alternating) against one warmed
    # entry point, overhead = 1 - median of per-pair rate ratios.
    # Sequential whole-leg comparison confounds a ~5% effect with
    # machine drift on a loaded 1-core host; pairing cancels the drift
    # because both legs of a pair run ~0.1s apart.
    from deeplearning4j_tpu.monitor import events as _events
    from deeplearning4j_tpu.monitor import tracing as _tracing

    def overhead_ab(set_off, pairs=10):
        ep_j = DeepLearning4jEntryPoint(max_batch=MAX_BATCH,
                                        max_wait_ms=2.0,
                                        min_batch=CONCURRENCY)
        ep_j.predict(model_path, features=client_rows[0][0])

        def one_burst():
            threads = [threading.Thread(target=lambda rs: [
                ep_j.predict(model_path, features=r) for r in rs],
                args=(rows,)) for rows in client_rows]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return CONCURRENCY * REQS / (time.perf_counter() - t0)

        def off_burst():
            set_off(True)
            try:
                return one_burst()
            finally:
                set_off(False)
        one_burst()
        ratios, on_rates, off_rates = [], [], []
        try:
            for i in range(pairs):
                if i % 2:
                    off = off_burst()
                    on = one_burst()
                else:
                    on = one_burst()
                    off = off_burst()
                on_rates.append(on)
                off_rates.append(off)
                ratios.append(on / max(off, 1e-9))
        finally:
            ep_j.close()
        overhead = 1.0 - statistics.median(ratios)
        return overhead, {
            "on_req_per_sec_best": round(max(on_rates), 1),
            "off_req_per_sec_best": round(max(off_rates), 1),
            "on_req_per_sec_median": round(statistics.median(on_rates), 1),
            "off_req_per_sec_median": round(statistics.median(off_rates), 1),
            "pair_ratio_median": round(statistics.median(ratios), 4),
            "pairs": len(ratios),
        }

    span_overhead, legs["spans_ab"] = overhead_ab(
        lambda off: _tracing.set_enabled(False if off else None))
    journal_overhead, legs["journal_ab"] = overhead_ab(
        lambda off: _events.set_enabled(False if off else None))
    # SLO-evaluator A/B (same paired methodology): a live tracker
    # evaluates the stock serving objectives against the process
    # registry at a tight cadence through both legs; the lever is the
    # DL4J_SLO kill switch (evaluate() becomes a no-op), so the ratio
    # isolates exactly what always-on burn-rate evaluation costs the
    # serving path.  Required ≤ 5% like spans and the journal.
    from deeplearning4j_tpu.monitor import slo as _slo
    tracker = _slo.SloTracker(_slo.default_objectives())
    tracker.start(interval_s=0.05)
    try:
        slo_overhead, legs["slo_ab"] = overhead_ab(
            lambda off: _slo.set_enabled(False if off else None))
    finally:
        tracker.stop()
    speedup = (legs["coalesced"]["requests_per_sec"]
               / max(legs["per_request"]["requests_per_sec"], 1e-9))
    ladder = legs["coalesced"]["warmed_buckets"]
    return {
        "span_overhead_pct": round(span_overhead * 100.0, 2),
        "span_overhead_within_5pct": span_overhead <= 0.05,
        "journal_overhead_pct": round(journal_overhead * 100.0, 2),
        "journal_overhead_within_5pct": journal_overhead <= 0.05,
        "slo_overhead_pct": round(slo_overhead * 100.0, 2),
        "slo_overhead_within_5pct": slo_overhead <= 0.05,
        "metric": f"serving predict requests/sec, {CONCURRENCY} concurrent "
                  "clients, dynamic micro-batching",
        "value": legs["coalesced"]["requests_per_sec"],
        "unit": "requests/sec",
        "concurrency": CONCURRENCY,
        "requests_per_client": REQS,
        "max_batch": MAX_BATCH,
        "speedup_coalesced_vs_per_request": round(speedup, 2),
        "meets_2x_target": speedup >= 2.0,
        "retraces_bounded_by_ladder":
            legs["coalesced"]["output_programs"] <= max(1, len(ladder)),
        **legs,
    }


def bench_decode():
    """Stateful-decode A/B (ROADMAP 3b): serving T autoregressive tokens
    to K concurrent streams via the slot-pool decode path
    (``server/decode.py`` — carries live on device, each token is ONE
    pre-compiled gather→step→scatter call, O(1) in prefix length) vs
    the re-run-prefix baseline (every new token re-runs ``output()``
    over the whole consumed prefix — O(T), the only option without
    carried state).  Reports per-token step time at growing prefix
    checkpoints (the O(1) claim is that the stateful line is FLAT),
    steady-state tokens/sec with window variance, the speedup at T=256,
    and the compiled-program count, which the slot/bucket ladder must
    bound."""
    import jax

    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.server.decode import DecodePool

    F, H, K, T = 32, 160, 4, 256
    CHECKPOINTS = (32, 64, 128, 256)
    conf = (NeuralNetConfiguration.builder().seed(17).learning_rate(0.01)
            .shape_bucketing(True)
            .list()
            .layer(L.GravesLSTM(n_in=F, n_out=H, activation="tanh"))
            .layer(L.RnnOutputLayer(n_in=H, n_out=F, activation="softmax",
                                    loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(23)
    x = rng.normal(size=(K, T, F)).astype(np.float32)

    # --- leg A: re-run-prefix.  Serving token P+1 without carried state
    # means output() over the full [K, P, F] prefix; per-token cost is
    # one whole-prefix forward.  Shapes are warmed off-clock so the leg
    # measures compute, not compiles (pow2 checkpoints = bucket rungs).
    prefix_leg = {}
    for p in CHECKPOINTS:
        net.output(x[:, :p])  # warm this bucket rung
        reps = [0.0] * 3
        for i in range(len(reps)):
            t0 = time.perf_counter()
            out = net.output(x[:, :p])
            np.asarray(out)
            reps[i] = time.perf_counter() - t0
        t_med = statistics.median(reps)
        prefix_leg[str(p)] = {
            "per_token_ms": round(t_med * 1e3, 3),
            "tokens_per_sec": round(K / t_med, 1),
        }
    prefix_tps_256 = prefix_leg[str(T)]["tokens_per_sec"]

    # --- leg B: stateful slot decode.  K sessions step token-by-token;
    # each round submits one step per session and the pool coalesces
    # them into one jitted dispatch (min_batch=K holds the batch until
    # every stream joins — the continuous-batching steady state).
    pool = DecodePool(net, name="bench", max_slots=K, max_wait_ms=5.0,
                      min_batch=K)
    sids = [pool.open_session() for _ in range(K)]
    tok = {"t": 0}

    def step_round():
        t = tok["t"]
        futs = [pool.submit_step(sid, x[i, t:t + 1])
                for i, sid in enumerate(sids)]
        for f in futs:
            f.result(timeout=120)
        tok["t"] += 1

    step_round()  # compile off-clock (the one decode program)
    bins = {}
    prev = 1
    for p in CHECKPOINTS:
        n = p - prev
        t0 = time.perf_counter()
        for _ in range(n):
            step_round()
        bins[str(p)] = {
            "per_token_ms": round((time.perf_counter() - t0) / n * 1e3, 3),
        }
        prev = p
    # steady state with window variance: the prefix only grows, so flat
    # windows here ARE the O(1) evidence
    times = []
    for _ in range(WINDOWS):
        t0 = time.perf_counter()
        for _ in range(32):
            step_round()
        times.append(time.perf_counter() - t0)
    stats = window_stats(times, K, 32)
    decode_programs = pool.stats().get("decode_programs", 0)
    ladder = list(pool._ladder)
    carry_bytes_f32 = sum(int(leaf.nbytes) for leaf in
                          jax.tree_util.tree_leaves(pool._pool))
    pool.stop()

    # --- leg C: bf16 resident carry (precision tier).  Same stateful
    # workload but the pool keeps non-KV carry leaves in bfloat16 and
    # upcasts to f32 at the gather, so step compute is unchanged while
    # resident carry bytes halve.  Reports the byte ratio and the
    # steady-state throughput ratio vs the f32 pool above.
    pool16 = DecodePool(net, name="bench16", max_slots=K, max_wait_ms=5.0,
                        min_batch=K, carry_dtype="bfloat16")
    sids = [pool16.open_session() for _ in range(K)]
    tok["t"] = 0

    def step_round16():
        t = tok["t"]
        futs = [pool16.submit_step(sid, x[i, t:t + 1])
                for i, sid in enumerate(sids)]
        for f in futs:
            f.result(timeout=120)
        tok["t"] += 1

    step_round16()  # compile off-clock
    times16 = []
    for _ in range(WINDOWS):
        t0 = time.perf_counter()
        for _ in range(32):
            step_round16()
        times16.append(time.perf_counter() - t0)
    stats16 = window_stats(times16, K, 32)
    carry_bytes_bf16 = sum(int(leaf.nbytes) for leaf in
                           jax.tree_util.tree_leaves(pool16._pool))
    pool16.stop()
    bf16_tps = stats16["items_per_sec_median"]

    per_tok = [bins[str(p)]["per_token_ms"] for p in CHECKPOINTS]
    flat = max(per_tok) / max(min(per_tok), 1e-9)
    decode_tps = stats["items_per_sec_median"]
    speedup = decode_tps / max(prefix_tps_256, 1e-9)
    return {
        "metric": f"stateful slot-decode tokens/sec, {K} concurrent "
                  f"sessions, T={T}",
        "value": round(decode_tps, 1),
        "unit": "tokens/sec",
        "sessions": K,
        "prefix_checkpoints": list(CHECKPOINTS),
        "decode_per_token_ms_by_prefix": bins,
        "decode_flat_ratio_max_over_min": round(flat, 3),
        "decode_flat_in_prefix": flat <= 1.5,
        "rerun_prefix": prefix_leg,
        "speedup_vs_rerun_prefix_at_256": round(speedup, 2),
        "meets_3x_target": speedup >= 3.0,
        "decode_programs": decode_programs,
        "slot_ladder": ladder,
        "retraces_bounded_by_ladder": decode_programs <= max(1, len(ladder)),
        "bf16_carry": {
            "tokens_per_sec": round(bf16_tps, 1),
            "tps_ratio_vs_f32": round(bf16_tps / max(decode_tps, 1e-9), 3),
            "carry_bytes_f32": carry_bytes_f32,
            "carry_bytes_bf16": carry_bytes_bf16,
            "carry_bytes_ratio": round(
                carry_bytes_f32 / max(carry_bytes_bf16, 1), 3),
        },
        **stats,
    }


def bench_spec():
    """KV-cache + speculative-decode A/B (ISSUE 13, ROADMAP 2).

    Leg A — **cached vs re-run-window attention**: an attention model
    decodes token-by-token through the slot pool (the KV-ring carry
    makes each step O(window)) against the only alternative without a
    cache: re-running ``output()`` over the whole consumed window for
    every new token (O(T)).  Reports per-token time at T=64 and T=256
    for both; the cached line's 256/64 ratio must stay ~flat (≤ 1.2)
    while the re-run line grows ~O(T).

    Leg B — **speculative on vs off**: greedy generation through the
    fused verify program (one compiled dispatch scores the pending
    token + K n-gram drafts and commits the agreeing prefix) against
    plain one-token-per-dispatch greedy decode.  Exact same emitted
    tokens (greedy parity is exact by construction); reports dispatches
    per accepted token, acceptance rate, and wall-clock tokens/sec.

    Leg C — **resident-tokens axis** (ISSUE 16): paged KV arena vs
    dense per-slot rings at FIXED KV HBM.  The dense pool pre-commits a
    worst-case ``max_slots x window`` rectangle, so its admission limit
    is slot count no matter how short the streams are; the paged pool
    holds the same token budget in a shared arena and admits by tokens
    actually resident.  A mixed short/long session load is pushed into
    both until they shed; reports sessions admitted (paged/dense must
    be >= 2x), aggregate tokens/sec while filling, and the paged
    pool's own per-token flat ratio (256/64 <= 1.2 — paging must not
    reintroduce O(T) steps)."""
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.resilience.errors import OverloadedError
    from deeplearning4j_tpu.server.decode import DecodePool
    from deeplearning4j_tpu.server.speculative import (
        NGramDraft, SpeculativeDecoder, one_hot)

    V, H, K, T = 16, 32, 2, 256
    CHECKPOINTS = (64, 256)
    conf = (NeuralNetConfiguration.builder().seed(29).learning_rate(0.01)
            .shape_bucketing(True)
            .list()
            .layer(L.SelfAttentionLayer(n_in=V, n_out=H, n_heads=4,
                                        causal=True, cache_window=T))
            .layer(L.RnnOutputLayer(n_in=H, n_out=V, activation="softmax",
                                    loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(41)
    x = rng.normal(size=(K, T, V)).astype(np.float32)

    # --- leg A1: re-run-window.  Serving one more token without a KV
    # cache means output() over the full consumed window — per-token
    # cost IS one whole-window forward (warmed off-clock per rung).
    rerun = {}
    for p in CHECKPOINTS:
        net.output(x[:, :p])
        reps = [0.0] * 3
        for i in range(len(reps)):
            t0 = time.perf_counter()
            np.asarray(net.output(x[:, :p]))
            reps[i] = time.perf_counter() - t0
        rerun[str(p)] = {"per_token_ms":
                         round(statistics.median(reps) * 1e3, 3)}
    rerun_ratio = (rerun[str(CHECKPOINTS[-1])]["per_token_ms"]
                   / max(rerun[str(CHECKPOINTS[0])]["per_token_ms"], 1e-9))

    # --- leg A2: KV-cached slot decode, token-by-token.
    pool = DecodePool(net, name="bench_spec", max_slots=K,
                      max_wait_ms=5.0, min_batch=K)
    sids = [pool.open_session() for _ in range(K)]
    tok = {"t": 0}

    def step_round():
        t = tok["t"]
        futs = [pool.submit_step(sid, x[i, t % T:t % T + 1])
                for i, sid in enumerate(sids)]
        for f in futs:
            f.result(timeout=120)
        tok["t"] += 1

    step_round()   # compile off-clock
    cached = {}
    prev = 1
    for p in CHECKPOINTS:
        n = p - prev
        t0 = time.perf_counter()
        for _ in range(n):
            step_round()
        cached[str(p)] = {"per_token_ms":
                          round((time.perf_counter() - t0) / n * 1e3, 3)}
        prev = p
    flat = (cached[str(CHECKPOINTS[-1])]["per_token_ms"]
            / max(cached[str(CHECKPOINTS[0])]["per_token_ms"], 1e-9))
    for sid in sids:
        pool.close_session(sid)

    # --- leg B: speculative on/off greedy generation.  The untrained
    # model's greedy feedback loop settles into a repetitive stream —
    # the draft-friendly regime structured output lives in — so the
    # n-gram proposer reaches high acceptance after its cold start.
    N_GEN = 96
    prompt = one_hot([i % V for i in range(4)], V)

    def greedy_plain():
        sid = pool.open_session()
        (o,) = pool.step(sid, prompt)
        pending = int(np.argmax(o[-1]))
        toks = []
        t0 = time.perf_counter()
        for _ in range(N_GEN):
            toks.append(pending)
            (o,) = pool.step(sid, one_hot([pending], V))
            pending = int(np.argmax(o[-1]))
        dt = time.perf_counter() - t0
        pool.close_session(sid)
        return toks, N_GEN, dt       # one dispatch per token

    def greedy_spec(k):
        sid = pool.open_session()
        (o,) = pool.step(sid, prompt)
        first = int(np.argmax(o[-1]))
        dec = SpeculativeDecoder(pool, vocab=V, k=k,
                                 draft=NGramDraft(order=3))
        t0 = time.perf_counter()
        res = dec.generate(sid, first, N_GEN)
        dt = time.perf_counter() - t0
        pool.close_session(sid)
        return res["tokens"], res["dispatches"], dt

    greedy_spec(3)   # warm the spec program rungs off-clock
    toks_off, disp_off, dt_off = greedy_plain()
    toks_on, disp_on, dt_on = greedy_spec(3)
    parity = toks_on == toks_off
    spec_stats = {k: v for k, v in pool.metrics.snapshot().items()
                  if k.startswith("spec")}
    st = pool.stats()
    programs = {"decode": st.get("decode_programs", 0),
                "spec": st.get("spec_programs", 0)}
    pool.stop()

    # --- leg C1: paged pool per-token flatness.  Same token-by-token
    # loop as leg A2, but the KV carry is block tables into the shared
    # arena — the ratio proves block-table indirection stays O(window).
    ppool = DecodePool(net, name="bench_spec_pgflat", max_slots=K,
                       max_wait_ms=5.0, min_batch=K, kv_paged=True,
                       kv_block=16, kv_arena_tokens=(K + 1) * T)
    sids = [ppool.open_session() for _ in range(K)]
    tok["t"] = 0

    def pstep_round():
        t = tok["t"]
        futs = [ppool.submit_step(sid, x[i, t % T:t % T + 1])
                for i, sid in enumerate(sids)]
        for f in futs:
            f.result(timeout=120)
        tok["t"] += 1

    pstep_round()   # compile off-clock
    pcached = {}
    prev = 1
    for p in CHECKPOINTS:
        n = p - prev
        t0 = time.perf_counter()
        for _ in range(n):
            pstep_round()
        pcached[str(p)] = {"per_token_ms":
                           round((time.perf_counter() - t0) / n * 1e3, 3)}
        prev = p
    pflat = (pcached[str(CHECKPOINTS[-1])]["per_token_ms"]
             / max(pcached[str(CHECKPOINTS[0])]["per_token_ms"], 1e-9))
    for sid in sids:
        ppool.close_session(sid)
    ppool.stop()

    # --- leg C2: admission at fixed KV HBM.  Dense baseline: 4 slots x
    # the full T=256 window (1024 tokens pre-committed whether streams
    # use them or not).  Paged: the SAME 1024-token budget as a shared
    # arena.  The load is mixed — every 4th session streams the full
    # window, the rest stop at 32 tokens — so the paged pool's 64
    # blocks go 16+2+2+2 per cycle instead of 4x16.
    S_DENSE, SHORT, CHUNK = 4, 32, 32
    ARENA_TOKENS = S_DENSE * T

    def admit_mixed(p):
        """Open+stream sessions until the pool sheds; a session counts
        only when its whole stream landed.  Returns (admitted sids,
        tokens streamed, wall seconds)."""
        warm = p.open_session()          # compile the chunk rung
        p.step(warm, x[0, :CHUNK])       # off-clock
        p.close_session(warm)
        admitted, toks = [], 0
        t0 = time.perf_counter()
        for i in range(64):
            ln = T if i % 4 == 0 else SHORT
            try:
                sid = p.open_session()
            except OverloadedError:
                break
            try:
                for c0 in range(0, ln, CHUNK):
                    p.step(sid, x[i % K, c0:c0 + CHUNK])
            except OverloadedError:
                p.close_session(sid)     # shed mid-stream: not admitted
                break
            admitted.append(sid)
            toks += ln
        return admitted, toks, time.perf_counter() - t0

    dpool = DecodePool(net, name="bench_spec_dense", max_slots=S_DENSE,
                       max_wait_ms=2.0, min_batch=1)
    adm_d, toks_d, dt_d = admit_mixed(dpool)
    dense_kv = dpool.stats().get("kv_cache")
    dpool.stop()

    apool = DecodePool(net, name="bench_spec_paged", max_slots=48,
                       max_wait_ms=2.0, min_batch=1, kv_paged=True,
                       kv_block=16, kv_arena_tokens=ARENA_TOKENS)
    adm_p, toks_p, dt_p = admit_mixed(apool)
    arena_kv = apool.stats().get("kv_arena")
    apool.stop()
    admit_ratio = len(adm_p) / max(len(adm_d), 1)

    tokens_per_dispatch = N_GEN / max(disp_on, 1)
    return {
        "metric": "speculative greedy decode, accepted tokens per "
                  "compiled dispatch",
        "value": round(tokens_per_dispatch, 2),
        "unit": "tokens/dispatch",
        "cached_per_token_ms": cached,
        "cached_flat_ratio_256_over_64": round(flat, 3),
        "cached_flat": flat <= 1.2,
        "rerun_window_per_token_ms": rerun,
        "rerun_ratio_256_over_64": round(rerun_ratio, 3),
        "spec_greedy_parity": parity,
        "spec_dispatches": disp_on,
        "plain_dispatches": disp_off,
        "dispatch_reduction": round(disp_off / max(disp_on, 1), 2),
        "meets_2x_accept_target": tokens_per_dispatch >= 2.0,
        "spec_tokens_per_sec": round(N_GEN / max(dt_on, 1e-9), 1),
        "plain_tokens_per_sec": round(N_GEN / max(dt_off, 1e-9), 1),
        "pool_spec_counters": spec_stats,
        "compiled_programs": programs,
        "kv_cache": st.get("kv_cache"),
        "paged": {
            "kv_hbm_tokens": ARENA_TOKENS,
            "dense_sessions_admitted": len(adm_d),
            "paged_sessions_admitted": len(adm_p),
            "session_admit_ratio": round(admit_ratio, 2),
            "meets_2x_sessions_target": admit_ratio >= 2.0,
            "dense_fill_tokens_per_sec": round(toks_d / max(dt_d, 1e-9), 1),
            "paged_fill_tokens_per_sec": round(toks_p / max(dt_p, 1e-9), 1),
            "paged_per_token_ms": pcached,
            "paged_flat_ratio_256_over_64": round(pflat, 3),
            "paged_flat": pflat <= 1.2,
            "dense_kv_cache": dense_kv,
            "kv_arena": arena_kv,
        },
    }


def bench_fleet():
    """Fleet scaling A/B (ROADMAP 3 → the fleet tier): K closed-loop
    decode clients streaming through the consistent-hash
    ``SessionRouter`` against 1 vs 2 gateway replicas (in-process HTTP
    servers — real wire hops, localhost transport).  Reports routed
    tokens/sec per leg with window variance, p50/p99 routed step
    latency, and the 2-vs-1 scaling ratio.  On a 1-core CPU box the
    replicas share the core, so the scaling ratio mostly measures
    router overhead; on real hardware (one chip per replica) it is the
    horizontal-scale headline."""
    import tempfile

    from deeplearning4j_tpu.fleet import SessionRouter
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.serialization import write_model
    from deeplearning4j_tpu.server import DeepLearning4jEntryPoint, Server

    F, H, K, STEPS = 16, 96, 4, 24
    conf = (NeuralNetConfiguration.builder().seed(11).learning_rate(0.01)
            .shape_bucketing(True)
            .list()
            .layer(L.GravesLSTM(n_in=F, n_out=H, activation="tanh"))
            .layer(L.RnnOutputLayer(n_in=H, n_out=F, activation="softmax",
                                    loss="mcxent"))
            .build())
    path = os.path.join(tempfile.mkdtemp(prefix="dl4j_bench_fleet_"),
                        "lstm.zip")
    write_model(MultiLayerNetwork(conf).init(), path)
    rng = np.random.default_rng(31)
    x = rng.normal(size=(K, STEPS, F)).astype(np.float32)

    def leg(n_replicas):
        servers = [Server(DeepLearning4jEntryPoint(
            decode_slots=2 * K, max_wait_ms=1.0), port=0).start()
            for _ in range(n_replicas)]
        router = SessionRouter()
        for i, s in enumerate(servers):
            router.add_replica(f"r{i}", f"http://{s.host}:{s.port}")
        try:
            sids = [router.open_session(path)["session_id"]
                    for _ in range(K)]
            lat_lock = threading.Lock()

            def run_client(ci, sid, n_steps, lats=None):
                for t in range(n_steps):
                    t0 = time.perf_counter()
                    router.decode_step(sid, x[ci, t % STEPS:
                                              t % STEPS + 1].tolist())
                    if lats is not None:
                        dt = time.perf_counter() - t0
                        with lat_lock:
                            lats.append(dt)

            def round_trip(n_steps, collect):
                lats = [] if collect else None
                threads = [threading.Thread(
                    target=run_client, args=(i, sid, n_steps, lats))
                    for i, sid in enumerate(sids)]
                t0 = time.perf_counter()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=600)
                return time.perf_counter() - t0, lats

            round_trip(2, collect=False)   # compile + route warm, off-clock
            times, all_lats = [], []
            for _ in range(WINDOWS):
                wall, lats = round_trip(STEPS, collect=True)
                times.append(wall)
                all_lats.extend(lats)
            for sid in sids:
                router.close_session(sid)
            all_lats.sort()

            def pct(p):
                return round(
                    all_lats[min(len(all_lats) - 1,
                                 int(p * (len(all_lats) - 1)))] * 1e3, 3)
            out = window_stats(times, K, STEPS)
            out.update({
                "replicas": n_replicas,
                "clients": K,
                "routed_p50_ms": pct(0.50),
                "routed_p99_ms": pct(0.99),
                "router": {k: v for k, v in router.stats().items()
                           if k in ("sessions_lost",)},
            })
            return out
        finally:
            for s in servers:
                s.stop()

    one = leg(1)
    two = leg(2)
    scaling = (two["items_per_sec_median"]
               / max(one["items_per_sec_median"], 1e-9))
    return {
        "metric": f"routed decode tokens/sec through the fleet router, "
                  f"{K} closed-loop clients, 2 replicas",
        "value": round(two["items_per_sec_median"], 1),
        "unit": "tokens/sec",
        "one_replica": one,
        "two_replicas": two,
        "scaling_2v1": round(scaling, 3),
        "routed_p99_ms": two["routed_p99_ms"],
        **{k: v for k, v in two.items()
           if k.startswith("items_per_sec") or k in (
               "window_rel_spread", "best_of", "window_sec",
               "steps_per_window")},
    }


def bench_elastic():
    """Elastic-cluster training A/B (ROADMAP 1 → distributed/): the
    SAME model+stream trained single-host vs as a 2-worker
    coordinator-backed cluster (in-process worker threads — real
    barrier, real membership protocol, localhost-free transport), plus
    the preemption headline: TIME-TO-RECOVER from a fault-injected
    worker kill (``dist.worker``), measured as the survivor's wall time
    for the step that spans detection (lease+grace lapse) → generation
    roll → reshard → first post-resize commit.  On a 1-core CPU the
    workers share the core so steady-state mostly measures barrier
    overhead; on real multi-host hardware the cluster leg is the
    horizontal-scale headline."""
    import threading

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.distributed import Coordinator, DistSession
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.resilience import faults as faults_mod

    ROWS, FEAT, HID, CLASSES = 64, 32, 96, 8
    STEPS = 8
    LEASE_MS = 250.0

    def make_net(dist, quant=None):
        b = (NeuralNetConfiguration.builder().seed(5).learning_rate(0.01)
             .updater("adam"))
        if dist:
            b.distributed(processes=2, heartbeat_ms=50, lease_ms=LEASE_MS)
        if quant:
            b.precision(grad_allreduce=quant)
        conf = (b.list()
                .layer(L.DenseLayer(n_in=FEAT, n_out=HID,
                                    activation="relu"))
                .layer(L.OutputLayer(n_out=CLASSES, activation="softmax",
                                     loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(17)

    def batches(n):
        return [DataSet(
            rng.normal(size=(ROWS, FEAT)).astype(np.float32),
            np.eye(CLASSES, dtype=np.float32)[
                rng.integers(0, CLASSES, ROWS)]) for _ in range(n)]

    window_sets = [batches(STEPS) for _ in range(WINDOWS)]

    # -- leg 1: single host -------------------------------------------
    net = make_net(dist=False)
    net.fit(ListDataSetIterator(batches(2)))   # compile, off-clock
    single_times = []
    for ws in window_sets:
        t0 = time.perf_counter()
        net.fit(ListDataSetIterator(list(ws)))
        single_times.append(time.perf_counter() - t0)
    single = window_stats(single_times, ROWS, STEPS)

    # -- leg 2: 2-worker cluster steady state -------------------------
    from deeplearning4j_tpu import monitor

    def _grad_bytes(dtype):
        fam = monitor.get_registry().get("dl4j_precision_grad_bytes_total")
        if fam is None:
            return 0.0
        return sum(s["value"] for s in fam.samples()
                   if s["labels"].get("dtype") == dtype)

    faults_mod.reset()
    co = Coordinator(expected=2, lease_ms=LEASE_MS)
    cluster_times = []
    errors = []
    f32_bytes0 = _grad_bytes("float32")

    def steady_worker(wid):
        try:
            wnet = make_net(dist=True)
            sess = DistSession(co, wid, heartbeat_ms=50)
            sess.connect()
            wnet._dist_session = sess
            wnet.fit(ListDataSetIterator(batches(2)))   # warm
            for ws in window_sets:
                t0 = time.perf_counter()
                wnet.fit(ListDataSetIterator(list(ws)))
                if wid == "w0":
                    cluster_times.append(time.perf_counter() - t0)
            sess.close()
        except BaseException as e:  # noqa: BLE001
            errors.append(f"{wid}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=steady_worker, args=(f"w{i}",))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
    assert not errors, errors
    cluster = window_stats(cluster_times, ROWS, STEPS)
    f32_bytes = _grad_bytes("float32") - f32_bytes0

    # -- leg 4 (run before the chaos leg so counters stay clean):
    # quantized-gradient cluster (precision tier).  Same 2-worker
    # steady state, but every barrier contribution ships int8 codes +
    # per-block scales with persistent error feedback
    # (conf.precision(grad_allreduce="int8")).  Measures bytes-per-step
    # through the engine's own dl4j_precision_grad_bytes_total counter
    # — the ACTUAL wire payload sizes, not an estimate — plus the
    # step-time ratio and cross-worker bit-identity of final params.
    faults_mod.reset()
    co4 = Coordinator(expected=2, lease_ms=LEASE_MS)
    quant_times = []
    qerrors = []
    qparams = {}

    def quant_worker(wid):
        try:
            wnet = make_net(dist=True, quant="int8")
            sess = DistSession(co4, wid, heartbeat_ms=50)
            sess.connect()
            wnet._dist_session = sess
            wnet.fit(ListDataSetIterator(batches(2)))   # warm
            for ws in window_sets:
                t0 = time.perf_counter()
                wnet.fit(ListDataSetIterator(list(ws)))
                if wid == "q0":
                    quant_times.append(time.perf_counter() - t0)
            qparams[wid] = np.ascontiguousarray(
                np.asarray(wnet.params()), np.float32)
            sess.close()
        except BaseException as e:  # noqa: BLE001
            qerrors.append(f"{wid}: {type(e).__name__}: {e}")

    int8_bytes0 = _grad_bytes("int8")
    threads = [threading.Thread(target=quant_worker, args=(f"q{i}",))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
    assert not qerrors, qerrors
    int8_bytes = _grad_bytes("int8") - int8_bytes0
    quant = window_stats(quant_times, ROWS, STEPS)
    # both legs run the identical step structure (2 warm + WINDOWS*STEPS
    # per worker), so per-step bytes divide by the same count
    barrier_steps = 2 * (2 + WINDOWS * STEPS)
    bytes_reduction = f32_bytes / max(int8_bytes, 1e-9)

    # -- leg 3: time-to-recover from a killed worker ------------------
    faults_mod.reset()
    co2 = Coordinator(expected=2, lease_ms=LEASE_MS,
                      suspect_grace_ms=LEASE_MS)
    step_times = {}
    KILL_AT = 6

    class _StepClock:
        def __init__(self):
            self.marks = []
            self.last = time.perf_counter()

        def iteration_done(self, model, iteration):
            now = time.perf_counter()
            self.marks.append((iteration, now - self.last))
            self.last = now

    def chaos_worker(wid):
        try:
            wnet = make_net(dist=True)
            clock = _StepClock()
            wnet.add_listener(clock)
            sess = DistSession(co2, wid, heartbeat_ms=50)
            sess.connect()
            wnet._dist_session = sess
            wnet.fit(ListDataSetIterator(batches(16)))
            step_times[wid] = clock.marks
            sess.close()
        except BaseException:  # noqa: BLE001 — the preempted worker
            step_times.setdefault("killed", []).append(wid)

    faults_mod.arm({"site": "dist.worker", "mode": "kill",
                    "on_call": 2 * KILL_AT, "max_injections": 1})
    threads = [threading.Thread(target=chaos_worker, args=(f"c{i}",))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
    faults_mod.reset()
    survivor = [w for w in ("c0", "c1") if w in step_times]
    assert survivor and step_times.get("killed"), step_times
    marks = step_times[survivor[0]]
    # the recovery step is the one that waited out the dead lease and
    # recomputed under the shrunk generation: the max post-warmup step
    post = [dt for it, dt in marks if it > 2]
    steady_ms = statistics.median(post) * 1e3
    recover_s = max(post)

    overhead = (cluster["step_time_ms_median"]
                / max(single["step_time_ms_median"], 1e-9))
    return {
        "metric": "elastic 2-worker cluster examples/sec (steady "
                  "state) + time-to-recover from a worker kill",
        "value": round(cluster["items_per_sec_median"], 1),
        "unit": "examples/sec",
        "single_host": single,
        "cluster_2w": cluster,
        "barrier_overhead_x": round(overhead, 3),
        "recover_from_kill_s": round(recover_s, 3),
        "recovery_vs_steady_step_ms": [round(recover_s * 1e3, 1),
                                       round(steady_ms, 1)],
        "lease_ms": LEASE_MS,
        "generations": co2.status()["generation"],
        "grad_quant": {
            "quant_active": int8_bytes > 0,
            "bytes_per_step_fp32": round(f32_bytes / barrier_steps, 1),
            "bytes_per_step_int8": round(int8_bytes / barrier_steps, 1),
            "bytes_reduction_x": round(bytes_reduction, 3),
            "meets_3_5x_target": int8_bytes > 0 and bytes_reduction >= 3.5,
            "step_time_ratio_vs_fp32": round(
                quant["step_time_ms_median"]
                / max(cluster["step_time_ms_median"], 1e-9), 3),
            "cluster_2w_int8": quant,
            "workers_bit_identical": bool(
                len(qparams) == 2
                and np.array_equal(qparams["q0"], qparams["q1"])),
        },
        **{k: v for k, v in cluster.items()
           if k.startswith("items_per_sec") or k in (
               "window_rel_spread", "best_of", "window_sec",
               "steps_per_window")},
    }


def bench_sharded_serving(n_chips):
    """Sharded-inference A/B (ROADMAP 3a): the same wide-MLP ``output()``
    replica-style vs under ``conf.sharding(data=1, fsdp=n_chips)`` — the
    pjit'd output path with the plan's in/out shardings (params stay in
    their fsdp layout, batch shards over the mesh, ONE host gather at
    the response edge).  Reports rows/sec per leg with window variance
    and cross-leg output parity; on one device the sharded conf degrades
    to replica-style and the record says so."""
    import jax
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    BATCH, FEAT, HID, CLASSES = 256, 512, 512, 64
    fsdp_degree = max(1, n_chips)
    rng = np.random.default_rng(29)
    x = rng.normal(size=(BATCH, FEAT)).astype(np.float32)

    def make_net(shard):
        b = NeuralNetConfiguration.builder().seed(3).updater("adam") \
            .learning_rate(1e-3)
        if shard:
            b.sharding(data=1, fsdp=fsdp_degree)
        conf = (b.list()
                .layer(L.DenseLayer(n_in=FEAT, n_out=HID,
                                    activation="relu"))
                .layer(L.DenseLayer(n_in=HID, n_out=HID,
                                    activation="relu"))
                .layer(L.OutputLayer(n_in=HID, n_out=CLASSES,
                                     activation="softmax", loss="mcxent"))
                .build())
        return MultiLayerNetwork(conf).init()

    legs = {}
    outs = {}
    for name, shard in (("replica", False), ("sharded", True)):
        net = make_net(shard)
        if shard:
            # identical weights so the parity row is meaningful
            import jax.numpy as jnp
            ref = legs["replica"]["_net"]
            net.net_params = jax.tree_util.tree_map(jnp.asarray,
                                                    ref.net_params)
            net._output_fn = None
        net.output(x)  # compile off-clock

        def run():
            outs[name] = net.output(x)

        times = timed_windows(
            run, lambda: jax.block_until_ready(outs[name]), steps=10,
            warmup=2)
        leg = window_stats(times, BATCH, 10)
        leg["_net"] = net
        if shard:
            leg["sharding_active"] = \
                getattr(net, "_sharding_plan", None) is not None
        legs[name] = leg
    parity = float(np.max(np.abs(
        np.asarray(jax.device_get(outs["replica"]))
        - np.asarray(jax.device_get(outs["sharded"])))))
    for leg in legs.values():
        leg.pop("_net")
    sh = legs["sharded"]
    return {
        "metric": f"wide-MLP output() rows/sec, replica vs sharded "
                  f"serving (fsdp={fsdp_degree})",
        "value": round(sh["items_per_sec_median"], 1),
        "unit": "rows/sec (sharded leg)",
        "fsdp_degree": fsdp_degree,
        "sharding_active": sh.get("sharding_active", False),
        "single_device_degrade": not sh.get("sharding_active", False),
        "speedup_vs_replica": round(
            sh["items_per_sec_median"]
            / max(legs["replica"]["items_per_sec_median"], 1e-9), 3),
        "output_abs_parity": parity,
        "parity_within_1e6": parity <= 1e-6,
        **legs,
    }


def acquire_backend():
    """Initialise the JAX backend this process measures on.  The bench
    is a chip bench: it runs on a TPU, or on the CPU only when the
    caller asks for the CPU by name (``DL4J_BENCH_PLATFORM=cpu`` — the
    dry-run smoke and the count-only CPU legs).  Anything else raises:
    a backend that fails to initialise, or a default backend that is
    not a TPU, must never produce a record that says
    ``samples/sec/chip``.  This process is the only one that touches
    the chip.  Returns (devices, info)."""
    import jax
    info = {}
    forced = os.environ.get("DL4J_BENCH_PLATFORM")
    if forced:
        jax.config.update("jax_platforms", forced)
        info["platform_forced"] = forced
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and forced != "cpu":
        raise RuntimeError(
            f"bench.py needs a TPU and found platform '{platform}' "
            "(set DL4J_BENCH_PLATFORM=cpu to run the CPU legs by name)")
    info["platform"] = platform
    return devs, info


_EMIT_LOCK = threading.Lock()
_EMITTED = False


def _emit(result):
    """Print the one JSON line exactly once (main path and watchdog race)."""
    global _EMITTED
    with _EMIT_LOCK:
        if _EMITTED:
            return
        _EMITTED = True
        print(json.dumps(result), flush=True)


# Mutable watchdog deadline (epoch seconds): tight while acquiring the
# backend (the likely C-hang point), extended by _run_configs once the
# backend is up and the slow-but-progressing compile/run phase starts.
_WATCHDOG = {"deadline": None}


def _start_watchdog(result, deadline_s):
    """Daemon thread that force-emits the JSON line and exits the process
    when the (mutable) deadline passes.  This is the ONLY guard that works
    when the main thread is wedged in C (PJRT backend init / XLA compile):
    signal handlers only run at Python bytecode boundaries, but another
    thread can still print and os._exit."""
    _WATCHDOG["deadline"] = time.time() + deadline_s

    def _watch():
        while True:
            deadline = _WATCHDOG["deadline"]
            if deadline is None:  # run finished — stand down
                return
            remaining = deadline - time.time()
            if remaining <= 0:
                break
            time.sleep(min(remaining, 15))
        # The main thread may be mutating `result` concurrently — any
        # failure here (e.g. dict-changed-during-json.dumps) must still
        # reach os._exit with SOME JSON line, or the guard is useless.
        try:
            result.setdefault(
                "fatal_error",
                "watchdog: hard deadline hit "
                "(likely C-level hang in backend init or compile)")
            log(result["fatal_error"])
            _emit(result)
        except BaseException:
            try:
                _emit({"metric": result.get("metric", "bench"),
                       "value": 0.0, "unit": "samples/sec/chip",
                       "vs_baseline": 0.0,
                       "fatal_error": "watchdog: hard deadline hit "
                                      "(result dict unserializable)"})
            except BaseException:
                pass
        finally:
            os._exit(3)

    threading.Thread(target=_watch, daemon=True, name="bench-watchdog").start()


def main():
    # From here down every failure mode must still end in ONE JSON line
    # on stdout — a bench that can exit without printing is not a bench.
    result = {
        "metric": "LeNet-MNIST MultiLayerNetwork.fit() samples/sec/chip",
        "value": 0.0,
        "unit": "samples/sec/chip",
        "vs_baseline": 0.0,
    }
    try:
        import signal

        def _bail(signum, frame):
            raise TimeoutError(f"signal {signum}")
        # SIGTERM (driver kill) and a hard alarm at 2x the config budget
        # both unwind through the except below so the JSON line still
        # prints.  Neither can interrupt a C-level hang — that is the
        # watchdog thread's job.
        signal.signal(signal.SIGTERM, _bail)
        signal.signal(signal.SIGALRM, _bail)
        budget = float(os.environ.get("DL4J_BENCH_BUDGET_SEC", 1500))
        # Tight while acquiring the backend: if that wedges the process
        # in C, the bench still emits within 10 minutes instead of being
        # SIGKILLed mute.
        _start_watchdog(result, 600)
        signal.alarm(int(budget * 2) + 300)
        # the run-phase watchdog (set after backend acquisition) must
        # fire AFTER this alarm so a budget overrun takes the graceful
        # SIGALRM unwind (traceback recorded) and the watchdog stays a
        # C-hang backstop only
        _WATCHDOG["alarm_time"] = time.time() + budget * 2 + 300
        _run_configs(result)
        signal.alarm(0)
        _WATCHDOG["deadline"] = None  # completed: cancel the force-exit
    except BaseException as e:  # incl. KeyboardInterrupt from a driver kill
        result["fatal_error"] = f"{type(e).__name__}: {e}"[:500]
        log(traceback.format_exc())
    finally:
        _emit(result)
    if (result.get("bench_gate") or {}).get("failed"):
        # regression gate (ROADMAP 5): the record is out — now fail the
        # process so CI / the nightly driver can't miss it
        sys.exit(4)
    failed = [n for n, c in (result.get("configs") or {}).items()
              if "error" in c]
    disabled = (result.get("pallas_kernels") or {}).get("disabled")
    if "fatal_error" in result or failed or disabled:
        # the record is out; no backend, a config that raised, or a
        # kernel tier the self-test switched off is a failed run
        log(f"bench FAILED: fatal={result.get('fatal_error')} "
            f"configs={failed} disabled_tiers={disabled}")
        sys.exit(1)


def _run_configs(result):
    from deeplearning4j_tpu.ops import platform

    devices, backend_info = acquire_backend()
    result.update(backend_info)
    result["machine"] = machine_fingerprint(devices)
    # Backend is up: extend the watchdog to cover the compile/run phase —
    # strictly AFTER the SIGALRM guard so the graceful unwind goes first.
    budget = float(os.environ.get("DL4J_BENCH_BUDGET_SEC", 1500))
    _WATCHDOG["deadline"] = max(
        time.time() + budget * 2 + 240,
        (_WATCHDOG.get("alarm_time") or 0) + 60)
    import jax
    n_chips = max(1, len(devices))
    kind = platform.device_kind()
    peak = platform.peak_flops_bf16()
    log(f"devices={n_chips} kind={kind!r} is_tpu={platform.is_tpu()} "
        f"bf16_peak={peak}")

    # DL4J_BENCH_DRY_RUN=1: exercise every piece of record/registry
    # plumbing (backend acquisition, config registration, the final JSON
    # record with its metrics_registry digest) WITHOUT running a single
    # bench — the tier-1 smoke test that catches a main()-path crash
    # in pytest instead of the nightly.
    dry_run = os.environ.get("DL4J_BENCH_DRY_RUN") == "1"

    # Compile-check the Pallas kernels BEFORE any config touches them:
    # a Mosaic rejection here downgrades that tier to the dense path so
    # the other configs still run, is recorded under
    # pallas_kernels["disabled"], and fails the process at exit.
    if not dry_run:
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        t0 = time.perf_counter()
        result["pallas_kernels"] = pk.kernel_self_test()
        log(f"pallas self-test ({time.perf_counter() - t0:.1f}s): "
            f"{result['pallas_kernels']}")

    # Per-run wall-clock budget: the headline (lenet) runs first; if a
    # later config's compile drags past the budget the remaining ones
    # are reported as skipped rather than risking the whole bench being
    # killed with NO output (DL4J_BENCH_BUDGET_SEC to override).
    budget = float(os.environ.get("DL4J_BENCH_BUDGET_SEC", 1500))
    t_start = time.perf_counter()
    configs = {}
    result["compile_cache_dir"] = jax.config.jax_compilation_cache_dir
    config_list = [
        ("lenet", lambda: bench_lenet("bf16")),
        ("lenet_etl", bench_lenet_etl),
        ("lenet_f32", lambda: bench_lenet("f32")),
        ("bench_ragged", bench_ragged),
        ("bench_pipeline", bench_pipeline),
        ("bench_serving", bench_serving),
        ("bench_decode", bench_decode),
        ("bench_spec", bench_spec),
        ("bench_fleet", bench_fleet),
        ("bench_elastic", bench_elastic),
        ("bench_resilience", bench_resilience),
        ("bench_sharded", lambda: bench_sharded(n_chips, peak)),
        ("bench_sharded_serving", lambda: bench_sharded_serving(n_chips)),
        ("bench_kernels", bench_kernels),
        ("vgg16", lambda: bench_vgg16(peak)),
        ("charrnn", bench_charrnn),
        ("word2vec", bench_word2vec),
        ("resnet50", lambda: bench_resnet50(n_chips, peak)),
    ]
    on_tpu = platform.is_tpu()
    if on_tpu:
        # TPU-only A/B experiments (round-3 verdict next #3): the
        # dispatch-free scan ceilings (meaningless on XLA:CPU, where scan
        # bodies miss fusion), the NHWC-internal conv layout, and the
        # vgg16 batch ladder (round-4 verdict next #2: name the next
        # lever if MFU falls short)
        config_list.insert(2, ("lenet_scan", bench_lenet_scan))
        vgg_at = [n for n, _ in config_list].index("vgg16")
        config_list.insert(vgg_at + 1,
                           ("vgg16_nhwc", lambda: bench_vgg16(peak, "nhwc")))
        config_list.insert(vgg_at + 2,
                           ("vgg16_b512",
                            lambda: bench_vgg16(peak, batch=512)))
        rnn_at = [n for n, _ in config_list].index("charrnn")
        config_list.insert(rnn_at + 1, ("charrnn_scan", bench_charrnn_scan))
    else:
        # CPU (DL4J_BENCH_PLATFORM=cpu): the conv giants take the whole
        # wall-clock budget — run the cheap configs first
        order = ["lenet", "lenet_etl", "lenet_f32", "bench_ragged",
                 "bench_kernels", "bench_pipeline", "bench_serving",
                 "bench_decode", "bench_spec", "bench_fleet",
                 "bench_elastic", "bench_resilience",
                 "bench_sharded", "bench_sharded_serving", "charrnn",
                 "word2vec", "vgg16", "resnet50"]
        config_list.sort(key=lambda nv: order.index(nv[0])
                         if nv[0] in order else len(order))
        if os.environ.get("DL4J_BENCH_SCAN") == "1":
            config_list.insert(2, ("lenet_scan", bench_lenet_scan))
    if dry_run:
        # the precision A/B legs (int8 serving, bf16 decode carry,
        # quantized gradient all-reduce) ride bench_serving /
        # bench_decode / bench_elastic — those configs must stay
        # registered whichever order branch (TPU-first insertions or
        # the CPU sort) built the final list
        names = [n for n, _ in config_list]
        for cfg in ("bench_serving", "bench_decode", "bench_elastic"):
            assert cfg in names, (cfg, names)
        result["precision_ab_configs"] = [
            "bench_serving", "bench_decode", "bench_elastic"]
        # the lint gate rides the dry-run smoke: a rule regression (or a
        # new unsuppressed finding) fails tier-1 loudly, next to the
        # record-plumbing checks this path already covers
        import subprocess
        import sys as _sys
        repo = os.path.dirname(os.path.abspath(__file__))
        proc = subprocess.run(
            [_sys.executable, "-m", "deeplearning4j_tpu.analysis",
             "deeplearning4j_tpu", "tests", "--format", "json"],
            cwd=repo, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, (
            f"dl4j-lint gate failed (exit {proc.returncode}):\n"
            f"{proc.stdout[-2000:]}{proc.stderr[-1000:]}")
        lint_summary = json.loads(proc.stdout)["summary"]
        assert lint_summary["gating"] == 0, lint_summary
        result["lint"] = {"exit_code": proc.returncode, **lint_summary}
        log(f"dl4j-lint gate: exit 0, {lint_summary}")
        # the concurrency checker rides the same smoke: a bounded
        # exploration of the serving-stack protocols must stay at zero
        # violations (CPU-forced: the checker never needs the chip and
        # a second TPU client in a subprocess would fight this one)
        chk = subprocess.run(
            [_sys.executable, "-m", "deeplearning4j_tpu.analysis.check",
             "--schedules", "40", "--seed", "0", "--budget-s", "120",
             "--format", "json"],
            cwd=repo, env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=600)
        assert chk.returncode == 0, (
            f"dl4j-check gate failed (exit {chk.returncode}):\n"
            f"{chk.stdout[-2000:]}{chk.stderr[-1000:]}")
        chk_doc = json.loads(chk.stdout)
        assert not chk_doc["violations"], chk_doc["violations"][:3]
        result["check"] = {
            "exit_code": chk.returncode,
            "total_runs": chk_doc["total_runs"],
            "total_distinct": chk_doc["total_distinct"],
            "violations": len(chk_doc["violations"]),
            "scenarios": {k: {"runs": v["runs"],
                              "distinct": v["distinct"]}
                          for k, v in chk_doc["scenarios"].items()},
        }
        log(f"dl4j-check gate: exit 0, {chk_doc['total_runs']} "
            f"schedules, {chk_doc['total_distinct']} distinct, "
            "0 violations")
        # federated-scrape smoke: the fleet router's ?scope=fleet
        # surface must return text the exposition parser round-trips
        # (two in-process gateway replicas over real HTTP — no model,
        # no jit, cheap enough for tier-1)
        from deeplearning4j_tpu import monitor as _monitor
        from deeplearning4j_tpu.fleet import SessionRouter
        from deeplearning4j_tpu.server import (
            DeepLearning4jEntryPoint, Server)
        fed_servers = [Server(DeepLearning4jEntryPoint(), port=0).start()
                       for _ in range(2)]
        fed_router = SessionRouter()
        try:
            for i, s in enumerate(fed_servers):
                fed_router.add_replica(f"r{i}",
                                       f"http://{s.host}:{s.port}")
            scraped = fed_router.federation_scrape()
            assert all(scraped.values()), scraped
            fed = fed_router.metrics(scope="fleet")
            parsed = _monitor.parse_prometheus(fed["body"])
            assert "dl4j_federation_scrape_age_seconds" in parsed, \
                sorted(parsed)[:8]
            result["federation"] = {"replicas": len(fed_servers),
                                    "families": len(parsed),
                                    "parse_ok": True}
        finally:
            fed_router.close()
            for s in fed_servers:
                s.stop()
        log(f"federated-scrape smoke: {result['federation']}")

    for name, fn in config_list:
        if dry_run:
            configs[name] = {"skipped": "dry-run"}
            continue
        elapsed = time.perf_counter() - t_start
        if name != "lenet" and elapsed > budget:
            configs[name] = {"skipped": f"time budget ({elapsed:.0f}s "
                                        f"> {budget:.0f}s)"}
            log(f"{name} SKIPPED: over time budget")
            continue
        t0 = time.perf_counter()
        try:
            compiled_step.last_compile_sec = None
            configs[name] = fn()
            if compiled_step.last_compile_sec is not None:
                configs[name].setdefault("compile_sec",
                                         compiled_step.last_compile_sec)
            configs[name]["config_wall_sec"] = round(
                time.perf_counter() - t0, 1)
            # every record carries its own fingerprint so a single
            # config copied out of the JSON stays attributable
            configs[name].setdefault("machine", result["machine"])
            log(f"{name}: {configs[name]['value']} {configs[name]['unit']} "
                f"({time.perf_counter() - t0:.1f}s)")
        except Exception as e:
            configs[name] = {"error": f"{type(e).__name__}: {e}"}
            log(f"{name} FAILED: {e}\n{traceback.format_exc()}")

    head = configs.get("lenet", {})
    value = head.get("value", 0.0)
    result.update({
        "value": value,
        "vs_baseline": round(value / BASELINE_SAMPLES_SEC, 2),
        "device_kind": kind,
        "n_chips": n_chips,
        "measurement": f"median of {WINDOWS} timed windows",
        "configs": configs,
    })
    # Cumulative monitor-registry digest over the whole bench run
    # (retrace counts by jit entry, per-phase fit time breakdown,
    # serving percentiles, cache hit rates): a perf regression in a
    # future BENCH record can be attributed to a phase, not just seen
    # in the headline number.
    try:
        from deeplearning4j_tpu import monitor
        result["metrics_registry"] = monitor.summarize(
            monitor.get_registry().snapshot())
    except Exception as e:
        result["metrics_registry"] = {"error": f"{type(e).__name__}: {e}"}
    # regression gate LAST: every config's record (incl. errors/skips)
    # is already in place, so the gate sees exactly what gets emitted
    gate_regressions(result, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_history"))


if __name__ == "__main__":
    main()
