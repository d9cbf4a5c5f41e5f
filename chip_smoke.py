#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, on one TPU chip, through the entry points a
user calls, in ONE process (a chip belongs to one process at a time):

  device   jax.devices(): the platform must be ``tpu``, read from the device
  kernels  ops/helpers.kernel_self_test: the four fused tiers (lstm step,
           dropout, softmax cross-entropy, flash attention) compiled by Mosaic
  fit      models.vgg.vgg16_cifar10() at full width, batch 256, default
           precision (bf16 on the chip), a few ``net.fit(iterator)`` steps
  serve    a LeNet written with write_model, served by server.Server over
           real HTTP; every answer is compared with ``net.output``

``--chips 4`` runs, instead of the three phases after ``device``, the
sharded VGG16 fit (README "Multi-chip = pick a mesh": ParallelWrapper on
a data=2 x fsdp=2 mesh) and the single-device fit it is compared with.

Weights and data come from ``--seed``; nothing is downloaded.  Any
failed phase makes the script exit non-zero.  The last stdout line is
one JSON object, ``{"ok": true, "device": {...}}``; the other lines are
information, not metrics — one run, no warm-up discipline, not a
benchmark.  With no TPU (or outside the repo) it exits non-zero and
prints no result.  The phase functions take their sizes as arguments so
that tests/test_chip_smoke.py can rehearse them on the CPU at tiny size
(``chip=False`` drops only what a CPU cannot show: the platform, the
compiled kernels and the fused-tier selection counts).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

# |served - net.output| on softmax probabilities.  bf16 carries 8 bits of
# mantissa (2^-8 = 4e-3 relative per op); the served batch is padded to a
# bucket, so XLA may pick another tiling than the reference's, and the
# error compounds over LeNet's four weighted layers.
SERVE_ATOL = 2e-2
# score of the sharded fit against its single-device twin, per step:
# |a - b| <= SHARD_RTOL * max(|a|, |b|) + SHARD_ATOL.  Both twins run XLA's
# own bf16 convolutions, and this model engages no fused tier; they differ
# in how XLA tiles a quarter of the batch and in reduction order across the
# four batch shards.
SHARD_RTOL, SHARD_ATOL = 0.05, 0.05
# vgg16_cifar10's own default (0.01, Nesterov) overshoots on one repeated
# batch from a random start: the score jumps to ~11 and the net dies at
# ln 10.  At 5e-4 it falls steadily (3.46 -> 1.44 in 12 steps, f32, CPU).
FIT_LEARNING_RATE = 5e-4


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, **info) -> None:
    print(f"[{phase}] " + json.dumps(info, default=str), flush=True)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------
def phase_device(n_chips: int, chip: bool = True) -> dict:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}
    say("device", **info, jax=jax.__version__, process_id=os.getpid())
    if chip:
        check(d0.platform == "tpu",
              f"no TPU: jax.devices()[0].platform is '{d0.platform}'")
    check(len(devs) >= n_chips,
          f"needs {n_chips} device(s), JAX reports {len(devs)}")
    return info


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def phase_kernels(chip: bool = True) -> dict:
    """The self-test of every registered tier: lstm_step, dropout,
    softmax_xent, attention."""
    from deeplearning4j_tpu.ops import helpers
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    t0 = time.perf_counter()
    verdicts = helpers.kernel_self_test(disable_on_error=False)
    say("kernels", seconds=round(time.perf_counter() - t0, 1), **verdicts)
    for op in helpers.OPS:
        name = helpers.helper_for(op).test_name
        check(verdicts.get(name) == "ok", f"tier {name}: {verdicts.get(name)}")
    check(not pk._disabled, f"tiers disabled: {pk._disabled}")
    if chip:
        check(verdicts["interpret_mode"] is False,
              "kernels ran in interpret mode, not through Mosaic")
    return verdicts


# ---------------------------------------------------------------------------
# fit
# ---------------------------------------------------------------------------
class _Trajectory:
    """IterationListener: the score after every step, and when it was
    known on the host (float() waits for the device)."""

    def __init__(self):
        self.scores, self.times = [], []

    def iteration_done(self, model, iteration):
        self.scores.append(float(model._score))
        self.times.append(time.perf_counter())


def _cifar_like(seed: int, batch: int):
    """One seeded CIFAR-10-shaped batch a net can learn: each class is a
    fixed random 3x32x32 template, each row its class template + noise."""
    import numpy as np
    from deeplearning4j_tpu.datasets.dataset import DataSet
    rng = np.random.default_rng(seed)
    templates = rng.normal(size=(10, 3, 32, 32)).astype(np.float32)
    labels = rng.integers(0, 10, batch)
    x = templates[labels] + 0.5 * rng.normal(
        size=(batch, 3, 32, 32)).astype(np.float32)
    return DataSet(x, np.eye(10, dtype=np.float32)[labels])


def _selection_counts(since: dict | None = None) -> dict:
    """{"selected": {op: n}, "fallback": {op: n}}: the trace-time helper
    decisions so far, or those made after the snapshot ``since``."""
    from deeplearning4j_tpu import monitor
    snap = monitor.get_registry().snapshot()
    out = {}
    for kind in ("selected", "fallback"):
        samples = snap.get(f"dl4j_pallas_{kind}_total", {}).get("samples", [])
        now = {s["labels"]["op"]: int(s["value"]) for s in samples}
        was = (since or {}).get(kind, {})
        out[kind] = {op: n - was.get(op, 0) for op, n in now.items()
                     if since is None or n > was.get(op, 0)}
    return out


def _fit_vgg16(seed: int, batch: int, steps: int, mesh=None) -> dict:
    """``steps`` fit() steps of vgg16_cifar10 on one repeated batch, on
    one device or through ParallelWrapper on ``mesh``."""
    import numpy as np
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.models.vgg import vgg16_cifar10
    net = vgg16_cifar10(learning_rate=FIT_LEARNING_RATE, seed=seed)
    net.init()
    params_before = np.asarray(net.params())
    traj = _Trajectory()
    net.set_listeners(traj)
    it = ListDataSetIterator([_cifar_like(seed, batch)] * steps)
    counts_before = _selection_counts()
    t0 = time.perf_counter()
    if mesh is None:
        net.fit(it)
    else:
        from deeplearning4j_tpu.parallel import ParallelWrapper
        ParallelWrapper(net, mesh).fit(it)
    check(len(traj.scores) == steps,
          f"{len(traj.scores)} steps ran, {steps} asked")
    stamps = [t0] + traj.times
    secs = [b - a for a, b in zip(stamps, stamps[1:])]
    counts = _selection_counts(since=counts_before)
    return {"net": net, "params_before": params_before,
            "scores": traj.scores,
            "pallas_selected": counts["selected"],
            "pallas_fallback": counts["fallback"],
            "first_step_s_with_compile": round(secs[0], 2),
            "steady_step_ms_median": round(
                float(np.median(secs[1:])) * 1e3, 2)}


def _check_learning(scores, where: str) -> None:
    import math
    check(all(math.isfinite(s) for s in scores),
          f"{where}: non-finite score in {scores}")
    check(scores[-1] < scores[0],
          f"{where}: score did not fall: {scores[0]} -> {scores[-1]}")


def phase_fit(seed: int = 0, batch: int = 256, steps: int = 12,
              chip: bool = True) -> dict:
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.ops import dtypes
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    run = _fit_vgg16(seed, batch, steps)
    net, before, scores = run["net"], run["params_before"], run["scores"]
    after = np.asarray(net.params())
    policy = dtypes.resolve(net.conf.global_conf.precision)
    info = {"model": "vgg16_cifar10", "params": int(after.size),
            "batch": batch, "steps": steps,
            "precision": jnp.dtype(policy.compute_dtype).name,
            "first_step_s_with_compile": run["first_step_s_with_compile"],
            "steady_step_ms_median": run["steady_step_ms_median"],
            "scores": [round(s, 4) for s in scores],
            "pallas_selected": run["pallas_selected"],
            "pallas_fallback": run["pallas_fallback"],
            "tiers_disabled": dict(pk._disabled)}
    say("fit", **info)
    _check_learning(scores, "fit")
    check(np.all(np.isfinite(after)), "fit: non-finite parameter")
    check(before.shape == after.shape and np.any(before != after),
          "fit: parameters did not change")
    check(not pk._disabled, f"fit: tiers disabled: {pk._disabled}")
    if chip:
        check(info["precision"] == "bfloat16",
              f"fit: default precision on the chip is {info['precision']}")
    return info


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def _rpc(url: str, method: str, **params):
    req = urllib.request.Request(
        url, data=json.dumps({"method": method, "params": params}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as r:
        body = json.loads(r.read())
    check("result" in body, f"{method}: {body}")
    return body["result"]


def phase_serve(seed: int = 0, ks=(1, 3, 8, 2, 16, 5, 32, 7)) -> dict:
    import numpy as np
    from deeplearning4j_tpu.models.lenet import lenet
    from deeplearning4j_tpu.nn.serialization import write_model
    from deeplearning4j_tpu.server import Server
    rng = np.random.default_rng(seed)
    net = lenet(seed=seed).init()
    threads_before = threading.active_count()
    worst = 0.0
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "lenet.zip")
        write_model(net, path)
        srv = Server().start()
        try:
            url = f"http://{srv.host}:{srv.port}/"
            t0 = time.perf_counter()
            warm = _rpc(url, "warmup", model_path=path,
                        feature_dims=[1, 28, 28])  # the gateway's ladder
            warm_s = time.perf_counter() - t0
            lat_ms = []
            for k in ks:
                rows = rng.normal(size=(k, 1, 28, 28)).astype(np.float32)
                t0 = time.perf_counter()
                res = _rpc(url, "predict", model_path=path,
                           features=rows.tolist())
                lat_ms.append((time.perf_counter() - t0) * 1e3)
                got = np.asarray(res["predictions"], np.float32)
                want = np.asarray(net.output(rows), np.float32)
                check(got.shape == want.shape == (k, 10),
                      f"serve: k={k} answered shape {got.shape}")
                check(np.all(np.isfinite(got)), f"serve: k={k} non-finite")
                err = float(np.max(np.abs(got - want)))
                worst = max(worst, err)
                check(err <= SERVE_ATOL,
                      f"serve: k={k} differs from net.output by {err}")
                # argmax must agree wherever the reference's top two are
                # further apart than the tolerance could move them
                top2 = np.sort(want, axis=-1)[:, -2:]
                decided = (top2[:, 1] - top2[:, 0]) > 2 * SERVE_ATOL
                check(np.array_equal(got.argmax(-1)[decided],
                                     want.argmax(-1)[decided]),
                      f"serve: k={k} argmax differs from net.output")
            stats = _rpc(url, "stats")
            serving = next(iter(stats["serving"].values()))
            programs = serving["compile_telemetry"]["by_kind"]["output"]
        finally:
            srv.stop()
    srv._thread.join(timeout=10)
    info = {"model": "lenet", "requests": len(ks), "ks": list(ks),
            "warmed_ladder": warm["buckets"], "warmup_s": round(warm_s, 2),
            "output_programs": programs,
            "max_abs_diff_vs_output": worst, "atol": SERVE_ATOL,
            "request_ms_median": round(float(np.median(lat_ms)), 2),
            "served_requests": serving["requests"]}
    say("serve", **info)
    check(programs <= len(warm["buckets"]),
          f"serve: {programs} output programs > warmed ladder "
          f"{warm['buckets']}")
    check(serving["requests"] == len(ks),
          f"serve: batcher saw {serving['requests']} requests")
    check(not srv._thread.is_alive(), "serve: HTTP thread still alive")
    deadline = time.time() + 10
    while threading.active_count() > threads_before and time.time() < deadline:
        time.sleep(0.05)
    check(threading.active_count() <= threads_before,
          f"serve: threads left running: "
          f"{[t.name for t in threading.enumerate()]}")
    return info


# ---------------------------------------------------------------------------
# --chips 4: the sharded fit and its single-device twin
# ---------------------------------------------------------------------------
def _residency(tree, fsdp: int) -> dict:
    """Where a pytree lives: bytes on each device, and whether every
    fsdp-sharded leaf keeps 1/fsdp of itself per device."""
    import jax
    per_device: dict = {}
    total = sharded_leaves = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += leaf.nbytes
        is_sharded = "fsdp" in jax.tree_util.tree_leaves(
            tuple(leaf.sharding.spec))
        sharded_leaves += is_sharded
        for sh in leaf.addressable_shards:
            per_device[sh.device.id] = (per_device.get(sh.device.id, 0)
                                        + sh.data.nbytes)
            if is_sharded:
                check(sh.data.nbytes * fsdp == leaf.nbytes,
                      f"leaf {leaf.shape} ({leaf.sharding.spec}): shard "
                      f"holds {sh.data.nbytes} of {leaf.nbytes} bytes")
    return {"total_bytes": total, "sharded_leaves": sharded_leaves,
            "bytes_per_device": per_device}


def phase_sharded(seed: int = 0, batch: int = 256, steps: int = 8,
                  devices=None, chip: bool = True) -> dict:
    import jax
    import numpy as np
    from deeplearning4j_tpu.parallel import MeshConfig, make_mesh
    devices = list(devices if devices is not None else jax.devices()[:4])
    check(len(devices) == 4, f"sharded: {len(devices)} devices, 4 needed")
    single = _fit_vgg16(seed, batch, steps)
    mesh = make_mesh(MeshConfig(data=2, fsdp=2), devices=devices)
    shard = _fit_vgg16(seed, batch, steps, mesh=mesh)
    params = _residency(shard["net"].net_params, 2)
    updater = _residency(shard["net"].opt_states, 2)
    # the trained model answers from where its params live: score() and
    # output() are partitioned programs over the same four devices
    ds = _cifar_like(seed, batch)
    score_after = float(shard["net"].score(ds))
    out = np.asarray(shard["net"].output(ds.features[:8]))
    pairs = list(zip(single["scores"], shard["scores"]))
    shown = ("first_step_s_with_compile", "steady_step_ms_median",
             "pallas_selected", "pallas_fallback")
    info = {"model": "vgg16_cifar10", "batch": batch, "steps": steps,
            "mesh": {k: v for k, v in mesh.shape.items() if v > 1},
            "scores_single": [round(s, 4) for s in single["scores"]],
            "scores_sharded": [round(s, 4) for s in shard["scores"]],
            "max_abs_score_diff": max(abs(a - b) for a, b in pairs),
            "sharded_score_after_fit": score_after,
            "tolerance": {"rtol": SHARD_RTOL, "atol": SHARD_ATOL},
            "single": {k: single[k] for k in shown},
            "sharded": {k: shard[k] for k in shown},
            "params": params, "updater": updater}
    say("sharded", **info)
    _check_learning(single["scores"], "single-device fit")
    _check_learning(shard["scores"] + [score_after], "sharded fit")
    check(out.shape == (8, 10) and np.all(np.isfinite(out)),
          f"sharded: output() after the fit gave {out.shape}, "
          f"finite={np.all(np.isfinite(out))}")
    for i, (a, b) in enumerate(pairs):
        check(abs(a - b) <= SHARD_RTOL * max(abs(a), abs(b)) + SHARD_ATOL,
              f"sharded: step {i} score {b} vs single-device {a}")
    want_ids = {d.id for d in devices}
    for name, res in (("params", params), ("updater", updater)):
        per = res["bytes_per_device"]
        check(set(per) == want_ids,
              f"sharded: {name} live on devices {sorted(per)}, "
              f"not on {sorted(want_ids)}")
        check(res["sharded_leaves"] > 0, f"sharded: no {name} leaf sharded")
        # all but the small replicated leaves (biases) is halved by fsdp=2
        check(max(per.values()) <= 0.51 * res["total_bytes"],
              f"sharded: a device holds {max(per.values())} of "
              f"{res['total_bytes']} {name} bytes")
        check(max(per.values()) - min(per.values())
              <= 0.01 * res["total_bytes"],
              f"sharded: {name} bytes uneven across devices: {per}")
    if chip:
        check(not shard["pallas_selected"],
              f"sharded: a Mosaic tier was selected under the mesh: "
              f"{shard['pallas_selected']}")
    return info


# ---------------------------------------------------------------------------
# the compile cache, as information
# ---------------------------------------------------------------------------
class _CacheWatch:
    def __init__(self):
        import jax
        self.dir = jax.config.jax_compilation_cache_dir
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)
        self.entries_at_start = self.entries()

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def entries(self) -> int:
        try:
            return sum(n.endswith("-cache") for n in os.listdir(self.dir))
        except OSError:
            return 0

    def report(self, when: str) -> None:
        say("compile_cache", when=when, dir=self.dir,
            set_by=("JAX_COMPILATION_CACHE_DIR"
                    if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                    else "deeplearning4j_tpu (fixed path in the checkout)"),
            entries=self.entries(), entries_at_start=self.entries_at_start,
            hits=self.hits, misses=self.misses)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded fit and its "
                         "single-device twin (default 1: the whole smoke)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        import deeplearning4j_tpu  # noqa: F401  (places the compile cache)
        from deeplearning4j_tpu import native
    except ImportError as e:
        print(f"chip_smoke: the deeplearning4j_tpu package is not "
              f"importable from here: {e}", file=sys.stderr)
        return 2
    try:
        device = phase_device(args.chips)
        cache = _CacheWatch()
        cache.report("start")
        say("native", io_library_built=native.available())
        if args.chips == 4:
            phase_sharded(seed=args.seed)
        else:
            phase_kernels()
            phase_fit(seed=args.seed)
            phase_serve(seed=args.seed)
        cache.report("end")
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
