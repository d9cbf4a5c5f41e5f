"""The benchmark's own tests.  Run by hand, on the CPU:

    python -m pytest benchmark/tests -q -p no:cacheprovider

They are not part of the repo's tier-1 suite.  Every run of ``run.py``
here is a ``--rehearse`` run at the traffic file's tiny sizes, which is no
measurement; the limits it is held to are the chip's.
"""

import json
import os
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark.harness import compare, flops, stats  # noqa: E402

CELL = "vgg16.fit_b128"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _cfg(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


# --- the yardstick's arithmetic -------------------------------------------
@pytest.mark.parametrize("config,module,gflop,step_tflop", [
    ("vgg16_imagenet", "benchmark.reference.vgg16", 30.94, 11.86),
    ("resnet50_imagenet", "benchmark.reference.resnet50", 7.716, 2.933),
])
def test_flop_walk_gives_the_hand_counts(config, module, gflop, step_tflop):
    import importlib
    cfg = _cfg(config)
    layers = importlib.import_module(module).layers(cfg)
    assert round(flops.forward_flops_per_row(layers) / 1e9, 2 if gflop > 10 else 3) == gflop
    # forward + weight gradient everywhere, input gradient but for layer 1
    assert round(flops.step_flops(layers, 128) / 1e12, 2 if gflop > 10 else 3) == step_tflop
    assert flops.step_flops(layers, 128) < 3 * 128 * flops.forward_flops_per_row(layers)


def test_least_seconds_takes_the_larger_bound():
    peaks = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    layer = flops.conv(1, 1, 1, 1, 2, 2, 0)     # 8 FLOPs, tiny
    total, by_flops, by_bytes = flops.least_seconds([layer], 1, peaks)
    # two passes (first layer: no input gradient), each bound by its bytes
    assert by_flops == 0 and total == by_bytes == 2 * 2 * (4 + 1 + 4) / 10.0


def test_percentile_is_nearest_rank_and_intervals_are_ms():
    v = list(range(1, 101))
    assert stats.percentile(v, 95) == 95 and stats.percentile(v, 50) == 50
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1, 2, 3, 4], 95) == 4
    assert stats.intervals_ms([0.0, 0.25, 1.0]) == [250.0, 750.0]


def test_pool_iterator_stops_at_the_deadline_and_cycles():
    from benchmark.modes import fit
    pool = fit.make_pool(5, 3, 2, 1, 4, 7)
    assert all(d.features.shape == (2, 1, 4, 4) and d.labels.sum() == 2 for d in pool)
    it = fit.pool_iterator(pool, steps=5)
    seen = []
    while it.has_next():
        seen.append(it.next())
    assert [next(i for i, p in enumerate(pool) if p is d) for d in seen] == [0, 1, 2, 0, 1]
    it = fit.pool_iterator(pool, deadline=time.perf_counter() + 0.05)
    assert it.has_next()
    time.sleep(0.06)
    assert not it.has_next()


def test_gaps_measure_norms_by_the_worst_live_leaf():
    ref = {"losses": [2.0], "grad_norms": {"a": 1.0, "b": 2.0, "dead": 1e-9},
           "change_norms": {"a": 1.0, "b": 2.0, "dead": 0.0}}
    prog = {"losses": [2.2], "grad_norms": {"a": 1.1, "b": 2.0, "dead": 3e-9},
            "change_norms": {"a": 1.0, "b": 0.0, "dead": 5.0}}
    g, where = compare.gaps(prog, ref)
    assert g["loss1_gap"] == pytest.approx(0.1)
    assert g["grad_gap"] == pytest.approx(0.1) and where["grad_gap"] == "a"
    # b did not move: reads 1; the dead leaf is left out of the change
    assert g["change_gap"] == pytest.approx(1.0) and where["change_gap"] == "b"


def test_trace_reduce_on_the_recorded_trace():
    from benchmark.harness import trace_reduce
    path = os.path.join(DATA, "small.xplane.pb")
    want = json.load(open(os.path.join(DATA, "small.expected.json")))
    from benchmark.reference import vgg16
    layers = vgg16.layers(_cfg("vgg16_imagenet"))
    red = trace_reduce.reduce(trace_reduce.load(path), layers)
    assert red["device_ops"][0][0] == want["top_op"]
    assert [g[0] for g in red["idle_gaps"]] == want["idle_phases"]
    # without the configuration's kernel shapes nothing counts as convolution
    assert trace_reduce.reduce(trace_reduce.load(path))["conv_s"] == 0
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert red["conv_s"] == pytest.approx(want["conv_s"], rel=1e-9)
    assert 0 < red["conv_s"] <= red["busy_s"] <= red["window_s"]


# --- the command ----------------------------------------------------------
def _run(*extra, cell=CELL):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "2147483999", "--seconds", "1",
         "--trace", "0", *extra],
        capture_output=True, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_without_a_chip_it_exits_non_zero_and_prints_no_result():
    r = _run()
    assert r.returncode != 0
    assert "metrics" not in r.stdout and "no TPU" in r.stderr


@pytest.mark.parametrize("cell", ["vgg16.fit_b128", "resnet50.fit_b128"])
def test_rehearsal_prints_a_well_formed_last_line(cell):
    r = _run("--rehearse", cell=cell)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "compared" and "rehearsal" in line
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert set(line["metrics"]) == {"train_samples_per_s", "step_ms_p95", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    # every number compared stands beside its limit at the end of stderr
    tail = r.stderr.strip().splitlines()[-len(line["compared"]):]
    assert [t.split()[1].rstrip(":") for t in tail] == list(line["compared"])


# --- correct has to be able to come out false -----------------------------
def _broken_step(kind):
    """A ``_build_step`` for the list engine with the timed path broken
    underneath the benchmark."""
    import jax

    def build(self):
        raw = self._build_step_raw()

        def step(params, state, opts, x, y, fmask, lmask, it, rng):
            if kind == "half_batch":
                n = x.shape[0] // 2
                return raw(params, state, opts, x[:n], y[:n], fmask, lmask, it, rng)
            score = raw(params, state, opts, x, y, fmask, lmask, it, rng)[3]
            return params, state, opts, score      # the state comes back unchanged

        return jax.jit(step)
    return build


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch"])
def test_a_broken_timed_path_is_not_correct(kind, monkeypatch, capsys):
    from benchmark import run
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    monkeypatch.setattr(MultiLayerNetwork, "_build_step", _broken_step(kind))
    run.main(["--workload", CELL, "--seed", "77", "--seconds", "1",
              "--trace", "0", "--rehearse"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    failing = {n for n, c in line["compared"].items() if c["value"] > c["limit"]}
    assert failing & {"grad_gap", "change_gap"}, line["compared"]


def test_the_control_in_lower_precision_is_not_correct():
    """The reference put in the program's place, computed in the control's
    precision (float8 where the configuration states bfloat16)."""
    from benchmark.modes import fit
    cfg = _cfg("vgg16_imagenet")
    traffic = json.load(open(os.path.join(ROOT, "benchmark", "traffic", "fit_b128.json")))
    limits = json.load(open(os.path.join(ROOT, "benchmark", "limits", CELL + ".json")))["limits"]
    mode = fit.Mode(cfg, traffic, 5, 1, rehearse=True)
    mode.pool = fit.make_pool(5, 3, mode.batch, 3, mode.cfg["image_size"], 1000)
    ref = mode.reference_readings()
    control = mode.reference_readings(cfg["precision"]["control"])
    rows = compare.verdict(compare.gaps(control, ref)[0],
                           {k: v for k, v in limits.items() if k.endswith("_gap")})
    assert not all(ok for *_, ok in rows), rows
