"""Production FSDP (parallel/fsdp.py): conf.sharding() in the default
fit path — ZeRO-style sharded weight update with mesh-reshape-tolerant
checkpoints.

Runs on the 8-virtual-CPU-device mesh conftest.py forces (the same
environment the MULTICHIP dry-runs use); the cross-mesh checkpoint and
graceful-degrade cases spawn 1-device subprocesses."""

import json
import os
import subprocess
import sys
import tempfile

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.nn.checkpoint import (
    CheckpointListener, read_manifest, resume_from_checkpoint)
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.conf.network import (
    GlobalConf, MultiLayerConfiguration, NeuralNetConfiguration)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel import fsdp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PARITY = dict(rtol=1e-6, atol=1e-6)


def _conf_builder(shard, updater="adam", seed=7, **shard_kw):
    b = (NeuralNetConfiguration.builder().seed(seed).learning_rate(0.05)
         .updater(updater))
    if shard:
        kw = dict(data=2, fsdp=4, replicate_below=8)
        kw.update(shard_kw)
        b.sharding(**kw)
    return b


def _net(shard, updater="adam", seed=7, **shard_kw):
    conf = (_conf_builder(shard, updater, seed, **shard_kw).list()
            .layer(DenseLayer(n_in=16, n_out=32, activation="relu"))
            .layer(OutputLayer(n_in=32, n_out=4, activation="softmax",
                               loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _batches(n=5, rows=24, seed=0):
    rng = np.random.default_rng(seed)
    return [DataSet(rng.normal(size=(rows, 16)).astype(np.float32),
                    np.eye(4, dtype=np.float32)[rng.integers(0, 4, rows)])
            for _ in range(n)]


# ---------------------------------------------------------------------------
# conf serde + graceful degrade (CI/tooling satellite)
# ---------------------------------------------------------------------------

def test_sharding_conf_serde_roundtrip():
    conf = (_conf_builder(True, data=2, fsdp=4, model=1,
                          replicate_below=123).list()
            .layer(DenseLayer(n_in=4, n_out=8))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .build())
    back = MultiLayerConfiguration.from_json(conf.to_json()).global_conf
    assert back.sharding_enabled is True
    assert back.sharding_data == 2
    assert back.sharding_fsdp == 4
    assert back.sharding_replicate_below == 123


def test_pre_sharding_conf_dict_still_loads():
    """A config dict from before the sharding fields existed (PR-5-era
    checkpoints) must deserialize with sharding off."""
    conf = (NeuralNetConfiguration.builder().list()
            .layer(DenseLayer(n_in=4, n_out=8))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .build())
    d = conf.to_dict()
    for k in list(d["global"]):
        if k.startswith("sharding_"):
            del d["global"][k]
    back = MultiLayerConfiguration.from_dict(d)
    assert back.global_conf.sharding_enabled is False
    assert fsdp.plan_from_conf(back.global_conf) is None


def test_plan_inactive_without_conf_sharding():
    net = _net(False)
    net.fit(ListDataSetIterator(_batches(1)))
    assert getattr(net, "_sharding_plan", None) is None


def test_unsatisfiable_mesh_degrades_with_warning():
    g = GlobalConf(sharding_enabled=True, sharding_data=3, sharding_fsdp=5)
    with pytest.warns(UserWarning, match="replica-style"):
        assert fsdp.plan_from_conf(g) is None


def test_single_device_degrades_to_replica_subprocess():
    """conf.sharding(fsdp=8) on a 1-device host must be inert: plan
    None, fit() trains, params finite — the tier-1 graceful-degrade
    smoke."""
    code = """
import numpy as np
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
import jax
assert len(jax.devices()) == 1, jax.devices()
conf = (NeuralNetConfiguration.builder().seed(7).learning_rate(0.05)
        .updater("adam").sharding(data=2, fsdp=4)
        .list()
        .layer(DenseLayer(n_in=16, n_out=32, activation="relu"))
        .layer(OutputLayer(n_in=32, n_out=4, activation="softmax",
                           loss="mcxent"))
        .build())
net = MultiLayerNetwork(conf).init()
rng = np.random.default_rng(0)
x = rng.normal(size=(24, 16)).astype(np.float32)
y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 24)]
net.fit(x, y, epochs=2)
assert getattr(net, "_sharding_plan", None) is None
p = np.asarray(net.params())
assert np.isfinite(p).all()
print("DEGRADE_OK")
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "DEGRADE_OK" in p.stdout


# ---------------------------------------------------------------------------
# numerics parity (satellite 1 / acceptance: 1e-6 vs the replica path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("updater", ["sgd", "adam"])
def test_sharded_fit_matches_replica_params(updater):
    batches = _batches(5)
    a = _net(False, updater)
    b = _net(True, updater)
    a.fit(ListDataSetIterator(list(batches)), epochs=3)
    b.fit(ListDataSetIterator(list(batches)), epochs=3)
    assert b._sharding_plan is not None
    np.testing.assert_allclose(np.asarray(a.params()),
                               np.asarray(b.params()), **PARITY)
    assert abs(a.score() - b.score()) < 1e-6


def test_sharded_fit_pads_ragged_batch_exactly():
    """22 % 8 != 0: the pad-and-mask remainder policy must keep the
    sharded step equal to the unsharded one on every real example."""
    batches = _batches(3, rows=22)
    a = _net(False)
    b = _net(True)
    a.fit(ListDataSetIterator(list(batches)), epochs=2)
    b.fit(ListDataSetIterator(list(batches)), epochs=2)
    np.testing.assert_allclose(np.asarray(a.params()),
                               np.asarray(b.params()), **PARITY)
    assert b.last_batch_size == 22  # real examples, not padded count


def test_sharded_fit_under_bucketing_parity():
    """Sharding composed with PR-1 shape bucketing: a ragged stream
    trains bucket-shaped AND data-degree-divisible, still at parity
    with the plain replica fit."""
    rng = np.random.default_rng(3)
    sizes = [24, 17, 9, 24, 13]
    batches = [DataSet(rng.normal(size=(s, 16)).astype(np.float32),
                       np.eye(4, dtype=np.float32)[rng.integers(0, 4, s)])
               for s in sizes]
    a = _net(False)
    a.fit(ListDataSetIterator(list(batches)), epochs=2)

    conf = (_conf_builder(True).shape_bucketing(True).list()
            .layer(DenseLayer(n_in=16, n_out=32, activation="relu"))
            .layer(OutputLayer(n_in=32, n_out=4, activation="softmax",
                               loss="mcxent"))
            .build())
    b = MultiLayerNetwork(conf).init()
    b.fit(ListDataSetIterator(list(batches)), epochs=2)
    assert b._sharding_plan is not None
    np.testing.assert_allclose(np.asarray(a.params()),
                               np.asarray(b.params()), **PARITY)
    # bucketing did its job too: launches land on sharded_step buckets
    snap = b.compile_telemetry.snapshot()
    assert snap["bucket_hits"]


def test_sharded_fused_steps_matches_replica():
    batches = _batches(7)
    a = _net(False)
    b = _net(True)
    a.fit(ListDataSetIterator(list(batches)), fused_steps=3)
    b.fit(ListDataSetIterator(list(batches)), fused_steps=3)
    assert a.iteration == b.iteration == 7
    np.testing.assert_allclose(np.asarray(a.params()),
                               np.asarray(b.params()), **PARITY)


def test_sharded_computation_graph_parity():
    from deeplearning4j_tpu.nn.conf.graph_conf import GraphBuilder
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    def build(shard):
        g = GlobalConf(seed=5, learning_rate=0.05, updater="adam")
        if shard:
            g.sharding_enabled = True
            g.sharding_data = 2
            g.sharding_fsdp = 4
            g.sharding_replicate_below = 8
        conf = (GraphBuilder(g)
                .add_inputs("in")
                .add_layer("h", DenseLayer(n_in=16, n_out=32,
                                           activation="relu"), "in")
                .add_layer("out", OutputLayer(n_in=32, n_out=4,
                                              activation="softmax",
                                              loss="mcxent"), "h")
                .set_outputs("out")
                .build())
        return ComputationGraph(conf).init()

    batches = _batches(4)
    a = build(False)
    b = build(True)
    a.fit(ListDataSetIterator(list(batches)), epochs=2)
    b.fit(ListDataSetIterator(list(batches)), epochs=2)
    assert b._sharding_plan is not None
    np.testing.assert_allclose(np.asarray(a.params()),
                               np.asarray(b.params()), **PARITY)


def test_sharded_crash_resume_parity(tmp_path):
    """Sharding composed with PR-5 crash-resume: an interrupted sharded
    run restored from its checkpoint converges identically to an
    uninterrupted sharded run AND to the uninterrupted replica run."""
    batches = _batches(4)
    straight = _net(True)
    straight.fit(ListDataSetIterator(list(batches)), epochs=4)

    crashed = _net(True)
    crashed.add_listener(CheckpointListener(tmp_path, save_every_epoch=True))
    crashed.fit(ListDataSetIterator(list(batches)), epochs=2)  # "crash"

    conf = (_conf_builder(True)
            .fault_tolerance(resume=True, checkpoint_dir=str(tmp_path))
            .list()
            .layer(DenseLayer(n_in=16, n_out=32, activation="relu"))
            .layer(OutputLayer(n_in=32, n_out=4, activation="softmax",
                               loss="mcxent"))
            .build())
    resumed = MultiLayerNetwork(conf).init()
    resumed.fit(ListDataSetIterator(list(batches)), epochs=4)
    np.testing.assert_allclose(np.asarray(straight.params()),
                               np.asarray(resumed.params()), **PARITY)
    replica = _net(False)
    replica.fit(ListDataSetIterator(list(batches)), epochs=4)
    np.testing.assert_allclose(np.asarray(replica.params()),
                               np.asarray(resumed.params()), **PARITY)


# ---------------------------------------------------------------------------
# observability (dl4j_sharding_* gauges)
# ---------------------------------------------------------------------------

def _gauge(name):
    fam = monitor.get_registry().get(name)
    assert fam is not None, f"{name} not registered"
    return fam.samples()


def test_updater_bytes_shrink_by_fsdp_degree():
    """The ZeRO claim, asserted from the gauges: per-device updater
    bytes ~ total/fsdp (small replicated biases allowed for)."""
    conf = (NeuralNetConfiguration.builder().seed(1).learning_rate(0.05)
            .updater("adam").sharding(data=1, fsdp=8, replicate_below=64)
            .list()
            .layer(DenseLayer(n_in=256, n_out=256, activation="relu"))
            .layer(DenseLayer(n_in=256, n_out=256, activation="relu"))
            .layer(OutputLayer(n_in=256, n_out=8, activation="softmax",
                               loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(16, 256)).astype(np.float32)
    y = np.eye(8, dtype=np.float32)[rng.integers(0, 8, 16)]
    net.fit(x, y)
    total = _gauge("dl4j_sharding_updater_bytes_total")[0]["value"]
    per_dev = _gauge("dl4j_sharding_updater_bytes_per_device")[0]["value"]
    assert total > 0
    assert per_dev <= total / 8 * 1.3, (per_dev, total)
    p_total = _gauge("dl4j_sharding_param_bytes_total")[0]["value"]
    p_dev = _gauge("dl4j_sharding_param_bytes_per_device")[0]["value"]
    assert p_dev <= p_total / 8 * 1.3
    axes = {s["labels"]["axis"]: s["value"]
            for s in _gauge("dl4j_sharding_mesh_devices")}
    assert axes["fsdp"] == 8 and axes["data"] == 1


def test_collective_bytes_gauges_are_the_sharded_remainder():
    """What a step moves between chips, from the gauges: every parameter
    byte a device does not hold is gathered for the forward and its
    gradient scattered after the backward.  Over fsdp=4 both weights and
    the 32-wide bias shard; the 4-wide bias (under ``replicate_below``)
    stays whole."""
    net = _net(True)
    rng = np.random.default_rng(0)
    net.fit(rng.normal(size=(16, 16)).astype(np.float32),
            np.eye(4, dtype=np.float32)[rng.integers(0, 4, 16)])

    def gauge(name):
        return _gauge(f"dl4j_sharding_{name}")[0]["value"]

    assert (gauge("params_sharded"), gauge("params_replicated")) == (3, 1)
    sharded = (16 * 32 + 32 + 32 * 4) * 4        # bytes, float32
    assert gauge("param_bytes_total") == sharded + 4 * 4
    assert gauge("param_bytes_per_device") == sharded / 4 + 4 * 4
    assert gauge("allgather_bytes_per_step") == sharded * 3 / 4
    assert gauge("reducescatter_bytes_per_step") == sharded * 3 / 4


# ---------------------------------------------------------------------------
# mesh-reshape-tolerant checkpoints
# ---------------------------------------------------------------------------

def test_manifest_records_mesh_and_legacy_entries_still_work(tmp_path):
    net = _net(True)
    net.add_listener(CheckpointListener(tmp_path, save_every_epoch=True))
    net.fit(ListDataSetIterator(_batches(2)), epochs=1)
    entries = read_manifest(tmp_path)
    assert entries, "manifest missing"
    sh = entries[-1]["sharding"]
    assert sh is not None
    assert sh["mesh"]["fsdp"] == 4 and sh["mesh"]["data"] == 2
    assert any("fsdp" in str(spec) for spec in sh["params"].values())

    # a PR-5-era manifest entry (no sharding key) must restore fine
    for e in entries:
        e.pop("sharding", None)
    (tmp_path / "checkpoint_manifest.json").write_text(
        json.dumps({"version": 1, "checkpoints": entries}))
    restored = resume_from_checkpoint(tmp_path)
    assert restored is not None
    np.testing.assert_allclose(np.asarray(restored.params()),
                               np.asarray(net.params()), rtol=1e-6,
                               atol=1e-6)


def test_checkpoint_replica_written_resumes_on_sharded_mesh(tmp_path):
    """1-device-style (replica) checkpoint → 8-device sharded model:
    restore must redistribute params onto the mesh and keep training."""
    batches = _batches(3)
    writer = _net(False)
    writer.add_listener(CheckpointListener(tmp_path, save_every_epoch=True))
    writer.fit(ListDataSetIterator(list(batches)), epochs=2)

    conf = (_conf_builder(True)
            .fault_tolerance(resume=True, checkpoint_dir=str(tmp_path))
            .list()
            .layer(DenseLayer(n_in=16, n_out=32, activation="relu"))
            .layer(OutputLayer(n_in=32, n_out=4, activation="softmax",
                               loss="mcxent"))
            .build())
    resumed = MultiLayerNetwork(conf).init()
    resumed.fit(ListDataSetIterator(list(batches)), epochs=3)
    assert resumed._sharding_plan is not None
    # params landed sharded over fsdp
    spec = resumed.net_params[0]["W"].sharding.spec
    assert "fsdp" in str(spec)
    # parity with an uninterrupted replica run of the same schedule
    straight = _net(False)
    straight.fit(ListDataSetIterator(list(batches)), epochs=3)
    np.testing.assert_allclose(np.asarray(straight.params()),
                               np.asarray(resumed.params()), **PARITY)


def test_checkpoint_sharded_written_resumes_on_one_device(tmp_path):
    """8-device sharded checkpoint → 1-device process: the flat host
    vector reshards down and training continues — the acceptance
    criterion's 8→1 leg (1→8 is the test above)."""
    net = _net(True)
    listener = CheckpointListener(tmp_path, save_every_epoch=True)
    net.add_listener(listener)
    net.fit(ListDataSetIterator(_batches(3)), epochs=2)
    expect = np.asarray(net.params())
    np.save(tmp_path / "expected.npy", expect)

    code = f"""
import numpy as np
import jax
assert len(jax.devices()) == 1
from deeplearning4j_tpu.nn.checkpoint import resume_from_checkpoint
net = resume_from_checkpoint({str(tmp_path)!r})
assert net is not None
expect = np.load({str(tmp_path / 'expected.npy')!r})
np.testing.assert_allclose(np.asarray(net.params()), expect,
                           rtol=1e-6, atol=1e-6)
rng = np.random.default_rng(0)
x = rng.normal(size=(24, 16)).astype(np.float32)
y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 24)]
net.fit(x, y)   # sharding conf degrades on 1 device; fit still works
assert getattr(net, "_sharding_plan", None) is None
assert np.isfinite(np.asarray(net.params())).all()
print("RESHAPE_OK")
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "RESHAPE_OK" in p.stdout
