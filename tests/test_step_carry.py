"""The train step carries its own key and iteration.

Each launch splits the carried key inside the step (``key, rng =
split(key)``) and returns the rest of it with the iteration advanced, so a
steady step sends nothing to the device before it starts, and the tail's
fetches (the score, what the publishers read) leave for the host at launch.
The random stream has to stay the one the host's own split gave: these
tests run the host-side loop beside ``fit()`` (``_parent``: the host splits
``net._key`` and sends the iteration before every launch, the step body is
the engine's own) and compare keys, scores and parameters bit for bit."""

import contextlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.monitor import tracing
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.graph_conf import GraphBuilder
from deeplearning4j_tpu.nn.conf.network import (
    GlobalConf, NeuralNetConfiguration)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.listeners import IterationListener
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

FEATS, HIDDEN, CLASSES, ROWS, SEED = 6, 16, 3, 8, 11


def _mln(iterations=1):
    conf = (NeuralNetConfiguration.builder().seed(SEED).learning_rate(0.05)
            .updater("adam").iterations(iterations).list()
            .layer(L.DenseLayer(n_in=FEATS, n_out=HIDDEN, activation="relu",
                                dropout=0.5))
            .layer(L.OutputLayer(n_in=HIDDEN, n_out=CLASSES,
                                 activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _cg(iterations=1):
    conf = (GraphBuilder(GlobalConf(seed=SEED, learning_rate=0.05,
                                    updater="adam", iterations=iterations))
            .add_inputs("in")
            .add_layer("d", L.DenseLayer(n_in=FEATS, n_out=HIDDEN,
                                         activation="relu", dropout=0.5),
                       "in")
            .add_layer("out", L.OutputLayer(n_in=HIDDEN, n_out=CLASSES,
                                            activation="softmax",
                                            loss="mcxent"), "d")
            .set_outputs("out").build())
    return ComputationGraph(conf).init()


ENGINES = {"mln": _mln, "cg": _cg}


def _batches(n, seed=0):
    rng = np.random.default_rng(seed)
    return [DataSet(rng.normal(size=(ROWS, FEATS)).astype(np.float32),
                    np.eye(CLASSES, dtype=np.float32)[
                        rng.integers(0, CLASSES, ROWS)])
            for _ in range(n)]


def _resyncs():
    fam = monitor.get_registry().get("dl4j_fit_carry_resync_total")
    samples = fam.describe()["samples"] if fam is not None else []
    return {s["labels"]["what"]: s["value"] for s in samples}


def _delta(after, before):
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v - before.get(k, 0.0)}


class _Record(IterationListener):
    """What each launch leaves: the host's iteration, its key and the
    score ``iteration_done`` sees."""

    def __init__(self):
        self.seen = []

    def iteration_done(self, model, iteration):
        self.seen.append((iteration, np.asarray(model._key),
                          float(model.score())))


def _parent(net, launches):
    """The host-side loop: before every launch the host splits the key
    and sends the iteration.  ``launches`` is a list of
    ``(batches, iteration)``, one entry a launch: one batch for a single
    step, k for a fused group (the scan's i-th step under
    ``fold_in(sub, i)``).  Returns what ``_Record`` would see, and the
    parameters after the last launch."""
    graph = isinstance(net, ComputationGraph)
    body = net._build_step_body()

    def wrap(a):
        return (a,) if graph else a

    def single(p, s, o, x, y, it, rng):
        return body(p, s, o, wrap(x), wrap(y), None, None, it, rng)

    def fused(p, s, o, xs, ys, it, key):
        def step(c, inp):
            i, x, y = inp
            p, s, o, score = single(*c, x, y, it + i,
                                    jax.random.fold_in(key, i))
            return (p, s, o), score
        (p, s, o), scores = jax.lax.scan(step, (p, s, o),
                                         (jnp.arange(xs.shape[0]), xs, ys))
        return p, s, o, scores[-1]

    single, fused = jax.jit(single), jax.jit(fused)
    key = jax.random.PRNGKey(SEED)
    p, s, o = net.net_params, net.net_state, net.opt_states
    seen = []
    for group, it in launches:
        key, sub = jax.random.split(key)
        it_arr = jnp.asarray(it, jnp.int32)
        if len(group) == 1:
            p, s, o, score = single(p, s, o, jnp.asarray(group[0].features),
                                    jnp.asarray(group[0].labels), it_arr, sub)
        else:
            p, s, o, score = fused(
                p, s, o, jnp.stack([d.features for d in group]),
                jnp.stack([d.labels for d in group]), it_arr, sub)
        seen.append((it + len(group), np.asarray(key), float(score)))
    return seen, p


def _same(got, want):
    assert [g[0] for g in got] == [w[0] for w in want]
    for (_, gk, gs), (_, wk, ws) in zip(got, want):
        np.testing.assert_array_equal(gk, wk)
        assert gs == ws
    # and the sub keys the launches split off the same keys are the same
    for (_, gk, _), (_, wk, _) in zip(got, want):
        np.testing.assert_array_equal(np.asarray(jax.random.split(gk)[1]),
                                      np.asarray(jax.random.split(wk)[1]))


def _same_params(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --- the random stream and the iteration, bit for bit ------------------------
@pytest.mark.parametrize("engine", sorted(ENGINES))
@pytest.mark.parametrize("mode", ["single", "fused3", "iterations2"])
def test_the_stream_is_the_host_splits(engine, mode):
    batches = _batches(7)
    if mode == "single":
        net, launches = ENGINES[engine](), [([d], i)
                                            for i, d in enumerate(batches)]
        fit = dict()
    elif mode == "fused3":
        # the first launch runs one batch alone, then groups of k, and
        # the ragged tail runs step by step
        net, fit = ENGINES[engine](), dict(fused_steps=3)
        launches = [(batches[0:1], 0), (batches[1:3], 1),
                    (batches[3:6], 3), (batches[6:7], 6)]
    else:
        # the list engine launches twice a batch; the graph engine steps
        # once a batch whatever conf.iterations says
        net, fit = ENGINES[engine](iterations=2), dict()
        reps = 2 if engine == "mln" else 1
        launches = [([d], reps * i + r) for i, d in enumerate(batches)
                    for r in range(reps)]
    want, want_params = _parent(ENGINES[engine](
        iterations=2 if mode == "iterations2" else 1), launches)
    rec = _Record()
    net.set_listeners(rec)
    before = _resyncs()
    net.fit(ListDataSetIterator(batches), **fit)
    _same(rec.seen, want)
    _same_params(net.net_params, want_params)
    # the first launch sends both, and no other launch sends anything
    assert _delta(_resyncs(), before) == {"key": 1, "iteration": 1}


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_an_iteration_set_by_hand_is_sent_and_used(engine):
    """Adam's bias correction reads the host's iteration: set by hand
    between two fits, the next launch sends it, once."""
    batches = _batches(4)
    net, rec = ENGINES[engine](), _Record()
    net.set_listeners(rec)
    before = _resyncs()
    net.fit(ListDataSetIterator(batches[:2]))
    net.iteration = 50
    net.fit(ListDataSetIterator(batches[2:]))
    want, want_params = _parent(ENGINES[engine](), [
        ([batches[0]], 0), ([batches[1]], 1), ([batches[2]], 50),
        ([batches[3]], 51)])
    _same(rec.seen, want)
    _same_params(net.net_params, want_params)
    assert _delta(_resyncs(), before) == {"key": 1, "iteration": 2}
    # a path that draws from the host's key as feed_forward(train=True)
    # does: the next launch sends the key it left
    before = _resyncs()
    net._key, _ = jax.random.split(net._key)
    net.fit(ListDataSetIterator(batches[:1]))
    assert _delta(_resyncs(), before) == {"key": 1}


def test_a_checkpoint_restored_mid_fit_is_sent_and_used(tmp_path):
    """A listener restores the checkpoint of iteration 2 at iteration 3:
    the next launch sends the restored iteration and key, and the run
    goes on as one that had never trained the discarded batch."""
    from deeplearning4j_tpu.nn.checkpoint import (
        CheckpointListener, restore_into)
    batches = _batches(5)
    net, rec = _mln(), _Record()

    class Restore(IterationListener):
        done = False

        def iteration_done(self, model, iteration):
            if iteration == 3 and not self.done:
                self.done = True
                assert restore_into(model, tmp_path)["iteration"] == 2

    net.set_listeners(CheckpointListener(tmp_path, save_every_n_iterations=2),
                      rec, Restore())
    before = _resyncs()
    net.fit(ListDataSetIterator(batches))
    want, want_params = _parent(_mln(), [
        ([batches[0]], 0), ([batches[1]], 1), ([batches[3]], 2),
        ([batches[4]], 3)])
    got = rec.seen[:2] + rec.seen[3:]     # the third launch was undone
    _same(got, want)
    _same_params(net.net_params, want_params)
    assert _delta(_resyncs(), before) == {"key": 2, "iteration": 2}


# --- nothing crosses between two steady steps --------------------------------
@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_steady_steps_send_nothing_and_compile_nothing(engine, monkeypatch):
    """Steps 2 to N of a ``fit()``: no host-to-device transfer in
    ``dispatch_prep``, no new signature for the step, no re-send."""
    armed = []
    span = tracing.StepSpans.span

    @contextlib.contextmanager
    def guarded(self, name, phase, iteration=None):
        with span(self, name, phase, iteration) as sp:
            if phase == "dispatch_prep" and armed:
                with jax.transfer_guard_host_to_device("disallow"):
                    yield sp
            else:
                yield sp

    class Arm(IterationListener):
        def iteration_done(self, model, iteration):
            armed.append(iteration)

    monkeypatch.setattr(tracing.StepSpans, "span", guarded)
    batches = _batches(6)
    net, rec = ENGINES[engine](), _Record()
    net.set_listeners(rec, Arm())
    before = _resyncs()
    net.fit(ListDataSetIterator(batches))
    assert len(armed) == 6
    assert _delta(_resyncs(), before) == {"key": 1, "iteration": 1}
    assert net.compile_telemetry.snapshot()["by_kind"] == {"train_step": 1}
    assert net._step_fn._cache_size() == 1
    want, _ = _parent(ENGINES[engine](), [([d], i)
                                          for i, d in enumerate(batches)])
    _same(rec.seen, want)
    # a second fit on the same net: the carry is still the device's
    before = _resyncs()
    net.fit(ListDataSetIterator(batches[:2]))
    assert _delta(_resyncs(), before) == {}
    assert net._step_fn._cache_size() == 1


def test_the_steps_returned_key_and_iteration():
    """The raw step: the key after one split, the iteration plus one."""
    net = _mln()
    x, y = _batches(1)[0].features, _batches(1)[0].labels
    key = jax.random.PRNGKey(3)
    step = jax.jit(net._build_step_raw())
    out = step(
        net.net_params, net.net_state, net.opt_states, jnp.asarray(x),
        jnp.asarray(y), None, None, jnp.asarray(7, jnp.int32), key)
    assert len(out) == 6
    np.testing.assert_array_equal(np.asarray(out[4]),
                                  np.asarray(jax.random.split(key)[0]))
    assert int(out[5]) == 8 and out[5].dtype == jnp.int32


# --- the publishers read what the step left ----------------------------------
class _Reads(IterationListener):
    """After each step, beside what the publishers wrote: the registry's
    readings and what the state holds."""

    def __init__(self, read):
        self.read, self.rows = read, []

    def iteration_done(self, model, iteration):
        self.rows.append(self.read(model))


def _series(name):
    fam = monitor.get_registry().get(name)
    if fam is None:
        return {}
    return {tuple(sorted(s["labels"].items())): s["value"]
            for s in fam.describe()["samples"]}


def _tiles(series, vertex="l2_attn"):
    return {dict(k)["outcome"]: v for k, v in series.items()
            if dict(k)["vertex"] == vertex}


def test_the_expert_publishers_read_each_steps_state():
    lfm2 = importlib.import_module("test_lfm2")
    net = lfm2._net(dict(lfm2.CFG, layers_run=[2, 3]))
    net.init()
    held = {k: list(net.conf.vertices[k].layer_conf()._held())
            for k in ("l2_moe", "l3_moe")}

    def read(model):
        state = jax.device_get({k: (model.net_state[k]["moe_expert_counts"],
                                    model.net_state[k]["moe_row_segments"])
                                for k in held})
        return (_series("dl4j_moe_assignments_total"),
                _series("dl4j_moe_row_segments_total"),
                _series("dl4j_attention_tiles"), state)

    reads = _Reads(read)
    net.set_listeners(reads)
    rng = np.random.default_rng(5)
    ids = rng.integers(0, lfm2.CFG["vocab_size"], (3, 2, 33), dtype=np.int32)
    from deeplearning4j_tpu.datasets.dataset import MultiDataSet
    data = [MultiDataSet([b[:, :-1]], [b[:, 1:]]) for b in ids]
    first = read(net)
    net.fit(data[0])
    for d in data[1:]:
        net.fit(d)
    rows = [first] + reads.rows
    tiles_before = _tiles(first[2])
    for (a0, s0, _, _), (a1, s1, tiles, state) in zip(rows, rows[1:]):
        for k, (c, (run, skipped)) in state.items():
            here = int(c[held[k]].sum())
            assert a1[(("held", "1"), ("vertex", k))] \
                - a0.get((("held", "1"), ("vertex", k)), 0) == here
            assert a1[(("held", "0"), ("vertex", k))] \
                - a0.get((("held", "0"), ("vertex", k)), 0) \
                == int(c.sum()) - here
            for outcome, n in (("run", run), ("skipped", skipped),
                               ("recomputed", run - 1)):
                lab = (("outcome", outcome), ("vertex", k))
                assert s1[lab] - s0.get(lab, 0) == int(n)
        want = net.conf.vertices["l2_attn"].layer_conf().core_tiles(32)
        if want is None:
            # the dense core: the fit publishes nothing, and the gauge
            # reads what it read before (another net may have set it)
            want = tiles_before
        assert _tiles(tiles) == {o: float(n) for o, n in want.items()}


def test_the_loop_publisher_reads_each_steps_state():
    ouro = importlib.import_module("test_ouro")
    net = ouro._net()
    net.init()

    def read(model):
        mass = jax.device_get(model.net_state["head"]["loop_exit_mass"])
        return _series("dl4j_loop_exit_mass"), mass

    reads = _Reads(read)
    net.set_listeners(reads)
    rng = np.random.default_rng(6)
    ids = rng.integers(0, ouro.CFG["vocab_size"], (3, 2, 33), dtype=np.int32)
    from deeplearning4j_tpu.datasets.dataset import MultiDataSet
    for b in ids:
        net.fit(MultiDataSet([b[:, :-1]], [b[:, 1:]]))
    assert len(reads.rows) == 3
    for got, mass in reads.rows:
        assert [got[(("pass", str(r + 1)), ("vertex", "head"))]
                for r in range(len(mass))] == [float(m) for m in mass]
