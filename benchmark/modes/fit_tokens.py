"""Mode ``fit_tokens``: mode ``fit`` for a language model.  One
``net.fit(iterator)`` call on one chip over a pool of host batches of
``[batch, seq_len]`` int32 token ids with next-token labels (``[batch,
seq_len]`` int32 class ids), all sequences distinct; a sample is a
sequence.  Everything that times and traces is ``modes/fit.py``'s.

What differs is the set-up and the reference's side of the check:

* the builder's arguments are the configuration's own keys (the published
  names), so a width stands in the file once;
* the expert layers' selection bias is state, not a parameter: the
  reference seeds it and the program is handed the same values;
* the updater is Adam.  After its first step the first moment is
  ``(1 - beta1) g``, so the first gradient is read from ``m`` (``v`` is
  the second moment there);
* the reference follows the steps a sequence at a time with Adam written
  out (``reference/lfm2_moe.py:follow``);
* a traced run keeps its trace (``BENCHMARK_KEEP_TRACE``, which ``run.py``
  honours) with the expert layers' assignment counters of the traced
  steps beside it, for the readers of the expert layer's metrics
  (``harness/moe_scopes.py``).
"""

from __future__ import annotations

import json
import os
import shutil
import time

import numpy as np

from benchmark.harness.moe_scopes import COUNTERS_FILE
from benchmark.modes import fit
from benchmark.modes.fit import _flat, _key, _load

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ASSIGNMENTS = "dl4j_moe_assignments_total"

# the configuration's keys that the builder takes under the same name
PUBLISHED = ("vocab_size", "hidden_size", "num_attention_heads",
             "num_key_value_heads", "intermediate_size",
             "moe_intermediate_size", "num_experts_per_tok", "layer_types",
             "num_dense_layers", "conv_L_cache", "norm_eps", "rope_theta",
             "norm_topk_prob", "use_expert_bias")


def make_pool(seed: int, n: int, batch: int, seq_len: int, vocab: int):
    """``n`` host batches as a tokenised corpus hands them over: int32 ids
    ``[batch, seq_len]`` and the ids one place on as labels, every
    sequence drawn anew from the rows of the vocabulary held here."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    rng = np.random.default_rng(seed)
    pool = []
    for _ in range(n):
        ids = rng.integers(0, vocab, (batch, seq_len + 1), dtype=np.int32)
        pool.append(DataSet(np.ascontiguousarray(ids[:, :-1]),
                            np.ascontiguousarray(ids[:, 1:])))
    return pool


def held_assignments():
    """{vertex: assignments to experts held here} so far, from the
    program's counter; {} where the program has none."""
    from deeplearning4j_tpu import monitor
    fam = monitor.get_registry().snapshot().get(ASSIGNMENTS, {})
    return {s["labels"]["vertex"]: float(s["value"])
            for s in fam.get("samples", []) if s["labels"].get("held") == "1"}


class Mode(fit.Mode):
    def __init__(self, cfg, traffic, seed, chips, rehearse):
        if rehearse:
            over = traffic.get("rehearse", {})
            traffic = {**traffic, **over.get("traffic", {})}
            cfg = {**cfg, **over.get("config", {})}
        self.seq_len = int(traffic["seq_len"])
        cfg = {**cfg, "seq_len": self.seq_len}
        self.cfg, self.traffic, self.seed, self.chips = cfg, traffic, seed, chips
        os.environ.update(cfg.get("environment", {}))
        self.batch = int(traffic["batch"])
        self.ref = _load(cfg["reference"])
        self.layers = self.ref.layers(cfg)
        u = cfg["updater"]
        self.lr, self.adam = float(u["learning_rate"]), (
            float(u["beta1"]), float(u["beta2"]), float(u["epsilon"]))
        self.net = None
        self.readings = None
        # where run.py leaves the traced run's trace for the readers
        self.kept = os.environ.setdefault(
            "BENCHMARK_KEEP_TRACE",
            os.path.join(ROOT, ".bench_trace", "kept_" + cfg["name"]))

    # -- set-up ---------------------------------------------------------
    def _bias(self):
        return self.ref.init_expert_bias(self.cfg, _key(self.seed))

    def setup(self):
        import jax
        cfg, tr = self.cfg, self.traffic
        marks = self.setup_marks = {}
        t = time.perf_counter()

        def mark(name):
            nonlocal t
            now = time.perf_counter()
            marks[name], t = now - t, now

        self.pool = make_pool(self.seed, int(tr["pool_batches"]), self.batch,
                              self.seq_len, cfg["vocab_size"])
        mark("host_pool_s")
        net = _load(cfg["builder"])(
            seed=self.seed % (2 ** 31 - 1),
            **{k: cfg[k] for k in PUBLISHED},
            num_experts=cfg["num_experts_published"],
            layers=cfg["layers_run"], experts_held=cfg["experts_held"],
            seq_len=self.seq_len, learning_rate=self.lr)
        self._shapes = jax.eval_shape(
            lambda k: self.ref.init_params(cfg, k), _key(0))
        weights = self._weights()
        net.init(params={n: weights.get(n, {}) for n in net.order})
        del weights
        for vertex, bias in self._bias().items():
            net.net_state[vertex] = {**net.net_state[vertex],
                                     "expert_bias": bias}
        jax.block_until_ready((net.net_params, net.net_state))
        mark("build_and_weights_s")
        self.net = net
        warm = int(tr["warmup_steps"])
        norms = jax.jit(lambda t: self._norms(t))
        change = jax.jit(lambda p, k: self._norms(jax.tree_util.tree_map(
            lambda a, b: a - b, self._ours(p), self.ref.init_params(cfg, k))))
        got = {}
        beta1 = self.adam[0]

        def on_step(model, n):
            if n == 1:
                m1 = self._ours({k: (o.get("m", {}) if isinstance(o, dict)
                                     else {})
                                 for k, o in model.opt_states.items()})
                got["m1"] = norms(m1)
                # the first gradient itself, on the host: m1 = (1 - beta1) g
                got["g1"] = {k: np.asarray(v) / (1 - beta1)
                             for k, v in _flat(jax.device_get(m1)).items()}
            if n == warm:
                got["dp"] = change(model.net_params, _key(self.seed))

        clock = fit._Clock(on_step)
        net.set_listeners(clock)
        net.fit(fit.pool_iterator(self.pool, steps=warm),
                fused_steps=int(tr["fused_steps"]))
        mark("warmup_fit_s")
        if len(clock.scores) != warm:
            raise SystemExit(f"benchmark: warm-up ran {len(clock.scores)} "
                             f"steps, {warm} asked")
        self.readings = {
            "losses": list(clock.scores),
            "grad_norms": {k: float(v) / (1 - beta1)
                           for k, v in jax.device_get(got["m1"]).items()},
            "change_norms": {k: float(v)
                             for k, v in jax.device_get(got["dp"]).items()},
            "first_grad": got["g1"]}
        self.retraces_before = net.compile_telemetry.retraces
        mark("readings_s")

    # -- the traced stretch ---------------------------------------------
    def traced(self):
        shutil.rmtree(self.kept, ignore_errors=True)
        before = held_assignments()
        stretch = super().traced()
        after = held_assignments()
        os.makedirs(self.kept, exist_ok=True)
        with open(os.path.join(self.kept, COUNTERS_FILE), "w") as f:
            json.dump({"steps": stretch["steps"],
                       "held_assignments": {
                           v: after[v] - before.get(v, 0.0) for v in after}}, f)
        return stretch

    # -- the check ------------------------------------------------------
    def reference_readings(self, numerics="float32", rows=None):
        warm = int(self.traffic["warmup_steps"])
        batches = [(d.features, d.labels) for d in self.pool[:warm]]
        out = self.ref.follow(
            self.ref.loss_fn(self.cfg, numerics, self._bias()),
            self._weights(), batches, self.lr, *self.adam, rows=rows)
        return {"losses": out["losses"],
                "grad_norms": {k: float(v) for k, v in _flat(out["grad_norms"]).items()},
                "change_norms": {k: float(v) for k, v in _flat(out["change_norms"]).items()},
                "first_grad": {k: np.asarray(v) for k, v in _flat(out["first_grad"]).items()}}
