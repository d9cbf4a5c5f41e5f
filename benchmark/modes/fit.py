"""Mode ``fit``: one ``net.fit(iterator)`` call on one chip.

The traffic file gives the batch, the pool of host batches, the warm-up
steps and ``fused_steps``; the configuration file gives the builder and
its plain reference.  Set-up builds ONE network, gives it weights made
from the seed by the reference's initialiser, and drives it through its
first ``warmup_steps`` steps with the same ``fit()`` call and the same
iterator class the window uses, on batches that all differ.  The
readings of those steps are what ``check()`` compares with the reference
once the window has closed.  The window hands the same network to one
``fit()`` whose iterator stops offering batches at the deadline.
"""

from __future__ import annotations

import gc
import importlib
import os
import time

import numpy as np

from benchmark.harness import compare, stats

SPAN_METRIC = "dl4j_phase_seconds"


def _load(dotted: str):
    mod, _, attr = dotted.partition(":")
    m = importlib.import_module(mod)
    return getattr(m, attr) if attr else m


def _key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _flat(tree):
    """{path: leaf} of a pytree."""
    import jax
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): v for p, v in leaves}


def _like(program_tree, reference_tree):
    """The program's leaves at the reference's paths: the list engine
    holds a list by layer, the graph engine a dict by vertex."""
    if isinstance(reference_tree, dict):
        return {k: _like(program_tree[k], v) for k, v in reference_tree.items()}
    if isinstance(reference_tree, (list, tuple)):
        return [_like(p, r) for p, r in zip(program_tree, reference_tree)]
    return program_tree


def _momentum(opt_states):
    """The Nesterov velocity of each layer, shaped like the parameters."""
    if isinstance(opt_states, dict):
        return {k: (o.get("v", {}) if isinstance(o, dict) else {})
                for k, o in opt_states.items()}
    return [(o.get("v", {}) if isinstance(o, dict) else {}) for o in opt_states]


def span_totals():
    """{phase: (sum of seconds, count)} of the program's fit/step spans."""
    from deeplearning4j_tpu import monitor
    fam = monitor.get_registry().snapshot().get(SPAN_METRIC, {})
    return {s["labels"]["phase"]: (float(s["sum"]), int(s["count"]))
            for s in fam.get("samples", [])
            if s["labels"].get("span") == "fit/step"}


def make_pool(seed: int, n: int, batch: int, channels: int, size: int,
              classes: int, contrast=(1.0, 1.0)):
    """``n`` host batches as a user's image pipeline hands them over:
    float32 NCHW images and one-hot float32 labels, all rows distinct.
    ``contrast`` scales the rows of a batch along a ramp from its first
    value to its second, so that no half of a batch stands for the whole."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    rng = np.random.default_rng(seed)
    ramp = np.linspace(contrast[0], contrast[1], batch, dtype=np.float32)
    pool = []
    for _ in range(n):
        x = rng.standard_normal((batch, channels, size, size), dtype=np.float32)
        x *= ramp[:, None, None, None]
        y = np.zeros((batch, classes), np.float32)
        y[np.arange(batch), rng.integers(0, classes, batch)] = 1.0
        pool.append(DataSet(x, y))
    return pool


def pool_iterator(pool, steps=None, deadline=None):
    """Cycles the pool; offers ``steps`` batches, or batches until the
    clock passes ``deadline``."""
    from deeplearning4j_tpu.datasets.iterators import DataSetIterator

    class PoolIterator(DataSetIterator):
        def __init__(self):
            self._i = 0

        def has_next(self):
            if steps is not None:
                return self._i < steps
            return time.perf_counter() < deadline

        def next(self):
            d = pool[self._i % len(pool)]
            self._i += 1
            return d

        def reset(self):
            self._i = 0

        def batch_size(self):
            return pool[0].num_examples()

    return PoolIterator()


class _Clock:
    """Listener: the host clock and the score at every iteration_done."""

    def __init__(self, on_step=None):
        self.times, self.scores, self.on_step = [], [], on_step

    def iteration_done(self, model, iteration):
        self.times.append(time.perf_counter())
        self.scores.append(float(model._score))
        if self.on_step is not None:
            self.on_step(model, len(self.times))


class Mode:
    def __init__(self, cfg, traffic, seed, chips, rehearse):
        if rehearse:
            over = traffic.get("rehearse", {})
            traffic = {**traffic, **over.get("traffic", {})}
            cfg = {**cfg, **over.get("config", {})}
            cfg["builder_args"] = {**cfg["builder_args"],
                                   **over.get("builder_args", {})}
        self.cfg, self.traffic, self.seed, self.chips = cfg, traffic, seed, chips
        # switches of the program that the configuration states (its file
        # says why); set before the program is imported
        os.environ.update(cfg.get("environment", {}))
        self.batch = int(traffic["batch"])
        self.ref = _load(cfg["reference"])
        self.layers = self.ref.layers(cfg)
        self.lr = float(cfg["updater"]["learning_rate"])
        self.mu = float(cfg["updater"]["momentum"])
        self.net = None
        self.readings = None

    # -- set-up ---------------------------------------------------------
    def _weights(self):
        import jax
        return jax.jit(lambda k: self.ref.init_params(self.cfg, k))(
            _key(self.seed))

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
                              cfg["channels"], cfg["image_size"],
                              cfg["num_classes"],
                              tuple(tr.get("row_contrast", (1.0, 1.0))))
        mark("host_pool_s")
        net = _load(cfg["builder"])(seed=self.seed % (2 ** 31 - 1),
                                    **cfg["builder_args"])
        self._shapes = jax.eval_shape(
            lambda k: self.ref.init_params(cfg, k), _key(0))
        weights = self._weights()
        if hasattr(net, "order"):      # graph engine: every vertex has an entry
            weights = {n: weights.get(n, {}) for n in net.order}
        net.init(params=weights)
        del weights
        jax.block_until_ready(net.net_params)
        mark("build_and_weights_s")
        self.net = net
        warm = int(tr["warmup_steps"])
        norms = jax.jit(lambda t: self._norms(t))
        change = jax.jit(lambda p, k: self._norms(jax.tree_util.tree_map(
            lambda a, b: a - b, self._ours(p), self.ref.init_params(cfg, k))))
        got = {}

        def on_step(model, n):
            if n == 1:
                v1 = self._ours(_momentum(model.opt_states))
                got["v1"] = norms(v1)
                # the first gradient itself, on the host: v1 = -lr g
                got["g1"] = {k: np.asarray(v) / -self.lr
                             for k, v in _flat(jax.device_get(v1)).items()}
            if n == warm:
                got["dp"] = change(model.net_params, _key(self.seed))

        clock = _Clock(on_step)
        net.set_listeners(clock)
        net.fit(pool_iterator(self.pool, steps=warm),
                fused_steps=int(tr["fused_steps"]))
        mark("warmup_fit_s")
        if len(clock.scores) != warm:
            raise SystemExit(f"benchmark: warm-up ran {len(clock.scores)} "
                             f"steps, {warm} asked")
        self.readings = {
            "losses": list(clock.scores),
            "grad_norms": {k: float(v) / self.lr
                           for k, v in jax.device_get(got["v1"]).items()},
            "change_norms": {k: float(v)
                             for k, v in jax.device_get(got["dp"]).items()},
            "first_grad": got["g1"]}
        self.retraces_before = net.compile_telemetry.retraces
        mark("readings_s")

    def _ours(self, program_tree):
        return _like(program_tree, self._shapes)

    @staticmethod
    def _norms(tree):
        import jax.numpy as jnp
        return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                for k, v in _flat(tree).items()}

    # -- the window -----------------------------------------------------
    def _fit(self, **how):
        clock = _Clock()
        self.net.set_listeners(clock)
        spans0 = span_totals()
        t0 = time.perf_counter()
        self.net.fit(pool_iterator(self.pool, **how),
                     fused_steps=int(self.traffic["fused_steps"]))
        t1 = time.perf_counter()
        spans1 = span_totals()
        spans = {p: (s - spans0.get(p, (0.0, 0))[0], c - spans0.get(p, (0.0, 0))[1])
                 for p, (s, c) in spans1.items()}
        return {"seconds": t1 - t0, "steps": len(clock.times),
                "batch": self.batch, "spans": spans,
                "step_ms": stats.intervals_ms([t0] + clock.times),
                "scores": clock.scores, "t0": t0, "t1": t1}

    def window(self, seconds: float):
        w = self._fit(deadline=time.perf_counter() + seconds)
        w["retraces"] = self.net.compile_telemetry.retraces - self.retraces_before
        bad = sum(1 for s in w["scores"] if not np.isfinite(s))
        self.result = w
        return {
            "attempted": w["steps"], "failed": bad,
            "end_to_end": {
                "train_samples_per_s": w["steps"] * self.batch / w["seconds"],
                "step_ms_p95": stats.percentile(w["step_ms"], 95)},
            "window": w}

    def traced(self):
        """The short steady stretch a traced run profiles."""
        return self._fit(steps=int(self.traffic["trace_steps"]))

    # -- the check ------------------------------------------------------
    def release(self):
        """Free the program's state so that the reference has the chip."""
        if self.net is not None:
            self.net.set_listeners()
            self.net.net_params = self.net.opt_states = self.net.net_state = None
            self.net._step_fn = None
        self.net = None
        gc.collect()

    def reference_readings(self, numerics="float32", rows=None):
        from benchmark.reference import common
        warm = int(self.traffic["warmup_steps"])
        batches = [(d.features, d.labels) for d in self.pool[:warm]]
        out = common.follow(
            self.ref.loss_fn(self.cfg, numerics), self._weights(), batches,
            self.lr, self.mu, rows=rows,
            row_blocks=(self.ref.ROW_BLOCKS if rows is None
                        else max(1, self.ref.ROW_BLOCKS // 2)))
        return {"losses": out["losses"],
                "grad_norms": {k: float(v) for k, v in _flat(out["grad_norms"]).items()},
                "change_norms": {k: float(v) for k, v in _flat(out["change_norms"]).items()},
                "first_grad": {k: np.asarray(v) for k, v in _flat(out["first_grad"]).items()}}

    def check(self, limits):
        """[(name, value, limit, ok)], after the window has closed."""
        self.release()
        values, where = compare.gaps(self.readings, self.reference_readings())
        values["window_retraces"] = self.result["retraces"]
        self.where = where
        return compare.verdict(values, limits)
