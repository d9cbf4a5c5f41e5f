"""Mode ``fit_looped``: mode ``fit_tokens`` for a looped decoder (a stack
run several times over the same leaves, an exit gate after every pass).
One ``net.fit(iterator)`` call on one chip over a pool of host batches of
``[batch, seq_len]`` int32 token ids with next-token labels; a sample is
a sequence.  Everything that times, traces and checks is
``modes/fit.py``'s; the pool is ``modes/fit_tokens.py``'s.

What differs from ``fit_tokens`` (whose set-up hands its builder the
expert layers' arguments and a selection bias):

* the builder's arguments are the configuration's own keys
  (``PUBLISHED``), ``layers_run`` and the sequence length;
* the reference's leaves stand under the program's vertex names as they
  are (the loop vertex holds its body's leaves under
  ``"<body vertex>/<leaf>"``), so the comparison walks both trees alike;
* a traced run keeps its trace (``BENCHMARK_KEEP_TRACE``, which ``run.py``
  honours) with the passes the program's counter counted over the traced
  steps beside it, for the readers of the loop's metrics
  (``harness/loop_scopes.py``).

The updater is Adam: after its first step the first moment is
``(1 - beta1) g``, so the first gradient is read from ``m``.
"""

from __future__ import annotations

import json
import os
import shutil
import time

import numpy as np

from benchmark.harness.loop_scopes import COUNTERS_FILE, passes_run
from benchmark.modes import fit, fit_tokens
from benchmark.modes.fit import _flat, _key, _load

# the configuration's keys that the builder takes under the same name
PUBLISHED = ("vocab_size", "hidden_size", "num_attention_heads",
             "num_key_value_heads", "intermediate_size", "total_ut_steps",
             "rms_norm_eps", "rope_theta", "entropy_weight")


class Mode(fit_tokens.Mode):
    """``fit_tokens.Mode``'s constructor as it is (sizes, Adam's numbers,
    where the trace is kept); its own set-up, traced stretch and
    reference."""

    # -- set-up ---------------------------------------------------------
    def setup(self):
        import jax
        cfg, tr = self.cfg, self.traffic
        marks = self.setup_marks = {}
        t = time.perf_counter()

        def mark(name):
            nonlocal t
            now = time.perf_counter()
            marks[name], t = now - t, now

        self.pool = fit_tokens.make_pool(self.seed, int(tr["pool_batches"]), self.batch,
                              self.seq_len, cfg["vocab_size"])
        mark("host_pool_s")
        net = _load(cfg["builder"])(
            seed=self.seed % (2 ** 31 - 1),
            **{k: cfg[k] for k in PUBLISHED}, layers=cfg["layers_run"],
            seq_len=self.seq_len, learning_rate=self.lr)
        self._shapes = jax.eval_shape(
            lambda k: self.ref.init_params(cfg, k), _key(0))
        weights = self._weights()
        net.init(params={n: weights.get(n, {}) for n in net.order})
        del weights
        jax.block_until_ready(net.net_params)
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
        before = passes_run()
        stretch = fit.Mode.traced(self)     # not fit_tokens': no expert counters
        after = passes_run()
        os.makedirs(self.kept, exist_ok=True)
        with open(os.path.join(self.kept, COUNTERS_FILE), "w") as f:
            json.dump({"steps": stretch["steps"],
                       "passes": {v: after[v] - before.get(v, 0.0)
                                  for v in after}}, f)
        return stretch

    # -- the check ------------------------------------------------------
    def reference_readings(self, numerics="float32", rows=None, **fault):
        """``fault``: keywords of the reference's ``loss_fn`` (and
        ``passes``, which overrides ``total_ut_steps``) that put a fault
        of the mechanism in the program's place, for the readings the
        limits are set from."""
        warm = int(self.traffic["warmup_steps"])
        batches = [(d.features, d.labels) for d in self.pool[:warm]]
        cfg = self.cfg
        if "passes" in fault:
            cfg = {**cfg, "total_ut_steps": fault.pop("passes")}
        out = self.ref.follow(
            self.ref.loss_fn(cfg, numerics, **fault), self._weights(),
            batches, self.lr, *self.adam, rows=rows)
        return {"losses": out["losses"],
                "grad_norms": {k: float(v) for k, v in _flat(out["grad_norms"]).items()},
                "change_norms": {k: float(v) for k, v in _flat(out["change_norms"]).items()},
                "first_grad": {k: np.asarray(v) for k, v in _flat(out["first_grad"]).items()}}
