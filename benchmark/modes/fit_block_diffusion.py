"""Mode ``fit_block_diffusion``: mode ``fit_tokens`` for a model trained
by block diffusion.  One ``net.fit(iterator)`` call on one chip over a
pool of host batches of ``[batch, seq_len]`` clean int32 token ids,
cycled through the program's own seeded pre-processor
(``datasets.diffusion.BlockDiffusionNoiser``), which makes ``([x_t ; x0]
[batch, 2 seq_len], x0, weights)`` of every batch anew inside the timed
loop, as a user's job does; a sample is one clean sequence.  Everything
that times and traces is ``modes/fit.py``'s (its ``_fit`` is repeated
here for the one line that builds the iterator).

What differs from ``fit_tokens``:

* the builder's arguments are the configuration's own keys
  (``PUBLISHED``), the share held and the block length;
* the batches the warm-up's steps were fed are recorded AFTER the
  pre-processor and are what the reference is given; ``check()`` holds
  them to the definition in its own numpy (x_t equals x0 off the mask
  and the mask id on it; one weight a block on its masked tokens, at
  least 1 and at most 1 / t_min, and 0 off the mask; the clean half and
  the labels untouched; no clean id equal to the mask id), and the
  violations are a compared number with the limit 0;
* the reference takes ``(ids, labels, weights)`` and, for the readings
  the limits are set from, a ``fault`` of the mechanism or a control of
  the precision;
* one more compared number, ``early_rows_grad_diff``: the head's first
  gradient at the columns that single early rows write (a fault of the
  mask moves those rows' states at order one, the summed gradient
  hardly);
* a traced run keeps its trace (``BENCHMARK_KEEP_TRACE``) with the steps
  and the program's diffusion counters and tile counts beside it, for
  the readers of the attention core's metrics
  (``harness/blockdiff.py``).

The updater is Adam: the first gradient is read from ``m``.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

import numpy as np

from benchmark.harness import blockdiff, compare, stats
from benchmark.modes import fit, fit_tokens
from benchmark.modes.fit import _flat, _key, _load

# the configuration's keys that the builder takes under the same name
PUBLISHED = ("vocab_size", "hidden_size", "num_attention_heads",
             "num_key_value_heads", "head_dim", "moe_intermediate_size",
             "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
             "rope_theta")
EARLY_ROWS = 32             # masked rows ``early_rows_grad_diff`` looks at
HEAD = "['head']['W']"      # the head's leaf among the readings


def make_pool(seed: int, n: int, batch: int, seq_len: int, mask_id: int):
    """``n`` host batches of clean ids as a tokenised corpus hands them
    over: int32 ``[batch, seq_len]``, every sequence drawn anew from the
    rows held but the mask id (the last)."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    rng = np.random.default_rng(seed)
    return [DataSet(rng.integers(0, mask_id, (batch, seq_len),
                                 dtype=np.int32), None) for _ in range(n)]


def off_definition(batches, seq_len, block_length, mask_id, t_min):
    """How many of the recorded batches break the definition of the
    noise, and the first reason."""
    bad, why = 0, None
    L, b = seq_len, block_length
    for x, y, w in batches:
        x, y, w = np.asarray(x), np.asarray(y), np.asarray(w)
        masked = w > 0
        per_block = w.reshape(w.shape[0], L // b, b)
        top = per_block.max(-1, keepdims=True)
        reasons = [
            ("shapes", x.shape == (y.shape[0], 2 * L) and y.shape == w.shape
             == (x.shape[0], L)),
            ("the clean half is x0", np.array_equal(x[:, L:], y)),
            ("a clean id is the mask id", not (y == mask_id).any()),
            ("x_t off the mask is x0",
             np.array_equal(x[:, :L][~masked], y[~masked])),
            ("x_t on the mask is the mask id",
             bool((x[:, :L][masked] == mask_id).all())),
            ("one weight a block", bool(((per_block == 0)
                                         | (per_block == top)).all())),
            ("weights are 1 / t, t in [t_min, 1]",
             bool((w[masked] >= 1.0).all()
                  and (w[masked] <= (1.0 / t_min) * (1 + 1e-6)).all())),
        ]
        failed = [name for name, ok in reasons if not ok]
        if failed:
            bad += 1
            why = why or failed[0]
    return bad, why


class _Recording:
    """The program's pre-processor, with what it handed on kept while
    ``keep`` is set."""

    def __init__(self, noiser):
        self.noiser, self.keep, self.seen = noiser, False, []

    def pre_process(self, ds):
        out = self.noiser.pre_process(ds)
        if self.keep:
            self.seen.append((out.features, out.labels, out.labels_mask))
        return out


class Mode(fit_tokens.Mode):
    def __init__(self, cfg, traffic, seed, chips, rehearse):
        self.block_length = int(traffic["block_length"])
        super().__init__({**cfg, "block_length": self.block_length}, traffic,
                         seed, chips, rehearse)

    # -- the iterator ---------------------------------------------------
    def _iterator(self, **how):
        from deeplearning4j_tpu.datasets.diffusion import PreProcessingIterator
        return PreProcessingIterator(fit.pool_iterator(self.pool, **how),
                                     self.noise)

    # -- set-up ---------------------------------------------------------
    def setup(self):
        import jax
        from deeplearning4j_tpu.datasets.diffusion import BlockDiffusionNoiser
        cfg, tr = self.cfg, self.traffic
        marks = self.setup_marks = {}
        t = time.perf_counter()

        def mark(name):
            nonlocal t
            now = time.perf_counter()
            marks[name], t = now - t, now

        self.pool = make_pool(self.seed, int(tr["pool_batches"]), self.batch,
                              self.seq_len, cfg["mask_id"])
        self.noise = _Recording(BlockDiffusionNoiser(
            self.block_length, cfg["mask_id"], self.seed,
            t_min=float(tr["t_min"])))
        mark("host_pool_s")
        net = _load(cfg["builder"])(
            seed=self.seed % (2 ** 31 - 1),
            **{k: cfg[k] for k in PUBLISHED},
            num_experts=cfg["num_experts_published"],
            layers=cfg["layers_run"], experts_held=cfg["experts_held"],
            seq_len=self.seq_len, block_length=self.block_length,
            recompute_experts=bool(cfg.get("recompute_experts", False)),
            learning_rate=self.lr)
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
        self.noise.keep = True
        net.fit(self._iterator(steps=warm), fused_steps=int(tr["fused_steps"]))
        self.noise.keep = False
        mark("warmup_fit_s")
        if len(clock.scores) != warm or len(self.noise.seen) != warm:
            raise SystemExit(f"benchmark: warm-up ran {len(clock.scores)} "
                             f"steps on {len(self.noise.seen)} noised "
                             f"batches, {warm} asked")
        self.readings = {
            "losses": list(clock.scores),
            "grad_norms": {k: float(v) / (1 - beta1)
                           for k, v in jax.device_get(got["m1"]).items()},
            "change_norms": {k: float(v)
                             for k, v in jax.device_get(got["dp"]).items()},
            "first_grad": got["g1"]}
        self.retraces_before = net.compile_telemetry.retraces
        mark("readings_s")

    # -- the window: fit.Mode._fit with this mode's iterator ---------------
    def _fit(self, **how):
        clock = fit._Clock()
        self.net.set_listeners(clock)
        spans0 = fit.span_totals()
        t0 = time.perf_counter()
        self.net.fit(self._iterator(**how),
                     fused_steps=int(self.traffic["fused_steps"]))
        t1 = time.perf_counter()
        spans1 = fit.span_totals()
        spans = {p: (s - spans0.get(p, (0.0, 0))[0], c - spans0.get(p, (0.0, 0))[1])
                 for p, (s, c) in spans1.items()}
        return {"seconds": t1 - t0, "steps": len(clock.times),
                "batch": self.batch, "spans": spans,
                "step_ms": stats.intervals_ms([t0] + clock.times),
                "scores": clock.scores, "t0": t0, "t1": t1}

    # -- the traced stretch ---------------------------------------------
    def traced(self):
        shutil.rmtree(self.kept, ignore_errors=True)
        stretch = fit.Mode.traced(self)     # not fit_tokens': its own counters
        os.makedirs(self.kept, exist_ok=True)
        with open(os.path.join(self.kept, blockdiff.COUNTERS_FILE), "w") as f:
            json.dump({"steps": stretch["steps"],
                       "program": blockdiff.program_counters()}, f)
        return stretch

    # -- the check ------------------------------------------------------
    def reference_readings(self, numerics="float32", rows=None, fault=None,
                           steps=None):
        """What the reference reads over the warm-up's batches; the first
        ``steps`` of them, for a control or a fault whose verdict the first
        loss and the first gradient decide."""
        out = self.ref.follow(
            self.ref.loss_fn(self.cfg, numerics, fault), self._weights(),
            self.noise.seen[:steps], self.lr, *self.adam, rows=rows,
            hold="bfloat16" if numerics == "params_bfloat16" else "float32")
        return {"losses": out["losses"],
                "grad_norms": {k: float(v) for k, v in _flat(out["grad_norms"]).items()},
                "change_norms": {k: float(v) for k, v in _flat(out["change_norms"]).items()},
                "first_grad": {k: np.asarray(v) for k, v in _flat(out["first_grad"]).items()}}

    def early_rows_grad_diff(self, prog, ref):
        """The first gradient where a single row shows in it: column y of
        the head's gradient is, but for what every row's softmax adds
        (under a hundredth of it), -w_i / L times the final hidden state
        of the row i whose label is y.  Over the columns of the first
        ``EARLY_ROWS`` masked rows of the first sequence of the first
        batch: the norm of the difference over the reference's norm, by
        the median column.  A row of block j sees 4 + 4 j keys, so what is
        wrong with the mask (four keys too many, the clean copy of the
        row's own block) moves an early row's state at order one and the
        whole gradient, summed over 4,096 rows, hardly."""
        _, y, w = (np.asarray(a) for a in self.noise.seen[0])
        cols = y[0][np.flatnonzero(w[0] > 0)[:EARLY_ROWS]]
        a, b = (np.asarray(r["first_grad"][HEAD], np.float64)[:, cols]
                for r in (prog, ref))
        d = np.linalg.norm(a - b, axis=0) / np.linalg.norm(b, axis=0)
        return float(np.median(d)) if np.isfinite(d).all() \
            else compare.NOT_A_NUMBER

    def gaps(self, prog, ref):
        """``compare.gaps`` and this mode's own number."""
        values, where = compare.gaps(prog, ref)
        values["early_rows_grad_diff"] = self.early_rows_grad_diff(prog, ref)
        return values, where

    def check(self, limits):
        bad, why = off_definition(
            self.noise.seen, self.seq_len, self.block_length,
            self.cfg["mask_id"], float(self.traffic["t_min"]))
        t0 = time.perf_counter()
        self.release()
        values, self.where = self.gaps(self.readings, self.reference_readings())
        print(f"check: reference and comparison {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)
        values["window_retraces"] = self.result["retraces"]
        values["noise_off_definition"] = bad
        if bad:
            self.where["noise_off_definition"] = why
        return compare.verdict(values, limits)
