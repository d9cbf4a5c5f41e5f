"""ParallelWrapper — data-parallel training over the device mesh.

The reference replicates the model into per-device worker threads and
synchronously averages parameters every ``averagingFrequency`` iterations
through the host (ref: parallelism/ParallelWrapper.java:49-679,
``Nd4j.averageAndPropagate`` :218).  TPU-natively there are two modes:

* ``averaging_frequency=1`` (default, recommended): per-step gradient
  all-reduce — the batch is sharded over the 'data' axis, params are
  replicated, and XLA inserts the psum over ICI inside the one jitted
  step.  Mathematically stronger than parameter averaging (equivalent to
  large-batch SGD) and what BASELINE.json prescribes.

* ``averaging_frequency=N>1`` (reference-compat): each device runs N
  independent local steps on its own replica (params carry a leading
  device axis, sharded over 'data'), then replicas are averaged — the
  mean over the device axis is XLA's all-reduce.  Reproduces the
  reference's parameter-averaging semantics including optional updater
  state averaging (ref: ParallelWrapper.averageUpdatersState :239-257).
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.ops import bucketing
from deeplearning4j_tpu.parallel import fsdp
from deeplearning4j_tpu.parallel import mesh as mesh_util


class ParallelWrapper:
    def __init__(self, model, mesh: Optional[Mesh] = None,
                 averaging_frequency: int = 1,
                 average_updaters: bool = True,
                 prefetch_buffer: int = 4,
                 fused_steps: int = 1):
        """``fused_steps=K>1`` (all-reduce mode only) fuses K same-shape
        sharded batches into ONE compiled lax.scan launch — the engine's
        fit(fused_steps=K) dispatch elimination, composed with the
        per-step gradient psum.  Same caveats: listeners fire once per
        launch, ragged tails fall back per-step."""
        self.model = model
        self.mesh = mesh if mesh is not None else mesh_util.make_mesh()
        self.averaging_frequency = averaging_frequency
        self.average_updaters = average_updaters
        self.prefetch_buffer = prefetch_buffer
        self.fused_steps = max(1, int(fused_steps))
        self._sharded_step = None
        self._sharded_fused = None
        self._local_step = None
        g = model.conf.global_conf
        # the wrapper predates conf.sharding(); its explicit mesh wins,
        # but the small-array replication threshold is honored when the
        # conf opted into sharding
        rb = (g.sharding_replicate_below
              if getattr(g, "sharding_enabled", False) else 0)
        self.plan = fsdp.plan_from_mesh(self.mesh, replicate_below=rb)
        self.n_data = self.plan.n_data

    # ------------------------------------------------------------------
    def _adopt_plan(self, plan):
        """Point the model's grad-constraint/sharding hooks at the
        wrapper's plan (or None in param-averaging mode, where the
        vmapped local step must not constrain) so the shared
        _apply_updates traces against THIS mesh, not a conf-derived
        one."""
        m = self.model
        if fsdp.plan_key(getattr(m, "_sharding_plan", None)) != \
                fsdp.plan_key(plan):
            m._sharding_plan = plan
            m._step_fn = m._score_fn = m._output_fn = None
            m._fused_fns = None

    def _build_sharded_step(self):
        """Mode 1: batch sharded over 'data', params replicated/FSDP;
        XLA inserts the gradient psum (reduce-scatter under fsdp — see
        parallel/fsdp.jit_sharded_step)."""
        m = self.model
        if m.net_params is None:
            m.init()
        return fsdp.jit_sharded_step(m._build_step_raw(), self.plan,
                                     m.net_params, m.opt_states)

    def _place(self):
        """Move model state onto the mesh with the right shardings."""
        fsdp.place_model(self.plan, self.model)

    # ------------------------------------------------------------------
    def fit(self, iterator, epochs: int = 1):
        if self.averaging_frequency <= 1:
            return self._fit_allreduce(iterator, epochs)
        return self._fit_param_averaging(iterator, epochs)

    # Pad/mask primitives now live in ops/bucketing.py (shared with the
    # engines' shape-bucketing paths); kept as aliases for callers/tests.
    _MASK_NONLINEAR_LOSSES = bucketing.MASK_NONLINEAR_LOSSES
    _cycle_rows = staticmethod(bucketing.cycle_rows)
    _scaled_mask = staticmethod(bucketing.scaled_mask)

    def _pad_supported(self):
        """See ops/bucketing.pad_supported — mean reduction, mask-linear
        losses, no batch-coupled aux losses."""
        return bucketing.pad_supported(self.model)

    def _normalize_batch(self, ds, is_graph):
        """Pad-or-trim one batch to the data degree — the shared
        implementation lives in parallel/fsdp.normalize_batch (the
        engines' conf.sharding() fit path uses the very same function).
        Returns (batch, n) with ``n`` the REAL example count, or None
        when everything would be dropped."""
        norm = fsdp.normalize_batch(self.model, ds, self.n_data, is_graph,
                                    owner=self)
        if norm is None:
            return None
        batch, n, bucket = norm
        if bucket is not None:
            tel = getattr(self.model, "compile_telemetry", None)
            if tel is not None:
                tel.record("sharded_step", batch, bucket=bucket)
        return batch, n

    _host_batch = staticmethod(fsdp.host_batch)

    def _run_sharded_step(self, batch, n, bucket=None):
        """One step on one normalized host batch: scattered over the mesh
        (phase ``shard_h2d``), then the engines' own dispatch, wait,
        bookkeeping and listeners (``dispatch_train_step``), as the
        engines' ``conf.sharding()`` path does."""
        from deeplearning4j_tpu.nn.multilayer import dispatch_train_step
        t_step = time.perf_counter()
        with self.model._steps.span("fit/step", phase="shard_h2d"):
            placed = fsdp.shard_put(self.plan, batch)
        self.model.last_batch_size = n
        dispatch_train_step(
            self.model, self._sharded_step, "sharded_step", placed, placed,
            t_step, bucket=bucket)

    def _run_fused_group(self, group):
        m = self.model
        k = len(group)
        if self._sharded_fused is None:
            self._sharded_fused = {}
            # structure warmup (carried-state keys) through one per-step
            self._run_sharded_step(*group[0])
            group = group[1:]
            k = len(group)
            if not k:
                return
        if k not in self._sharded_fused:
            # the engine's own fused builder (MultiLayerNetwork/
            # ComputationGraph._build_fused_step) IS the right program:
            # params/opt/state are committed with their mesh shardings by
            # _place() and the stacked batches carry the scan-axis
            # sharding, so the jit composes the per-step psum with the
            # scan without wrapper-side re-implementation
            self._sharded_fused[k] = self.model._build_fused_step(k)
        scan_sh = NamedSharding(self.mesh, P(None, ("data", "fsdp")))
        stacked = jax.tree_util.tree_map(
            lambda *leaves: self._put_batch(np.stack(leaves), scan_sh),
            *[g[0] for g in group])
        xs, ys, fms, lms = stacked
        m._key, sub = jax.random.split(m._key)
        (m.net_params, m.net_state, m.opt_states,
         score) = self._sharded_fused[k](
            m.net_params, m.net_state, m.opt_states, xs, ys, fms, lms,
            jnp.asarray(m.iteration, jnp.int32), sub)
        m._strip_rnn_state()
        m._score = score
        m.iteration += k
        m.last_batch_size = group[0][1] * k
        for lst in m.listeners:
            lst.iteration_done(m, m.iteration)

    @staticmethod
    def _batch_sig(batch):
        leaves, treedef = jax.tree_util.tree_flatten(batch)
        # dtype included: np.stack would silently promote a mixed-dtype
        # group and train it at the promoted precision
        return (treedef, tuple((a.shape, a.dtype) for a in leaves))

    def _fit_allreduce(self, iterator, epochs: int):
        from deeplearning4j_tpu import monitor
        from deeplearning4j_tpu.datasets.iterators import AsyncDataSetIterator
        m = self.model
        is_graph = type(m).__name__ == "ComputationGraph"
        if m.net_params is None:
            m.init()
        self._adopt_plan(self.plan)
        if self._sharded_step is None:
            self._sharded_step = self._build_sharded_step()
            self._place()
        fuse = self.fused_steps

        def normalize(ds):
            return fsdp.normalize_batch(m, ds, self.n_data, is_graph,
                                        owner=self)

        it = AsyncDataSetIterator(iterator, queue_size=self.prefetch_buffer)
        try:
            # the phases of fit/step tile this loop as they tile the
            # engines' own (what runs between two of them is ``glue``)
            with monitor.profile_if_configured("fit") as profiling:
                m._steps = steps = monitor.StepSpans(
                    annotate=profiling or None)
                for _ in range(epochs):
                    with steps.span("fit/step", phase="epoch"):
                        it.reset()
                    pending = []
                    while True:
                        with steps.span("fit/step", phase="has_next"):
                            more = it.has_next()
                        if not more:
                            break
                        with steps.span("fit/step", phase="data_wait"):
                            ds = it.next()
                        with steps.span("fit/step", phase="bucket"):
                            norm = normalize(ds)
                        if norm is None:
                            continue
                        if fuse == 1:
                            self._run_sharded_step(*norm)
                            continue
                        if pending and self._batch_sig(pending[0][0]) \
                                != self._batch_sig(norm[0]):
                            for g in pending:  # mixed shapes: per-step
                                self._run_sharded_step(*g)
                            pending = []
                        pending.append(norm)
                        if len(pending) == fuse:
                            self._run_fused_group(pending)
                            pending = []
                    for g in pending:
                        self._run_sharded_step(*g)
        finally:
            it.close()  # a producer blocked on a full queue must not leak
            if m._steps is not None:
                m._steps.close()
        return m

    # ------------------------------------------------------------------
    def _build_local_step(self):
        """Mode 2: per-replica independent step via vmap over a leading
        device axis, sharded over 'data' → no cross-device traffic during
        local steps; averaging afterwards is the collective."""
        m = self.model
        base_step = m._build_step_raw()

        def local(params, state, opts, x, y, fm, lm, it, rng):
            return base_step(params, state, opts, x, y, fm, lm, it, rng)

        vstep = jax.vmap(local, in_axes=(0, 0, 0, 0, 0, 0, 0, None, 0))
        dev_axis = NamedSharding(self.mesh, P(("data", "fsdp")))

        jit_step = jax.jit(vstep, donate_argnums=(0, 1, 2))

        def average(params, opts):
            avg_p = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(jnp.mean(a, axis=0), a.shape), params)
            if self.average_updaters:
                opts = jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(jnp.mean(a, axis=0), a.shape), opts)
            return avg_p, opts

        jit_avg = jax.jit(average, donate_argnums=(0, 1))
        return jit_step, jit_avg, dev_axis

    @staticmethod
    def _put_batch(arr, batch_sh):
        """Place one batch onto the mesh.  Multi-process (the cluster
        tier, scaleout/multislice.py): each host feeds its process-LOCAL
        rows and the global array is assembled across hosts — the Spark
        executors-feed-disjoint-partitions pattern
        (ref: spark/impl/paramavg/ParameterAveragingTrainingMaster.java
        executeTraining split semantics)."""
        arr = np.asarray(arr)
        if jax.process_count() > 1:
            return jax.make_array_from_process_local_data(batch_sh, arr)
        return jax.device_put(arr, batch_sh)

    def _fit_param_averaging(self, iterator, epochs: int):
        m = self.model
        # the vmapped local step must not carry sharding constraints —
        # params deliberately live replica-per-device here
        self._adopt_plan(None)
        if m.net_params is None:
            m.init()
        if self._local_step is None:
            self._local_step = self._build_local_step()
        jit_step, jit_avg, dev_axis = self._local_step
        D = self.n_data

        # replicate model state with a leading device axis
        stack = lambda t: jax.device_put(  # noqa: E731
            jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (D,) + a.shape), t),
            jax.tree_util.tree_map(lambda a: dev_axis, t))
        params = stack(m.net_params)
        opts = stack(m.opt_states)
        state = stack(m.net_state)

        since_avg = 0
        for _ in range(epochs):
            iterator.reset()
            while iterator.has_next():
                # one remainder policy for both modes (pad+mask, or
                # trim+warn fallback) — see _normalize_batch
                norm = self._normalize_batch(iterator.next(), False)
                if norm is None:
                    continue
                (x, y, fm, lm), _ = norm
                n = len(x)   # padded/trimmed row count, divisible by D
                shard = lambda a: (  # noqa: E731
                    None if a is None else jax.device_put(
                        np.asarray(a).reshape((D, n // D) + a.shape[1:]),
                        dev_axis))
                m._key, sub = jax.random.split(m._key)
                rngs = jax.random.split(sub, D)
                params, state, opts, scores = jit_step(
                    params, state, opts, shard(x), shard(y),
                    shard(fm), shard(lm),
                    jnp.asarray(m.iteration, jnp.int32), rngs)
                m._score = jnp.mean(scores)  # lazy; score() converts
                m.iteration += 1
                since_avg += 1
                if since_avg >= self.averaging_frequency:
                    params, opts = jit_avg(params, opts)
                    since_avg = 0
                for lst in m.listeners:
                    lst.iteration_done(m, m.iteration)
        if since_avg:
            params, opts = jit_avg(params, opts)
        # collapse the device axis back
        m.net_params = jax.tree_util.tree_map(lambda a: a[0], params)
        m.opt_states = jax.tree_util.tree_map(lambda a: a[0], opts)
        m.net_state = jax.tree_util.tree_map(lambda a: a[0], state)
        return m
