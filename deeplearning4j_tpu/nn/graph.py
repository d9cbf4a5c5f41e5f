"""ComputationGraph — arbitrary-DAG networks with multi-input/multi-output.

(ref: nn/graph/ComputationGraph.java (2897 LoC): topologicalOrder :122,
init :312, fit(MultiDataSetIterator) :828, feedForward :1212,
calcBackpropGradients :1421).  As with MultiLayerNetwork, the eager
vertex-by-vertex dispatch becomes one traced function over the topological
order, compiled once by XLA; gradients come from jax.value_and_grad over
the summed output-layer losses instead of the reference's hand-scheduled
reverse pass.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.analysis import sanitizer
from deeplearning4j_tpu.monitor import events
from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.nn import params as param_util
from deeplearning4j_tpu.nn.conf.graph_conf import (
    ComputationGraphConfiguration, GraphVertexConf, LayerVertex)
from deeplearning4j_tpu.nn.conf.layers import BaseOutputLayer, LossLayer
from deeplearning4j_tpu.nn.listeners import IterationListener
from deeplearning4j_tpu.ops import bucketing
from deeplearning4j_tpu.ops import dtypes as dtype_ops
from deeplearning4j_tpu.ops import updaters as upd_ops
from deeplearning4j_tpu.nn.multilayer import (
    BIAS_KEYS, WEIGHT_KEYS, _updater_for, dispatch_train_step)


class ComputationGraph:
    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.order = conf.topological_order()
        self.net_params: Optional[Dict[str, dict]] = None
        self.net_state: Optional[Dict[str, dict]] = None
        self.opt_states: Optional[Dict[str, Any]] = None
        self.updaters: Dict[str, upd_ops.Updater] = {}
        self.iteration = 0
        self.epoch = 0
        self.listeners: List[IterationListener] = []
        self._score = float("nan")
        self._key = jax.random.PRNGKey(conf.global_conf.seed)
        self._step_fn = None
        self._output_fn = None
        self._score_fn = None
        self._ext_grad_fn = None
        self._apply_fn = None
        self.last_batch_size = 0
        self.last_etl_time_ms = 0.0
        self.compile_telemetry = bucketing.CompileTelemetry()
        self._steps: Optional[monitor.StepSpans] = None   # fit() owns it
        self._bucket_train_ok: Optional[bool] = None

    # ------------------------------------------------------------------
    def init(self, params: Optional[Dict[str, dict]] = None) -> "ComputationGraph":
        with monitor.span("net/init", phase="default_weights"):
            conf = self.conf
            types: Dict[str, Any] = {}
            if conf.input_types:
                types.update(dict(zip(conf.network_inputs, conf.input_types)))
            key = jax.random.PRNGKey(conf.global_conf.seed)
            ps: Dict[str, dict] = {}
            ss: Dict[str, dict] = {}
            for name in self.order:
                v = conf.vertices[name]
                in_names = conf.vertex_inputs[name]
                in_types = [types.get(i) for i in in_names]
                if any(t is None for t in in_types):
                    # inputs without declared types: best effort via layer n_in
                    if isinstance(v, LayerVertex):
                        lc = v.layer_conf()
                        from deeplearning4j_tpu.nn.conf.layers import FrozenLayerConf
                        if isinstance(lc, FrozenLayerConf):
                            lc = lc._inner()
                        n_in = getattr(lc, "n_in", None)
                        if n_in:
                            from deeplearning4j_tpu.nn.conf.inputs import InputType
                            from deeplearning4j_tpu.nn.conf import layers as L
                            if isinstance(lc, (L.GravesLSTM, L.GravesBidirectionalLSTM,
                                               L.RnnOutputLayer)):
                                in_types = [InputType.recurrent(n_in)]
                            else:
                                in_types = [InputType.feed_forward(n_in)]
                        else:
                            raise ValueError(
                                f"Vertex '{name}': set_input_types() required or "
                                f"explicit n_in on the layer")
                    else:
                        raise ValueError(
                            f"Vertex '{name}': upstream type unknown — call "
                            f"set_input_types() on the GraphBuilder")
                key, sub = jax.random.split(key)
                p, s, out_t = v.initialize(sub, in_types)
                ps[name] = p
                ss[name] = s
                types[name] = out_t
        with monitor.span("net/init", phase="given_weights"):
            self.net_params = params if params is not None else ps
            self.net_state = ss
            def updater(v):
                lc = (v.layer_conf() if isinstance(v, LayerVertex)
                      else v.updater_layer())
                return _updater_for(lc) if lc is not None \
                    else upd_ops.make("sgd")
            self.updaters = {name: updater(conf.vertices[name])
                             for name in self.order}
            self.opt_states = {name: self.updaters[name].init(self.net_params[name])
                               for name in self.order}
        return self

    def _vertex_layer(self, name: str):
        return self.conf.vertices[name].layer_conf()

    def _output_layer_confs(self) -> Dict[str, Any]:
        out = {}
        for name in self.conf.network_outputs:
            v = self.conf.vertices[name]
            if isinstance(v, LayerVertex):
                lc = v.layer_conf()
                if isinstance(lc, (BaseOutputLayer, LossLayer)):
                    out[name] = lc
        return out

    # ------------------------------------------------------------------
    def _forward_all(self, params, state, inputs: Dict[str, Any],
                     masks: Dict[str, Any], train: bool, rng,
                     preout_for: Sequence[str] = ()):
        """Activate every vertex in topological order.  For vertices named
        in `preout_for` (output layers), record PRE-activations instead."""
        from deeplearning4j_tpu.nn.conf.graph_conf import (
            DuplicateToTimeSeriesVertex, LastTimeStepVertex)
        acts: Dict[str, Any] = dict(inputs)
        out_masks: Dict[str, Any] = dict(masks)
        new_states: Dict[str, dict] = {}
        preouts: Dict[str, Any] = {}
        for vi, name in enumerate(self.order):
            v = self.conf.vertices[name]
            in_names = self.conf.vertex_inputs[name]
            ins = [acts[i] for i in in_names]
            ms = [out_masks.get(i) for i in in_names]
            # named-input semantics (ref: rnn/LastTimeStepVertex.java takes
            # its mask from a NAMED network input; DuplicateToTimeSeries
            # takes T from a named reference sequence)
            if isinstance(v, LastTimeStepVertex) and v.mask_input:
                ms = [out_masks.get(v.mask_input)]
            if isinstance(v, DuplicateToTimeSeriesVertex) and v.ts_input \
                    and len(ins) == 1:
                ins = ins + [acts[v.ts_input]]
                ms = ms + [out_masks.get(v.ts_input)]
            r = jax.random.fold_in(rng, vi)
            kind = type(v.layer_conf() if isinstance(v, LayerVertex)
                        else v).__name__
            # the scope names this vertex's operations in a device trace;
            # JAX wraps the backward's in transpose(jvp(...)) of the same
            with jax.named_scope(f"fwd/{kind}/{name}"):
                if name in preout_for:
                    lc = v.layer_conf()
                    x = ins[0]
                    if train:
                        x = lc._maybe_dropout(x, True, r)
                    if lc.scores_from_input:
                        # it scores from x itself (_output_score): no
                        # pre-activations, and no activation either
                        preouts[name] = x
                        new_states[name] = state[name]
                        out_masks[name] = ms[0] if ms else None
                        continue
                    pre = lc.preoutput(
                        lc._maybe_drop_connect(params[name], train, r), x)
                    preouts[name] = pre
                    new_states[name] = state[name]
                    acts[name] = lc._act(pre)
                    out_masks[name] = ms[0] if ms else None
                else:
                    def fwd(p, s, ins_, ms_, v=v, r=r):
                        return v.forward(p, s, ins_, train=train, rng=r,
                                         masks=ms_)
                    if train and \
                            self.conf.global_conf.gradient_checkpointing:
                        # per-vertex remat: recompute this vertex's
                        # forward in the backward pass instead of storing
                        # activations
                        fwd = jax.checkpoint(fwd)
                    y, ns, m = fwd(params[name], state[name], ins, ms)
                    acts[name] = y
                    new_states[name] = ns
                    out_masks[name] = m
        return acts, preouts, new_states, out_masks

    def _reg_penalty(self, params):
        total = 0.0
        for name in self.order:
            v = self.conf.vertices[name]
            if not isinstance(v, LayerVertex):
                continue
            layer = v.layer_conf()
            lp = params[name]
            l1 = layer.l1 or 0.0
            l2 = layer.l2 or 0.0
            for k, val in lp.items():
                if k in WEIGHT_KEYS:
                    if l1:
                        total = total + l1 * jnp.sum(jnp.abs(val))
                    if l2:
                        total = total + 0.5 * l2 * jnp.sum(val * val)
                elif k in BIAS_KEYS:
                    if layer.l1_bias:
                        total = total + layer.l1_bias * jnp.sum(jnp.abs(val))
                    if layer.l2_bias:
                        total = total + 0.5 * layer.l2_bias * jnp.sum(val * val)
        return total

    # ------------------------------------------------------------------
    @staticmethod
    def _resolve_label_mask(preout, lm, out_mask):
        """Label-mask resolution shared by the training step and the
        gradient checker (nn/gradientcheck._check_cg_x64) so the checked
        function IS the trained function.  compute_score owns any
        [..., None] expansion (RnnOutputLayer expands [N,T] itself);
        only an already-expanded [N,T,1] TIME mask is squeezed — a
        per-example [N,1] mask on a 2-D output broadcasts as-is."""
        if lm is None:
            lm = out_mask if (out_mask is not None
                              and out_mask.ndim == preout.ndim - 1) else None
        if lm is not None and preout.ndim == 3 and lm.ndim == 3 \
                and lm.shape[-1] == 1:
            lm = lm[..., 0]
        return lm

    def _output_score(self, name, lc, cparams, states, pre, y, lm, out_mask):
        """Per-example score [N] of output layer ``name`` from what
        ``_forward_all(preout_for=...)`` recorded for it: its
        pre-activations, or, for a layer that scores from its input
        (``scores_from_input``), that input; such a layer reads its
        leaves from ``cparams`` (as the forward pass had them) and may
        leave a new state in ``states[name]``."""
        if lc.scores_from_input:
            if lm is None:
                lm = out_mask
            with jax.named_scope(f"fwd/{type(lc).__name__}/{name}"):
                per_ex, states[name] = lc.score_from_input(
                    cparams[name], states[name], pre, y, lm)
            return per_ex
        return lc.compute_score(
            y, pre, self._resolve_label_mask(pre, lm, out_mask))

    def _assemble_training_score(self, params, preouts, new_states,
                                 out_masks, ys, lmasks, out_confs, out_pos,
                                 compute_params=None):
        """Multi-output training score from forward results: per-output
        loss (masked), minibatch reduction, regularization penalty, and
        layer-surfaced aux losses (MoE load balancing).  Single source of
        truth for the step AND the gradient checker.  ``compute_params``
        are the leaves as the forward pass had them (the policy's cast of
        ``params``; ``params`` where none is given)."""
        g = self.conf.global_conf
        score = 0.0
        for name, lc in out_confs.items():
            oi = out_pos[name]
            per_ex = self._output_score(
                name, lc, params if compute_params is None
                else compute_params, new_states, preouts[name], ys[oi],
                lmasks[oi] if lmasks is not None else None,
                out_masks.get(name))
            score = score + (jnp.mean(per_ex) if g.mini_batch
                             else jnp.sum(per_ex))
        score = score + self._reg_penalty(params)
        for s in new_states.values():
            if isinstance(s, dict) and "moe_aux_loss" in s:
                score = score + s["moe_aux_loss"]
        return score

    def _build_grad_raw(self):
        """The loss-and-gradient half of the graph train step — same
        split and contract as ``MultiLayerNetwork._build_grad_raw``
        (the distributed runtime's all-reduce seam)."""
        g = self.conf.global_conf
        policy = dtype_ops.resolve(g.precision)
        out_confs = self._output_layer_confs()
        if not out_confs:
            raise ValueError("ComputationGraph.fit() needs >=1 output layer "
                             "vertex (OutputLayer/LossLayer)")
        out_names = list(out_confs)
        # labels/masks arrive ordered by conf.network_outputs — index by that
        # position, NOT by position in the (filtered) out_confs dict
        out_pos = {n: self.conf.network_outputs.index(n) for n in out_names}

        def grad_step(params, state, xs, ys, fmasks, lmasks, rng):
            xs_c, fmasks_c = policy.cast_to_compute((xs, fmasks))

            def loss_fn(p):
                pc = policy.cast_to_compute(p)
                inputs = dict(zip(self.conf.network_inputs, xs_c))
                masks = dict(zip(self.conf.network_inputs, fmasks_c)) \
                    if fmasks_c is not None else {}
                acts, preouts, new_states, out_masks = self._forward_all(
                    pc, state, inputs, masks, True, rng, preout_for=out_names)
                preouts = {n: v if out_confs[n].scores_from_input
                           else policy.cast_to_accum(v)
                           for n, v in preouts.items()}
                new_states = policy.cast_to_param(new_states)
                with jax.named_scope("loss"):
                    score = self._assemble_training_score(
                        p, preouts, new_states, out_masks, ys, lmasks,
                        out_confs, out_pos, compute_params=pc)
                    if not g.minimize:
                        score = -score  # maximize: parity with the MLN step
                return score, new_states

            (score, new_states), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            return score, new_states, grads

        return grad_step

    def _build_step_raw(self):
        grad_step = self._build_grad_raw()

        # the name is what a compile event and a trace show (jit_<name>)
        def cg_train_step(params, state, opts, xs, ys, fmasks, lmasks, it,
                          rng):
            score, new_states, grads = grad_step(params, state, xs, ys,
                                                 fmasks, lmasks, rng)
            with jax.named_scope("update"):
                new_params, new_opts = self._apply_updates(params, opts,
                                                           grads, it)
            return new_params, new_states, new_opts, score

        from deeplearning4j_tpu.parallel import fsdp
        return fsdp.partitioned_if_sharded(self, cg_train_step)

    def _apply_updates(self, params, opts, grads, it):
        """Traceable gradient→param update over the vertex dict (per-layer
        normalization, LR schedule, learning rule).  Shared by the fused
        train step and the external-gradients path (apply_gradients)."""
        g = self.conf.global_conf
        plan = getattr(self, "_sharding_plan", None)
        new_params, new_opts = {}, {}
        for name in self.order:
            gi = grads[name]
            if not gi:
                new_params[name] = params[name]
                new_opts[name] = opts[name]
                continue
            v = self.conf.vertices[name]
            layer = v.layer_conf() if isinstance(v, LayerVertex) else None
            if type(layer).__name__ == "FrozenLayerConf":
                # frozen vertex (transfer learning): params must not move
                new_params[name] = params[name]
                new_opts[name] = opts[name]
                continue
            if plan is not None:
                # ZeRO reduce-scatter point — see
                # MultiLayerNetwork._apply_updates
                gi = plan.constrain_grads(gi)
            if layer is not None:
                gi = upd_ops.normalize_gradient(
                    gi, layer.gradient_normalization,
                    layer.gradient_normalization_threshold or 1.0)
                lr_base = (layer.learning_rate
                           if layer.learning_rate is not None
                           else g.learning_rate)
            else:
                lr_base = g.learning_rate
            lr = upd_ops.schedule_lr(
                lr_base, g.lr_policy, it,
                decay_rate=g.lr_policy_decay_rate, steps=g.lr_policy_steps,
                power=g.lr_policy_power, schedule_map=g.learning_rate_schedule)
            upd, new_opt = self.updaters[name].apply(gi, opts[name], lr, it)
            new_params[name] = {k: params[name][k] - upd[k]
                                for k in params[name]}
            new_opts[name] = new_opt
        return new_params, new_opts

    def _build_step(self):
        plan = getattr(self, "_sharding_plan", None)
        if plan is not None:
            from deeplearning4j_tpu.parallel import fsdp
            return fsdp.jit_sharded_step(self._build_step_raw(), plan,
                                         self.net_params, self.opt_states)
        return jax.jit(self._build_step_raw(), donate_argnums=(0, 1, 2))

    # ------------------------------------------------------------------
    def fit(self, data, labels=None, epochs: int = 1,
            fused_steps: int = 1):
        """fit(MultiDataSet | DataSet | iterator | (features, labels))
        (ref: ComputationGraph.fit :828).  ``fused_steps=K>1`` fuses K
        same-shape batches into one compiled lax.scan launch — same
        semantics and caveats as MultiLayerNetwork.fit(fused_steps=K):
        listeners fire once per launch, ragged/mixed groups fall back,
        TBPTT and iterations>1 ignore the flag."""
        if labels is not None:
            data = MultiDataSet([np.asarray(data)], [np.asarray(labels)])
        if isinstance(data, DataSet):
            data = MultiDataSet([data.features], [data.labels],
                                [data.features_mask], [data.labels_mask])
        from deeplearning4j_tpu.nn.listeners import TrainingListener

        def epoch_hook(which):
            for lst in self.listeners:
                if isinstance(lst, TrainingListener):
                    getattr(lst, which)(self)

        fuse = (max(1, int(fused_steps))
                if (self.conf.backprop_type != "truncatedbptt"
                    and self.conf.global_conf.iterations <= 1) else 1)
        if self.net_params is None:
            self.init()
        # warm-validate the fused-kernel helper tier (ops/helpers.py) —
        # same contract as MultiLayerNetwork.fit: a kernel rejection
        # disables its tier before the first step traces
        from deeplearning4j_tpu.ops import helpers as pallas_helpers
        with monitor.span("fit/setup", phase="kernel_self_test"):
            pallas_helpers.ensure_validated()
        self._check_trace_token()
        self._ensure_sharding()
        # elastic cluster training (conf.distributed(...)) — same
        # contract as MultiLayerNetwork.fit: batches route through the
        # coordinator barrier step; inert without a coordinator
        if getattr(self, "_dist_session", None) is None \
                and getattr(self.conf.global_conf, "dist_enabled", False):
            from deeplearning4j_tpu import distributed as dist_mod
            self._dist_session = dist_mod.maybe_session(
                self.conf.global_conf)
        dist_sess = getattr(self, "_dist_session", None)
        if dist_sess is not None:
            dist_sess.attach(self)
            fuse = 1   # the distributed step barriers per batch
        # crash-safe resume (conf.fault_tolerance(resume=True)) — same
        # contract as MultiLayerNetwork.fit: restore the newest valid
        # checkpoint, then skip the already-trained epochs/batches
        from deeplearning4j_tpu.nn import checkpoint as ckpt_mod
        skip_epochs, skip_batches = ckpt_mod.maybe_auto_resume(self)
        if dist_sess is not None:
            skip_epochs, skip_batches = dist_sess.resume_position(
                self, skip_epochs, skip_batches)
        # iterator of DataSet or MultiDataSet — wrapped in the parallel
        # input pipeline so ETL + H2D overlap the jitted step (the MLN
        # fit path's AsyncDataSetIterator, multi-head flavored); one
        # MultiDataSet is a one-batch epoch
        from deeplearning4j_tpu.datasets.iterators import (
            AsyncDataSetIterator, AsyncMultiDataSetIterator,
            reader_retry_from_conf)
        single = isinstance(data, MultiDataSet)
        it = [data] if single else data
        g = self.conf.global_conf
        if (not single and g.pipeline_workers > 0
                and not isinstance(it, AsyncDataSetIterator)
                and getattr(it, "async_supported", lambda: True)()):
            bucket_on = self._bucket_train_enabled()
            gg = self.conf.global_conf
            plan = getattr(self, "_sharding_plan", None)
            min_mult = plan.n_data if plan is not None else 1

            def to_mds(item):
                if isinstance(item, DataSet):
                    item = MultiDataSet(
                        [item.features], [item.labels],
                        [item.features_mask], [item.labels_mask])
                if bucket_on:  # pad on the worker, off the critical path
                    # (lifted to a data-degree multiple under sharding)
                    item = bucketing.bucket_train_multidataset(
                        item, gg, min_multiple=min_mult)[0]
                return item
            it = AsyncMultiDataSetIterator(
                it, queue_size=g.pipeline_prefetch,
                workers=g.pipeline_workers,
                staging_depth=g.pipeline_staging_depth,
                # sharded fit scatters batches across the mesh itself
                device_put=(plan is None), transform=to_mds,
                reader_retry=reader_retry_from_conf(g))
        # MultiDataSetIterator protocol when available; plain
        # __iter__-only iterables (duck-typed inputs) still work
        has_protocol = (callable(getattr(it, "has_next", None))
                        and callable(getattr(it, "next", None)))

        def batches():
            if not has_protocol:
                yield from it
                return
            while True:
                with steps.span("fit/step", phase="has_next"):
                    more = it.has_next()
                if not more:
                    return
                with steps.span("fit/step", phase="data_wait"):
                    item = it.next()
                yield item

        try:
            # DL4J_SANITIZE: debug-nans/rank checks for the duration,
            # retrace-budget assertion on clean exit (analysis/sanitizer);
            # the events.scope correlates every span/event under one fit
            with sanitizer.armed_fit(self), \
                    monitor.profile_if_configured("fit") as profiling, \
                    events.scope(fit_id=events.new_request_id(),
                                 model=type(self).__name__):
                # the phases of fit/step tile the loop from here to the
                # pipeline's close (a profiled fit mirrors them into its
                # trace whatever DL4J_TRACE_ANNOTATIONS says)
                self._steps = steps = monitor.StepSpans(
                    annotate=profiling or None)
                events.emit("fit.start", epochs=epochs,
                            iteration=self.iteration)
                for ep_i in range(epochs):
                    if ep_i < skip_epochs:
                        continue  # resumed past this epoch entirely
                    to_skip = skip_batches if ep_i == skip_epochs else 0
                    self._epoch_start_iter = self.iteration - to_skip
                    with steps.span("fit/step", phase="epoch"):
                        epoch_hook("on_epoch_start")
                        if callable(getattr(it, "reset", None)):
                            it.reset()
                    pending = []
                    for item in batches():
                        if to_skip > 0:
                            # replay-skip the already-trained prefix —
                            # consume to keep stream position, don't fit
                            to_skip -= 1
                            continue
                        if isinstance(item, DataSet):
                            item = MultiDataSet(
                                [item.features], [item.labels],
                                [item.features_mask], [item.labels_mask])
                        if fuse > 1 and not single:
                            pending.append(item)
                            if len(pending) == fuse:
                                self._fit_fused_group(pending)
                                pending = []
                        else:
                            self._fit_batch(item)
                    for item in pending:
                        self._fit_batch(item)
                    with steps.span("fit/step", phase="epoch"):
                        epoch_hook("on_epoch_end")
                        self.epoch += 1
                if isinstance(it, AsyncDataSetIterator):
                    with steps.span("fit/step", phase="epoch"):
                        it.close()
                events.emit("fit.end", iteration=self.iteration,
                            epoch=self.epoch)
        finally:
            if isinstance(it, AsyncDataSetIterator):
                it.close()
            if self._steps is not None:
                self._steps.close()
        return self

    def _build_fused_step(self, k: int):
        """K graph train steps in one lax.scan launch (see
        MultiLayerNetwork._build_fused_step — identical contract over
        the vertex-dict carry)."""
        raw = self._build_step_raw()

        def strip_rnn(state):
            return {n: {kk: v for kk, v in s.items() if kk != "rnn_state"}
                    for n, s in state.items()}

        def cg_fused_steps(params, state, opts, xs, ys, fms, lms, it0, key):
            def body(carry, inp):
                p, s, o = carry
                i, x, y, fm, lm = inp
                p, s, o, score = raw(p, s, o, x, y, fm, lm, it0 + i,
                                     jax.random.fold_in(key, i))
                return (p, strip_rnn(s), o), score
            (params, state, opts), scores = jax.lax.scan(
                body, (params, strip_rnn(state), opts),
                (jnp.arange(k), xs, ys, fms, lms))
            return params, state, opts, scores[-1]

        return jax.jit(cg_fused_steps, donate_argnums=(0, 1, 2))  # dl4j: noqa[DL4J104] one jitted fn per k, cached in _fused_fns[k]

    def _fit_fused_group(self, group):
        if self.net_params is None:
            self.init()
        self._check_trace_token()
        if getattr(self, "_sharding_plan", None) is not None:
            # stacking the multi-head tuple batches for a sharded scan is
            # not supported yet — per-step keeps exact sharded numerics
            for m in group:
                self._fit_batch(m)
            return
        sizes = [m.num_examples() for m in group]
        # ragged groups become bucket-uniform and stay on the fused scan
        # path instead of degrading to per-step (see MultiLayerNetwork)
        with self._steps.span("fit/step", phase="bucket"):
            group = [self._maybe_bucket_train(m)[0] for m in group]

        def shape_sig(m):
            # per-ELEMENT mask presence: MultiDataSet wraps a missing
            # mask as [None], so a top-level None check alone would fuse
            # masked and unmasked batches together (wrong gradients)
            def mask_sig(ms):
                return None if ms is None else tuple(
                    x is None for x in ms)
            return (tuple((f.shape, f.dtype) for f in m.features),
                    tuple((l.shape, l.dtype) for l in m.labels),
                    mask_sig(m.features_masks), mask_sig(m.labels_masks))
        if len({shape_sig(m) for m in group}) != 1:
            for m in group:
                self._fit_batch(m)
            return
        if getattr(self, "_fused_fns", None) is None:
            self._fused_fns = {}
            self._fit_batch(group[0])   # carried-state structure warmup
            group, sizes = group[1:], sizes[1:]
            if not group:
                return
        k = len(group)
        if k not in self._fused_fns:
            self._fused_fns[k] = self._build_fused_step(k)

        def stack_tuple(get, present):
            if not present:
                return None
            n_el = len(get(group[0]))
            return tuple(
                (jnp.stack([jnp.asarray(get(m)[i]) for m in group])
                 if get(group[0])[i] is not None else None)
                for i in range(n_el))

        t_step = time.perf_counter()
        with self._steps.span("fit/step", phase="h2d"):
            batch = (stack_tuple(lambda m: m.features, True),
                     stack_tuple(lambda m: m.labels, True),
                     stack_tuple(lambda m: m.features_masks,
                                 group[0].features_masks is not None),
                     stack_tuple(lambda m: m.labels_masks,
                                 group[0].labels_masks is not None))
        self.last_batch_size = sum(sizes)
        dispatch_train_step(self, self._fused_fns[k], f"fused_step_k{k}",
                            batch, batch, t_step, k=k)

    def _check_trace_token(self):
        """See MultiLayerNetwork._check_trace_token — retrace when the
        ambient sequence-parallel regime or precision policy changes."""
        from deeplearning4j_tpu.parallel import fsdp
        from deeplearning4j_tpu.parallel import sequence as seq_ops
        tok = (seq_ops.cache_token(),
               dtype_ops.resolve(self.conf.global_conf.precision),
               self.conf.global_conf.gradient_checkpointing,
               fsdp.conf_key(self.conf.global_conf),
               getattr(self, "_infer_quant", None))
        if tok != getattr(self, "_trace_token", None):
            self._trace_token = tok
            self._step_fn = self._score_fn = self._output_fn = None
            self._rnn_step_fn = None
            self._ext_grad_fn = self._apply_fn = None
            self._score_ex_fn = None
            self._dist_cache = None
            self._fused_fns = None
            self.compile_telemetry.invalidate()

    def _ensure_sharding(self):
        """Activate/deactivate the conf-declared sharding plan — see
        MultiLayerNetwork._ensure_sharding (same contract over the
        vertex-dict pytrees)."""
        from deeplearning4j_tpu.parallel import fsdp
        plan = (None if self.conf.backprop_type == "truncatedbptt"
                else fsdp.plan_from_conf(self.conf.global_conf))
        if fsdp.plan_key(plan) == fsdp.plan_key(
                getattr(self, "_sharding_plan", None)):
            return
        self._sharding_plan = plan
        self._step_fn = self._score_fn = None
        self._fused_fns = None
        # inference entry points re-jit too: the output path carries the
        # plan's in/out_shardings (sharded serving, ROADMAP 3a)
        self._output_fn = None
        self._rnn_step_fn = None
        if plan is not None and self.net_params is not None:
            fsdp.place_model(plan, self)

    def _replace_on_mesh(self):
        """Re-commit params/updater/state to the active plan's layout
        after a host-side overwrite (set_params / checkpoint restore)."""
        plan = getattr(self, "_sharding_plan", None)
        if plan is not None:
            from deeplearning4j_tpu.parallel import fsdp
            fsdp.place_model(plan, self)

    # ------------------------------------------------------------------
    # Shape bucketing (ops/bucketing.py) — see MultiLayerNetwork
    # ------------------------------------------------------------------
    def _bucket_train_enabled(self) -> bool:
        g = self.conf.global_conf
        if not g.shape_bucketing or self.conf.backprop_type == "truncatedbptt":
            return False
        if self._bucket_train_ok is None:
            self._bucket_train_ok = bucketing.pad_supported(self)
        return self._bucket_train_ok

    def _maybe_bucket_train(self, mds, scale_loss: bool = True):
        if self._bucket_train_enabled():
            return bucketing.bucket_train_multidataset(
                mds, self.conf.global_conf, scale_loss=scale_loss)
        return mds, None

    def _fit_batch(self, mds: MultiDataSet):
        if self.net_params is None:
            self.init()
        if self.conf.backprop_type == "truncatedbptt" \
                and any(f.ndim == 3 for f in mds.features):
            with self._steps.span("fit/step", phase="tbptt"):
                self._fit_tbptt(mds)
            return
        dist_sess = getattr(self, "_dist_session", None)
        if dist_sess is not None:
            # cluster step — see MultiLayerNetwork._fit_batch
            from deeplearning4j_tpu.distributed import worker as dist_worker
            self._steps.close()     # timed by the worker's own spans
            dist_worker.fit_batch(self, mds, dist_sess, is_graph=True)
            self._steps.restart()
            return
        steps = self._steps
        with steps.span("fit/step", phase="dispatch_prep"):
            # is the step function still the one to dispatch?  (the list
            # engine asks once a fit(); here it is a step's cost)
            self._check_trace_token()
            if self._step_fn is None:
                self._step_fn = self._build_step()
        self.last_batch_size = mds.num_examples()
        t_step = time.perf_counter()
        plan = getattr(self, "_sharding_plan", None)
        if plan is not None:
            from deeplearning4j_tpu.parallel import fsdp
            with steps.span("fit/step", phase="bucket"):
                norm = fsdp.normalize_batch(self, mds, plan.n_data,
                                            is_graph=True)
            if norm is None:
                return
            sig_args, n, bucket = norm
            self.last_batch_size = n
            kind = "sharded_step"
            with steps.span("fit/step", phase="shard_h2d"):
                batch = fsdp.shard_put(plan, sig_args)
        else:
            with steps.span("fit/step", phase="bucket"):
                mds, bucket = self._maybe_bucket_train(mds)
            kind = "train_step"
            with steps.span("fit/step", phase="h2d"):
                def put(arrs):
                    return None if arrs is None else tuple(
                        None if a is None else jnp.asarray(a) for a in arrs)
                batch = sig_args = (put(mds.features), put(mds.labels),
                                    put(mds.features_masks),
                                    put(mds.labels_masks))
        dispatch_train_step(self, self._step_fn, kind, sig_args, batch,
                            t_step, bucket=bucket)

    def _strip_rnn_state(self):
        if self.net_state is None:
            return
        self.net_state = {n: {k: v for k, v in s.items() if k != "rnn_state"}
                          for n, s in self.net_state.items()}

    def _fit_tbptt(self, mds: MultiDataSet):
        """Truncated BPTT over time segments with carried RNN state —
        the graph analog of MultiLayerNetwork._fit_tbptt
        (ref: ComputationGraph.doTruncatedBPTT :1476).  Time-major-3D
        features [N, T, C] are segmented along T; the per-vertex
        rnn_state carries across segments inside one batch and is
        cleared between batches."""
        if self.net_params is None:
            self.init()
        self._check_trace_token()
        if self._step_fn is None:
            self._step_fn = self._build_step()
        self.last_batch_size = mds.num_examples()
        T = max(f.shape[1] for f in mds.features if f.ndim == 3)
        L = self.conf.tbptt_fwd_length
        self.rnn_clear_previous_state()

        def seg(arr, sl):
            return arr[:, sl] if (arr is not None and arr.ndim == 3) else arr

        def seg_mask(m, sl):
            # masks are [N, T] (or [N, T, 1]); slice any mask whose time
            # axis matches the full length — 2-D masks included
            # (MultiLayerNetwork._fit_tbptt slices its masks the same way)
            if m is None or m.ndim < 2 or m.shape[1] != T:
                return m
            return m[:, sl]

        for t0 in range(0, T, L):
            sl = slice(t0, min(t0 + L, T))
            xs = tuple(jnp.asarray(seg(f, sl)) for f in mds.features)
            ys = tuple(jnp.asarray(seg(l, sl)) for l in mds.labels)
            fm = (tuple(None if m is None else jnp.asarray(seg_mask(m, sl))
                        for m in mds.features_masks)
                  if mds.features_masks is not None else None)
            lm = (tuple(None if m is None else jnp.asarray(seg_mask(m, sl))
                        for m in mds.labels_masks)
                  if mds.labels_masks is not None else None)
            self._key, sub = jax.random.split(self._key)
            (self.net_params, self.net_state, self.opt_states,
             score) = self._step_fn(
                self.net_params, self.net_state, self.opt_states, xs, ys,
                fm, lm, jnp.asarray(self.iteration, jnp.int32), sub)
            self._score = score
            self.iteration += 1
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration)

    # ------------------------------------------------------------------
    # Stateful RNN inference (ref: ComputationGraph.rnnTimeStep :1569)
    # ------------------------------------------------------------------
    def _rnn_step_raw(self):
        """The pure carried decode step — the seam shared by
        :meth:`rnn_time_step` and the serving decode pool
        (``server/decode.py``): ``(params, base_state, carries, xs, ms)
        -> (outs, new_carries)`` with ``carries`` a dict keyed by the
        recurrent vertices' names.  Explicit carries keep the traced
        structure closed under iteration: one compiled program serves
        every step of an autoregressive stream (see
        MultiLayerNetwork._rnn_step_raw).  The forward traces under
        ``kv_decode_scope``: attention vertices decode incrementally
        against a KV-ring carry leaf instead of re-running their
        window."""
        from deeplearning4j_tpu.parallel import sequence as seq_ops
        policy = dtype_ops.resolve(self.conf.global_conf.precision)

        def rnn_fn(params, state, carries, xs, ms):
            pc, cc, xs_c, ms_c = policy.cast_to_compute(
                (params, carries, xs, ms))
            st = {}
            for n, s in state.items():
                s = {k: v for k, v in s.items() if k != "rnn_state"}
                if n in cc:
                    s["rnn_state"] = cc[n]
                st[n] = s
            ins = dict(zip(self.conf.network_inputs, xs_c))
            masks = ({n: m for n, m in zip(self.conf.network_inputs, ms_c)
                      if m is not None} if ms_c is not None else {})
            with seq_ops.kv_decode_scope():
                acts, _, new_states, _ = self._forward_all(
                    pc, st, ins, masks, False, jax.random.PRNGKey(0))
            outs = tuple(policy.cast_to_param(acts[n])
                         for n in self.conf.network_outputs)
            new_carries = {n: ns["rnn_state"]
                           for n, ns in new_states.items()
                           if isinstance(ns, dict) and "rnn_state" in ns}
            return outs, policy.cast_to_param(new_carries)

        return rnn_fn

    def rnn_carry_template(self, n: int, feature_tails=None,
                           dtype=jnp.float32):
        """Zero-initialized carry dict (vertex name → carry pytree) for
        ``n`` concurrent streams, discovered via ``jax.eval_shape`` over
        the carried step.  ``feature_tails`` is one per-example shape
        tail per network input (``(T, C)``); defaults from the conf's
        declared input types."""
        if self.net_params is None:
            self.init()
        if feature_tails is None:
            if not self.conf.input_types:
                raise ValueError("rnn_carry_template needs explicit "
                                 "feature_tails= (no set_input_types())")
            feature_tails = [(1, it.size) if it.kind == "rnn"
                             else (it.size,)
                             for it in self.conf.input_types]
        xs = tuple(jax.ShapeDtypeStruct(
            (int(n),) + tuple(int(d) for d in t), dtype)
            for t in feature_tails)
        base = {k: {kk: v for kk, v in s.items() if kk != "rnn_state"}
                for k, s in self.net_state.items()}
        _, spec = jax.eval_shape(self._rnn_step_raw(), self.net_params,
                                 base, {}, xs, None)
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), spec)

    def rnn_time_step(self, *inputs):
        """Single/multi-step stateful inference: each call consumes
        [N, T, C] sequences, returns the network outputs, and carries
        every recurrent vertex's hidden state to the next call.

        Every call re-dispatches ONE cached jitted step: the first call
        materializes a zero carry template so the carry structure (and
        therefore the trace) is identical with and without stored state
        — token-by-token sampling pays neither op-by-op dispatch nor a
        second steady-state retrace."""
        if self.net_params is None:
            self.init()
        self._check_trace_token()
        if getattr(self, "_rnn_step_fn", None) is None:
            self._rnn_step_fn = jax.jit(self._rnn_step_raw())
        xs = tuple(jnp.asarray(x) for x in inputs)
        carries = {n: s["rnn_state"] for n, s in self.net_state.items()
                   if "rnn_state" in s}
        if not carries:
            carries = self.rnn_carry_template(
                xs[0].shape[0],
                feature_tails=[tuple(x.shape[1:]) for x in xs],
                dtype=xs[0].dtype)
        self.compile_telemetry.record("rnn_time_step", (xs, carries))
        outs, new_carries = self._rnn_step_fn(
            self.net_params,
            {n: {k: v for k, v in s.items() if k != "rnn_state"}
             for n, s in self.net_state.items()},
            carries, xs, None)
        merged = {}
        for name, old in self.net_state.items():
            s = {k: v for k, v in old.items() if k != "rnn_state"}
            if name in new_carries:
                s["rnn_state"] = new_carries[name]
            merged[name] = s
        self.net_state = merged
        return outs

    def rnn_clear_previous_state(self):
        """(ref: ComputationGraph.rnnClearPreviousState :1608)"""
        self._strip_rnn_state()

    # ------------------------------------------------------------------
    def quantize_inference(self, mode: str = "int8"):
        """Weight-only quantized serving — see
        MultiLayerNetwork.quantize_inference (same tier registry,
        kill switches and lazy re-quantization over the vertex-dict
        param pytree)."""
        from deeplearning4j_tpu.ops import helpers as pallas_helpers
        if mode is None:
            self._infer_quant = None
            self._q_params = None
            self._check_trace_token()
            return self
        if self.net_params is None:
            self.init()
        self._ensure_sharding()
        mode = str(mode).lower()
        if mode not in ("int8", "fp8"):
            raise ValueError(f"unknown inference quantization '{mode}' "
                             "(known: int8, fp8)")
        if getattr(self, "_sharding_plan", None) is not None:
            return self  # sharded serving keeps the dense fsdp layout
        tier = f"{mode}_infer"
        if not (pallas_helpers.precision_enabled(tier, True)
                and pallas_helpers.ensure_precision_validated(tier)):
            self._infer_quant = None
            self._q_params = None
            self._check_trace_token()
            return self
        self._infer_quant = mode
        self._q_params = None
        self._check_trace_token()
        return self

    def _infer_params(self):
        """See MultiLayerNetwork._infer_params."""
        quant = getattr(self, "_infer_quant", None)
        if quant is None:
            return self.net_params
        if getattr(self, "_q_params", None) is None \
                or getattr(self, "_q_iteration", -1) != self.iteration:
            from deeplearning4j_tpu.ops import quantize as qz
            self._q_params, self._q_stats = qz.quantize_params(
                self.net_params, quant)
            self._q_iteration = self.iteration
        return self._q_params

    def output(self, *inputs, train: bool = False):
        """Multi-output inference in topological order
        (ref: ComputationGraph feedForward/outputs)."""
        if self.net_params is None:
            self.init()
        self._check_trace_token()
        self._ensure_sharding()
        if self._output_fn is None:
            policy = dtype_ops.resolve(self.conf.global_conf.precision)
            quant = getattr(self, "_infer_quant", None)

            def out_fn(params, state, xs, ms):
                if quant is not None:
                    # dequant-in-trace: int8/fp8 codes + per-channel
                    # scales expand inside the compiled program
                    from deeplearning4j_tpu.ops import quantize as qz
                    params = qz.dequantize_params(params)
                pc, xs_c, ms_c = policy.cast_to_compute((params, xs, ms))
                ins = dict(zip(self.conf.network_inputs, xs_c))
                masks = ({n: m for n, m in zip(self.conf.network_inputs,
                                               ms_c) if m is not None}
                         if ms_c is not None else {})
                acts, _, _, _ = self._forward_all(pc, state, ins, masks,
                                                  False, jax.random.PRNGKey(0))
                return tuple(policy.cast_to_param(acts[n])
                             for n in self.conf.network_outputs)
            from deeplearning4j_tpu.parallel import fsdp
            out_fn = fsdp.partitioned_if_sharded(self, out_fn)
            out_plan = getattr(self, "_sharding_plan", None)
            if out_plan is not None:
                # sharded serving (ROADMAP 3a): pjit'd output with the
                # plan's in/out shardings — see MultiLayerNetwork.output
                self._output_fn = fsdp.jit_sharded_output(
                    out_fn, out_plan, self.net_params)
            else:
                self._output_fn = jax.jit(out_fn)
        state = {n: {k: v for k, v in s.items() if k != "rnn_state"}
                 for n, s in self.net_state.items()}
        g = self.conf.global_conf
        plan = getattr(self, "_sharding_plan", None)
        masks = unpad = bucket = None
        ms_p = [None] * len(inputs)
        if g.shape_bucketing:
            xs_p, ms_p, pairs, n = [], [], [], None
            for x in inputs:
                xp, mp, n, t, b = bucketing.bucket_inference_features(
                    x, None, g)
                xs_p.append(xp)
                ms_p.append(mp)
                pairs.append((t, b[1]))
            inputs = xs_p
            bucket = (b[0], tuple(tb for _, tb in pairs))
            unpad = (n, pairs)
        if plan is not None:
            # batch rows must divide the mesh's data degree; zero rows
            # are exact at inference and sliced back off below
            from deeplearning4j_tpu.parallel import fsdp
            padded = [fsdp.pad_inference_rows(x, m, plan.n_data)
                      for x, m in zip(inputs, ms_p)]
            if any(nr is not None for _, _, nr in padded):
                n0 = next(nr for _, _, nr in padded if nr is not None)
                inputs = [x for x, _, _ in padded]
                ms_p = [m for _, m, _ in padded]
                if unpad is None:
                    unpad = (n0, [])
        if any(m is not None for m in ms_p):
            # explicit H2D for the masks, like the inputs below — a
            # numpy mask handed to the jitted fn transfers implicitly
            masks = tuple(None if m is None else jnp.asarray(m)
                          for m in ms_p)
        xs = tuple(jnp.asarray(x) for x in inputs)
        self.compile_telemetry.record("output", (xs, masks), bucket=bucket)
        outs = self._output_fn(self._infer_params(), state, xs, masks)
        if unpad is not None:
            n, pairs = unpad
            outs = tuple(self._unpad_graph_output(o, n, pairs)
                         for o in outs)
        return outs

    def warmup_inference(self, feature_dims, max_batch: int = 32,
                         batch_sizes=None, dtype=np.float32) -> dict:
        """ComputationGraph analog of
        ``MultiLayerNetwork.warmup_inference``: pre-compile the jitted
        multi-input ``output`` path for every batch bucket on the
        serving ladder.  ``feature_dims`` is one per-example shape tail
        per network input (a single tail is broadcast to all inputs)."""
        if self.net_params is None:
            self.init()
        dims = list(feature_dims)
        if not dims or not isinstance(dims[0], (tuple, list)):
            dims = [tuple(dims)] * len(self.conf.network_inputs)
        dims = [tuple(int(d) for d in t) for t in dims]
        g = self.conf.global_conf
        ladder = bucketing.warmup_ladder(
            batch_sizes or g.bucket_batch_sizes, max_batch)
        t0 = time.perf_counter()
        for nb in ladder:
            outs = self.output(*[np.zeros((nb,) + t, dtype) for t in dims])
            jax.block_until_ready(outs)
        return {"buckets": ladder,
                "warmup_sec": round(time.perf_counter() - t0, 3)}

    @staticmethod
    def _unpad_graph_output(out, n, time_pairs):
        """Slice one padded graph output back to the real extent: rows
        always; the time axis when it matches a padded input's time
        bucket (multi-input graphs may mix time lengths)."""
        out = out[:n]
        for t, tb in time_pairs:
            if t is not None and tb != t and out.ndim >= 3 \
                    and out.shape[1] == tb:
                return out[:, :t]
        return out

    def feed_forward(self, *inputs, train: bool = False):
        """All vertex activations by name (ref: ComputationGraph.feedForward
        :1143) — the UI's conv-activation capture reads these."""
        if self.net_params is None:
            self.init()
        ins = dict(zip(self.conf.network_inputs,
                       (jnp.asarray(x) for x in inputs)))
        state = {n: {k: v for k, v in s.items() if k != "rnn_state"}
                 for n, s in self.net_state.items()}
        acts, _, _, _ = self._forward_all(self.net_params, state, ins, {},
                                          train, jax.random.PRNGKey(0))
        return acts

    def score(self, data: Optional[Union[DataSet, MultiDataSet]] = None) -> float:
        if data is None:
            return float(self._score)
        if isinstance(data, DataSet):
            data = MultiDataSet([data.features], [data.labels],
                                [data.features_mask], [data.labels_mask])
        self._check_trace_token()
        if self._score_fn is None:
            out_confs = self._output_layer_confs()
            out_pos = {n: self.conf.network_outputs.index(n) for n in out_confs}
            g = self.conf.global_conf
            policy = dtype_ops.resolve(g.precision)

            def score_fn(params, state, xs, ys, fms, lms):
                pc, xs_c, fm_c = policy.cast_to_compute((params, xs, fms))
                inputs = dict(zip(self.conf.network_inputs, xs_c))
                masks = ({n: m for n, m in zip(self.conf.network_inputs,
                                               fm_c) if m is not None}
                         if fm_c is not None else {})
                _, preouts, states, out_masks = self._forward_all(
                    pc, state, inputs, masks, False, jax.random.PRNGKey(0),
                    preout_for=list(out_confs))
                total = 0.0
                for name, lc in out_confs.items():
                    pre = preouts[name] if lc.scores_from_input \
                        else policy.cast_to_accum(preouts[name])
                    per_ex = self._output_score(
                        name, lc, pc, states, pre, ys[out_pos[name]],
                        lms[out_pos[name]] if lms is not None else None,
                        out_masks.get(name))
                    total = total + (jnp.mean(per_ex) if g.mini_batch
                                     else jnp.sum(per_ex))
                return total + self._reg_penalty(params)

            from deeplearning4j_tpu.parallel import fsdp
            score_fn = fsdp.partitioned_if_sharded(self, score_fn)
            self._score_fn = jax.jit(score_fn)
        data, bucket = self._maybe_bucket_train(data)
        xs = tuple(jnp.asarray(f) for f in data.features)
        ys = tuple(jnp.asarray(l) for l in data.labels)

        def mask_tuple(ms):
            if ms is None or all(m is None for m in ms):
                return None
            return tuple(None if m is None else jnp.asarray(m) for m in ms)

        fms = mask_tuple(data.features_masks)
        lms = mask_tuple(data.labels_masks)
        self.compile_telemetry.record("score", (xs, ys, fms, lms),
                                      bucket=bucket)
        return float(self._score_fn(self.net_params, self.net_state,
                                    xs, ys, fms, lms))

    def evaluate(self, iterator_or_dataset, output_idx: int = 0):
        from deeplearning4j_tpu.nn.evaluation import Evaluation
        ev = Evaluation()
        if isinstance(iterator_or_dataset, (DataSet, MultiDataSet)):
            batches = [iterator_or_dataset]
        else:
            iterator_or_dataset.reset()
            batches = list(iterator_or_dataset)
        for ds in batches:
            if isinstance(ds, DataSet):
                feats, labels = [ds.features], [ds.labels]
            else:
                feats, labels = ds.features, ds.labels
            outs = self.output(*feats)
            ev.eval(labels[output_idx], jax.device_get(outs[output_idx]))
        return ev

    # ------------------------------------------------------------------
    def params(self) -> jnp.ndarray:
        """Canonical flat view: vertices in topological order."""
        plist = [self.net_params[n] for n in self.order]
        return param_util.flatten(plist)

    def set_params(self, flat) -> None:
        plist = [self.net_params[n] for n in self.order]
        new = param_util.unflatten(flat, plist)
        self.net_params = {n: new[i] for i, n in enumerate(self.order)}
        self._replace_on_mesh()

    def num_params(self) -> int:
        return param_util.num_params([self.net_params[n] for n in self.order])

    def param_table(self) -> Dict[str, jnp.ndarray]:
        """Named param map keyed ``"<vertexName>_<paramName>"`` (ref:
        Model.paramTable on ComputationGraph)."""
        if self.net_params is None:
            self.init()
        return {f"{n}_{k}": v for n in self.order
                for k, v in self.net_params[n].items()}

    def _split_param_key(self, key: str):
        # vertex names may themselves contain '_' and so may param names
        # (f_W, b_RW) — match the longest vertex-name prefix
        for n in sorted(self.net_params, key=len, reverse=True):
            if key.startswith(n + "_"):
                return n, key[len(n) + 1:]
        raise KeyError(f"no vertex owns param key '{key}'")

    def get_param(self, key: str) -> jnp.ndarray:
        name, k = self._split_param_key(key)
        return self.net_params[name][k]

    def set_param(self, key: str, value) -> None:
        name, k = self._split_param_key(key)
        cur = self.net_params[name][k]
        value = jnp.asarray(value, cur.dtype)
        if value.shape != cur.shape:
            raise ValueError(f"setParam('{key}'): shape {value.shape} != "
                             f"{cur.shape}")
        self.net_params[name] = {**self.net_params[name], k: value}

    def updater_state_flat(self) -> jnp.ndarray:
        leaves = jax.tree_util.tree_leaves(
            [self.opt_states[n] for n in self.order])
        if not leaves:
            return jnp.zeros((0,), jnp.float32)
        # host-side gather for concrete arrays: op-by-op concatenate
        # over the mixed NamedShardings an FSDP model carries
        # miscomputes (see nn/params.flatten)
        if any(isinstance(l, jax.core.Tracer) for l in leaves):
            return jnp.concatenate([jnp.ravel(l) for l in leaves])
        return jnp.asarray(np.concatenate(
            [np.ravel(np.asarray(l)) for l in leaves]))

    def set_updater_state_flat(self, flat) -> None:
        ordered = [self.opt_states[n] for n in self.order]
        leaves, treedef = jax.tree_util.tree_flatten(ordered)
        out, off = [], 0
        flat = jnp.asarray(flat).reshape(-1)
        for l in leaves:
            size = int(np.prod(l.shape))
            out.append(flat[off:off + size].reshape(l.shape).astype(l.dtype))
            off += size
        restored = jax.tree_util.tree_unflatten(treedef, out)
        self.opt_states = {n: restored[i] for i, n in enumerate(self.order)}
        self._replace_on_mesh()

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    # ------------------------------------------------------------------
    def score_examples(self, data, add_regularization_terms: bool = False):
        """Per-example scores without minibatch averaging, summed over all
        output layers (ref: ComputationGraph.scoreExamples — the
        anomaly-detection API; addRegularizationTerms adds the graph's
        l1/l2 penalty to every example)."""
        if self.net_params is None:
            self.init()
        self._check_trace_token()
        if getattr(self, "_score_ex_fn", None) is None:
            g = self.conf.global_conf
            policy = dtype_ops.resolve(g.precision)
            out_confs = self._output_layer_confs()
            out_names = list(out_confs)
            out_pos = {n: self.conf.network_outputs.index(n)
                       for n in out_names}

            def score_ex(params, state, xs, ys, fmasks, lmasks, add_reg):
                pc, xs_c, fm_c = policy.cast_to_compute((params, xs, fmasks))
                inputs = dict(zip(self.conf.network_inputs, xs_c))
                masks = dict(zip(self.conf.network_inputs, fm_c)) \
                    if fm_c is not None else {}
                _, preouts, states, out_masks = self._forward_all(
                    pc, state, inputs, masks, False, jax.random.PRNGKey(0),
                    preout_for=out_names)
                total = 0.0
                for name, lc in out_confs.items():
                    pre = preouts[name] if lc.scores_from_input \
                        else policy.cast_to_accum(preouts[name])
                    total = total + self._output_score(
                        name, lc, pc, states, pre, ys[out_pos[name]],
                        lmasks[out_pos[name]] if lmasks is not None
                        else None, out_masks.get(name))
                return total + jnp.where(add_reg,
                                         self._reg_penalty(params), 0.0)

            self._score_ex_fn = jax.jit(score_ex)
        if isinstance(data, DataSet):
            data = MultiDataSet([data.features], [data.labels],
                                [data.features_mask], [data.labels_mask])
        batches = [data] if isinstance(data, MultiDataSet) else data
        g = self.conf.global_conf
        bucket_ok = (g.shape_bucketing
                     and bucketing.pad_supported(self, require_mean=False))
        out = []
        for mds in batches:
            if isinstance(mds, DataSet):
                mds = MultiDataSet([mds.features], [mds.labels],
                                   [mds.features_mask], [mds.labels_mask])
            n = mds.num_examples()
            bucket = None
            if bucket_ok:
                # per-example scoring: masks stay unscaled, padded rows
                # are sliced back off below
                mds, bucket = bucketing.bucket_train_multidataset(
                    mds, g, scale_loss=False)
            args = (tuple(mds.features), tuple(mds.labels),
                    tuple(mds.features_masks) if mds.features_masks else None,
                    tuple(mds.labels_masks) if mds.labels_masks else None)
            self.compile_telemetry.record("score_examples", args,
                                          bucket=bucket)
            per = np.asarray(self._score_ex_fn(
                self.net_params, self.net_state, *args,
                jnp.asarray(add_regularization_terms)))
            out.append(per[:n] if bucket is not None else per)
        return np.concatenate(out)

    # ------------------------------------------------------------------
    # Layerwise unsupervised pretraining over the DAG
    # ------------------------------------------------------------------
    def pretrain(self, data, epochs: int = 1):
        """Layerwise pretrain of every pretrain-capable layer vertex in
        topological order (ref: ComputationGraph.pretrain :549-561)."""
        for name in self.order:
            v = self.conf.vertices[name]
            if isinstance(v, LayerVertex) and \
                    v.layer_conf().is_pretrain_layer():
                self.pretrain_layer(name, data, epochs=epochs)
        return self

    def pretrain_layer(self, name: str, data, epochs: int = 1):
        """Unsupervised fit of one layer vertex on the activations of its
        upstream subgraph (ref: ComputationGraph.pretrainLayer).  The
        upstream forward runs inside the same jitted step; XLA dead-code-
        eliminates every vertex the target doesn't depend on."""
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        layer = self._vertex_layer(name)
        if not layer.is_pretrain_layer():
            return self
        if self.net_params is None:
            self.init()
        in_name = self.conf.vertex_inputs[name][0]
        updater = self.updaters[name]
        g = self.conf.global_conf

        def pre_step(lp, opt, all_params, state, xs, it, rng):
            ins = dict(zip(self.conf.network_inputs, xs))
            acts, _, _, _ = self._forward_all(
                all_params, state, ins, {}, False, rng)
            feats = jax.lax.stop_gradient(acts[in_name])

            def full_loss(p):
                loss = layer.pretrain_loss(p, feats, rng) + \
                    MultiLayerNetwork._layer_reg_penalty(layer, p)
                return loss if g.minimize else -loss

            loss, grads = jax.value_and_grad(full_loss)(lp)
            grads = upd_ops.normalize_gradient(
                grads, layer.gradient_normalization,
                layer.gradient_normalization_threshold or 1.0)
            lr = upd_ops.schedule_lr(
                layer.learning_rate if layer.learning_rate is not None
                else g.learning_rate,
                g.lr_policy, it,
                decay_rate=g.lr_policy_decay_rate, steps=g.lr_policy_steps,
                power=g.lr_policy_power,
                schedule_map=g.learning_rate_schedule)
            upd, new_opt = updater.apply(grads, opt, lr, it)
            return {k: lp[k] - upd[k] for k in lp}, new_opt, loss

        # no donation: the target vertex's params are passed BOTH as the
        # trained leaf (lp) and inside all_params for the upstream forward
        step_jit = jax.jit(pre_step)
        if isinstance(data, (np.ndarray, jax.Array)):
            data = DataSet(np.asarray(data), np.asarray(data))
        if isinstance(data, DataSet):
            data = MultiDataSet([data.features], [data.labels])
        batches = [data] if isinstance(data, MultiDataSet) else None
        for _ in range(epochs):
            it_ = batches if batches is not None else (data.reset() or data)
            for item in it_:
                if isinstance(item, DataSet):
                    item = MultiDataSet([item.features], [item.labels])
                self._key, sub = jax.random.split(self._key)
                lp, opt, loss = step_jit(
                    self.net_params[name], self.opt_states[name],
                    self.net_params, self.net_state, tuple(item.features),
                    jnp.asarray(self.iteration, jnp.int32), sub)
                self.net_params[name] = lp
                self.opt_states[name] = opt
                self._score = loss
                self.iteration += 1
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration)
        return self

    # ------------------------------------------------------------------
    # External-errors backprop (the RL pattern: caller owns the loss)
    # ------------------------------------------------------------------
    def backprop_gradient(self, inputs, epsilons, masks=None,
                          train: bool = False):
        """Vertex-param gradients + per-input epsilons from EXTERNAL error
        signals dL/d(output_i) — no labels/loss (ref:
        ComputationGraph.calcBackpropGradients external epsilons,
        nn/graph/ComputationGraph.java:1421).  ``inputs`` and ``epsilons``
        are sequences ordered like network_inputs / network_outputs.
        Returns ``(grads, input_epsilons)``.  ``train=False`` (default)
        reproduces output()'s exact forward; ``train=True`` samples fresh
        dropout masks and folds updated carried state (BN running stats)
        back into the network (see MultiLayerNetwork.backprop_gradient)."""
        if self.net_params is None:
            self.init()
        self._check_trace_token()
        if self._ext_grad_fn is None:
            self._ext_grad_fn = {}
        if train not in self._ext_grad_fn:
            policy = dtype_ops.resolve(self.conf.global_conf.precision)

            def ext_grad(params, state, xs, eps, ms, rng, _train=train):
                def fwd(p, xs_):
                    # same precision-policy cast as the fused step /
                    # output(): under bf16 the VJP differentiates the
                    # forward the caller actually saw, and grads come
                    # back in the f32 master-param dtype
                    pc = policy.cast_to_compute(p)
                    xs_c, ms_c = policy.cast_to_compute((xs_, ms))
                    ins = dict(zip(self.conf.network_inputs, xs_c))
                    mdict = dict(zip(self.conf.network_inputs, ms_c)) \
                        if ms_c is not None else {}
                    acts, _, ns, _ = self._forward_all(
                        pc, state, ins, mdict, _train, rng)
                    return tuple(acts[n]
                                 for n in self.conf.network_outputs), ns
                outs, vjp, ns = jax.vjp(fwd, params, xs, has_aux=True)
                cot = tuple(e.astype(o.dtype) for e, o in zip(eps, outs))
                g, dxs = vjp(cot)
                return g, dxs, policy.cast_to_param(ns)
            self._ext_grad_fn[train] = jax.jit(ext_grad)
        if train:
            self._key, sub = jax.random.split(self._key)
        else:
            sub = jax.random.PRNGKey(0)
        xs = tuple(jnp.asarray(x) for x in inputs)
        eps = tuple(jnp.asarray(e) for e in epsilons)
        grads, dxs, new_states = self._ext_grad_fn[train](
            self.net_params, self.net_state, xs, eps, masks, sub)
        if train:
            self.net_state = new_states
            self._strip_rnn_state()
        return grads, dxs

    def apply_gradients(self, grads):
        """Apply externally computed vertex gradients through the
        configured updaters — one jitted step (see
        MultiLayerNetwork.apply_gradients: l1/l2 regularization gradients
        are added here and ``minimize=False`` negates, matching fit())."""
        if self.net_params is None:
            self.init()
        self._check_trace_token()
        if self._apply_fn is None:
            g_conf = self.conf.global_conf

            def apply(p, o, gr, it):
                reg = jax.grad(
                    lambda p_: jnp.asarray(self._reg_penalty(p_),
                                           jnp.float32))(p)
                gr = jax.tree_util.tree_map(jnp.add, gr, reg)
                if not g_conf.minimize:
                    gr = jax.tree_util.tree_map(jnp.negative, gr)
                return self._apply_updates(p, o, gr, it)

            self._apply_fn = jax.jit(apply, donate_argnums=(0, 1))
        self.net_params, self.opt_states = self._apply_fn(
            self.net_params, self.opt_states, grads,
            jnp.asarray(self.iteration, jnp.int32))
        self.iteration += 1
        return self

    # ------------------------------------------------------------------
    def summary(self) -> str:
        """Printable vertex table in topological order: name, vertex type,
        inputs, param count (ref: ComputationGraph.summary)."""
        if self.net_params is None:
            self.init()
        rows = [("VertexName", "VertexType", "Inputs", "ParamCount")]
        total = 0
        for name in self.order:
            v = self.conf.vertices[name]
            lp = self.net_params[name]
            n = sum(int(np.prod(a.shape)) for a in lp.values()) if lp else 0
            total += n
            vtype = (type(v.layer_conf()).__name__
                     if isinstance(v, LayerVertex) else type(v).__name__)
            rows.append((name, vtype,
                         ",".join(self.conf.vertex_inputs[name]) or "-",
                         f"{n:,}"))
        from deeplearning4j_tpu.nn.multilayer import render_table
        return render_table(rows, [
            f"Total parameters: {total:,}",
            f"Inputs: {', '.join(self.conf.network_inputs)}",
            f"Outputs: {', '.join(self.conf.network_outputs)}"])

    def clone(self) -> "ComputationGraph":
        import copy
        net = ComputationGraph(copy.deepcopy(self.conf))
        if self.net_params is not None:
            copy_tree = lambda t: jax.tree_util.tree_map(  # noqa: E731
                lambda a: jnp.array(a, copy=True), t)
            net.init()
            net.net_params = copy_tree(self.net_params)
            net.net_state = copy_tree(self.net_state)
            net.opt_states = copy_tree(self.opt_states)
        net.iteration = self.iteration
        return net
