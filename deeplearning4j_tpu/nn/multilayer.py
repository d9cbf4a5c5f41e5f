"""MultiLayerNetwork — the sequential-network engine.

The reference's MultiLayerNetwork (ref: nn/multilayer/MultiLayerNetwork.java,
2747 LoC) runs an eager per-op training loop: feedForwardToLayer →
backprop → updater → params-=gradient, dispatching every op through nd4j
(call stack SURVEY.md §3.1).  Here the ENTIRE update step — forward, loss,
backward (jax.grad), gradient normalization, learning rule, param update —
is traced once and compiled into a single XLA program with donated
buffers, which is precisely the north star's "trace a full update step
into one cached XLA computation".

Public surface parity: init(), fit(iterator|DataSet|(x,y)),
output(), predict(), score(), params()/set_params() (flat row-vector
view parity), rnn_time_step(), tbptt via conf.backprop_type, listeners.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.analysis import sanitizer
from deeplearning4j_tpu.monitor import events
from deeplearning4j_tpu.nn import params as param_util
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    BaseOutputLayer, Layer, LossLayer, MixtureOfExpertsLayer)
from deeplearning4j_tpu.nn.conf.network import MultiLayerConfiguration
from deeplearning4j_tpu.nn.listeners import IterationListener, TrainingListener
from deeplearning4j_tpu.ops import bucketing
from deeplearning4j_tpu.ops import dtypes as dtype_ops
from deeplearning4j_tpu.ops import updaters as upd_ops

WEIGHT_KEYS = {"W", "RW", "f_W", "f_RW", "b_W", "b_RW"}
BIAS_KEYS = {"b", "f_b", "b_b"}


def render_table(rows: Sequence[Tuple[str, ...]], footer: Sequence[str] = ()):
    """Fixed-width text table: rows[0] is the header; footer lines follow
    a rule.  Shared by MultiLayerNetwork.summary and
    ComputationGraph.summary."""
    ncols = len(rows[0])
    widths = [max(len(r[c]) for r in rows) for c in range(ncols)]
    lines = ["  ".join(r[c].ljust(widths[c]) for c in range(ncols))
             for r in rows]
    sep = "-" * len(lines[0])
    lines.insert(1, sep)
    lines.append(sep)
    lines.extend(footer)
    return "\n".join(lines)


def _updater_for(layer: Layer) -> upd_ops.Updater:
    name = (layer.updater or "sgd").lower()
    hyper = {}
    if name == "nesterovs":
        hyper["momentum"] = layer.momentum if layer.momentum is not None else 0.9
    elif name == "adadelta":
        hyper["rho"] = layer.rho if layer.rho is not None else 0.95
        if layer.epsilon is not None:
            hyper["epsilon"] = layer.epsilon
    elif name == "rmsprop":
        hyper["rmsdecay"] = layer.rms_decay if layer.rms_decay is not None else 0.95
        if layer.epsilon is not None:
            hyper["epsilon"] = layer.epsilon
    elif name in ("adam", "adamax"):
        hyper["beta1"] = layer.adam_mean_decay if layer.adam_mean_decay is not None else 0.9
        hyper["beta2"] = layer.adam_var_decay if layer.adam_var_decay is not None else 0.999
        if layer.epsilon is not None:
            hyper["epsilon"] = layer.epsilon
    elif name == "adagrad" and layer.epsilon is not None:
        hyper["epsilon"] = layer.epsilon
    return upd_ops.make(name, **hyper)


def _expert_layers(net) -> dict:
    """{index or vertex name: ids of the experts held} of the net's
    expert layers that route without capacity (they leave their
    per-expert assignment counts in state)."""
    if hasattr(net, "order"):
        from deeplearning4j_tpu.nn.conf.graph_conf import LayerVertex
        confs = {n: v.layer_conf() for n, v in net.conf.vertices.items()
                 if isinstance(v, LayerVertex)}
    else:
        confs = dict(enumerate(net.layers))
    return {k: list(l._held()) for k, l in confs.items()
            if isinstance(l, MixtureOfExpertsLayer) and l.top_k is not None}


def publish_expert_load(net) -> None:
    """The step's expert load, out of the state the step returned and
    into counters: ``dl4j_moe_assignments_total{vertex, held}`` (token x
    expert assignments, by whether this net holds the expert) and
    ``dl4j_moe_expert_load_max_over_mean{vertex}`` (the fullest expert's
    assignments over the mean over all experts, this step) and
    ``dl4j_moe_row_segments_total{vertex, outcome}`` (the segments of its
    sorted rows the layer ran and skipped, as the device counted them in
    "moe_row_segments", and of those it ran the ones behind the first,
    whose hidden rows the backward pass computed again: run - 1).  The state
    holds one step's counts, so a dispatch of ``fused_steps=k`` publishes
    its last step's and the counter reads a k-th of the routing.  A net
    without such a layer publishes nothing and pays one attribute
    read."""
    held = getattr(net, "_expert_layers", None)
    if held is None:
        held = net._expert_layers = _expert_layers(net)
    if not held:
        return
    reg = monitor.get_registry()
    total = reg.counter(
        "dl4j_moe_assignments_total",
        "token x expert assignments routed in the last step of each "
        "dispatch, by whether this net holds the expert",
        labels=("vertex", "held"))
    skew = reg.gauge(
        "dl4j_moe_expert_load_max_over_mean",
        "fullest expert's assignments over the mean expert's, last step",
        labels=("vertex",))
    segments = reg.counter(
        "dl4j_moe_row_segments_total",
        "segments of an expert layer's sorted rows, by whether the device "
        "ran or skipped them (recomputed: those it ran behind the first, "
        "which keep nothing for the backward pass), last step of each "
        "dispatch",
        labels=("vertex", "outcome"))
    counts = jax.device_get({k: (net.net_state[k]["moe_expert_counts"],
                                 net.net_state[k]["moe_row_segments"])
                             for k in held})
    for k, (c, (run, skipped)) in counts.items():
        segments.labels(vertex=str(k), outcome="run").inc(int(run))
        segments.labels(vertex=str(k), outcome="skipped").inc(int(skipped))
        segments.labels(vertex=str(k), outcome="recomputed").inc(int(run) - 1)
        here = int(c[held[k]].sum())
        total.labels(vertex=str(k), held="1").inc(here)
        total.labels(vertex=str(k), held="0").inc(int(c.sum()) - here)
        if c.sum():
            skew.labels(vertex=str(k)).set(float(c.max() / c.mean()))


def _loop_vertices(net) -> Tuple[list, list]:
    """(the graph's looped stacks, the heads that score their exits),
    by vertex name."""
    if not hasattr(net, "order"):
        return [], []
    from deeplearning4j_tpu.nn.conf.graph_conf import LayerVertex, LoopVertex
    from deeplearning4j_tpu.nn.conf.layers import LoopExitOutputLayer
    vs = net.conf.vertices
    return ([n for n, v in vs.items() if isinstance(v, LoopVertex)],
            [n for n, v in vs.items() if isinstance(v, LayerVertex)
             and isinstance(v.layer_conf(), LoopExitOutputLayer)])


def publish_loop_exits(net) -> None:
    """A looped stack's step, out of the state the step returned and
    into ``dl4j_loop_passes_total{vertex}`` (passes run, last step of
    each dispatch) and, of the head that scores its exits, the gauges
    ``dl4j_loop_exit_mass{vertex, pass}`` (the exit distribution's mean
    over tokens) and ``dl4j_loop_exit_loss{vertex, pass}`` (each pass's
    mean cross-entropy): whether later passes still lower the loss, and
    where the gate puts its mass.  A net without a loop publishes
    nothing and pays one attribute read."""
    found = getattr(net, "_loops", None)
    if found is None:
        found = net._loops = _loop_vertices(net)
    loops, heads = found
    if not loops and not heads:
        return
    reg = monitor.get_registry()
    passes = reg.counter(
        "dl4j_loop_passes_total",
        "passes a looped stack ran, last step of each dispatch",
        labels=("vertex",))
    mass = reg.gauge(
        "dl4j_loop_exit_mass",
        "mean over tokens of the exit distribution, by pass, last step",
        labels=("vertex", "pass"))
    loss = reg.gauge(
        "dl4j_loop_exit_loss",
        "mean over tokens of each pass's cross-entropy, last step",
        labels=("vertex", "pass"))
    got = jax.device_get((
        {n: net.net_state[n]["loop_passes"] for n in loops},
        {n: (net.net_state[n]["loop_exit_mass"],
             net.net_state[n]["loop_exit_loss"]) for n in heads}))
    for n, r in got[0].items():
        passes.labels(vertex=n).inc(int(r))
    for n, (p, ce) in got[1].items():
        for r in range(len(p)):
            mass.labels(**{"vertex": n, "pass": str(r + 1)}).set(float(p[r]))
            loss.labels(**{"vertex": n, "pass": str(r + 1)}).set(float(ce[r]))


def _diffusion_trained(net) -> bool:
    """Does the graph hold an attention layer under the block-diffusion
    mask rule (its batches are ``datasets/diffusion.py``'s)?"""
    if not hasattr(net, "order"):
        return False
    from deeplearning4j_tpu.nn.conf.graph_conf import LayerVertex
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
    from deeplearning4j_tpu.ops import mask_rules
    return any(isinstance(v, LayerVertex)
               and isinstance(v.layer_conf(), SelfAttentionLayer)
               and isinstance(mask_rules.resolve(v.layer_conf().causal),
                              mask_rules.BlockDiffusion)
               for v in net.conf.vertices.values())


def publish_diffusion(net, batch) -> None:
    """A block-diffusion step's noise, out of the weights the batch came
    with (its labels mask: 1 / t on a masked token, 0 on a clean one)
    and into ``dl4j_diffusion_tokens_total{kind=clean|masked}`` and
    ``dl4j_diffusion_loss_weight_sum``: the masked share should sit at
    the schedule's mean t, and the weights' sum near the tokens' count.
    A fused group publishes its last batch's.  Any other net publishes
    nothing and pays one attribute read."""
    on = getattr(net, "_diffusion", None)
    if on is None:
        on = net._diffusion = _diffusion_trained(net)
    if not on or len(batch) < 4 or not batch[3] or batch[3][0] is None:
        return
    w = jax.device_get(batch[3][0])
    if w.ndim == 3:         # a fused group's stacked batches
        w = w[-1]
    reg = monitor.get_registry()
    tokens = reg.counter(
        "dl4j_diffusion_tokens_total",
        "tokens of the noised copy by whether the noise masked them, "
        "last batch of each dispatch", labels=("kind",))
    masked = int(np.count_nonzero(w))
    tokens.labels(kind="masked").inc(masked)
    tokens.labels(kind="clean").inc(int(w.size) - masked)
    reg.counter(
        "dl4j_diffusion_loss_weight_sum",
        "sum of the loss weights (1 / t on masked tokens), last batch of "
        "each dispatch").inc(float(w.sum()))


def _attention_layers(net) -> dict:
    """{index or vertex name: the layer} of the net's attention
    layers."""
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
    if hasattr(net, "order"):
        from deeplearning4j_tpu.nn.conf.graph_conf import LayerVertex
        confs = {n: v.layer_conf() for n, v in net.conf.vertices.items()
                 if isinstance(v, LayerVertex)}
    else:
        confs = dict(enumerate(net.layers))
    return {k: l for k, l in confs.items()
            if isinstance(l, SelfAttentionLayer)}


def publish_attention_tiles(net, batch) -> None:
    """``dl4j_attention_tiles{vertex, outcome}``: of the tiles a head of
    each attention layer's flash core has over the batch's time steps,
    those the kernels visit whole (``visited``), with the mask rule's
    comparison inside (``boundary``) and not at all (``skipped``), as the
    layer counts them from the plan its kernels run
    (``SelfAttentionLayer.core_tiles``).  Static per shape: set when the
    time length changes, for a single-device step (a partitioned one
    runs the dense core).  A net without such a layer publishes nothing
    and pays one attribute read."""
    layers = getattr(net, "_attention_layers", None)
    if layers is None:
        layers = net._attention_layers = _attention_layers(net)
    if not layers or getattr(net, "_sharding_plan", None) is not None:
        return
    x = batch[0][0] if isinstance(batch[0], (list, tuple)) else batch[0]
    # [.., T] token ids or [.., T, F] features (a fused group leads with k)
    T = x.shape[-1] if jnp.issubdtype(x.dtype, jnp.integer) else x.shape[-2]
    if getattr(net, "_attention_tiles_T", None) == T:
        return
    net._attention_tiles_T = T
    g = monitor.get_registry().gauge(
        "dl4j_attention_tiles",
        "tiles a head's flash attention core visits whole, visits with "
        "the mask's comparison inside, and skips; set when the time "
        "length changes", labels=("vertex", "outcome"))
    for k, layer in layers.items():
        for outcome, n in (layer.core_tiles(int(T)) or {}).items():
            g.labels(vertex=str(k), outcome=outcome).set(n)


def dispatch_train_step(net, step_fn, kind, sig_args, batch, t_step,
                        bucket=None, k=1, repeats=1):
    """Launch ``step_fn`` on a staged ``batch`` and account for it: the
    one tail of every step path of both engines.  ``kind``/``sig_args``/
    ``bucket`` are what ``CompileTelemetry`` keys the signature on, ``k``
    the iterations one launch advances (a fused group), ``repeats`` the
    launches on this batch (``conf.iterations``)."""
    steps = net._steps
    fresh = None
    for _ in range(repeats):
        with steps.span("fit/step", phase="dispatch_prep"):
            if fresh is None:
                fresh = net.compile_telemetry.record(kind, sig_args,
                                                     bucket=bucket)
            net._key, sub = jax.random.split(net._key)
            # the iteration scalar moves H2D here, OUTSIDE the guarded
            # dispatch — inside it every transfer is a bug
            it_arr = jnp.asarray(net.iteration, jnp.int32)
        with steps.span("fit/step", phase="jit_call",
                        iteration=net.iteration), \
                sanitizer.guard_step(compiling=fresh):
            (net.net_params, net.net_state, net.opt_states,
             score) = step_fn(net.net_params, net.net_state,
                              net.opt_states, *batch, it_arr, sub)
        with steps.span("fit/step", phase="block_until_ready"):
            jax.block_until_ready(score)
        # the tail's fetches under their own names: the score's
        # transfer, then the host's own work, then the publishers'
        # fetches of what the layers left in state
        with steps.span("fit/step", phase="score_fetch"):
            score_f = float(jax.device_get(score))
        with steps.span("fit/step", phase="bookkeeping"):
            net._strip_rnn_state()
            net._score = score
            net.iteration += k
            monitor.record_fit_step(net.last_batch_size,
                                    time.perf_counter() - t_step, score_f)
        with steps.span("fit/step", phase="publish"):
            publish_expert_load(net)
            publish_loop_exits(net)
            publish_diffusion(net, batch)
            publish_attention_tiles(net, batch)
        with steps.span("fit/step", phase="listeners"):
            for lst in net.listeners:
                lst.iteration_done(net, net.iteration)
        steps.step_done(net.iteration, compiling=fresh, k=k)
        t_step = time.perf_counter()
        fresh = False


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers: List[Layer] = conf.layers
        self.net_params: Optional[List[dict]] = None
        self.net_state: Optional[List[dict]] = None
        self.opt_states: Optional[List[Any]] = None
        self.updaters = [_updater_for(l) for l in self.layers]
        self.iteration = 0
        self.epoch = 0
        self.listeners: List[IterationListener] = []
        self._score: float = float("nan")
        self._key = jax.random.PRNGKey(conf.global_conf.seed)
        self._step_fn = None
        self._score_fn = None
        self._output_fn = None
        self._ext_grad_fn = None
        self._apply_fn = None
        self.last_batch_size = 0
        self.last_etl_time_ms = 0.0
        self.compile_telemetry = bucketing.CompileTelemetry()
        self._steps: Optional[monitor.StepSpans] = None   # fit() owns it
        self._bucket_train_ok: Optional[bool] = None
        self.frozen: List[bool] = [type(l).__name__ == "FrozenLayerConf"
                                   for l in self.layers]

    # ------------------------------------------------------------------
    # Initialization
    # ------------------------------------------------------------------
    def init(self, params: Optional[List[dict]] = None) -> "MultiLayerNetwork":
        """Build param/state pytrees (ref: MultiLayerNetwork.init :411)."""
        with monitor.span("net/init", phase="default_weights"):
            cur = self._input_type_chain_start()
            key = jax.random.PRNGKey(self.conf.global_conf.seed)
            ps, ss = [], []
            for i, layer in enumerate(self.layers):
                if i in self.conf.preprocessors:
                    cur = self.conf.preprocessors[i].output_type(cur)
                key, sub = jax.random.split(key)
                p, s, cur = layer.initialize(sub, cur)
                ps.append(p)
                ss.append(s)
        with monitor.span("net/init", phase="given_weights"):
            self.net_params = params if params is not None else ps
            self.net_state = ss
            self.opt_states = [self.updaters[i].init(self.net_params[i])
                               for i in range(len(self.layers))]
        return self

    def _input_type_chain_start(self) -> InputType:
        if self.conf.input_type is not None:
            return self.conf.input_type
        from deeplearning4j_tpu.nn.conf import layers as L
        first = self.layers[0]
        if isinstance(first, L.FrozenLayerConf):
            first = first._inner()
        n_in = getattr(first, "n_in", None)
        if n_in:
            if isinstance(first, (L.GravesLSTM, L.GravesBidirectionalLSTM)):
                return InputType.recurrent(n_in)
            return InputType.feed_forward(n_in)
        raise ValueError("Network needs conf.input_type or an explicit n_in on layer 0")

    # ------------------------------------------------------------------
    # Forward (pure, traceable)
    # ------------------------------------------------------------------
    def _layer_step(self, layer, i, train: bool, rng):
        """One layer's forward as a pure fn of (params, state, x, mask),
        wrapped in jax.checkpoint when gradient_checkpointing is on and
        we're training — activations are then recomputed during the
        backward pass instead of living in HBM (the TPU remat recipe)."""
        def fwd(p, s, x, mask):
            return layer.forward(p, s, x, train=train,
                                 rng=jax.random.fold_in(rng, i), mask=mask)
        if train and self.conf.global_conf.gradient_checkpointing:
            return jax.checkpoint(fwd)
        return fwd

    def _forward_core(self, params, state, x, mask, train: bool, rng,
                      stateful_rnn: bool, collect_acts: bool = False,
                      stop: Optional[int] = None):
        """THE per-layer forward loop (preprocessor hook, rnn-state
        gating, per-layer rng fold) — single source for _forward,
        _forward_to_preout, feed_forward and
        rnn_activate_using_stored_state so the loop contract cannot
        drift between them.  ``stop`` runs only layers[:stop]."""
        acts = []
        new_states = []
        layers = self.layers if stop is None else self.layers[:stop]
        for i, layer in enumerate(layers):
            s = state[i]
            if not stateful_rnn and "rnn_state" in s:
                s = {k: v for k, v in s.items() if k != "rnn_state"}
            # the scope names this layer's operations in a device trace;
            # JAX wraps the backward's in transpose(jvp(...)) of the same
            with jax.named_scope(f"fwd/{type(layer).__name__}/{i}"):
                if i in self.conf.preprocessors:
                    x, mask = self.conf.preprocessors[i](x, mask)
                x, ns, mask = self._layer_step(layer, i, train, rng)(
                    params[i], s, x, mask)
            new_states.append(ns)
            if collect_acts:
                acts.append(x)
        return x, new_states, mask, acts

    def _forward(self, params, state, x, mask, train: bool, rng,
                 stateful_rnn: bool = False):
        """Full-stack activations.  Returns (out, new_states, out_mask)."""
        out, new_states, mask, _ = self._forward_core(
            params, state, x, mask, train, rng, stateful_rnn)
        return out, new_states, mask

    def _forward_to_preout(self, params, state, x, mask, train: bool, rng,
                           stateful_rnn: bool = False):
        """Forward to the output layer's PRE-activation (stable fused loss)."""
        n = len(self.layers)
        x, new_states, mask, _ = self._forward_core(
            params, state, x, mask, train, rng, stateful_rnn, stop=n - 1)
        last = self.layers[-1]
        with jax.named_scope(f"fwd/{type(last).__name__}/{n - 1}"):
            if (n - 1) in self.conf.preprocessors:
                x, mask = self.conf.preprocessors[n - 1](x, mask)
            if train:
                x = last._maybe_dropout(x, True,
                                        jax.random.fold_in(rng, n - 1))
            preout = last.preoutput(
                last._maybe_drop_connect(params[-1], train,
                                         jax.random.fold_in(rng, n - 1)), x)
        new_states.append(state[-1])
        return preout, new_states, mask, x

    def _reg_penalty(self, params):
        total = 0.0
        for layer, lp in zip(self.layers, params):
            total = total + self._layer_reg_penalty(layer, lp)
        return total

    @staticmethod
    def _layer_reg_penalty(layer, lp):
        total = 0.0
        l1 = layer.l1 or 0.0
        l2 = layer.l2 or 0.0
        l1b = layer.l1_bias or 0.0
        l2b = layer.l2_bias or 0.0
        for k, v in lp.items():
            if k in BIAS_KEYS:
                if l1b:
                    total = total + l1b * jnp.sum(jnp.abs(v))
                if l2b:
                    total = total + 0.5 * l2b * jnp.sum(v * v)
            elif k in WEIGHT_KEYS:
                if l1:
                    total = total + l1 * jnp.sum(jnp.abs(v))
                if l2:
                    total = total + 0.5 * l2 * jnp.sum(v * v)
        return total

    def _check_trace_token(self):
        """Invalidate cached jitted functions when ambient trace-relevant
        state changed: the sequence-parallel regime
        (parallel/sequence.sequence_mesh — shard_map collectives are baked
        into the traced program) or the mixed-precision policy
        (ops/dtypes.set_default_policy — compute dtypes are baked in too)."""
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
            self._ext_grad_fn = self._apply_fn = None
            self._score_ex_fn = None
            self._fused_fns = None
            self._rnn_step_fn = None
            self._dist_cache = None
            self.compile_telemetry.invalidate()

    def _ensure_sharding(self):
        """Activate (or deactivate) the conf-declared sharding plan
        (conf.sharding(...), parallel/fsdp.py): resolve the mesh, place
        params/updater state with their NamedShardings and invalidate
        the cached step so it re-jits with in/out_shardings.  A no-op —
        replica-style training, byte-identical numerics — when sharding
        is off, only one device is visible, or the net trains TBPTT."""
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
        after a host-side overwrite (set_params / checkpoint restore) —
        the host-side reshard that makes checkpoints mesh-tolerant."""
        plan = getattr(self, "_sharding_plan", None)
        if plan is not None:
            from deeplearning4j_tpu.parallel import fsdp
            fsdp.place_model(plan, self)

    # ------------------------------------------------------------------
    # Shape bucketing (ops/bucketing.py)
    # ------------------------------------------------------------------
    def _bucket_train_enabled(self) -> bool:
        """Bucketing for loss-bearing paths (fit/score): needs the conf
        knob AND the exact pad-and-mask preconditions (mask-linear
        losses, mean reduction, no batch-coupled aux losses).  TBPTT
        segments its own time axis — excluded."""
        g = self.conf.global_conf
        if not g.shape_bucketing or self.conf.backprop_type == "truncatedbptt":
            return False
        if self._bucket_train_ok is None:
            self._bucket_train_ok = bucketing.pad_supported(self)
        return self._bucket_train_ok

    def _maybe_bucket_train(self, ds):
        """(ds, bucket) — ds padded up to its bucket when enabled."""
        if self._bucket_train_enabled():
            return bucketing.bucket_train_dataset(ds, self.conf.global_conf)
        return ds, None

    # ------------------------------------------------------------------
    # The jitted train step — ONE XLA computation per step
    # ------------------------------------------------------------------
    def _build_step(self):
        plan = getattr(self, "_sharding_plan", None)
        if plan is not None:
            from deeplearning4j_tpu.parallel import fsdp
            return fsdp.jit_sharded_step(self._build_step_raw(), plan,
                                         self.net_params, self.opt_states)
        return jax.jit(self._build_step_raw(), donate_argnums=(0, 1, 2))

    def _build_grad_raw(self):
        """The loss-and-gradient HALF of the train step — ``(params,
        state, x, y, fmask, lmask, rng) → (score, new_states, grads)``.
        The fused step composes it with ``_apply_updates`` in one trace
        (identical jaxpr to the pre-split single-closure step); the
        distributed runtime jits it alone so the cluster all-reduce sits
        between gradient and update (distributed/worker.fit_batch).

        Mixed precision (the reference trains f32; the TPU-native fast path
        is bf16 on the MXU): the policy from conf.precision / ops.dtypes
        casts params+inputs to the compute dtype INSIDE the loss closure, so
        jax.grad differentiates through the cast and yields float32 master
        gradients; updater state and the loss/softmax accumulation stay
        float32, and carried state (BN stats, RNN carries) is upcast back."""
        g = self.conf.global_conf
        policy = dtype_ops.resolve(g.precision)
        out_layer = self.layers[-1]
        if not isinstance(out_layer, (BaseOutputLayer, LossLayer)):
            raise ValueError("Last layer must be an output/loss layer to fit()")

        def grad_step(params, state, x, y, fmask, lmask, rng):
            xc, fmc = policy.cast_to_compute((x, fmask))

            def loss_fn(p):
                pc = policy.cast_to_compute(p)
                preout, new_states, m, feats = self._forward_to_preout(
                    pc, state, xc, fmc, True, rng,
                    stateful_rnn=(self.conf.backprop_type == "truncatedbptt"))
                preout = policy.cast_to_accum(preout)
                new_states = policy.cast_to_param(new_states)
                lm = lmask if lmask is not None else (
                    m if (m is not None and m.ndim == preout.ndim - 1) else None)
                with jax.named_scope("loss"):
                    if getattr(out_layer, "requires_features_for_score",
                               False):
                        per_ex = out_layer.compute_score_with_features(
                            y, preout, policy.cast_to_accum(feats), p[-1], lm)
                    else:
                        per_ex = out_layer.compute_score(y, preout, lm)
                    score = (jnp.mean(per_ex) if g.mini_batch
                             else jnp.sum(per_ex))
                    score = score + self._reg_penalty(p)
                    # auxiliary losses surfaced by layers through their
                    # state (e.g. MoE load-balancing, the MoE layer)
                    for s in new_states:
                        if isinstance(s, dict) and "moe_aux_loss" in s:
                            score = score + s["moe_aux_loss"]
                    if not g.minimize:
                        score = -score
                return score, new_states

            (score, new_states), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            return score, new_states, grads

        return grad_step

    def _build_step_raw(self):
        """The pure (un-jitted) train step — ParallelWrapper re-jits it
        with mesh shardings or vmaps it for parameter-averaging compat.
        Tracing inlines :meth:`_build_grad_raw`, so the compiled step is
        byte-identical to the pre-split single-closure form."""
        grad_step = self._build_grad_raw()

        # the name is what a compile event and a trace show (jit_<name>)
        def mln_train_step(params, state, opts, x, y, fmask, lmask, it, rng):
            score, new_states, grads = grad_step(params, state, x, y,
                                                 fmask, lmask, rng)
            with jax.named_scope("update"):
                new_params, new_opts = self._apply_updates(params, opts,
                                                           grads, it)
            return new_params, new_states, new_opts, score

        from deeplearning4j_tpu.parallel import fsdp
        return fsdp.partitioned_if_sharded(self, mln_train_step)

    def _apply_updates(self, params, opts, grads, it):
        """Traceable gradient→param update: per-layer gradient
        normalization, LR schedule, learning rule, bias-LR override and
        frozen-layer gating.  Shared by the fused train step and the
        external-gradients path (apply_gradients)."""
        g = self.conf.global_conf
        plan = getattr(self, "_sharding_plan", None)
        new_params, new_opts = [], []
        for i, layer in enumerate(self.layers):
            gi = grads[i]
            if not gi:
                new_params.append(params[i])
                new_opts.append(opts[i])
                continue
            if self.frozen[i]:
                new_params.append(params[i])
                new_opts.append(opts[i])
                continue
            if plan is not None:
                # ZeRO weight-update sharding (arXiv 2004.13336): pin
                # each gradient to its param's fsdp layout so XLA lowers
                # the data-parallel reduction as reduce-scatter into
                # shards; the updater below then runs per-shard and the
                # next forward all-gathers the updated params.
                gi = plan.constrain_grads(gi)
            gi = upd_ops.normalize_gradient(
                gi, layer.gradient_normalization,
                layer.gradient_normalization_threshold or 1.0)
            lr = upd_ops.schedule_lr(
                layer.learning_rate if layer.learning_rate is not None else g.learning_rate,
                g.lr_policy, it,
                decay_rate=g.lr_policy_decay_rate, steps=g.lr_policy_steps,
                power=g.lr_policy_power, schedule_map=g.learning_rate_schedule)
            blr = layer.bias_learning_rate
            upd, new_opt = self.updaters[i].apply(gi, opts[i], lr, it)
            if blr is not None and blr != (layer.learning_rate or g.learning_rate):
                # bias LR override: rescale bias update (exact for linear-in-lr rules)
                base = layer.learning_rate if layer.learning_rate is not None else g.learning_rate
                scale = blr / base if base else 1.0
                upd = {k: (v * scale if k in BIAS_KEYS else v)
                       for k, v in upd.items()}
            new_params.append({k: params[i][k] - upd[k] for k in params[i]})
            new_opts.append(new_opt)
        return new_params, new_opts

    def _build_score_fn(self):
        out_layer = self.layers[-1]
        g = self.conf.global_conf
        policy = dtype_ops.resolve(g.precision)

        def score_fn(params, state, x, y, fmask, lmask):
            pc, xc, fmc = policy.cast_to_compute((params, x, fmask))
            preout, _, m, feats = self._forward_to_preout(
                pc, state, xc, fmc, False, jax.random.PRNGKey(0))
            preout = policy.cast_to_accum(preout)
            lm = lmask if lmask is not None else (
                m if (m is not None and m.ndim == preout.ndim - 1) else None)
            if getattr(out_layer, "requires_features_for_score", False):
                per_ex = out_layer.compute_score_with_features(
                    y, preout, policy.cast_to_accum(feats), params[-1], lm)
            else:
                per_ex = out_layer.compute_score(y, preout, lm)
            score = jnp.mean(per_ex) if g.mini_batch else jnp.sum(per_ex)
            return score + self._reg_penalty(params)

        from deeplearning4j_tpu.parallel import fsdp
        score_fn = fsdp.partitioned_if_sharded(self, score_fn)
        return jax.jit(score_fn)

    def _build_output_fn(self):
        policy = dtype_ops.resolve(self.conf.global_conf.precision)
        quant = getattr(self, "_infer_quant", None)

        def output_fn(params, state, x, fmask):
            if quant is not None:
                # weight-only quantized serving: params arrive as int8/
                # fp8 codes + per-channel scales; the expand fuses into
                # the first consumer matmul (ops/quantize.py)
                from deeplearning4j_tpu.ops import quantize as qz
                params = qz.dequantize_params(params)
            pc, xc, fmc = policy.cast_to_compute((params, x, fmask))
            out, _, _ = self._forward(pc, state, xc, fmc, False,
                                      jax.random.PRNGKey(0))
            return policy.cast_to_param(out)
        from deeplearning4j_tpu.parallel import fsdp
        output_fn = fsdp.partitioned_if_sharded(self, output_fn)
        plan = getattr(self, "_sharding_plan", None)
        if plan is not None:
            # sharded serving (ROADMAP 3a): a model that only fits
            # sharded serves through the same plan the fit path uses —
            # params stay in their fsdp layout, the batch shards over
            # data(+fsdp), the output all-gathers on device
            return fsdp.jit_sharded_output(output_fn, plan, self.net_params)
        return jax.jit(output_fn)

    # ------------------------------------------------------------------
    # Training API
    # ------------------------------------------------------------------
    def set_listeners(self, *listeners: IterationListener):
        self.listeners = list(listeners)
        return self

    def add_listener(self, listener: IterationListener):
        self.listeners.append(listener)
        return self

    def fit(self, data, labels=None, epochs: int = 1,
            fused_steps: int = 1):
        """fit(DataSetIterator) | fit(DataSet) | fit(x, y)
        (ref: MultiLayerNetwork.fit :996).

        ``fused_steps=K>1`` fuses K consecutive same-shape batches into
        ONE compiled launch (`lax.scan` over the train step) — the
        per-step host dispatch that bounds small-model TPU throughput
        disappears; the reference has no analog (its fit loop is
        inherently per-batch, MultiLayerNetwork.fit :996).  Semantics
        divergence, documented: listeners fire once per LAUNCH (seeing
        the last score of the group), not once per batch; groups need
        identical shapes/mask-presence (ragged tails fall back to
        per-step); TBPTT ignores the flag."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.datasets.iterators import (
            AsyncDataSetIterator, DataSetIterator, ListDataSetIterator,
            reader_retry_from_conf)

        if labels is not None:
            data = DataSet(np.asarray(data), np.asarray(labels))
        if isinstance(data, DataSet):
            data = ListDataSetIterator([data])
        assert isinstance(data, DataSetIterator)
        if self.net_params is None:
            self.init()
        # warm-validate the fused-kernel helper tier (ops/helpers.py)
        # BEFORE the first step traces: a Mosaic rejection flips that
        # tier's kill switch here instead of killing the training run
        from deeplearning4j_tpu.ops import helpers as pallas_helpers
        with monitor.span("fit/setup", phase="kernel_self_test"):
            pallas_helpers.ensure_validated()
        self._check_trace_token()
        self._ensure_sharding()
        if self._step_fn is None:
            self._step_fn = self._build_step()

        it = data
        g = self.conf.global_conf
        # elastic cluster training (conf.distributed(...)): attach the
        # process's DistSession so every batch routes through the
        # coordinator barrier step (distributed/worker.fit_batch);
        # without a coordinator the conf is inert (replica semantics)
        if getattr(self, "_dist_session", None) is None \
                and getattr(g, "dist_enabled", False):
            from deeplearning4j_tpu import distributed as dist_mod
            self._dist_session = dist_mod.maybe_session(g)
        dist_sess = getattr(self, "_dist_session", None)
        if dist_sess is not None:
            dist_sess.attach(self)
        # crash-safe resume (conf.fault_tolerance(resume=True)): restore
        # the newest valid checkpoint into this model and skip the
        # already-trained epochs/batches so the resumed trajectory
        # matches an uninterrupted run (nn/checkpoint.py)
        from deeplearning4j_tpu.nn import checkpoint as ckpt_mod
        skip_epochs, skip_batches = ckpt_mod.maybe_auto_resume(self)
        if dist_sess is not None:
            # a worker absorbed into a running cluster restores the
            # survivors' in-memory snapshot and replay-skips the
            # already-trained prefix, exactly like a checkpoint resume
            skip_epochs, skip_batches = dist_sess.resume_position(
                self, skip_epochs, skip_batches)
        if (g.pipeline_workers > 0 and it.async_supported()
                and not isinstance(it, AsyncDataSetIterator)):
            plan = getattr(self, "_sharding_plan", None)
            transform = None
            if self._bucket_train_enabled():
                gg = self.conf.global_conf
                # bucket on a worker thread, BEFORE device_put: the
                # H2D transfer is then already bucket-shaped and the
                # engine's own bucketing hits its no-op fast path.
                # Under a sharding plan the bucket is lifted to a
                # data-degree multiple so the sharded normalize is a
                # no-op too.
                min_mult = plan.n_data if plan is not None else 1
                transform = lambda d: bucketing.bucket_train_dataset(  # noqa: E731
                    d, gg, min_multiple=min_mult)[0]
            it = AsyncDataSetIterator(
                it, queue_size=g.pipeline_prefetch,
                workers=g.pipeline_workers,
                staging_depth=g.pipeline_staging_depth,
                # sharded fit scatters each batch across the mesh itself
                # (fsdp.shard_put); staging to one device first would
                # just bounce the rows device→host→mesh
                device_put=(plan is None), transform=transform,
                reader_retry=reader_retry_from_conf(g))

        # fused path steps the updater once per batch; a conf with
        # iterations>1 (multiple updates per batch) keeps exact
        # semantics on the per-step path instead; the distributed step
        # barriers per batch, so scan fusion cannot apply
        fuse = (max(1, int(fused_steps))
                if (self.conf.backprop_type != "truncatedbptt"
                    and self.conf.global_conf.iterations <= 1
                    and dist_sess is None) else 1)
        try:
            # DL4J_SANITIZE: debug-nans/rank checks for the duration,
            # retrace-budget assertion on clean exit (analysis/sanitizer).
            # The events.scope gives this fit a correlation ID so every
            # fit/step span and checkpoint event journals under it.
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
                    # the epoch's notional starting iteration — what
                    # CheckpointListener subtracts to record how many
                    # batches into the epoch a save landed
                    self._epoch_start_iter = self.iteration - to_skip
                    with steps.span("fit/step", phase="epoch"):
                        for lst in self.listeners:
                            if isinstance(lst, TrainingListener):
                                lst.on_epoch_start(self)
                        it.reset()
                    t_etl = time.perf_counter()
                    pending = []
                    while True:
                        with steps.span("fit/step", phase="has_next"):
                            more = it.has_next()
                        if not more:
                            break
                        with steps.span("fit/step", phase="data_wait"):
                            ds = it.next()
                        if to_skip > 0:
                            # replay-skip: consume (keeps the stream
                            # position identical to the crashed run)
                            # without training or advancing iteration
                            to_skip -= 1
                            t_etl = time.perf_counter()
                            continue
                        self.last_etl_time_ms = \
                            (time.perf_counter() - t_etl) * 1e3
                        if fuse > 1:
                            pending.append(ds)
                            if len(pending) == fuse:
                                self._fit_fused_group(pending)
                                pending = []
                        else:
                            self._fit_batch(ds)
                        t_etl = time.perf_counter()
                    for ds in pending:  # ragged tail: per-step path
                        self._fit_batch(ds)
                    with steps.span("fit/step", phase="epoch"):
                        for lst in self.listeners:
                            if isinstance(lst, TrainingListener):
                                lst.on_epoch_end(self)
                        self.epoch += 1
                if isinstance(it, AsyncDataSetIterator):
                    with steps.span("fit/step", phase="epoch"):
                        it.close()
                events.emit("fit.end", iteration=self.iteration,
                            epoch=self.epoch)
        finally:
            # release pipeline threads — a producer blocked on a full
            # queue mid-exception would otherwise leak (close() is
            # idempotent and the iterator restarts lazily if reused)
            if isinstance(it, AsyncDataSetIterator):
                it.close()
            if self._steps is not None:
                self._steps.close()
        return self

    def _build_fused_step(self, k: int):
        """K train steps as one compiled program: lax.scan over the raw
        step with the batch axis stacked in front.  Dispatch once, step
        K times."""
        raw = self._build_step_raw()

        def strip_rnn(state):
            # in-trace equivalent of _strip_rnn_state: RNN layers emit a
            # carried 'rnn_state' each step; dropping it inside the body
            # keeps the scan carry structure closed AND stops hidden
            # state leaking across unrelated minibatches in a group
            return [{kk: v for kk, v in s.items() if kk != "rnn_state"}
                    for s in state]

        def mln_fused_steps(params, state, opts, xs, ys, fms, lms, it0, key):
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

        return jax.jit(mln_fused_steps, donate_argnums=(0, 1, 2))  # dl4j: noqa[DL4J104] one jitted fn per k, cached in _fused_fns[k]

    def _fit_fused_group(self, group):
        if getattr(self, "_sharding_plan", None) is not None:
            self._fit_fused_group_sharded(group)
            return
        sizes = [d.num_examples() for d in group]
        # bucketing makes ragged groups (mixed batch sizes / RNN time
        # lengths, the tail of any real stream) bucket-uniform so they
        # STAY on the fused scan path instead of degrading to per-step
        with self._steps.span("fit/step", phase="bucket"):
            group = [self._maybe_bucket_train(d)[0] for d in group]
        k = len(group)
        shapes = {(d.features.shape, d.labels.shape,
                   d.features.dtype, d.labels.dtype,
                   d.features_mask is None, d.labels_mask is None)
                  for d in group}
        if len(shapes) != 1:
            for d in group:   # mixed shapes can't stack — per-step
                self._fit_batch(d)
            return
        # first-ever launch runs ONE batch per-step so carried state
        # (e.g. a layer adding aux-state keys) reaches its steady
        # structure before it becomes a scan carry
        if getattr(self, "_fused_fns", None) is None:
            self._fused_fns = {}
            self._fit_batch(group[0])
            group, sizes = group[1:], sizes[1:]
            k = len(group)
            if not k:
                return
        if k not in self._fused_fns:
            self._fused_fns[k] = self._build_fused_step(k)
        t_step = time.perf_counter()
        with self._steps.span("fit/step", phase="h2d"):
            xs = jnp.stack([jnp.asarray(d.features) for d in group])
            ys = jnp.stack([jnp.asarray(d.labels) for d in group])
            fms = (jnp.stack([jnp.asarray(d.features_mask) for d in group])
                   if group[0].features_mask is not None else None)
            lms = (jnp.stack([jnp.asarray(d.labels_mask) for d in group])
                   if group[0].labels_mask is not None else None)
        self.last_batch_size = sum(sizes)
        batch = (xs, ys, fms, lms)
        dispatch_train_step(self, self._fused_fns[k], f"fused_step_k{k}",
                            batch, batch, t_step, k=k)

    def _fit_fused_group_sharded(self, group):
        """fused_steps=K under a sharding plan: each batch is padded to
        the data degree, the group stacks along a leading scan axis with
        the scan-aware sharding P(None, ('data','fsdp')), and the
        engine's own fused builder runs — params/updater are committed
        with their mesh shardings so jit composes the per-step
        reduce-scatter/all-gather with the scan without a wrapper-side
        re-implementation."""
        from deeplearning4j_tpu.parallel import fsdp
        plan = self._sharding_plan
        norms = [fsdp.normalize_batch(self, d, plan.n_data, is_graph=False)
                 for d in group]
        if any(n is None for n in norms):
            for d in group:
                self._fit_batch(d)
            return

        def sig(batch):
            leaves, treedef = jax.tree_util.tree_flatten(batch)
            return (treedef, tuple((a.shape, a.dtype) for a in leaves))
        if len({sig(b) for b, _, _ in norms}) != 1:
            for d in group:   # mixed shapes can't stack — per-step
                self._fit_batch(d)
            return
        # first-ever launch runs ONE batch per-step so carried state
        # reaches its steady structure before it becomes a scan carry
        if getattr(self, "_fused_fns", None) is None:
            self._fused_fns = {}
            self._fit_batch(group[0])
            group, norms = group[1:], norms[1:]
            if not norms:
                return
        k = len(norms)
        if k not in self._fused_fns:
            self._fused_fns[k] = self._build_fused_step(k)
        t_step = time.perf_counter()
        with self._steps.span("fit/step", phase="shard_h2d"):
            batch = fsdp.stack_for_scan(plan, [b for b, _, _ in norms])
        self.last_batch_size = sum(n for _, n, _ in norms)
        dispatch_train_step(self, self._fused_fns[k], f"fused_step_k{k}",
                            batch, batch, t_step, k=k)

    def _fit_batch(self, ds):
        g = self.conf.global_conf
        self.last_batch_size = ds.num_examples()
        if self.conf.backprop_type == "truncatedbptt" and ds.features.ndim == 3:
            with self._steps.span("fit/step", phase="tbptt"):
                self._fit_tbptt(ds)
            return
        dist_sess = getattr(self, "_dist_session", None)
        if dist_sess is not None:
            # cluster step: shard-local grads → coordinator all-reduce →
            # updater apply (docs/DISTRIBUTED.md); TBPTT stays local
            from deeplearning4j_tpu.distributed import worker as dist_worker
            self._steps.close()     # timed by the worker's own spans
            dist_worker.fit_batch(self, ds, dist_sess, is_graph=False)
            self._steps.restart()
            return
        t_step = time.perf_counter()
        steps = self._steps
        plan = getattr(self, "_sharding_plan", None)
        if plan is not None:
            from deeplearning4j_tpu.parallel import fsdp
            with steps.span("fit/step", phase="bucket"):
                # pad (mask-exact) or trim the batch to the data degree;
                # shape bucketing, when on, subsumes this by lifting the
                # bucket to a data-degree multiple
                norm = fsdp.normalize_batch(self, ds, plan.n_data,
                                            is_graph=False)
            if norm is None:
                return
            sig_args, n, bucket = norm
            self.last_batch_size = n
            kind = "sharded_step"
            with steps.span("fit/step", phase="shard_h2d"):
                # host→mesh scatter: each device receives only its batch
                # shard (the sharded step's in_shardings layout)
                batch = fsdp.shard_put(plan, sig_args)
        else:
            with steps.span("fit/step", phase="bucket"):
                ds, bucket = self._maybe_bucket_train(ds)
            kind = "train_step"
            sig_args = (ds.features, ds.labels, ds.features_mask,
                        ds.labels_mask)
            with steps.span("fit/step", phase="h2d"):
                # no-op when the async iterator already device_put the
                # batch; otherwise this is the host→device transfer,
                # timed apart from the jitted call it used to hide inside
                batch = tuple(None if a is None else jnp.asarray(a)
                              for a in sig_args)
        dispatch_train_step(self, self._step_fn, kind, sig_args, batch,
                            t_step, bucket=bucket,
                            repeats=max(1, g.iterations))

    def _fit_tbptt(self, ds):
        """Truncated BPTT over time segments, carrying RNN state
        (ref: MultiLayerNetwork.doTruncatedBPTT :1227)."""
        T = ds.features.shape[1]  # native layout [N, T, C]
        L = self.conf.tbptt_fwd_length
        self.rnn_clear_previous_state()
        for t0 in range(0, T, L):
            seg = slice(t0, min(t0 + L, T))
            f = ds.features[:, seg]
            l = ds.labels[:, seg] if ds.labels.ndim == 3 else ds.labels
            fm = ds.features_mask[:, seg] if ds.features_mask is not None else None
            lm = ds.labels_mask[:, seg] if ds.labels_mask is not None else None
            self._key, sub = jax.random.split(self._key)
            (self.net_params, self.net_state, self.opt_states, score) = self._step_fn(
                self.net_params, self.net_state, self.opt_states,
                f, l, fm, lm, jnp.asarray(self.iteration, jnp.int32), sub)
            self._score = score
            self.iteration += 1
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration)

    # ------------------------------------------------------------------
    # Layerwise unsupervised pretraining (AE / RBM / VAE)
    # ------------------------------------------------------------------
    def pretrain(self, data, epochs: int = 1):
        """Layerwise pretrain every pretrain-capable layer
        (ref: MultiLayerNetwork.pretrain :1010-1024)."""
        for i, layer in enumerate(self.layers):
            if layer.is_pretrain_layer():
                self.pretrain_layer(i, data, epochs=epochs)
        return self

    def pretrain_layer(self, layer_idx: int, data, epochs: int = 1):
        """Unsupervised fit of one layer on activations of the layers below
        (ref: MultiLayerNetwork.pretrainLayer :197).  The per-layer step —
        forward-to-layer, pretrain loss, grad, updater — is one jitted XLA
        program with donated param/opt buffers."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.datasets.iterators import (
            DataSetIterator, ListDataSetIterator)

        layer = self.layers[layer_idx]
        if not layer.is_pretrain_layer():
            return self
        if self.net_params is None:
            self.init()
        if isinstance(data, (np.ndarray, jax.Array)):
            data = DataSet(np.asarray(data), np.asarray(data))
        if isinstance(data, DataSet):
            data = ListDataSetIterator([data])
        assert isinstance(data, DataSetIterator)

        g = self.conf.global_conf
        updater = self.updaters[layer_idx]

        def pre_step(lp, opt, prefix_params, state, x, it, rng):
            def to_layer_input(xi):
                m = None
                for j in range(layer_idx):
                    if j in self.conf.preprocessors:
                        xi, m = self.conf.preprocessors[j](xi, m)
                    xi, _, m = self.layers[j].forward(
                        prefix_params[j], state[j], xi, train=False,
                        rng=jax.random.fold_in(rng, j), mask=m)
                if layer_idx in self.conf.preprocessors:
                    xi, m = self.conf.preprocessors[layer_idx](xi, m)
                return xi

            feats = jax.lax.stop_gradient(to_layer_input(x))

            def full_loss(p):
                # pretrain score includes this layer's l1/l2 and honors
                # minimize, matching the supervised step (ref:
                # BasePretrainNetwork score includes regularization)
                loss = layer.pretrain_loss(p, feats, rng) + \
                    self._layer_reg_penalty(layer, p)
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
                power=g.lr_policy_power, schedule_map=g.learning_rate_schedule)
            upd, new_opt = updater.apply(grads, opt, lr, it)
            new_lp = {k: lp[k] - upd[k] for k in lp}
            return new_lp, new_opt, loss

        step_jit = jax.jit(pre_step, donate_argnums=(0, 1))  # dl4j: noqa[DL4J104] one pretrain jit per layer by design
        for _ in range(epochs):
            data.reset()
            while data.has_next():
                ds = data.next()
                self._key, sub = jax.random.split(self._key)
                lp, opt, loss = step_jit(
                    self.net_params[layer_idx], self.opt_states[layer_idx],
                    self.net_params[:layer_idx], self.net_state, ds.features,
                    jnp.asarray(self.iteration, jnp.int32), sub)
                self.net_params[layer_idx] = lp
                self.opt_states[layer_idx] = opt
                self._score = loss
                self.iteration += 1
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration)
        return self

    def _strip_rnn_state(self):
        """Drop per-batch RNN carry so standard training doesn't leak state
        across minibatches (and jit sees a stable state structure)."""
        if self.net_state is None:
            return
        self.net_state = [{k: v for k, v in s.items() if k != "rnn_state"}
                          for s in self.net_state]

    # ------------------------------------------------------------------
    # Inference API
    # ------------------------------------------------------------------
    def quantize_inference(self, mode: str = "int8"):
        """Serve from weight-only quantized params (docs/PERFORMANCE.md
        "Precision tiers"): every ndim>=2 float param becomes int8 (or
        fp8) codes + per-channel f32 scales, dequantized IN-TRACE, so
        ``output()``/the micro-batcher/warmup hold ~4x-smaller resident
        weights.  Selection goes through the precision-tier registry
        (ops/helpers.py): the tier's parity self-test runs first, and a
        kill switch (``DL4J_PRECISION_{INT8,FP8}=0``) or failed
        self-test degrades to dense serving.  ``mode=None`` restores
        dense serving.  Inert under a sharding plan (sharded serving
        keeps the fsdp layout).  Training is untouched — fit() keeps
        the fp32 master params, and the codes refresh from them lazily
        after further training."""
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
        self._q_params = None  # re-quantized lazily by _infer_params
        self._check_trace_token()
        return self

    def _infer_params(self):
        """Params for the serving path: the quantized codes when the
        int8/fp8 tier is on (refreshed when training moved the masters
        since the last quantization), else the dense params."""
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

    def output(self, x, train: bool = False, mask=None):
        """(ref: MultiLayerNetwork.output :1668)"""
        if self.net_params is None:
            self.init()
        self._check_trace_token()
        self._ensure_sharding()
        if self._output_fn is None:
            self._output_fn = self._build_output_fn()
        plan = getattr(self, "_sharding_plan", None)
        unpad = bucket = None
        if self.conf.global_conf.shape_bucketing:
            x, mask, n, t, bucket = bucketing.bucket_inference_features(
                x, mask, self.conf.global_conf)
            unpad = (n, t, bucket[1])
        if plan is not None:
            # data-sharded layout needs a batch divisible by the mesh's
            # batch degree; zero rows are exact at inference and the
            # unpad slice below removes them
            from deeplearning4j_tpu.parallel import fsdp
            x, mask, n_real = fsdp.pad_inference_rows(x, mask, plan.n_data)
            if n_real is not None and unpad is None:
                unpad = (n_real, None, None)
        self.compile_telemetry.record("output", (x, mask), bucket=bucket)
        out = self._output_fn(self._infer_params(),
                              [{k: v for k, v in s.items() if k != "rnn_state"}
                               for s in self.net_state],
                              jnp.asarray(x),
                              None if mask is None else jnp.asarray(mask))
        if unpad is not None:
            out = bucketing.unpad_outputs(out, *unpad)
        return out

    def predict(self, x) -> np.ndarray:
        """Argmax class predictions (ref: MultiLayerNetwork.predict :1456)."""
        out = self.output(x)
        return jax.device_get(jnp.argmax(out, axis=-1))

    def warmup_inference(self, feature_dims, max_batch: int = 32,
                         batch_sizes=None, dtype=np.float32) -> dict:
        """Pre-compile the jitted inference path for every batch bucket
        a serving frontend can hand it, so first requests never pay a
        cold XLA compile.  ``feature_dims`` is the per-example feature
        shape (``(F,)``, ``(C, H, W)``, ``(T, C)`` …); the ladder is
        ``batch_sizes`` / the configured bucket ladder / powers of two
        up to ``max_batch`` (ops/bucketing.warmup_ladder).  Reuses the
        same jitted ``output`` entry point real requests hit — with
        shape bucketing enabled each warmed bucket is exactly the
        program a padded request executes.  Returns the warmed ladder
        and wall time."""
        if self.net_params is None:
            self.init()
        g = self.conf.global_conf
        ladder = bucketing.warmup_ladder(
            batch_sizes or g.bucket_batch_sizes, max_batch)
        dims = tuple(int(d) for d in feature_dims)
        t0 = time.perf_counter()
        for nb in ladder:
            jax.block_until_ready(self.output(np.zeros((nb,) + dims, dtype)))
        return {"buckets": ladder,
                "warmup_sec": round(time.perf_counter() - t0, 3)}

    def feed_forward(self, x, train: bool = False, mask=None):
        """All layer activations (ref: feedForward :696-788)."""
        if self.net_params is None:
            self.init()
        self._key, sub = jax.random.split(self._key)
        _, _, _, acts = self._forward_core(
            self.net_params, self.net_state, jnp.asarray(x), mask, train,
            sub, stateful_rnn=False, collect_acts=True)
        return acts

    def score(self, dataset=None) -> float:
        """Loss on a DataSet, or last training score
        (ref: MultiLayerNetwork.score)."""
        if dataset is None:
            return float(self._score)
        self._check_trace_token()
        if self._score_fn is None:
            self._score_fn = self._build_score_fn()
        ds, bucket = self._maybe_bucket_train(dataset)
        self.compile_telemetry.record(
            "score", (ds.features, ds.labels, ds.features_mask,
                      ds.labels_mask), bucket=bucket)
        return float(self._score_fn(self.net_params, self.net_state,
                                    ds.features, ds.labels,
                                    ds.features_mask, ds.labels_mask))

    def score_examples(self, data, add_regularization_terms: bool = False):
        """Per-example scores WITHOUT minibatch averaging — the anomaly-
        detection / per-example-attribution API (ref:
        MultiLayerNetwork.scoreExamples :1884 iterator, :1901 DataSet;
        addRegularizationTerms adds the net's l1/l2 penalty to every
        example's score).  Accepts a DataSet or an iterator; returns a 1-D
        np.ndarray of length total-examples."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        if self.net_params is None:
            self.init()
        self._check_trace_token()
        if getattr(self, "_score_ex_fn", None) is None:
            out_layer = self.layers[-1]
            policy = dtype_ops.resolve(self.conf.global_conf.precision)

            def score_ex(params, state, x, y, fmask, lmask, add_reg):
                pc, xc, fmc = policy.cast_to_compute((params, x, fmask))
                preout, _, m, feats = self._forward_to_preout(
                    pc, state, xc, fmc, False, jax.random.PRNGKey(0))
                preout = policy.cast_to_accum(preout)
                lm = lmask if lmask is not None else (
                    m if (m is not None and m.ndim == preout.ndim - 1)
                    else None)
                if getattr(out_layer, "requires_features_for_score", False):
                    per_ex = out_layer.compute_score_with_features(
                        y, preout, policy.cast_to_accum(feats), params[-1],
                        lm)
                else:
                    per_ex = out_layer.compute_score(y, preout, lm)
                return per_ex + jnp.where(add_reg,
                                          self._reg_penalty(params), 0.0)

            self._score_ex_fn = jax.jit(score_ex)
        batches = [data] if isinstance(data, DataSet) else data
        g = self.conf.global_conf
        # per-example scoring needs no minibatch mean, so the bucket gate
        # drops the mean-reduction requirement; padded rows are sliced
        # back off (masks stay UNSCALED so real rows keep exact values)
        bucket_ok = (g.shape_bucketing
                     and bucketing.pad_supported(self, require_mean=False))
        out = []
        for ds in batches:
            n = ds.num_examples()
            bucket = None
            if bucket_ok:
                ds, bucket = bucketing.bucket_train_dataset(
                    ds, g, scale_loss=False)
            self.compile_telemetry.record(
                "score_examples", (ds.features, ds.labels, ds.features_mask,
                                   ds.labels_mask), bucket=bucket)
            per = np.asarray(self._score_ex_fn(
                self.net_params, self.net_state, ds.features, ds.labels,
                ds.features_mask, ds.labels_mask,
                jnp.asarray(add_regularization_terms)))
            out.append(per[:n] if bucket is not None else per)
        return np.concatenate(out)

    def _merge_rnn_state(self, new_states) -> None:
        """Persist per-layer rnn carries into the live state, leaving
        everything else (BN running stats) untouched."""
        merged = []
        for old, new in zip(self.net_state, new_states):
            s = dict(old)
            if "rnn_state" in new:
                s["rnn_state"] = new["rnn_state"]
            merged.append(s)
        self.net_state = merged

    def _rnn_step_raw(self):
        """The pure carried decode step — the seam shared by
        :meth:`rnn_time_step` and the serving decode pool
        (``server/decode.py``): ``(params, base_state, carries, x,
        fmask) -> (out, new_carries)`` where ``carries`` is a per-layer
        list of recurrent carry pytrees (``None`` for carry-free
        layers).  Keeping the carry EXPLICIT in the signature (instead
        of buried inside ``net_state``) is what makes the structure
        closed under iteration, so ONE jitted trace serves every step
        of an autoregressive stream (arXiv 2603.09555's compiled-carry
        contract — no per-step retrace, no per-step re-dispatch of the
        whole layer stack).  The forward traces under
        ``kv_decode_scope``: attention layers swap their re-run-window
        core for the incremental ring-cached step, so their KV ring is
        just another carry leaf closed under iteration."""
        from deeplearning4j_tpu.parallel import sequence as seq_ops
        policy = dtype_ops.resolve(self.conf.global_conf.precision)

        def rnn_fn(params, state, carries, x, fmask):
            pc, cc, xc, fmc = policy.cast_to_compute(
                (params, carries, x, fmask))
            st = []
            for s, c in zip(state, cc):
                s = {k: v for k, v in s.items() if k != "rnn_state"}
                if c is not None:
                    s["rnn_state"] = c
                st.append(s)
            with seq_ops.kv_decode_scope():
                out, new_states, _ = self._forward(
                    pc, st, xc, fmc, False, jax.random.PRNGKey(0),
                    stateful_rnn=True)
            new_carries = [ns.get("rnn_state")
                           if isinstance(ns, dict) else None
                           for ns in new_states]
            return (policy.cast_to_param(out),
                    policy.cast_to_param(new_carries))

        return rnn_fn

    def rnn_carry_template(self, n: int, feature_tail=None,
                           dtype=jnp.float32):
        """Zero-initialized per-layer carry pytree for ``n`` concurrent
        streams — shapes discovered via ``jax.eval_shape`` over the
        carried step (no compile, no device work), so ANY layer that
        emits an ``rnn_state`` carry participates without a per-type
        registry.  ``feature_tail`` is the per-example input shape tail
        (``(T, C)``); defaults to one timestep of the conf's recurrent
        input type."""
        if self.net_params is None:
            self.init()
        if feature_tail is None:
            it = self._input_type_chain_start()
            if it.kind != "rnn":
                raise ValueError(
                    "rnn_carry_template needs a recurrent input type "
                    "(or an explicit feature_tail=)")
            feature_tail = (1, it.size)
        x_sds = jax.ShapeDtypeStruct(
            (int(n),) + tuple(int(d) for d in feature_tail), dtype)
        base = [{k: v for k, v in s.items() if k != "rnn_state"}
                for s in self.net_state]
        _, spec = jax.eval_shape(
            self._rnn_step_raw(), self.net_params, base,
            [None] * len(self.layers), x_sds, None)
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), spec)

    def rnn_time_step(self, x, mask=None):
        """Stateful single/multi-step inference, carrying RNN state across
        calls (ref: MultiLayerNetwork.rnnTimeStep :2383).  x: [N, T, C].

        Every call is the SAME cached jitted step: the first call
        materializes a zero carry template (so the carry structure is
        identical with and without stored state) and each subsequent
        call re-dispatches the one compiled program — per-token cost is
        O(1) in how much history the stream has consumed."""
        if self.net_params is None:
            self.init()
        self._check_trace_token()
        if getattr(self, "_rnn_step_fn", None) is None:
            self._rnn_step_fn = jax.jit(self._rnn_step_raw())
        x = jnp.asarray(x)
        m = None if mask is None else jnp.asarray(mask)
        carries = [s.get("rnn_state") for s in self.net_state]
        if all(c is None for c in carries):
            carries = self.rnn_carry_template(
                x.shape[0], feature_tail=tuple(x.shape[1:]), dtype=x.dtype)
        self.compile_telemetry.record("rnn_time_step", (x, m, carries))
        out, new_carries = self._rnn_step_fn(
            self.net_params,
            [{k: v for k, v in s.items() if k != "rnn_state"}
             for s in self.net_state],
            carries, x, m)
        merged = []
        for s, c in zip(self.net_state, new_carries):
            s = {k: v for k, v in s.items() if k != "rnn_state"}
            if c is not None:
                s["rnn_state"] = c
            merged.append(s)
        self.net_state = merged
        return out

    def rnn_clear_previous_state(self):
        self._strip_rnn_state()

    def rnn_activate_using_stored_state(self, x, training: bool = False,
                                        store_last_for_tbptt: bool = False):
        """All layer activations computed FROM the stored RNN state,
        optionally persisting the final carry (ref:
        MultiLayerNetwork.rnnActivateUsingStoredState :1955 — the TBPTT
        engine's forward; exposed for parity and inspection)."""
        if self.net_params is None:
            self.init()
        if training:
            # fresh dropout masks per call (feed_forward's convention);
            # a fixed key would train a fixed subnetwork
            self._key, sub = jax.random.split(self._key)
        else:
            sub = jax.random.PRNGKey(0)
        _, new_states, _, acts = self._forward_core(
            self.net_params, self.net_state, jnp.asarray(x), None, training,
            sub, stateful_rnn=True, collect_acts=True)
        if store_last_for_tbptt:
            self._merge_rnn_state(new_states)
        return acts

    # ------------------------------------------------------------------
    # External-errors backprop (the RL pattern: caller owns the loss)
    # ------------------------------------------------------------------
    def backprop_gradient(self, x, epsilon, mask=None, train: bool = False):
        """Param gradients + input epsilon from an EXTERNAL error signal
        dL/d(output) — no labels or loss function involved (ref:
        ComputationGraph.calcBackpropGradients external epsilons,
        nn/graph/ComputationGraph.java:1421; MLN backpropGradient).
        Reinforcement-learning frameworks drive the reference engine this
        way: run output(), compute their own loss outside, hand the error
        back.  Returns ``(grads, input_epsilon)`` where grads matches the
        net_params structure and input_epsilon is dL/dx.

        ``train=False`` (default) makes the internal forward EXACTLY the
        one output() ran — no dropout — so the gradients correspond to the
        activations the caller computed its error from.  ``train=True``
        samples fresh dropout masks (a different stochastic forward than
        the caller's output() call) and also folds the forward's updated
        carried state (BatchNorm running stats) back into the network,
        like a fit() step does."""
        if self.net_params is None:
            self.init()
        self._check_trace_token()
        if self._ext_grad_fn is None:
            self._ext_grad_fn = {}
        if train not in self._ext_grad_fn:
            policy = dtype_ops.resolve(self.conf.global_conf.precision)

            def ext_grad(params, state, xi, eps, m, rng, _train=train):
                def fwd(p, xin):
                    # cast through the precision policy exactly like
                    # _build_output_fn: under bf16 the VJP must
                    # differentiate the same forward output() ran, and
                    # grads come back in the f32 master-param dtype
                    pc, xc, mc = policy.cast_to_compute((p, xin, m))
                    out, ns, _ = self._forward(pc, state, xc, mc, _train,
                                               rng)
                    return out, ns
                out, vjp, ns = jax.vjp(fwd, params, xi, has_aux=True)
                g, dx = vjp(eps.astype(out.dtype))
                return g, dx, policy.cast_to_param(ns)
            self._ext_grad_fn[train] = jax.jit(ext_grad)
        if train:
            self._key, sub = jax.random.split(self._key)
        else:
            sub = jax.random.PRNGKey(0)
        x = jnp.asarray(x)
        grads, dx, new_states = self._ext_grad_fn[train](
            self.net_params, self.net_state, x, jnp.asarray(epsilon), mask,
            sub)
        if train:
            self.net_state = new_states
            self._strip_rnn_state()
        return grads, dx

    def apply_gradients(self, grads):
        """Apply externally computed per-layer gradients through the
        configured updaters (normalization, LR schedule, learning rule,
        frozen gating) — one jitted step.  Completes the external-errors
        training loop started by :meth:`backprop_gradient`.

        The l1/l2 regularization gradient is added here, matching the
        fused fit step's in-loss penalty (reference analog:
        UpdaterBlock.postApply applies l1/l2 updater-side so externally
        driven training still decays weights); ``minimize=False`` negates
        like fit() does, so callers always pass plain dL/dparam."""
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
        """Printable layer table: index, type, param shapes, param count
        (ref: MultiLayerNetwork.summary :2689)."""
        if self.net_params is None:
            self.init()
        rows = [("Idx", "LayerType", "ParamShapes", "ParamCount")]
        total = 0
        for i, (layer, lp) in enumerate(zip(self.layers, self.net_params)):
            n = sum(int(np.prod(v.shape)) for v in lp.values())
            total += n
            shapes = ", ".join(f"{k}{tuple(int(d) for d in v.shape)}"
                               for k, v in sorted(lp.items()))
            rows.append((str(i), type(layer).__name__, shapes or "-",
                         f"{n:,}"))
        return render_table(rows, [f"Total parameters: {total:,}"])

    # ------------------------------------------------------------------
    # Param view parity
    # ------------------------------------------------------------------
    def params(self) -> jnp.ndarray:
        """Flat 1-D param vector (ref: Model.params() 1xN row view)."""
        return param_util.flatten(self.net_params)

    def set_params(self, flat) -> None:
        self.net_params = param_util.unflatten(flat, self.net_params)
        self._replace_on_mesh()

    def num_params(self) -> int:
        return param_util.num_params(self.net_params)

    def get_layer_params(self, i: int) -> dict:
        return self.net_params[i]

    def param_table(self) -> Dict[str, jnp.ndarray]:
        """Named param map keyed ``"<layerIdx>_<paramName>"`` — e.g.
        ``"0_W"``, ``"1_b"`` (ref: Model.paramTable / MLN param keys)."""
        if self.net_params is None:
            self.init()
        return {f"{i}_{k}": v for i, lp in enumerate(self.net_params)
                for k, v in lp.items()}

    def get_param(self, key: str) -> jnp.ndarray:
        """(ref: Model.getParam("0_W"))"""
        i, k = key.split("_", 1)
        return self.net_params[int(i)][k]

    def set_param(self, key: str, value) -> None:
        """(ref: Model.setParam) — shape must match the existing param."""
        i, k = key.split("_", 1)
        cur = self.net_params[int(i)][k]
        value = jnp.asarray(value, cur.dtype)
        if value.shape != cur.shape:
            raise ValueError(f"setParam('{key}'): shape {value.shape} != "
                             f"{cur.shape}")
        self.net_params[int(i)] = {**self.net_params[int(i)], k: value}

    def updater_state_flat(self) -> jnp.ndarray:
        leaves = jax.tree_util.tree_leaves(self.opt_states)
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
        leaves, treedef = jax.tree_util.tree_flatten(self.opt_states)
        out, off = [], 0
        flat = jnp.asarray(flat).reshape(-1)
        for l in leaves:
            n = int(np.prod(l.shape))
            out.append(flat[off:off + n].reshape(l.shape).astype(l.dtype))
            off += n
        self.opt_states = jax.tree_util.tree_unflatten(treedef, out)
        self._replace_on_mesh()

    # ------------------------------------------------------------------
    def evaluate(self, iterator_or_dataset):
        """Classification evaluation (ref: MultiLayerNetwork.evaluate)."""
        from deeplearning4j_tpu.nn.evaluation import Evaluation
        from deeplearning4j_tpu.datasets.dataset import DataSet
        ev = Evaluation()
        if isinstance(iterator_or_dataset, DataSet):
            batches = [iterator_or_dataset]
        else:
            iterator_or_dataset.reset()
            batches = iterator_or_dataset
        for ds in batches:
            out = self.output(ds.features)
            ev.eval(ds.labels, jax.device_get(out), mask=ds.labels_mask)
        return ev

    def clone(self) -> "MultiLayerNetwork":
        # Arrays must be COPIED, not aliased: the jitted step donates its
        # input buffers, so a clone sharing buffers with a live net would be
        # invalidated by the next fit() step on either of them.
        import copy
        net = MultiLayerNetwork(copy.deepcopy(self.conf))
        if self.net_params is not None:
            copy_tree = lambda t: jax.tree_util.tree_map(  # noqa: E731
                lambda a: jnp.array(a, copy=True), t)
            # assign directly — no init(): avoids sampling a fresh random
            # initialization that would be immediately discarded
            net.net_params = copy_tree(self.net_params)
            net.net_state = copy_tree(self.net_state)
            net.opt_states = copy_tree(self.opt_states)
        net.iteration = self.iteration
        return net
