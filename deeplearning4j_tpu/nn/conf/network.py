"""Network configuration: builder DSL + MultiLayerConfiguration.

Mirrors the reference's Jackson-serializable config stack
(ref: nn/conf/NeuralNetConfiguration.java:539+ builder,
nn/conf/MultiLayerConfiguration.java) — global hyperparameters with
per-layer overrides, automatic nIn inference and preprocessor insertion
from ``InputType`` (ref: nn/conf/layers/InputTypeUtil.java), JSON
round-trip for checkpoint parity.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Optional

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import BatchNormalization, Layer
from deeplearning4j_tpu.nn.conf import preprocessors as pp


@dataclasses.dataclass
class GlobalConf:
    """Global hyperparameters (the reference's NeuralNetConfiguration fields)."""

    seed: int = 12345
    iterations: int = 1
    learning_rate: float = 1e-1
    bias_learning_rate: Optional[float] = None
    updater: str = "sgd"
    momentum: float = 0.9
    rho: float = 0.95
    rms_decay: float = 0.95
    adam_mean_decay: float = 0.9
    adam_var_decay: float = 0.999
    epsilon: Optional[float] = None
    activation: str = "sigmoid"
    weight_init: str = "xavier"
    bias_init: float = 0.0
    dist: Optional[dict] = None
    l1: float = 0.0
    l2: float = 0.0
    l1_bias: float = 0.0
    l2_bias: float = 0.0
    dropout: float = 0.0
    use_regularization: bool = False
    use_drop_connect: bool = False
    minimize: bool = True
    mini_batch: bool = True
    optimization_algo: str = "stochastic_gradient_descent"
    gradient_normalization: Optional[str] = None
    gradient_normalization_threshold: float = 1.0
    lr_policy: Optional[str] = None
    lr_policy_decay_rate: Optional[float] = None
    lr_policy_steps: Optional[float] = None
    lr_policy_power: Optional[float] = None
    learning_rate_schedule: Optional[dict] = None
    # Mixed-precision policy for the compiled step: None = auto (bf16
    # compute on TPU, f32 elsewhere); 'float32' | 'bfloat16' | 'float64'.
    # Master params/updater state stay float32 either way (ops/dtypes.py).
    precision: Optional[str] = None
    # Weight-only quantized inference (ops/quantize.py): 'int8' | 'fp8'
    # quantizes every ndim>=2 float param per-output-channel once on the
    # host and dequantizes in-trace, so output()/serving hold ~4x
    # smaller resident weights.  None = dense serving, byte-identical
    # to the pre-tier path.  Selection goes through the precision-tier
    # registry (ops/helpers.py): DL4J_PRECISION_{INT8,FP8}=0 kills it.
    precision_infer_quant: Optional[str] = None
    # Rematerialization: recompute each layer's forward during backward
    # instead of keeping its activations in HBM (jax.checkpoint per
    # layer/vertex) — the FLOPs-for-memory trade for deep nets on TPU.
    gradient_checkpointing: bool = False
    # Shape bucketing (ops/bucketing.py): pad ragged batch/time dims up
    # to a small ladder of buckets so jitted entry points compile once
    # per bucket instead of once per exact shape.  None ladders mean
    # powers of two.  Padded rows/timesteps are mask-excluded; outputs
    # and scores are un-padded, so results match the unbucketed run.
    shape_bucketing: bool = False
    bucket_batch_sizes: Optional[List[int]] = None
    bucket_time_sizes: Optional[List[int]] = None
    # Input pipeline (datasets/iterators.AsyncDataSetIterator): number of
    # parallel ETL worker threads the fit loops wrap iterators with
    # (0 = synchronous, no wrapper), raw-batch prefetch queue depth, and
    # how many already-device_put batches may be staged ahead of the
    # consumer (None = prefetch depth).  See docs/PERFORMANCE.md.
    pipeline_workers: int = 1
    pipeline_prefetch: int = 4
    pipeline_staging_depth: Optional[int] = None
    # Fault tolerance (resilience/, nn/checkpoint.py): ``ft_resume``
    # makes fit() auto-restore the newest valid checkpoint from the
    # attached CheckpointListener's directory (or ``ft_checkpoint_dir``)
    # and skip the already-trained prefix of the stream, so a crashed
    # run restarted with the same script converges like an
    # uninterrupted one.  ``ft_reader_retries`` retries transient
    # reader failures inside the input-pipeline feeder with exponential
    # backoff instead of surfacing them.  See docs/RESILIENCE.md.
    ft_resume: bool = False
    ft_reader_retries: int = 0
    ft_checkpoint_dir: Optional[str] = None
    # Sharded training (parallel/fsdp.py): ``sharding_enabled`` makes
    # fit() train FSDP-style on the device mesh — the batch shards over
    # data×fsdp, large params and their updater state shard over the
    # ``fsdp`` axis (ZeRO weight-update sharding: reduce-scatter grads →
    # per-shard updater → all-gather params, arXiv 2004.13336), arrays
    # under ``sharding_replicate_below`` elements stay replicated.
    # data=-1 means "all remaining devices".  Degrades to replica-style
    # on a single device or an unsatisfiable mesh.  TBPTT nets ignore
    # sharding (time-segmented stepping keeps replica semantics).
    sharding_enabled: bool = False
    sharding_data: int = -1
    sharding_fsdp: int = 1
    sharding_model: int = 1
    sharding_replicate_below: int = 2048
    # Elastic multi-host training (distributed/): ``dist_enabled`` makes
    # fit() train as one worker of a coordinator-backed cluster — each
    # global batch is shard-sliced by (rank, world) of the current
    # cluster generation, gradients all-reduce through the coordinator
    # barrier, and membership changes (a preempted worker, a returning
    # one) roll the generation and re-slice live.  ``dist_processes`` is
    # the initial formation size; ``dist_coordinator`` the coordinator
    # URL (the launcher exports DL4J_DIST_COORDINATOR instead).  Without
    # a reachable coordinator the conf is inert — single-process fit()
    # is byte-identical to a non-distributed one.  See
    # docs/DISTRIBUTED.md.
    dist_enabled: bool = False
    dist_processes: int = 0
    dist_coordinator: Optional[str] = None
    dist_heartbeat_ms: float = 250.0
    dist_lease_ms: float = 2000.0
    # Quantized gradient all-reduce (ops/quantize.py): 'int8' makes the
    # worker's barrier contribution int8 codes + per-block scales with a
    # persistent error-feedback residual (~4x fewer cross-host bytes;
    # the coordinator dequantizes per contribution before its rank-order
    # accumulation, so mixed fleets interoperate).  None = fp32 wire,
    # byte-identical to the pre-tier path.  DL4J_DIST_QUANT=0 kills it.
    dist_grad_quant: Optional[str] = None


_MERGE_FIELDS = [
    "activation", "weight_init", "bias_init", "dist", "learning_rate",
    "bias_learning_rate", "l1", "l2", "l1_bias", "l2_bias", "dropout",
    "use_drop_connect", "updater", "momentum", "rho", "rms_decay",
    "adam_mean_decay", "adam_var_decay", "epsilon",
    "gradient_normalization", "gradient_normalization_threshold",
]


def merge_layer_conf(layer: Layer, g: GlobalConf) -> Layer:
    """Fill a layer's unset (None) hyperparams from the global conf —
    the reference's global-then-override merge."""
    updates = {}
    for f in _MERGE_FIELDS:
        if getattr(layer, f, None) is None and hasattr(g, f):
            updates[f] = getattr(g, f)
    # L1/L2 are inert unless regularization is enabled (reference semantics:
    # per-layer values are ignored too when the flag is off).
    if not g.use_regularization:
        for f in ("l1", "l2", "l1_bias", "l2_bias"):
            updates[f] = 0.0
    return dataclasses.replace(layer, **{k: v for k, v in updates.items()
                                         if hasattr(layer, k)})


@dataclasses.dataclass
class MultiLayerConfiguration:
    """(ref: nn/conf/MultiLayerConfiguration.java)"""

    layers: List[Layer]
    global_conf: GlobalConf
    input_type: Optional[InputType] = None
    preprocessors: Dict[int, pp.InputPreProcessor] = dataclasses.field(default_factory=dict)
    backprop: bool = True
    pretrain: bool = False
    backprop_type: str = "standard"  # 'standard' | 'truncatedbptt'
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20

    # ---- serde (checkpoint parity: configuration.json) ----
    def to_dict(self) -> dict:
        return {
            "global": dataclasses.asdict(self.global_conf),
            "layers": [l.to_dict() for l in self.layers],
            "input_type": self.input_type.to_dict() if self.input_type else None,
            "preprocessors": {str(k): v.to_dict() for k, v in self.preprocessors.items()},
            "backprop": self.backprop,
            "pretrain": self.pretrain,
            "backprop_type": self.backprop_type,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
        }

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2)

    def to_yaml(self) -> str:
        """(ref: MultiLayerConfiguration.toYaml — Jackson YAML mapper)"""
        import yaml
        return yaml.safe_dump(self.to_dict(), sort_keys=False)

    @staticmethod
    def from_yaml(s: str) -> "MultiLayerConfiguration":
        import yaml
        return MultiLayerConfiguration.from_dict(yaml.safe_load(s))

    @staticmethod
    def from_dict(d: dict) -> "MultiLayerConfiguration":
        return MultiLayerConfiguration(
            layers=[Layer.from_dict(ld) for ld in d["layers"]],
            global_conf=GlobalConf(**d["global"]),
            input_type=InputType.from_dict(d["input_type"]) if d.get("input_type") else None,
            preprocessors={int(k): pp.InputPreProcessor.from_dict(v)
                           for k, v in d.get("preprocessors", {}).items()},
            backprop=d.get("backprop", True),
            pretrain=d.get("pretrain", False),
            backprop_type=d.get("backprop_type", "standard"),
            tbptt_fwd_length=d.get("tbptt_fwd_length", 20),
            tbptt_back_length=d.get("tbptt_back_length", 20),
        )

    @staticmethod
    def from_json(s: str) -> "MultiLayerConfiguration":
        return MultiLayerConfiguration.from_dict(json.loads(s))


class NeuralNetConfiguration:
    """Entry point: ``NeuralNetConfiguration.builder()`` — the reference's
    fluent DSL (ref: nn/conf/NeuralNetConfiguration.java Builder)."""

    @staticmethod
    def builder() -> "Builder":
        return Builder()


class Builder:
    def __init__(self):
        self._g = GlobalConf()

    # Fluent setters — names follow the reference's builder methods.
    def seed(self, s):
        self._g.seed = int(s); return self

    def iterations(self, n):
        self._g.iterations = int(n); return self

    def learning_rate(self, lr):
        self._g.learning_rate = float(lr); return self

    def bias_learning_rate(self, lr):
        self._g.bias_learning_rate = float(lr); return self

    def updater(self, u: str):
        self._g.updater = u.lower(); return self

    def momentum(self, m):
        self._g.momentum = float(m); return self

    def rho(self, r):
        self._g.rho = float(r); return self

    def rms_decay(self, r):
        self._g.rms_decay = float(r); return self

    def adam_mean_decay(self, b):
        self._g.adam_mean_decay = float(b); return self

    def adam_var_decay(self, b):
        self._g.adam_var_decay = float(b); return self

    def epsilon(self, e):
        self._g.epsilon = float(e); return self

    def activation(self, a: str):
        self._g.activation = a; return self

    def weight_init(self, w: str):
        self._g.weight_init = w; return self

    def bias_init(self, b):
        self._g.bias_init = float(b); return self

    def dist(self, d: dict):
        self._g.dist = d; return self

    def regularization(self, on: bool = True):
        self._g.use_regularization = bool(on); return self

    def l1(self, v):
        self._g.l1 = float(v); return self

    def l2(self, v):
        self._g.l2 = float(v); return self

    def drop_out(self, v):
        self._g.dropout = float(v); return self

    def use_drop_connect(self, on: bool = True):
        """Reuse the dropout probability on weights instead of activations
        (ref: NeuralNetConfiguration.Builder.useDropConnect /
        util/Dropout.java applyDropConnect)."""
        self._g.use_drop_connect = bool(on); return self

    def minimize(self, on: bool = True):
        self._g.minimize = bool(on); return self

    def mini_batch(self, on: bool = True):
        self._g.mini_batch = bool(on); return self

    def optimization_algo(self, algo: str):
        self._g.optimization_algo = algo.lower(); return self

    def gradient_normalization(self, mode: str, threshold: float = 1.0):
        self._g.gradient_normalization = mode
        self._g.gradient_normalization_threshold = float(threshold)
        return self

    _UNSET = object()

    def precision(self, p=_UNSET, *, compute: Optional[str] = None,
                  infer_quant=_UNSET, grad_allreduce=_UNSET):
        """Precision tiers (docs/PERFORMANCE.md "Precision tiers").

        ``compute`` (or the positional ``p``): mixed-precision policy
        for the compiled step — 'bfloat16' (TPU fast path: bf16
        activations/matmuls, f32 master weights, f32 accumulation),
        'float32', 'float64', or None/'auto' (bf16 on TPU, f32
        elsewhere).  ``infer_quant``: 'int8' | 'fp8' weight-only
        quantized serving (dequant-in-trace, ~4x smaller resident
        weights).  ``grad_allreduce``: 'int8' block-quantized
        error-feedback gradient collectives for distributed fit.
        Every tier is byte-identical to the dense path when unset."""
        if compute is not None:
            self._g.precision = compute
        elif p is not Builder._UNSET:
            self._g.precision = p
        if infer_quant is not Builder._UNSET:
            self._g.precision_infer_quant = infer_quant
        if grad_allreduce is not Builder._UNSET:
            self._g.dist_grad_quant = grad_allreduce
        return self

    def gradient_checkpointing(self, on: bool = True):
        """Recompute layer forwards in the backward pass (jax.checkpoint)
        — trades ~33% more FLOPs for O(depth) less activation HBM, the
        standard remat recipe for deep nets on TPU."""
        self._g.gradient_checkpointing = bool(on)
        return self

    def shape_bucketing(self, on: bool = True, batch_sizes=None,
                        time_sizes=None):
        """Pad ragged batch/time dims up to a bucket ladder (powers of
        two unless given) so every jitted path compiles once per bucket
        — see ops/bucketing.py and docs/PERFORMANCE.md."""
        self._g.shape_bucketing = bool(on)
        if batch_sizes is not None:
            self._g.bucket_batch_sizes = [int(s) for s in batch_sizes]
        if time_sizes is not None:
            self._g.bucket_time_sizes = [int(s) for s in time_sizes]
        return self

    def input_pipeline(self, workers: Optional[int] = None,
                       prefetch: Optional[int] = None,
                       staging_depth: Optional[int] = None):
        """Tune the async input pipeline the fit loops wrap iterators
        with: ``workers`` parallel ETL threads (0 disables the wrapper),
        ``prefetch`` raw batches queued ahead, ``staging_depth`` device-
        resident batches staged ahead of the consumer."""
        if workers is not None:
            self._g.pipeline_workers = int(workers)
        if prefetch is not None:
            self._g.pipeline_prefetch = int(prefetch)
        if staging_depth is not None:
            self._g.pipeline_staging_depth = int(staging_depth)
        return self

    def fault_tolerance(self, resume: Optional[bool] = None,
                        reader_retries: Optional[int] = None,
                        checkpoint_dir=None):
        """Crash-safe training (docs/RESILIENCE.md): ``resume=True``
        auto-restores fit() from the newest valid checkpoint (written
        by an attached ``CheckpointListener``, or found in
        ``checkpoint_dir``) and replays the input stream past the
        already-trained prefix; ``reader_retries=N`` retries transient
        reader failures in the input-pipeline feeder up to N times with
        seeded exponential backoff before surfacing them."""
        if resume is not None:
            self._g.ft_resume = bool(resume)
        if reader_retries is not None:
            self._g.ft_reader_retries = max(0, int(reader_retries))
        if checkpoint_dir is not None:
            self._g.ft_checkpoint_dir = str(checkpoint_dir)
        return self

    def sharding(self, data: Optional[int] = None,
                 fsdp: Optional[int] = None,
                 model: Optional[int] = None,
                 replicate_below: Optional[int] = None,
                 enabled: bool = True):
        """Promote fit() to sharded (FSDP/ZeRO) training on the device
        mesh (docs/PERFORMANCE.md "Sharded training"): the global batch
        shards over ``data``×``fsdp`` devices, large weight matrices AND
        their updater state shard over ``fsdp`` (reduce-scatter grads →
        per-shard updater update → all-gather params inside the one
        compiled step), ``model`` adds Megatron-style tensor
        parallelism, and arrays under ``replicate_below`` elements
        (biases, BN stats) stay replicated.  ``data=-1`` (default)
        takes all remaining devices.  On a single device or an
        unsatisfiable mesh the conf is inert — fit() stays
        replica-style with identical numerics."""
        self._g.sharding_enabled = bool(enabled)
        if data is not None:
            self._g.sharding_data = int(data)
        if fsdp is not None:
            self._g.sharding_fsdp = int(fsdp)
        if model is not None:
            self._g.sharding_model = int(model)
        if replicate_below is not None:
            self._g.sharding_replicate_below = max(0, int(replicate_below))
        return self

    def distributed(self, processes: Optional[int] = None,
                    coordinator: Optional[str] = None,
                    heartbeat_ms: Optional[float] = None,
                    lease_ms: Optional[float] = None,
                    enabled: bool = True):
        """Route fit() through the elastic multi-worker cluster runtime
        (docs/DISTRIBUTED.md) — the modern equivalent of the reference's
        Spark ``TrainingMaster`` tier: N workers (usually spawned by
        ``python -m deeplearning4j_tpu.distributed.launch``) slice each
        global batch by their generation's (rank, world), all-reduce
        gradients through the coordinator barrier, tolerate preemption
        (survivors continue on N−1 within the run) and absorb returning
        workers from an in-memory state snapshot.  ``processes`` is the
        initial formation size; ``coordinator`` overrides the
        ``DL4J_DIST_COORDINATOR`` env the launcher exports.  Without a
        coordinator the conf is inert (replica semantics)."""
        self._g.dist_enabled = bool(enabled)
        if processes is not None:
            self._g.dist_processes = max(0, int(processes))
        if coordinator is not None:
            self._g.dist_coordinator = str(coordinator)
        if heartbeat_ms is not None:
            self._g.dist_heartbeat_ms = float(heartbeat_ms)
        if lease_ms is not None:
            self._g.dist_lease_ms = float(lease_ms)
        return self

    def data_type(self, p: Optional[str]):  # reference-style alias
        return self.precision(p)

    def learning_rate_policy(self, policy: str, decay_rate=None, steps=None,
                             power=None, schedule: Optional[dict] = None):
        self._g.lr_policy = policy
        self._g.lr_policy_decay_rate = decay_rate
        self._g.lr_policy_steps = steps
        self._g.lr_policy_power = power
        self._g.learning_rate_schedule = schedule
        return self

    def list(self) -> "ListBuilder":
        return ListBuilder(self._g)


class ListBuilder:
    """(ref: NeuralNetConfiguration.ListBuilder / MultiLayerConfiguration.Builder)"""

    def __init__(self, g: GlobalConf):
        self._g = g
        self._layers: List[Layer] = []
        self._input_type: Optional[InputType] = None
        self._preprocs: Dict[int, pp.InputPreProcessor] = {}
        self._backprop = True
        self._pretrain = False
        self._bp_type = "standard"
        self._tbptt_f = 20
        self._tbptt_b = 20

    def layer(self, idx_or_layer, layer: Optional[Layer] = None) -> "ListBuilder":
        if layer is None:
            self._layers.append(idx_or_layer)
        else:
            idx = int(idx_or_layer)
            while len(self._layers) <= idx:
                self._layers.append(None)  # type: ignore
            self._layers[idx] = layer
        return self

    def set_input_type(self, it: InputType) -> "ListBuilder":
        self._input_type = it
        return self

    def input_pre_processor(self, idx: int, proc: pp.InputPreProcessor) -> "ListBuilder":
        self._preprocs[idx] = proc
        return self

    def backprop(self, on: bool) -> "ListBuilder":
        self._backprop = on
        return self

    def pretrain(self, on: bool) -> "ListBuilder":
        self._pretrain = on
        return self

    def backprop_type(self, t: str) -> "ListBuilder":
        self._bp_type = t.lower()
        return self

    def t_bptt_forward_length(self, n: int) -> "ListBuilder":
        self._tbptt_f = int(n)
        return self

    def t_bptt_backward_length(self, n: int) -> "ListBuilder":
        self._tbptt_b = int(n)
        return self

    def build(self) -> MultiLayerConfiguration:
        if any(l is None for l in self._layers):
            raise ValueError("Gap in layer indices")
        layers = [merge_layer_conf(l, self._g) for l in self._layers]
        preprocs = dict(self._preprocs)
        if self._input_type is not None:
            layers, preprocs = _infer_shapes(layers, self._input_type, preprocs)
        return MultiLayerConfiguration(
            layers=layers, global_conf=self._g, input_type=self._input_type,
            preprocessors=preprocs, backprop=self._backprop,
            pretrain=self._pretrain, backprop_type=self._bp_type,
            tbptt_fwd_length=self._tbptt_f, tbptt_back_length=self._tbptt_b)


def _needs(layer: Layer) -> str:
    """Which input family a layer consumes: 'ff' | 'cnn' | 'rnn' | 'any'."""
    from deeplearning4j_tpu.nn.conf import layers as L
    if isinstance(layer, (L.ConvolutionLayer, L.SubsamplingLayer,
                          L.ZeroPaddingLayer, L.LocalResponseNormalization)):
        return "cnn"
    if isinstance(layer, (L.GravesLSTM, L.GravesBidirectionalLSTM, L.RnnOutputLayer)):
        return "rnn"
    if isinstance(layer, L.DenseLayer):
        return "ff"
    # an EmbeddingLayer takes [N] indices or an [N, T] id sequence
    return "any"


def _adapter(cur: InputType, needed: str) -> Optional[pp.InputPreProcessor]:
    if needed == "any" or cur.kind == needed or (needed == "ff" and cur.kind == "cnnflat"):
        return None
    if cur.kind == "cnn" and needed == "ff":
        return pp.CnnToFeedForwardPreProcessor(cur.height, cur.width, cur.channels)
    if cur.kind == "cnnflat" and needed == "cnn":
        return pp.FeedForwardToCnnPreProcessor(cur.height, cur.width, cur.channels)
    if cur.kind == "ff" and needed == "rnn":
        return pp.FeedForwardToRnnPreProcessor(cur.timesteps)
    if cur.kind == "rnn" and needed == "ff":
        return pp.RnnToFeedForwardPreProcessor()
    if cur.kind == "cnn" and needed == "rnn":
        return pp.CnnToRnnPreProcessor()
    if cur.kind == "rnn" and needed == "cnn":
        raise ValueError("RnnToCnn requires explicit preprocessor with target shape")
    raise ValueError(f"No automatic preprocessor from {cur.kind} to {needed}")


def _infer_shapes(layers: List[Layer], input_type: InputType,
                  preprocs: Dict[int, pp.InputPreProcessor]):
    """Walk the stack inferring nIn and inserting preprocessors — the
    reference's setInputType pass (MultiLayerConfiguration.Builder)."""
    cur = input_type
    out_layers = []
    for i, layer in enumerate(layers):
        if i not in preprocs:
            adapter = _adapter(cur, _needs(layer))
            if adapter is not None:
                preprocs[i] = adapter
        if i in preprocs:
            cur = preprocs[i].output_type(cur)
        updates = {}
        if hasattr(layer, "n_in") and getattr(layer, "n_in") is None:
            updates["n_in"] = cur.flat_size() if cur.kind != "cnn" else cur.channels
        if isinstance(layer, BatchNormalization) and layer.n_features is None:
            updates["n_features"] = cur.channels if cur.kind == "cnn" else cur.flat_size()
        if updates:
            layer = dataclasses.replace(layer, **updates)
        out_layers.append(layer)
        cur = layer.output_type(cur)
    return out_layers, preprocs
