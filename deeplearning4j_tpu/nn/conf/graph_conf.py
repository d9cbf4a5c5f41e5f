"""ComputationGraph configuration: DAG of vertices.

(ref: nn/conf/ComputationGraphConfiguration.java (750 LoC),
nn/graph/vertex/impl/{LayerVertex, MergeVertex, ElementWiseVertex,
StackVertex, UnstackVertex, SubsetVertex, ScaleVertex, ShiftVertex,
L2Vertex, L2NormalizeVertex, PreprocessorVertex}.java and
rnn/{LastTimeStepVertex, DuplicateToTimeSeriesVertex}.java)

Each vertex is a dataclass with ``initialize`` (params/state) and
``forward(params, state, inputs, ...)`` over a LIST of input arrays —
the whole DAG traces into one XLA computation in topological order.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import Layer
from deeplearning4j_tpu.nn.conf.network import GlobalConf, merge_layer_conf
from deeplearning4j_tpu.nn.conf import preprocessors as pp
from deeplearning4j_tpu.ops.recompute import keeping_offers

VERTEX_REGISTRY: Dict[str, type] = {}


def register_vertex(cls):
    VERTEX_REGISTRY[cls.__name__] = cls
    return cls


@dataclasses.dataclass
class GraphVertexConf:
    #: as ``Layer.scope_parts`` / ``Layer.scope_kernels``: what
    #: monitor/profile.py reads of a vertex that names parts in its scope
    scope_parts = ()
    scope_kernels = {}

    def initialize(self, key, input_types: List[InputType], dtype=jnp.float32
                   ) -> Tuple[dict, dict, InputType]:
        return {}, {}, self.output_type(input_types)

    def forward(self, params, state, inputs: List, *, train, rng, masks=None):
        raise NotImplementedError

    def output_type(self, input_types: List[InputType]) -> InputType:
        raise NotImplementedError

    def output_mask(self, masks):
        return masks[0] if masks else None

    def has_params(self) -> bool:
        return False

    def updater_layer(self) -> Optional[Layer]:
        """The layer conf whose updater fields rule this vertex's leaves;
        None (a vertex without leaves) takes plain SGD."""
        return None

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["@class"] = type(self).__name__
        return d

    @staticmethod
    def from_dict(d: dict) -> "GraphVertexConf":
        d = dict(d)
        cls = VERTEX_REGISTRY[d.pop("@class")]
        return cls(**d)


@register_vertex
@dataclasses.dataclass
class LayerVertex(GraphVertexConf):
    """Wraps a layer config (ref: nn/graph/vertex/impl/LayerVertex.java)."""

    layer: Optional[dict] = None  # serialized Layer

    def layer_conf(self) -> Layer:
        return Layer.from_dict(self.layer)

    def has_params(self):
        return self.layer_conf().has_params()

    def initialize(self, key, input_types, dtype=jnp.float32):
        p, s, out = self.layer_conf().initialize(key, input_types[0], dtype)
        return p, s, out

    def forward(self, params, state, inputs, *, train, rng, masks=None):
        mask = masks[0] if masks else None
        y, ns, m = self.layer_conf().forward(params, state, inputs[0],
                                             train=train, rng=rng, mask=mask)
        return y, ns, m

    def output_type(self, input_types):
        return self.layer_conf().output_type(input_types[0])

    @staticmethod
    def of(layer: Layer) -> "LayerVertex":
        return LayerVertex(layer=layer.to_dict())


@register_vertex
@dataclasses.dataclass
class MergeVertex(GraphVertexConf):
    """Concatenate along the feature axis (ref: MergeVertex.java) —
    axis 1 for FF/CNN(NCHW), axis 2 for RNN [N,T,C]."""

    def forward(self, params, state, inputs, *, train, rng, masks=None):
        axis = 2 if inputs[0].ndim == 3 else 1
        return jnp.concatenate(inputs, axis=axis), state, self.output_mask(masks)

    def output_type(self, input_types):
        t = input_types[0]
        if t.kind == "cnn":
            return InputType.convolutional(t.height, t.width,
                                           sum(i.channels for i in input_types))
        if t.kind == "rnn":
            return InputType.recurrent(sum(i.size for i in input_types), t.timesteps)
        return InputType.feed_forward(sum(i.flat_size() for i in input_types))


@register_vertex
@dataclasses.dataclass
class ElementWiseVertex(GraphVertexConf):
    """(ref: ElementWiseVertex.java) op: add|subtract|product|average|max."""

    op: str = "add"

    def forward(self, params, state, inputs, *, train, rng, masks=None):
        op = self.op.lower()
        if op == "add":
            out = sum(inputs[1:], inputs[0])
        elif op == "subtract":
            out = inputs[0] - inputs[1]
        elif op in ("product", "mul"):
            out = inputs[0]
            for i in inputs[1:]:
                out = out * i
        elif op in ("average", "avg"):
            out = sum(inputs[1:], inputs[0]) / len(inputs)
        elif op == "max":
            out = jnp.stack(inputs).max(axis=0)
        else:
            raise ValueError(f"Unknown ElementWise op '{self.op}'")
        return out, state, self.output_mask(masks)

    def output_type(self, input_types):
        return input_types[0]


@register_vertex
@dataclasses.dataclass
class StackVertex(GraphVertexConf):
    """Stack along batch dim (ref: StackVertex.java)."""

    def forward(self, params, state, inputs, *, train, rng, masks=None):
        m = None
        if masks and any(mm is not None for mm in masks):
            ref = next(mm for mm in masks if mm is not None)
            # branches with no mask contribute all-ones (fully valid)
            filled = [mm if mm is not None
                      else jnp.ones((x.shape[0],) + ref.shape[1:], ref.dtype)
                      for mm, x in zip(masks, inputs)]
            m = jnp.concatenate(filled, axis=0)
        return jnp.concatenate(inputs, axis=0), state, m

    def output_type(self, input_types):
        return input_types[0]


@register_vertex
@dataclasses.dataclass
class UnstackVertex(GraphVertexConf):
    """Take slice `from_idx` of `stack_size` equal batch chunks
    (ref: UnstackVertex.java)."""

    from_idx: int = 0
    stack_size: int = 1

    def forward(self, params, state, inputs, *, train, rng, masks=None):
        x = inputs[0]
        step = x.shape[0] // self.stack_size
        sl = slice(self.from_idx * step, (self.from_idx + 1) * step)
        m = masks[0][sl] if (masks and masks[0] is not None) else None
        return x[sl], state, m

    def output_type(self, input_types):
        return input_types[0]


@register_vertex
@dataclasses.dataclass
class SubsetVertex(GraphVertexConf):
    """Feature-range subset [from, to] inclusive (ref: SubsetVertex.java)."""

    from_idx: int = 0
    to_idx: int = 0

    def forward(self, params, state, inputs, *, train, rng, masks=None):
        x = inputs[0]
        sl = slice(self.from_idx, self.to_idx + 1)
        if x.ndim == 3:
            out = x[:, :, sl]
        elif x.ndim == 4:
            out = x[:, sl]
        else:
            out = x[:, sl]
        return out, state, self.output_mask(masks)

    def output_type(self, input_types):
        n = self.to_idx - self.from_idx + 1
        t = input_types[0]
        if t.kind == "rnn":
            return InputType.recurrent(n, t.timesteps)
        if t.kind == "cnn":
            return InputType.convolutional(t.height, t.width, n)
        return InputType.feed_forward(n)


@register_vertex
@dataclasses.dataclass
class ScaleVertex(GraphVertexConf):
    """(ref: ScaleVertex.java)"""

    scale: float = 1.0

    def forward(self, params, state, inputs, *, train, rng, masks=None):
        return inputs[0] * self.scale, state, self.output_mask(masks)

    def output_type(self, input_types):
        return input_types[0]


@register_vertex
@dataclasses.dataclass
class ShiftVertex(GraphVertexConf):
    """(ref: ShiftVertex.java)"""

    shift: float = 0.0

    def forward(self, params, state, inputs, *, train, rng, masks=None):
        return inputs[0] + self.shift, state, self.output_mask(masks)

    def output_type(self, input_types):
        return input_types[0]


@register_vertex
@dataclasses.dataclass
class L2Vertex(GraphVertexConf):
    """Pairwise L2 distance between two inputs → [N, 1] (ref: L2Vertex.java)."""

    eps: float = 1e-8

    def forward(self, params, state, inputs, *, train, rng, masks=None):
        a, b = inputs[0], inputs[1]
        d = a.reshape(a.shape[0], -1) - b.reshape(b.shape[0], -1)
        out = jnp.sqrt(jnp.sum(d * d, axis=1, keepdims=True) + self.eps)
        return out, state, None

    def output_type(self, input_types):
        return InputType.feed_forward(1)


@register_vertex
@dataclasses.dataclass
class L2NormalizeVertex(GraphVertexConf):
    """x / ||x||_2 per example (ref: L2NormalizeVertex.java)."""

    eps: float = 1e-8

    def forward(self, params, state, inputs, *, train, rng, masks=None):
        x = inputs[0]
        flat = x.reshape(x.shape[0], -1)
        norm = jnp.linalg.norm(flat, axis=1, keepdims=True)
        out = (flat / (norm + self.eps)).reshape(x.shape)
        return out, state, self.output_mask(masks)

    def output_type(self, input_types):
        return input_types[0]


@register_vertex
@dataclasses.dataclass
class PreprocessorVertex(GraphVertexConf):
    """Standalone InputPreProcessor as a vertex (ref: PreprocessorVertex.java)."""

    preprocessor: Optional[dict] = None

    def forward(self, params, state, inputs, *, train, rng, masks=None):
        proc = pp.InputPreProcessor.from_dict(self.preprocessor)
        m = masks[0] if masks else None
        y, m = proc(inputs[0], m)
        return y, state, m

    def output_type(self, input_types):
        return pp.InputPreProcessor.from_dict(self.preprocessor).output_type(input_types[0])

    @staticmethod
    def of(proc: pp.InputPreProcessor) -> "PreprocessorVertex":
        return PreprocessorVertex(preprocessor=proc.to_dict())


@register_vertex
@dataclasses.dataclass
class LastTimeStepVertex(GraphVertexConf):
    """[N,T,C] → [N,C] at the last unmasked step
    (ref: rnn/LastTimeStepVertex.java); mask comes from the named input."""

    mask_input: Optional[str] = None

    def forward(self, params, state, inputs, *, train, rng, masks=None):
        x = inputs[0]
        mask = masks[0] if masks else None
        if mask is None:
            out = x[:, -1]
        else:
            idx = jnp.maximum(jnp.sum(mask > 0, axis=1).astype(jnp.int32) - 1, 0)
            out = x[jnp.arange(x.shape[0]), idx]
        return out, state, None

    def output_type(self, input_types):
        return InputType.feed_forward(input_types[0].size)


@register_vertex
@dataclasses.dataclass
class TimeRangeVertex(GraphVertexConf):
    """Time steps ``from_step`` up to, not including, ``to_step`` (None:
    to the end) of [N, T, C], with the same steps of the mask: what is
    scored when only part of a sequence's rows carry a loss (the noised
    half of ``[x_t ; x0]``), ahead of the final norm and the head, so
    that no logits exist for the rest.  No reference analog
    (``SubsetVertex`` cuts features, ``LastTimeStepVertex`` keeps one
    step)."""

    from_step: int = 0
    to_step: Optional[int] = None

    def forward(self, params, state, inputs, *, train, rng, masks=None):
        sl = slice(self.from_step, self.to_step)
        mask = masks[0] if masks else None
        return (inputs[0][:, sl], state,
                None if mask is None else mask[:, sl])

    def output_type(self, input_types):
        t = input_types[0]
        n = None if t.timesteps is None else len(
            range(t.timesteps)[self.from_step:self.to_step])
        return InputType.recurrent(t.size, n)


@register_vertex
@dataclasses.dataclass
class DuplicateToTimeSeriesVertex(GraphVertexConf):
    """[N,C] → [N,T,C] by duplication; T from a reference input
    (ref: rnn/DuplicateToTimeSeriesVertex.java).  The engine passes the
    reference sequence as inputs[1]."""

    ts_input: Optional[str] = None

    def forward(self, params, state, inputs, *, train, rng, masks=None):
        x = inputs[0]
        T = inputs[1].shape[1]
        out = jnp.broadcast_to(x[:, None, :], (x.shape[0], T, x.shape[-1]))
        m = masks[1] if masks and len(masks) > 1 else None
        return out, state, m

    def output_type(self, input_types):
        t = input_types[1].timesteps if len(input_types) > 1 else None
        return InputType.recurrent(input_types[0].flat_size(), t)


@register_vertex
@dataclasses.dataclass
class ReshapeVertex(GraphVertexConf):
    """Reshape trailing dims, batch preserved (ref: ReshapeVertex.java)."""

    shape: Optional[tuple] = None  # new shape excluding batch

    def forward(self, params, state, inputs, *, train, rng, masks=None):
        x = inputs[0]
        return x.reshape((x.shape[0],) + tuple(self.shape)), state, self.output_mask(masks)

    def output_type(self, input_types):
        import math
        n = math.prod(self.shape)
        if len(self.shape) == 3:
            return InputType.convolutional(self.shape[1], self.shape[2], self.shape[0])
        return InputType.feed_forward(n)


@register_vertex
@dataclasses.dataclass
class LoopVertex(GraphVertexConf):
    """A body of vertices run ``passes`` times over THE SAME leaves:
    ``h_0 = x;  h_r = Body(h_{r-1}), r = 1..passes``; the output is every
    pass's, stacked ``[passes, ...]`` (for a sequence ``[R, N, T, C]``).

    ``body`` is a whole graph configuration (serialized) with one input,
    the carried value, and one output of the same shape and type.  The
    vertex holds the body's leaves once, under ``"<body vertex>/<leaf>"``,
    so the engine's updater, parameter count, summary and checkpoints
    see each leaf once, as any vertex's; every pass reads them and their
    gradient is the sum over the passes.  The step holds the body once:
    the passes are a ``lax.scan``.  The body's leaves take the body's
    global updater (no per-layer updater, l1 or l2 inside a loop), and a
    body vertex that keeps state (batch-norm statistics, expert counts)
    is refused: which pass's would it be.

    ``recompute_blocks`` declares recomputation where the loop is
    declared: the body's vertices in consecutive runs (a block of a
    decoder, say), each run recomputed in the backward pass from what it
    was handed.  A block keeps one input a pass and what its layers
    offer (``ops/recompute.py``: the flash attention core's output with
    its row statistics, a gated MLP's output where a norm reads it),
    each only where the backward pass reads it: 2 x ``[N, T, C]`` a
    block a pass at most beside the input, for the attention kernel
    and the MLP's last product not run a second time.  None keeps every
    activation.  The global ``gradient_checkpointing`` flag would keep
    the loop's input alone and recompute all the passes in one piece.

    The carried decode step cannot run a loop: each pass of each
    attention layer would need a cache of its own."""

    scope_parts = ("body",)

    body: Optional[dict] = None
    passes: int = 1
    recompute_blocks: Optional[list] = None

    @staticmethod
    def of(body: "ComputationGraphConfiguration", passes: int,
           recompute_blocks=None) -> "LoopVertex":
        if len(body.network_inputs) != 1 or len(body.network_outputs) != 1:
            raise ValueError("LoopVertex: the body takes the carried value "
                             "and hands on its next: one input, one output")
        v = LoopVertex(body=body.to_dict(), passes=int(passes),
                       recompute_blocks=recompute_blocks)
        v._runs()       # validate early
        return v

    def body_conf(self) -> "ComputationGraphConfiguration":
        conf = self.__dict__.get("_parsed")
        if conf is None:
            conf = self.__dict__["_parsed"] = \
                ComputationGraphConfiguration.from_dict(self.body)
        return conf

    def infer_body(self, input_types) -> None:
        """Fill the body's unset n_in from the loop's input type (the
        outer graph's pass calls this)."""
        conf = ComputationGraphConfiguration.from_dict(self.body)
        conf.input_types = [input_types[0]]
        _infer_graph_nin(conf)
        self.body = conf.to_dict()
        self.__dict__.pop("_parsed", None)

    def _runs(self) -> List[List[str]]:
        """The body's vertices as the runs one pass takes them in."""
        order = self.body_conf().topological_order()
        if self.passes < 1:
            raise ValueError(f"LoopVertex: passes={self.passes}")
        if any("/" in n for n in order):
            raise ValueError("LoopVertex: a body vertex's name may not "
                             "hold '/' (it separates vertex from leaf)")
        if not self.recompute_blocks:
            return [order]
        runs = [list(r) for r in self.recompute_blocks]
        flat = [n for r in runs for n in r]
        if sorted(flat) != sorted(order):
            raise ValueError("LoopVertex: recompute_blocks must name every "
                             "body vertex once")
        seen = set(self.body_conf().network_inputs)
        for n in flat:      # a run may use only what came before it
            ins = self.body_conf().vertex_inputs[n]
            if any(i not in seen for i in ins):
                raise ValueError(f"LoopVertex: recompute_blocks out of "
                                 f"order at '{n}' (inputs {ins})")
            seen.add(n)
        return runs

    def has_params(self):
        return any(v.has_params() for v in self.body_conf().vertices.values())

    def updater_layer(self):
        return merge_layer_conf(Layer(), self.body_conf().global_conf)

    def output_type(self, input_types):
        return input_types[0]

    def initialize(self, key, input_types, dtype=jnp.float32):
        conf = self.body_conf()
        types = {conf.network_inputs[0]: input_types[0]}
        params = {}
        for name in conf.topological_order():
            key, sub = jax.random.split(key)
            p, s, types[name] = conf.vertices[name].initialize(
                sub, [types[i] for i in conf.vertex_inputs[name]], dtype)
            if s:
                raise ValueError(
                    f"LoopVertex: body vertex '{name}' keeps state "
                    f"({sorted(s)}); a loop's body may not")
            params.update({f"{name}/{k}": a for k, a in p.items()})
        out = types[conf.network_outputs[0]]
        if out != input_types[0]:
            raise ValueError(f"LoopVertex: the body turns {input_types[0]} "
                             f"into {out}; a pass must hand on what it took")
        return (params, {"loop_passes": jnp.zeros((), jnp.int32)},
                input_types[0])

    def forward(self, params, state, inputs, *, train, rng, masks=None):
        from deeplearning4j_tpu.parallel import sequence as seq_ops
        if seq_ops.kv_decode_active() and not train:
            raise NotImplementedError(
                "LoopVertex: the carried decode step cannot run a looped "
                "stack (a cache per pass per layer)")
        conf = self.body_conf()
        carried, out_name = conf.network_inputs[0], conf.network_outputs[0]
        mask = masks[0] if masks else None
        leaves: Dict[str, dict] = {n: {} for n in conf.vertices}
        for path, a in params.items():
            name, _, k = path.partition("/")
            leaves[name][k] = a
        index = {n: i for i, n in enumerate(conf.topological_order())}
        runs = self._runs()
        recompute = train and bool(self.recompute_blocks)

        def run_fn(names):
            inside = set(names)
            later = {i for n in index if n not in inside
                     for i in conf.vertex_inputs[n]} | {out_name}
            gives = [n for n in names if n in later]

            def run(p, acts, r):
                acts = dict(acts)
                for name in names:
                    v = conf.vertices[name]
                    ins = [acts[i] for i in conf.vertex_inputs[name]]
                    kind = type(v.layer_conf() if isinstance(v, LayerVertex)
                                else v).__name__
                    with jax.named_scope(f"{kind}/{name}"):
                        acts[name], _, _ = v.forward(
                            p[name], {}, ins, train=train,
                            rng=jax.random.fold_in(r, index[name]),
                            masks=[mask] * len(ins))
                return {n: acts[n] for n in gives}

            needs = [i for i in dict.fromkeys(
                i for n in names for i in conf.vertex_inputs[n])
                if i not in inside]
            return needs, (keeping_offers(run) if recompute else run)

        fns = [(names,) + run_fn(names) for names in runs]

        def one_pass(x, i):
            r = jax.random.fold_in(rng, i)
            acts = {carried: x}
            for names, needs, fn in fns:
                acts.update(fn({n: leaves[n] for n in names},
                               {n: acts[n] for n in needs}, r))
            y = acts[out_name].astype(x.dtype)
            return y, y

        with jax.named_scope("body"):
            _, ys = jax.lax.scan(one_pass, inputs[0],
                                 jnp.arange(self.passes))
        return (ys, {**state, "loop_passes": jnp.asarray(self.passes,
                                                         jnp.int32)}, mask)


# ==========================================================================
# Configuration + builder
# ==========================================================================

@dataclasses.dataclass
class ComputationGraphConfiguration:
    """(ref: nn/conf/ComputationGraphConfiguration.java)"""

    network_inputs: List[str]
    network_outputs: List[str]
    vertices: Dict[str, GraphVertexConf]
    vertex_inputs: Dict[str, List[str]]
    global_conf: GlobalConf
    input_types: Optional[List[InputType]] = None
    backprop_type: str = "standard"
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20

    def topological_order(self) -> List[str]:
        """Kahn's algorithm over vertex dependencies
        (ref: ComputationGraph.topologicalOrder :122)."""
        indeg = {name: 0 for name in self.vertices}
        for name, ins in self.vertex_inputs.items():
            indeg[name] = sum(1 for i in ins if i in self.vertices)
        ready = sorted([n for n, d in indeg.items() if d == 0])
        order = []
        consumers: Dict[str, List[str]] = {}
        for name, ins in self.vertex_inputs.items():
            for i in ins:
                if i in self.vertices:
                    consumers.setdefault(i, []).append(name)
        while ready:
            n = ready.pop(0)
            order.append(n)
            for c in sorted(consumers.get(n, [])):
                indeg[c] -= 1
                if indeg[c] == 0:
                    ready.append(c)
        if len(order) != len(self.vertices):
            raise ValueError("Cycle detected in ComputationGraph")
        return order

    def to_dict(self) -> dict:
        return {
            "global": dataclasses.asdict(self.global_conf),
            "network_inputs": self.network_inputs,
            "network_outputs": self.network_outputs,
            "vertices": {k: v.to_dict() for k, v in self.vertices.items()},
            "vertex_inputs": self.vertex_inputs,
            "input_types": ([t.to_dict() for t in self.input_types]
                            if self.input_types else None),
            "backprop_type": self.backprop_type,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
        }

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2)

    def to_yaml(self) -> str:
        """(ref: ComputationGraphConfiguration.toYaml)"""
        import yaml
        return yaml.safe_dump(self.to_dict(), sort_keys=False)

    @staticmethod
    def from_yaml(s: str) -> "ComputationGraphConfiguration":
        import yaml
        return ComputationGraphConfiguration.from_dict(yaml.safe_load(s))

    @staticmethod
    def from_dict(d: dict) -> "ComputationGraphConfiguration":
        return ComputationGraphConfiguration(
            network_inputs=list(d["network_inputs"]),
            network_outputs=list(d["network_outputs"]),
            vertices={k: GraphVertexConf.from_dict(v)
                      for k, v in d["vertices"].items()},
            vertex_inputs={k: list(v) for k, v in d["vertex_inputs"].items()},
            global_conf=GlobalConf(**d["global"]),
            input_types=([InputType.from_dict(t) for t in d["input_types"]]
                         if d.get("input_types") else None),
            backprop_type=d.get("backprop_type", "standard"),
            tbptt_fwd_length=d.get("tbptt_fwd_length", 20),
            tbptt_back_length=d.get("tbptt_back_length", 20),
        )

    @staticmethod
    def from_json(s: str) -> "ComputationGraphConfiguration":
        return ComputationGraphConfiguration.from_dict(json.loads(s))


class GraphBuilder:
    """(ref: ComputationGraphConfiguration.GraphBuilder via
    NeuralNetConfiguration.Builder.graphBuilder())"""

    def __init__(self, g: Optional[GlobalConf] = None):
        self._g = g or GlobalConf()
        self._inputs: List[str] = []
        self._outputs: List[str] = []
        self._vertices: Dict[str, GraphVertexConf] = {}
        self._vertex_inputs: Dict[str, List[str]] = {}
        self._input_types: Optional[List[InputType]] = None
        self._bp_type = "standard"
        self._tf = 20
        self._tb = 20

    def add_inputs(self, *names: str) -> "GraphBuilder":
        self._inputs.extend(names)
        return self

    def add_layer(self, name: str, layer: Layer, *inputs: str) -> "GraphBuilder":
        merged = merge_layer_conf(layer, self._g)
        self._vertices[name] = LayerVertex.of(merged)
        self._vertex_inputs[name] = list(inputs)
        return self

    def add_vertex(self, name: str, vertex: GraphVertexConf, *inputs: str) -> "GraphBuilder":
        self._vertices[name] = vertex
        self._vertex_inputs[name] = list(inputs)
        return self

    def set_outputs(self, *names: str) -> "GraphBuilder":
        self._outputs = list(names)
        return self

    def set_input_types(self, *types: InputType) -> "GraphBuilder":
        self._input_types = list(types)
        return self

    def backprop_type(self, t: str) -> "GraphBuilder":
        self._bp_type = t.lower()
        return self

    def t_bptt_forward_length(self, n: int) -> "GraphBuilder":
        self._tf = n
        return self

    def t_bptt_backward_length(self, n: int) -> "GraphBuilder":
        self._tb = n
        return self

    def build(self) -> ComputationGraphConfiguration:
        if not self._inputs:
            raise ValueError("GraphBuilder needs at least one input")
        if not self._outputs:
            raise ValueError("GraphBuilder needs at least one output")
        known = set(self._inputs) | set(self._vertices)
        for name, ins in self._vertex_inputs.items():
            for i in ins:
                if i not in known:
                    raise ValueError(
                        f"Vertex '{name}' wired to unknown input '{i}' "
                        f"(known: {sorted(known)})")
        for name in self._outputs:
            if name not in self._vertices:
                raise ValueError(f"Output '{name}' is not a vertex")
        conf = ComputationGraphConfiguration(
            network_inputs=self._inputs, network_outputs=self._outputs,
            vertices=self._vertices, vertex_inputs=self._vertex_inputs,
            global_conf=self._g, input_types=self._input_types,
            backprop_type=self._bp_type, tbptt_fwd_length=self._tf,
            tbptt_back_length=self._tb)
        conf.topological_order()  # validate acyclicity early
        _infer_graph_nin(conf)
        return conf


def _infer_graph_nin(conf: ComputationGraphConfiguration) -> None:
    """Infer nIn for LayerVertex layers from upstream output types, and
    auto-insert flatten preprocessors between CNN activations and dense
    layers (the reference's graph-level addPreProcessors pass)."""
    if conf.input_types is None:
        return
    from deeplearning4j_tpu.nn.conf.network import _needs
    types: Dict[str, InputType] = dict(zip(conf.network_inputs, conf.input_types))
    for name in conf.topological_order():
        v = conf.vertices[name]
        in_names = conf.vertex_inputs[name]
        in_types = [types[i] for i in in_names]
        if isinstance(v, LoopVertex):
            v.infer_body(in_types)
        if isinstance(v, LayerVertex):
            layer = v.layer_conf()
            if _needs(layer) == "ff" and in_types[0].kind == "cnn":
                # insert CnnToFeedForward between upstream and this layer
                t = in_types[0]
                proc = pp.CnnToFeedForwardPreProcessor(t.height, t.width,
                                                       t.channels)
                pv_name = f"{name}-cnn2ff"
                conf.vertices[pv_name] = PreprocessorVertex.of(proc)
                conf.vertex_inputs[pv_name] = [in_names[0]]
                conf.vertex_inputs[name] = [pv_name] + in_names[1:]
                types[pv_name] = proc.output_type(t)
                in_types[0] = types[pv_name]
            updates = {}
            if hasattr(layer, "n_in") and getattr(layer, "n_in") is None:
                t = in_types[0]
                updates["n_in"] = t.channels if t.kind == "cnn" else t.flat_size()
            from deeplearning4j_tpu.nn.conf.layers import BatchNormalization
            if isinstance(layer, BatchNormalization) and layer.n_features is None:
                t = in_types[0]
                updates["n_features"] = t.channels if t.kind == "cnn" else t.flat_size()
            if updates:
                layer = dataclasses.replace(layer, **updates)
                conf.vertices[name] = LayerVertex.of(layer)
        types[name] = conf.vertices[name].output_type(in_types)
