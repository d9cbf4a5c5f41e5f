"""LFM2-MoE — a decoder of gated short convolutions and grouped-query
attention with a sparse-expert feed-forward (LiquidAI's ``lfm2_moe``
family; the keyword names are its ``config.json``'s), built on
ComputationGraph: pre-norm blocks whose residual adds are
ElementWiseVertex, as in models/resnet.py.

    h = x + Op(RMSNorm(x));  y = h + FF(RMSNorm(h))

``Op`` is a gated short convolution or causal attention by
``layer_types``; ``FF`` is a gated SiLU MLP in the first
``num_dense_layers`` layers and a top-k mixture of experts after them.
After the last layer one more RMSNorm, then the head.  No layer has a
bias.  Input is ``[B, T]`` int32 token ids, labels are ``[B, T]`` int32
class ids, and the score is the mean cross-entropy over tokens.

``layers`` builds a subset of the published layers (by index, so each
keeps its kind), ``experts_held`` a subset of each expert layer's
experts, and ``vocab_size`` may be a slice of the vocabulary: one
chip's share of an expert-parallel job.  The embedding and the head are
two leaves (the engines hold one leaf a vertex; the family ties them).
"""

from __future__ import annotations

from typing import Optional, Sequence

from deeplearning4j_tpu.nn.conf.graph_conf import (
    ElementWiseVertex, GraphBuilder)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    EmbeddingLayer, GatedDenseLayer, GatedShortConvLayer,
    MixtureOfExpertsLayer, RMSNormLayer, RnnOutputLayer, SelfAttentionLayer)
from deeplearning4j_tpu.nn.conf.network import GlobalConf
from deeplearning4j_tpu.nn.graph import ComputationGraph

# LFM2-8B-A1B: two leading layers, then attention, conv, conv, conv
_LAYER_TYPES_8B = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))


def lfm2_moe(vocab_size: int = 65536, hidden_size: int = 2048,
             num_attention_heads: int = 32, num_key_value_heads: int = 8,
             intermediate_size: int = 7168,
             moe_intermediate_size: int = 1792, num_experts: int = 32,
             num_experts_per_tok: int = 4,
             layer_types: Sequence[str] = _LAYER_TYPES_8B,
             num_dense_layers: int = 2, conv_L_cache: int = 3,
             norm_eps: float = 1e-5, rope_theta: float = 1e6,
             norm_topk_prob: bool = True, use_expert_bias: bool = True,
             layers: Optional[Sequence[int]] = None,
             experts_held: Optional[Sequence[int]] = None,
             seq_len: Optional[int] = None, learning_rate: float = 3e-4,
             seed: int = 12345) -> ComputationGraph:
    g = GlobalConf(seed=seed, learning_rate=learning_rate, updater="adam",
                   adam_mean_decay=0.9, adam_var_decay=0.95, epsilon=1e-8,
                   activation="identity", weight_init="normal")
    b = GraphBuilder(g).add_inputs("ids")
    b.add_layer("embed", EmbeddingLayer(n_in=vocab_size, n_out=hidden_size,
                                        bias=False), "ids")
    x = "embed"
    for i in (range(len(layer_types)) if layers is None else layers):
        kind, name = layer_types[i], f"l{i}"
        b.add_layer(f"{name}_op_norm", RMSNormLayer(eps=norm_eps), x)
        if kind == "conv":
            op = f"{name}_conv"
            b.add_layer(op, GatedShortConvLayer(
                n_out=hidden_size, kernel=conv_L_cache), f"{name}_op_norm")
        elif kind == "full_attention":
            op = f"{name}_attn"
            b.add_layer(op, SelfAttentionLayer(
                n_out=hidden_size, n_heads=num_attention_heads,
                n_kv_heads=num_key_value_heads, causal=True,
                rotary_theta=rope_theta, qk_norm=True,
                bias=False), f"{name}_op_norm")
        else:
            raise ValueError(f"layer_types[{i}]={kind!r}: "
                             "conv | full_attention")
        b.add_vertex(f"{name}_op_add", ElementWiseVertex(op="add"), op, x)
        b.add_layer(f"{name}_ff_norm", RMSNormLayer(eps=norm_eps),
                    f"{name}_op_add")
        if i < num_dense_layers:
            ff = f"{name}_mlp"
            b.add_layer(ff, GatedDenseLayer(
                n_out=hidden_size, hidden=intermediate_size),
                f"{name}_ff_norm")
        else:
            ff = f"{name}_moe"
            b.add_layer(ff, MixtureOfExpertsLayer(
                n_out=hidden_size, n_experts=num_experts,
                hidden=moe_intermediate_size, top_k=num_experts_per_tok,
                scoring="sigmoid", norm_topk=norm_topk_prob,
                expert_bias=use_expert_bias, gated=True,
                experts_held=(None if experts_held is None
                              else tuple(experts_held)),
                residual=False), f"{name}_ff_norm")
        b.add_vertex(f"{name}_ff_add", ElementWiseVertex(op="add"), ff,
                     f"{name}_op_add")
        x = f"{name}_ff_add"
    b.add_layer("final_norm", RMSNormLayer(eps=norm_eps), x)
    b.add_layer("head", RnnOutputLayer(
        n_out=vocab_size, activation="softmax", loss="mcxent", bias=False,
        time_reduction="mean"), "final_norm")
    conf = (b.set_outputs("head")
            .set_input_types(InputType.recurrent(vocab_size, seq_len))
            .build())
    return ComputationGraph(conf)
