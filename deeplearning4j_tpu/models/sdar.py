"""SDAR-MoE — a mixture-of-experts decoder trained as a block-diffusion
model (JetLM's ``sdar_moe`` family, arXiv:2510.06303; the training form
is that of block diffusion language models, arXiv:2503.09573; the
keyword names are its ``config.json``'s), built on ComputationGraph as
models/lfm2.py is: pre-norm blocks whose residual adds are
ElementWiseVertex.

    h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h))

``Attn`` is grouped-query attention with an RMSNorm over each head of q
and k and rotary positions; ``MoE`` routes every token to
``num_experts_per_tok`` of ``num_experts`` gated SiLU experts by softmax
score, renormalised over the selected ones (``norm_topk_prob``); no
shared expert, no dense layer, no bias.  After the last layer the first
``seq_len`` time steps are kept, then one more RMSNorm and the head.

The input is ``[B, 2L]`` int32 ids, a noised copy of each sequence of
``L = seq_len`` tokens and then the clean copy
(``datasets.diffusion.BlockDiffusionNoiser`` makes it), and the stack
runs once over the 2L rows under the block-diffusion mask rule
(``ops/mask_rules.py``), both halves at positions 0..L-1.  Labels are
the clean ids ``[B, L]`` and the labels mask their weights (1 / t on a
masked token, 0 elsewhere); the score is ``(1 / L) sum_i w_i CE_i`` over
the noised half, the prediction at the masked position itself.
``output()`` gives the distribution over the first L rows.

``layers`` builds a subset of the published layers, ``experts_held`` a
subset of each layer's experts, and ``vocab_size`` may be a slice of the
vocabulary: one chip's share of an expert-parallel job.
``recompute_experts`` has each expert layer run again in the backward
pass instead of keeping its first row segment (twice the held share of
the tokens x k rows, at hidden_size + 2 moe_intermediate_size).
"""

from __future__ import annotations

from typing import Optional, Sequence

from deeplearning4j_tpu.nn.conf.graph_conf import (
    ElementWiseVertex, GraphBuilder, TimeRangeVertex)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    EmbeddingLayer, MixtureOfExpertsLayer, RMSNormLayer, RnnOutputLayer,
    SelfAttentionLayer)
from deeplearning4j_tpu.nn.conf.network import GlobalConf
from deeplearning4j_tpu.nn.graph import ComputationGraph


def sdar_moe(vocab_size: int = 151936, hidden_size: int = 2048,
             num_attention_heads: int = 32, num_key_value_heads: int = 4,
             head_dim: int = 128, moe_intermediate_size: int = 768,
             num_experts: int = 128, num_experts_per_tok: int = 8,
             norm_topk_prob: bool = True, rms_norm_eps: float = 1e-6,
             rope_theta: float = 1e6, num_hidden_layers: int = 48,
             block_length: int = 4, seq_len: int = 4096,
             layers: Optional[Sequence[int]] = None,
             experts_held: Optional[Sequence[int]] = None,
             recompute_experts: bool = False,
             learning_rate: float = 1e-5,
             seed: int = 12345) -> ComputationGraph:
    g = GlobalConf(seed=seed, learning_rate=learning_rate, updater="adam",
                   adam_mean_decay=0.9, adam_var_decay=0.95, epsilon=1e-8,
                   activation="identity", weight_init="normal")
    rule = ["block_diffusion", int(seq_len), int(block_length)]
    b = GraphBuilder(g).add_inputs("ids")
    b.add_layer("embed", EmbeddingLayer(n_in=vocab_size, n_out=hidden_size,
                                        bias=False), "ids")
    x = "embed"
    for i in (range(num_hidden_layers) if layers is None else layers):
        name = f"l{i}"
        b.add_layer(f"{name}_attn_norm", RMSNormLayer(eps=rms_norm_eps), x)
        b.add_layer(f"{name}_attn", SelfAttentionLayer(
            n_out=hidden_size, n_heads=num_attention_heads,
            n_kv_heads=num_key_value_heads, head_dim=head_dim, causal=rule,
            rotary_theta=rope_theta, qk_norm=True, qk_norm_eps=rms_norm_eps,
            bias=False), f"{name}_attn_norm")
        b.add_vertex(f"{name}_attn_add", ElementWiseVertex(op="add"),
                     f"{name}_attn", x)
        b.add_layer(f"{name}_moe_norm", RMSNormLayer(eps=rms_norm_eps),
                    f"{name}_attn_add")
        b.add_layer(f"{name}_moe", MixtureOfExpertsLayer(
            n_out=hidden_size, n_experts=num_experts,
            hidden=moe_intermediate_size, top_k=num_experts_per_tok,
            scoring="softmax", norm_topk=norm_topk_prob, expert_bias=False,
            gated=True,
            experts_held=(None if experts_held is None
                          else tuple(experts_held)),
            residual=False, recompute=recompute_experts),
            f"{name}_moe_norm")
        b.add_vertex(f"{name}_moe_add", ElementWiseVertex(op="add"),
                     f"{name}_moe", f"{name}_attn_add")
        x = f"{name}_moe_add"
    # the noised half alone carries a loss: no logits for the clean copy
    b.add_vertex("noisy_rows", TimeRangeVertex(from_step=0, to_step=seq_len),
                 x)
    b.add_layer("final_norm", RMSNormLayer(eps=rms_norm_eps), "noisy_rows")
    b.add_layer("head", RnnOutputLayer(
        n_out=vocab_size, activation="softmax", loss="mcxent", bias=False,
        time_reduction="steps"), "final_norm")
    conf = (b.set_outputs("head")
            .set_input_types(InputType.recurrent(vocab_size, 2 * seq_len))
            .build())
    return ComputationGraph(conf)
