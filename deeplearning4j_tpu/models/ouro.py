"""Ouro — a looped language model (ByteDance's ``ouro`` family, "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741; the
keyword names are its ``config.json``'s), built on ComputationGraph:
one stack of decoder blocks that ``total_ut_steps`` passes run over the
same leaves (``LoopVertex``), an exit gate after every pass
(``LoopExitOutputLayer``).

    block   a = x + N2(Attn(N1(x)));   y = a + N4(MLP(N3(a)))
    loop    h_0 = E[ids];  h_r = N_f(Stack(h_{r-1})),  r = 1..R
    exit    lam_r = sigmoid(h_r w_g + b_g);  p_r = lam_r prod_{j<r}(1 - lam_j),
            the last pass taking the rest
    loss    mean over tokens of  sum_r p_r CE(h_r W_head, label) - beta H(p)

A block is a sandwich of four RMSNorms around causal attention (rotary
over the whole head, no q/k norm) and a gated SiLU MLP; the final norm
``N_f`` closes every pass, so its output feeds the next pass and the
head.  No layer has a bias but the gate.  Input is ``[B, T]`` int32
token ids, labels are ``[B, T]`` int32 class ids, and ``output()``
gives the last pass's distribution.  Each block is recomputed in the
backward pass from its input (``recompute``): one saved input a block a
pass, and the two values its layers offer because they are dear to
compute again (``ops/recompute.py``): the attention core's output with
its row statistics, and the MLP's output, which ``N4``'s backward reads.

``layers`` builds a subset of the published layers (one pipeline
stage's): what the passes loop over is then that stage's layers.  The
embedding and the head are two leaves, as the family has them
(``tie_word_embeddings`` false).
"""

from __future__ import annotations

from typing import Optional, Sequence

from deeplearning4j_tpu.nn.conf.graph_conf import (
    ElementWiseVertex, GraphBuilder, LoopVertex)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    EmbeddingLayer, GatedDenseLayer, LoopExitOutputLayer, RMSNormLayer,
    SelfAttentionLayer)
from deeplearning4j_tpu.nn.conf.network import GlobalConf
from deeplearning4j_tpu.nn.graph import ComputationGraph


def ouro(vocab_size: int = 49152, hidden_size: int = 2048,
         num_attention_heads: int = 16, num_key_value_heads: int = 16,
         intermediate_size: int = 5632, num_hidden_layers: int = 48,
         total_ut_steps: int = 4, rms_norm_eps: float = 1e-6,
         rope_theta: float = 1e6, entropy_weight: float = 0.1,
         layers: Optional[Sequence[int]] = None, recompute: bool = True,
         seq_len: Optional[int] = None, learning_rate: float = 3e-4,
         seed: int = 12345) -> ComputationGraph:
    g = GlobalConf(seed=seed, learning_rate=learning_rate, updater="adam",
                   adam_mean_decay=0.9, adam_var_decay=0.95, epsilon=1e-8,
                   activation="identity", weight_init="normal")
    body = GraphBuilder(g).add_inputs("h")
    blocks, x = [], "h"
    for i in (range(num_hidden_layers) if layers is None else layers):
        n = f"l{i}"
        body.add_layer(f"{n}_attn_in_norm", RMSNormLayer(eps=rms_norm_eps), x)
        body.add_layer(f"{n}_attn", SelfAttentionLayer(
            n_out=hidden_size, n_heads=num_attention_heads,
            n_kv_heads=num_key_value_heads, causal=True,
            rotary_theta=rope_theta, bias=False), f"{n}_attn_in_norm")
        body.add_layer(f"{n}_attn_out_norm", RMSNormLayer(eps=rms_norm_eps), f"{n}_attn")
        body.add_vertex(f"{n}_attn_add", ElementWiseVertex(op="add"),
                        f"{n}_attn_out_norm", x)
        body.add_layer(f"{n}_mlp_in_norm", RMSNormLayer(eps=rms_norm_eps), f"{n}_attn_add")
        body.add_layer(f"{n}_mlp", GatedDenseLayer(
            n_out=hidden_size, hidden=intermediate_size), f"{n}_mlp_in_norm")
        body.add_layer(f"{n}_mlp_out_norm", RMSNormLayer(eps=rms_norm_eps), f"{n}_mlp")
        body.add_vertex(f"{n}_mlp_add", ElementWiseVertex(op="add"),
                        f"{n}_mlp_out_norm", f"{n}_attn_add")
        x = f"{n}_mlp_add"
        blocks.append([f"{n}_{part}" for part in (
            "attn_in_norm", "attn", "attn_out_norm", "attn_add",
            "mlp_in_norm", "mlp", "mlp_out_norm", "mlp_add")])
    body.add_layer("final_norm", RMSNormLayer(eps=rms_norm_eps), x)
    blocks[-1].append("final_norm")     # closes the last block of a pass
    stack = LoopVertex.of(body.set_outputs("final_norm").build(),
                          passes=total_ut_steps,
                          recompute_blocks=blocks if recompute else None)

    b = GraphBuilder(g).add_inputs("ids")
    b.add_layer("embed", EmbeddingLayer(n_in=vocab_size, n_out=hidden_size,
                                        bias=False), "ids")
    b.add_vertex("stack", stack, "embed")
    b.add_layer("head", LoopExitOutputLayer(
        n_out=vocab_size, passes=total_ut_steps,
        entropy_weight=entropy_weight, activation="softmax", loss="mcxent"),
        "stack")
    conf = (b.set_outputs("head")
            .set_input_types(InputType.recurrent(vocab_size, seq_len))
            .build())
    return ComputationGraph(conf)
