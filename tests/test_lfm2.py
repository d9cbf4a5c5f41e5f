"""The LFM2-MoE decoder (models/lfm2.py) and the layers it brought, on the
CPU at a small size, against the plain reference the benchmark keeps
(benchmark/reference/lfm2_moe.py): hidden 64, 4 query heads over 2
key/value heads, 8 experts top-2 (4 held), 2 + 4 layers, vocabulary 256,
32 tokens a sequence, seeded weights.

Tolerances.  Both sides compute in float32 on the CPU and differ only in
the order of their sums (a grouped product over sorted rows against a
dense product masked by the routing; one fused step against a sequence at
a time), so agreement is to round-off: 2e-5 of the largest value
compared, a few hundred float32 ulps through six layers.  A selection
that flipped on such a difference would show as a gap of order 1e-1, and
none does on these seeds."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.modes.fit_tokens import PUBLISHED
from benchmark.reference import lfm2_moe as ref
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.models.lfm2 import lfm2_moe
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.graph_conf import GraphBuilder
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.network import GlobalConf
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.ops import helpers, losses
from deeplearning4j_tpu.ops import pallas_kernels as pk

RTOL = 2e-5     # of the largest value compared; see the module's docstring

CFG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "moe_intermediate_size": 48,
    "num_experts_published": 8, "num_experts": 4, "experts_held": [1, 2, 5, 6],
    "num_experts_per_tok": 2, "vocab_size": 256, "seq_len": 32,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv"],
    "num_dense_layers": 2, "layers_run": [0, 1, 2, 3, 4, 5],
    "conv_L_cache": 3, "norm_eps": 1e-5, "rope_theta": 1e6,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "use_expert_bias": True,
}
ADAM = dict(lr=3e-4, beta1=0.9, beta2=0.95, eps=1e-8)
VERTICES = (["embed", "final_norm", "head"]
            + [f"l{i}_{part}" for i in range(6)
               for part in ("op_norm", "ff_norm",
                            "attn" if i == 2 else "conv",
                            "mlp" if i < 2 else "moe")])


def _close(got, want, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.abs(want).max()), 1e-30)
    assert got.shape == want.shape
    assert float(np.abs(got - want).max()) <= rtol * scale, \
        (float(np.abs(got - want).max()), scale)


def _net(cfg=CFG, **over):
    args = {k: cfg[k] for k in PUBLISHED}
    args.update(num_experts=cfg["num_experts_published"],
                layers=cfg["layers_run"], experts_held=cfg["experts_held"],
                seq_len=cfg["seq_len"], **over)
    return lfm2_moe(**args)


@pytest.fixture(scope="module")
def seeded():
    """The program with the reference's seeded weights and bias, two
    batches of token ids, and both sides' first gradient."""
    key = jax.random.PRNGKey(29)
    weights = ref.init_params(CFG, key)
    bias = ref.init_expert_bias(CFG, key)
    net = _net()
    net.init(params={n: weights.get(n, {}) for n in net.order})
    for v, b in bias.items():
        net.net_state[v] = {**net.net_state[v], "expert_bias": jnp.array(b)}
    rng = np.random.default_rng(29)
    ids = rng.integers(0, CFG["vocab_size"], (3, 2, CFG["seq_len"] + 1),
                       dtype=np.int32)
    batches = [(b[:, :-1], b[:, 1:]) for b in ids]
    x, y = (jnp.asarray(a) for a in batches[0])
    grad_step = jax.jit(net._build_grad_raw())
    score, _, grads = grad_step(
        net.net_params, net.net_state, (x,), (y,), None, None,
        jax.random.PRNGKey(0))
    ref_loss, ref_grads = jax.value_and_grad(
        ref.loss_fn(CFG, "float32", bias))(weights, x, y)
    return dict(net=net, weights=weights, bias=bias, batches=batches,
                score=float(score), grads=grads, ref_loss=float(ref_loss),
                ref_grads=ref_grads)


# --- the whole model against the reference ---------------------------------
def test_logits_and_loss_match_the_reference(seeded):
    net, (x, y) = seeded["net"], seeded["batches"][0]
    _, preouts, _, _ = net._forward_all(
        net.net_params, net.net_state, {"ids": jnp.asarray(x)}, {}, True,
        jax.random.PRNGKey(0), preout_for=["head"])
    logits = ref.logits_fn(CFG, "float32", seeded["bias"])
    for s in range(x.shape[0]):
        _close(preouts["head"][s], logits(seeded["weights"], x[s]))
    assert seeded["score"] == pytest.approx(seeded["ref_loss"], rel=RTOL)
    # the mean over tokens: about ln(256) from a random start
    assert 5.0 < seeded["score"] < 6.5


@pytest.mark.parametrize("vertex", VERTICES)
def test_every_leaf_gradient_matches_the_reference(seeded, vertex):
    want = seeded["ref_grads"][vertex]
    assert set(seeded["grads"][vertex]) == set(want)
    for leaf in want:
        assert float(jnp.abs(want[leaf]).max()) > 0
        _close(seeded["grads"][vertex][leaf], want[leaf])


@pytest.fixture(scope="module")
def trained(seeded):
    """Three Adam steps through ComputationGraph.fit() on a fresh copy,
    and the reference's three steps written out."""
    net = _net()
    # fit() donates the parameters it is given to its step: hand it copies
    net.init(params={n: jax.tree_util.tree_map(
        jnp.array, seeded["weights"].get(n, {})) for n in net.order})
    for v, b in seeded["bias"].items():
        net.net_state[v] = {**net.net_state[v], "expert_bias": jnp.array(b)}
    scores = []

    class Scores:
        def iteration_done(self, model, iteration):
            scores.append(float(model._score))
    net.set_listeners(Scores())
    net.fit(ListDataSetIterator([DataSet(x, y)
                                 for x, y in seeded["batches"]]))
    out = ref.follow(ref.loss_fn(CFG, "float32", seeded["bias"]),
                     seeded["weights"], seeded["batches"], ADAM["lr"],
                     ADAM["beta1"], ADAM["beta2"], ADAM["eps"])
    return net, scores, out


def test_three_adam_steps_through_fit_follow_the_reference_losses(trained):
    net, scores, out = trained
    assert net.iteration == 3 and len(scores) == 3
    assert scores == pytest.approx(out["losses"], rel=RTOL)
    # the bias is state: three steps leave it as it was seeded
    assert net.compile_telemetry.retraces <= 1


@pytest.mark.parametrize("vertex", VERTICES)
def test_three_adam_steps_move_every_leaf_as_the_reference_does(
        seeded, trained, vertex):
    net, _, out = trained
    for leaf, start in seeded["weights"][vertex].items():
        moved = np.asarray(net.net_params[vertex][leaf]) - np.asarray(start)
        norm = float(np.sqrt(np.sum(np.square(moved.astype(np.float64)))))
        # Adam's first steps are near lr * sign(g): every leaf moves, and
        # the norm of its move is the reference's to round-off of the
        # parameters (1e-7 of a weight of order 1 against a move of 1e-3)
        assert norm > 0
        assert norm == pytest.approx(float(out["change_norms"][vertex][leaf]),
                                     rel=2e-3)


def test_the_expert_bias_is_state_and_stays_as_seeded(seeded, trained):
    net = trained[0]
    for v, b in seeded["bias"].items():
        np.testing.assert_array_equal(np.asarray(net.net_state[v]["expert_bias"]),
                                      np.asarray(b))
        assert "expert_bias" not in net.net_params[v]


def test_the_builder_states_the_updater_the_configuration_assumes():
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "lfm2_8b_a1b_ep4.json")) as f:
        u = json.load(f)["updater"]
    g = _net().conf.global_conf
    assert (g.updater, g.learning_rate, g.adam_mean_decay, g.adam_var_decay,
            g.epsilon) == (u["name"], u["learning_rate"], u["beta1"],
                           u["beta2"], u["epsilon"])


def test_fit_publishes_the_expert_load(trained):
    from deeplearning4j_tpu import monitor
    snap = monitor.get_registry().snapshot()
    per = {}
    for s in snap["dl4j_moe_assignments_total"]["samples"]:
        if s["labels"]["vertex"] == "l3_moe":
            per[s["labels"]["held"]] = s["value"]
    # 2 sequences x 32 tokens x top-2 a step, every step a whole number
    assert (per["1"] + per["0"]) % (2 * 32 * 2) == 0 and per["1"] > 0 < per["0"]
    skew = {s["labels"]["vertex"]: s["value"] for s in
            snap["dl4j_moe_expert_load_max_over_mean"]["samples"]}
    assert all(skew[f"l{i}_moe"] >= 1.0 for i in (2, 3, 4, 5))


# --- each new layer alone, against its lines of the reference ---------------
def _u(seed=3, t=32, d=64):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, t, d), jnp.float32)


def _apply(layer, params, x, state=None):
    y, _, _ = layer.forward(params, state or {}, x, train=True, rng=None)
    return y


def test_rms_norm_layer():
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (64,))
    y = _apply(L.RMSNormLayer(eps=1e-5), {"gamma": w}, _u())
    _close(y, ref.rms_norm(_u(), w, 1e-5))
    p, s, t = L.RMSNormLayer().initialize(jax.random.PRNGKey(0),
                                          InputType.recurrent(64, 32))
    assert p["gamma"].shape == (64,) and not s and t.size == 64


def test_gated_short_conv_layer(seeded):
    p = seeded["weights"]["l0_conv"]
    y = _apply(L.GatedShortConvLayer(n_out=64, kernel=3), p, _u())
    conv = ref.blocks(CFG)["conv"]
    for s in range(2):
        _close(y[s], conv(p, _u()[s]))
    # causal: a later token changes no earlier output
    x2 = _u().at[:, 20:].set(0.0)
    y2 = _apply(L.GatedShortConvLayer(n_out=64, kernel=3), p, x2)
    np.testing.assert_array_equal(np.asarray(y[:, :20]), np.asarray(y2[:, :20]))


def test_grouped_query_attention_with_rotary_and_qk_norm(seeded):
    p = seeded["weights"]["l2_attn"]
    layer = L.SelfAttentionLayer(
        n_out=64, n_heads=4, n_kv_heads=2, causal=True, rotary_theta=1e6,
        qk_norm=True, bias=False, activation="identity")
    y = _apply(layer, p, _u())
    attention = ref.blocks(CFG)["attention"]
    for s in range(2):
        _close(y[s], attention(p, _u()[s]))
    init, _, _ = layer.initialize(jax.random.PRNGKey(0),
                                  InputType.recurrent(64, 32))
    assert {k: v.shape for k, v in init.items()} == {
        k: v.shape for k, v in p.items()}


def test_gated_dense_layer(seeded):
    p = seeded["weights"]["l0_mlp"]
    y = _apply(L.GatedDenseLayer(n_out=64, hidden=96), p, _u())
    _close(y[0], ref.blocks(CFG)["mlp"](p, _u()[0]))


def _moe(held, **over):
    return L.MixtureOfExpertsLayer(**dict(
        dict(n_out=64, n_experts=8, hidden=48, top_k=2, scoring="sigmoid",
             expert_bias=True, gated=True, residual=False,
             activation="identity", experts_held=held), **over))


def test_expert_layer_gives_the_held_experts_part(seeded):
    p, bias = seeded["weights"]["l3_moe"], seeded["bias"]["l3_moe"]
    layer = _moe((1, 2, 5, 6))
    state = {"moe_aux_loss": jnp.zeros(()), "expert_bias": bias,
             "moe_expert_counts": jnp.zeros((8,), jnp.int32)}
    y, new_state, _ = layer.forward(p, state, _u(), train=True, rng=None)
    experts = ref.blocks(CFG)["experts"]
    for s in range(2):
        _close(y[s], experts(p, bias, _u()[s]))
    assert int(new_state["moe_expert_counts"].sum()) == 2 * 32 * 2
    np.testing.assert_array_equal(np.asarray(new_state["expert_bias"]),
                                  np.asarray(bias))


def test_embedding_layer_takes_id_sequences():
    layer = L.EmbeddingLayer(n_in=11, n_out=5, bias=False,
                             activation="identity")
    p, _, out = layer.initialize(jax.random.PRNGKey(0),
                                 InputType.recurrent(11, 7))
    assert set(p) == {"W"} and out.kind == "rnn" and out.timesteps == 7
    ids = jnp.asarray([[0, 3, 10], [2, 2, 9]], jnp.int32)
    y = _apply(layer, p, ids)
    assert y.shape == (2, 3, 5)
    np.testing.assert_array_equal(np.asarray(y[1, 2]), np.asarray(p["W"][9]))
    # one index a row still gives one row an example
    assert _apply(layer, p, ids[:, :1]).shape == (2, 5)


# --- the share: expert parallelism's four parts make the whole --------------
@pytest.fixture(scope="module")
def whole_layer():
    key = jax.random.PRNGKey(5)
    layer = _moe(None)
    p, s, _ = layer.initialize(key, InputType.recurrent(64, 32))
    # an uneven bias: experts 0 and 7 are favoured, 3 is nearly shut out
    s["expert_bias"] = jnp.asarray([0.3, 0, 0, -0.5, 0, 0.05, 0, 0.2])
    return layer, p, s


def _segments_run(layer, st, rows):
    """Segments the layer ran, by its own arithmetic on the held count."""
    from deeplearning4j_tpu.ops import row_segments
    held = int(np.asarray(st["moe_expert_counts"])[list(layer._held())].sum())
    seg, n_seg = layer.segment_shape(rows)
    run = row_segments.segments_run(held, seg)
    # and the device counted the same
    assert list(np.asarray(st["moe_row_segments"])) == [run, n_seg - run]
    return run, n_seg


# a share holds 2 of 8 experts: 2 segments of 64 of the 128 sorted rows (a
# segment is twice the even share of 32).  Near an even load a share runs its
# first alone; with every token on experts 0 and 1 the first share runs both
# of its own and the others their first alone
ROUTINGS = {
    "uneven-bias-some-segments": [0.3, 0, 0, -0.5, 0, 0.05, 0, 0.2],
    "even-bias-some-segments": [0.0] * 8,
    "two-experts-take-all-one-share-runs-every-segment":
        [100.0, 50.0, 0, 0, 0, 0, 0, 0],
}


def _share(p, held):
    """The leaves of the layer that holds ``held`` of ``p``'s experts."""
    return {"Wg": p["Wg"], **{k: p[k][jnp.asarray(held)]
                              for k in ("W1", "W2", "W3")}}


def _out_and_grads(layer, p, s, x, mask):
    def loss(p, x):
        y, st, _ = layer.forward(p, s, x, train=True, rng=None, mask=mask)
        # a cotangent that differs from row to row
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))), (y, st)
    (_, (y, st)), (gp, gx) = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(p, x)
    return y, st, {**gp, "x": gx}


@pytest.mark.parametrize("recompute,masked", [
    (False, False), (True, False), (False, True), (True, True)],
    ids=["kept", "recomputed", "kept-masked", "recomputed-masked"])
@pytest.mark.parametrize("bias", list(ROUTINGS.values()), ids=list(ROUTINGS))
def test_the_four_shares_add_up_to_the_uncut_layer(whole_layer, bias,
                                                   recompute, masked):
    """Outputs and every leaf's gradient: a share's expert stacks take
    the uncut layer's own slices, the router and the tokens the sum over
    the shares."""
    layer, p, s = whole_layer
    s = {**s, "expert_bias": jnp.asarray(bias)}
    x = _u(7)
    mask = jnp.ones((2, 32)).at[1, 16:].set(0.0) if masked else None
    whole, st, want = _out_and_grads(layer, p, s, x, mask)
    assert layer.segment_shape(2 * 32 * 2) == (2 * 32 * 2, 1)
    assigned = (48 if masked else 64) * 2
    total = 0.0
    summed = {"Wg": 0.0, "x": 0.0}
    counted = 0
    ran = []
    for share in range(4):
        held = (2 * share, 2 * share + 1)
        part_layer = _moe(held, recompute=recompute)
        part, st_part, got = _out_and_grads(part_layer, _share(p, held), s, x,
                                            mask)
        total = total + part
        # every share routes over all eight and counts them alike
        np.testing.assert_array_equal(np.asarray(st_part["moe_expert_counts"]),
                                      np.asarray(st["moe_expert_counts"]))
        counted += int(st_part["moe_expert_counts"][jnp.asarray(held)].sum())
        ran.append(_segments_run(part_layer, st_part, 2 * 32 * 2))
        for leaf in ("W1", "W2", "W3"):
            _close(got[leaf], want[leaf][jnp.asarray(held)])
        summed = {leaf: summed[leaf] + got[leaf] for leaf in summed}
    _close(total, whole)
    for leaf in summed:
        assert float(jnp.abs(want[leaf]).max()) > 0
        _close(summed[leaf], want[leaf])
    assert counted == assigned              # each assignment held once
    assert float(jnp.abs(whole).max()) > 0.1
    assert all(n_seg == 2 for _, n_seg in ran)
    if bias[0] == 100.0:
        assert [run for run, _ in ran] == [2, 1, 1, 1]
    else:
        # no share is sent twice its even load: the first segment alone
        assert [run for run, _ in ran] == [1, 1, 1, 1]
    if bias[3] < 0:
        counts = np.asarray(st["moe_expert_counts"])
        assert counts[0] > counts[3]        # the bias steers the selection


def _dense_part(p, s, x, held):
    """The held experts' part of the routed sum by dense products, token
    by token with nothing left out, and each token's weight on them."""
    tokens = x.reshape(-1, 64)
    scores = jax.nn.sigmoid(tokens @ p["Wg"])
    _, sel = jax.lax.top_k(scores + s["expert_bias"], 2)
    w = jnp.take_along_axis(scores, sel, axis=1)
    w = w / (w.sum(-1, keepdims=True) + 1e-6)
    total, weights = 0.0, []
    for e in held:
        w_e = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)
        dense = (jax.nn.silu(tokens @ p["W1"][e]) * (tokens @ p["W3"][e])) @ p["W2"][e]
        total = total + w_e[:, None] * dense
        weights.append(w_e)
    return total, weights


@pytest.mark.parametrize("held,second,run", [
    ((4,), 0.0, 2),         # expert 4 alone: 64 of 128 rows, 2 of 4 segments of 32
    ((4, 5), 50.0, 2),      # every assignment on a held expert: both of 64 run
    ((3, 4), 0.0, 2),       # the second choices of some tokens beside it: 64 and a few
    ((0,), 0.0, 1),         # an expert few tokens choose: the first alone
], ids=["held-alone-half-the-segments", "every-assignment-held-all-segments",
        "held-with-a-neighbour-over-the-edge", "another-held-one-segment"])
def test_no_token_is_dropped_when_one_expert_is_sent_every_token(
        whole_layer, held, second, run):
    layer, p, s = whole_layer
    x = _u(9)
    s = {**s, "expert_bias": jnp.zeros((8,)).at[4].set(100.0).at[5].set(second)}
    y, st, _ = layer.forward(p, s, x, train=True, rng=None)
    counts = np.asarray(st["moe_expert_counts"])
    assert counts[4] == 2 * 32 and counts.sum() == 2 * 32 * 2
    # the held experts' own part, token by token, with nothing left out:
    # hold them alone and compare with their dense products under the
    # same weights
    part = _moe(held)
    alone, st_part, _ = part.forward(
        {"Wg": p["Wg"], **{k: p[k][jnp.asarray(held)] for k in ("W1", "W2", "W3")}},
        s, x, train=True, rng=None)
    dense, weights = _dense_part(p, s, x, held)
    if 4 in held:
        assert float(weights[held.index(4)].min()) > 0      # every token is sent
    _close(alone.reshape(-1, 64), dense)
    ran, n_seg = _segments_run(part, st_part, 2 * 32 * 2)
    assert n_seg > 1 and ran == run
    if len(held) < 2 or second == 0.0:
        assert float(jnp.abs(y - alone).max()) > 0      # and its second expert


@pytest.fixture(scope="module")
def sixteen():
    """Sixteen experts, top-2: a layer that holds two of them cuts its
    128 sorted rows into 4 segments of 32."""
    layer = _moe(None, n_experts=16)
    p, s, _ = layer.initialize(jax.random.PRNGKey(6), InputType.recurrent(64, 32))
    return layer, p, {**s, "expert_bias": jnp.zeros((16,))}


def _lift(experts, **lifted):
    """An expert bias that lifts expert e by ``lifted["e<e>"]``."""
    bias = jnp.zeros((experts,))
    for name, by in lifted.items():
        bias = bias.at[int(name[1:])].set(by)
    return bias


# held, experts, the biases to route by in turn, the segments they run
RETRACE = {
    "whole": (None, 8, [_lift(8, e2=100.0), _lift(8, e4=100.0),
                        _lift(8, e4=100.0, e3=50.0)], {1}),
    "held-1-of-8": ((4,), 8, [_lift(8, e2=100.0), _lift(8, e4=100.0),
                              _lift(8, e4=100.0, e3=50.0)], {1, 2}),
    "held-3-of-8": ((3, 4, 5), 8, [_lift(8, e2=100.0), _lift(8, e4=100.0),
                                   _lift(8, e4=100.0, e3=50.0)], {1, 2}),
    # expert 5 takes every token, expert 9 beside it none (64 rows, 2
    # segments), the few whose second choice it is (3) or all (all 4)
    "held-2-of-16-one-to-all-segments": (
        (5, 9), 16, [_lift(16, e5=100.0, e9=-100.0), _lift(16, e5=100.0),
                     _lift(16, e5=100.0, e9=50.0)], {1, 2, 3, 4}),
}


@pytest.mark.parametrize("recompute", [False, True], ids=["kept", "recomputed"])
@pytest.mark.parametrize("case", list(RETRACE), ids=list(RETRACE))
def test_routing_changes_no_shape_and_retraces_nothing(
        whole_layer, sixteen, case, recompute):
    held, experts, biases, want_ran = RETRACE[case]
    _, p, s = whole_layer if experts == 8 else sixteen
    layer = _moe(held, n_experts=experts, recompute=recompute)
    if held:
        p = _share(p, held)

    @jax.jit
    def fn(s, x):
        def loss(p, x):
            y, st, _ = layer.forward(p, s, x, train=True, rng=None)
            return jnp.sum(y), st
        (_, st), g = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(p, x)
        return st, g

    ran = set()
    # loads on both sides of a segment's edge, and every segment
    for bias in [s["expert_bias"]] + biases:
        st, g = fn({**s, "expert_bias": bias}, _u(len(ran) + 1))
        assert all(bool(jnp.isfinite(v).all()) for v in jax.tree_util.tree_leaves(g))
        ran.add(_segments_run(layer, st, 2 * 32 * 2)[0])
    assert fn._cache_size() == 1
    assert ran == want_ran


@pytest.mark.parametrize("held", [None, (0, 1), (4, 5, 6, 7)],
                         ids=["whole", "held-2-of-8", "held-4-of-8"])
def test_padding_claims_no_expert(whole_layer, held):
    _, whole_p, s = whole_layer
    layer, p = _moe(held), whole_p
    if held:
        p = {"Wg": p["Wg"], **{k: p[k][jnp.asarray(held)] for k in ("W1", "W2", "W3")}}
    mask = jnp.ones((2, 32)).at[1, 16:].set(0.0)
    y, st, _ = layer.forward(p, s, _u(4), train=True, rng=None, mask=mask)
    assert int(st["moe_expert_counts"].sum()) == (32 + 16) * 2
    assert float(jnp.abs(y[1, 16:]).max()) == 0.0
    assert float(jnp.abs(y[1, :16]).max()) > 0.0
    # the padding's rows sort behind every held row, into segments that
    # do not run or rows that are zeroed: the rest is as without it
    want, _ = _dense_part(whole_p, s, _u(4), held or tuple(range(8)))
    _close(y.reshape(-1, 64)[:48], want[:48])


# --- the segments: the same numbers as the uncut buffers, at every edge ------
def _unsegmented(monkeypatch):
    from deeplearning4j_tpu.ops import row_segments
    monkeypatch.setattr(row_segments, "segment_rows",
                        lambda rows, held, experts: (rows, 1))


# held, of how many experts, the bias that routes, a segment's rows, and the
# edge (behind which segment) the held count is brought to
EDGES = {
    "two-held-edge64": ((4, 6), 8, _lift(8, e4=100.0), 64, 1),
    "one-held-edge32": ((4,), 8, _lift(8, e4=100.0), 32, 1),
    # 4 segments of 32: every token on expert 5, most on expert 9 beside it
    "two-of-16-second-edge": ((5, 9), 16, _lift(16, e5=100.0, e9=0.5), 32, 2),
    "two-of-16-third-edge": ((5, 9), 16, _lift(16, e5=100.0, e9=0.5), 32, 3),
}


@pytest.mark.parametrize("recompute", [False, True], ids=["kept", "recomputed"])
@pytest.mark.parametrize("case", list(EDGES), ids=list(EDGES))
@pytest.mark.parametrize("off", [-1, 0, 1], ids=["one-under", "at", "one-over"])
def test_segments_agree_with_the_uncut_buffers_at_an_edge(
        whole_layer, sixteen, monkeypatch, case, off, recompute):
    """Held experts 4 and 6 of 8: segments of 64 of the 128 sorted rows
    (expert 4 alone: of 32; 5 and 9 of 16: of 32).  Every token is sent
    to the lifted expert and to its best other, so a token holds one row
    or two, and the mask picks tokens until the held count is a
    segment's edge, one under, or one over it: behind the first edge one
    or two segments run, behind the third three or all four.  Outputs
    and every leaf's gradient are those of the uncut buffer."""
    held, experts, bias, seg_want, edge = EDGES[case]
    _, p, s = whole_layer if experts == 8 else sixteen
    layer = _moe(held, n_experts=experts, recompute=recompute)
    p = _share(p, held)
    s = {**s, "expert_bias": bias}
    x = _u(11)
    seg, n_seg = layer.segment_shape(2 * 32 * 2)
    assert (seg, n_seg) == (seg_want, 128 // seg_want)
    target = edge * seg + off
    scores = jax.nn.sigmoid(x.reshape(-1, 64) @ p["Wg"])
    _, sel = jax.lax.top_k(scores + s["expert_bias"], 2)
    rows_of = np.isin(np.asarray(sel), held).sum(axis=1)      # 1 or 2 a token
    mask, have = np.zeros((64,), np.float32), 0
    for t in np.argsort(-rows_of, kind="stable"):   # the twos first, then ones
        if have + rows_of[t] <= target:
            mask[t] = 1.0
            have += int(rows_of[t])
    assert have == target <= int(rows_of.sum())
    mask = jnp.asarray(mask.reshape(2, 32))
    y, st, grads = _out_and_grads(layer, p, s, x, mask)
    counts = np.asarray(st["moe_expert_counts"])
    assert int(counts[list(held)].sum()) == target
    assert (counts[list(held)] > 0).all()       # every held group has rows
    assert _segments_run(layer, st, 128)[0] == -(-target // seg) \
        == edge + (off > 0)
    _unsegmented(monkeypatch)
    assert layer.segment_shape(128) == (128, 1)
    y1, st1, grads1 = _out_and_grads(layer, p, s, x, mask)
    np.testing.assert_array_equal(np.asarray(st1["moe_expert_counts"]), counts)
    _close(y, y1)
    assert set(grads) == {"Wg", "W1", "W2", "W3", "x"}
    for leaf in grads:
        assert float(jnp.abs(grads1[leaf]).max()) > 0
        _close(grads[leaf], grads1[leaf])


def test_rows_that_do_not_fill_whole_segments_are_filled_behind_the_last():
    """36 rows in segments of 32: the sorted buffer is filled to 64, and
    the filling sorts behind every row, held or not."""
    layer = L.MixtureOfExpertsLayer(
        n_out=8, n_experts=6, hidden=5, top_k=2, scoring="sigmoid", gated=True,
        experts_held=(2, 5), residual=False, activation="identity")
    assert layer.segment_shape(36) == (32, 2)
    p, s, _ = layer.initialize(jax.random.PRNGKey(0), InputType.recurrent(8, 6))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 6, 8))
    y, st, grads = _out_and_grads(layer, p, s, x, None)
    with pytest.MonkeyPatch.context() as m:
        _unsegmented(m)
        y1, _, grads1 = _out_and_grads(layer, p, s, x, None)
    assert _segments_run(layer, st, 36)[0] >= 1
    _close(y, y1)
    for leaf in grads:
        _close(grads[leaf], grads1[leaf])


@pytest.mark.parametrize("rows,held,experts,want", [
    (32768, 8, 32, (16384, 2)),     # lfm2's cell: twice the even share of 8,192
    (65536, 16, 128, (16384, 4)),   # sdar's cell
    (32768, 32, 32, (32768, 1)),    # every expert held: one segment
    (32768, 16, 32, (32768, 1)),    # half of them: twice the share is the buffer
    (128, 2, 8, (64, 2)), (128, 3, 8, (96, 2)), (128, 1, 8, (32, 4)),
    (36, 2, 6, (32, 2)),            # rounded up to the row tile, 64 rows
    (36, 4, 6, (36, 1)),
    (16, 1, 8, (16, 1)),            # a segment as long as the buffer
])
def test_the_segment_follows_the_share_held(rows, held, experts, want):
    from deeplearning4j_tpu.ops import row_segments
    assert row_segments.segment_rows(rows, held, experts) == want
    seg, n_seg = want
    assert seg * n_seg >= rows > seg * (n_seg - 1)
    # the first always runs; an edge belongs to the segment before it
    assert [row_segments.segments_run(h, seg) for h in
            (0, 1, seg, min(seg + 1, rows), rows)] == [
        1, 1, 1, min(2, n_seg), n_seg]


@pytest.mark.parametrize("recompute", [False, True], ids=["kept", "recomputed"])
def test_a_layer_that_holds_every_expert_lowers_without_control_flow(
        whole_layer, recompute):
    """And one that holds a share lowers to loops over its later segments
    and to no array longer than a segment and as wide as the model or an
    expert: the sorted buffer of all 128 rows exists nowhere."""
    from deeplearning4j_tpu.ops import row_segments
    layer, p, s = whole_layer

    def lowered(layer, p):
        def loss(p, x):
            return jnp.sum(layer.forward(p, s, x, train=True, rng=None)[0])
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            p, _u()).as_text()

    whole = lowered(_moe(None, recompute=recompute), p)
    for word in ("while", "conditional", "stablehlo.case", "stablehlo.if"):
        assert word not in whole
    # the one segment is the whole buffer, and the checker sees it
    assert (128, 64) in row_segments.tall_arrays(whole, 64, (64, 48))
    held = (2, 3)
    part = _moe(held, recompute=recompute)
    seg, n_seg = part.segment_shape(128)
    assert (seg, n_seg) == (64, 2)
    cut = lowered(part, _share(p, held))
    # the later segments forward and backward: two loops whose trip count
    # is data (the forward run again for the backward needs segment 0's
    # hidden rows alone, and no sum)
    assert cut.count("stablehlo.while") == 2
    for word in ("conditional", "stablehlo.case", "stablehlo.if"):
        assert word not in cut
    # recomputed or not the step selects once and sorts twice (the order
    # and its inverse): the routing's integers are offered to a recomputed
    # run (``ops/recompute.py``); and nothing is looked up by an index
    # but rows: no scatter at all
    assert cut.count("chlo.top_k") == 1 and cut.count("stablehlo.sort") == 2
    assert "stablehlo.scatter" not in cut
    # (off the chip JAX itself writes a grouped product out group by group
    # over the segment's rows, [2, seg, width]: the chip's compiler takes
    # it whole, tests/test_tpu_compile.py)
    assert row_segments.tall_arrays(
        cut, seg, (64, 48),
        stacks=[(2, 64, 48), (2, seg, 64), (2, seg, 48)]) == []


def test_fit_counts_the_row_segments_run_and_skipped():
    from deeplearning4j_tpu import monitor

    def read():
        out = {}
        for s_ in monitor.get_registry().snapshot().get(
                "dl4j_moe_row_segments_total", {"samples": []})["samples"]:
            if s_["labels"]["vertex"] == "l3_moe":
                out[s_["labels"]["outcome"]] = s_["value"]
        return np.array([out.get(o, 0) for o in ("run", "skipped", "recomputed")])

    net = _net(dict(CFG, layers_run=[0, 3], num_experts=2, experts_held=[2, 5]))
    net.init()
    layer = net.conf.vertices["l3_moe"].layer_conf()
    seg, n_seg = layer.segment_shape(2 * 32 * 2)
    assert (seg, n_seg) == (64, 2)           # 2 of 8 held: twice their share of 128
    rng = np.random.default_rng(3)
    # the third batch is half padding: the segments are still those of the
    # 128 rows the device sorted, not of the 64 assignments counted; for the
    # last the bias sends every token to the two held experts, and the second
    # segment runs, its hidden rows computed again in the backward pass
    half = np.ones((2, 32), np.float32)
    half[:, 16:] = 0.0
    # (the step donates its state: a copy on the host outlives it)
    seeded_bias = np.asarray(net.net_state["l3_moe"]["expert_bias"])
    for mask, bias, want_run in [
            (None, seeded_bias, 1), (None, seeded_bias, 1),
            (half, seeded_bias, 1),
            (None, _lift(8, e2=100.0, e5=50.0), 2)]:
        net.net_state["l3_moe"] = {**net.net_state["l3_moe"],
                                   "expert_bias": jnp.asarray(bias)}
        ids = rng.integers(0, CFG["vocab_size"], (2, 33), dtype=np.int32)
        before = read()
        net.fit(ListDataSetIterator([DataSet(
            ids[:, :-1], ids[:, 1:], features_mask=mask, labels_mask=mask)]))
        run, skipped, recomputed = read() - before
        counts = np.asarray(net.net_state["l3_moe"]["moe_expert_counts"])
        assert int(counts.sum()) == (128 if mask is None else 64)
        held = int(counts[list(layer._held())].sum())
        assert run == max(1, -(-held // seg)) == want_run
        assert 0 < held < 128 or want_run == 2
        assert run + skipped == n_seg and recomputed == run - 1


@pytest.mark.parametrize("held", [(), (3, 1), (0, 0), (8,)])
def test_experts_held_must_be_distinct_ascending_ids(held):
    with pytest.raises(ValueError):
        _moe(held).initialize(jax.random.PRNGKey(0),
                              InputType.recurrent(64, 32))


def test_the_top_1_capacity_path_is_as_it_was():
    layer = L.MixtureOfExpertsLayer(n_out=16, n_experts=4,
                                    activation="identity")
    p, s, _ = layer.initialize(jax.random.PRNGKey(0),
                               InputType.recurrent(16, 8))
    assert set(p) == {"Wg", "W1", "b1", "W2", "b2"} and set(s) == {"moe_aux_loss"}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    y, ns, _ = layer.forward(p, s, x, train=True, rng=None)
    # the block adds its input: a token over capacity passes unchanged
    full = dataclasses.replace(layer, capacity_factor=1e-9)   # C = 1
    y1, _, _ = full.forward(p, s, x, train=True, rng=None)
    moved = np.abs(np.asarray(y1 - x)).reshape(16, 16).max(axis=1) > 0
    assert 1 <= moved.sum() <= 4 < (np.asarray(y) != np.asarray(x)).any(-1).sum()
    assert float(ns["moe_aux_loss"]) > 0


# --- attention: the flash tier against the dense core, grouped heads --------
def test_flash_path_agrees_with_dense_attention_on_grouped_heads(monkeypatch):
    layer = L.SelfAttentionLayer(
        n_out=128, n_heads=4, n_kv_heads=2, causal=True, rotary_theta=1e4,
        qk_norm=True, bias=False, activation="identity", weight_init="normal")
    p, _, _ = layer.initialize(jax.random.PRNGKey(2),
                               InputType.recurrent(128, 128))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 128, 128), jnp.float32)

    def run():
        helpers.reset_validation()
        pk._disabled.clear()
        return jax.value_and_grad(
            lambda p: jnp.sum(jnp.square(_apply(layer, p, x))))(p)

    monkeypatch.setenv("DL4J_PALLAS_FLASH", "0")
    dense, dense_g = run()
    monkeypatch.setenv("DL4J_PALLAS_FLASH", "1")     # interpret mode here
    flash, flash_g = run()
    helpers.reset_validation()
    # the kernel's online softmax sums in blocks of 128: round-off of a
    # float32 softmax over 128 keys, through a sum of squares
    assert float(flash) == pytest.approx(float(dense), rel=1e-4)
    for k in dense_g:
        _close(flash_g[k], dense_g[k], rtol=1e-3)


def test_rotary_attention_refuses_the_carried_decode_step():
    from deeplearning4j_tpu.parallel import sequence as seq_ops
    layer = L.SelfAttentionLayer(n_out=16, n_heads=2, rotary_theta=1e4,
                                 causal=True, activation="identity")
    p, _, _ = layer.initialize(jax.random.PRNGKey(0),
                               InputType.recurrent(16, 8))
    with seq_ops.kv_decode_scope(True), pytest.raises(NotImplementedError):
        layer.forward(p, {}, jnp.zeros((1, 1, 16)), train=False, rng=None)


# --- integer labels against one-hot ones ------------------------------------
def _labels(n=6, t=5, v=160, seed=0):
    rng = np.random.default_rng(seed)
    z = jnp.asarray(rng.normal(size=(n, t, v)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, v, (n, t)), jnp.int32)
    return z, ids, jax.nn.one_hot(ids, v, dtype=jnp.float32)


@pytest.mark.parametrize("fused", ["0", "1"], ids=["dense", "fused-kernel"])
@pytest.mark.parametrize("masked", [False, True], ids=["whole", "masked"])
def test_integer_labels_score_as_one_hot_ones(monkeypatch, fused, masked):
    monkeypatch.setenv("DL4J_FUSED_XENT", fused)     # "1": interpret mode
    z, ids, onehot = _labels()
    mask = (jnp.ones(ids.shape).at[2, 3:].set(0.0)[..., None]
            if masked else None)
    want, want_g = jax.value_and_grad(
        lambda z: jnp.sum(losses.mcxent(onehot, z, "softmax", mask)))(z)
    got, got_g = jax.value_and_grad(
        lambda z: jnp.sum(losses.mcxent(ids, z, "softmax", mask)))(z)
    # the same sums in the same order but for the kernel's row blocks
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    _close(got_g, want_g, rtol=1e-5)


@pytest.mark.parametrize("fused", ["0", "1"], ids=["dense", "fused-kernel"])
def test_an_id_outside_the_classes_is_a_row_without_a_label(monkeypatch, fused):
    monkeypatch.setenv("DL4J_FUSED_XENT", fused)
    z, ids, onehot = _labels()
    ids = ids.at[0, 0].set(-1).at[1, 1].set(160)
    onehot = onehot.at[0, 0].set(0.0).at[1, 1].set(0.0)
    want, want_g = jax.value_and_grad(
        lambda z: jnp.sum(losses.mcxent(onehot, z, "softmax", None)))(z)
    got, got_g = jax.value_and_grad(
        lambda z: jnp.sum(losses.mcxent(ids, z, "softmax", None)))(z)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    _close(got_g, want_g, rtol=1e-5)
    assert float(jnp.abs(got_g[0, 0]).max()) == 0.0


def test_integer_labels_under_another_activation():
    z, ids, onehot = _labels(v=7)
    _close(losses.mcxent(ids, z, "sigmoid", None),
           losses.mcxent(onehot, z, "sigmoid", None), rtol=1e-6)


@pytest.mark.parametrize("reduction,scale", [("sum", 5.0), ("mean", 1.0)])
def test_rnn_output_layer_scores_ids_and_reduces_over_time(reduction, scale):
    z, ids, onehot = _labels()
    layer = L.RnnOutputLayer(n_out=160, activation="softmax", loss="mcxent",
                             time_reduction=reduction)
    per_ex = layer.compute_score(ids, z)
    _close(per_ex, layer.compute_score(onehot, z), rtol=1e-6)
    per_token = losses.mcxent(ids.reshape(-1), z.reshape(-1, 160))
    assert float(jnp.mean(per_ex)) == pytest.approx(
        scale * float(jnp.mean(per_token)), rel=1e-6)
    mask = jnp.ones((6, 5)).at[0, 2:].set(0.0)
    masked = layer.compute_score(ids, z, mask)
    assert float(masked[0]) == pytest.approx(
        float(per_token[:2].sum()) / (2.0 if reduction == "mean" else 1.0),
        rel=1e-6)
    with pytest.raises(ValueError):
        dataclasses.replace(layer, time_reduction="max").compute_score(ids, z)


@pytest.mark.parametrize("layer,leaves", [
    (L.DenseLayer(n_out=4, bias=False, activation="identity"), {"W"}),
    (L.OutputLayer(n_out=4, bias=False), {"W"}),
    (L.RnnOutputLayer(n_out=4, bias=False), {"W"}),
    (L.DenseLayer(n_out=4, activation="identity"), {"W", "b"}),
])
def test_layers_without_a_bias_have_no_bias_leaf(layer, leaves):
    t = InputType.recurrent(3, 2) if isinstance(layer, L.RnnOutputLayer) \
        else InputType.feed_forward(3)
    p, _, _ = layer.initialize(jax.random.PRNGKey(0), t)
    assert set(p) == leaves
    x = jnp.ones((2, 2, 3) if t.kind == "rnn" else (2, 3))
    want = x @ p["W"] + (p["b"] if "b" in p else 0.0)
    _close(layer.preoutput(p, x) if hasattr(layer, "preoutput")
           else _apply(layer, p, x), want)


# --- f64 numeric gradient checks (nn/gradientcheck.py) ----------------------
def _one_layer_graph(layer, n_in=8, t=6, classes=5):
    g = GlobalConf(seed=3, learning_rate=0.1, updater="sgd",
                   activation="identity", weight_init="xavier")
    b = (GraphBuilder(g).add_inputs("in")
         .add_layer("layer", layer, "in")
         .add_layer("out", L.RnnOutputLayer(n_out=classes, activation="softmax",
                                            loss="mcxent"), "layer"))
    net = ComputationGraph(b.set_outputs("out").set_input_types(
        InputType.recurrent(n_in, t)).build())
    net.init()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(3, t, n_in))
    ids = rng.integers(0, classes, (3, t)).astype(np.int32)
    return net, x, ids


@pytest.mark.parametrize("layer", [
    L.SelfAttentionLayer(n_out=8, n_heads=4, n_kv_heads=2, causal=True,
                         rotary_theta=1e4, qk_norm=True, bias=False),
    L.GatedShortConvLayer(n_out=8, kernel=3),
    L.MixtureOfExpertsLayer(n_out=8, n_experts=6, hidden=5, top_k=2,
                            scoring="sigmoid", expert_bias=True, gated=True,
                            experts_held=(0, 2, 3, 5), residual=False),
    L.MixtureOfExpertsLayer(n_out=8, n_experts=4, hidden=5, top_k=2,
                            scoring="softmax", gated=False),
    L.GatedDenseLayer(n_out=8, hidden=7),
    L.RMSNormLayer(),
], ids=["attention-gqa-rotary-qknorm", "gated-short-conv", "experts-held-4-of-6",
        "experts-softmax-gelu", "gated-dense", "rms-norm"])
def test_numeric_gradients_in_float64(layer):
    from deeplearning4j_tpu.nn.gradientcheck import (
        check_computation_graph_gradients)
    net, x, ids = _one_layer_graph(layer)
    # integer class ids as labels: the check keeps them integers
    assert check_computation_graph_gradients(net, [x], [ids], subset=48,
                                             print_results=False)


# --- the device trace's scopes -----------------------------------------------
@pytest.mark.parametrize("op_name,want", [
    ("jit(cg_train_step)/jvp(fwd/MixtureOfExpertsLayer/l2_moe)/experts/"
     "ragged_dot_general", "fwd/MixtureOfExpertsLayer/experts"),
    ("jit(cg_train_step)/transpose(jvp(fwd/MixtureOfExpertsLayer/l2_moe))/"
     "dispatch/scatter-add", "bwd/MixtureOfExpertsLayer/dispatch"),
    ("jit(cg_train_step)/jvp(fwd/MixtureOfExpertsLayer/l2_moe)/route/"
     "jit(top_k)/top_k", "fwd/MixtureOfExpertsLayer/route"),
    ("ragged-dot-none.7", "kernel/MixtureOfExpertsLayer/experts"),
    # a part's scope stands outside the control flow the part contains: a
    # loop's body or a branch under it is the part's, in both directions
    ("jit(cg_train_step)/jvp(fwd/MixtureOfExpertsLayer/l2_moe)/experts/"
     "while/body/ragged_dot_general", "fwd/MixtureOfExpertsLayer/experts"),
    ("jit(cg_train_step)/jvp(fwd/MixtureOfExpertsLayer/l2_moe)/dispatch/"
     "while/cond/lt", "fwd/MixtureOfExpertsLayer/dispatch"),
    ("jit(cg_train_step)/transpose(jvp(fwd/MixtureOfExpertsLayer/l2_moe))/"
     "combine/while/body/gather", "bwd/MixtureOfExpertsLayer/combine"),
    ("jit(cg_train_step)/transpose(jvp(fwd/MixtureOfExpertsLayer/l2_moe))/"
     "experts/while/body/transpose(jvp())/ragged_dot_general",
     "bwd/MixtureOfExpertsLayer/experts"),
    ("jit(cg_train_step)/transpose(jvp(fwd/MixtureOfExpertsLayer/l2_moe))/"
     "dispatch/cond/branch_1_fun/scatter", "bwd/MixtureOfExpertsLayer/dispatch"),
    # a loop that runs the parts once a trip (the later row segments):
    # the part's, in both directions; the loop's own condition is no part
    ("jit(cg_train_step)/jvp(fwd/MixtureOfExpertsLayer/l2_moe)/while/body/"
     "dispatch/gather", "fwd/MixtureOfExpertsLayer/dispatch"),
    ("jit(cg_train_step)/transpose(jvp(fwd/MixtureOfExpertsLayer/l2_moe))/"
     "while/body/experts/transpose(jvp())/ragged_dot_general",
     "bwd/MixtureOfExpertsLayer/experts"),
    ("jit(cg_train_step)/jvp(fwd/MixtureOfExpertsLayer/l2_moe)/while/cond/"
     "lt", None),
    # a layer run again in the backward pass (jax.checkpoint): the
    # backward names the forward and the checkpoint before the part
    ("jit(cg_train_step)/transpose(jvp(fwd/MixtureOfExpertsLayer/l2_moe))/"
     "jvp(fwd/MixtureOfExpertsLayer/l2_moe)/checkpoint/combine/gather",
     "bwd/MixtureOfExpertsLayer/combine"),
    ("jit(cg_train_step)/transpose(jvp(fwd/MixtureOfExpertsLayer/l2_moe))/"
     "jvp(fwd/MixtureOfExpertsLayer/l2_moe)/checkpoint/rematted_computation/"
     "route/dot_general", "bwd/MixtureOfExpertsLayer/route"),
    ("jit(cg_train_step)/transpose(jvp(fwd/MixtureOfExpertsLayer/l2_moe))/"
     "jvp(fwd/MixtureOfExpertsLayer/l2_moe)/checkpoint/while/body/experts/"
     "transpose(jvp())/ragged_dot_general", "bwd/MixtureOfExpertsLayer/experts"),
    ("jit(cg_train_step)/transpose(jvp(fwd/SelfAttentionLayer/l2_attn))/"
     "jvp(fwd/SelfAttentionLayer/l2_attn)/checkpoint/attn_core/pallas_call",
     "bwd/SelfAttentionLayer/attn_core"),
    ("jit(cg_train_step)/transpose(jvp(fwd/MixtureOfExpertsLayer/l2_moe))/"
     "jvp(fwd/MixtureOfExpertsLayer/l2_moe)/checkpoint/add", None),
    ("jit(cg_train_step)/jvp(fwd/MixtureOfExpertsLayer/l2_moe)/while/body/"
     "add", None),
    ("jit(cg_train_step)/jvp(fwd/DenseLayer/fc)/dot_general", None),
    ("jit(cg_train_step)/jvp(fwd/MixtureOfExpertsLayer/l2_moe)/eq", None),
    ("jit(cg_train_step)/update/mul", None),
])
def test_profile_splits_a_layer_by_the_parts_it_names(op_name, want):
    from deeplearning4j_tpu.monitor import profile
    assert profile.sub_scope(op_name) == want
    if want:
        direction, kind, _ = profile.classify(op_name)
        assert f"{direction}/{kind}" == want.rsplit("/", 1)[0]


def test_profile_leaves_an_event_that_spans_others_out_of_the_sums():
    """A ``while`` (or ``conditional``, ``call``) event lies over the
    events of the instructions it runs, on the same line: it is busy
    time, and no scope's."""
    from deeplearning4j_tpu.monitor import profile
    part = "jit(cg_train_step)/transpose(jvp(fwd/MixtureOfExpertsLayer/l2_moe))/experts"
    planes = [("/device:TPU:0", [("XLA Ops", [
        (0.0, 10.0, "%while.3 = (s32[]{:T(128)}, bf16[64,8]{1,0}) while((s32[], "
         "bf16[64,8]) %tuple.1), condition=%cond.2, body=%body.2", part + "/while"),
        (0.0, 4.0, "%fusion.7 = bf16[16,8]{1,0} fusion(...)", part + "/while/body/mul"),
        (5.0, 3.0, "%ragged-dot-none.2 = bf16[16,8]{1,0} custom-call(...)",
         "ragged-dot-none"),
        (8.0, 1.0, "%fusion.8 = bf16[16,8]{1,0} fusion(...)",
         part + "/while/body/dynamic_update_slice"),
        (12.0, 2.0, "%conditional.1 = bf16[8]{0} conditional(s32[] %p, ...), "
         "branch_computations={%b0, %b1}", part + "/cond"),
        (12.0, 2.0, "%fusion.9 = bf16[8]{0} fusion(...)",
         part + "/cond/branch_1_fun/add"),
        (15.0, 1.0, "%copy.4 = bf16[8]{0} copy(...)", "")])])]
    evs = profile.device_events(planes)[0]
    assert [op for _, _, op in evs].count(profile.SPANS_OTHERS) == 2
    chip = profile.summarize(planes)["chips"]["0"]
    ns = 1e-9
    assert chip["busy_s"] == pytest.approx(13 * ns)     # 0-10, 12-14, 15-16
    assert chip["sub_scope_s"] == {
        "bwd/MixtureOfExpertsLayer/experts": pytest.approx(7 * ns),
        "kernel/MixtureOfExpertsLayer/experts": pytest.approx(3 * ns)}
    assert chip["device_s"] == {
        "bwd": {"MixtureOfExpertsLayer": pytest.approx(7 * ns)},
        "kernel": {"MixtureOfExpertsLayer": pytest.approx(3 * ns)},
        "unscoped": {"unscoped": pytest.approx(1 * ns)}}
    # of the 11 ns of instructions that ran, 1 has no scope
    assert chip["scoped_share"] == pytest.approx(10 / 11)


def test_the_parts_and_kernels_come_from_the_layer_classes():
    from deeplearning4j_tpu.monitor import profile
    parts, kernels = profile.layer_tables()
    assert parts["MixtureOfExpertsLayer"] == frozenset(
        L.MixtureOfExpertsLayer.scope_parts)
    # vertices declare parts as layers do
    assert parts["LoopVertex"] == frozenset(("body",))
    assert set(parts) == {"MixtureOfExpertsLayer", "LoopVertex",
                          "LoopExitOutputLayer", "SelfAttentionLayer"}
    assert parts["SelfAttentionLayer"] == frozenset(("attn_core", "attn_gate"))
    # XLA's grouped products and the gmm tier's kernels, by name
    assert kernels == {name: ("MixtureOfExpertsLayer", "experts")
                       for name in ("ragged-dot", "dl4j_gmm", "dl4j_tgmm")}

    class Another(L.Layer):
        scope_kernels = {"ragged-dot": "products"}
    L.LAYER_REGISTRY["Another"] = Another
    try:
        with pytest.raises(ValueError, match="claimed by"):
            profile.layer_tables()
    finally:
        del L.LAYER_REGISTRY["Another"]
    # a part is a part only of the layer that names it
    assert profile.sub_scope(
        "jit(s)/jvp(fwd/DenseLayer/fc)/experts/dot_general") is None


def test_the_step_names_the_expert_layers_parts():
    import re
    from deeplearning4j_tpu.monitor import profile
    net = _net(dict(CFG, layers_run=[0, 3]))
    net.init()
    ids = jnp.zeros((2, 32), jnp.int32)
    hlo = jax.jit(net._build_step_raw()).lower(
        net.net_params, net.net_state, net.opt_states, (ids,), (ids,), None,
        None, jnp.int32(0), jax.random.PRNGKey(0)).as_text(debug_info=True)
    seen = {profile.sub_scope(n) for n in re.findall(r'"(jit\([^"]+)"', hlo)}
    assert {f"{d}/MixtureOfExpertsLayer/{p}" for d in ("fwd", "bwd")
            for p in ("route", "dispatch", "experts", "combine")} <= seen
