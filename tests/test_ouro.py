"""The Ouro looped decoder (models/ouro.py), the graph's loop construct
(LoopVertex) and the head that scores its exits (LoopExitOutputLayer), on
the CPU at a small size, against the plain reference the benchmark keeps
(benchmark/reference/ouro.py): hidden 64, 4 heads of 16, MLP 96, 3 layers
run 3 times, vocabulary 256, 32 tokens a sequence, seeded weights.

Tolerances.  Both sides compute in float32 on the CPU and differ only in
the order of their sums (one scan over the passes against a Python loop;
one fused step against a sequence at a time), so agreement is to
round-off: 2e-5 of the largest value compared."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ouro as ref
from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.models.ouro import ouro
from deeplearning4j_tpu.nn.conf import graph_conf as G
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.network import GlobalConf
from deeplearning4j_tpu.nn.graph import ComputationGraph

RTOL = 2e-5     # of the largest value compared; see the module's docstring

CFG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 16, "intermediate_size": 96, "vocab_size": 256,
    "num_hidden_layers": 3, "layers_run": [0, 1, 2], "total_ut_steps": 3,
    "rms_norm_eps": 1e-6, "rope_theta": 1e6, "entropy_weight": 0.1,
    "seq_len": 32,
}
BUILDER = ("vocab_size", "hidden_size", "num_attention_heads",
           "num_key_value_heads", "intermediate_size", "total_ut_steps",
           "rms_norm_eps", "rope_theta", "entropy_weight")
ADAM = dict(lr=3e-4, beta1=0.9, beta2=0.95, eps=1e-8)
STACK_LEAVES = [f"l{i}_{leaf}" for i in CFG["layers_run"] for leaf in (
    "attn_in_norm/gamma", "attn/Wq", "attn/Wk", "attn/Wv", "attn/Wo",
    "attn_out_norm/gamma", "mlp_in_norm/gamma", "mlp/W1", "mlp/W3", "mlp/W2",
    "mlp_out_norm/gamma")] + ["final_norm/gamma"]
LEAVES = ([("embed", "W")] + [("stack", k) for k in STACK_LEAVES]
          + [("head", k) for k in ("W", "w_g", "b_g")])


def _close(got, want, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.abs(want).max()), 1e-30)
    assert got.shape == want.shape
    assert float(np.abs(got - want).max()) <= rtol * scale, \
        (float(np.abs(got - want).max()), scale)


def _net(cfg=CFG, **over):
    args = {k: cfg[k] for k in BUILDER}
    args.update(layers=cfg["layers_run"], seq_len=cfg["seq_len"], **over)
    return ouro(**args)


def _copies(weights):
    return {n: {k: jnp.array(v) for k, v in leaves.items()}
            for n, leaves in weights.items()}


def _grads(net, x, y):
    grad_step = jax.jit(net._build_grad_raw())
    return grad_step(
        net.net_params, net.net_state, (jnp.asarray(x),), (jnp.asarray(y),),
        None, None, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def seeded():
    """The program with the reference's seeded weights, three batches of
    token ids, and both sides' first gradient."""
    weights = ref.init_params(CFG, jax.random.PRNGKey(33))
    # a gate that is not at its start: bias 0 and a small weight leave
    # every lam near a half
    weights["head"]["b_g"] = jnp.array([0.3], jnp.float32)
    net = _net()
    net.init(params=_copies(weights))
    rng = np.random.default_rng(33)
    ids = rng.integers(0, CFG["vocab_size"], (3, 2, CFG["seq_len"] + 1),
                       dtype=np.int32)
    batches = [(b[:, :-1], b[:, 1:]) for b in ids]
    x, y = batches[0]
    score, state, grads = _grads(net, x, y)
    ref_loss, ref_grads = jax.value_and_grad(
        ref.loss_fn(CFG, "float32"))(weights, jnp.asarray(x), jnp.asarray(y))
    return dict(net=net, weights=weights, batches=batches, state=state,
                score=float(score), grads=grads, ref_loss=float(ref_loss),
                ref_grads=ref_grads)


# --- the whole model against the reference ---------------------------------
def test_loss_matches_the_reference(seeded):
    assert seeded["score"] == pytest.approx(seeded["ref_loss"], rel=RTOL)
    # about ln(256) less at most 0.1 ln(3) from a random start
    assert 5.0 < seeded["score"] < 6.5


def test_the_vertices_hold_the_reference_leaves_and_no_other(seeded):
    for vertex, leaves in seeded["weights"].items():
        assert set(seeded["grads"][vertex]) == set(leaves)
    assert sorted(LEAVES) == sorted(
        (v, k) for v in ("embed", "stack", "head")
        for k in seeded["weights"][v])


@pytest.mark.parametrize("vertex,leaf", LEAVES,
                         ids=[f"{v}.{k}" for v, k in LEAVES])
def test_every_leaf_gradient_matches_the_reference(seeded, vertex, leaf):
    want = seeded["ref_grads"][vertex][leaf]
    assert float(jnp.abs(want).max()) > 0
    _close(seeded["grads"][vertex][leaf], want)


@pytest.fixture(scope="module")
def trained(seeded):
    """Three Adam steps through ComputationGraph.fit() on a fresh copy,
    and the reference's three steps written out."""
    net = _net()
    net.init(params=_copies(seeded["weights"]))
    scores = []

    class Scores:
        def iteration_done(self, model, iteration):
            scores.append(float(model._score))
    net.set_listeners(Scores())
    net.fit(ListDataSetIterator([DataSet(x, y)
                                 for x, y in seeded["batches"]]))
    # the reference's loop takes its weights over: a set of its own
    out = ref.follow(ref.loss_fn(CFG, "float32"), _copies(seeded["weights"]),
                     seeded["batches"], ADAM["lr"], ADAM["beta1"],
                     ADAM["beta2"], ADAM["eps"])
    return net, scores, out


def test_three_adam_steps_through_fit_follow_the_reference_losses(trained):
    net, scores, out = trained
    assert net.iteration == 3 and len(scores) == 3
    assert scores == pytest.approx(out["losses"], rel=RTOL)
    assert net.compile_telemetry.retraces <= 1


@pytest.mark.parametrize("vertex", ["embed", "stack", "head"])
def test_three_adam_steps_move_every_leaf_as_the_reference_does(
        seeded, trained, vertex):
    net, _, out = trained
    for leaf, start in seeded["weights"][vertex].items():
        moved = np.asarray(net.net_params[vertex][leaf]) - np.asarray(start)
        norm = float(np.sqrt(np.sum(np.square(moved.astype(np.float64)))))
        assert norm > 0
        assert norm == pytest.approx(float(out["change_norms"][vertex][leaf]),
                                     rel=2e-3)


def test_the_builder_states_the_updater_the_configuration_assumes():
    net = _net()
    for name in ("embed", "stack", "head"):
        u = net.init().updaters[name]
        assert u.name == "adam" and u.hyper == {
            "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8}, name


# --- the loop against its unrolled copy --------------------------------------
@G.register_vertex
@dataclasses.dataclass
class _PassStack(G.GraphVertexConf):
    """[N, T, C] x R -> [R, N, T, C]: what a loop hands its head."""

    def forward(self, params, state, inputs, *, train, rng, masks=None):
        return jnp.stack(inputs), state, self.output_mask(masks)

    def output_type(self, input_types):
        return input_types[0]


def _block(b, n, x, cfg=CFG):
    norm = lambda: L.RMSNormLayer(eps=cfg["rms_norm_eps"])  # noqa: E731
    b.add_layer(f"{n}_attn_in_norm", norm(), x)
    b.add_layer(f"{n}_attn", L.SelfAttentionLayer(
        n_out=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        causal=True, rotary_theta=cfg["rope_theta"], bias=False),
        f"{n}_attn_in_norm")
    b.add_layer(f"{n}_attn_out_norm", norm(), f"{n}_attn")
    b.add_vertex(f"{n}_attn_add", G.ElementWiseVertex(op="add"),
                 f"{n}_attn_out_norm", x)
    b.add_layer(f"{n}_mlp_in_norm", norm(), f"{n}_attn_add")
    b.add_layer(f"{n}_mlp", L.GatedDenseLayer(
        n_out=cfg["hidden_size"], hidden=cfg["intermediate_size"]),
        f"{n}_mlp_in_norm")
    b.add_layer(f"{n}_mlp_out_norm", norm(), f"{n}_mlp")
    b.add_vertex(f"{n}_mlp_add", G.ElementWiseVertex(op="add"),
                 f"{n}_mlp_out_norm", f"{n}_attn_add")
    return f"{n}_mlp_add"


def _unrolled(cfg=CFG, head=None):
    """The same model with no loop: R copies of the stack in a row, each
    with leaves of its own (vertices ``p<r>_<block vertex>``)."""
    g = GlobalConf(seed=1, learning_rate=3e-4, updater="adam",
                   activation="identity", weight_init="normal")
    b = G.GraphBuilder(g).add_inputs("ids")
    b.add_layer("embed", L.EmbeddingLayer(
        n_in=cfg["vocab_size"], n_out=cfg["hidden_size"], bias=False), "ids")
    x, outs = "embed", []
    for r in range(cfg["total_ut_steps"]):
        for i in cfg["layers_run"]:
            x = _block(b, f"p{r}_l{i}", x, cfg)
        b.add_layer(f"p{r}_final_norm",
                    L.RMSNormLayer(eps=cfg["rms_norm_eps"]), x)
        x = f"p{r}_final_norm"
        outs.append(x)
    if head is not None:
        b.add_layer("head", head, x)
    else:
        b.add_vertex("passes", _PassStack(), *outs)
        b.add_layer("head", L.LoopExitOutputLayer(
            n_out=cfg["vocab_size"], passes=cfg["total_ut_steps"],
            entropy_weight=cfg["entropy_weight"], activation="softmax"),
            "passes")
    return ComputationGraph(
        b.set_outputs("head").set_input_types(
            InputType.recurrent(cfg["vocab_size"], cfg["seq_len"])).build())


def _unrolled_weights(weights, passes):
    out = {"embed": weights["embed"], "head": weights["head"]}
    for path, a in weights["stack"].items():
        vertex, leaf = path.split("/")
        for r in range(passes):
            out.setdefault(f"p{r}_{vertex}", {})[leaf] = a
    return out


@pytest.fixture(scope="module")
def unrolled(seeded):
    net = _unrolled()
    w = _unrolled_weights(seeded["weights"], CFG["total_ut_steps"])
    net.init(params={n: _copies({n: w.get(n, {})})[n] for n in net.order})
    x, y = seeded["batches"][0]
    score, _, grads = _grads(net, x, y)
    return float(score), grads


def test_the_loop_scores_as_its_unrolled_copy(seeded, unrolled):
    assert seeded["score"] == pytest.approx(unrolled[0], rel=RTOL)
    for leaf in ("W", "w_g", "b_g"):
        _close(seeded["grads"]["head"][leaf], unrolled[1]["head"][leaf])
    _close(seeded["grads"]["embed"]["W"], unrolled[1]["embed"]["W"])


@pytest.mark.parametrize("path", STACK_LEAVES)
def test_a_shared_leaf_gradient_is_the_sum_over_the_passes(
        seeded, unrolled, path):
    vertex, leaf = path.split("/")
    copies = [unrolled[1][f"p{r}_{vertex}"][leaf]
              for r in range(CFG["total_ut_steps"])]
    # every pass sends its own, and they differ
    assert all(float(jnp.abs(c).max()) > 0 for c in copies)
    assert float(jnp.abs(copies[0] - copies[-1]).max()) > 0
    _close(seeded["grads"]["stack"][path], sum(copies))


def test_one_pass_is_the_plain_stack_with_the_final_norm(seeded):
    """R = 1: p = (1), no entropy, and the loss is the plain decoder's
    cross-entropy through an RnnOutputLayer."""
    one = dict(CFG, total_ut_steps=1)
    looped = _net(one)
    looped.init(params=_copies(seeded["weights"]))
    plain = _unrolled(one, head=L.RnnOutputLayer(
        n_out=CFG["vocab_size"], activation="softmax", loss="mcxent",
        bias=False, time_reduction="mean"))
    w = _unrolled_weights(seeded["weights"], 1)
    w["head"] = {"W": w["head"]["W"]}
    plain.init(params={n: _copies({n: w.get(n, {})})[n] for n in plain.order})
    x, y = seeded["batches"][0]
    s1, st, g1 = _grads(looped, x, y)
    s2, _, g2 = _grads(plain, x, y)
    assert float(s1) == pytest.approx(float(s2), rel=RTOL)
    _close(g1["head"]["W"], g2["head"]["W"])
    _close(g1["embed"]["W"], g2["embed"]["W"])
    for path in STACK_LEAVES:
        vertex, leaf = path.split("/")
        _close(g1["stack"][path], g2[f"p0_{vertex}"][leaf])
    assert float(jnp.abs(g1["head"]["w_g"]).max()) == 0.0
    assert np.asarray(st["head"]["loop_exit_mass"]).tolist() == [1.0]
    _close(looped.output(x)[0], plain.output(x)[0])


def test_recomputation_per_block_changes_no_gradient(seeded):
    kept = _net(recompute=False)
    assert kept.conf.vertices["stack"].recompute_blocks is None
    blocks = seeded["net"].conf.vertices["stack"].recompute_blocks
    assert len(blocks) == 3 and blocks[-1][-1] == "final_norm"
    kept.init(params=_copies(seeded["weights"]))
    score, _, grads = _grads(kept, *seeded["batches"][0])
    assert float(score) == pytest.approx(seeded["score"], rel=1e-6)
    for vertex, leaf in LEAVES:
        _close(grads[vertex][leaf], seeded["grads"][vertex][leaf], rtol=2e-6)


def _loop_scans(net, x, y):
    """The gradient's scans over the passes: the stack's forward (the
    first) and its way back (the last; the head's two stand between)."""
    jaxpr = jax.make_jaxpr(net._build_grad_raw())(
        net.net_params, net.net_state, (jnp.asarray(x),), (jnp.asarray(y),),
        None, None, jax.random.PRNGKey(0))
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    return scans[0], scans[-1]


def _stacked(scan, passes, like):
    """How many [passes, N, T, C] values a forward scan hands on."""
    shape = (passes,) + tuple(like)
    return sum(1 for v in scan.outvars if tuple(v.aval.shape) == shape)


def test_recomputation_keeps_one_input_a_block_a_pass(seeded):
    """What the backward pass is handed from the forward: with the blocks
    recomputed, [passes, N, T, C] once a block for its input and once for
    the MLP's output it offers (``ops/recompute.py``; attention is dense
    here and offers nothing); without, every activation inside them as
    well."""
    def stacked(net):
        fwd, _ = _loop_scans(net, *seeded["batches"][0])
        return _stacked(fwd, CFG["total_ut_steps"],
                        (2, CFG["seq_len"], CFG["hidden_size"]))
    kept = _net(recompute=False)
    kept.init(params=_copies(seeded["weights"]))
    n_blocks = len(CFG["layers_run"])
    # the passes' outputs, one input a block (the first block's is the
    # carried value itself) and one offered value a block
    assert n_blocks < stacked(seeded["net"]) <= 2 * n_blocks + 2
    assert stacked(kept) >= 2 * stacked(seeded["net"])


def test_a_recomputed_block_does_not_run_the_mlps_last_product_again(
        seeded, monkeypatch):
    """N4's backward reads the MLP's output.  Kept, the second forward
    stops before the product that made it: the way back holds one
    ``dot_general`` a block fewer than under ``jax.checkpoint``'s default
    policy, which keeps a run's inputs alone."""
    def products(net):
        _, bwd = _loop_scans(net, *seeded["batches"][0])
        return str(bwd.params["jaxpr"]).count("dot_general")
    offered = products(seeded["net"])
    monkeypatch.setattr(G, "keeping_offers", jax.checkpoint)
    plain = _net()
    plain.init(params=_copies(seeded["weights"]))
    assert products(plain) - offered == len(CFG["layers_run"])


def test_a_recomputed_block_runs_the_attention_kernel_once(monkeypatch):
    """Under the flash tier (interpret mode here) the core's output and
    row statistics are kept as the backward kernels' residuals: one
    ``dl4j_flash_fwd`` a block in the forward scan, none on the way back,
    where the default policy launches it again; the gradient is the same
    to the last bit, since a kept value is the value computed again."""
    from deeplearning4j_tpu.ops import helpers
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    cfg = dict(CFG, num_attention_heads=2, num_key_value_heads=2,
               head_dim=32, layers_run=[0, 1], seq_len=128)
    rng = np.random.default_rng(34)
    ids = rng.integers(0, cfg["vocab_size"], (1, 129), dtype=np.int32)
    x, y = ids[:, :-1], ids[:, 1:]

    def case():
        helpers.reset_validation()
        pk._disabled.clear()
        net = _net(cfg, seed=7)
        net.init()
        fwd, bwd = _loop_scans(net, x, y)
        calls = [str(s.params["jaxpr"]).count("name=dl4j_flash_fwd")
                 for s in (fwd, bwd)]
        return calls, _grads(net, x, y)

    monkeypatch.setenv("DL4J_PALLAS_FLASH", "1")
    try:
        calls, (score, _, grads) = case()
        monkeypatch.setattr(G, "keeping_offers", jax.checkpoint)
        plain_calls, (plain_score, _, plain_grads) = case()
    finally:
        helpers.reset_validation()
    assert calls == [2, 0]
    assert plain_calls == [2, 2]
    assert float(score) == float(plain_score)
    for path, g in grads["stack"].items():
        np.testing.assert_array_equal(np.asarray(g),
                                      np.asarray(plain_grads["stack"][path]))


def _pre_norm_loop():
    """x + MLP(N(x)) over [N, 8, 16], three passes: nothing reads the
    MLP's output but the add."""
    body = (G.GraphBuilder(GlobalConf(activation="identity",
                                      weight_init="normal"))
            .add_inputs("h")
            .add_layer("norm", L.RMSNormLayer(), "h")
            .add_layer("mlp", L.GatedDenseLayer(n_out=16, hidden=24), "norm")
            .add_vertex("add", G.ElementWiseVertex(op="add"), "mlp", "h")
            .set_outputs("add").build())
    loop = G.LoopVertex.of(body, passes=3,
                           recompute_blocks=[["norm", "mlp", "add"]])
    kind = InputType.recurrent(16, 8)
    loop.infer_body([kind])
    params, state, _ = loop.initialize(jax.random.PRNGKey(0), [kind])
    return loop, params, state


def test_an_offer_without_a_reader_costs_no_residual(monkeypatch):
    """A pre-norm body: the add's backward needs no value, so the MLP's
    offered output is pruned with the product that made it, and the
    forward scan hands on what it hands on under the default policy."""
    x = jnp.ones((2, 8, 16), jnp.float32)

    def handed_on():
        loop, params, state = _pre_norm_loop()
        jaxpr = jax.make_jaxpr(jax.grad(lambda p: jnp.sum(jnp.square(
            loop.forward(p, state, [x], train=True,
                         rng=jax.random.PRNGKey(1))[0]))))(params)
        fwd = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"][0]
        return _stacked(fwd, 3, x.shape), len(fwd.outvars)
    offered = handed_on()
    monkeypatch.setattr(G, "keeping_offers", jax.checkpoint)
    assert offered == handed_on()
    assert offered[0] == 2      # the passes' outputs and the block's input


def test_an_offer_outside_a_recomputed_run_lowers_to_nothing():
    """A layer offers without knowing who runs it: where no
    ``jax.checkpoint`` takes the offer (every model but a looped one) the
    gradient's program is the program without it, text for text."""
    from deeplearning4j_tpu.ops import recompute

    def program(offer):
        def loss(x, w):
            y = offer(jnp.tanh(x @ w))
            return jnp.sum(y * y)
        return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            jnp.ones((8, 8)), jnp.ones((8, 8))).as_text()
    assert program(recompute.offer) == program(lambda y: y)


def test_the_step_holds_the_body_once(seeded):
    """One scan over the passes, not R copies: the step's program has as
    many matrix products at three passes as at one."""
    def products(net):
        x, y = (jnp.asarray(a) for a in seeded["batches"][0])
        text = str(jax.make_jaxpr(net._build_grad_raw())(
            net.net_params, net.net_state, (x,), (y,), None, None,
            jax.random.PRNGKey(0)))
        return text.count("scan["), text.count("dot_general")
    one = _net(dict(CFG, total_ut_steps=1))
    one.init()
    scans, dots = products(seeded["net"])
    assert scans >= 2                       # the passes, and their way back
    assert dots == products(one)[1]


# --- the head that scores the exits ------------------------------------------
@pytest.fixture(scope="module")
def head_case():
    layer = L.LoopExitOutputLayer(n_out=11, passes=4, entropy_weight=0.1,
                                  activation="softmax", weight_init="normal")
    params, state, _ = layer.initialize(jax.random.PRNGKey(1),
                                        InputType.recurrent(8, 6))
    params["b_g"] = jnp.array([-0.4], jnp.float32)
    params["w_g"] = params["w_g"] * 3.0
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 3, 6, 8)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 11, (3, 6)), jnp.int32)
    return layer, params, state, x, ids


def test_the_exit_distribution_sums_to_one(head_case):
    layer, params, _, x, _ = head_case
    p = np.asarray(layer.exit_distribution(params, x))
    assert p.shape == (4, 3, 6) and (p > 0).all()
    np.testing.assert_allclose(p.sum(axis=0), 1.0, rtol=1e-6)
    lam = 1 / (1 + np.exp(-(np.asarray(x) @ np.asarray(params["w_g"])
                            + float(params["b_g"][0]))))
    np.testing.assert_allclose(p[0], lam[0], rtol=1e-5)
    np.testing.assert_allclose(p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]),
                               rtol=1e-5)
    np.testing.assert_allclose(
        p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), rtol=1e-5)


@pytest.mark.parametrize("scale", [30.0, 300.0])
def test_a_gate_that_saturates_sends_finite_gradients(head_case, scale):
    """lam = 1 to float32's last digit makes a later pass's p exactly 0;
    the distribution is kept in logarithms, so p log p is 0 and its
    gradient a number (it read NaN on the chip once, PERF.md 6)."""
    layer, params, state, x, ids = head_case
    hot = dict(params, w_g=params["w_g"] * scale)
    assert float(layer.exit_distribution(hot, x).min()) < 1e-30
    loss, grads = jax.value_and_grad(lambda p: jnp.mean(
        layer.score_from_input(p, state, x, ids)[0]))(hot)
    assert np.isfinite(float(loss))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads.values())
    np.testing.assert_allclose(
        np.asarray(layer.exit_distribution(hot, x)).sum(axis=0), 1.0,
        rtol=1e-6)


@pytest.mark.parametrize("masked", [False, True], ids=["whole", "masked"])
@pytest.mark.parametrize("labels", ["ids", "one-hot"])
def test_the_loss_is_the_formula_from_the_passes_cross_entropies(
        head_case, labels, masked):
    layer, params, state, x, ids = head_case
    z = np.asarray(x, np.float64) @ np.asarray(params["W"], np.float64)
    logp = z - np.log(np.exp(z).sum(-1, keepdims=True))
    ce = -np.take_along_axis(logp, np.asarray(ids)[None, ..., None], -1)[..., 0]
    p = np.asarray(layer.exit_distribution(params, x), np.float64)
    rows = (p * ce).sum(0) + 0.1 * (p * np.log(p)).sum(0)       # [N, T]
    mask = (np.arange(6)[None, :] < np.array([6, 4, 1])[:, None]) \
        .astype(np.float32) if masked else None
    m = np.ones((3, 6)) if mask is None else mask
    want = (rows * m).sum(1) / m.sum(1)
    y = ids if labels == "ids" else jax.nn.one_hot(ids, 11)
    got, new = layer.score_from_input(
        params, state, x, y, None if mask is None else jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(new["loop_exit_mass"]),
                               (p * m).sum((1, 2)) / m.sum(), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(new["loop_exit_loss"]),
                               (ce * m).sum((1, 2)) / m.sum(), rtol=2e-5)
    assert float(new["loop_exit_mass"].sum()) == pytest.approx(1.0, rel=1e-5)


def test_output_is_the_last_pass_distribution(seeded):
    net, (x, _) = seeded["net"], seeded["batches"][0]
    out = np.asarray(net.output(x)[0])
    assert out.shape == (2, CFG["seq_len"], CFG["vocab_size"])
    hs = ref.passes_fn(CFG)(seeded["weights"], jnp.asarray(x[1]))
    z = hs[-1] @ seeded["weights"]["head"]["W"]
    _close(out[1], jax.nn.softmax(z, axis=-1), rtol=2e-4)


def test_a_head_that_scores_from_its_input_is_asked_for_no_preactivations(
        seeded):
    net, (x, y) = seeded["net"], seeded["batches"][0]
    acts, preouts, _, _ = net._forward_all(
        net.net_params, net.net_state, {"ids": jnp.asarray(x)}, {}, True,
        jax.random.PRNGKey(0), preout_for=["head"])
    assert preouts["head"].shape == (3, 2, CFG["seq_len"], 64)
    assert "head" not in acts
    assert net.score(DataSet(x, y)) == pytest.approx(seeded["score"],
                                                     rel=RTOL)
    per = net.score_examples(DataSet(x, y))
    assert per.shape == (2,) and float(per.mean()) == pytest.approx(
        seeded["score"], rel=RTOL)


@pytest.mark.parametrize("recompute", [True, False],
                         ids=["recomputed", "kept"])
def test_numeric_gradients_in_float64(recompute):
    from deeplearning4j_tpu.nn.gradientcheck import (
        check_computation_graph_gradients)
    net = ouro(vocab_size=12, hidden_size=8, num_attention_heads=2,
               num_key_value_heads=2, intermediate_size=6,
               num_hidden_layers=2, total_ut_steps=3, seq_len=5,
               recompute=recompute, seed=5)
    net.init()
    net.net_params["head"]["b_g"] = jnp.array([0.2], jnp.float32)
    rng = np.random.default_rng(5)
    ids = rng.integers(0, 12, (2, 6)).astype(np.int32)
    assert check_computation_graph_gradients(
        net, [ids[:, :-1]], [ids[:, 1:]], subset=32, print_results=False)


# --- each leaf once ----------------------------------------------------------
def test_num_params_and_summary_count_each_leaf_once(seeded):
    net = seeded["net"]
    D, F, V, n = 64, 96, 256, len(CFG["layers_run"])
    layer = 4 * D * D + 3 * D * F + 4 * D
    assert net.num_params() == n * layer + D + 2 * V * D + D + 1
    assert net.params().shape == (net.num_params(),)
    assert f"Total parameters: {net.num_params():,}" in net.summary()
    assert "LoopVertex" in net.summary()
    assert len(jax.tree_util.tree_leaves(net.opt_states["stack"]["m"])) \
        == len(STACK_LEAVES)
    # at the published sizes, 8 of the 48 layers: the configuration's count
    assert 8 * (4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048) + 2048 \
        + 2 * 49152 * 2048 + 2048 + 1 == 612_438_017


def test_the_configuration_round_trips_through_json(seeded):
    conf = seeded["net"].conf
    again = type(conf).from_json(conf.to_json())
    loop = again.vertices["stack"]
    assert isinstance(loop, G.LoopVertex) and loop.passes == 3
    assert loop.body_conf().topological_order() == \
        conf.vertices["stack"].body_conf().topological_order()
    assert again.to_dict() == conf.to_dict()


@pytest.mark.parametrize("how", ["zip", "checkpoint-directory"])
def test_save_and_restore_continue_the_loss(seeded, trained, tmp_path, how):
    from deeplearning4j_tpu.nn import checkpoint, serialization
    net = _net()
    net.init(params=_copies(seeded["weights"]))
    two = [DataSet(x, y) for x, y in seeded["batches"][:2]]
    if how == "zip":
        net.fit(ListDataSetIterator(two))
        serialization.write_model(net, tmp_path / "ouro.zip")
        back = serialization.restore_computation_graph(tmp_path / "ouro.zip")
        back.iteration = net.iteration
    else:
        net.set_listeners(checkpoint.CheckpointListener(
            tmp_path, save_every_n_iterations=1))
        net.fit(ListDataSetIterator(two))
        back = checkpoint.resume_from_checkpoint(tmp_path)
    assert back.iteration == 2 and back.num_params() == net.num_params()
    for vertex, leaf in LEAVES:
        np.testing.assert_array_equal(np.asarray(back.net_params[vertex][leaf]),
                                      np.asarray(net.net_params[vertex][leaf]))
    back.fit(DataSet(*seeded["batches"][2]))
    assert float(back.score()) == pytest.approx(trained[1][2], rel=RTOL)


# --- what the loop refuses, and what it publishes -----------------------------
def test_a_body_that_keeps_state_is_refused():
    g = GlobalConf(seed=1)
    body = (G.GraphBuilder(g).add_inputs("h")
            .add_layer("bn", L.BatchNormalization(), "h")
            .set_outputs("bn").build())
    b = G.GraphBuilder(g).add_inputs("x")
    b.add_vertex("loop", G.LoopVertex.of(body, passes=2), "x")
    b.add_layer("out", L.OutputLayer(n_out=3, activation="softmax"), "loop")
    net = ComputationGraph(b.set_outputs("out").set_input_types(
        InputType.feed_forward(4)).build())
    with pytest.raises(ValueError, match="keeps state"):
        net.init()


@pytest.mark.parametrize("blocks,match", [
    ([["a"]], "every body vertex once"),
    ([["b"], ["a"]], "out of order"),
])
def test_recompute_blocks_name_every_vertex_in_order(blocks, match):
    body = (G.GraphBuilder(GlobalConf()).add_inputs("h")
            .add_layer("a", L.RMSNormLayer(), "h")
            .add_layer("b", L.RMSNormLayer(), "a").set_outputs("b").build())
    with pytest.raises(ValueError, match=match):
        G.LoopVertex.of(body, passes=2, recompute_blocks=blocks)


def test_the_loop_refuses_the_carried_decode_step(seeded):
    net = seeded["net"]
    with pytest.raises(NotImplementedError, match="cache per pass"):
        net.rnn_time_step(np.zeros((1, 4), np.int32))


def test_fit_publishes_the_passes_and_the_exits(trained):
    snap = monitor.get_registry().snapshot()

    def series(name):
        return {tuple(sorted(s["labels"].items())): s["value"]
                for s in snap[name]["samples"]}
    assert series("dl4j_loop_passes_total")[(("vertex", "stack"),)] >= 9
    mass, loss = series("dl4j_loop_exit_mass"), series("dl4j_loop_exit_loss")
    got = [mass[(("pass", str(r)), ("vertex", "head"))] for r in (1, 2, 3)]
    assert sum(got) == pytest.approx(1.0, rel=1e-5)
    net = trained[0]
    np.testing.assert_allclose(
        got, np.asarray(net.net_state["head"]["loop_exit_mass"]), rtol=1e-6)
    assert all(5.0 < loss[(("pass", str(r)), ("vertex", "head"))] < 6.5
               for r in (1, 2, 3))


# --- the device trace's scopes -------------------------------------------------
@pytest.mark.parametrize("op_name,want", [
    ("jit(cg_train_step)/jvp(fwd/LoopVertex/stack)/body/while/body/"
     "checkpoint/SelfAttentionLayer/l0_attn/dot_general",
     "fwd/LoopVertex/body"),
    ("jit(cg_train_step)/transpose(jvp(fwd/LoopVertex/stack))/body/while/"
     "body/checkpoint/rematted_computation/GatedDenseLayer/l0_mlp/dot_general",
     "bwd/LoopVertex/body"),
    ("jit(cg_train_step)/jvp(loss/fwd/LoopExitOutputLayer/head)/head/while/"
     "body/checkpoint/dot_general", "fwd/LoopExitOutputLayer/head"),
    ("jit(cg_train_step)/transpose(jvp(loss/fwd/LoopExitOutputLayer/head))/"
     "gate/mul", "bwd/LoopExitOutputLayer/gate"),
    ("jit(cg_train_step)/jvp(fwd/LoopVertex/stack)/convert_element_type",
     None),
])
def test_profile_reads_the_loops_parts(op_name, want):
    from deeplearning4j_tpu.monitor import profile
    assert profile.sub_scope(op_name) == want
    direction, kind, _ = profile.classify(op_name)
    assert (direction, kind) == (op_name.count("transpose(") and "bwd"
                                 or "fwd", op_name.split("fwd/")[1].split("/")[0])


def test_profile_tells_a_kernel_launched_again_from_its_first_launch():
    """``recomputed_kernels_s``: a Pallas kernel's device time under
    ``rematted_computation``, by kernel; a kernel that ran and was never
    launched again reads 0.0, and an instruction that is no Pallas
    kernel has no entry."""
    from deeplearning4j_tpu.monitor import profile
    stack = "jit(cg_train_step)/transpose(jvp(fwd/LoopVertex/stack))/body/while/body/"
    call = ('%{}.{} = (bf16[16,4096,128]{{2,1,0}}, f32[16,1,4096]{{2,1,0}}) '
            'custom-call(bf16[16,4096,128]{{2,1,0}} %p), '
            'custom_call_target="tpu_custom_call"')
    first = ("jit(cg_train_step)/jvp(fwd/LoopVertex/stack)/body/while/body/"
             "checkpoint/SelfAttentionLayer/l0_attn/pallas_call")
    again = (stack + "checkpoint/rematted_computation/SelfAttentionLayer/"
             "l0_attn/pallas_call")
    events = [
        (0.0, 700.0, call.format("dl4j_flash_fwd", 3), first),
        (1000.0, 300.0, call.format("dl4j_flash_bwd", 1),
         stack + "checkpoint/SelfAttentionLayer/l0_attn/pallas_call"),
        (2000.0, 50.0, "%fusion.9 = bf16[8]{0} fusion(...)",
         stack + "checkpoint/rematted_computation/RMSNormLayer/l0_n/mul")]
    assert profile.recomputed_kernels(events) == {
        "dl4j_flash_fwd": 0.0, "dl4j_flash_bwd": 0.0}
    events.append((3000.0, 650.0, call.format("dl4j_flash_fwd", 12), again))
    assert profile.recomputed_kernels(events) == {
        "dl4j_flash_fwd": pytest.approx(650e-9), "dl4j_flash_bwd": 0.0}
    chip = profile.summarize(
        [("/device:TPU:0", [("XLA Ops", events)])])["chips"]["0"]
    assert chip["recomputed_kernels_s"] == profile.recomputed_kernels(events)
    # the sum by layer type counts the same launch, and the fusion
    assert chip["recomputed_s"] == {"LoopVertex": pytest.approx(700e-9)}


def test_the_step_names_the_loops_parts(seeded):
    import re
    from deeplearning4j_tpu.monitor import profile
    net = _net()
    net.init()
    ids = jnp.zeros((2, 32), jnp.int32)
    # the compiled program's metadata: a loop's body is a function of its
    # own, and only the compiler joins its names to the caller's
    hlo = jax.jit(net._build_step_raw()).lower(
        net.net_params, net.net_state, net.opt_states, (ids,), (ids,), None,
        None, jnp.int32(0), jax.random.PRNGKey(0)).compile().as_text()
    names = re.findall(r'op_name="([^"]+)"', hlo)
    seen = {profile.sub_scope(n) for n in names}
    assert {"fwd/LoopVertex/body", "bwd/LoopVertex/body"} | {
        f"{d}/LoopExitOutputLayer/{p}" for d in ("fwd", "bwd")
        for p in ("head", "gate")} <= seen
    # the blocks' second forward is told apart by name
    assert any("rematted_computation" in n and "fwd/LoopVertex/stack" in n
               for n in names)
