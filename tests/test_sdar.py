"""The SDAR-MoE block-diffusion decoder (models/sdar.py) and what it
brought, on the CPU at a small size, against the plain reference the
benchmark keeps (benchmark/reference/sdar_moe.py): hidden 64, 4 query
heads over 2 key/value heads of 16, 8 experts top-2 by softmax score (4
held), 3 layers, vocabulary 256, sequences of 32 tokens in blocks of 4
(64 rows a sequence through the stack), seeded weights; the mask rule
and the flash kernels under it (interpret mode) at sizes of their own.

Tolerances as tests/test_lfm2.py states them: both sides compute in
float32 on the CPU and differ in the order of their sums, so agreement
is to round-off, 2e-5 of the largest value compared."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import sdar_moe as ref
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.diffusion import (
    BlockDiffusionNoiser, PreProcessingIterator)
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.models.sdar import sdar_moe
from deeplearning4j_tpu.nn.conf import graph_conf as G
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.ops import helpers, mask_rules
from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.parallel import sequence as seq_ops

RTOL = 2e-5

PUBLISHED = ("vocab_size", "hidden_size", "num_attention_heads",
             "num_key_value_heads", "head_dim", "moe_intermediate_size",
             "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
             "rope_theta")
CFG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "moe_intermediate_size": 48,
    "num_experts_published": 8, "num_experts": 4, "experts_held": [1, 2, 5, 6],
    "num_experts_per_tok": 2, "norm_topk_prob": True, "vocab_size": 256,
    "seq_len": 32, "block_length": 4, "layers_run": [0, 1, 2],
    "rms_norm_eps": 1e-6, "rope_theta": 1e6, "mask_id": 255,
}
MASK_ID = CFG["mask_id"]
ADAM = dict(lr=1e-5, beta1=0.9, beta2=0.95, eps=1e-8)
LEAVES = ([("embed", "W"), ("final_norm", "gamma"), ("head", "W")]
          + [(f"l{i}_{v}", leaf) for i in CFG["layers_run"]
             for v, leaves in (("attn_norm", ("gamma",)),
                               ("moe_norm", ("gamma",)),
                               ("attn", ("Wq", "Wk", "Wv", "Wo", "q_norm",
                                         "k_norm")),
                               ("moe", ("Wg", "W1", "W3", "W2")))
             for leaf in leaves])


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
                seq_len=cfg["seq_len"], block_length=cfg["block_length"],
                **over)
    return sdar_moe(**args)


def _copies(weights, net):
    return {n: jax.tree_util.tree_map(jnp.array, weights.get(n, {}))
            for n in net.order}


def _noised(seed=35, batches=3, rows=2):
    """Batches as the pre-processor hands them over."""
    rng = np.random.default_rng(seed)
    noiser = BlockDiffusionNoiser(CFG["block_length"], MASK_ID, seed)
    return [noiser.pre_process(DataSet(
        rng.integers(0, MASK_ID, (rows, CFG["seq_len"]), dtype=np.int32),
        None)) for _ in range(batches)]


@pytest.fixture(scope="module")
def seeded():
    weights = ref.init_params(CFG, jax.random.PRNGKey(35))
    net = _net()
    net.init(params=_copies(weights, net))
    batches = _noised()
    d = batches[0]
    x, y, w = (jnp.asarray(a) for a in (d.features, d.labels, d.labels_mask))
    grad_step = jax.jit(net._build_grad_raw())
    score, _, grads = grad_step(
        net.net_params, net.net_state, (x,), (y,), None, (w,),
        jax.random.PRNGKey(0))
    ref_loss, ref_grads = jax.value_and_grad(ref.loss_fn(CFG))(
        weights, x, y, w)
    return dict(net=net, weights=weights, batches=batches, score=float(score),
                grads=grads, ref_loss=float(ref_loss), ref_grads=ref_grads)


# --- the whole model against the reference ---------------------------------
def test_logits_and_loss_match_the_reference(seeded):
    net, d = seeded["net"], seeded["batches"][0]
    _, preouts, _, _ = net._forward_all(
        net.net_params, net.net_state, {"ids": jnp.asarray(d.features)}, {},
        True, jax.random.PRNGKey(0), preout_for=["head"])
    logits = ref.logits_fn(CFG)
    assert preouts["head"].shape == (2, CFG["seq_len"], CFG["vocab_size"])
    for s in range(2):
        _close(preouts["head"][s], logits(seeded["weights"], d.features[s]))
    assert seeded["score"] == pytest.approx(seeded["ref_loss"], rel=RTOL)
    # weights of mean about 1 on cross-entropies of about ln(256)
    assert 3.0 < seeded["score"] < 9.0


def test_the_vertices_hold_the_reference_leaves_and_no_other(seeded):
    held = {(v, leaf) for v, p in seeded["net"].net_params.items()
            for leaf in p}
    assert held == set(LEAVES)


@pytest.mark.parametrize("vertex,leaf", LEAVES,
                         ids=[f"{v}-{l}" for v, l in LEAVES])
def test_every_leaf_gradient_matches_the_reference(seeded, vertex, leaf):
    want = seeded["ref_grads"][vertex][leaf]
    assert float(jnp.abs(want).max()) > 0
    _close(seeded["grads"][vertex][leaf], want)


@pytest.fixture(scope="module")
def trained(seeded):
    """Three Adam steps through ComputationGraph.fit() on a fresh copy,
    and the reference's three steps written out."""
    net = _net()
    net.init(params=_copies(seeded["weights"], net))
    scores = []

    class Scores:
        def iteration_done(self, model, iteration):
            scores.append(float(model._score))
    net.set_listeners(Scores())
    net.fit(ListDataSetIterator(seeded["batches"]))
    out = ref.follow(ref.loss_fn(CFG), seeded["weights"],
                     [(d.features, d.labels, d.labels_mask)
                      for d in seeded["batches"]],
                     ADAM["lr"], ADAM["beta1"], ADAM["beta2"], ADAM["eps"])
    return net, scores, out


def test_three_adam_steps_through_fit_follow_the_reference_losses(trained):
    net, scores, out = trained
    assert net.iteration == 3 and len(scores) == 3
    assert scores == pytest.approx(out["losses"], rel=RTOL)
    assert net.compile_telemetry.retraces <= 1


@pytest.mark.parametrize("vertex", sorted({v for v, _ in LEAVES}))
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
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "sdar_30b_a3b_ep8.json")) as f:
        cfg = json.load(f)
    g = _net().conf.global_conf
    u = cfg["updater"]
    assert (g.updater, g.learning_rate, g.adam_mean_decay, g.adam_var_decay,
            g.epsilon) == (u["name"], u["learning_rate"], u["beta1"],
                           u["beta2"], u["epsilon"])
    # the builder's defaults are the published widths, which the file holds
    import inspect
    defaults = {k: p.default for k, p in
                inspect.signature(sdar_moe).parameters.items()}
    for k in ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "head_dim", "moe_intermediate_size", "num_experts_per_tok",
              "norm_topk_prob", "rms_norm_eps", "rope_theta"):
        assert cfg[k] == defaults[k], k
    assert defaults["num_experts"] == cfg["num_experts_published"] == 128
    assert defaults["vocab_size"] == cfg["vocab_size_published"] == 151936
    assert defaults["num_hidden_layers"] == cfg["num_hidden_layers_published"]


def test_output_is_the_distribution_over_the_first_half(seeded):
    net, d = seeded["net"], seeded["batches"][0]
    out = np.asarray(net.output(d.features)[0])
    assert out.shape == (2, CFG["seq_len"], CFG["vocab_size"])
    np.testing.assert_allclose(out.sum(-1), 1.0, rtol=1e-5)
    logits = ref.logits_fn(CFG)(seeded["weights"], d.features[1])
    _close(out[1], jax.nn.softmax(logits, axis=-1), rtol=1e-4)


# --- the mask rule ------------------------------------------------------------
def _four_comparisons(L_, b):
    i = np.arange(2 * L_)[:, None]
    c = np.arange(2 * L_)[None, :]
    j_i, j_c = (i % L_) // b, (c % L_) // b
    noisy_i, noisy_c = i < L_, c < L_
    return np.where(noisy_i,
                    (noisy_c & (j_c == j_i)) | (~noisy_c & (j_c < j_i)),
                    ~noisy_c & (j_c <= j_i))


SHAPES = [(32, 4), (128, 4), (128, 128), (256, 128), (384, 3), (96, 1),
          (64, 64)]


@pytest.mark.parametrize("L_,b", SHAPES)
def test_the_rule_is_the_four_comparisons_written_out(L_, b):
    rule = mask_rules.BlockDiffusion(L_, b)
    T = 2 * L_
    got = np.asarray(rule.live(jnp.arange(T)[:, None], jnp.arange(T)[None, :]))
    want = _four_comparisons(L_, b)
    np.testing.assert_array_equal(got, want)
    assert want.sum() == L_ * L_ + L_ * b == ref.live_pairs(L_, b)
    # the reference writes the same mask on its own
    np.testing.assert_array_equal(
        np.asarray(ref.mask(jnp.arange(T), jnp.arange(T), L_, b)), want)
    np.testing.assert_array_equal(np.asarray(rule.positions(T)),
                                  np.arange(T) % L_)


@pytest.mark.parametrize("L_,b,tile", [(128, 4, 128), (256, 128, 128),
                                       (384, 3, 384), (384, 4, 128),
                                       (512, 8, 256),
                                       (1024, 4, 512), (4096, 4, 512)])
def test_the_tile_plans_cover_the_live_pairs_and_nothing_else(L_, b, tile):
    """Both plans, the q blocks' and the key tiles', name the same tiles:
    every tile with a live pair, none without; a tile visited whole has
    every pair live."""
    rule = mask_rules.BlockDiffusion(L_, b)
    n = 2 * L_ // tile
    live = _four_comparisons(L_, b) if L_ <= 1024 else None

    def tiles(visits):
        whole, boundary = set(), set()
        for a in range(n):
            for step in visits(a, n, tile):
                if step[0] == "range":
                    whole.update((a, s) for s in range(step[1], step[2]))
                elif step[3] is None or int(step[3]):
                    boundary.add((a, step[1]))
        return whole, boundary
    qw, qb = tiles(rule.q_visits)
    kw, kb = tiles(rule.k_visits)
    assert qw == {(q, k) for k, q in kw} and qb == {(q, k) for k, q in kb}
    assert not qw & qb
    counts = mask_rules.tile_counts(rule, 2 * L_, tile)
    assert counts == {"visited": len(qw), "boundary": len(qb),
                      "skipped": n * n - len(qw) - len(qb)}
    if live is not None:
        for q in range(n):
            for k in range(n):
                t = live[q * tile:(q + 1) * tile, k * tile:(k + 1) * tile]
                # (a boundary tile may be all live: a block as wide as it)
                assert t.all() or (q, k) not in qw, (q, k)
                # no tile with a live pair is left out, and none without
                # one is visited (but the clean copy of a noisy q block's
                # own tile where one block fills the tile)
                planned = (q, k) in qw or (q, k) in qb
                assert planned or not t.any(), (q, k)
                assert t.any() or not planned or b == tile, (q, k)


def test_the_cells_tiles_are_eighty_of_two_hundred_and_fifty_six():
    rule = mask_rules.BlockDiffusion(4096, 4)
    assert pk._flash_block(8192, rule) == 512
    assert mask_rules.tile_counts(rule, 8192, 512) == {
        "visited": 56, "boundary": 24, "skipped": 176}
    # a causal mask over the same 2L rows visits 136
    assert mask_rules.tile_counts(mask_rules.CAUSAL, 8192, 512) == {
        "visited": 120, "boundary": 16, "skipped": 120}
    noisy = [rule.q_visits(q, 16, 512) for q in range(8)]
    assert sum(s[0][2] - s[0][1] for s in noisy) == 28
    assert all(int(s[2][3]) == 1 for s in noisy)        # own-half tile
    clean = [rule.q_visits(q, 16, 512) for q in range(8, 16)]
    assert sum(s[0][2] - s[0][1] for s in clean) == 28
    assert all(int(s[2][3]) == 0 for s in clean)


@pytest.mark.parametrize("L_,b,match", [
    (192, 4, "no tile"),            # L is not a multiple of 128
    (256, 3, "whole number"),       # b does not divide L
    (384, 5, "whole number"),
])
def test_a_shape_the_tiles_cannot_meet_on_is_refused(L_, b, match):
    with pytest.raises(ValueError, match=match):
        rule = mask_rules.BlockDiffusion(L_, b)
        pk._flash_block(2 * L_, rule)


def test_a_block_that_divides_no_tile_is_refused():
    rule = mask_rules.BlockDiffusion(640, 40)      # 128 % 40, and 640 > the cap
    with pytest.raises(ValueError, match="no tile"):
        pk._flash_block(1280, rule)
    q = jnp.zeros((1, 1, 1280, 32))
    with pytest.raises(ValueError, match="no tile"):
        pk.flash_attention(q, q, q, jnp.ones((1, 1280)), rule)


def test_the_rule_refuses_any_other_row_count():
    rule = mask_rules.BlockDiffusion(128, 4)
    q = jnp.zeros((1, 1, 384, 32))
    with pytest.raises(ValueError, match="2 x 128 rows"):
        pk.flash_attention(q, q, q, jnp.ones((1, 384)), rule)
    with pytest.raises(ValueError, match="2 x 128 rows"):
        seq_ops.dense_attention(q, q, q, causal=rule, allow_flash=False)


@pytest.mark.parametrize("spec,want", [
    (False, None), (None, None), (True, mask_rules.CAUSAL),
    (("block_diffusion", 64, 4), mask_rules.BlockDiffusion(64, 4)),
    (["block_diffusion", 64, 4], mask_rules.BlockDiffusion(64, 4)),
    (mask_rules.CAUSAL, mask_rules.CAUSAL),
    (mask_rules.BlockDiffusion(8, 2), mask_rules.BlockDiffusion(8, 2)),
])
def test_a_layers_causal_argument_names_a_rule(spec, want):
    assert mask_rules.resolve(spec) == want


def test_an_unknown_rule_is_refused():
    with pytest.raises(ValueError, match="unknown attention mask rule"):
        mask_rules.resolve(("sliding_window", 128))


# --- the kernels under the rule, interpret mode, against the dense core --------
def _qkv(T, H=2, D=32, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(1, H, T, D)), jnp.float32)
                 for _ in range(3))


KERNEL_SHAPES = [(128, 4, 512), (256, 128, 512), (384, 3, 512),
                 (384, 4, 128), (256, 128, 128), (512, 64, 128),
                 (128, 128, 128)]


@pytest.fixture(scope="module")
def kernel_cases():
    """Forward and the three gradients of every shape, both paths, once."""
    cap = pk._FLASH_BLOCK_CAP
    out = {}
    try:
        for L_, b, tile_cap in KERNEL_SHAPES:
            pk._FLASH_BLOCK_CAP = tile_cap
            rule = mask_rules.BlockDiffusion(L_, b)
            q, k, v = _qkv(2 * L_, seed=L_ + b)
            km = jnp.ones((1, 2 * L_), jnp.float32)

            def flash(q, k, v):
                return pk.flash_attention(q, k, v, km, rule)

            def dense(q, k, v):
                return seq_ops.dense_attention(q, k, v, causal=rule,
                                               allow_flash=False)
            got = (flash(q, k, v),) + jax.grad(
                lambda *a: jnp.sum(flash(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
            want = (dense(q, k, v),) + jax.grad(
                lambda *a: jnp.sum(dense(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
            out[L_, b, tile_cap] = (got, want)
    finally:
        pk._FLASH_BLOCK_CAP = cap
    return out


@pytest.mark.parametrize("which", range(4), ids=["forward", "dq", "dk", "dv"])
@pytest.mark.parametrize("shape", KERNEL_SHAPES,
                         ids=[f"L{l}-b{b}-tile{t}" for l, b, t in KERNEL_SHAPES])
def test_the_kernels_agree_with_dense_attention_under_the_rule(
        kernel_cases, shape, which):
    got, want = kernel_cases[shape]
    assert float(jnp.abs(want[which]).max()) > 0
    _close(got[which], want[which], rtol=1e-4)


def test_the_kernels_honour_a_key_mask_under_the_rule():
    L_, b = 128, 4
    rule = mask_rules.BlockDiffusion(L_, b)
    q, k, v = _qkv(2 * L_, seed=9)
    km = jnp.ones((1, 2 * L_), jnp.float32).at[0, L_ + 5:L_ + 9].set(0.0)
    got = pk.flash_attention(q, k, v, km, rule)
    want = seq_ops.dense_attention(q, k, v, causal=rule, key_mask=km,
                                   allow_flash=False)
    _close(got, want, rtol=1e-4)


@pytest.mark.parametrize("which", range(3), ids=["dq", "dk", "dv"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_fused_backward_honours_a_key_mask_under_the_rule(
        monkeypatch, dtype, which):
    """Four tiles of 128 a head; dead keys inside a clean block, and the
    whole of the first noisy block dead: its four rows, which no clean
    block precedes, see no live key, take a dq of zeros and give dk and
    dv nothing."""
    monkeypatch.setattr(pk, "_FLASH_BLOCK_CAP", 128)
    L_, b = 256, 4
    rule = mask_rules.BlockDiffusion(L_, b)
    q, k, v = (a.astype(dtype) for a in _qkv(2 * L_, H=3, D=64, seed=10))
    w = _qkv(2 * L_, H=3, D=64, seed=11)[0]
    km = jnp.ones((1, 2 * L_), jnp.float32).at[0, L_ + 133:L_ + 139].set(0.0)
    km = km.at[0, :b].set(0.0)
    dead_rows = np.zeros(2 * L_, bool)
    dead_rows[:b] = True

    def dense_loss(q, k, v):
        out = seq_ops.dense_attention(
            *(a.astype(jnp.float32) for a in (q, k, v)), causal=rule,
            key_mask=km, allow_flash=False)
        return jnp.sum(jnp.where(dead_rows[None, None, :, None], 0.0, out)
                       * w)
    want = jax.grad(dense_loss, argnums=which)(q, k, v)
    # the kernel is given the dead rows' cotangent too: their p is 0
    got = jax.grad(
        lambda q, k, v: jnp.sum(
            pk.flash_attention(q, k, v, km, rule).astype(jnp.float32) * w),
        argnums=which)(q, k, v)
    assert got.dtype == dtype and float(jnp.abs(want).max()) > 0
    if which == 0:
        assert not np.asarray(got, np.float32)[:, :, dead_rows].any()
    if dtype == jnp.float32:
        _close(got, want, rtol=1e-4)
    else:
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        assert np.linalg.norm(got - want) < 1e-2 * np.linalg.norm(want)


def _count(jaxpr, primitive):
    n = 0
    for e in jaxpr.eqns:
        n += e.primitive.name == primitive
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    n += _count(inner, primitive)
    return n


@pytest.mark.parametrize("model", ["lfm2", "ouro"])
def test_a_causal_layer_lowers_to_the_kernel_calls_it_had(model, monkeypatch):
    """A causal layer's step holds the two kernels once a layer
    application, each with what the causal kernels had before the rule:
    one loop over the tiles before the diagonal and the diagonal tile
    apart (2 + 2 products forward, 5 + 5 for the backward, which builds
    a tile once for dq, dk and dv), at the tile the sequence gives, and
    the rule's tile count is the triangle's."""
    from deeplearning4j_tpu.models import lfm2_moe, ouro
    monkeypatch.setenv("DL4J_PALLAS_FLASH", "1")     # interpret mode here
    helpers.reset_validation()
    pk._disabled.clear()
    try:
        if model == "lfm2":
            net = lfm2_moe(
                vocab_size=64, hidden_size=128, num_attention_heads=4,
                num_key_value_heads=2, intermediate_size=32,
                moe_intermediate_size=16, num_experts=4, num_experts_per_tok=2,
                layer_types=["conv", "conv", "full_attention"],
                num_dense_layers=2, layers=[1, 2], experts_held=[0, 1],
                seq_len=256, seed=1)
            layers_with_attention = 1
        else:
            net = ouro(vocab_size=64, hidden_size=64, num_attention_heads=2,
                       num_key_value_heads=2, intermediate_size=32,
                       num_hidden_layers=2, total_ut_steps=2, seq_len=256,
                       seed=1)
            layers_with_attention = 2       # traced once: the loop's body
        net.init()
        ids = jnp.zeros((1, 256), jnp.int32)
        text = str(jax.make_jaxpr(net._build_grad_raw())(
            net.net_params, net.net_state, (ids,), (ids,), None, None,
            jax.random.PRNGKey(0)))
    finally:
        helpers.reset_validation()
    for name in ("dl4j_flash_fwd", "dl4j_flash_bwd"):
        assert text.count(f"name={name}") >= layers_with_attention, name
    # the kernels alone, at the same shape: products and loops a kernel
    q, k, v = _qkv(256, H=2, D=64)
    km = jnp.ones((1, 256), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(pk.flash_attention(q, k, v, km, True) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    calls = {}

    def walk(j):
        for e in j.eqns:
            if e.primitive.name == "pallas_call":
                calls[e.params["name"]] = e
            for val in e.params.values():
                for sub in (val if isinstance(val, (list, tuple)) else (val,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns") and e.primitive.name != "pallas_call":
                        walk(inner)
    walk(jaxpr.jaxpr)
    want = {"dl4j_flash_fwd": 4, "dl4j_flash_bwd": 10}
    assert set(calls) == set(want)
    for name, e in calls.items():
        body = e.params["jaxpr"]
        assert _count(body, "dot_general") == want[name], name
        assert _count(body, "while") + _count(body, "scan") == 1, name
        assert e.params["grid_mapping"].grid == (2, 1), name   # T 256: one tile of 256
    n = 4096 // 512
    assert mask_rules.tile_counts(mask_rules.CAUSAL, 4096, 512) == {
        "visited": n * (n - 1) // 2, "boundary": n,
        "skipped": n * (n - 1) // 2}


def test_the_layer_runs_the_flash_core_under_the_rule_and_counts_its_tiles(
        monkeypatch):
    from deeplearning4j_tpu import monitor
    layer = L.SelfAttentionLayer(
        n_out=64, n_heads=2, n_kv_heads=1, head_dim=32,
        causal=("block_diffusion", 128, 4), rotary_theta=1e4, qk_norm=True,
        bias=False, activation="identity", weight_init="normal")
    p, _, _ = layer.initialize(jax.random.PRNGKey(2),
                               InputType.recurrent(64, 256))
    assert p["Wq"].shape == (64, 64) and p["Wk"].shape == (64, 32) \
        and p["Wo"].shape == (64, 64)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 256, 64), jnp.float32)

    def run():
        helpers.reset_validation()
        pk._disabled.clear()
        return jax.value_and_grad(lambda p: jnp.sum(jnp.square(
            layer.forward(p, {}, x, train=True, rng=None)[0])))(p), \
            layer.core_tiles(256)
    monkeypatch.setenv("DL4J_PALLAS_FLASH", "0")
    (dense, dense_g), no_tiles = run()
    monkeypatch.setenv("DL4J_PALLAS_FLASH", "1")
    (flash, flash_g), tiles = run()
    helpers.reset_validation()
    assert float(flash) == pytest.approx(float(dense), rel=1e-4)
    for k in dense_g:
        _close(flash_g[k], dense_g[k], rtol=1e-3)
    # the layer counts its core's tiles from the plan the kernels run
    # (the fit loop publishes them as dl4j_attention_tiles); none where
    # the core is dense
    assert no_tiles is None
    assert tiles == {"visited": 0, "boundary": 3, "skipped": 1}


def test_rotary_positions_start_again_at_the_clean_copy():
    a = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 16, 8))
    rule = mask_rules.BlockDiffusion(8, 2)
    twice = L.SelfAttentionLayer._rotate(a, 1e4, rule.positions(16))
    once = L.SelfAttentionLayer._rotate(a[:, :, 8:], 1e4)
    _close(twice[:, :, 8:], once)
    _close(L.SelfAttentionLayer._rotate(a, 1e4),
           L.SelfAttentionLayer._rotate(a, 1e4, jnp.arange(16)))


@pytest.mark.parametrize("strategy", ["ring", "ulysses", "auto"])
def test_sequence_parallel_strategies_refuse_the_rule(strategy):
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:2]), ("seq",))
    q = jnp.zeros((1, 2, 64, 8))
    with seq_ops.sequence_mesh(mesh), \
            pytest.raises(NotImplementedError, match="know causal masks only"):
        seq_ops.attention(q, q, q, causal=("block_diffusion", 32, 4),
                          strategy=strategy)


# --- the expert layer: softmax top-k, eight shares ----------------------------------
def test_eight_shares_of_sixteen_add_up_to_the_uncut_layer():
    """Softmax top-8 of 128 at a small width: the eight holders' parts
    sum to the layer that holds every expert, and each routed token is
    counted once."""
    D, F, E, k = 32, 16, 128, 8

    def layer(held):
        return L.MixtureOfExpertsLayer(
            n_out=D, n_experts=E, hidden=F, top_k=k, scoring="softmax",
            norm_topk=True, gated=True, experts_held=held, residual=False,
            activation="identity", weight_init="normal")
    whole = layer(None)
    p, s, _ = whole.initialize(jax.random.PRNGKey(1),
                               InputType.recurrent(D, 24))
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, D), jnp.float32)
    want, ws, _ = whole.forward(p, s, x, train=True, rng=None)
    total, held_counts = 0.0, 0
    for share in range(8):
        held = tuple(range(16 * share, 16 * share + 16))
        part = layer(held)
        assert part.segment_shape(2 * 24 * k)[1] == 4    # 4 row segments of two shares
        pp = {"Wg": p["Wg"], **{n: p[n][16 * share:16 * share + 16]
                                for n in ("W1", "W3", "W2")}}
        y, st, _ = part.forward(pp, s, x, train=True, rng=None)
        total = total + y
        counts = np.asarray(st["moe_expert_counts"])
        held_counts += int(counts[list(held)].sum())
        np.testing.assert_array_equal(counts,
                                      np.asarray(ws["moe_expert_counts"]))
    _close(total, want, rtol=1e-5)
    assert held_counts == 2 * 24 * k
    # the weights over the selected eight sum to one, exactly renormalised
    scores = jax.nn.softmax(x.reshape(-1, D) @ p["Wg"], axis=-1)
    top, _ = jax.lax.top_k(scores, k)
    np.testing.assert_allclose(np.asarray((top / top.sum(-1, keepdims=True))
                                          .sum(-1)), 1.0, rtol=1e-6)


def test_softmax_routing_matches_the_references_experts(seeded):
    cfg = CFG
    p = seeded["weights"]["l1_moe"]
    u = jax.random.normal(jax.random.PRNGKey(4), (64, 64), jnp.float32)
    layer = L.MixtureOfExpertsLayer(
        n_out=64, n_experts=8, hidden=48, top_k=2, scoring="softmax",
        norm_topk=True, gated=True, experts_held=tuple(cfg["experts_held"]),
        residual=False, activation="identity")
    _, s, _ = layer.initialize(jax.random.PRNGKey(0),
                               InputType.recurrent(64, 64))
    got, _, _ = layer.forward(p, s, u[None], train=True, rng=None)
    _close(got[0], ref.blocks(cfg)["experts"](p, u))


# --- the noising pre-processor ----------------------------------------------------
def _clean(rng, rows=4, L_=4096):
    return rng.integers(0, 18991, (rows, L_), dtype=np.int32)


def test_the_noiser_follows_the_definition():
    rng = np.random.default_rng(1)
    x0 = _clean(rng)
    noiser = BlockDiffusionNoiser(4, 18991, seed=2_500_000_011)
    d = noiser.pre_process(DataSet(x0, None))
    L_ = x0.shape[1]
    assert d.features.shape == (4, 2 * L_) and d.features.dtype == np.int32
    assert d.labels.shape == (4, L_) and d.labels.dtype == np.int32
    assert d.labels_mask.shape == (4, L_) and d.labels_mask.dtype == np.float32
    assert d.features_mask is None
    x_t, clean = d.features[:, :L_], d.features[:, L_:]
    np.testing.assert_array_equal(clean, x0)
    np.testing.assert_array_equal(d.labels, x0)
    masked = d.labels_mask > 0
    np.testing.assert_array_equal(x_t[masked], 18991)
    np.testing.assert_array_equal(x_t[~masked], x0[~masked])
    # one t a block: the weights of a block's masked tokens are one 1 / t
    w = d.labels_mask.reshape(4, L_ // 4, 4)
    top = w.max(-1, keepdims=True)
    assert np.all((w == 0) | (w == top))
    assert w.max() <= 1000.0 * (1 + 1e-6) and w[w > 0].min() >= 1.0
    # masked share near E[t] = 0.5005; E[w] = 1
    assert abs(masked.mean() - 0.5005) < 0.02
    assert abs(d.labels_mask.mean() - 1.0) < 0.05


def test_the_noise_is_a_function_of_the_seed_and_the_batch_number():
    x0 = _clean(np.random.default_rng(3), rows=1, L_=256)
    a = BlockDiffusionNoiser(4, 18991, seed=7)
    b = BlockDiffusionNoiser(4, 18991, seed=7)
    c = BlockDiffusionNoiser(4, 18991, seed=8)
    a1, a2 = a.pre_process(DataSet(x0, None)), a.pre_process(DataSet(x0, None))
    b1 = b.pre_process(DataSet(x0, None))
    c1 = c.pre_process(DataSet(x0, None))
    np.testing.assert_array_equal(a1.features, b1.features)
    np.testing.assert_array_equal(a1.labels_mask, b1.labels_mask)
    assert (a1.features != a2.features).any()       # a batch seen again
    assert (a1.features != c1.features).any()
    feats, _, w, t = a.noise(x0, 0)
    np.testing.assert_array_equal(feats, a1.features)
    per_token = np.repeat(t, 4, axis=1)
    np.testing.assert_array_equal(w[w > 0],
                                  (1.0 / per_token).astype(np.float32)[w > 0])
    assert t.min() >= 1e-3 and t.max() <= 1.0


@pytest.mark.parametrize("bad,match", [
    ("mask_id", "hold the mask id"), ("length", "whole number of blocks")])
def test_the_noiser_refuses_what_it_cannot_noise(bad, match):
    x0 = np.zeros((1, 10 if bad == "length" else 8), np.int32)
    noiser = BlockDiffusionNoiser(4, 0 if bad == "mask_id" else 99, seed=1)
    with pytest.raises(ValueError, match=match):
        noiser.pre_process(DataSet(x0, None))


def test_the_iterator_hands_on_pre_processed_batches_in_order():
    rng = np.random.default_rng(5)
    pool = [DataSet(_clean(rng, 1, 32), None) for _ in range(3)]
    it = PreProcessingIterator(ListDataSetIterator(pool),
                               BlockDiffusionNoiser(4, 18991, seed=11))
    seen = list(it)
    assert len(seen) == 3 and it.batch_size() == 1
    for d, raw in zip(seen, pool):
        np.testing.assert_array_equal(d.labels, raw.features)
    again = list(it)                                # reset: an epoch more
    assert len(again) == 3
    assert any((a.features != b.features).any() for a, b in zip(seen, again))


# --- the weighted loss and the time cut --------------------------------------------
@pytest.mark.parametrize("labels", ["ids", "one-hot"])
def test_the_weighted_loss_is_the_formula(labels):
    layer = L.RnnOutputLayer(n_out=7, activation="softmax", loss="mcxent",
                             time_reduction="steps")
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.normal(size=(3, 10, 7)), jnp.float32)
    ids = rng.integers(0, 7, (3, 10))
    w = np.where(rng.random((3, 10)) < 0.4,
                 1.0 / rng.uniform(1e-3, 1, (3, 10)), 0.0).astype(np.float32)
    y = jnp.asarray(ids, jnp.int32) if labels == "ids" \
        else jax.nn.one_hot(ids, 7)
    got = layer.compute_score(y, z, jnp.asarray(w))
    logp = jax.nn.log_softmax(z, axis=-1)
    ce = -np.take_along_axis(np.asarray(logp), ids[..., None], -1)[..., 0]
    _close(got, (w * ce).sum(1) / 10)
    # "mean" divides by the mask's sum, "sum" by nothing: neither is it
    other = dataclasses.replace(layer, time_reduction="mean")
    assert not np.allclose(other.compute_score(y, z, jnp.asarray(w)), got)
    # no mask: every row weighs one
    _close(layer.compute_score(y, z), ce.sum(1) / 10)


def test_an_unknown_time_reduction_is_refused():
    layer = L.RnnOutputLayer(n_out=3, time_reduction="median")
    with pytest.raises(ValueError, match="sum | mean | steps"):
        layer.compute_score(jnp.zeros((1, 2), jnp.int32),
                            jnp.zeros((1, 2, 3)))


@pytest.mark.parametrize("lo,hi,want", [(0, 3, 3), (2, None, 4), (1, 5, 4)])
def test_the_time_range_vertex_cuts_steps_and_mask(lo, hi, want):
    v = G.TimeRangeVertex(from_step=lo, to_step=hi)
    x = jnp.arange(2 * 6 * 3.0).reshape(2, 6, 3)
    m = jnp.arange(12.0).reshape(2, 6)
    y, _, mask = v.forward({}, {}, [x], train=True, rng=None, masks=[m])
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x)[:, lo:hi])
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(m)[:, lo:hi])
    out = v.output_type([InputType.recurrent(3, 6)])
    assert (out.size, out.timesteps) == (3, want)
    assert v.forward({}, {}, [x], train=True, rng=None)[2] is None
    again = G.GraphVertexConf.from_dict(v.to_dict())
    assert again == v


def test_no_logits_exist_for_the_clean_copy(seeded):
    net, d = seeded["net"], seeded["batches"][0]
    text = str(jax.make_jaxpr(net._build_grad_raw())(
        net.net_params, net.net_state, (jnp.asarray(d.features),),
        (jnp.asarray(d.labels),), None, (jnp.asarray(d.labels_mask),),
        jax.random.PRNGKey(0)))
    V, T = CFG["vocab_size"], 2 * CFG["seq_len"]
    assert f"f32[2,{T // 2},{V}]" in text
    assert f"f32[2,{T},{V}]" not in text


# --- gradients, serialisation, checkpoints, counters -------------------------------
def test_numeric_gradients_in_float64():
    from deeplearning4j_tpu.nn.gradientcheck import (
        check_computation_graph_gradients)
    net = sdar_moe(vocab_size=12, hidden_size=8, num_attention_heads=2,
                   num_key_value_heads=1, head_dim=4, moe_intermediate_size=6,
                   num_experts=4, num_experts_per_tok=2, layers=[0, 1],
                   experts_held=[0, 2, 3], seq_len=6, block_length=2, seed=5)
    net.init()
    rng = np.random.default_rng(5)
    d = BlockDiffusionNoiser(2, 11, seed=5).pre_process(
        DataSet(rng.integers(0, 11, (2, 6)).astype(np.int32), None))
    assert check_computation_graph_gradients(
        net, [d.features], [d.labels], lmasks=[d.labels_mask], subset=32,
        print_results=False)


def test_the_configuration_round_trips_through_json(seeded):
    conf = seeded["net"].conf
    again = type(conf).from_json(conf.to_json())
    assert again.to_json() == conf.to_json()
    attn = again.vertices["l1_attn"].layer_conf()
    assert mask_rules.resolve(attn.causal) == mask_rules.BlockDiffusion(
        CFG["seq_len"], CFG["block_length"])
    assert attn.head_dim == 16 and attn.qk_norm_eps == 1e-6
    cut = again.vertices["noisy_rows"]
    assert isinstance(cut, G.TimeRangeVertex) and cut.to_step == CFG["seq_len"]
    assert again.vertices["head"].layer_conf().time_reduction == "steps"
    net = ComputationGraph(again)
    net.init(params=_copies(seeded["weights"], net))
    d = seeded["batches"][0]
    net.fit(d)
    assert np.isfinite(float(net.score()))


@pytest.mark.parametrize("how", ["zip", "checkpoint-directory"])
def test_save_and_restore_continue_the_loss(seeded, trained, tmp_path, how):
    from deeplearning4j_tpu.nn import checkpoint, serialization
    net = _net()
    net.init(params=_copies(seeded["weights"], net))
    two = seeded["batches"][:2]
    if how == "zip":
        net.fit(ListDataSetIterator(two))
        serialization.write_model(net, tmp_path / "sdar.zip")
        back = serialization.restore_computation_graph(tmp_path / "sdar.zip")
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
    back.fit(seeded["batches"][2])
    assert float(back.score()) == pytest.approx(trained[1][2], rel=RTOL)


def test_num_params_at_the_cells_sizes():
    D, H, Hkv, Dh, F, G_, E = 2048, 32, 4, 128, 768, 16, 128
    layer = (D * H * Dh + 2 * D * Hkv * Dh + H * Dh * D + 2 * Dh   # attention
             + 2 * D + D * E + 3 * G_ * D * F)
    assert layer == 94_638_336              # the issue's 94.64M a layer
    assert 5 * layer + 2 * 18992 * D + D == 550_984_960
    net = seeded_small = _net()
    seeded_small.init()
    d, f, g, e = 64, 48, 4, 8
    small = (d * 4 * 16 + 2 * d * 2 * 16 + 4 * 16 * d + 2 * 16
             + 2 * d + d * e + 3 * g * d * f)
    assert net.num_params() == 3 * small + 2 * 256 * d + d


def test_fit_publishes_the_noise_and_the_expert_load(trained):
    from deeplearning4j_tpu import monitor
    snap = monitor.get_registry().snapshot()
    tokens = {s["labels"]["kind"]: s["value"] for s in
              snap["dl4j_diffusion_tokens_total"]["samples"]}
    # 2 sequences of 32 tokens a step, every step a whole number of them
    assert (tokens["masked"] + tokens["clean"]) % 64 == 0
    assert tokens["masked"] > 0 < tokens["clean"]
    assert snap["dl4j_diffusion_loss_weight_sum"]["samples"][0]["value"] > 0
    held = [s["value"] for s in snap["dl4j_moe_assignments_total"]["samples"]
            if s["labels"]["vertex"] == "l1_moe"]
    # 2 sequences x 64 rows x top-2 a step
    assert sum(held) % (2 * 64 * 2) == 0 and sum(held) > 0


def test_a_net_without_the_rule_publishes_no_noise():
    from deeplearning4j_tpu.nn import multilayer
    from deeplearning4j_tpu.models import lenet
    net = lenet()
    assert multilayer._diffusion_trained(net) is False
    multilayer.publish_diffusion(net, (None, None, None, None))
    assert net._diffusion is False


@pytest.mark.parametrize("flash, want", [
    ("1", {"visited": 0, "boundary": 3, "skipped": 1}), ("0", {})])
def test_the_fit_loop_publishes_the_tiles_the_layers_count(
        monkeypatch, flash, want):
    """``dl4j_attention_tiles`` under the layer's own name, from
    ``SelfAttentionLayer.core_tiles`` at the batch's time length; nothing
    where the core is dense."""
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.nn import multilayer
    monkeypatch.setenv("DL4J_PALLAS_FLASH", flash)
    pk._disabled.clear()
    # 256 rows: 2 x 2 tiles of 128; a head of 32, the narrowest the core takes
    net = _net({**CFG, "seq_len": 128, "head_dim": 32})
    ids = np.zeros((1, 256), np.int32)
    monitor.get_registry().gauge(
        "dl4j_attention_tiles", "", labels=("vertex", "outcome"))
    before = len(monitor.get_registry().snapshot()
                 ["dl4j_attention_tiles"]["samples"])
    multilayer.publish_attention_tiles(net, ([ids], None, None, None))
    samples = monitor.get_registry().snapshot()[
        "dl4j_attention_tiles"]["samples"]
    vertex = f"l{CFG['layers_run'][0]}_attn" + ("" if flash == "1" else "_x")
    got = {s["labels"]["outcome"]: s["value"] for s in samples
           if s["labels"]["vertex"] == vertex}
    assert got == want
    if flash == "0":
        assert len(samples) == before
    assert net._attention_tiles_T == 256


def test_the_step_names_the_core_the_cut_and_the_weighted_loss(seeded):
    import re
    from deeplearning4j_tpu.monitor import profile
    net, d = seeded["net"], seeded["batches"][0]
    hlo = jax.jit(net._build_step_raw()).lower(
        net.net_params, net.net_state, net.opt_states,
        (jnp.asarray(d.features),), (jnp.asarray(d.labels),), None,
        (jnp.asarray(d.labels_mask),), jnp.asarray(0, jnp.int32),
        jax.random.PRNGKey(0)).as_text(debug_info=True)
    names = re.findall(r'"(jit\([^"]+)"', hlo)
    seen = {profile.sub_scope(n) for n in names}
    assert {"fwd/SelfAttentionLayer/attn_core",
            "bwd/SelfAttentionLayer/attn_core"} <= seen
    assert any("fwd/TimeRangeVertex/noisy_rows" in n for n in names)
    assert any("loss" in n and "weighted_rows" in n for n in names)
    assert profile.sub_scope(
        "jit(s)/jvp(fwd/SelfAttentionLayer/l0_attn)/attn_core/dot_general") \
        == "fwd/SelfAttentionLayer/attn_core"
    assert profile.sub_scope(
        "jit(s)/transpose(jvp(fwd/SelfAttentionLayer/l0_attn))/attn_core/"
        "dl4j_flash_bwd") == "bwd/SelfAttentionLayer/attn_core"
