"""The expert layer's grouped products through the gmm tier's kernels
(``ops/row_segments.routed_sum``), on the CPU: the gradients with the
tier forced on and off agree on the expert layers of the tiny LFM2, SDAR
and Trinity models the suite builds (kernels in Pallas interpret mode),
and a training step lowered for the TPU holds each kernel signature
once, however many expert layers call it and whether they run again in
the backward pass (lowering only: no TPU library is loaded)."""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

F32 = jnp.float32


def _close(got, want, rtol=1e-5):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    assert got.shape == want.shape
    scale = max(float(np.abs(want).max()), 1e-30)
    assert float(np.abs(got - want).max()) <= rtol * scale, \
        (float(np.abs(got - want).max()), scale)


MODELS = {"test_lfm2": "l2_moe", "test_sdar": "l1_moe", "test_trinity": "l2_moe"}


@pytest.fixture(params=sorted(MODELS))
def expert_layer(request):
    mod = importlib.import_module(request.param)
    net = mod._net()
    net.init()
    name = MODELS[request.param]
    layer = net.conf.vertices[name].layer_conf()
    assert layer.top_k is not None
    T, D = mod.CFG["seq_len"], mod.CFG["hidden_size"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, T, D), F32)
    return layer, net.net_params[name], net.net_state[name], x


def _value_and_grads(layer, params, state, x):
    r = jax.random.normal(jax.random.PRNGKey(4), x.shape[:-1] + (layer.n_out,))

    def loss(p, x):
        y, _, _ = layer.forward(p, state, x, train=True, rng=None)
        return jnp.sum(y * r)
    return jax.value_and_grad(loss, argnums=(0, 1))(params, x)


def test_routed_sum_gradients_with_the_tier_on_and_off(expert_layer,
                                                        monkeypatch):
    layer, params, state, x = expert_layer

    def selected():
        from deeplearning4j_tpu import monitor
        fam = monitor.get_registry().get("dl4j_pallas_selected_total")
        return sum(s["value"] for s in (fam.samples() if fam else [])
                   if s["labels"].get("op") == "gmm")

    monkeypatch.setenv("DL4J_PALLAS_GMM", "0")
    before = selected()
    want = _value_and_grads(layer, params, state, x)
    assert selected() == before
    monkeypatch.setenv("DL4J_PALLAS_GMM", "1")
    got = _value_and_grads(layer, params, state, x)
    assert selected() > before
    _close(got[0], want[0])
    for g, w in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(want[1])):
        _close(g, w)


# --- one lowering a kernel signature ------------------------------------------
KERNELS = ("grouped_matmul", "grouped_matmul_t")


def _signatures(jaxpr, found):
    """The distinct (primitive, operand shapes and dtypes, params) of the
    gmm tier's primitives anywhere in ``jaxpr`` and its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in KERNELS:
            found.add((eqn.primitive.name,
                       tuple((v.aval.shape, str(v.aval.dtype))
                             for v in eqn.invars),
                       tuple(sorted((k, str(v))
                                    for k, v in eqn.params.items()))))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _signatures(inner, found)
    return found


def _lowered_step(layers, recompute):
    """(kernels in the text of the tiny SDAR model's training step lowered
    for the TPU, distinct kernel signatures in its jaxpr)."""
    mod = importlib.import_module("test_sdar")
    cfg = dict(mod.CFG, layers_run=list(range(layers)))
    net = mod._net(cfg, recompute_experts=recompute)
    net.init()
    step = net._build_step_raw()
    ids = jnp.zeros((2, 2 * cfg["seq_len"]), jnp.int32)
    args = (net.net_params, net.net_state, net.opt_states, (ids,),
            (jnp.zeros((2, cfg["seq_len"]), jnp.int32),), None,
            (jnp.ones((2, cfg["seq_len"]), F32),), jnp.int32(0),
            jax.random.PRNGKey(0))
    text = jax.jit(step).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    kernels = len(re.findall(r'kernel_name = "dl4j_t?gmm"', text))
    signatures = _signatures(jax.make_jaxpr(step)(*args).jaxpr, set())
    return kernels, len(signatures)


def test_a_step_lowers_each_kernel_signature_once(monkeypatch):
    """Each expert layer calls the kernels from about twenty sites (the
    first segment and the later segments' loop, forward and backward,
    and the recomputed forward); the program holds one kernel for each
    distinct signature, the six of a gated layer, whatever the count of
    layers or sites."""
    from deeplearning4j_tpu.ops import helpers
    monkeypatch.setenv("DL4J_TPU", "1")
    # the tier's self-test would run the kernels, which only a chip can:
    # lowering is all this needs
    monkeypatch.setitem(helpers._FIRST_USE, "gmm", True)
    seen = {(layers, recompute): _lowered_step(layers, recompute)
            for layers in (2, 4) for recompute in (False, True)}
    for kernels, signatures in seen.values():
        assert kernels == signatures == 6, seen
