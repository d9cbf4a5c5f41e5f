"""Numeric-vs-analytic gradient checking — the correctness backbone.

(ref: gradientcheck/GradientCheckUtil.java:77 — perturbs each param ±ε in
double precision and compares relative error; the reference's test suites
in deeplearning4j-core/src/test/java/org/deeplearning4j/gradientcheck/
are the model for tests/test_gradientcheck.py.)

TPU f64 is emulated/slow, so checks run under the CPU backend with x64
enabled (the cuDNN-vs-builtin cross-validation pattern of
CuDNNGradientChecks.java becomes TPU-vs-CPU here: same code, two
backends).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn import params as param_util


def check_gradients(net, x, y, *, epsilon: float = 1e-6,
                    max_rel_error: float = 1e-3, min_abs_error: float = 1e-8,
                    fmask=None, lmask=None, subset: Optional[int] = 128,
                    seed: int = 0, print_results: bool = False) -> bool:
    """Compare jax.grad of the training loss against central finite
    differences, param by param (ref: GradientCheckUtil.checkGradients).

    subset: max number of randomly-chosen scalar params to check per layer
    (None = exhaustive, as the reference does).
    Returns True if every checked param's relative error is within bounds.

    float64 is enabled locally via the jax.enable_x64 context
    (the reference forces double precision the same way,
    GradientCheckUtil.java:87-92) so callers/tests don't leak x64 into the
    rest of the process.
    """
    with jax.enable_x64(True):
        return _check_gradients_x64(
            net, x, y, epsilon=epsilon, max_rel_error=max_rel_error,
            min_abs_error=min_abs_error, fmask=fmask, lmask=lmask,
            subset=subset, seed=seed, print_results=print_results)


def _check_gradients_x64(net, x, y, *, epsilon, max_rel_error, min_abs_error,
                         fmask, lmask, subset, seed, print_results) -> bool:
    if net.net_params is None:
        net.init()
    out_layer = net.layers[-1]
    g = net.conf.global_conf
    rng = jax.random.PRNGKey(seed)

    params64 = jax.tree_util.tree_map(
        lambda a: jnp.asarray(np.asarray(a), jnp.float64), net.net_params)
    state64 = jax.tree_util.tree_map(
        lambda a: jnp.asarray(np.asarray(a), jnp.float64), net.net_state)
    x64 = jnp.asarray(np.asarray(x), jnp.float64)
    y64 = jnp.asarray(np.asarray(y), jnp.float64)

    def score(p):
        preout, _, m, feats = net._forward_to_preout(p, state64, x64, fmask,
                                                     True, rng)
        lm = lmask if lmask is not None else (
            m if (m is not None and m.ndim == preout.ndim - 1) else None)
        if getattr(out_layer, "requires_features_for_score", False):
            per_ex = out_layer.compute_score_with_features(
                y64, preout, feats, p[-1], lm)
        else:
            per_ex = out_layer.compute_score(y64, preout, lm)
        s = jnp.mean(per_ex) if g.mini_batch else jnp.sum(per_ex)
        return s + net._reg_penalty(p)

    score_jit = jax.jit(score)
    analytic = jax.grad(score)(params64)

    nprng = np.random.default_rng(seed)
    total_checked = 0
    failures = []
    for li, lp in enumerate(params64):
        for k in param_util.ordered_keys(lp):
            fails, checked = _fd_check_one(
                lp[k], np.asarray(analytic[li][k]),
                lambda arr, li=li, k=k: float(
                    score_jit(_with(params64, li, k, arr))),
                epsilon, max_rel_error, min_abs_error, subset, nprng)
            total_checked += checked
            failures.extend((f"layer {li} {k}", i, a, num, rel)
                            for i, a, num, rel in fails)

    if print_results or failures:
        print(f"Gradient check: {total_checked} params checked, "
              f"{len(failures)} failures")
        for label, i, a, num, rel in failures[:20]:
            print(f"  {label}[{i}]: analytic={a:.3e} numeric={num:.3e} "
                  f"rel={rel:.3e}")
    return not failures


def _fd_check_one(arr, analytic, eval_with, epsilon, max_rel_error,
                  min_abs_error, subset, nprng):
    """Central-difference check of one param tensor.  ``eval_with(new_arr)``
    evaluates the scalar loss with the tensor replaced.  Returns
    ([(flat_idx, analytic, numeric, rel_err)...] failures, n_checked)."""
    shape = arr.shape
    # NB: reshape on an np.array-of-jax-array can silently COPY, so
    # the flat buffer is the single mutable source of truth here.
    flat = np.array(arr, dtype=np.float64).reshape(-1).copy()
    an = analytic.reshape(-1)
    n = flat.size
    idxs = (np.arange(n) if subset is None or n <= subset
            else nprng.choice(n, subset, replace=False))
    failures = []
    for i in idxs:
        orig = flat[i]
        flat[i] = orig + epsilon
        plus = eval_with(flat.reshape(shape))
        flat[i] = orig - epsilon
        minus = eval_with(flat.reshape(shape))
        flat[i] = orig
        numeric = (plus - minus) / (2 * epsilon)
        a = an[i]
        denom = max(abs(a), abs(numeric))
        rel = abs(a - numeric) / denom if denom > 0 else 0.0
        if rel > max_rel_error and abs(a - numeric) > min_abs_error:
            failures.append((int(i), float(a), numeric, rel))
    return failures, len(idxs)


def check_computation_graph_gradients(
        graph, inputs, labels, *, epsilon: float = 1e-6,
        max_rel_error: float = 1e-3, min_abs_error: float = 1e-8,
        fmasks=None, lmasks=None, subset: Optional[int] = 64,
        seed: int = 0, print_results: bool = False) -> bool:
    """ComputationGraph analog of :func:`check_gradients` — rebuilds the
    training score exactly as ComputationGraph._build_step_raw's loss
    closure does (multi-output sum, masks, regularization, MoE aux loss)
    and central-differences every vertex's params in f64 on CPU
    (ref: GradientCheckUtil.checkGradients(ComputationGraph...):238,
    GradientCheckTestsComputationGraph.java).

    inputs/labels: list-like ordered by network_inputs/network_outputs.
    """
    with jax.enable_x64(True):
        return _check_cg_x64(graph, inputs, labels, epsilon=epsilon,
                             max_rel_error=max_rel_error,
                             min_abs_error=min_abs_error, fmasks=fmasks,
                             lmasks=lmasks, subset=subset, seed=seed,
                             print_results=print_results)


def _check_cg_x64(graph, inputs, labels, *, epsilon, max_rel_error,
                  min_abs_error, fmasks, lmasks, subset, seed,
                  print_results) -> bool:
    if graph.net_params is None:
        graph.init()
    g = graph.conf.global_conf
    rng = jax.random.PRNGKey(seed)
    out_confs = graph._output_layer_confs()
    out_names = list(out_confs)
    out_pos = {n: graph.conf.network_outputs.index(n) for n in out_names}

    to64 = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jnp.asarray(np.asarray(a), jnp.float64)
        if np.asarray(a).dtype.kind == "f" else jnp.asarray(a), t)
    params64 = to64(graph.net_params)
    state64 = to64(graph.net_state)
    # integer inputs (token ids) and labels (class ids) stay integers
    xs64 = to64(list(inputs))
    ys64 = to64(list(labels))

    def score(p):
        ins = dict(zip(graph.conf.network_inputs, xs64))
        masks = (dict(zip(graph.conf.network_inputs, fmasks))
                 if fmasks is not None else {})
        acts, preouts, new_states, out_masks = graph._forward_all(
            p, state64, ins, masks, True, rng, preout_for=out_names)
        # the SAME loss assembly the training step compiles
        # (ComputationGraph._assemble_training_score) — no drift between
        # checked and trained functions
        return graph._assemble_training_score(
            p, preouts, new_states, out_masks, ys64, lmasks,
            out_confs, out_pos)

    score_jit = jax.jit(score)
    analytic = jax.grad(score)(params64)

    nprng = np.random.default_rng(seed)
    total_checked = 0
    failures = []
    for name in graph.order:
        lp = params64[name]
        if not lp:
            continue
        for k in param_util.ordered_keys(lp):
            if np.asarray(lp[k]).dtype.kind != "f":
                continue

            def eval_with(arr, name=name, k=k):
                pp = dict(params64)
                pp[name] = {**pp[name], k: jnp.asarray(arr)}
                return float(score_jit(pp))

            fails, checked = _fd_check_one(
                lp[k], np.asarray(analytic[name][k]), eval_with,
                epsilon, max_rel_error, min_abs_error, subset, nprng)
            total_checked += checked
            failures.extend((f"vertex {name} {k}", i, a, num, rel)
                            for i, a, num, rel in fails)

    if print_results or failures:
        print(f"CG gradient check: {total_checked} params checked, "
              f"{len(failures)} failures")
        for label, i, a, num, rel in failures[:20]:
            print(f"  {label}[{i}]: analytic={a:.3e} numeric={num:.3e} "
                  f"rel={rel:.3e}")
    return not failures


def check_pretrain_gradients(layer, params, x, *, epsilon: float = 1e-6,
                             max_rel_error: float = 1e-3,
                             min_abs_error: float = 1e-8,
                             subset: Optional[int] = 64, seed: int = 0) -> bool:
    """Gradient-check a pretrain layer's unsupervised loss
    (ref: VaeGradientCheckTests.java — checks the pretrain path).

    Stochastic pieces (corruption masks, MC samples, Gibbs chains) are made
    deterministic by fixing the rng across both analytic and numeric
    evaluation, so the finite difference probes the same realized loss.
    """
    with jax.enable_x64(True):
        rng = jax.random.PRNGKey(seed)
        p64 = jax.tree_util.tree_map(
            lambda a: jnp.asarray(np.asarray(a), jnp.float64), params)
        x64 = jnp.asarray(np.asarray(x), jnp.float64)

        def loss(p):
            return layer.pretrain_loss(p, x64, rng)

        loss_jit = jax.jit(loss)
        analytic = jax.grad(loss)(p64)
        nprng = np.random.default_rng(seed)
        failures = []
        for k in param_util.ordered_keys(p64):
            def eval_with(arr, k=k):
                pp = dict(p64)
                pp[k] = jnp.asarray(arr)
                return float(loss_jit(pp))

            fails, _ = _fd_check_one(
                p64[k], np.asarray(analytic[k]), eval_with, epsilon,
                max_rel_error, min_abs_error, subset, nprng)
            failures.extend((k, i, a, num, rel) for i, a, num, rel in fails)
        if failures:
            print(f"Pretrain gradient check: {len(failures)} failures")
            for k, i, a, num, rel in failures[:20]:
                print(f"  {k}[{i}]: analytic={a:.3e} numeric={num:.3e} "
                      f"rel={rel:.3e}")
        return not failures


def _with(params, li, k, arr):
    """Rebuild the param pytree with layer li's key k replaced by arr
    (arr is the mutated numpy buffer; re-wrap to jnp)."""
    out = []
    for i, lp in enumerate(params):
        if i == li:
            lp = dict(lp)
            lp[k] = jnp.asarray(arr)
        out.append(lp)
    return out
