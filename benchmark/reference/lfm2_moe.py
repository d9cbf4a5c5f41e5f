"""LFM2-MoE (LiquidAI, ``model_type`` ``lfm2_moe``; the equations are those
of the family's public modelling code) as a plain reference: one chip's
share of an expert-parallel job.  ``jax.numpy``, float32, ``highest``;
nothing here imports the program.

With u a block's normed input and RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w:

  layer   h = x + Op(RMSNorm(x));  y = h + FF(RMSNorm(h));  after the last
          layer one more RMSNorm, then the head; no bias anywhere
  conv    [b, c, z] = split3(u W_in);  s = b * z;
          g_t = K_0 s_{t-2} + K_1 s_{t-1} + K_2 s_t per channel (zeros
          before the sequence);  Op = (c * g) W_out
  attn    q = u W_q (H heads), k = u W_k, v = u W_v (H_kv heads); RMSNorm
          with a learned weight over each head of q and of k; rotary over
          the whole head, halves paired (i with i + Dh/2); causal
          softmax(q k^T / sqrt(Dh)) v, a key/value head serving H / H_kv
          consecutive query heads;  Op = (.) W_o
  FF      leading layers: (silu(u W_1) * (u W_3)) W_2
          expert layers: s = sigmoid(u W_g); the k experts with the
          largest s + bias; weights s_e (without the bias) over their sum
          + 1e-6, times routed_scaling_factor; the sum over the selected
          experts THAT ARE HELD HERE of w_e (silu(u W_1e) * (u W_3e)) W_2e.
          Selection and renormalisation are over all the experts and all
          k; what the absent experts would add is left out, here as in
          the program.  The bias is a constant, not a parameter.
  loss    mean over tokens of the cross-entropy over the vocabulary rows
          held here

A sequence is taken at a time (no layer mixes sequences), each layer
under ``jax.checkpoint`` and attention a key/value head at a time, so
that float32 at 4,096 tokens fits the chip beside Adam's state.  The
held experts are computed densely, every token through every held
expert, and masked by the routing: the plain form of a grouped product.

``numerics`` rounds the operands of every matrix product but the
router's, which the configuration states in float32 (``precision``).

``layers(cfg)`` is the FLOP walk.  ``harness/flops.py`` counts per row of
a batch and knows ``conv`` and ``dense``: here a row is one SEQUENCE, and
every matrix product of a step is a ``dense`` entry whose ``nin x nout``
is its multiply-adds for one sequence: the projections at T tokens,
attention's two products at T^2/2 (causal), the held experts at the
expected share k x held/experts of the tokens.  The first entry is an
empty product, because the walk gives its first entry no input gradient
and the first real product (layer 0's W_in) needs one for the embedding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.harness import flops
from benchmark.reference import common as C

ROUTED_EPS = 1e-6


def _plan(cfg):
    """[(published layer index, operator kind, is a leading dense layer)]"""
    return [(i, cfg["layer_types"][i], i < cfg["num_dense_layers"])
            for i in cfg["layers_run"]]


def _head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layers(cfg):
    T, D = cfg["seq_len"], cfg["hidden_size"]
    H, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  _head_dim(cfg))
    held_share = (cfg["num_experts_per_tok"] * len(cfg["experts_held"])
                  / cfg["num_experts_published"])
    out = [flops.dense(0, 0)]
    for _, kind, leading in _plan(cfg):
        if kind == "conv":
            out += [flops.dense(T * D, 3 * D), flops.dense(T * D, D)]
        else:
            out += [flops.dense(T * D, H * Dh), flops.dense(T * D, Hkv * Dh),
                    flops.dense(T * D, Hkv * Dh),
                    flops.dense(T * T // 2, H * Dh),      # q k^T, causal
                    flops.dense(T * T // 2, H * Dh),      # p v
                    flops.dense(T * H * Dh, D)]
        if leading:
            out += [flops.dense(T * D, cfg["intermediate_size"])] * 3
        else:
            out.append(flops.dense(T * D, cfg["num_experts_published"]))
            out += [flops.dense(int(T * held_share) * D,
                                cfg["moe_intermediate_size"])] * 3
    out.append(flops.dense(T * D, cfg["vocab_size"]))
    return out


# --------------------------------------------------------------------------
def _matrix(key, n_in, n_out, lead=()):
    return jax.random.normal(key, lead + (n_in, n_out), jnp.float32) \
        / jnp.sqrt(float(n_in))


def init_params(cfg, key):
    """Seeded weights, a dict by the vertex names of the program's graph:
    matrices N(0, 1/fan_in), embedding rows N(0, 1), norm weights
    N(1, 0.1), the convolution's taps N(0, 1/3) (``assumed``)."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    Hkv, Dh = cfg["num_key_value_heads"], _head_dim(cfg)
    G, E = len(cfg["experts_held"]), cfg["num_experts_published"]
    params = {}

    def norm(k, n):
        return {"gamma": C.small_normal(k, (n,), 0.1, mean=1.0)}

    key, k = jax.random.split(key)
    params["embed"] = {"W": jax.random.normal(k, (V, D), jnp.float32)}
    for i, kind, leading in _plan(cfg):
        key, kn1, kn2, *ks = jax.random.split(key, 13)
        params[f"l{i}_op_norm"] = norm(kn1, D)
        params[f"l{i}_ff_norm"] = norm(kn2, D)
        if kind == "conv":
            params[f"l{i}_conv"] = {
                "W_in": _matrix(ks[0], D, 3 * D),
                "K": C.small_normal(ks[1], (cfg["conv_L_cache"], D),
                                    cfg["conv_L_cache"] ** -0.5),
                "W_out": _matrix(ks[2], D, D)}
        else:
            params[f"l{i}_attn"] = {
                "Wq": _matrix(ks[0], D, D), "Wk": _matrix(ks[1], D, Hkv * Dh),
                "Wv": _matrix(ks[2], D, Hkv * Dh), "Wo": _matrix(ks[3], D, D),
                "q_norm": C.small_normal(ks[4], (Dh,), 0.1, mean=1.0),
                "k_norm": C.small_normal(ks[5], (Dh,), 0.1, mean=1.0)}
        if leading:
            F = cfg["intermediate_size"]
            params[f"l{i}_mlp"] = {"W1": _matrix(ks[6], D, F),
                                   "W3": _matrix(ks[7], D, F),
                                   "W2": _matrix(ks[8], F, D)}
        else:
            F = cfg["moe_intermediate_size"]
            params[f"l{i}_moe"] = {"Wg": _matrix(ks[9], D, E),
                                   "W1": _matrix(ks[6], D, F, (G,)),
                                   "W3": _matrix(ks[7], D, F, (G,)),
                                   "W2": _matrix(ks[8], F, D, (G,))}
    key, kn, kh = jax.random.split(key, 3)
    params["final_norm"] = norm(kn, D)
    params["head"] = {"W": _matrix(kh, D, V)}
    return params


def init_expert_bias(cfg, key):
    """{expert vertex: [experts] selection bias}, N(0, 0.01): a constant
    of the run (the family moves it between steps by the experts' load;
    here it stays as seeded, ``assumed``)."""
    key = jax.random.fold_in(key, 0xB1A5)
    return {f"l{i}_moe": C.small_normal(jax.random.fold_in(key, i),
                                        (cfg["num_experts_published"],), 0.01)
            for i, _, leading in _plan(cfg) if not leading}


# --------------------------------------------------------------------------
def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * w


def rotary(a, theta):
    """a [T, heads, Dh], positions 0..T-1, halves paired."""
    T, _, Dh = a.shape
    inv = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a1, a2 = a[..., :Dh // 2], a[..., Dh // 2:]
    return jnp.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin], -1)


def blocks(cfg, numerics="float32"):
    """The four sub-blocks as functions of one sequence's normed input
    u [T, hidden]: {"conv": f(p, u), "attention": f(p, u), "mlp": f(p, u),
    "experts": f(p, bias, u)}, p the block's own leaves."""
    rnd, ct = C.rounder(numerics)
    eps = cfg["norm_eps"]
    H, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  _head_dim(cfg))
    held, topk = list(cfg["experts_held"]), cfg["num_experts_per_tok"]

    def mm(x, w):
        return ct(jnp.dot(rnd(x), rnd(w), precision=C.HIGHEST))

    def conv(p, u):
        b, c, z = jnp.split(mm(u, p["W_in"]), 3, axis=-1)
        s = b * z
        L = p["K"].shape[0]
        sp = jnp.pad(s, ((L - 1, 0), (0, 0)))
        g = sum(p["K"][j] * sp[j:j + s.shape[0]] for j in range(L))
        return mm(c * g, p["W_out"])

    def attention(p, u):
        T = u.shape[0]
        q = mm(u, p["Wq"]).reshape(T, H, Dh)
        k = mm(u, p["Wk"]).reshape(T, Hkv, Dh)
        v = mm(u, p["Wv"]).reshape(T, Hkv, Dh)
        q = rotary(rms_norm(q, p["q_norm"], eps), cfg["rope_theta"])
        k = rotary(rms_norm(k, p["k_norm"], eps), cfg["rope_theta"])
        causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

        @jax.checkpoint
        def group(qkv):     # one key/value head and the query heads it serves
            qg, kg, vg = qkv                     # [T, H/Hkv, Dh], [T, Dh] x 2
            s = ct(jnp.einsum("thd,sd->hts", rnd(qg), rnd(kg),
                              precision=C.HIGHEST)) / jnp.sqrt(float(Dh))
            pr = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
            return ct(jnp.einsum("hts,sd->thd", rnd(pr), rnd(vg),
                                 precision=C.HIGHEST))

        qg = q.reshape(T, Hkv, H // Hkv, Dh).transpose(1, 0, 2, 3)
        out = lax.map(group, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
        return mm(out.transpose(1, 0, 2, 3).reshape(T, H * Dh), p["Wo"])

    def mlp(p, u):
        return mm(jax.nn.silu(mm(u, p["W1"])) * mm(u, p["W3"]), p["W2"])

    def experts(p, bias, u):
        # the router in float32, whatever the numerics
        s = jax.nn.sigmoid(jnp.dot(u, p["Wg"], precision=C.HIGHEST))
        _, sel = lax.top_k(s + bias, topk)                       # [T, k]
        w = jnp.take_along_axis(s, sel, axis=1)
        if cfg["norm_topk_prob"]:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTED_EPS)
        w = w * cfg["routed_scaling_factor"]
        out = jnp.zeros_like(u)
        for g, e in enumerate(held):
            w_e = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)  # 0: not sent
            out = out + w_e[:, None] * mlp(
                {k: p[k][g] for k in ("W1", "W3", "W2")}, u)
        return out

    return {"conv": conv, "attention": attention, "mlp": mlp,
            "experts": experts, "mm": mm}


def logits_fn(cfg, numerics="float32", expert_bias=None):
    """logits(params, ids [T]) -> [T, vocabulary rows held] of one
    sequence; ``expert_bias`` as :func:`init_expert_bias` gives it."""
    b = blocks(cfg, numerics)
    eps = cfg["norm_eps"]

    def logits(params, ids):
        x = params["embed"]["W"][ids]
        for i, kind, leading in _plan(cfg):
            def layer(p, x, i=i, kind=kind, leading=leading):
                u = rms_norm(x, p[f"l{i}_op_norm"]["gamma"], eps)
                h = x + (b["conv"](p[f"l{i}_conv"], u) if kind == "conv"
                         else b["attention"](p[f"l{i}_attn"], u))
                u = rms_norm(h, p[f"l{i}_ff_norm"]["gamma"], eps)
                if leading:
                    return h + b["mlp"](p[f"l{i}_mlp"], u)
                return h + b["experts"](p[f"l{i}_moe"],
                                        expert_bias[f"l{i}_moe"], u)
            sub = {k: v for k, v in params.items() if k.startswith(f"l{i}_")}
            x = jax.checkpoint(layer)(sub, x)
        x = rms_norm(x, params["final_norm"]["gamma"], eps)
        return b["mm"](x, params["head"]["W"])

    return logits


def loss_fn(cfg, numerics="float32", expert_bias=None):
    """loss(params, ids [S, T], labels [S, T]) -> mean cross-entropy over
    the tokens."""
    logits = logits_fn(cfg, numerics, expert_bias)

    def sequence(params, ids, labels):
        z = logits(params, ids)
        logp = z - jax.scipy.special.logsumexp(z, axis=-1, keepdims=True)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))

    def loss(params, ids, labels):
        return jnp.mean(jnp.stack([sequence(params, i, l)
                                   for i, l in zip(ids, labels)]))

    return loss


# --------------------------------------------------------------------------
def follow(loss_fn_, params, batches, lr, beta1, beta2, eps, rows=None):
    """Train ``len(batches)`` steps from ``params`` under Adam and return
    what the comparison reads (as ``common.follow`` does under Nesterov):
    each step's loss, the first gradient with its per-leaf norms, and the
    per-leaf norm of the parameters' change over all the steps.

    Adam as Kingma & Ba 2015 write it at the end of their section 2, the
    form that folds both bias corrections into the step size:

        m' = b1 m + (1 - b1) g;   v' = b2 v + (1 - b2) g^2
        a_t = lr sqrt(1 - b2^t) / (1 - b1^t);   p' = p - a_t m' / (sqrt(v') + eps)

    with t counted from 1.  A batch's gradient is the mean of its
    sequences' gradients, taken a sequence at a time.  ``rows`` keeps
    only the first so many sequences of each batch (the half-batch
    fault)."""
    grad = jax.jit(jax.value_and_grad(loss_fn_))
    tm = jax.tree_util.tree_map

    # 541M float32 parameters at the cell's size: the parameters, Adam's
    # two moments and the gradient are each 2.2 GB, so every pass over
    # them writes in place, and the start is kept on the host
    def update(p, m, v, g, t):
        m2 = tm(lambda a, b: beta1 * a + (1 - beta1) * b, m, g)
        v2 = tm(lambda a, b: beta2 * a + (1 - beta2) * b * b, v, g)
        a_t = lr * jnp.sqrt(1 - beta2 ** t) / (1 - beta1 ** t)
        p2 = tm(lambda a, b, c: a - a_t * b / (jnp.sqrt(c) + eps), p, m2, v2)
        return p2, m2, v2
    update = jax.jit(update, donate_argnums=(0, 1, 2))
    add = jax.jit(lambda acc, gi, n: tm(lambda a, b: a + b / n, acc, gi),
                  donate_argnums=(0,))

    p0 = jax.device_get(params)
    p = tm(jnp.array, params)       # the caller keeps its own
    m = tm(jnp.zeros_like, params)
    v = tm(jnp.zeros_like, params)
    losses, g1, first = [], None, None
    for t, (ids, labels) in enumerate(batches, start=1):
        ids, labels = jnp.asarray(ids), jnp.asarray(labels)
        if rows is not None:
            ids, labels = ids[:rows], labels[:rows]
        n = ids.shape[0]
        loss, g = 0.0, tm(jnp.zeros_like, params)
        for i in range(n):
            li, gi = grad(p, ids[i:i + 1], labels[i:i + 1])
            loss = loss + float(li) / n
            g = add(g, gi, jnp.float32(n))
            del gi
        losses.append(loss)
        if g1 is None:
            g1, first = jax.device_get(C.leaf_norms(g)), jax.device_get(g)
        p, m, v = update(p, m, v, g, jnp.float32(t))
        del g
    del m, v
    dp = jax.device_get(jax.jit(
        lambda a, b: C.leaf_norms(tm(lambda s, u: s - u, a, b)))(p, p0))
    return {"losses": losses, "grad_norms": g1, "change_norms": dp,
            "first_grad": first}
