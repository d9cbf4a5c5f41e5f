"""Ouro (ByteDance, ``model_type`` ``ouro``; "Scaling Latent Reasoning via
Looped Language Models", arXiv:2510.25741) as a plain reference: one
pipeline stage's layers of a looped decoder, run ``total_ut_steps`` times
over the same leaves, with an exit gate after every pass.  ``jax.numpy``,
float32, ``highest``; nothing here imports the program.

With RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w, and no bias but the gate's:

  block l   a = x + N2_l(Attn_l(N1_l(x)));   y = a + N4_l(MLP_l(N3_l(a)))
  Attn      q, k, v = u W_q, u W_k, u W_v: H heads of Dh each; rotary
            (theta) over the whole head, halves paired (i with i + Dh/2);
            causal softmax(q k^T / sqrt(Dh)) v;  (.) W_o
  MLP       (silu(u W_1) * (u W_3)) W_2    (W_1 the gate's, W_3 the up
            projection, W_2 the way down)
  loop      h_0 = E[ids];  h_r = N_f(Stack(h_{r-1})),  r = 1..R:  Stack is
            the L blocks in order, THE SAME LEAVES every pass, N_f the
            model's final RMSNorm, whose output feeds the next pass and
            the heads
  gate      lam_r = sigmoid(h_r w_g + b_g)  per token, in float32
  exit      p_1 = lam_1;  p_r = lam_r prod_{j<r}(1 - lam_j) for r < R;
            p_R = prod_{j<R}(1 - lam_j)
  loss      per token  sum_r p_r CE(h_r W_head, label) - beta H(p),
            H(p) = -sum_r p_r log p_r;  the mean over tokens

From the source's ``config.json``: every width, ``total_ut_steps``,
``rms_norm_eps``, ``rope_theta``, the untied head.  From the family's
public modelling code and the paper (the configuration's ``assumed``):
the sandwich of norms, the final norm closing every pass, the gate, the
loss and its beta.

The loop is a Python ``for`` over the passes on one dict of leaves, so a
leaf's gradient is the sum over the passes by the chain rule and nothing
else.  A sequence is taken at a time, each block under
``jax.checkpoint``, attention a head at a time and the logits in blocks
of rows, so that float32 at 4,096 tokens fits the chip beside Adam's
state.  ``numerics`` rounds the operands of every matrix product but the
gate's, which the configuration states in float32 (``precision``).

``layers(cfg)`` is the FLOP walk, as ``reference/lfm2_moe.py`` has it: a
row is one SEQUENCE and every matrix product of every pass is a ``dense``
entry whose ``nin x nout`` is its multiply-adds for one sequence (the
projections at T tokens, attention's two products at T^2/2), the first
entry an empty product so that the first real one gets its input
gradient (the embedding's).

``grad_passes`` is for the benchmark's readings, which put a fault of
the mechanism in the program's place: the passes whose use of the
stack's leaves sends a gradient (None: all, the model).

The step is not one program: a block and a pass's cross-entropy are each
a ``jax.jit`` of their own (one program serves every block of every pass,
the blocks differing in their leaves alone) and ``follow`` differentiates
the loss around them without a ``jit`` of the whole.  As one program the
step compiled for two minutes at the published sizes into 314 MB, more
than the machine's compile cache keeps, in every run; under a caller's
own ``jit`` the pieces fold into the caller's program as before.

``follow`` is ``reference/lfm2_moe.py``'s loop with Adam written out, for
612M parameters: that one keeps the caller's weights, its own copy, the
two moments, a batch's summed gradient and a sequence's (six times 2.45
GB, and the chip has 16), so this one takes the weights over (the caller
hands it a fresh set) and, where a batch is one sequence, takes that
sequence's gradient as the batch's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.harness import flops
from benchmark.reference import common as C
from benchmark.reference.lfm2_moe import rms_norm, rotary

LOGIT_ROWS = 512
BLOCK_NORMS = ("attn_in_norm", "attn_out_norm", "mlp_in_norm", "mlp_out_norm")


def _head_dim(cfg):
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layers(cfg):
    T, D, F = cfg["seq_len"], cfg["hidden_size"], cfg["intermediate_size"]
    H, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  _head_dim(cfg))
    block = [flops.dense(T * D, H * Dh), flops.dense(T * D, Hkv * Dh),
             flops.dense(T * D, Hkv * Dh),
             flops.dense(T * T // 2, H * Dh),       # q k^T, causal
             flops.dense(T * T // 2, H * Dh),       # p v
             flops.dense(T * H * Dh, D)] + [flops.dense(T * D, F)] * 3
    out = [flops.dense(0, 0)]
    for _ in range(cfg["total_ut_steps"]):
        out += block * len(cfg["layers_run"])
    for _ in range(cfg["total_ut_steps"]):
        out += [flops.dense(T * D, cfg["vocab_size"]), flops.dense(T * D, 1)]
    return out


# --------------------------------------------------------------------------
def _matrix(key, n_in, n_out):
    return jax.random.normal(key, (n_in, n_out), jnp.float32) \
        / jnp.sqrt(float(n_in))


def init_params(cfg, key):
    """Seeded weights, a dict by the vertex names of the program's graph
    (the stack's leaves under ``"<block vertex>/<leaf>"``, as its loop
    vertex holds them): matrices N(0, 1/fan_in), embedding rows N(0, 1),
    norm weights N(1, 0.1), the gate's weight N(0, 1/hidden) and its
    bias 0 (``assumed``)."""
    D, V, F = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    H, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  _head_dim(cfg))
    key, k = jax.random.split(key)
    params = {"embed": {"W": jax.random.normal(k, (V, D), jnp.float32)}}
    stack = {}
    for i in cfg["layers_run"]:
        key, *ks = jax.random.split(key, 12)
        for n, kn in zip(BLOCK_NORMS, ks[:4]):
            stack[f"l{i}_{n}/gamma"] = C.small_normal(kn, (D,), 0.1, mean=1.0)
        stack[f"l{i}_attn/Wq"] = _matrix(ks[4], D, H * Dh)
        stack[f"l{i}_attn/Wk"] = _matrix(ks[5], D, Hkv * Dh)
        stack[f"l{i}_attn/Wv"] = _matrix(ks[6], D, Hkv * Dh)
        stack[f"l{i}_attn/Wo"] = _matrix(ks[7], H * Dh, D)
        stack[f"l{i}_mlp/W1"] = _matrix(ks[8], D, F)
        stack[f"l{i}_mlp/W3"] = _matrix(ks[9], D, F)
        stack[f"l{i}_mlp/W2"] = _matrix(ks[10], F, D)
    key, kn, kh, kg = jax.random.split(key, 4)
    stack["final_norm/gamma"] = C.small_normal(kn, (D,), 0.1, mean=1.0)
    params["stack"] = stack
    params["head"] = {"W": _matrix(kh, D, V), "w_g": _matrix(kg, D, 1)[:, 0],
                      "b_g": jnp.zeros((1,), jnp.float32)}
    return params


# --------------------------------------------------------------------------
def block_fn(cfg, numerics="float32"):
    """(block(leaves, x) -> y for one sequence x [T, hidden] and one
    block's leaves by their names without the ``l<i>_`` in front, mm)."""
    rnd, ct = C.rounder(numerics)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    H, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  _head_dim(cfg))

    def mm(x, w):
        return ct(jnp.dot(rnd(x), rnd(w), precision=C.HIGHEST))

    def attention(p, u):
        T = u.shape[0]
        q = rotary(mm(u, p["attn/Wq"]).reshape(T, H, Dh), theta)
        k = rotary(mm(u, p["attn/Wk"]).reshape(T, Hkv, Dh), theta)
        v = mm(u, p["attn/Wv"]).reshape(T, Hkv, Dh)
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
        return mm(out.transpose(1, 0, 2, 3).reshape(T, H * Dh),
                  p["attn/Wo"])

    def mlp(p, u):
        return mm(jax.nn.silu(mm(u, p["mlp/W1"])) * mm(u, p["mlp/W3"]),
                  p["mlp/W2"])

    def block(p, x):
        n1, n2, n3, n4 = (p[f"{n}/gamma"] for n in BLOCK_NORMS)
        a = x + rms_norm(attention(p, rms_norm(x, n1, eps)), n2, eps)
        return a + rms_norm(mlp(p, rms_norm(a, n3, eps)), n4, eps)

    return block, mm


def passes_fn(cfg, numerics="float32", grad_passes=None):
    """hidden(params, ids [T]) -> [h_1 .. h_R], each [T, hidden]: the
    final norm's output after every pass, of one sequence."""
    block = jax.jit(jax.checkpoint(block_fn(cfg, numerics)[0]))
    eps = cfg["rms_norm_eps"]

    def hidden(params, ids):
        h, out = params["embed"]["W"][ids], []
        for r in range(cfg["total_ut_steps"]):          # THE LOOP
            p = params["stack"]
            if grad_passes is not None and r not in grad_passes:
                p = lax.stop_gradient(p)
            for i in cfg["layers_run"]:
                own = f"l{i}_"
                h = block({k[len(own):]: v for k, v in p.items()
                           if k.startswith(own)}, h)
            h = rms_norm(h, p["final_norm/gamma"], eps)
            out.append(h)
        return out

    return hidden


def exit_distribution(head, hs):
    """p [R, T] from the passes' hidden states, in float32."""
    lam = [jax.nn.sigmoid(jnp.dot(h, head["w_g"], precision=C.HIGHEST)
                          + head["b_g"][0]) for h in hs]
    p, stay = [], jnp.ones_like(lam[0])
    for l in lam[:-1]:
        p.append(l * stay)
        stay = stay * (1.0 - l)
    return jnp.stack(p + [stay])


def pass_losses(cfg, numerics="float32", grad_passes=None):
    """terms(params, ids [T], labels [T]) -> (p [R, T], CE [R, T]) of one
    sequence."""
    hidden = passes_fn(cfg, numerics, grad_passes)
    _, mm = block_fn(cfg, numerics)

    @jax.jit
    def ce_pass(W, h, labels):          # one pass's CE [T]
        rows = min(LOGIT_ROWS, h.shape[0])

        @jax.checkpoint
        def ce_rows(hl):
            hr, lab = hl
            z = mm(hr, W)
            return jax.scipy.special.logsumexp(z, axis=-1) \
                - jnp.take_along_axis(z, lab[:, None], axis=1)[:, 0]

        return lax.map(ce_rows, (h.reshape(-1, rows, h.shape[-1]),
                                 labels.reshape(-1, rows))).reshape(-1)

    def terms(params, ids, labels):
        hs = hidden(params, ids)
        ce = jnp.stack([ce_pass(params["head"]["W"], h, labels) for h in hs])
        return exit_distribution(params["head"], hs), ce

    return terms


def loss_fn(cfg, numerics="float32", grad_passes=None):
    """loss(params, ids [S, T], labels [S, T]) -> the mean over tokens of
    the expected exit loss less beta times the exit entropy."""
    terms = pass_losses(cfg, numerics, grad_passes)
    beta = cfg["entropy_weight"]

    def sequence(params, ids, labels):
        p, ce = terms(params, ids, labels)
        plogp = jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)
        return jnp.mean(jnp.sum(p * ce + beta * plogp, axis=0))

    def loss(params, ids, labels):
        return jnp.mean(jnp.stack([sequence(params, i, l)
                                   for i, l in zip(ids, labels)]))

    return loss


# --------------------------------------------------------------------------
def follow(loss_fn_, params, batches, lr, beta1, beta2, eps, rows=None):
    """Train ``len(batches)`` steps from ``params`` under Adam and return
    what the comparison reads: each step's loss, the first gradient with
    its per-leaf norms, and the per-leaf norm of the parameters' change
    over all the steps.  ``params`` are taken over: on a device that
    honours donation they are gone when this returns.

    Adam as Kingma & Ba 2015 write it at the end of their section 2, the
    form that folds both bias corrections into the step size:

        m' = b1 m + (1 - b1) g;   v' = b2 v + (1 - b2) g^2
        a_t = lr sqrt(1 - b2^t) / (1 - b1^t);   p' = p - a_t m' / (sqrt(v') + eps)

    with t counted from 1.  A batch's gradient is the mean of its
    sequences' gradients, taken a sequence at a time.  ``rows`` keeps
    only the first so many sequences of each batch."""
    grad = jax.value_and_grad(loss_fn_)     # its pieces are compiled, not it
    tm = jax.tree_util.tree_map

    def update(p, m, v, g, t):
        m2 = tm(lambda a, b: beta1 * a + (1 - beta1) * b, m, g)
        v2 = tm(lambda a, b: beta2 * a + (1 - beta2) * b * b, v, g)
        a_t = lr * jnp.sqrt(1 - beta2 ** t) / (1 - beta1 ** t)
        p2 = tm(lambda a, b, c: a - a_t * b / (jnp.sqrt(c) + eps), p, m2, v2)
        return p2, m2, v2
    update = jax.jit(update, donate_argnums=(0, 1, 2))
    add = jax.jit(lambda acc, gi, n: tm(lambda a, b: a + b / n, acc, gi),
                  donate_argnums=(0,))

    p0 = jax.device_get(params)     # the start stays on the host
    p = params
    m = tm(jnp.zeros_like, params)
    v = tm(jnp.zeros_like, params)
    losses, g1, first = [], None, None
    for t, (ids, labels) in enumerate(batches, start=1):
        ids, labels = jnp.asarray(ids), jnp.asarray(labels)
        if rows is not None:
            ids, labels = ids[:rows], labels[:rows]
        n = ids.shape[0]
        loss, g = grad(p, ids[:1], labels[:1])
        loss = float(loss) / n
        if n > 1:
            g = tm(lambda a: a / n, g)
        for i in range(1, n):
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
