"""SDAR-MoE (JetLM, ``model_type`` ``sdar_moe``; "SDAR: A Synergistic
Diffusion-AutoRegression Paradigm for Scalable Sequence Generation",
arXiv:2510.06303; trained as block diffusion language models are,
arXiv:2503.09573) as a plain reference: one chip's share of an
expert-parallel job.  ``jax.numpy``, float32, ``highest``; nothing here
imports the program.

With N(x) = x / sqrt(mean(x^2) + eps) * w:

  block   h = x + Attn(N1(x));  y = h + MoE(N2(h));  no bias anywhere
  Attn    q = u W_q (H heads of Dh), k = u W_k, v = u W_v (H_kv heads);
          N with a learned weight over each head of q and of k; rotary
          over the whole head, halves paired (i with i + Dh/2), at the
          row's position; o = softmax(q k^T / sqrt(Dh) + M) v, a
          key/value head serving H / H_kv consecutive query heads;
          out = o W_o
  MoE     s = softmax_E(u W_r) in float32;  S = the k largest;
          w_e = s_e / sum_S s (``norm_topk_prob``);  the sum over the
          selected experts THAT ARE HELD HERE of
          w_e (silu(u W_1e) * (u W_3e)) W_2e.  Selection and
          renormalisation are over all E experts; what the absent
          experts would add is left out, here as in the program.
  model   logits = N_f(y_last) W_head

Training, one clean sequence x0 of L tokens in blocks of b consecutive
tokens (the noising itself is the program's pre-processor's, and the
benchmark checks its batches against the definition; this file is
handed ``[x_t ; x0]``, x0 and the weights):

  the stack runs ONCE over the 2L rows [x_t ; x0], both halves at
  positions 0 .. L-1
  M, for row i in block j_i and column c in block j_c of their halves
  ("noisy" = first half, "clean" = second half):
      noisy row  sees noisy columns with j_c == j_i
                 and  clean columns with j_c <  j_i
      clean row  sees clean columns with j_c <= j_i
  loss = (1 / L) sum_i w_i CE(logits_i over the noisy half, x0_i),
  w_i = 1 / t_{j_i} on a masked token and 0 elsewhere, no shift

Attention is taken a key/value head and a block of query rows at a time
under ``jax.checkpoint`` and each layer under ``jax.checkpoint``, so
that float32 at 2 x 4,096 rows fits the chip beside Adam's state.  The
held experts are computed densely, every row through every held expert,
one expert after another, and masked by the routing: the plain form of a
grouped product.

The step is not one program: a layer, and the head with its loss, are
each a ``jax.jit`` of their own (one program serves all the layers,
which differ in their leaves alone) and ``follow`` differentiates the
loss around them without a ``jit`` of the whole, as
``reference/ouro.py`` does.  As one program the step compiled for three
to four minutes at the published sizes into several hundred MB, more
than the machine's compile cache keeps, in every run (PERF.md, PR 35:
210 of a run's 225 s of reference were that compilation; the products
themselves take 3 s a step).  Under a caller's own ``jit`` the pieces
fold into the caller's program as before.

``numerics`` rounds the operands of every matrix product but the
router's, which the configuration states in float32 (``precision``);
``router_bfloat16`` leaves every product in float32 and rounds the
router's operands and scores to bfloat16 instead, and
``params_bfloat16`` is for ``follow``: the parameters are held in
bfloat16 (rounded at the start and after every update).  Both are
controls of what the configuration's ``precision`` states, for the
readings.  ``fault`` puts a fault of the mechanism in the program's
place, for the readings the limits are set from: ``causal_2L`` (a causal
mask over the 2L rows), ``positions_2L`` (the noisy half at positions
L..2L-1), ``no_weight`` (the weight 1 / t left out: 1 on a masked
token), ``own_clean_block`` (a noisy row sees the clean copy of its own
block).

``layers(cfg)`` is the FLOP walk (``harness/flops.py`` counts per row
of a batch; a row here is one SEQUENCE and every product a ``dense``
entry whose ``nin x nout`` is its multiply-adds): the stack at 2L rows,
attention's two products at the live pairs L^2 + L b, the held experts
at their even share of 2L x k assignments, the head at L rows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.harness import flops
from benchmark.reference import common as C

FAULTS = ("causal_2L", "positions_2L", "no_weight", "own_clean_block")
Q_ROWS = 1024       # query rows a checkpointed attention piece holds
CONTROLS = ("router_bfloat16", "params_bfloat16")


def live_pairs(seq_len: int, block_length: int) -> int:
    """Pairs the mask leaves live of the (2L)^2: a noisy row its own
    block (b) and the clean blocks before it, a clean row the clean
    blocks up to its own."""
    return seq_len * seq_len + seq_len * block_length


def layers(cfg):
    L, D = cfg["seq_len"], cfg["hidden_size"]
    H, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    pairs = live_pairs(L, cfg["block_length"])
    held_share = (cfg["num_experts_per_tok"] * len(cfg["experts_held"])
                  / cfg["num_experts_published"])
    out = [flops.dense(0, 0)]       # as lfm2's: the first entry has no input gradient
    for _ in cfg["layers_run"]:
        out += [flops.dense(2 * L * D, H * Dh),
                flops.dense(2 * L * D, Hkv * Dh),
                flops.dense(2 * L * D, Hkv * Dh),
                flops.dense(pairs, H * Dh),           # q k^T at the live pairs
                flops.dense(pairs, H * Dh),           # p v
                flops.dense(2 * L * H * Dh, D),
                flops.dense(2 * L * D, cfg["num_experts_published"])]
        out += [flops.dense(int(2 * L * held_share) * D,
                            cfg["moe_intermediate_size"])] * 3
    out.append(flops.dense(L * D, cfg["vocab_size"]))
    return out


# --------------------------------------------------------------------------
def _matrix(key, n_in, n_out, lead=()):
    return jax.random.normal(key, lead + (n_in, n_out), jnp.float32) \
        / jnp.sqrt(float(n_in))


def init_params(cfg, key):
    """Seeded weights, a dict by the vertex names of the program's graph:
    matrices N(0, 1/fan_in), embedding rows N(0, 1), norm weights
    N(1, 0.1) (``assumed``: lfm2's initialisers); the mask id's embedding
    row is zero (``assumed``: the token is new to the checkpoint that
    continued training starts from, so its rows are told apart by what
    they attend to, not by a row of noise they all share)."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    G, E, F = (len(cfg["experts_held"]), cfg["num_experts_published"],
               cfg["moe_intermediate_size"])
    params = {}

    def norm(k, n):
        return {"gamma": C.small_normal(k, (n,), 0.1, mean=1.0)}

    key, k = jax.random.split(key)
    params["embed"] = {"W": jax.random.normal(k, (V, D), jnp.float32)}
    if cfg.get("mask_id") is not None:
        params["embed"]["W"] = params["embed"]["W"].at[cfg["mask_id"]].set(0.0)
    for i in cfg["layers_run"]:
        key, kn1, kn2, *ks = jax.random.split(key, 13)
        params[f"l{i}_attn_norm"] = norm(kn1, D)
        params[f"l{i}_moe_norm"] = norm(kn2, D)
        params[f"l{i}_attn"] = {
            "Wq": _matrix(ks[0], D, H * Dh), "Wk": _matrix(ks[1], D, Hkv * Dh),
            "Wv": _matrix(ks[2], D, Hkv * Dh),
            "Wo": _matrix(ks[3], H * Dh, D),
            "q_norm": C.small_normal(ks[4], (Dh,), 0.1, mean=1.0),
            "k_norm": C.small_normal(ks[5], (Dh,), 0.1, mean=1.0)}
        params[f"l{i}_moe"] = {"Wg": _matrix(ks[9], D, E),
                               "W1": _matrix(ks[6], D, F, (G,)),
                               "W3": _matrix(ks[7], D, F, (G,)),
                               "W2": _matrix(ks[8], F, D, (G,))}
    key, kn, kh = jax.random.split(key, 3)
    params["final_norm"] = norm(kn, D)
    params["head"] = {"W": _matrix(kh, D, V)}
    return params


# --------------------------------------------------------------------------
def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * w


def rotary(a, positions, theta):
    """a [T, heads, Dh] at ``positions`` [T], halves paired."""
    Dh = a.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a1, a2 = a[..., :Dh // 2], a[..., Dh // 2:]
    return jnp.concatenate([a1 * cos - a2 * sin, a2 * cos + a1 * sin], -1)


def mask(rows, cols, L, b, fault=None):
    """M [len(rows), len(cols)] (True = the row sees the column) for row
    and column indices into the 2L rows ``[x_t ; x0]``: the four
    comparisons, on explicit block indices."""
    i, c = rows[:, None], cols[None, :]
    if fault == "causal_2L":
        return i >= c
    noisy_i, noisy_c = i < L, c < L
    clean_i, clean_c = i >= L, c >= L
    j_i, j_c = (i % L) // b, (c % L) // b       # block within its half
    before = (j_c <= j_i) if fault == "own_clean_block" else (j_c < j_i)
    return ((noisy_i & noisy_c & (j_c == j_i))      # noisy row, noisy column
            | (noisy_i & clean_c & before)          # noisy row, clean column
            | (clean_i & clean_c & (j_c <= j_i)))   # clean row, clean column


def blocks(cfg, numerics="float32", fault=None):
    """{"attention": f(p, u), "experts": f(p, u), "mm": f(x, w)} over one
    sequence's normed input u [2L, hidden], p the block's own leaves."""
    rnd, ct = C.rounder("float32" if numerics in CONTROLS else numerics)
    bf16 = C.rounder("bfloat16")[0]
    eps = cfg["rms_norm_eps"]
    H, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    L, b = cfg["seq_len"], cfg["block_length"]
    held, topk = list(cfg["experts_held"]), cfg["num_experts_per_tok"]
    G = H // Hkv

    def mm(x, w):
        return ct(jnp.dot(rnd(x), rnd(w), precision=C.HIGHEST))

    def attention(p, u):
        T = u.shape[0]                                  # 2L
        positions = jnp.arange(T) % L
        if fault == "positions_2L":                     # noisy half at L..2L-1
            positions = jnp.where(jnp.arange(T) < L, jnp.arange(T) + L,
                                  jnp.arange(T) - L)
        q = mm(u, p["Wq"]).reshape(T, H, Dh)
        k = mm(u, p["Wk"]).reshape(T, Hkv, Dh)
        v = mm(u, p["Wv"]).reshape(T, Hkv, Dh)
        q = rotary(rms_norm(q, p["q_norm"], eps), positions, cfg["rope_theta"])
        k = rotary(rms_norm(k, p["k_norm"], eps), positions, cfg["rope_theta"])
        rows = min(Q_ROWS, T)
        nq = T // rows
        cols = jnp.arange(T)

        @jax.checkpoint
        def piece(args):    # one key/value head, one block of query rows
            qg, kg, vg, first = args     # [rows, H/Hkv, Dh], [T, Dh] x 2, ()
            s = ct(jnp.einsum("thd,sd->hts", rnd(qg), rnd(kg),
                              precision=C.HIGHEST)) / jnp.sqrt(float(Dh))
            m = mask(first + jnp.arange(rows), cols, L, b, fault)
            pr = jax.nn.softmax(jnp.where(m[None], s, -jnp.inf), axis=-1)
            return ct(jnp.einsum("hts,sd->thd", rnd(pr), rnd(vg),
                                 precision=C.HIGHEST))

        # [Hkv x nq] pieces: query rows by block, heads by key/value head
        qg = q.reshape(nq, rows, Hkv, G, Dh).transpose(2, 0, 1, 3, 4) \
            .reshape(Hkv * nq, rows, G, Dh)
        kg = jnp.repeat(k.transpose(1, 0, 2), nq, axis=0)
        vg = jnp.repeat(v.transpose(1, 0, 2), nq, axis=0)
        first = jnp.tile(jnp.arange(nq) * rows, Hkv)
        out = lax.map(piece, (qg, kg, vg, first))       # [Hkv*nq, rows, G, Dh]
        out = out.reshape(Hkv, nq, rows, G, Dh).transpose(1, 2, 0, 3, 4)
        return mm(out.reshape(T, H * Dh), p["Wo"])

    def mlp(p, u):
        return mm(jax.nn.silu(mm(u, p["W1"])) * mm(u, p["W3"]), p["W2"])

    def experts(p, u):
        # the router in float32, whatever the numerics (but under the
        # control that rounds it, and it alone, to bfloat16)
        if numerics == "router_bfloat16":
            z = bf16(jnp.dot(bf16(u), bf16(p["Wg"]), precision=C.HIGHEST))
        else:
            z = jnp.dot(u, p["Wg"], precision=C.HIGHEST)
        s = jax.nn.softmax(z, axis=-1)
        w, sel = lax.top_k(s, topk)                              # [T, k]
        if cfg["norm_topk_prob"]:
            w = w / jnp.sum(w, axis=-1, keepdims=True)

        @jax.checkpoint
        def one(out, leaves):       # the next held expert's part of the sum
            w1, w3, w2, e = leaves
            w_e = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)  # 0: not sent
            return out + w_e[:, None] * mlp(
                {"W1": w1, "W3": w3, "W2": w2}, u), None

        return lax.scan(one, jnp.zeros_like(u),
                        (p["W1"], p["W3"], p["W2"], jnp.asarray(held)))[0]

    return {"attention": attention, "experts": experts, "mm": mm}


PARTS = ("attn_norm", "attn", "moe_norm", "moe")   # a layer's vertices


def layer_fn(cfg, numerics="float32", fault=None):
    """layer(p, x [2L, hidden]) -> [2L, hidden], p the layer's own leaves
    by ``PARTS``: a program of its own, recomputed in the backward pass."""
    b = blocks(cfg, numerics, fault)
    eps = cfg["rms_norm_eps"]

    def layer(p, x):
        h = x + b["attention"](p["attn"],
                               rms_norm(x, p["attn_norm"]["gamma"], eps))
        return h + b["experts"](p["moe"],
                                rms_norm(h, p["moe_norm"]["gamma"], eps))

    return jax.jit(jax.checkpoint(layer))


def hidden_fn(cfg, numerics="float32", fault=None):
    """hidden(params, ids [2L]) -> [2L, hidden] after the last layer."""
    layer = layer_fn(cfg, numerics, fault)

    def hidden(params, ids):
        x = params["embed"]["W"][ids]
        for i in cfg["layers_run"]:
            x = layer({part: params[f"l{i}_{part}"] for part in PARTS}, x)
        return x

    return hidden


def logits_fn(cfg, numerics="float32", fault=None):
    """logits(params, ids [2L]) -> [L, vocabulary rows held]: the noisy
    half's rows of one sequence ``[x_t ; x0]``."""
    hidden = hidden_fn(cfg, numerics, fault)
    mm = blocks(cfg, numerics, fault)["mm"]
    eps, L = cfg["rms_norm_eps"], cfg["seq_len"]

    @jax.jit
    def head(gamma, W, x):
        return mm(rms_norm(x[:L], gamma, eps), W)

    return lambda params, ids: head(params["final_norm"]["gamma"],
                                    params["head"]["W"], hidden(params, ids))


def loss_fn(cfg, numerics="float32", fault=None):
    """loss(params, ids [S, 2L], labels [S, L], weights [S, L]) -> mean
    over the sequences of (1 / L) sum_i w_i CE_i."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}: {FAULTS}")
    logits = logits_fn(cfg, numerics, fault)
    L = cfg["seq_len"]

    @jax.jit
    def weighted_ce(z, labels, weights):
        logp = z - jax.scipy.special.logsumexp(z, axis=-1, keepdims=True)
        ce = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
        if fault == "no_weight":
            weights = (weights > 0).astype(ce.dtype)
        return jnp.sum(weights * ce) / L

    def loss(params, ids, labels, weights):
        return jnp.mean(jnp.stack([weighted_ce(logits(params, i), l, w)
                                   for i, l, w in zip(ids, labels, weights)]))

    return loss


# --------------------------------------------------------------------------
def follow(loss_fn_, params, batches, lr, beta1, beta2, eps, rows=None,
           hold="float32"):
    """Train ``len(batches)`` steps from ``params`` under Adam and return
    what the comparison reads: each step's loss, the first gradient with
    its per-leaf norms, and the per-leaf norm of the parameters' change
    over all the steps.  ``batches``: (ids [S, 2L], labels [S, L],
    weights [S, L]).  ``params`` are taken over: on a device that honours
    donation they are gone when this returns.

    Adam as Kingma & Ba 2015 write it at the end of their section 2, the
    form that folds both bias corrections into the step size:

        m' = b1 m + (1 - b1) g;   v' = b2 v + (1 - b2) g^2
        a_t = lr sqrt(1 - b2^t) / (1 - b1^t);   p' = p - a_t m' / (sqrt(v') + eps)

    with t counted from 1.  A batch's gradient is the mean of its
    sequences' gradients, taken a sequence at a time.  ``rows`` keeps
    only the first so many sequences of each batch.  ``hold`` is the
    precision the parameters are held in: under ``"bfloat16"`` (the
    ``params_bfloat16`` control) they are rounded at the start and after
    every update, and the change is taken from the rounded start."""
    grad = jax.value_and_grad(loss_fn_)     # its pieces are compiled, not it
    tm = jax.tree_util.tree_map

    # half a billion float32 parameters at the cell's size: parameters,
    # both moments and the gradient are 2.2 GB each and a layer's backward
    # program takes 2.1 GB more, so the parameters are taken over (the
    # caller hands over a fresh set), every pass over them writes in
    # place, a batch of one sequence takes that sequence's gradient as it
    # comes, and the start is kept on the host
    def update(p, m, v, g, t):
        m2 = tm(lambda a, b: beta1 * a + (1 - beta1) * b, m, g)
        v2 = tm(lambda a, b: beta2 * a + (1 - beta2) * b * b, v, g)
        a_t = lr * jnp.sqrt(1 - beta2 ** t) / (1 - beta1 ** t)
        p2 = tm(lambda a, b, c: a - a_t * b / (jnp.sqrt(c) + eps), p, m2, v2)
        return p2, m2, v2
    update = jax.jit(update, donate_argnums=(0, 1, 2))
    add = jax.jit(lambda acc, gi, n: tm(lambda a, b: a + b / n, acc, gi),
                  donate_argnums=(0,))

    held = (lambda t: t) if hold == "float32" else \
        jax.jit(lambda t: tm(C.rounder(hold)[0], t), donate_argnums=(0,))
    p = held(params)
    p0 = jax.device_get(p)
    m = v = None
    losses, g1, first = [], None, None
    for t, (ids, labels, weights) in enumerate(batches, start=1):
        ids, labels, weights = (jnp.asarray(ids), jnp.asarray(labels),
                                jnp.asarray(weights))
        if rows is not None:
            ids, labels, weights = ids[:rows], labels[:rows], weights[:rows]
        n = ids.shape[0]
        loss, g = grad(p, ids[:1], labels[:1], weights[:1])
        loss = float(loss) / n
        if n > 1:
            g = tm(lambda a: a / n, g)
        for i in range(1, n):
            li, gi = grad(p, ids[i:i + 1], labels[i:i + 1], weights[i:i + 1])
            loss = loss + float(li) / n
            g = add(g, gi, jnp.float32(n))
            del gi
        losses.append(loss)
        if g1 is None:
            g1, first = jax.device_get(C.leaf_norms(g)), jax.device_get(g)
        if m is None:
            m, v = tm(jnp.zeros_like, g), tm(jnp.zeros_like, g)
        p, m, v = update(p, m, v, g, jnp.float32(t))
        p = held(p)
        del g
    del m, v
    dp = jax.device_get(jax.jit(
        lambda a, b: C.leaf_norms(tm(lambda s, u: s - u, a, b)))(p, p0))
    return {"losses": losses, "grad_norms": g1, "change_norms": dp,
            "first_grad": first}
