"""Pallas kernel numerics vs the XLA reference implementations, run in
interpret mode on CPU (the TPU-vs-interpreter cross-check of SURVEY.md
§4; the same kernels compile natively on the chip)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import helpers
from deeplearning4j_tpu.ops import pallas_kernels as pk


def _qkv(B=2, H=2, T=256, D=128, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(
        rng.normal(size=(B, H, T, D)).astype(np.float32) * 0.3)
    return mk(), mk(), mk()


def _mask(B=2, T=256, pad_from=None):
    m = np.ones((B, T), np.float32)
    if pad_from is not None:
        m[:, pad_from:] = 0.0
    return jnp.asarray(m)


def test_flash_matches_dense():
    q, k, v = _qkv()
    km = _mask()
    out = pk.flash_attention(q, k, v, km)
    ref = pk._dense_reference(q, k, v, km, False, 1.0 / (128 ** 0.5))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_causal_matches_dense():
    q, k, v = _qkv(seed=1)
    km = _mask()
    out = pk.flash_attention(q, k, v, km, True)
    ref = pk._dense_reference(q, k, v, km, True, 1.0 / (128 ** 0.5))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_key_mask():
    q, k, v = _qkv(seed=2)
    km = _mask(pad_from=180)
    out = pk.flash_attention(q, k, v, km)
    ref = pk._dense_reference(q, k, v, km, False, 1.0 / (128 ** 0.5))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_grads():
    q, k, v = _qkv(B=1, H=1, seed=3)
    km = _mask(B=1)

    def loss_flash(q, k, v):
        return jnp.sum(pk.flash_attention(q, k, v, km, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(
            pk._dense_reference(q, k, v, km, True, 1.0 / (128 ** 0.5)) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_flash_supported_gate():
    q, _, _ = _qkv(T=256, D=128)
    assert pk.flash_attention_supported(q)
    q_small = jnp.zeros((2, 2, 64, 128))
    assert not pk.flash_attention_supported(q_small)
    # head dims 64/96 are lane-padded now (round-2 verdict: the D%128
    # gate excluded every realistic head dim)
    q_64 = jnp.zeros((2, 2, 256, 64))
    assert pk.flash_attention_supported(q_64)
    q_tiny_d = jnp.zeros((2, 2, 256, 16))
    assert not pk.flash_attention_supported(q_tiny_d)
    # ragged/bucketed T that isn't a 128-multiple is zero-padded inside
    # flash_attention (masked), so the gate accepts it now
    assert pk.flash_attention_supported(jnp.zeros((2, 2, 200, 64)))
    assert pk.flash_attention_supported(jnp.zeros((2, 2, 130, 128)))


@pytest.mark.parametrize("T,causal,pad_from", [
    (200, False, None), (200, True, 180), (130, True, None),
    (384 + 64, False, 300),
    # multiples of 128 that the 512-row tile does not divide: the tile
    # follows T (384: one tile of 384; 640: five of 128)
    (384, False, None), (384, True, 300), (640, False, 500),
    (640, True, None)])
def test_flash_ragged_T_padding_matches_dense(T, causal, pad_from):
    """Sequence lengths that don't tile into 128-row blocks pad (masked)
    inside flash_attention — bucketed ladders that aren't 128-multiples
    keep the flash path, forward AND gradient."""
    D = 64
    q, k, v = _qkv(B=2, H=2, T=T, D=D, seed=7)
    km = _mask(B=2, T=T, pad_from=pad_from)
    out = pk.flash_attention(q, k, v, km, causal)
    ref = pk._dense_reference(q, k, v, km, causal, 1.0 / (D ** 0.5))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    def loss_flash(q, k, v):
        return jnp.sum(pk.flash_attention(q, k, v, km, causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(
            pk._dense_reference(q, k, v, km, causal, 1.0 / (D ** 0.5)) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def _all_eqns(jaxpr):
    """Every equation of a jaxpr, those inside its kernels and loops too."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


@pytest.mark.parametrize("D", [64, 96, 100])
def test_flash_head_dim_padding_matches_dense(D):
    q, k, v = _qkv(D=D, seed=4)
    km = _mask()
    out = pk.flash_attention(q, k, v, km, True)
    ref = pk._dense_reference(q, k, v, km, True, 1.0 / (D ** 0.5))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)

    def loss_flash(q, k, v):
        return jnp.sum(pk.flash_attention(q, k, v, km, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(
            pk._dense_reference(q, k, v, km, True, 1.0 / (D ** 0.5)) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("D", [64, 96])
def test_flash_kernels_take_the_head_at_its_own_width(D):
    """The kernels need no 128-lane head (a block whose last
    dimension is the array's own is legal): called under the pad that
    flash_attention keeps for the cell's sake, forward and gradients."""
    q, k, v = _qkv(B=1, H=2, D=D, seed=14)
    km = _mask(B=1, pad_from=250)
    scale = 1.0 / (D ** 0.5)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(pk._flash_core(q, k, v, km, True, scale) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    widths = {var.aval.shape[-1] for e in _all_eqns(jaxpr.jaxpr)
              for var in e.outvars if getattr(var.aval, "ndim", 0) >= 2}
    assert D in widths and 128 not in widths, widths
    got = jax.grad(
        lambda q, k, v: jnp.sum(pk._flash_core(q, k, v, km, True, scale) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(
        lambda q, k, v: jnp.sum(
            pk._dense_reference(q, k, v, km, True, scale) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_flash_grads_with_key_mask():
    q, k, v = _qkv(B=1, H=1, seed=5)
    km = _mask(B=1, pad_from=150)

    def loss_flash(q, k, v):
        return jnp.sum(pk.flash_attention(q, k, v, km) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(
            pk._dense_reference(q, k, v, km, False, 1.0 / (128 ** 0.5)) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def _rel(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("pad_from", [None, 200], ids=["whole", "key-mask"])
def test_flash_bfloat16_operands_match_float32_reference(causal, pad_from):
    """bfloat16 q, k, v (what the chip's policy hands the core): the
    products run in bfloat16 with float32 accumulation, the softmax in
    float32.  Against the dense reference in float32 on the SAME rounded
    inputs, forward and all three gradients, at bfloat16's tolerance (8
    bits: the output, p and ds are each rounded once)."""
    T, D = 384, 64
    q, k, v = (a.astype(jnp.bfloat16)
               for a in _qkv(B=2, H=2, T=T, D=D, seed=11))
    km = _mask(B=2, T=T, pad_from=pad_from)
    w = _qkv(B=2, H=2, T=T, D=D, seed=12)[0]

    def loss_flash(q, k, v):
        out = pk.flash_attention(q, k, v, km, causal)
        assert out.dtype == jnp.bfloat16
        return jnp.sum(out.astype(jnp.float32) * w)

    def loss_ref(q, k, v):
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        return jnp.sum(
            pk._dense_reference(q, k, v, km, causal, 1.0 / (D ** 0.5)) * w)

    out = pk.flash_attention(q, k, v, km, causal)
    ref = pk._dense_reference(*(a.astype(jnp.float32) for a in (q, k, v)),
                              km, causal, 1.0 / (D ** 0.5))
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert a.dtype == jnp.bfloat16
        assert _rel(a, b) < 1e-2
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   rtol=5e-2, atol=2e-2)


def _dense_grads(q, k, v, km, rule, w):
    """dq, dk, dv of ``sum(out * w)`` through the dense reference in
    float32, a query row with no live key giving an output of zeros (the
    flash core's convention, where a softmax over nothing but masked
    scores would spread itself evenly)."""
    from deeplearning4j_tpu.ops import mask_rules
    T, D = q.shape[2], q.shape[3]
    pos = jnp.arange(T)
    rule = mask_rules.resolve(rule)
    pairs = jnp.ones((T, T), bool) if rule is None \
        else rule.live(pos[:, None], pos[None, :])
    row_lives = jnp.any(pairs[None] & (km[:, None, :] > 0), axis=-1)  # [B, T]

    def loss(q, k, v):
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        out = pk._dense_reference(q, k, v, km, rule, 1.0 / (D ** 0.5))
        return jnp.sum(jnp.where(row_lives[:, None, :, None], out, 0.0) * w)
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v), row_lives


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("D", [64, 128], ids=["D64-padded", "D128"])
@pytest.mark.parametrize("rule,T", [
    (False, 328), (True, 328), (("block_diffusion", 256, 4), 512)],
    ids=["full-T328", "causal-T328", "block-diffusion-L256"])
def test_flash_fused_backward_matches_dense_grads(monkeypatch, rule, T, D,
                                                  dtype):
    """The one backward kernel's dq, dk and dv against ``jax.grad`` of
    the dense reference, over several tiles a head (the cap lowered to
    128: T 328 pads to three tiles, 2 x 256 rows are four), B·H of 4, a
    key mask with dead keys, and, where the rule lets a row see its own
    first keys alone, query rows with no live key at all (``_LSE_DEAD``):
    those take a dq of exact zeros and give dk and dv nothing."""
    monkeypatch.setattr(pk, "_FLASH_BLOCK_CAP", 128)
    q, k, v = (a.astype(dtype) for a in _qkv(B=2, H=2, T=T, D=D, seed=21))
    w = _qkv(B=2, H=2, T=T, D=D, seed=22)[0]
    km = np.ones((2, T), np.float32)
    km[:, :4] = 0.0          # the only keys rows 0..3 see, under a rule
    km[0, 140:150] = 0.0     # dead keys inside the second tile
    km[1, 300:] = 0.0
    km = jnp.asarray(km)
    want, row_lives = _dense_grads(q, k, v, km, rule, w)
    assert bool(jnp.all(row_lives[:, :4])) == (rule is False)

    got = jax.grad(
        lambda q, k, v: jnp.sum(
            pk.flash_attention(q, k, v, km, rule).astype(jnp.float32) * w),
        argnums=(0, 1, 2))(q, k, v)
    dead = np.broadcast_to(~np.asarray(row_lives)[:, None, :, None], q.shape)
    assert not np.asarray(got[0], np.float32)[dead].any()
    for a, b in zip(got, want):
        assert a.dtype == dtype and float(jnp.abs(b).max()) > 0
        if dtype == jnp.float32:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)
        else:       # 8 bits: p and ds are each rounded once
            assert _rel(a, b) < 1e-2
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b), rtol=5e-2, atol=2e-2)


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_q_tile_dq_does_not_depend_on_how_many_key_tiles_the_grid_has(
        monkeypatch, causal):
    """dq gathers in one accumulator over a head's key tiles: zeroed at
    the head's first, written at its last.  The same 256 rows inside a
    sequence of 256 (two key tiles) and of 640 whose other keys are dead
    (five) take the same dq, in each of B·H = 6 heads."""
    monkeypatch.setattr(pk, "_FLASH_BLOCK_CAP", 128)
    short, long_ = 256, 640
    q, k, v = _qkv(B=2, H=3, T=long_, D=128, seed=23)
    w = _qkv(B=2, H=3, T=long_, D=128, seed=24)[0]

    def grads(T):
        km = _mask(B=2, T=T, pad_from=short)
        return jax.grad(
            lambda q, k, v: jnp.sum(
                (pk.flash_attention(q, k, v, km, causal) * w[:, :, :T])
                [:, :, :short]),
            argnums=(0, 1, 2))(q[:, :, :T], k[:, :, :T], v[:, :, :T])
    few, many = grads(short), grads(long_)
    assert pk._flash_block(short) == pk._flash_block(long_) == 128
    for a, b in zip(few, many):
        assert float(jnp.abs(a).max()) > 0
        np.testing.assert_allclose(np.asarray(b[:, :, :short]), np.asarray(a),
                                   rtol=1e-6, atol=1e-7)
        assert not np.asarray(b[:, :, short:]).any()
    heads = np.asarray(few[0]).reshape(6, -1)
    assert len({h.tobytes() for h in heads}) == 6


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_flash_products_run_in_the_operands_dtype(dtype):
    """No tile is lifted to float32 ahead of a product: every
    dot_general inside the two kernels multiplies operands of the
    input's dtype (float32 in, float32 products, as before) and
    accumulates in float32; and the backward builds a tile once: five
    products and one exp, where a dq and a dk/dv kernel ran seven and
    two."""
    q, k, v = (a.astype(dtype) for a in _qkv(B=1, H=2, T=256, D=64, seed=13))
    km = _mask(B=1)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(
            pk.flash_attention(q, k, v, km, True).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    kernels = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in kernels] == [
        "dl4j_flash_fwd", "dl4j_flash_bwd"]

    def inside(kern, primitive):
        return [e for sub in jax.core.jaxprs_in_params(kern.params)
                for e in _all_eqns(sub) if e.primitive.name == primitive]
    dots = [e for kern in kernels for e in inside(kern, "dot_general")]
    # forward, backward; twice, the tiles that cross the diagonal and
    # those below it being two loops over the same body
    assert len(dots) == 2 * (2 + 5)
    assert len(inside(kernels[1], "exp")) == 2 * 1
    for e in dots:
        assert [x.aval.dtype for x in e.invars] == [dtype, dtype]
        assert e.outvars[0].aval.dtype == jnp.float32


@pytest.mark.parametrize("T,block", [
    (128, 128), (256, 256), (384, 384), (512, 512), (640, 128), (768, 384),
    (1024, 512), (4096, 512), (4096 + 128, 384), (128 * 7, 128)])
def test_flash_tile_follows_T(T, block):
    """The square tile is the largest multiple of 128 under the cap
    that divides the (128-padded) T, so no sequence pays for rows beyond
    its own padding."""
    assert pk._flash_block(T) == block


def _assert_no_dense_tt(jaxpr, T):
    """No [T, T]-shaped intermediate anywhere in the traced program —
    the O(T) activation-memory invariant."""
    for eqn in jaxpr.jaxpr.eqns:
        for var in list(eqn.invars) + list(eqn.outvars):
            shape = getattr(getattr(var, "aval", None), "shape", ())
            assert not (len(shape) >= 2 and shape[-1] == T
                        and shape[-2] == T), \
                f"dense [T,T] intermediate: {eqn.primitive}"


def test_flash_bwd_is_blockwise_not_dense():
    """The backward jaxpr must contain no [T, T]-shaped intermediate —
    the round-2 verdict's O(T²) training-memory complaint."""
    T = 512
    q, k, v = _qkv(B=1, H=1, T=T, seed=6)
    km = _mask(B=1, T=T)

    def loss(q, k, v):
        return jnp.sum(pk.flash_attention(q, k, v, km, True) ** 2)

    _assert_no_dense_tt(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
        q, k, v), T)


def test_flash_8k_context_training_smoke():
    """T=8192 end-to-end training step through flash attention: gradient
    descent on projection params with O(T) activation memory — the dense
    path would materialize a 8192x8192 score matrix (256 MB fp32) per
    head in BOTH directions; the jaxpr proves no such intermediate
    exists (round-2 verdict item 2's done-criterion)."""
    T, DIN, D = 8192, 32, 64
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, T, DIN)).astype(np.float32) * 0.3)
    tgt = jnp.asarray(rng.normal(size=(1, T, D)).astype(np.float32) * 0.1)
    km = jnp.ones((1, T))
    params = {k: jnp.asarray(rng.normal(size=(DIN, D)).astype(np.float32)
                             * 0.1) for k in ("wq", "wk", "wv")}

    def loss(p):
        q = (x @ p["wq"])[:, None]          # [1, 1, T, D]
        k = (x @ p["wk"])[:, None]
        v = (x @ p["wv"])[:, None]
        out = pk.flash_attention(q, k, v, km, True)
        return jnp.mean((out[:, 0] - tgt) ** 2)

    # memory shape proof: no [T, T] intermediate anywhere in fwd+bwd
    _assert_no_dense_tt(jax.make_jaxpr(jax.grad(loss))(params), T)

    step = jax.jit(jax.value_and_grad(loss))
    l0, g = step(params)
    assert np.isfinite(float(l0))
    assert all(np.isfinite(np.asarray(v)).all() and
               float(jnp.abs(v).max()) > 0 for v in g.values())
    # sign-SGD (fixed step size) so descent is visible above fp32
    # resolution despite the mean-loss scale at T=8k
    for _ in range(5):
        params = jax.tree_util.tree_map(
            lambda p, gr: p - 1e-3 * jnp.sign(gr), params, g)
        l1, g = step(params)
    assert np.isfinite(float(l1))
    assert float(l1) < float(l0)            # the steps actually descend


def test_fused_softmax_xent():
    rng = np.random.default_rng(0)
    N, V = 100, 512
    logits = jnp.asarray(rng.normal(size=(N, V)).astype(np.float32))
    y = jnp.asarray(np.eye(V, dtype=np.float32)[rng.integers(0, V, N)])
    loss, grad = pk.fused_softmax_xent(logits, y)
    # reference
    logp = jax.nn.log_softmax(logits, axis=-1)
    ref_loss = -(y * logp).sum(-1)
    ref_grad = jax.nn.softmax(logits, -1) - y
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref_loss),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(ref_grad),
                               rtol=1e-5, atol=1e-5)


def test_fused_softmax_xent_soft_labels_grad():
    """Gradient stays exact for non-one-hot label rows (the p·Σy − y
    form), matching jax.grad of the dense formulation."""
    rng = np.random.default_rng(2)
    N, V = 32, 256
    logits = jnp.asarray(rng.normal(size=(N, V)).astype(np.float32))
    y = jnp.asarray(rng.uniform(0.0, 0.5, size=(N, V)).astype(np.float32))
    _, grad = pk.fused_softmax_xent(logits, y)
    ref_grad = jax.grad(
        lambda x: jnp.sum(-(y * jax.nn.log_softmax(x, -1))))(logits)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(ref_grad),
                               rtol=1e-4, atol=1e-5)


def test_mcxent_fused_dispatch_matches_dense(monkeypatch):
    """ops/losses.mcxent routed through softmax_xent_rows (forced via
    DL4J_FUSED_XENT) agrees with the unfused path in value AND gradient,
    including the 3-D RNN shape with a time mask."""
    from deeplearning4j_tpu.ops import losses

    rng = np.random.default_rng(3)
    for shape, mask in [
        ((64, 512), None),
        ((8, 16, 512), jnp.asarray((rng.uniform(size=(8, 16, 1)) > 0.3)
                                   .astype(np.float32))),
    ]:
        V = shape[-1]
        logits = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        idx = rng.integers(0, V, shape[:-1])
        y = jnp.asarray(np.eye(V, dtype=np.float32)[idx])

        def score(x, fused):
            monkeypatch.setenv("DL4J_FUSED_XENT", "1" if fused else "0")
            return losses.mcxent(y, x, "softmax", mask)

        v_fused = score(logits, True)
        v_dense = score(logits, False)
        np.testing.assert_allclose(np.asarray(v_fused), np.asarray(v_dense),
                                   rtol=1e-5, atol=1e-5)

        monkeypatch.setenv("DL4J_FUSED_XENT", "1")
        g_fused = jax.grad(lambda x: jnp.sum(losses.mcxent(
            y, x, "softmax", mask)))(logits)
        monkeypatch.setenv("DL4J_FUSED_XENT", "0")
        g_dense = jax.grad(lambda x: jnp.sum(losses.mcxent(
            y, x, "softmax", mask)))(logits)
        np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_dense),
                                   rtol=1e-4, atol=1e-5)


def test_fused_softmax_xent_ragged_rows():
    rng = np.random.default_rng(1)
    N, V = 37, 128  # N not a multiple of the row block
    logits = jnp.asarray(rng.normal(size=(N, V)).astype(np.float32))
    y = jnp.asarray(np.eye(V, dtype=np.float32)[rng.integers(0, V, N)])
    loss, grad = pk.fused_softmax_xent(logits, y, block_rows=16)
    assert loss.shape == (N,)
    assert grad.shape == (N, V)
    logp = jax.nn.log_softmax(logits, axis=-1)
    np.testing.assert_allclose(np.asarray(loss),
                               np.asarray(-(y * logp).sum(-1)),
                               rtol=1e-5, atol=1e-5)


class TestKernelSelfTest:
    """Per-kernel compile check + per-tier kill
    switch (the cuDNN-try/builtin-fallback pattern,
    ref ConvolutionLayer.java:67,157-212)."""

    def teardown_method(self):
        pk._disabled.clear()

    def test_self_test_ok(self):
        st = helpers.kernel_self_test()
        assert st["flash_attention"] == "ok"
        assert st["softmax_xent"] == "ok"
        assert st["interpret_mode"] is True  # CPU test mesh
        assert "disabled" not in st

    def test_per_tier_disable(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU", "1")  # pretend we're on TPU
        assert pk.flash_available() and pk.xent_available()
        pk.disable_kernels("flash broke", tier="flash")
        assert not pk.flash_available()
        assert pk.xent_available()  # healthy tier stays enabled
        pk.disable_kernels("all broke")
        assert not pk.xent_available()

    def test_env_kill_switch(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU", "1")
        monkeypatch.setenv("DL4J_PALLAS", "0")
        assert not pk.flash_available() and not pk.xent_available()

    def test_self_test_disables_on_error(self, monkeypatch):
        # a kernel that dies at dispatch must flip ONLY its own tier
        def boom(*a, **k):
            raise RuntimeError("mosaic rejected")
        monkeypatch.setattr(pk, "flash_attention", boom)
        st = helpers.kernel_self_test()
        assert st["flash_attention"].startswith("error")
        assert st["softmax_xent"] == "ok"
        assert "flash" in st["disabled"] and "xent" not in st["disabled"]


# ===========================================================================
# Fused LSTM cell (the cudnnRNN analog inside lstm_scan)
# ===========================================================================

def _lstm_fixture(N=4, H=16, nin=8, seed=5, dtype=jnp.float32):
    from deeplearning4j_tpu.ops import recurrent as rnn_ops
    rng = np.random.default_rng(seed)
    params = {
        "W": jnp.asarray(rng.normal(size=(nin, 4 * H)) * 0.3, dtype),
        "RW": jnp.asarray(rng.normal(size=(H, 4 * H)) * 0.3, dtype),
        "b": jnp.asarray(rng.normal(size=(4 * H,)) * 0.1, dtype),
        "pI": jnp.asarray(rng.normal(size=(H,)) * 0.1, dtype),
        "pF": jnp.asarray(rng.normal(size=(H,)) * 0.1, dtype),
        "pO": jnp.asarray(rng.normal(size=(H,)) * 0.1, dtype),
    }
    state = rnn_ops.LSTMState(
        jnp.asarray(rng.normal(size=(N, H)), dtype),
        jnp.asarray(rng.normal(size=(N, H)), dtype))
    return rng, params, state


class TestFusedLSTMStep:
    def test_step_forward_and_grad_parity(self):
        from deeplearning4j_tpu.ops import recurrent as rnn_ops
        rng, params, st = _lstm_fixture()
        N, H = st.c.shape
        zx = jnp.asarray(rng.normal(size=(N, 4 * H)), jnp.float32)
        p3 = jnp.stack([params["pI"], params["pF"], params["pO"]])
        c_f, h_f = pk.fused_lstm_step(zx, st.h, st.c, params["RW"], p3)
        ref_state, ref_h = rnn_ops._lstm_cell_pre(params, zx, st)
        np.testing.assert_allclose(np.asarray(c_f), np.asarray(ref_state.c),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(h_f), np.asarray(ref_h),
                                   rtol=1e-5, atol=1e-5)

        def lf(zx, h, c, rw, p3):
            cn, hn = pk.fused_lstm_step(zx, h, c, rw, p3)
            return jnp.sum(cn ** 2) + jnp.sum(hn ** 2)

        def lr(zx, h, c, rw, p3):
            pr = dict(params, RW=rw, pI=p3[0], pF=p3[1], pO=p3[2])
            s2, h2 = rnn_ops._lstm_cell_pre(
                pr, zx, rnn_ops.LSTMState(c, h))
            return jnp.sum(s2.c ** 2) + jnp.sum(h2 ** 2)

        gf = jax.grad(lf, argnums=(0, 1, 2, 3, 4))(
            zx, st.h, st.c, params["RW"], p3)
        gr = jax.grad(lr, argnums=(0, 1, 2, 3, 4))(
            zx, st.h, st.c, params["RW"], p3)
        for a, r in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("masked", [False, True])
    def test_scan_fused_vs_dense_parity(self, masked, monkeypatch):
        """lstm_scan with the lstm tier forced fused vs forced dense:
        full-sequence outputs, final state AND parameter gradients agree
        at <= 1e-5 (mask variants included)."""
        from deeplearning4j_tpu.ops import recurrent as rnn_ops
        N, T, nin, H = 3, 7, 8, 16
        rng, params, _ = _lstm_fixture(N=N, H=H, nin=nin, seed=9)
        x = jnp.asarray(rng.normal(size=(N, T, nin)), jnp.float32)
        mask = None
        if masked:
            m = np.ones((N, T), np.float32)
            m[0, 4:] = 0.0
            m[2, 2:] = 0.0
            mask = jnp.asarray(m)

        def run(forced):
            monkeypatch.setenv("DL4J_PALLAS_LSTM", forced)
            hs, fin = rnn_ops.lstm_scan(params, x, None, mask)
            return hs, fin

        def grads(forced):
            monkeypatch.setenv("DL4J_PALLAS_LSTM", forced)

            def loss(p):
                hs, _ = rnn_ops.lstm_scan(p, x, None, mask)
                return jnp.sum(hs ** 2)
            return jax.grad(loss)(params)

        hs_f, fin_f = run("1")
        hs_d, fin_d = run("0")
        np.testing.assert_allclose(np.asarray(hs_f), np.asarray(hs_d),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(fin_f.c), np.asarray(fin_d.c),
                                   rtol=1e-5, atol=1e-5)
        gf, gd = grads("1"), grads("0")
        for k in gf:
            np.testing.assert_allclose(np.asarray(gf[k]), np.asarray(gd[k]),
                                       rtol=1e-5, atol=1e-5, err_msg=k)

    def test_supported_predicate_edges(self):
        assert pk.lstm_fused_supported(8, 64, jnp.float32)
        assert not pk.lstm_fused_supported(8, 63, jnp.float32)   # ragged H
        assert not pk.lstm_fused_supported(8, 4, jnp.float32)    # tiny H
        assert not pk.lstm_fused_supported(8, 64, jnp.float64)   # gradcheck
        assert not pk.lstm_fused_supported(100000, 1024, jnp.float32)  # VMEM


# ===========================================================================
# In-kernel threshold dropout
# ===========================================================================

class TestThresholdDropout:
    def test_bit_exact_vs_xla_reference(self):
        """The kernel and the dense XLA reference share the counter-hash
        math — outputs are BIT-identical, over shapes that exercise the
        row padding."""
        rng = np.random.default_rng(3)
        key = jax.random.PRNGKey(17)
        for shape, rate in (((64, 130), 0.8), ((7, 33, 21), 0.5),
                            ((5000,), 0.3), ((2, 3, 8, 9), 0.9)):
            x = jnp.asarray(rng.normal(size=shape), jnp.float32)
            fused = pk.fused_threshold_dropout(x, rate, key)
            ref = pk.threshold_dropout_reference(x, rate, key)
            assert fused.shape == x.shape
            assert bool(jnp.all(fused == ref)), (shape, rate)

    def test_grad_parity(self):
        rng = np.random.default_rng(4)
        key = jax.random.PRNGKey(5)
        x = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)

        def lf(x):
            return jnp.sum(pk.fused_threshold_dropout(x, 0.7, key) ** 2)

        def lr(x):
            return jnp.sum(pk.threshold_dropout_reference(x, 0.7, key) ** 2)

        gf = jax.grad(lf)(x)
        gr = jax.grad(lr)(x)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=1e-5, atol=1e-5)
        # the gradient is the same masked scaling: zero exactly where the
        # forward dropped, (2x/rate)/rate elsewhere
        out = pk.fused_threshold_dropout(x, 0.7, key)
        assert bool(jnp.all((np.asarray(out) == 0) == (np.asarray(gf) == 0)))

    def test_keep_rate_and_scaling(self):
        rng = np.random.default_rng(6)
        x = jnp.asarray(np.abs(rng.normal(size=(512, 128))) + 1.0,
                        jnp.float32)
        for rate in (0.3, 0.5, 0.8):
            out = pk.fused_threshold_dropout(x, rate, jax.random.PRNGKey(1))
            frac = float(jnp.mean(out != 0))
            assert abs(frac - rate) < 0.01, (rate, frac)
            kept = np.asarray(out)[np.asarray(out) != 0]
            orig = np.asarray(x)[np.asarray(out) != 0]
            np.testing.assert_allclose(kept, orig / rate, rtol=1e-6)

    def test_seed_sensitivity_and_determinism(self):
        x = jnp.ones((256, 128), jnp.float32)
        a = pk.fused_threshold_dropout(x, 0.5, jax.random.PRNGKey(1))
        b = pk.fused_threshold_dropout(x, 0.5, jax.random.PRNGKey(1))
        c = pk.fused_threshold_dropout(x, 0.5, jax.random.PRNGKey(2))
        assert bool(jnp.all(a == b))          # same key -> same mask
        assert not bool(jnp.all(a == c))      # different key -> different

    def test_no_mask_tensor_saved_for_backward(self):
        """The O(HBM) point of the kernel: the vjp residual is the SEED,
        not a mask — no x-shaped saved intermediate beyond x itself ever
        flows fwd->bwd.  Proxy check: grad works under jit and the
        backward recomputes (same kernel applied to the cotangent)."""
        key = jax.random.PRNGKey(9)
        x = jnp.ones((128, 128), jnp.float32)
        grad_fn = jax.jit(jax.grad(
            lambda x: jnp.sum(pk.fused_threshold_dropout(x, 0.5, key))))
        g = grad_fn(x)
        ref = pk.threshold_dropout_reference(jnp.ones_like(x), 0.5, key)
        np.testing.assert_allclose(np.asarray(g), np.asarray(ref))

    def test_supported_predicate(self):
        assert pk.dropout_fused_supported((64, 128), jnp.float32)
        assert not pk.dropout_fused_supported((4, 4), jnp.float32)  # tiny
        assert not pk.dropout_fused_supported((64, 128), jnp.int32)
