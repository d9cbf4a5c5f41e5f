"""The fused kernel tiers, asked of the TPU's own compiler with no TPU.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
block shapes that do not tile, slices off the tiling, too much VMEM.
libtpu is installed here and compiles for a chip that is described, not
attached, so each tier's forward+backward is lowered and compiled for a
``v5e:2x2`` topology at its self-test shape and at one real width, and
the compiled HLO must carry the kernel (``tpu_custom_call``).  Nothing
runs: these say "the chip's compiler accepts it", never "it is right" or
"it is fast".

The topology, the sharding and the shapes are built inside fixtures of
THIS file only (one process may hold libtpu; under pytest-xdist that is
the worker this file lands on), and the non-interpret branch is steered
from the test with ``DL4J_TPU=1``."""

import re

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
        mp.undo()


@pytest.fixture
def compile_for_chip(topo, monkeypatch):
    """compile(fn, (shape, dtype), ...) → HLO text of ``fn`` compiled
    for one described v5e chip, on the branches ``is_tpu()`` selects."""
    from jax.sharding import SingleDeviceSharding
    from deeplearning4j_tpu.ops import helpers
    monkeypatch.setenv("DL4J_TPU", "1")
    # a tier validated on first use would run its self-test, which only a
    # chip can: compiling is all these ask
    monkeypatch.setitem(helpers._FIRST_USE, "gmm", True)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def compile_(fn, *specs):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in specs]
        return jax.jit(fn).lower(*args).compile().as_text()
    return compile_


def _sq(y):
    return jnp.sum(y.astype(jnp.float32) ** 2)


@pytest.mark.parametrize("n,h", [(4, 16), (32, 200)],
                         ids=["selftest", "charrnn-32x200"])
def test_lstm_step_compiles(compile_for_chip, n, h):
    assert pk.lstm_fused_supported(n, h, jnp.float32)

    def step(zx, hh, c, rw, p3):
        def loss(zx, hh, c, rw, p3):
            c_new, h_new = pk.fused_lstm_step(zx, hh, c, rw, p3)
            return _sq(c_new) + _sq(h_new)
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(
            zx, hh, c, rw, p3)
    f32 = jnp.float32
    hlo = compile_for_chip(step, ((n, 4 * h), f32), ((n, h), f32),
                           ((n, h), f32), ((h, 4 * h), f32), ((3, h), f32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n,v,dtype", [
    (256, 512, jnp.float32),
    (4096, 50304, jnp.bfloat16),
], ids=["selftest", "lm-head-4096x50304-bf16"])
def test_softmax_xent_compiles(compile_for_chip, n, v, dtype):
    def step(logits, labels):
        return jax.value_and_grad(
            lambda lg: pk.softmax_xent_rows(lg, labels).mean())(logits)
    hlo = compile_for_chip(step, ((n, v), dtype), ((n, v), dtype))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n,v,dtype", [
    (256, 512, jnp.float32),
    (8192, 16384, jnp.float32),
], ids=["selftest", "lm-head-8192x16384-f32"])
def test_softmax_xent_on_class_ids_compiles(compile_for_chip, n, v, dtype):
    def step(logits, ids):
        return jax.value_and_grad(
            lambda lg: pk.softmax_xent_rows(lg, ids).mean())(logits)
    hlo = compile_for_chip(step, ((n, v), dtype), ((n,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_grouped_expert_products_compile_to_a_kernel(compile_for_chip):
    """The expert layer's grouped products (``jax.lax.ragged_dot``, 8
    held experts of 2048 x 1792 over 8192 x 4 sorted rows, forward and
    both gradients): the chip's compiler takes each as its own grouped
    matmul kernel and not as one dense product a group."""
    def step(rows, w, sizes):
        return jax.value_and_grad(
            lambda r, w: _sq(jax.lax.ragged_dot(r, w, sizes)),
            argnums=(0, 1))(rows, w)
    bf16 = jnp.bfloat16
    hlo = compile_for_chip(step, ((32768, 2048), bf16),
                           ((8, 2048, 1792), bf16), ((8,), jnp.int32))
    assert hlo.count('custom_call_target="tpu_custom_call"') >= 3
    assert "ragged-dot" in hlo


@pytest.mark.parametrize("experts,held,k,hidden,scoring,recompute", [
    (32, 8, 4, 1792, "sigmoid", False),
    (128, 16, 8, 768, "softmax", True),
], ids=["lfm2-8-of-32-top4", "sdar-16-of-128-top8-recomputed"])
def test_expert_layer_compiles_with_its_segments_skipped_on_the_chip(
        compile_for_chip, experts, held, k, hidden, scoring, recompute):
    """The expert layer of the two expert cells, forward and backward
    (8,192 tokens of 2,048; 8 of 32 experts held, top-4: 2 segments of
    16,384 sorted rows; 16 of 128, top-8: 4 of 16,384): in the program
    the chip's compiler makes of it **no array is longer than a segment
    and as wide as the model or an expert** (the sorted buffer of tokens
    x k rows exists nowhere), the later segments are loops whose trip
    count the chip reads, no branch, and the grouped products the gmm
    tier's kernels, the stacks read as the step holds them (float32
    masters here, cast a block at a time in the kernels)."""
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.ops import row_segments
    layer = L.MixtureOfExpertsLayer(
        n_out=2048, n_experts=experts, hidden=hidden, top_k=k,
        scoring=scoring, expert_bias=True, gated=True, residual=False,
        activation="identity", experts_held=tuple(range(held)),
        recompute=recompute)
    seg, n_seg = layer.segment_shape(8192 * k)
    assert (seg, n_seg) == (16384, 8192 * k // 16384)

    def step(x, wg, w1, w2, w3, bias):
        def loss(p, x):
            state = {"expert_bias": bias,
                     "moe_expert_counts": jnp.zeros((experts,), jnp.int32)}
            y, st, _ = layer.forward(p, state, x, train=True, rng=None)
            return _sq(y), st["moe_expert_counts"]
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            {"Wg": wg, "W1": w1, "W2": w2, "W3": w3}, x)
    bf16, f32 = jnp.bfloat16, jnp.float32
    hlo = compile_for_chip(
        step, ((2, 4096, 2048), bf16), ((2048, experts), bf16),
        ((held, 2048, hidden), f32), ((held, hidden, 2048), f32),
        ((held, 2048, hidden), f32), ((experts,), bf16))
    assert row_segments.tall_arrays(
        hlo, seg, (2048, hidden), stacks=[(held, 2048, hidden)]) == []
    # the later segments, forward and backward
    assert len(re.findall(r"\s(while)\(", hlo)) >= 2
    assert " conditional(" not in hlo
    # three products forward and three to the rows, three to the weights
    assert len(set(re.findall(r"%(dl4j_gmm[\w.\-]*) = ", hlo))) >= 6
    assert len(set(re.findall(r"%(dl4j_tgmm[\w.\-]*) = ", hlo))) >= 3
    assert "ragged-dot" not in hlo
    # no pass casts a stack whole: the kernels cast a block at a time
    assert not re.search(
        rf"= bf16\[{held},(2048,{hidden}|{hidden},2048)\]\S* convert\(", hlo)


@pytest.mark.parametrize("shape", [(64, 128), (128, 4096)],
                         ids=["selftest", "fc-128x4096"])
def test_threshold_dropout_compiles(compile_for_chip, shape):
    assert pk.dropout_fused_supported(shape, jnp.float32)
    key = jax.random.PRNGKey(7)

    def step(x):
        return jax.value_and_grad(
            lambda x: _sq(pk.fused_threshold_dropout(x, 0.8, key)))(x)
    hlo = compile_for_chip(step, (shape, jnp.float32))
    # forward and backward are the same kernel, launched twice
    assert hlo.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("shape,causal", [
    ((1, 2, 256, 64), True),      # self-test shape
    ((2, 4, 512, 64), True),
    ((2, 4, 512, 64), False),
    ((2, 4, 200, 64), True),      # ragged T: padded to 256 inside
    ((1, 2, 4096, 64), True),     # the tiles of a long sequence
    ((1, 2, 640, 96), True),      # five tiles of 128, a head of 96
    ((1, 2, 4096, 128), True),    # a head of 128 as the looped decoder
                                  # has it: no pad, as many k/v heads
], ids=["selftest", "T512-causal", "T512-full", "ragged-T200",
        "T4096-causal", "T640-D96", "T4096-D128"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_flash_attention_compiles(compile_for_chip, shape, causal, dtype):
    B, H, T, D = shape
    assert pk.flash_attention_supported(jax.ShapeDtypeStruct(shape, dtype))

    def step(q, k, v, km):
        return jax.value_and_grad(
            lambda q, k, v: _sq(pk.flash_attention(q, k, v, km,
                                                   causal=causal)),
            argnums=(0, 1, 2))(q, k, v)
    hlo = compile_for_chip(step, (shape, dtype), (shape, dtype),
                           (shape, dtype), ((B, T), jnp.float32))
    # the forward kernel and the backward's one
    assert hlo.count("tpu_custom_call") == 2


def test_flash_attention_compiles_at_the_language_model_cell_shape(
        compile_for_chip):
    """The attention core of ``lfm2_8b_a1b.fit_seq4k_b2`` as the layer
    hands it over: bfloat16, 32 heads of 64 (K and V repeated from 8 by
    the layer), 2 sequences of 4,096, causal, forward and the three
    gradients, through ``flash_attention``'s pad of the head to 128
    lanes and, the kernels alone, at the head's own 64."""
    shape, bf16 = (2, 32, 4096, 64), jnp.bfloat16

    def step(q, k, v, km):
        return jax.value_and_grad(
            lambda q, k, v: _sq(pk.flash_attention(q, k, v, km,
                                                   causal=True)),
            argnums=(0, 1, 2))(q, k, v)
    def core_step(q, k, v, km):
        return jax.value_and_grad(
            lambda q, k, v: _sq(pk._flash_core(q, k, v, km, True, 0.125)),
            argnums=(0, 1, 2))(q, k, v)
    for fn in (step, core_step):
        hlo = compile_for_chip(fn, (shape, bf16), (shape, bf16),
                               (shape, bf16), ((2, 4096), jnp.float32))
        assert hlo.count("tpu_custom_call") == 2
        for name in ("dl4j_flash_fwd", "dl4j_flash_bwd"):
            assert name in hlo


@pytest.mark.parametrize("shape,rule", [
    ((1, 32, 8192, 128), ("block_diffusion", 4096, 4)),   # the cell's core
    ((1, 4, 768, 128), ("block_diffusion", 384, 3)),      # b no power of two
    ((1, 4, 1024, 64), ("block_diffusion", 512, 512)),    # a block a tile
], ids=["cell-L4096-b4", "L384-b3", "L512-b512"])
def test_flash_attention_compiles_under_the_block_diffusion_rule(
        compile_for_chip, shape, rule):
    """The two kernels under a rule whose tile visits the device
    derives from ``program_id``: ranges with computed bounds, a boundary
    tile run 0 or 1 times, the block distance by shift or division."""
    B, H, T, D = shape
    bf16 = jnp.bfloat16

    def step(q, k, v, km):
        return jax.value_and_grad(
            lambda q, k, v: _sq(pk.flash_attention(q, k, v, km, causal=rule)),
            argnums=(0, 1, 2))(q, k, v)
    hlo = compile_for_chip(step, (shape, bf16), (shape, bf16), (shape, bf16),
                           ((B, T), jnp.float32))
    assert hlo.count("tpu_custom_call") == 2
    for name in ("dl4j_flash_fwd", "dl4j_flash_bwd"):
        assert name in hlo


@pytest.mark.parametrize("shape,window", [
    ((1, 32, 8192, 128), 2048),     # the cell's windowed core
    ((1, 4, 1024, 64), 256),
    ((1, 4, 1024, 128), 4096),      # a window past the end: the causal tiles
], ids=["cell-T8192-W2048", "T1024-W256", "T1024-W4096"])
def test_flash_attention_compiles_under_the_window(compile_for_chip, shape,
                                                   window):
    """The two kernels under a sliding window: a boundary tile run 0 or 1
    times from ``program_id``, a range of whole tiles with computed
    bounds, the diagonal tile."""
    B, H, T, D = shape
    bf16 = jnp.bfloat16

    def step(q, k, v, km):
        return jax.value_and_grad(
            lambda q, k, v: _sq(pk.flash_attention(
                q, k, v, km, causal=("sliding_window", window))),
            argnums=(0, 1, 2))(q, k, v)
    hlo = compile_for_chip(step, (shape, bf16), (shape, bf16), (shape, bf16),
                           ((B, T), jnp.float32))
    assert hlo.count("tpu_custom_call") == 2
    for name in ("dl4j_flash_fwd", "dl4j_flash_bwd"):
        assert name in hlo


@pytest.mark.parametrize("shape,rule", [
    ((2, 32, 4096, 64), True),
    ((1, 16, 4096, 128), True),
    ((1, 32, 8192, 128), ("block_diffusion", 4096, 4)),
    ((1, 32, 8192, 128), ("sliding_window", 2048)),
], ids=["lfm2_8b_a1b.fit_seq4k_b2", "ouro_2_6b.fit_seq4k_b1",
        "sdar_30b_a3b.fit_bd4_seq4k_b1", "trinity_mini.fit_seq8k_b1"])
def test_the_fused_backward_compiles_inside_its_vmem_limit(
        compile_for_chip, shape, rule):
    """``dl4j_flash_bwd`` at the three language cells' cores, bfloat16:
    one kernel behind the forward's, and what the chip's compiler
    reserves of VMEM for it (the whole-sequence q and dO, the head's dq
    block and the float32 accumulator under it, the tiles' temporaries)
    lies inside the limit the kernel asks for."""
    B, H, T, D = shape
    bf16 = jnp.bfloat16

    def step(q, k, v, km):
        return jax.value_and_grad(
            lambda q, k, v: _sq(pk.flash_attention(q, k, v, km, causal=rule)),
            argnums=(0, 1, 2))(q, k, v)
    hlo = compile_for_chip(step, (shape, bf16), (shape, bf16), (shape, bf16),
                           ((B, T), jnp.float32))
    calls = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 2
    (bwd,) = [line for line in calls if "dl4j_flash_bwd" in line]
    asked = int(re.search(
        r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', bwd).group(1))
    used = int(re.search(
        r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', bwd).group(1))
    assert asked == pk._FLASH_VMEM_LIMIT
    # at least the accumulator and the q, dO and dq blocks it sits beside
    lanes = -(-D // pk.LANE) * pk.LANE
    assert T * lanes * (4 + 3 * 2) <= used <= asked
