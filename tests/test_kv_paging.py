"""Paged KV arena (ISSUE 16): paged-vs-dense decode parity (chunks,
masks, ring wraparound), capacity-by-tokens-resident admission
(exhaustion sheds retryably, frees unblock), close/TTL returning blocks,
bf16 page storage at bounded parity, migration interop in every
direction (paged→paged, paged→dense, dense→paged, plus the v1 JSON
wire), speculative greedy parity on a paged pool, the `watch_kv_arena`
probe's teeth, and the `kv_paging` model-checker scenario at ≥500
interleavings."""

import json

import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.network import NeuralNetConfiguration
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.resilience.errors import OverloadedError
from deeplearning4j_tpu.server.decode import DecodePool
from deeplearning4j_tpu.server.speculative import (NGramDraft,
                                                   SpeculativeDecoder,
                                                   one_hot)

F, H, V = 5, 12, 6
W = 8          # cache window — small so wraparound is cheap to reach
BS = 4         # arena block size: 2 blocks per full window


def _attn_mln(seed=7, window=W, n_in=F, n_out=4):
    conf = (NeuralNetConfiguration.builder().seed(seed).learning_rate(0.05)
            .shape_bucketing(True)
            .list()
            .layer(L.SelfAttentionLayer(n_in=n_in, n_out=H, n_heads=3,
                                        causal=True, cache_window=window))
            .layer(L.RnnOutputLayer(n_in=H, n_out=n_out,
                                    activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _seq(b, t, seed=3):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(b, t, F)).astype(np.float32)


def _paged(net, name, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_wait_ms", 0.5)
    return DecodePool(net, name=name, kv_paged=True, kv_block=BS, **kw)


# ---------------------------------------------------------------------------
# Parity: block tables + shared arena ≡ per-slot rings
# ---------------------------------------------------------------------------
def test_paged_decode_parity_vs_dense_incl_wraparound():
    net = _attn_mln()
    x = _seq(1, 14, seed=11)       # 14 tokens through window 8: wraps
    chunks = [3, 1, 4, 1, 5]
    dense = DecodePool(net, name="pp-d", max_slots=4, max_wait_ms=0.5)
    paged = _paged(net, "pp-p")
    try:
        a, b = dense.open_session(), paged.open_session()
        t = 0
        for n in chunks:
            (ref,) = dense.step(a, x[0, t:t + n])
            (got,) = paged.step(b, x[0, t:t + n])
            np.testing.assert_allclose(got, ref, atol=1e-6, rtol=1e-6)
            t += n
        st = paged.stats()["kv_arena"]
        assert st["block_size"] == BS
        assert st["tokens_resident"] == W     # capped at w_eff
    finally:
        dense.stop()
        paged.stop()


def test_paged_blocks_free_on_close():
    net = _attn_mln()
    x = _seq(1, 9, seed=5)
    pool = _paged(net, "pp-free", max_slots=3)
    try:
        a, b = pool.open_session(), pool.open_session()
        for t in range(5):
            pool.step(a, x[0, t:t + 1])
        for t in range(9):
            pool.step(b, x[0, t:t + 1])
        st = pool.stats()["kv_arena"]
        # a holds ceil(5/4)=2 blocks, b wrapped: ceil(8/4)=2
        assert st["blocks"] - st["blocks_free"] == 4
        assert st["tokens_resident"] == 5 + W
        pool.close_session(a)
        pool.close_session(b)
        st = pool.stats()["kv_arena"]
        assert st["blocks_free"] == st["blocks"]
        assert st["tokens_resident"] == 0
    finally:
        pool.stop()


def test_arena_exhaustion_sheds_retryably_and_close_unblocks():
    net = _attn_mln()
    x = _seq(1, 8, seed=9)
    # the arena is exactly ONE window: the second session cannot grow
    pool = _paged(net, "pp-shed", max_slots=3, kv_arena_tokens=W)
    try:
        a = pool.open_session()
        for t in range(8):
            pool.step(a, x[0, t:t + 1])
        assert pool.stats()["kv_arena"]["blocks_free"] == 0
        b = pool.open_session()          # slots are free, blocks aren't
        with pytest.raises(OverloadedError) as ei:
            pool.step(b, x[0, 0:1])
        assert ei.value.retry_after_s > 0
        # the shed is backpressure, not session death: freeing blocks
        # lets the SAME session proceed
        pool.close_session(a)
        (out,) = pool.step(b, x[0, 0:1])
        assert np.all(np.isfinite(np.asarray(out)))
    finally:
        pool.stop()


def test_paged_arena_admits_twice_the_dense_sessions_at_equal_kv_tokens():
    """Capacity by tokens resident: a mixed load (every fourth stream
    fills the window, the rest hold an eighth of it) is pushed into the
    dense pool and into an arena of the same KV tokens until each sheds.
    The dense pool stops at its slots; the arena at its tokens."""
    window, short, chunk, slots = 32, 4, 4, 4
    net = _attn_mln(seed=13, window=window)
    x = _seq(1, window, seed=17)

    def admit_mixed(pool):
        """Sessions whose whole stream landed before the first shed."""
        admitted = 0
        for i in range(64):
            try:
                sid = pool.open_session()
                for c0 in range(0, window if i % 4 == 0 else short, chunk):
                    pool.step(sid, x[0, c0:c0 + chunk])
            except OverloadedError:
                break
            admitted += 1
        return admitted

    dense = DecodePool(net, name="pp-adm-d", max_slots=slots,
                       max_wait_ms=0.5)
    paged = _paged(net, "pp-adm-p", max_slots=24,
                   kv_arena_tokens=slots * window)
    try:
        n_dense, n_paged = admit_mixed(dense), admit_mixed(paged)
        st = paged.stats()["kv_arena"]
    finally:
        dense.stop()
        paged.stop()
    assert n_dense == slots
    # 3 long + 8 short streams are 3*32 + 8*4 = 128 tokens: the arena
    assert n_paged == 11 and n_paged >= 2 * n_dense
    assert st["blocks_free"] == 0


def test_kv_dtype_bf16_bounded_parity():
    net = _attn_mln(seed=31)
    x = _seq(1, 10, seed=7)
    dense = DecodePool(net, name="bf-d", max_slots=2, max_wait_ms=0.5)
    half = _paged(net, "bf-p", kv_dtype="bfloat16")
    try:
        a, b = dense.open_session(), half.open_session()
        for t in range(10):
            (ref,) = dense.step(a, x[0, t:t + 1])
            (got,) = half.step(b, x[0, t:t + 1])
            # pages stored bf16, scores accumulated fp32: parity holds
            # to bf16 rounding, not 1e-6
            np.testing.assert_allclose(got, ref, atol=5e-2)
    finally:
        dense.stop()
        half.stop()


# ---------------------------------------------------------------------------
# Migration: paged and dense pools interoperate, both wire versions
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("src_paged,dst_paged", [(True, True),
                                                 (True, False),
                                                 (False, True)])
def test_migration_parity_vs_unmigrated_twin(src_paged, dst_paged):
    net = _attn_mln(seed=21)
    T0, T1 = 5, 6                   # resumes pre-wrap, wraps after
    x = _seq(1, T0 + T1, seed=13)

    def mk(name, paged):
        if paged:
            return _paged(net, name)
        return DecodePool(net, name=name, max_slots=4, max_wait_ms=0.5)

    src, dst = mk("mig-s", src_paged), mk("mig-d", dst_paged)
    try:
        mig, twin = src.open_session(), src.open_session()
        for t in range(T0):
            src.step(mig, x[0, t:t + 1])
            src.step(twin, x[0, t:t + 1])
        wire = json.loads(json.dumps(src.export_session(mig)))
        assert wire["version"] == 2
        # the wire is the DENSE v2 layout either way — paged pools
        # de-page on export, so mixed fleets interoperate
        assert dst.import_session(wire) == mig
        src.finish_export(mig, ok=True)
        for t in range(T0, T0 + T1):
            (a,) = dst.step(mig, x[0, t:t + 1])
            (b,) = src.step(twin, x[0, t:t + 1])
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)
        if src_paged:
            # the exported session's blocks went back to the free list
            st = src.stats()["kv_arena"]
            assert st["blocks"] - st["blocks_free"] == \
                -(-min(T0, W) // BS)
    finally:
        src.stop()
        dst.stop()


def test_paged_migration_v1_json_fallback(monkeypatch):
    net = _attn_mln(seed=23)
    x = _seq(1, 4, seed=15)
    monkeypatch.setenv("DL4J_CARRY_PAYLOAD", "json")
    src, dst = _paged(net, "v1-s"), _paged(net, "v1-d")
    try:
        sid = src.open_session()
        for t in range(4):
            src.step(sid, x[0, t:t + 1])
        payload = json.loads(json.dumps(src.export_session(sid)))
        assert payload["version"] == 1
        assert dst.import_session(payload) == sid
        src.finish_export(sid, ok=True)
        (out,) = dst.step(sid, x[0, 0:1])
        assert np.all(np.isfinite(np.asarray(out)))
    finally:
        src.stop()
        dst.stop()


def test_import_sheds_when_arena_cannot_hold_the_carry():
    net = _attn_mln(seed=25)
    x = _seq(1, 8, seed=17)
    src = _paged(net, "imp-s")
    dst = _paged(net, "imp-d", kv_arena_tokens=W)   # one window total
    try:
        filler = dst.open_session()
        for t in range(8):
            dst.step(filler, x[0, t:t + 1])         # dst arena now full
        sid = src.open_session()
        for t in range(5):
            src.step(sid, x[0, t:t + 1])
        wire = json.loads(json.dumps(src.export_session(sid)))
        with pytest.raises(OverloadedError):
            dst.import_session(wire)
        src.finish_export(sid, ok=False)            # migration aborts
        # the source session survived the failed hop
        (out,) = src.step(sid, x[0, 5:6])
        assert np.all(np.isfinite(np.asarray(out)))
        st = dst.stats()["kv_arena"]
        assert st["blocks"] - st["blocks_free"] == 2   # only filler's
    finally:
        src.stop()
        dst.stop()


# ---------------------------------------------------------------------------
# Speculative decode rides the paged carry unchanged (greedy is exact)
# ---------------------------------------------------------------------------
def test_paged_spec_greedy_byte_identical():
    net = _attn_mln(seed=5, window=32, n_in=V, n_out=V)
    N = 12
    dense = DecodePool(net, name="sp-d", max_slots=4, max_wait_ms=0.5)
    paged = _paged(net, "sp-p")
    try:
        sid = dense.open_session()
        (o,) = dense.step(sid, one_hot([1], V))
        pending = int(np.argmax(o[-1]))
        ref = []
        for _ in range(N):
            ref.append(pending)
            (o,) = dense.step(sid, one_hot([pending], V))
            pending = int(np.argmax(o[-1]))
        dense.close_session(sid)
        sid = paged.open_session()
        (o,) = paged.step(sid, one_hot([1], V))
        dec = SpeculativeDecoder(paged, vocab=V, k=3,
                                 draft=NGramDraft(order=3))
        res = dec.generate(sid, int(np.argmax(o[-1])), N)
        assert res["tokens"] == ref
        assert paged.metrics.snapshot()["spec_steps"] > 0
    finally:
        dense.stop()
        paged.stop()


# ---------------------------------------------------------------------------
# dl4j-check: the arena probe has teeth, the scenario explores clean
# ---------------------------------------------------------------------------
def test_arena_watch_flags_violations():
    from deeplearning4j_tpu.analysis.check.scenarios import (
        CheckPagedDecodePool, _StubModel)
    from deeplearning4j_tpu.analysis.check.specs import _arena_probe
    pool = CheckPagedDecodePool(_StubModel(), name="chk-arena",
                                max_slots=2, max_wait_ms=0.0,
                                arena_blocks=3)
    try:
        sid = pool.open_session()
        pool.step(sid, np.zeros((1, 1), np.float32), timeout=30)
        assert _arena_probe(pool) is None
        s = pool._sessions[sid]
        blk = s.kv_blocks[0][0]
        # a held block leaks onto the free list → double ownership next
        # allocation; the probe catches the overlap immediately
        pool._kv_free[0].append(blk)
        msg = _arena_probe(pool)
        assert msg and "both held and on" in msg
        pool._kv_free[0].pop()
        # a block freed twice
        free_blk = pool._kv_free[0][0]
        pool._kv_free[0].append(free_blk)
        msg = _arena_probe(pool)
        assert msg and "more than once" in msg
        pool._kv_free[0].pop()
        # two live sessions claiming one block
        sid2 = pool.open_session()
        pool.step(sid2, np.zeros((1, 1), np.float32), timeout=30)
        s2 = pool._sessions[sid2]
        stolen, s2.kv_blocks[0][0] = s2.kv_blocks[0][0], blk
        msg = _arena_probe(pool)
        assert msg and "owned by two live sessions" in msg
        s2.kv_blocks[0][0] = stolen
        assert _arena_probe(pool) is None
    finally:
        pool.stop()


def test_kv_paging_scenario_500_distinct_interleavings_clean():
    """The ISSUE 16 acceptance bar: ≥500 distinct interleavings of
    block allocation racing close/TTL/migration, zero violations."""
    from deeplearning4j_tpu.analysis.check import explore
    r = explore("kv_paging", schedules=500, seed=0, time_budget_s=120.0)
    assert r.violations == [], r.violations[:3]
    assert r.distinct >= 500, f"only {r.distinct} distinct schedules"
