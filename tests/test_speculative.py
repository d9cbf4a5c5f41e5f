"""KV-cache + speculative decode subsystem (ISSUE 13): ring-cached
attention parity against full ``dense_attention`` (masks, bucketed
chunks, ring wraparound), exact speculative greedy parity across every
acceptance length, KV-cached session migration parity against an
unmigrated twin, slot-reuse isolation, per-layout ``DecodeManager``
pools, binary carry payloads, and the gateway ``spec=``/``draft=``
knobs."""

import json
import os
import tempfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.graph_conf import GraphBuilder
from deeplearning4j_tpu.nn.conf.network import (GlobalConf,
                                                NeuralNetConfiguration)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.serialization import write_model
from deeplearning4j_tpu.parallel import sequence as seq_ops
from deeplearning4j_tpu.server.decode import (DecodeManager, DecodePool,
                                              _decode_carry_leaf)
from deeplearning4j_tpu.server.model_cache import ModelCache
from deeplearning4j_tpu.server.speculative import (ModelDraft, NGramDraft,
                                                   ScriptedDraft,
                                                   SpeculativeDecoder,
                                                   one_hot)

F, H, C = 5, 12, 4


def _attn_mln(seed=7, window=64, n_in=F, n_out=C, causal=True):
    conf = (NeuralNetConfiguration.builder().seed(seed).learning_rate(0.05)
            .shape_bucketing(True)
            .list()
            .layer(L.SelfAttentionLayer(n_in=n_in, n_out=H, n_heads=3,
                                        causal=causal, cache_window=window))
            .layer(L.RnnOutputLayer(n_in=H, n_out=n_out,
                                    activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _mixed_mln(seed=11, window=64):
    conf = (NeuralNetConfiguration.builder().seed(seed).learning_rate(0.05)
            .shape_bucketing(True)
            .list()
            .layer(L.GravesLSTM(n_in=F, n_out=H, activation="tanh"))
            .layer(L.SelfAttentionLayer(n_in=H, n_out=H, n_heads=2,
                                        causal=True, cache_window=window))
            .layer(L.RnnOutputLayer(n_in=H, n_out=C, activation="softmax",
                                    loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _seq(n, t, f=F, seed=0):
    return np.random.default_rng(seed).normal(
        size=(n, t, f)).astype(np.float32)


# ---------------------------------------------------------------------------
# attend_cached core: parity with dense attention, wraparound, chunking
# ---------------------------------------------------------------------------
def test_attend_cached_matches_dense_causal():
    B, Hh, T, D = 2, 3, 10, 4
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(B, Hh, T, D)),
                           jnp.float32) for _ in range(3))
    dense = np.asarray(seq_ops.dense_attention(q, k, v, causal=True,
                                               allow_flash=False))
    ring = seq_ops.kv_ring_init(B, Hh, 16, D)
    outs = []
    for t in range(T):
        o, ring = seq_ops.attend_cached(q[:, :, t:t + 1], k[:, :, t:t + 1],
                                        v[:, :, t:t + 1], ring)
        outs.append(np.asarray(o))
    got = np.concatenate(outs, axis=2)
    np.testing.assert_allclose(got, dense, atol=1e-5, rtol=1e-4)


def test_attend_cached_chunked_equals_token_by_token():
    B, Hh, T, D, W = 1, 2, 12, 4, 8
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.normal(size=(B, Hh, T, D)),
                           jnp.float32) for _ in range(3))
    ring1 = seq_ops.kv_ring_init(B, Hh, W, D)
    tok = []
    for t in range(T):
        o, ring1 = seq_ops.attend_cached(
            q[:, :, t:t + 1], k[:, :, t:t + 1], v[:, :, t:t + 1], ring1)
        tok.append(np.asarray(o))
    tok = np.concatenate(tok, axis=2)
    ring2 = seq_ops.kv_ring_init(B, Hh, W, D)
    chunks = []
    for a, b in ((0, 5), (5, 6), (6, 12)):
        o, ring2 = seq_ops.attend_cached(q[:, :, a:b], k[:, :, a:b],
                                         v[:, :, a:b], ring2)
        chunks.append(np.asarray(o))
    chunked = np.concatenate(chunks, axis=2)
    np.testing.assert_allclose(chunked, tok, atol=1e-6, rtol=1e-6)
    assert int(np.asarray(ring2["pos"])[0]) == T


def test_attend_cached_wraparound_is_sliding_window():
    """With W < T the ring attends exactly the last W tokens — the
    manual windowed-softmax reference, position by position."""
    B, Hh, T, D, W = 1, 2, 11, 4, 4
    rng = np.random.default_rng(7)
    qs = rng.normal(size=(B, Hh, T, D)).astype(np.float32)
    ks = rng.normal(size=(B, Hh, T, D)).astype(np.float32)
    vs = rng.normal(size=(B, Hh, T, D)).astype(np.float32)
    ring = seq_ops.kv_ring_init(B, Hh, W, D)
    scale = 1.0 / (D ** 0.5)
    for t in range(T):
        o, ring = seq_ops.attend_cached(
            jnp.asarray(qs[:, :, t:t + 1]), jnp.asarray(ks[:, :, t:t + 1]),
            jnp.asarray(vs[:, :, t:t + 1]), ring)
        lo = max(0, t - W + 1)
        kk, vv = ks[:, :, lo:t + 1], vs[:, :, lo:t + 1]
        scores = np.einsum("bhd,bhkd->bhk", qs[:, :, t], kk) * scale
        p = np.exp(scores - scores.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = np.einsum("bhk,bhkd->bhd", p, vv)
        np.testing.assert_allclose(np.asarray(o)[:, :, 0], ref,
                                   atol=1e-5, rtol=1e-4)


def test_attend_cached_masked_tokens_write_nothing():
    B, Hh, D, W = 1, 2, 4, 8
    rng = np.random.default_rng(9)
    q, k, v = (jnp.asarray(rng.normal(size=(B, Hh, 3, D)), jnp.float32)
               for _ in range(3))
    ring = seq_ops.kv_ring_init(B, Hh, W, D)
    _, ring = seq_ops.attend_cached(q, k, v, ring)
    frozen = jax.tree_util.tree_map(np.asarray, ring)
    # a fully-masked pad chunk carries the ring through unchanged
    _, ring2 = seq_ops.attend_cached(q, k, v, ring,
                                     key_mask=jnp.zeros((B, 3)))
    for a, b in zip(jax.tree_util.tree_leaves(frozen),
                    jax.tree_util.tree_leaves(ring2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Cached attention through the engines: pool/time-step parity
# ---------------------------------------------------------------------------
def test_attention_decode_parity_chunks_and_masks():
    """Ragged prefill chunks (time-bucket padded) under a real per-step
    mask: every UNMASKED position matches the full-sequence output (the
    masked tail carries the ring through unchanged — masked positions
    are unspecified, matching the decode suite's convention)."""
    net = _attn_mln()
    T = 9
    x = _seq(2, T, seed=1)
    mask = np.ones((2, T), np.float32)
    mask[1, 6:] = 0.0
    full = np.asarray(net.output(x, mask=mask))
    pool = DecodePool(net, max_slots=4, max_wait_ms=0.5)
    try:
        sids = [pool.open_session() for _ in range(2)]
        got = {0: [], 1: []}
        # ragged chunks exercise the time-bucket pad path (5 -> pow2)
        for a, b in ((0, 3), (3, 4), (4, 9)):
            for i, sid in enumerate(sids):
                (o,) = pool.step(sid, x[i, a:b], masks=mask[i, a:b])
                got[i].append(o)
        g0 = np.concatenate(got[0], axis=0)
        np.testing.assert_allclose(g0, full[0], atol=1e-5, rtol=1e-4)
        g1 = np.concatenate(got[1], axis=0)
        np.testing.assert_allclose(g1[:6], full[1, :6], atol=1e-5,
                                   rtol=1e-4)
    finally:
        pool.stop()


def test_attention_decode_wraparound_parity_vs_truncated_output():
    """Past the window, cached decode == full output() over the last W
    tokens (causal attention of the final position attends exactly the
    window) — the independent wraparound reference."""
    W = 8
    net = _attn_mln(window=W)
    T = 14
    x = _seq(1, T, seed=3)
    pool = DecodePool(net, max_slots=2, max_wait_ms=0.5)
    try:
        sid = pool.open_session()
        outs = [pool.step(sid, x[0, t:t + 1])[0] for t in range(T)]
        for t in range(W - 1, T):
            ref = np.asarray(net.output(x[:, t - W + 1:t + 1]))[0, -1]
            np.testing.assert_allclose(outs[t][0], ref,
                                       atol=1e-5, rtol=1e-4)
    finally:
        pool.stop()


def test_mixed_lstm_attention_carry_template_and_parity():
    net = _mixed_mln()
    tmpl = net.rnn_carry_template(3, feature_tail=(1, F))
    leaves = jax.tree_util.tree_leaves(tmpl)
    # KV ring leaves (k/v [n, H, W, Dh] + pos [n]) joined the LSTM carry
    assert any(getattr(a, "ndim", 0) == 4 for a in leaves)
    assert any(a.dtype == jnp.int32 for a in leaves)
    T = 7
    x = _seq(1, T, seed=5)
    full = np.asarray(net.output(x))
    pool = DecodePool(net, max_slots=2, max_wait_ms=0.5)
    try:
        sid = pool.open_session()
        outs = [pool.step(sid, x[0, t:t + 1])[0] for t in range(T)]
        got = np.concatenate(outs, axis=0)
        np.testing.assert_allclose(got, full[0], atol=1e-5, rtol=1e-4)
    finally:
        pool.stop()


def test_cg_attention_decode_parity():
    g = GlobalConf(seed=9, learning_rate=0.05, weight_init="xavier",
                   shape_bucketing=True)
    b = (GraphBuilder(g)
         .add_inputs("in")
         .add_layer("attn", L.SelfAttentionLayer(
             n_in=F, n_out=H, n_heads=2, causal=True, cache_window=32),
             "in")
         .add_layer("out", L.RnnOutputLayer(n_in=H, n_out=C,
                                            activation="softmax",
                                            loss="mcxent"), "attn")
         .set_outputs("out"))
    net = ComputationGraph(b.build()).init()
    T = 6
    x = _seq(1, T, seed=7)
    (full,) = net.output(x)
    full = np.asarray(full)
    pool = DecodePool(net, max_slots=2, max_wait_ms=0.5)
    try:
        sid = pool.open_session()
        outs = [pool.step(sid, x[0, t:t + 1])[0] for t in range(T)]
        got = np.concatenate(outs, axis=0)
        np.testing.assert_allclose(got, full[0], atol=1e-5, rtol=1e-4)
    finally:
        pool.stop()


def test_slot_reuse_never_sees_stale_ring():
    net = _attn_mln()
    x = _seq(1, 4, seed=11)
    fresh_pool = DecodePool(net, max_slots=1, max_wait_ms=0.5)
    try:
        sid = fresh_pool.open_session()
        (ref,) = fresh_pool.step(sid, x[0, 0:1])
        fresh_pool.close_session(sid)
    finally:
        fresh_pool.stop()
    pool = DecodePool(net, max_slots=1, max_wait_ms=0.5)
    try:
        a = pool.open_session()
        for t in range(4):
            pool.step(a, x[0, t:t + 1])
        pool.close_session(a)
        b = pool.open_session()   # same slot, ring must be zeroed
        (got,) = pool.step(b, x[0, 0:1])
        np.testing.assert_array_equal(got, ref)
    finally:
        pool.stop()


# ---------------------------------------------------------------------------
# Speculative greedy decode: exact parity, every acceptance length
# ---------------------------------------------------------------------------
V = 6


def _vocab_mln(seed=5, window=64):
    return _attn_mln(seed=seed, window=window, n_in=V, n_out=V)


def _greedy_ref(pool, prompt_toks, n):
    sid = pool.open_session()
    (o,) = pool.step(sid, one_hot(prompt_toks, V))
    pending = int(np.argmax(o[-1]))
    ref = []
    for _ in range(n):
        ref.append(pending)
        (o,) = pool.step(sid, one_hot([pending], V))
        pending = int(np.argmax(o[-1]))
    pool.close_session(sid)
    return ref


def test_spec_accept_lengths_0_to_k_exact():
    net = _vocab_mln()
    prompt = [0, 3, 1]
    K, N = 3, 10
    pool = DecodePool(net, max_slots=4, max_wait_ms=0.5)
    try:
        ref = _greedy_ref(pool, prompt, N + K + 1)
        for a in range(K + 1):   # a = accepted DRAFT tokens per verify
            sid = pool.open_session()
            (o,) = pool.step(sid, one_hot(prompt, V))
            pending = int(np.argmax(o[-1]))
            assert pending == ref[0]
            # drafts: the true continuation for `a` tokens, then junk
            good = ref[1:1 + a]
            junk = [(t + 1) % V for t in ref[1 + a:1 + K]]
            chunk = [pending] + good + junk
            outs, greedy, acc = pool.spec_step(sid, one_hot(chunk, V),
                                               chunk)
            assert acc == 1 + a, (a, acc)
            assert chunk[:acc] == ref[:acc]
            # the stream continues exactly from the acceptance point
            nxt = int(greedy[acc - 1])
            assert nxt == ref[acc]
            (o,) = pool.step(sid, one_hot([nxt], V))
            assert int(np.argmax(o[-1])) == ref[acc + 1]
            pool.close_session(sid)
    finally:
        pool.stop()


def test_spec_generate_byte_identical_ngram_and_scripted():
    net = _vocab_mln(seed=13)
    prompt = [2, 0, 4]
    N = 14
    pool = DecodePool(net, max_slots=4, max_wait_ms=0.5)
    try:
        ref = _greedy_ref(pool, prompt, N)
        for draft in (NGramDraft(order=3),
                      ScriptedDraft([[1, 2], [0], []]),
                      ScriptedDraft([])):
            sid = pool.open_session()
            (o,) = pool.step(sid, one_hot(prompt, V))
            first = int(np.argmax(o[-1]))
            dec = SpeculativeDecoder(pool, vocab=V, k=3, draft=draft)
            res = dec.generate(sid, first, N)
            assert res["tokens"] == ref, (draft, res)
            assert res["dispatches"] <= N
            pool.close_session(sid)
        snap = pool.metrics.snapshot()
        assert snap["spec_steps"] > 0
        assert snap["spec_tokens_accepted"] >= N
    finally:
        pool.stop()


def test_ngram_draft_commits_two_tokens_a_dispatch_on_a_repeating_stream():
    """What speculation is for: once the greedy stream repeats, the
    n-gram draft's K proposals agree, and one verify dispatch commits
    several tokens: at least two a dispatch over the run, the same
    tokens as plain greedy decode."""
    net = _vocab_mln(seed=13)
    prompt = [2, 0, 4]
    N = 48
    pool = DecodePool(net, max_slots=4, max_wait_ms=0.5)
    try:
        ref = _greedy_ref(pool, prompt, N)
        assert len(set(ref[8:])) == 1          # the stream has settled
        sid = pool.open_session()
        (o,) = pool.step(sid, one_hot(prompt, V))
        first = int(np.argmax(o[-1]))
        dec = SpeculativeDecoder(pool, vocab=V, k=3,
                                 draft=NGramDraft(order=3))
        res = dec.generate(sid, first, N)
        assert res["tokens"] == ref
        assert 2 * res["dispatches"] <= N
        pool.close_session(sid)
    finally:
        pool.stop()


def test_model_draft_proposes_and_stays_exact():
    net = _vocab_mln(seed=17)
    # the draft model IS a copy of the target here — proposals are
    # perfect, so acceptance hits K+1 once warm; parity must hold
    # regardless
    draft_net = _vocab_mln(seed=17)
    prompt = [1, 5, 2]
    N = 12
    pool = DecodePool(net, max_slots=4, max_wait_ms=0.5)
    try:
        ref = _greedy_ref(pool, prompt, N)
        sid = pool.open_session()
        (o,) = pool.step(sid, one_hot(prompt, V))
        first = int(np.argmax(o[-1]))
        md = ModelDraft(draft_net, vocab=V)
        md._feed(prompt)          # draft consumes the prompt too
        md._seen = 0              # history excludes the prompt
        dec = SpeculativeDecoder(pool, vocab=V, k=3, draft=md)
        res = dec.generate(sid, first, N)
        assert res["tokens"] == ref
        assert res["dispatches"] < N
        pool.close_session(sid)
    finally:
        pool.stop()


# ---------------------------------------------------------------------------
# Migration: KV carries ride the payload, binary encoding round-trips
# ---------------------------------------------------------------------------
def test_kv_migration_parity_vs_unmigrated_twin():
    net = _attn_mln(seed=21, window=16)
    T0, T1 = 5, 6
    x = _seq(1, T0 + T1, seed=13)
    poolA = DecodePool(net, name="A", max_slots=4, max_wait_ms=0.5)
    poolB = DecodePool(net, name="B", max_slots=4, max_wait_ms=0.5)
    try:
        mig = poolA.open_session()
        twin = poolA.open_session()
        for t in range(T0):
            poolA.step(mig, x[0, t:t + 1])
            poolA.step(twin, x[0, t:t + 1])
        payload = poolA.export_session(mig)
        # the payload crosses the wire as JSON (the fleet hop)
        wire = json.loads(json.dumps(payload))
        assert wire["version"] == 2
        assert all("npy_b64" in leaf for leaf in wire["carry"]["leaves"])
        # leaf-level EXACT binary round trip, KV rings included
        slot = poolA._sessions[mig].slot
        dev = jax.device_get(jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(lambda a: a[slot], poolA._pool)))
        for leaf, spec in zip(dev, wire["carry"]["leaves"]):
            np.testing.assert_array_equal(np.asarray(leaf),
                                          _decode_carry_leaf(spec))
        assert poolB.import_session(wire) == mig
        poolA.finish_export(mig, ok=True)
        for t in range(T0, T0 + T1):
            (a,) = poolB.step(mig, x[0, t:t + 1])
            (b,) = poolA.step(twin, x[0, t:t + 1])
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)
    finally:
        poolA.stop()
        poolB.stop()


def test_carry_payload_v1_json_fallback(monkeypatch):
    net = _attn_mln(seed=23)
    x = _seq(1, 3, seed=15)
    monkeypatch.setenv("DL4J_CARRY_PAYLOAD", "json")
    poolA = DecodePool(net, name="A1", max_slots=2, max_wait_ms=0.5)
    poolB = DecodePool(net, name="B1", max_slots=2, max_wait_ms=0.5)
    try:
        sid = poolA.open_session()
        for t in range(3):
            poolA.step(sid, x[0, t:t + 1])
        payload = json.loads(json.dumps(poolA.export_session(sid)))
        assert payload["version"] == 1
        assert all("data" in leaf for leaf in payload["carry"]["leaves"])
        assert poolB.import_session(payload) == sid
        poolA.finish_export(sid, ok=True)
        (out,) = poolB.step(sid, x[0, 0:1])
        assert np.all(np.isfinite(out))
    finally:
        poolA.stop()
        poolB.stop()


# ---------------------------------------------------------------------------
# DecodeManager: pools keyed by (model, carry layout)
# ---------------------------------------------------------------------------
def test_manager_changed_layout_rollout_adopts_fresh_pool():
    d = tempfile.mkdtemp(prefix="dl4j_spec_mgr_")
    path = os.path.join(d, "model.zip")
    lstm = NeuralNetConfiguration.builder().seed(7).learning_rate(0.05) \
        .shape_bucketing(True).list() \
        .layer(L.GravesLSTM(n_in=F, n_out=H, activation="tanh")) \
        .layer(L.RnnOutputLayer(n_in=H, n_out=C, activation="softmax",
                                loss="mcxent")).build()
    write_model(MultiLayerNetwork(lstm).init(), path)
    cache = ModelCache(capacity=4)
    mgr = DecodeManager(cache, max_slots=2, max_wait_ms=0.5)
    try:
        x = _seq(1, 1, seed=17)
        sid_old = mgr.open_session(path)["session_id"]
        mgr.decode_step(sid_old, x[0])
        old_pool = mgr._pool_of(sid_old)
        # roll out a model with a DIFFERENT carry structure (attention
        # KV ring): new sessions must adopt a fresh pool immediately,
        # not wait on the old layout's drain
        write_model(_attn_mln(seed=9), path)
        os.utime(path, ns=(os.stat(path).st_atime_ns,
                           os.stat(path).st_mtime_ns + 1_000_000))
        sid_new = mgr.open_session(path)["session_id"]
        new_pool = mgr._pool_of(sid_new)
        assert new_pool is not old_pool
        assert old_pool.held_slots == 1     # old session still served
        mgr.decode_step(sid_new, x[0])
        mgr.decode_step(sid_old, x[0])      # both layouts live at once
        assert len(mgr.stats()) == 2
        # the old layout's pool retires once its last session leaves
        mgr.close_session(sid_old)
        mgr.open_session(path)
        assert old_pool.held_slots == 0
        assert not any(p is old_pool for p in mgr._all_pools())
    finally:
        mgr.close()


# ---------------------------------------------------------------------------
# Gateway: spec=/draft= knobs end to end
# ---------------------------------------------------------------------------
def test_gateway_decode_step_spec_knob():
    from deeplearning4j_tpu.server import DeepLearning4jEntryPoint
    d = tempfile.mkdtemp(prefix="dl4j_spec_gw_")
    path = os.path.join(d, "attn.zip")
    write_model(_vocab_mln(seed=5), path)
    ep = DeepLearning4jEntryPoint(decode_slots=4, decode_max_wait_ms=0.5)
    try:
        sid = ep.open_session(path)["session_id"]
        prompt = one_hot([0, 3, 1], V)
        res = ep.decode_step(sid, prompt.tolist(),
                             spec={"tokens": 8, "k": 3}, draft="ngram")
        spec = res["spec"]
        assert len(spec["tokens"]) == 8
        assert spec["dispatches"] <= 8
        assert spec["accepted"] == 8
        # byte-identical to the plain greedy loop on a twin session
        sid2 = ep.open_session(path)["session_id"]
        r2 = ep.decode_step(sid2, prompt.tolist())
        pending = int(np.argmax(np.asarray(r2["predictions"])[-1]))
        ref = []
        for _ in range(8):
            ref.append(pending)
            r2 = ep.decode_step(sid2, one_hot([pending], V).tolist())
            pending = int(np.argmax(np.asarray(r2["predictions"])[-1]))
        assert spec["tokens"] == ref
        st = ep.decode_stats()
        pool_stats = next(iter(st.values()))
        assert pool_stats["spec_steps"] >= 1
        assert pool_stats["kv_cache"]["rings"] == 1
        ep.close_session(sid)
        ep.close_session(sid2)
    finally:
        ep.close()


# ---------------------------------------------------------------------------
# dl4j-check KV probe: the invariants have teeth (positive control)
# ---------------------------------------------------------------------------
def test_kv_ring_watch_flags_violations():
    from deeplearning4j_tpu.analysis.check.scenarios import (
        CheckKVDecodePool, _StubModel)
    from deeplearning4j_tpu.analysis.check.specs import _KVRingWatch
    pool = CheckKVDecodePool(_StubModel(), name="chk-unit", max_slots=2,
                             max_wait_ms=0.0)
    try:
        sid = pool.open_session()
        pool.step(sid, np.zeros((1, 1), np.float32), timeout=30)
        w = _KVRingWatch(pool)
        assert w.probe() is None
        s = pool._sessions[sid]
        # rewind: write position moved backwards
        kv = np.asarray(pool._pool["kv_pos"]).copy()
        kv[s.slot] = 99.0
        pool._pool = dict(pool._pool, kv_pos=jnp.asarray(kv))
        msg = w.probe()
        assert msg is not None and "fresh claim" in msg
        # exported limbo: the ring must freeze
        kv[s.slot] = 1.0
        pool._pool = dict(pool._pool, kv_pos=jnp.asarray(kv))
        w2 = _KVRingWatch(pool)
        assert w2.probe() is None
        s.exported = True
        assert w2.probe() is None        # freeze point recorded
        kv[s.slot] = 2.0
        pool._pool = dict(pool._pool, kv_pos=jnp.asarray(kv))
        msg = w2.probe()
        assert msg is not None and "exported limbo" in msg
        s.exported = False
    finally:
        pool.stop()


# ---------------------------------------------------------------------------
# Sampling-mode speculative decode (ISSUE 16): position-keyed coupling
# ---------------------------------------------------------------------------
def _sampled_trajectory(pool, n, *, k, draft=None, **sampling):
    """Generate ``n`` tokens (the literal seed token 1, then sampled)."""
    sid = pool.open_session()
    kw = dict(vocab=V, k=k, **sampling)
    if draft is not None:
        kw["draft"] = draft
    res = SpeculativeDecoder(pool, **kw).generate(sid, 1, n)
    pool.close_session(sid)
    return res


@pytest.mark.parametrize("top_k", [0, 4])
def test_sampling_spec_trajectory_parity_vs_nonspec(top_k):
    """Seeded speculative sampling emits EXACTLY the trajectory plain
    one-token-per-dispatch sampling emits at matched PRNG state: every
    stream position draws with a key derived from (seed, position), so
    the accepted prefix + first resample is chunking-independent."""
    net = _vocab_mln(seed=13)
    N = 16
    for paged in (False, True):
        pool = DecodePool(net, name=f"sm{int(paged)}{top_k}", max_slots=4,
                          max_wait_ms=0.5, kv_paged=paged, kv_block=4)
        try:
            base = _sampled_trajectory(pool, N, k=0, draft="none",
                                       temperature=0.8, top_k=top_k,
                                       seed=123)
            assert base["dispatches"] == N
            spec = _sampled_trajectory(pool, N, k=3,
                                       draft=NGramDraft(order=3),
                                       temperature=0.8, top_k=top_k,
                                       seed=123)
            assert spec["tokens"] == base["tokens"], (paged, top_k)
            # a different seed is a genuinely different trajectory —
            # the parity above isn't vacuous determinism
            other = _sampled_trajectory(pool, N, k=0, draft="none",
                                        temperature=0.8, top_k=top_k,
                                        seed=124)
            assert other["tokens"] != base["tokens"]
        finally:
            pool.stop()


def test_sampling_spec_acceptance_lengths_0_to_k_parity():
    """Scripted drafts force every acceptance length 0..K; the emitted
    trajectory never moves (the resample at the first rejection IS the
    token the non-speculative run would have drawn there)."""
    net = _vocab_mln(seed=13)
    N, K = 14, 3
    pool = DecodePool(net, name="smacc", max_slots=4, max_wait_ms=0.5)
    try:
        ref = _sampled_trajectory(pool, N, k=0, draft="none",
                                  temperature=0.8, seed=5)["tokens"]
        for corrupt_at in range(K + 1):
            # draft the true continuation but corrupt index corrupt_at,
            # pinning acceptance at exactly corrupt_at draft tokens
            props, i = [], 1
            while i < N:
                p = list(ref[i:i + K])
                if corrupt_at < len(p):
                    p[corrupt_at] = (p[corrupt_at] + 1) % V
                props.append(p)
                i += max(1, min(corrupt_at + 1, len(p) + 1))
            res = _sampled_trajectory(pool, N, k=K,
                                      draft=ScriptedDraft(props),
                                      temperature=0.8, seed=5)
            assert res["tokens"] == ref, (corrupt_at, res["tokens"])
    finally:
        pool.stop()


@pytest.mark.slow
def test_sampling_spec_chi_square_matches_model_distribution():
    """10k+ tokens sampled through the fused verify program follow the
    model's temperature-scaled distribution (ISSUE 16): with the output
    layer's weights zeroed the softmax head emits softmax(b) at every
    position, so sampling at temperature t must draw iid from
    softmax(b/t) — chi-square at alpha=0.001; top-k additionally
    renormalizes over the k best logits and NEVER emits the rest."""
    temp = 0.7
    bias = np.array([0.8, -0.4, 0.2, 1.1, -0.9, 0.0], np.float32)
    net = _vocab_mln(seed=5, window=16)
    net.set_param("1_W", np.zeros((H, V), np.float32))
    net.set_param("1_b", bias)

    def chi2(tokens, p):
        n = len(tokens)
        counts = np.bincount(tokens, minlength=V).astype(np.float64)
        exp = p * n
        live = exp > 0
        assert counts[~live].sum() == 0, "token outside the support"
        return float(((counts[live] - exp[live]) ** 2 / exp[live]).sum())

    pool = DecodePool(net, name="smchi", max_slots=2, max_wait_ms=0.5)
    try:
        # full-vocab sampling: dof = V-1 = 5, chi2(0.001) = 20.515
        res = _sampled_trajectory(pool, 10_001, k=3,
                                  draft=NGramDraft(order=3),
                                  temperature=temp, seed=99)
        toks = np.asarray(res["tokens"][1:])    # drop the literal seed
        assert len(toks) >= 10_000
        p = np.exp(bias / temp) / np.exp(bias / temp).sum()
        stat = chi2(toks, p)
        assert stat < 20.515, f"chi2={stat:.2f} vs softmax(b/t)"
        # top-k=4: dof = 3, chi2(0.001) = 16.266; the 2 masked tokens
        # must never appear
        res = _sampled_trajectory(pool, 3_001, k=3,
                                  draft=NGramDraft(order=3),
                                  temperature=temp, top_k=4, seed=7)
        toks = np.asarray(res["tokens"][1:])
        keep = np.argsort(bias)[-4:]
        pk = np.zeros(V)
        pk[keep] = np.exp(bias[keep] / temp)
        pk /= pk.sum()
        stat = chi2(toks, pk)
        assert stat < 16.266, f"chi2={stat:.2f} vs top-k renorm"
    finally:
        pool.stop()
