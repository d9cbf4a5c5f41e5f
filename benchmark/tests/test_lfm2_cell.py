"""The language-model cell's part of the benchmark: the FLOP walk of its
reference, the expert layer's operations and bytes, the two readers on a
recorded reduction, and the new mode's ``--rehearse`` line.  Run by hand,
on the CPU, like ``test_benchmark.py``:

    python -m pytest benchmark/tests/test_lfm2_cell.py -q -p no:cacheprovider
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark.harness import compare, flops, moe_scopes  # noqa: E402
from benchmark.harness.peaks import PEAKS  # noqa: E402
from benchmark.metrics import (  # noqa: E402
    moe_experts_roofline, moe_route_ms_per_step)

CELL = "lfm2_8b_a1b.fit_seq4k_b2"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
V5E = PEAKS["TPU v5 lite"]


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _cfg():
    cfg = _json("benchmark", "configs", "lfm2_8b_a1b_ep4.json")
    cfg["seq_len"] = _json("benchmark", "traffic", "fit_seq4k_b2.json")["seq_len"]
    return cfg


# --- the configuration ------------------------------------------------------
def test_the_configuration_holds_the_published_widths_and_names_its_cuts():
    cfg = _cfg()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert cfg["source"] == row["source_url"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["num_experts_published"],
            cfg["conv_L_cache"]) == (2048, 32, 8, 64, 7168, 1792, 4, 32, 3)
    # layer 0, then one whole period: attention, conv, conv, conv
    assert [cfg["layer_types"][i] for i in cfg["layers_run"]] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert len(cfg["layers_run"]) == cfg["num_hidden_layers"]
    assert len(cfg["experts_held"]) == cfg["num_experts"] == 8
    assert cfg["vocab_size"] * 4 == cfg["vocab_size_published"]
    bench = _json("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]


def test_the_reference_holds_the_parameters_the_configuration_states():
    import jax
    from benchmark.reference import lfm2_moe as ref
    cfg = _cfg()
    shapes = jax.eval_shape(lambda k: ref.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    n = sum(int(l.size) for l in jax.tree_util.tree_leaves(shapes))
    assert n == cfg["parameters"] == 541_374_592


# --- the yardstick's arithmetic ---------------------------------------------
def test_flop_walk_gives_the_hand_count():
    from benchmark.reference import lfm2_moe as ref
    cfg, T = _cfg(), 4096
    layers = ref.layers(cfg)
    # by hand, multiply-adds a token: the leading layer (conv 3 + 1 squares
    # of 2048, MLP 3 x 2048 x 7168), the attention layer (q, o squares; k, v
    # 2048 x 512; two products of T/2 x 2048), three conv layers, and in each
    # of the four the router (2048 x 32) and one held expert a token
    # (3 x 2048 x 1792); the head 2048 x 16384
    sq = 2048 * 2048
    conv, mlp = 4 * sq, 3 * 2048 * 7168
    attn = 2 * sq + 2 * 2048 * 512 + 2 * (T // 2) * 2048
    moe = 2048 * 32 + 3 * 2048 * 1792
    per_token = conv + mlp + attn + 3 * conv + 4 * moe + 2048 * 16384
    assert flops.forward_flops_per_row(layers) == 2 * per_token * T
    assert 2 * per_token / 1e9 == pytest.approx(0.416, abs=0.0005)
    step = flops.step_flops(layers, 2)
    # forward, weight gradient and input gradient of every product
    assert step == 3 * 2 * per_token * 2 * T
    assert step / 1e12 == pytest.approx(10.2, rel=0.02)


def test_expert_passes_count_three_products_three_ways():
    passes = moe_scopes.expert_passes(8192, 8, 2048, 1792)
    assert len(passes) == 9
    assert all(f == 2.0 * 8192 * 2048 * 1792 for _, f, _ in passes)
    # rows in, the eight experts' weights, rows out, in bf16
    assert passes[0][2] == 2 * (8192 * 2048 + 8 * 2048 * 1792 + 8192 * 1792)
    least = moe_scopes.least_seconds(8192, 8, 2048, 1792, V5E)
    # compute-bound at this load: 9 x 60.1 GFLOP at 197 TFLOP/s
    assert least == pytest.approx(9 * 2.0 * 8192 * 2048 * 1792 / 197e12)
    # a near-empty layer is bound by reading its weights
    tiny = moe_scopes.least_seconds(8, 8, 2048, 1792, V5E)
    assert tiny == pytest.approx(
        9 * 2 * (8 * 2048 + 8 * 2048 * 1792 + 8 * 1792) / 819e9)


# --- the readers, on a recorded reduction -----------------------------------
@pytest.fixture
def recorded():
    """What a traced run of the cell on one v5e left (PERF.md section 5):
    the profile's ``sub_scope_s`` and the counters of its 24 steps."""
    return _json("benchmark", "tests", "data", "lfm2_moe_scopes.json")


def _ctx(recorded):
    part_s = {}
    for name, s in recorded["sub_scope_s"].items():
        part = name.split("/")[2]
        part_s[part] = part_s.get(part, 0.0) + s
    return {"cfg": _cfg(), "peaks": V5E,
            "_moe_scopes": {"steps": recorded["steps"], "part_s": part_s,
                            "held_assignments": recorded["held_assignments"]}}


def test_readers_on_the_recorded_reduction(recorded):
    ctx = _ctx(recorded)
    steps = recorded["steps"]
    least = steps * sum(moe_scopes.least_seconds(a / steps, 8, 2048, 1792, V5E)
                        for a in recorded["held_assignments"].values())
    experts_s = sum(s for n, s in recorded["sub_scope_s"].items()
                    if n.endswith("/experts"))
    roofline = moe_experts_roofline.read(ctx)
    assert roofline == pytest.approx(100.0 * least / experts_s)
    assert 0 < roofline < 100
    routing_s = sum(s for n, s in recorded["sub_scope_s"].items()
                    if not n.endswith("/experts"))
    assert moe_route_ms_per_step.read(ctx) == pytest.approx(
        routing_s / recorded["steps"] * 1e3)


def test_readers_read_nothing_where_the_program_has_no_scopes(monkeypatch, tmp_path):
    # the parent of the PR that brought them: no kept trace, or one
    # without the parts and the counters
    monkeypatch.delenv("BENCHMARK_KEEP_TRACE", raising=False)
    ctx = {"cfg": _cfg(), "peaks": V5E}
    assert moe_experts_roofline.read(ctx) is None
    assert moe_route_ms_per_step.read(dict(ctx)) is None
    monkeypatch.setenv("BENCHMARK_KEEP_TRACE", str(tmp_path))
    assert moe_experts_roofline.read({"cfg": _cfg(), "peaks": V5E}) is None
    with open(tmp_path / moe_scopes.COUNTERS_FILE, "w") as f:
        json.dump({"steps": 3, "held_assignments": {}}, f)
    assert moe_route_ms_per_step.read({"cfg": _cfg(), "peaks": V5E}) is None


def test_traced_reads_the_kept_trace_and_the_counters(monkeypatch, tmp_path):
    """The small recorded trace has no expert layer: a program with the
    scopes but none of them in this trace reads nothing either."""
    import shutil
    shutil.copy(os.path.join(DATA, "small.xplane.pb"), tmp_path / "t.xplane.pb")
    with open(tmp_path / moe_scopes.COUNTERS_FILE, "w") as f:
        json.dump({"steps": 3, "held_assignments": {"l2_moe": 100.0}}, f)
    monkeypatch.setenv("BENCHMARK_KEEP_TRACE", str(tmp_path))
    assert moe_scopes.traced({}) is None


# --- the mode ---------------------------------------------------------------
def test_pool_is_token_ids_with_next_token_labels():
    from benchmark.modes import fit_tokens
    pool = fit_tokens.make_pool(7, 3, 2, 16, 50)
    assert len(pool) == 3
    for d in pool:
        assert d.features.shape == d.labels.shape == (2, 16)
        assert d.features.dtype == d.labels.dtype == "int32"
        assert (d.features[:, 1:] == d.labels[:, :-1]).all()
        assert 0 <= d.features.min() and d.features.max() < 50
    rows = [tuple(r) for d in pool for r in d.features]
    assert len(set(rows)) == 6           # all sequences distinct
    again = fit_tokens.make_pool(7, 3, 2, 16, 50)
    assert all((a.features == b.features).all() for a, b in zip(pool, again))


def _run(*extra):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "1",
         "--trace", "0", *extra],
        capture_output=True, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_without_a_chip_it_exits_non_zero():
    r = _run()
    assert r.returncode != 0 and "no TPU" in r.stderr


def test_rehearsal_prints_a_well_formed_last_line():
    r = _run("--rehearse")
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "compared" and "rehearsal" in line
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert set(line["metrics"]) == {"train_samples_per_s", "step_ms_p95", "setup_s"}
    limits = _json("benchmark", "limits", CELL + ".json")["limits"]
    assert set(limits) <= set(line["compared"])
    assert len(limits) > 1          # numbers of the first steps, not retraces alone


# --- correct has to be able to come out false --------------------------------
@pytest.mark.parametrize("fault", ["control", "half_batch"])
def test_the_control_and_the_half_batch_are_not_correct(fault):
    from benchmark.modes import fit_tokens
    cfg = _json("benchmark", "configs", "lfm2_8b_a1b_ep4.json")
    traffic = _json("benchmark", "traffic", "fit_seq4k_b2.json")
    limits = _json("benchmark", "limits", CELL + ".json")["limits"]
    mode = fit_tokens.Mode(cfg, traffic, 5, 1, rehearse=True)
    mode.pool = fit_tokens.make_pool(5, 3, mode.batch, mode.seq_len,
                                     mode.cfg["vocab_size"])
    ref = mode.reference_readings()
    other = (mode.reference_readings(cfg["precision"]["control"])
             if fault == "control" else mode.reference_readings(rows=1))
    rows = compare.verdict(compare.gaps(other, ref)[0],
                           {k: v for k, v in limits.items()
                            if k != "window_retraces"})
    assert not all(ok for *_, ok in rows), rows
