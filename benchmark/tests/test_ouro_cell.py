"""The looped decoder's cell: its files by the names in ``BENCHMARK.json``,
the FLOP walk of its reference, the three readers on a recorded reduction,
the new mode's ``--rehearse`` line and the exit counters after it.  Run by
hand, on the CPU, like ``test_benchmark.py``:

    python -m pytest benchmark/tests/test_ouro_cell.py -q -p no:cacheprovider
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark.harness import compare, flops, loop_scopes  # noqa: E402
from benchmark.metrics import (  # noqa: E402
    loop_body_ms_per_pass, loop_exit_head_ms_per_step, loop_recompute_share)

CELL = "ouro_2_6b.fit_seq4k_b1"
READERS = (loop_body_ms_per_pass, loop_exit_head_ms_per_step,
           loop_recompute_share)


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _cfg():
    cfg = _json("benchmark", "configs", "ouro_2_6b_pp6.json")
    cfg["seq_len"] = _json("benchmark", "traffic", "fit_seq4k_b1.json")["seq_len"]
    return cfg


# --- the files, by the names in BENCHMARK.json --------------------------------
def test_the_cell_finds_its_files_by_name():
    bench = _json("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro_2_6b_pp6", "fit_seq4k_b1", 1)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = _json(entry["file"])
    traffic = _json("benchmark", "traffic", cell["traffic"] + ".json")
    assert importlib.import_module("benchmark.modes." + traffic["mode"]).Mode
    assert importlib.import_module(cfg["reference"]).layers
    assert _json("benchmark", "limits", CELL + ".json")["limits"]
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    assert [m["name"] for m in mine] == [
        "loop_body_ms_per_pass", "loop_exit_head_ms_per_step",
        "loop_recompute_share"]
    for m in mine:
        assert importlib.import_module("benchmark.metrics." + m["name"]).read
        assert m["moves"] == "train_samples_per_s" and m["better"] == "lower"
    assert (traffic["batch"], traffic["seq_len"], traffic["pool_batches"],
            traffic["warmup_steps"], traffic["fused_steps"],
            traffic["trace_steps"]) == (1, 4096, 16, 3, 1, 6)


def test_the_configuration_holds_the_published_widths_and_names_its_cut():
    cfg = _cfg()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == {"num_hidden_layers"}
    assert cfg["source"] == row["source_url"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["total_ut_steps"], cfg["rms_norm_eps"], cfg["rope_theta"]) \
        == (2048, 16, 16, 128, 5632, 49152, 4, 1e-6, 1e6)
    assert cfg["layers_run"] == list(range(8))
    assert (cfg["num_hidden_layers"], cfg["num_hidden_layers_published"]) \
        == (8, 48)
    assert "six chips" in cfg["deployment"] and len(cfg["assumed"]) >= 8
    bench = _json("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]


def test_the_reference_holds_the_parameters_the_configuration_states():
    import jax
    from benchmark.reference import ouro as ref
    cfg = _cfg()
    shapes = jax.eval_shape(lambda k: ref.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    n = sum(int(l.size) for l in jax.tree_util.tree_leaves(shapes))
    assert n == cfg["parameters"] == 612_438_017


def test_the_builder_takes_the_configurations_own_keys():
    import inspect
    from benchmark.modes import fit_looped
    from deeplearning4j_tpu.models.ouro import ouro
    assert set(fit_looped.PUBLISHED) <= set(inspect.signature(ouro).parameters)
    assert set(fit_looped.PUBLISHED) <= set(_cfg())


# --- the yardstick's arithmetic ---------------------------------------------
def test_flop_walk_gives_the_hand_count():
    from benchmark.reference import ouro as ref
    cfg, T = _cfg(), 4096
    layers = ref.layers(cfg)
    # by hand, multiply-adds a token: a block is four squares of 2,048
    # and three products of 2,048 x 5,632, attention two products of
    # T/2 x 2,048; 8 blocks x 4 passes; four heads of 2,048 x 49,152 and
    # four gates of 2,048
    block = 4 * 2048 * 2048 + 3 * 2048 * 5632
    attn = 2 * (T // 2) * 2048
    per_token = 32 * (block + attn) + 4 * (2048 * 49152 + 2048)
    assert flops.forward_flops_per_row(layers) == 2 * per_token * T
    assert 2 * block / 1e6 == pytest.approx(102.8, abs=0.05)
    assert 2 * per_token / 1e9 == pytest.approx(4.63, abs=0.005)
    step = flops.step_flops(layers, 1)
    # forward, weight gradient and input gradient of every product;
    # nothing recomputed
    assert step == 3 * 2 * per_token * T
    assert step / 1e12 == pytest.approx(56.9, abs=0.05)
    # the four heads' share at this depth, and at the published one
    heads = 4 * 2048 * 49152
    assert heads / per_token == pytest.approx(0.17, abs=0.005)
    assert heads / (6 * 32 * (block + attn) + heads) == pytest.approx(
        0.03, abs=0.005)


# --- the readers, on a recorded reduction -----------------------------------
@pytest.fixture
def recorded():
    """What a traced run of the cell on one v5e left (PERF.md section 5)."""
    return _json("benchmark", "tests", "data", "ouro_loop_scopes.json")


def _chips(recorded):
    return {"0": {k: recorded[k]
                  for k in ("busy_s", "sub_scope_s", "recomputed_s")}}


def test_readers_on_the_recorded_reduction(recorded):
    counters = {"steps": recorded["steps"], "passes": recorded["passes"]}
    ctx = {"_loop_scopes": loop_scopes.reduce(_chips(recorded), counters)}
    sub = recorded["sub_scope_s"]
    body = sub["fwd/LoopVertex/body"] + sub["bwd/LoopVertex/body"]
    assert loop_body_ms_per_pass.read(ctx) == pytest.approx(body / 24 * 1e3)
    assert 50 < loop_body_ms_per_pass.read(ctx) < 200
    head = sum(s for n, s in sub.items() if "LoopExitOutputLayer" in n)
    assert loop_exit_head_ms_per_step.read(ctx) == pytest.approx(
        head / 6 * 1e3)
    share = loop_recompute_share.read(ctx)
    assert share == pytest.approx(
        100 * recorded["recomputed_s"]["LoopVertex"] / recorded["busy_s"])
    # a second forward is under a third of forward + backward + forward
    assert 10 < share < 33


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__.split(".")[-1])
def test_readers_read_nothing_where_there_is_nothing(reader, recorded,
                                                      monkeypatch, tmp_path):
    # no kept trace; a kept one without the counter; a profile without
    # the loop's scopes (the parent of the PR that brought them)
    monkeypatch.delenv("BENCHMARK_KEEP_TRACE", raising=False)
    assert reader.read({}) is None
    monkeypatch.setenv("BENCHMARK_KEEP_TRACE", str(tmp_path))
    assert reader.read({}) is None
    counters = {"steps": 6, "passes": {"stack": 24.0}}
    empty = {"0": {"busy_s": 1.0, "sub_scope_s": {}}}
    assert reader.read({"_loop_scopes": loop_scopes.reduce(empty, counters)}) \
        is None
    assert reader.read({"_loop_scopes": loop_scopes.reduce(
        _chips(recorded), {"steps": 6, "passes": {}})}) is None


def test_the_recompute_share_is_left_out_where_the_profile_does_not_tell_it(
        recorded):
    chips = {"0": {k: recorded[k] for k in ("busy_s", "sub_scope_s")}}
    ctx = {"_loop_scopes": loop_scopes.reduce(
        chips, {"steps": 6, "passes": {"stack": 24.0}})}
    assert loop_recompute_share.read(ctx) is None
    assert loop_body_ms_per_pass.read(ctx) is not None


def test_traced_reads_the_kept_trace_and_the_counters(monkeypatch, tmp_path):
    """The small recorded trace has no loop: a program with the scopes
    but none of them in this trace reads nothing."""
    import shutil
    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
    shutil.copy(os.path.join(data, "small.xplane.pb"), tmp_path / "t.xplane.pb")
    with open(tmp_path / loop_scopes.COUNTERS_FILE, "w") as f:
        json.dump({"steps": 3, "passes": {"stack": 12.0}}, f)
    monkeypatch.setenv("BENCHMARK_KEEP_TRACE", str(tmp_path))
    assert loop_scopes.traced({}) is None


# --- the mode ---------------------------------------------------------------
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


def test_the_exit_counters_appear_after_a_rehearsed_run():
    from benchmark.modes import fit_looped
    from deeplearning4j_tpu import monitor
    mode = fit_looped.Mode(_json("benchmark", "configs", "ouro_2_6b_pp6.json"),
                           _json("benchmark", "traffic", "fit_seq4k_b1.json"),
                           5, 1, rehearse=True)
    before = loop_scopes.passes_run()
    mode.setup()
    after = loop_scopes.passes_run()
    # three warm-up steps of four passes
    assert after["stack"] - before.get("stack", 0.0) == 12.0
    snap = monitor.get_registry().snapshot()
    for name in ("dl4j_loop_exit_mass", "dl4j_loop_exit_loss"):
        got = {s["labels"]["pass"]: s["value"] for s in snap[name]["samples"]
               if s["labels"]["vertex"] == "head"}
        assert sorted(got) == ["1", "2", "3", "4"]
    assert sum(s["value"] for s in snap["dl4j_loop_exit_mass"]["samples"]
               if s["labels"]["vertex"] == "head") == pytest.approx(1.0, rel=1e-5)
    mode.release()


# --- correct has to be able to come out false --------------------------------
@pytest.mark.parametrize("fault", [
    {"numerics": "float8"}, {"passes": 3}, {"grad_passes": (3,)}],
    ids=["control", "a-pass-left-out", "gradient-from-the-last-pass-alone"])
def test_the_control_and_the_faults_of_the_mechanism_are_not_correct(fault):
    from benchmark.modes import fit_looped, fit_tokens
    cfg = _json("benchmark", "configs", "ouro_2_6b_pp6.json")
    traffic = _json("benchmark", "traffic", "fit_seq4k_b1.json")
    limits = _json("benchmark", "limits", CELL + ".json")["limits"]
    mode = fit_looped.Mode(cfg, traffic, 5, 1, rehearse=True)
    mode.pool = fit_tokens.make_pool(5, 3, mode.batch, mode.seq_len,
                                     mode.cfg["vocab_size"])
    ref = mode.reference_readings()
    other = mode.reference_readings(**fault)
    rows = compare.verdict(compare.gaps(other, ref)[0],
                           {k: v for k, v in limits.items()
                            if k != "window_retraces"})
    assert not all(ok for *_, ok in rows), rows
