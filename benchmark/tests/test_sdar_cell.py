"""The block-diffusion cell: its files by the names in ``BENCHMARK.json``,
the configuration against the catalog's row, the FLOP walk of its
reference and the attention core's count, the three readers on a recorded
reduction and on nothing, the mode's check of the noise, the new mode's
``--rehearse`` line, and the control and the four faults at the rehearsal's
sizes.  Run by hand, on the CPU, like ``test_benchmark.py``:

    python -m pytest benchmark/tests/test_sdar_cell.py -q -p no:cacheprovider
"""

import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark.harness import blockdiff, compare, flops  # noqa: E402
from benchmark.metrics import (  # noqa: E402
    blockdiff_attn_ms_per_step, blockdiff_attn_roofline,
    blockdiff_noise_ms_per_batch)

CELL = "sdar_30b_a3b.fit_bd4_seq4k_b1"
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _traffic():
    return _json("benchmark", "traffic", "fit_bd4_seq4k_b1.json")


def _cfg():
    cfg, tr = _json("benchmark", "configs", "sdar_30b_a3b_ep8.json"), _traffic()
    return {**cfg, "seq_len": tr["seq_len"], "block_length": tr["block_length"]}


# --- the files, by the names in BENCHMARK.json --------------------------------
def test_the_cell_finds_its_files_by_name():
    bench = _json("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sdar_30b_a3b_ep8", "fit_bd4_seq4k_b1", 1)
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert len(entry["why"]) <= 200
    cfg, traffic = _json(entry["file"]), _traffic()
    assert importlib.import_module("benchmark.modes." + traffic["mode"]).Mode
    assert importlib.import_module(cfg["reference"]).layers
    limits = _json("benchmark", "limits", CELL + ".json")["limits"]
    assert limits["noise_off_definition"] == 0 and limits["window_retraces"] == 0
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    assert [(m["name"], m["unit"], m["source"], m["layer"]) for m in mine] == [
        ("blockdiff_attn_roofline", "%", "device_trace", "kernels"),
        ("blockdiff_attn_ms_per_step", "ms", "device_trace", "one jitted step"),
        ("blockdiff_noise_ms_per_batch", "ms", "program_span", "input pipeline")]
    for m in mine:
        assert importlib.import_module("benchmark.metrics." + m["name"]).read
        assert m["moves"] == "train_samples_per_s"
        assert m["workloads"] == [CELL]
    # the accepted entries stand as they were: new ones at the end
    assert [w["name"] for w in bench["workloads"]][:4] == [
        "vgg16.fit_b128", "resnet50.fit_b128", "lfm2_8b_a1b.fit_seq4k_b2",
        "ouro_2_6b.fit_seq4k_b1"]
    assert (traffic["batch"], traffic["seq_len"], traffic["block_length"],
            traffic["pool_batches"], traffic["warmup_steps"],
            traffic["fused_steps"], traffic["trace_steps"]) \
        == (1, 4096, 4, 64, 3, 1, 12)


def test_the_configuration_holds_the_published_widths_and_names_its_cut():
    cfg = _cfg()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    differs = {k for k, v in row["config"].items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert cfg["source"] == row["source_url"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["norm_topk_prob"], cfg["rms_norm_eps"], cfg["rope_theta"]) \
        == (2048, 32, 4, 128, 768, 8, True, 1e-6, 1e6)
    assert cfg["layers_run"] == list(range(cfg["num_hidden_layers"]))
    assert cfg["num_hidden_layers"] in (4, 5)
    assert (cfg["num_hidden_layers_published"], cfg["num_experts_published"],
            cfg["vocab_size_published"]) == (48, 128, 151936)
    assert cfg["experts_held"] == list(range(16)) and cfg["num_experts"] == 16
    assert cfg["vocab_size"] == 151936 // 8 and cfg["mask_id"] == 18991
    assert "eight chips" in cfg["deployment"] \
        and "nothing stands in" in cfg["deployment"]
    assert len(cfg["assumed"]) >= 7
    assert any("block length 4" in a for a in cfg["assumed"])
    bench = _json("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]


def test_the_reference_holds_the_parameters_the_configuration_states():
    import jax
    from benchmark.reference import sdar_moe as ref
    cfg = _cfg()
    shapes = jax.eval_shape(lambda k: ref.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    n = sum(int(l.size) for l in jax.tree_util.tree_leaves(shapes))
    layers = len(cfg["layers_run"])
    assert n == cfg["parameters"] == layers * 94_638_336 + 77_793_280
    assert str(16 * n) in cfg["parameter_bytes"].replace(",", "")


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference", "sdar_moe.py")) as f:
        text = f.read()
    assert "deeplearning4j_tpu" not in text.split('"""', 2)[2]
    assert "C.HIGHEST" in text


def test_the_builder_takes_the_configurations_own_keys():
    import inspect
    from benchmark.modes import fit_block_diffusion as mode
    from deeplearning4j_tpu.models.sdar import sdar_moe
    assert set(mode.PUBLISHED) <= set(inspect.signature(sdar_moe).parameters)
    assert set(mode.PUBLISHED) <= set(_cfg())


# --- the yardstick's arithmetic ---------------------------------------------
def test_flop_walk_gives_the_hand_count():
    from benchmark.reference import sdar_moe as ref
    cfg, L, b = _cfg(), 4096, 4
    n = len(cfg["layers_run"])
    pairs = L * L + L * b
    assert pairs == ref.live_pairs(L, b) == blockdiff.live_pairs(L, b) \
        == 16_793_600
    # by hand, multiply-adds a sequence: the stack at 2L rows, attention's
    # two products at the live pairs, the held experts at 2L x 8 x 16/128
    # = 2L rows each of three products, the head at L rows
    layer = (2 * L * 2048 * 4096 + 2 * (2 * L * 2048 * 512)
             + 2 * pairs * 4096 + 2 * L * 4096 * 2048
             + 2 * L * 2048 * 128 + 3 * (2 * L * 2048 * 768))
    head = L * 2048 * 18992
    assert flops.forward_flops_per_row(ref.layers(cfg)) == 2 * (n * layer + head)
    step = flops.step_flops(ref.layers(cfg), 1)
    if n == 5:
        assert step / 1e12 == pytest.approx(10.9, abs=0.1)
    attn = 3 * 2 * n * 2 * pairs * 4096
    assert attn / step == pytest.approx(0.38, abs=0.01)


def test_the_cores_count_is_seven_products_over_the_live_pairs():
    cfg = _cfg()
    one = blockdiff.core_flops(4096, 4, 32, 128)
    assert one == 7 * 2 * 16_793_600 * 32 * 128
    by = blockdiff.core_bytes(4096, 32, 4, 128)
    assert by == 2 * 2 * 8192 * 128 * (2 * 32 + 2 * 4)
    least = blockdiff.least_seconds(cfg, 1, PEAKS)
    assert least == pytest.approx(len(cfg["layers_run"]) * one / 197e12)
    assert one / 197e12 > by / 819e9        # compute-bound


# --- the readers, on a recorded reduction and on nothing -----------------------
CHIPS = {"0": {"busy_s": 1.5, "sub_scope_s": {
    "fwd/SelfAttentionLayer/attn_core": 0.12,
    "bwd/SelfAttentionLayer/attn_core": 0.36,
    "fwd/MixtureOfExpertsLayer/experts": 0.2,
    "kernel/MixtureOfExpertsLayer/experts": 0.3}}}


def _ctx(tr):
    return {"_blockdiff": tr, "peaks": PEAKS, "cfg": _cfg(),
            "traffic": _traffic(), "window": {"batch": 1}}


def test_the_readers_on_a_recorded_reduction():
    tr = blockdiff.reduce(CHIPS, {"steps": 12})
    assert tr == {"steps": 12, "core_s": pytest.approx(0.48)}
    ctx = _ctx(tr)
    assert blockdiff_attn_ms_per_step.read(ctx) == pytest.approx(40.0)
    least = blockdiff.least_seconds(_cfg(), 1, PEAKS)
    assert blockdiff_attn_roofline.read(ctx) == pytest.approx(
        100.0 * 12 * least / 0.48)
    assert 0 < blockdiff_attn_roofline.read(ctx) < 100


@pytest.mark.parametrize("chips,counters", [
    ({}, {"steps": 12}),
    ({"0": {"sub_scope_s": {"fwd/MixtureOfExpertsLayer/experts": 1.0}}},
     {"steps": 12}),
    (CHIPS, {}), (CHIPS, {"steps": 0})])
def test_the_readers_on_nothing(chips, counters):
    assert blockdiff.reduce(chips, counters) is None
    ctx = _ctx(None)
    assert blockdiff_attn_roofline.read(ctx) is None
    assert blockdiff_attn_ms_per_step.read(ctx) is None


def test_a_program_without_the_scope_or_the_span_reads_nothing(
        tmp_path, monkeypatch):
    monkeypatch.setenv("BENCHMARK_KEEP_TRACE", str(tmp_path / "absent"))
    ctx = {"peaks": PEAKS, "cfg": _cfg(), "traffic": _traffic(),
           "window": {"batch": 1}}
    assert blockdiff.traced(ctx) is None
    assert blockdiff_attn_roofline.read(ctx) is None
    from deeplearning4j_tpu import monitor
    snap = monitor.get_registry().snapshot().get("dl4j_phase_seconds", {})
    if not any(s["labels"].get("phase") == "noise"
               for s in snap.get("samples", [])):
        assert blockdiff_noise_ms_per_batch.read(ctx) is None


def test_the_noise_reader_reads_the_pre_processors_span():
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.diffusion import BlockDiffusionNoiser
    noiser = BlockDiffusionNoiser(4, 18991, seed=1)
    for _ in range(3):
        noiser.pre_process(DataSet(np.zeros((1, 4096), np.int32), None))
    ms = blockdiff_noise_ms_per_batch.read({})
    assert ms is not None and 0 < ms < 50


# --- the mode's check of the noise ---------------------------------------------
def _batch(seed=0, L=32, b=4, mask_id=255):
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.diffusion import BlockDiffusionNoiser
    rng = np.random.default_rng(seed)
    d = BlockDiffusionNoiser(b, mask_id, seed).pre_process(
        DataSet(rng.integers(0, mask_id, (2, L), dtype=np.int32), None))
    return d.features.copy(), d.labels.copy(), d.labels_mask.copy()


def _break(how):
    x, y, w = _batch()
    masked = np.argwhere(w > 0)
    clean = np.argwhere(w == 0)
    if how == "clean half touched":
        x[0, 32 + 3] = (x[0, 32 + 3] + 1) % 255
    elif how == "a masked token left as it was":
        r, c = masked[0]
        x[r, c] = y[r, c]
    elif how == "a clean token replaced":
        r, c = clean[0]
        x[r, c] = 255
    elif how == "two weights in a block":
        r, c = masked[0]
        w[r, c] *= 1.5
    elif how == "a weight under 1":
        blk = w.reshape(2, 8, 4)
        j = np.argwhere(blk.max(-1) > 0)[0]
        blk[j[0], j[1]][blk[j[0], j[1]] > 0] = 0.5
    elif how == "a weight on a clean token":
        r, c = clean[0]
        w[r, c] = 2.0
    elif how == "a label that is the mask id":
        y[0, 0] = 255
        x[0, 32] = 255
    return x, y, w


def test_the_check_passes_the_pre_processors_batches():
    from benchmark.modes.fit_block_diffusion import off_definition
    assert off_definition([_batch(s) for s in range(3)], 32, 4, 255, 1e-3) \
        == (0, None)


@pytest.mark.parametrize("how", [
    "clean half touched", "a masked token left as it was",
    "a clean token replaced", "two weights in a block", "a weight under 1",
    "a weight on a clean token", "a label that is the mask id"])
def test_the_check_sees_a_batch_off_the_definition(how):
    from benchmark.modes.fit_block_diffusion import off_definition
    bad, why = off_definition([_batch(1), _break(how)], 32, 4, 255, 1e-3)
    assert bad == 1 and why


# --- a rehearsed run, and the readings at its sizes ----------------------------
def test_a_rehearsed_run_prints_a_well_formed_last_line():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_samples_per_s", "step_ms_p95",
                                    "setup_s"}
    assert line["rehearsal"]
    compared = line["compared"]
    assert compared["noise_off_definition"] == {"value": 0, "limit": 0}
    assert compared["window_retraces"]["value"] == 0
    limits = _json("benchmark", "limits", CELL + ".json")["limits"]
    assert set(limits) <= set(compared)


@pytest.fixture(scope="module")
def rehearsed():
    from benchmark.modes.fit_block_diffusion import Mode
    mode = Mode(_json("benchmark", "configs", "sdar_30b_a3b_ep8.json"),
                _traffic(), 2000000123, 1, True)
    mode.setup()
    mode.release()
    return mode, mode.reference_readings()


def test_the_program_follows_the_reference_at_the_rehearsals_sizes(rehearsed):
    mode, ref = rehearsed
    values, _ = compare.gaps(mode.readings, ref)
    assert values["loss1_gap"] < 1e-5 and values["grad_gap"] < 1e-4
    assert values["grad_diff_median_leaf"] < 1e-4
    assert len(mode.noise.seen) == 3


def _verdict(mode, readings, ref):
    """(correct, names that failed, values) under the cell's committed
    limits, through the mode's own comparison; a variant that followed
    one step is held to the limits of the first loss and gradient."""
    limits = _json("benchmark", "limits", CELL + ".json")["limits"]
    values, _ = mode.gaps(readings, ref)
    if len(readings["losses"]) < len(ref["losses"]):
        limits = {k: v for k, v in limits.items()
                  if k.startswith(("loss1_", "grad_", "early_rows_"))}
    rows = compare.verdict(values, {k: v for k, v in limits.items()
                                    if k in values})
    return all(ok for *_, ok in rows), [n for n, _, _, ok in rows if not ok], \
        values


def test_the_program_is_correct_under_the_committed_limits(rehearsed):
    mode, ref = rehearsed
    ok, failed, values = _verdict(mode, mode.readings, ref)
    assert ok, (failed, values)
    assert values["early_rows_grad_diff"] < 1e-4


@pytest.mark.parametrize("how", [{"fault": f, "steps": 1} for f in (
    "causal_2L", "positions_2L", "no_weight", "own_clean_block")]
    + [{"numerics": "params_bfloat16"}],
    ids=lambda h: h.get("fault") or h["numerics"])
def test_the_faults_and_the_parameters_control_are_not_correct(rehearsed, how):
    """Through the mode's own comparison and the limits FILE of the cell:
    each fault of the mechanism put in the program's place, and the
    parameters held in bfloat16, come out not ``correct`` even at the
    rehearsal's sizes (the faults by the first loss and gradient alone)."""
    mode, ref = rehearsed
    ok, failed, values = _verdict(mode, mode.reference_readings(**how), ref)
    assert not ok and failed, values


@pytest.mark.parametrize("numerics", ["float8", "router_bfloat16"])
def test_the_products_controls_stand_apart_at_the_rehearsals_sizes(
        rehearsed, numerics):
    """At hidden 64 a rounding of the operands moves the numbers far less
    than at 2,048, so the cell's limits (set on the chip at the cell's
    sizes, PERF.md 2b) do not part these two from float32 here; in
    float32 the program sits at round-off, and each control stands a
    hundred times over it in some number."""
    mode, ref = rehearsed
    sound, _ = mode.gaps(mode.readings, ref)
    values, _ = mode.gaps(mode.reference_readings(numerics=numerics), ref)
    names = ("loss1_gap", "grad_gap", "grad_gap_median_leaf",
             "grad_diff_median_leaf", "early_rows_grad_diff")
    assert any(values[n] > max(100 * sound[n], 1e-4) for n in names), values


def test_the_early_rows_number_reads_single_rows():
    """A column of the head's gradient that one masked row's label owns
    is that row's -w / L times its final hidden state: scaling one early
    row's columns moves the number, scaling a late row's does not."""
    from benchmark.modes.fit_block_diffusion import EARLY_ROWS, HEAD, Mode
    mode = Mode.__new__(Mode)
    rng = np.random.default_rng(0)
    L, V = 256, 4096
    y = rng.permutation(V)[:L][None].astype(np.int32)
    w = np.where(rng.random((1, L)) < 0.5, 2.0, 0.0).astype(np.float32)
    mode.noise = type("N", (), {"seen": [(None, y, w)]})()
    g = rng.standard_normal((16, V)).astype(np.float32)
    masked = np.flatnonzero(w[0] > 0)
    early, late = y[0][masked[:EARLY_ROWS]], y[0][masked[EARLY_ROWS:]]
    ref = {"first_grad": {HEAD: g}}
    assert mode.early_rows_grad_diff(ref, ref) == 0.0
    off = g.copy(); off[:, late] *= 3.0
    assert mode.early_rows_grad_diff({"first_grad": {HEAD: off}}, ref) == 0.0
    off = g.copy(); off[:, early] *= 1.5
    assert mode.early_rows_grad_diff({"first_grad": {HEAD: off}}, ref) \
        == pytest.approx(0.5)
