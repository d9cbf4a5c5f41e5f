"""chip_smoke.py rehearsed on the CPU: its phase functions at tiny size
(what a CPU cannot show — the platform, compiled kernels, the fused-tier
selection counts — is dropped with ``chip=False``), and the script itself
refusing to pass without a chip."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_device_phase_reads_the_device(smoke):
    info = smoke.phase_device(1, chip=False)
    assert info == {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                    "count": len(jax.devices())}
    with pytest.raises(smoke.SmokeFailure, match="no TPU"):
        smoke.phase_device(1)                 # the chip check itself
    with pytest.raises(smoke.SmokeFailure, match="needs 4096"):
        smoke.phase_device(4096, chip=False)


def test_kernels_phase(smoke):
    from deeplearning4j_tpu.ops import helpers
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    try:
        verdicts = smoke.phase_kernels(chip=False)
        assert verdicts["interpret_mode"] is True
        with pytest.raises(smoke.SmokeFailure, match="interpret mode"):
            smoke.phase_kernels()             # on the chip this must be Mosaic
    finally:
        pk._disabled.clear()
        helpers.reset_validation()


def test_fit_phase_vgg16_full_width_tiny_batch(smoke):
    info = smoke.phase_fit(seed=0, batch=8, steps=4, chip=False)
    assert info["params"] == 15_245_130       # full width: 13 convs, FC 512
    assert info["scores"][-1] < info["scores"][0]
    with pytest.raises(smoke.SmokeFailure, match="default precision"):
        smoke.phase_fit(seed=0, batch=8, steps=2)   # f32 here, bf16 there


def test_serve_phase_over_http(smoke):
    info = smoke.phase_serve(seed=0, ks=(1, 3, 4))
    assert info["output_programs"] <= len(info["warmed_ladder"])
    assert info["served_requests"] == 3
    assert info["max_abs_diff_vs_output"] <= smoke.SERVE_ATOL


def test_sharded_phase_on_four_virtual_devices(smoke):
    info = smoke.phase_sharded(seed=0, batch=8, steps=3,
                               devices=jax.devices()[:4], chip=False)
    assert info["mesh"] == {"data": 2, "fsdp": 2}
    for res in (info["params"], info["updater"]):
        assert len(res["bytes_per_device"]) == 4
        assert max(res["bytes_per_device"].values()) \
            <= 0.51 * res["total_bytes"]


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in-repo-no-chip", "script-alone"])
def test_script_fails_without_a_chip(tmp_path, alone):
    """No accelerator, or nothing of the repo beside the script: exit
    non-zero and print no result line."""
    script, cwd = SCRIPT, ROOT
    if alone:
        script = shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
        cwd = tmp_path
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, timeout=240, env=env, cwd=cwd)
    assert p.returncode != 0, p.stdout[-2000:]
    assert '"ok"' not in p.stdout
    for line in p.stdout.splitlines():        # whatever it printed, no result
        if line.startswith("{"):
            assert "ok" not in json.loads(line)
