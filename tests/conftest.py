"""Test configuration: force the CPU backend with 8 virtual devices so
multi-chip sharding tests run without TPU hardware (the cuDNN-vs-builtin
cross-check pattern of the reference, SURVEY.md §4, becomes
TPU-vs-CPU-interpreter: the same code paths compile on both).  What the
chip's own compiler says of the kernels is in tests/test_tpu_compile.py.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

assert jax.default_backend() == "cpu", jax.default_backend()


import pytest  # noqa: E402


@pytest.fixture
def dl4j_sanitize():
    """Arm the runtime sanitizer (transfer guard + debug-nans + retrace
    budget) for one test — the fixture surface of
    ``deeplearning4j_tpu.analysis.sanitizer`` (docs/ANALYSIS.md)."""
    from deeplearning4j_tpu.analysis import sanitizer
    with sanitizer.sanitize(modes=("transfer", "nans", "retrace")):
        yield sanitizer


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`; register the marker so the serving
    # load-generator test (and future slow cases) don't warn
    config.addinivalue_line(
        "markers", "slow: long-running case excluded from tier-1 runs")
