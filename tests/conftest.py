"""Test configuration: force an 8-device virtual CPU mesh.

Unit tests run on the XLA CPU backend with 8 virtual devices (SURVEY.md §4:
"strictly better than the reference's fake-device story"); the chip is
driven by chip_smoke.py and by the `tpu`-marked tier of test_tpu_hw.py
(PADDLE_TPU_TESTS=1), which leaves JAX on its default backend.
"""

import os

import pytest

TPU_MODE = os.environ.get("PADDLE_TPU_TESTS") == "1"

# children spawned by tests inherit the device count through the env
os.environ.setdefault("JAX_NUM_CPU_DEVICES", "8")

import jax

if not TPU_MODE:
    # must happen before the CPU client is instantiated
    jax.config.update("jax_num_cpu_devices", 8)
    jax.config.update("jax_platforms", "cpu")

import paddle_tpu  # noqa: E402

if not TPU_MODE:
    paddle_tpu.set_device("cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tpu: hardware smoke test — runs only with PADDLE_TPU_TESTS=1 "
        "(one-command TPU tier: PADDLE_TPU_TESTS=1 pytest -m tpu tests/)")
    config.addinivalue_line(
        "markers",
        "slow: long-running tier-2 test — excluded from the tier-1 "
        "`-m 'not slow'` run")


def pytest_collection_modifyitems(config, items):
    for item in items:
        if "tpu" in item.keywords and not TPU_MODE:
            item.add_marker(pytest.mark.skip(
                reason="TPU hardware tier (set PADDLE_TPU_TESTS=1)"))
        elif "tpu" not in item.keywords and TPU_MODE:
            item.add_marker(pytest.mark.skip(
                reason="CPU-mesh test skipped in TPU hardware mode"))
