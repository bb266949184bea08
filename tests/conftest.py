"""Test config: run on a virtual 8-device CPU mesh so sharding/collective
tests work without TPU hardware (SURVEY §4 'TPU-build implication' (b)).

``PADDLE_TPU_TEST_HW=1 pytest -m tpu_hw tests/test_tpu_numerics.py`` keeps
the real accelerator backend instead, for the on-hardware numerics sweep.
"""

import os

_ON_HW = os.environ.get("PADDLE_TPU_TEST_HW") == "1"

if not _ON_HW:
    # plain env vars work when nothing imported jax yet; the config API
    # below also covers a caller that did, as long as no backend has been
    # initialized.
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            flags + " --xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

if not _ON_HW:
    jax.config.update("jax_platforms", "cpu")
    assert jax.default_backend() == "cpu", (
        "tests must run on the virtual CPU mesh; got "
        + jax.default_backend())
    assert len(jax.devices()) == 8


def pytest_collection_modifyitems(config, items):
    import pytest
    skip = pytest.mark.skip(
        reason="hardware numerics sweep: set PADDLE_TPU_TEST_HW=1 and run "
               "on a TPU backend (pytest -m tpu_hw)")
    for item in items:
        if "tpu_hw" in item.keywords and not _ON_HW:
            item.add_marker(skip)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "tpu_hw: runs on the real TPU chip (needs "
        "PADDLE_TPU_TEST_HW=1)")
    config.addinivalue_line(
        "markers", "slow: multi-minute subprocess scenarios excluded "
        "from the quick tier (-m 'not slow'); tools/ci.sh runs them")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _no_kept_flash_traces():
    """The flash kernels' kept jaxprs (``_traced_once``) do not outlive a
    test: what one test traced under its patches is no other's."""
    import sys
    yield
    fa = sys.modules.get("paddle_tpu.pallas.flash_attention")
    if fa is not None:
        fa._TRACED.clear()


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Each test gets fresh default programs + scope (ref tests use
    new Program() + program_guard; this keeps tests independent)."""
    import paddle_tpu as pt
    from paddle_tpu.framework import core, scope, unique_name
    main, startup = core.Program(), core.Program()
    old_main = core.switch_main_program(main)
    old_startup = core.switch_startup_program(startup)
    new_scope = scope.Scope()
    scope._scope_stack.append(new_scope)
    with unique_name.guard():
        yield
    scope._scope_stack.pop()
    core.switch_main_program(old_main)
    core.switch_startup_program(old_startup)
