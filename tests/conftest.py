"""Test environment: force JAX onto a virtual 8-device CPU platform so
multi-chip sharding paths compile and execute without TPU hardware.
Must run before any jax import (pytest loads conftest first). The env
dance lives in ``windflow_tpu.mesh.ensure_virtual_devices`` — the one
definition the mesh scripts (bench_mesh / soak_mesh / chaos) share, so
no script or test hand-rolls XLA_FLAGS anymore."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from windflow_tpu.mesh import (DEFAULT_VIRTUAL_DEVICES,  # noqa: E402
                               ensure_virtual_devices)

ensure_virtual_devices(DEFAULT_VIRTUAL_DEVICES)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    """The virtual 8-device mesh platform: skips when the interpreter
    came up with fewer devices (jax initialized before the env override
    could land). Mesh tests take this fixture instead of hand-rolling
    ``skipif(len(jax.devices()) < 8)``."""
    import jax
    if len(jax.devices()) < DEFAULT_VIRTUAL_DEVICES:
        pytest.skip(f"needs {DEFAULT_VIRTUAL_DEVICES} virtual devices, "
                    f"have {len(jax.devices())}")
    return jax.devices()[:DEFAULT_VIRTUAL_DEVICES]


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running matrix tests excluded from tier-1 "
        "(-m 'not slow')")
    config.addinivalue_line(
        "markers",
        "chaos: randomized crash-injection sweeps (scripts/chaos.py); "
        "run explicitly with -m chaos")
    config.addinivalue_line(
        "markers",
        "mesh: needs the virtual 8-device mesh platform "
        "(ensure_virtual_devices; auto-skipped when devices are short)")


def pytest_collection_modifyitems(config, items):
    """``mesh``-marked tests auto-skip when the device count is short —
    the shared replacement for each mesh test's hand-rolled skipif."""
    import jax
    if len(jax.devices()) >= DEFAULT_VIRTUAL_DEVICES:
        return
    skip = pytest.mark.skip(
        reason=f"needs {DEFAULT_VIRTUAL_DEVICES} virtual devices "
               f"(ensure_virtual_devices ran too late?)")
    for item in items:
        if "mesh" in item.keywords:
            item.add_marker(skip)
