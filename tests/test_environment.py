"""Environment sanity: the mesh suites must not silently evaporate.

Every multi-device test skips with a "needs the 8-device CPU mesh" guard; a
misconfigured runner (e.g. a caller-preset XLA_FLAGS without
--xla_force_host_platform_device_count) would skip them all and still report
green (VERDICT r2 weak #7). This test turns that silent degradation into a
loud failure; set KLLMS_ALLOW_NO_MESH=1 to acknowledge a deliberately
mesh-less run.
"""

import os

import jax
import pytest


def test_virtual_mesh_is_present():
    if os.environ.get("KLLMS_ALLOW_NO_MESH"):
        pytest.skip("mesh requirement explicitly waived via KLLMS_ALLOW_NO_MESH")
    assert len(jax.devices()) >= 8, (
        f"only {len(jax.devices())} JAX device(s) visible — the 8-device "
        "virtual CPU mesh is missing, so every mesh-marked suite would "
        "silently skip. tests/conftest.py appends "
        "--xla_force_host_platform_device_count=8 to XLA_FLAGS unless the "
        "caller already set a conflicting value; fix the environment or set "
        "KLLMS_ALLOW_NO_MESH=1 to run mesh-less deliberately."
    )


def test_platform_is_cpu():
    """Tests must run on the virtual CPU platform: they assume host execution
    and must not take the one chip a serving process may be holding."""
    assert jax.default_backend() == "cpu", jax.default_backend()
