"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's strategy of testing distributed behavior with
``mpirun -np N`` on a single box (ref: tests/unit/CMakeLists.txt:10-46);
here N virtual XLA host devices play the role of MPI ranks. Must run before
jax initializes its backends, hence the env mutation at import time.

``SKYLARK_TEST_TPU=1`` leaves the real backend in place so the
``@pytest.mark.tpu`` on-chip oracle tests (the run-on-target discipline of
ref: tests/unit/CMakeLists.txt:10-46) execute on hardware.
"""

import os

_ON_CHIP = os.environ.get("SKYLARK_TEST_TPU") == "1"

if not _ON_CHIP:
    # the environment, not only the config below, so that the child
    # processes the fleet and multihost tests spawn get the same devices
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax  # noqa: E402

if not _ON_CHIP:
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    if _ON_CHIP and len(devs) != 8:
        pytest.skip(
            "mesh tests need the 8-device virtual CPU mesh; run without "
            "SKYLARK_TEST_TPU=1 (on-chip runs select -m tpu)"
        )
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture()
def mesh1d(devices):
    from libskylark_tpu.parallel import make_mesh

    return make_mesh()


@pytest.fixture()
def mesh2d(devices):
    from libskylark_tpu.parallel import make_mesh

    return make_mesh((2, 4))
