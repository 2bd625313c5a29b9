"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; the collective logic is
validated on host-platform virtual devices instead — the "fake backend"
the reference never had (SURVEY.md §4). XLA_FLAGS carries the device
count to the subprocesses some tests start.
"""

import os

if os.environ.get("TTS_TEST_TPU"):
    # hardware mode: keep the attached TPU backend so the pallas-kernel
    # parity tests (tests/test_pallas_tpu.py) run; tests that need the
    # 8-device virtual mesh are skipped below when fewer chips exist
    import jax  # noqa: F401

    def pytest_collection_modifyitems(config, items):
        import jax as _jax

        import pytest as _pytest
        if _jax.device_count() >= 8:
            return
        skip = _pytest.mark.skip(
            reason="needs the 8-device mesh (CPU mode or a full slice)")
        for item in items:
            if ("distributed" in item.nodeid
                    or "test_engine_distributed" in item.nodeid):
                item.add_marker(skip)
else:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()

    import jax

    jax.config.update("jax_num_cpu_devices", 8)

    assert jax.device_count() == 8, jax.devices()
