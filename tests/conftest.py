"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; the collective logic is
validated on host-platform virtual devices instead — the "fake backend"
the reference never had (SURVEY.md §4). XLA_FLAGS carries the device
count to the subprocesses some tests start.
"""

import os

if os.environ.get("TTS_TEST_TPU"):
    # hardware mode: keep the attached TPU backend so the pallas-kernel
    # parity tests (tests/test_pallas_tpu.py) run; a distributed test is
    # skipped below when it needs more chips than are attached: its
    # `n_devices` parameter, or else the 8-device mesh of CPU mode
    import jax  # noqa: F401

    def pytest_collection_modifyitems(config, items):
        import jax as _jax

        import pytest as _pytest
        have = _jax.device_count()
        for item in items:
            if "distributed" not in item.nodeid:
                continue
            callspec = getattr(item, "callspec", None)
            need = (callspec.params.get("n_devices", 8)
                    if callspec is not None else 8)
            if need > have:
                item.add_marker(_pytest.mark.skip(
                    reason=f"needs {need} devices, {have} attached"))
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
