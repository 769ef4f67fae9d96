"""Test configuration: force an 8-device virtual CPU platform.

This is the TPU analog of the reference's CPU-fake-device trick
(tests/python/unittest/test_multi_device_exec.py:20-33 binds graphs across
mx.cpu(1)/mx.cpu(2)): multi-device/mesh tests run against 8 virtual host
devices so sharding logic is exercised without a pod.

jax captures JAX_PLATFORMS at import, so we both set XLA_FLAGS before the
first backend init AND pin the platform via jax.config after import — the
pin is what lets ``mx.tpu(i)`` name a virtual host device here
(context.py ``_pinned_to_cpu``).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")


@pytest.fixture(autouse=True, scope="module")
def _telemetry_flag_as_found():
    """``ServingEngine()`` enables telemetry process-wide and nothing turns
    it off again. ``--dist loadfile`` runs several files in one worker
    process, so every file hands the flag on as it found it: its neighbour
    starts where a process of its own would."""
    from mxnet_tpu import telemetry

    was = telemetry.enabled()
    yield
    (telemetry.enable if was else telemetry.disable)()


@pytest.fixture(params=[1, 2, 4], ids="chunk{}".format)
def chunk(request, monkeypatch):
    """``serving.engine.DECODE_CHUNK`` at 1 (one decode step a dispatch),
    2 and 4: an engine built in the test loops up to that many decode
    steps on the device a dispatch. What such an engine serves, books and
    leaves in its caches is the same at every value."""
    from mxnet_tpu.serving import engine

    monkeypatch.setattr(engine, "DECODE_CHUNK", request.param)
    return request.param


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "faults: fault-injection / robustness tests "
        "(ci/run_tests.sh faults tier; suite in tests_tpu/test_fault_tolerance.py)")
    config.addinivalue_line(
        "markers", "serving: paged-KV serving-engine tests "
        "(ci/run_tests.sh serving runs them alone, slow cases included)")
    config.addinivalue_line("markers", "slow: long-running tests")
