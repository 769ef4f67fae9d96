"""TPU-context test run (reference: tests/python/gpu/ — the whole CPU operator
suite re-executed under the device context, test_operator_gpu.py:5-14).

Unlike tests/conftest.py this does NOT pin JAX to CPU: it targets a real
accelerator and sets the framework default context to mx.tpu(0), so every
`mx.cpu()`-less test path executes on hardware. Run via `ci/run_tests.sh tpu`
(which sets MXNET_TPU_REQUIRE_HW=1 so a green "tpu" stage MEANS the sweep ran
on hardware). A bare `pytest` from the repo root that happens to collect this
directory on a CPU-only host skips it instead of aborting the whole run.
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

_HERE = os.path.dirname(os.path.abspath(__file__))

# Host-side suites that live here because they belong to the TPU build's
# runtime (ci/run_tests.sh faults / telemetry) but exercise no accelerator:
# they run on CPU-only hosts and are exempt from the hardware gate below.
_HOST_ONLY_FILES = {"test_fault_tolerance.py", "test_telemetry.py",
                    "test_pipeline_feed.py", "test_guard.py",
                    "test_analysis.py", "test_elastic.py",
                    "test_cluster_obs.py", "test_native_decode.py",
                    "test_compileobs.py", "test_kv_overlap.py",
                    "test_graphpass.py", "test_server_ha.py"}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "faults: fault-injection / robustness tests (host-only)")
    config.addinivalue_line(
        "markers", "telemetry: runtime-telemetry tests (host-only)")
    config.addinivalue_line(
        "markers", "pipeline: input-pipeline wire/feed tests (host-only)")
    config.addinivalue_line(
        "markers", "guard: training health-guard tests (host-only)")
    config.addinivalue_line(
        "markers", "analysis: fwlint / engine-sanitizer tests (host-only)")
    config.addinivalue_line(
        "markers", "elastic: elastic-membership / reshard tests (host-only)")
    config.addinivalue_line(
        "markers", "server_ha: parameter-server HA (replication / failover) "
                   "tests (host-only)")
    config.addinivalue_line(
        "markers", "perf: communication-overlap / perf-smoke tests "
                   "(host-only)")
    config.addinivalue_line(
        "markers", "compiler: graph-pass pipeline / persistent compile "
                   "cache tests (host-only)")
    config.addinivalue_line("markers", "slow: long-running tests")


def _activate_tpu_context():
    import mxnet_tpu as mx

    mx.test_utils.set_default_context(mx.tpu(0))
    # per-device tolerance (the reference's check_consistency tol matrix gives
    # GPU fp32 1e-3); TPU transcendentals differ from host libm at ~1e-4
    mx.test_utils.set_tolerance_floor(rtol=2e-3, atol=1e-4)
    # the suite also asserts through numpy directly; apply the same floor
    import numpy as np

    _orig = np.testing.assert_allclose

    def _floored(actual, desired, rtol=1e-7, atol=0, **kw):
        return _orig(actual, desired, rtol=max(rtol, 2e-3),
                     atol=max(atol, 1e-4), **kw)

    np.testing.assert_allclose = _floored


def pytest_collection_modifyitems(config, items):
    mine = [it for it in items
            if str(it.fspath).startswith(_HERE)
            and os.path.basename(str(it.fspath)) not in _HOST_ONLY_FILES]
    if not mine:
        return
    import mxnet_tpu as mx

    no_tpu = not mx.context.num_tpus()
    if no_tpu and os.environ.get("MXNET_TPU_REQUIRE_HW") == "1":
        # non-zero: a green "tpu" stage must MEAN the sweep ran on hardware
        pytest.exit("no TPU visible: the tests_tpu suite needs hardware", 2)
    if no_tpu:
        reason = ("no TPU visible (tests/conftest.py pins combined runs to "
                  "CPU); run `ci/run_tests.sh tpu` for the hardware sweep")
        for it in mine:
            it.add_marker(pytest.mark.skip(reason=reason))
        return
    if len(mine) != len(items):
        # mixed collection: the TPU default context + loosened numpy
        # tolerances are process-global and would leak into the CPU suite
        reason = "tests_tpu must run in its own pytest invocation"
        if os.environ.get("MXNET_TPU_REQUIRE_HW") == "1":
            pytest.exit(reason, 2)
        for it in mine:
            it.add_marker(pytest.mark.skip(reason=reason))
        return
    _activate_tpu_context()
