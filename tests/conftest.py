"""Test harness config: pin JAX to the CPU with an 8-device virtual
mesh (SURVEY §4). The config.update below must run before any backend
initialization; it is also what makes `mx.tpu()` mean a host device
here (context.py).
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-process spawns, example smoke runs, heavy model "
        "tests — tier-1 is `pytest -m 'not slow'`; the FULL suite "
        "remains the snapshot gate")


@pytest.fixture(scope="session", autouse=True)
def _flight_bundles_out_of_the_checkout(tmp_path_factory):
    """Flight dumps and fleet bundles default to the working directory
    (`MXNET_TPU_FLIGHT_DIR` is the deployment's setting). A test that
    sets neither a directory nor the variable would leave them in the
    checkout, so the session points the variable at a temp directory;
    a test's own `monkeypatch.setenv` still wins."""
    with pytest.MonkeyPatch.context() as mp:
        if "MXNET_TPU_FLIGHT_DIR" not in os.environ:
            mp.setenv("MXNET_TPU_FLIGHT_DIR",
                      str(tmp_path_factory.mktemp("flight")))
        yield


# tier-1 regression floor: a FULL-suite run (anything that collected at
# least the floor) must pass at least this many tests. Single-file and
# -k subset runs collect fewer and are exempt. Raise this when the
# suite grows — never lower it.
TIER1_PASSED_FLOOR = 1192


def pytest_sessionfinish(session, exitstatus):
    if session.config.option.collectonly:
        return
    if getattr(session, "testscollected", 0) < TIER1_PASSED_FLOOR:
        return  # subset run, floor does not apply
    passed = getattr(session, "testscollected", 0) - \
        getattr(session, "testsfailed", 0)
    # deselected/skipped tests never ran; only count hard failures
    # against the floor
    if passed < TIER1_PASSED_FLOOR:
        session.exitstatus = 1
        rep = session.config.pluginmanager.get_plugin("terminalreporter")
        if rep is not None:
            rep.write_line(
                f"tier-1 floor violated: {passed} < "
                f"{TIER1_PASSED_FLOOR} passing tests", red=True)
