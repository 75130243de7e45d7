"""Test configuration: CPU backend with 8 virtual devices, float64.

The suite runs on a virtual 8-device CPU mesh (the reference tests the MPI
build by launching the same suite under mpiexec; we test the sharded path by
forcing ``xla_force_host_platform_device_count=8`` — SURVEY.md §4) and in
double precision so golden comparisons can use the reference's 1e-10
tolerance.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Runtime lock-order validation (quest_tpu/testing/lockcheck.py): ON by
# default in the test tiers (QUEST_TPU_LOCKCHECK=0 opts out). The module
# is loaded STANDALONE by file path — importing quest_tpu.testing here
# would run the package __init__ and create its module-level locks
# (e.g. the global MetricsRegistry) before install() could track them.
# State is process-global (anchored on the threading module), so the
# copy tests import through the package shares this one's graph.
os.environ.setdefault("QUEST_TPU_LOCKCHECK", "1")
_lockcheck = None
if os.environ["QUEST_TPU_LOCKCHECK"] not in ("0", "", "off"):
    import importlib.util as _ilu

    _lc_spec = _ilu.spec_from_file_location(
        "quest_tpu_lockcheck_boot",
        os.path.join(os.path.dirname(__file__), os.pardir, "quest_tpu",
                     "testing", "lockcheck.py"))
    _lockcheck = _ilu.module_from_spec(_lc_spec)
    _lc_spec.loader.exec_module(_lockcheck)
    _lockcheck.install()

import jax  # noqa: E402

# Tests run on the CPU in double precision; the in-process config update
# selects the CPU before the first backend initialisation.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


# The "fast" tier (VERDICT r4 item 8): essential + golden + exchange core,
# guaranteed to finish inside any bounded driver budget (`pytest -m fast`
# < 2 min on this box; README "Testing").
FAST_MODULES = {
    "test_essential", "test_golden", "test_golden_ref", "test_exchange",
    "test_validation_taxonomy", "test_comm_trace", "test_serve_trace",
    "test_chaos_trace", "test_trace_io", "test_obs_console",
    "test_traj_trace", "test_mxu_saturation", "test_grad_trace",
    "test_sched_trace", "test_evolve_trace", "test_netserve_wire",
    "test_wire_trace",
}


def pytest_collection_modifyitems(config, items):
    """Run the essential tier first (the reference runs tests/essential/
    before everything and aborts on failure — `QuESTTest/__main__.py`),
    and mark the fast tier."""
    items.sort(key=lambda it: 0 if "test_essential" in it.nodeid else 1)
    for it in items:
        mod = it.nodeid.split("::")[0].rsplit("/", 1)[-1]
        if mod.endswith(".py"):
            mod = mod[:-3]
        if mod in FAST_MODULES:
            it.add_marker(pytest.mark.fast)


@pytest.fixture(scope="session", autouse=True)
def _lockcheck_gate():
    """Session-end gate for the runtime lock-order validator: zero
    :class:`LockOrderViolation` recorded (even ones swallowed by broad
    recovery handlers downstream) and an acyclic acquisition graph.
    A violation here is a latent deadlock — fix the nesting order."""
    yield
    if _lockcheck is not None:
        _lockcheck.assert_clean()


@pytest.fixture
def env():
    import quest_tpu as qt
    return qt.createQuESTEnv(num_devices=1, seed=[12345])


@pytest.fixture
def mesh_env():
    import quest_tpu as qt
    return qt.createQuESTEnv(num_devices=8, seed=[12345])


@pytest.fixture
def rng():
    return np.random.default_rng(20260729)
