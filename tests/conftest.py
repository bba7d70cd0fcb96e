"""Test config: force an 8-device virtual CPU platform before JAX inits.

Multi-chip sharding logic (TP/SP meshes, ring collectives) is tested on
virtual CPU devices exactly as the driver's dryrun does — see SURVEY.md §4's
"multi-host logic tests via JAX multi-process simulation on CPU devices".
"""

import os

# Set in the environment as well as in the live config below, so that the
# subprocesses the tests start inherit the same 8-device CPU platform.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: test runs under asyncio.run")
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 selection (-m 'not slow') to keep "
        "the suite inside the CI wall-clock budget; run explicitly with "
        "-m slow or no marker filter")


# -- per-test duration capture (scripts/check_tier1_budget.py's input) -------
# Every run records setup+call+teardown seconds per test. Set
# SHAI_TEST_DURATIONS=<path> to write the JSON snapshot at session end;
# tests/tier1_durations.json is the committed snapshot the budget gate
# reads (regenerate it with a full run on the CI container when timings
# shift materially).

_DURATIONS = {}


def pytest_runtest_logreport(report):
    _DURATIONS[report.nodeid] = (_DURATIONS.get(report.nodeid, 0.0)
                                 + getattr(report, "duration", 0.0))


def pytest_sessionfinish(session, exitstatus):
    import json

    path = os.environ.get("SHAI_TEST_DURATIONS", "")
    if path and _DURATIONS:
        with open(path, "w") as f:
            json.dump({k: round(v, 3) for k, v in sorted(_DURATIONS.items())},
                      f, indent=1)


def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests via asyncio.run (pytest-asyncio isn't baked in)."""
    import inspect
    import asyncio

    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {k: pyfuncitem.funcargs[k] for k in pyfuncitem._fixtureinfo.argnames}
        asyncio.run(fn(**kwargs))
        return True
    return None


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs
