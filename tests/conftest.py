import os

import pytest


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")
    # the stress marker is registered once, in pyproject.toml


@pytest.fixture(scope="session", autouse=True)
def lock_witness():
    """With REPRO_LOCK_WITNESS=1, every ProfiledLock in the process
    reports to a LockWitness configured from analysis/lock_hierarchy.toml
    for the whole session; any observed acquisition order contradicting
    the declared hierarchy (or completing a cycle) fails the suite at
    teardown.  Off by default: zero setup, one is-None test per lock op."""
    if os.environ.get("REPRO_LOCK_WITNESS") != "1":
        yield None
        return
    from repro import obs

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    hierarchy = os.path.join(here, "analysis", "lock_hierarchy.toml")
    w = obs.install_witness(obs.LockWitness.from_hierarchy(hierarchy))
    try:
        yield w
        w.check()
    finally:
        obs.uninstall_witness()
