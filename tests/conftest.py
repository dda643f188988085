"""Shared pytest config for the tier-1 suite.

Registers the ``slow`` marker (long-running / TPU-scale parametrizations)
and skips those tests by default so bare-CPU runs stay fast — opt in with
``--runslow`` or ``RUN_SLOW=1``.  Everything here must work on a bare
``jax + pytest`` environment (no hypothesis, no TPU).
"""
import os

import pytest


def pytest_addoption(parser):
    parser.addoption(
        "--runslow",
        action="store_true",
        default=False,
        help="also run tests marked @pytest.mark.slow",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test (skipped unless --runslow / RUN_SLOW=1)"
    )


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow") or os.environ.get("RUN_SLOW", "") not in ("", "0"):
        return
    skip_slow = pytest.mark.skip(reason="slow: pass --runslow (or RUN_SLOW=1) to run")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)
