"""Shared test config: sibling-fixture imports, the ``slow`` marker gate,
and a SIGALRM fallback for ``@pytest.mark.timeout``.

Tier-1 (`PYTHONPATH=src python -m pytest -q`) runs the fast suite; cases
marked ``@pytest.mark.slow`` (the deep serving-fuzz, worker-pool and
tracing profiles, a 3-table kernel-join case) are skipped unless
``--runslow`` is passed.

The threaded worker-pool tests carry ``@pytest.mark.timeout(N)`` so a
pool deadlock fails the test instead of hanging the whole suite.  CI
installs ``pytest-timeout`` (requirements-dev.txt), which honors the
marker natively; when the plugin is absent (bare local env) a SIGALRM
hookwrapper enforces it on POSIX mains threads, and elsewhere the marker
is inert (worker threads are daemons, so an interpreter exit is never
blocked either way).
"""

import os
import signal
import sys

import pytest

sys.path.insert(0, os.path.dirname(__file__))


def pytest_addoption(parser):
    parser.addoption(
        "--runslow",
        action="store_true",
        default=False,
        help="also run tests marked @pytest.mark.slow",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: expensive case, skipped unless --runslow is given"
    )
    if not config.pluginmanager.hasplugin("timeout"):
        # pytest-timeout absent: register the marker ourselves so it does
        # not warn, and enforce it via SIGALRM below
        config.addinivalue_line(
            "markers",
            "timeout(seconds): fail the test if it runs longer "
            "(SIGALRM fallback when pytest-timeout is not installed)",
        )


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip_slow = pytest.mark.skip(reason="slow: pass --runslow to run")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    marker = item.get_closest_marker("timeout")
    plugin = item.config.pluginmanager.hasplugin("timeout")
    if marker is None or plugin or not hasattr(signal, "SIGALRM"):
        yield
        return
    seconds = int(marker.args[0]) if marker.args else 60

    def _alarm(signum, frame):
        raise TimeoutError(
            f"test exceeded {seconds}s (SIGALRM fallback timeout)"
        )

    old = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
