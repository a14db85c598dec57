"""Suite-wide fixtures/hooks: per-test wall-clock timeouts, and the grids
of a jaxpr's Pallas kernels.

The container has no pytest-timeout plugin, so the timeout is a SIGALRM
alarm around each test call: a hung kernel interpret run or subprocess
fails loudly (with a stack) instead of wedging the whole suite.  Override
per test with ``@pytest.mark.timeout(seconds)``; 0 disables.
"""

from __future__ import annotations

import signal

import jax
import pytest

DEFAULT_TIMEOUT_S = 300


class TestTimeout(Exception):
    pass


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    marker = item.get_closest_marker("timeout")
    seconds = int(marker.args[0]) if (marker and marker.args) \
        else DEFAULT_TIMEOUT_S
    if seconds <= 0 or not hasattr(signal, "SIGALRM"):
        return (yield)

    def _alarm(signum, frame):
        raise TestTimeout(f"{item.nodeid} exceeded {seconds}s")

    old = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(seconds)
    try:
        return (yield)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def _pallas_grids(jaxpr):
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(tuple(eqn.params["grid_mapping"].grid))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            grids += _pallas_grids(sub)
    return grids


@pytest.fixture
def pallas_grids():
    """``pallas_grids(jaxpr)``: the grid of every ``pallas_call`` in a
    jaxpr, nested ones included, in order."""
    return _pallas_grids
