# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see
# the real device count (1 CPU).  Multi-device tests spawn subprocesses
# (tests/test_distributed.py) and the 512-way dry-run has its own entry
# point (repro.launch.dryrun).
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import pytest


def pytest_addoption(parser):
    parser.addoption("--run-slow", action="store_true", default=False,
                     help="run slow tests (full-size wavelength MILP etc.)")


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--run-slow"):
        return
    skip = pytest.mark.skip(reason="slow; use --run-slow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(params=["coo", "tiles"])
def operator(request, monkeypatch):
    """The operator PDHG applies K with: `coo` as a CPU runs it, `tiles`
    as a TPU runs it (solver._tiled_spmv forced true, so the CPU runs
    the chip's dense-tile operator)."""
    if request.param == "tiles":
        from repro.core import solver
        monkeypatch.setattr(solver, "_tiled_spmv", lambda: True)
    return request.param
