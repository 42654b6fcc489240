"""Shared test fixtures.

On first use the planner resolves its default ``CalibrationProfile``:
the checked-in reference profile
(``repro/core/calibration/reference_profile.json``) when it was fitted
on the running JAX backend (``cpu``), else the analytic defaults.  The
unit suites, however, pin their expectations
(variant crossovers, tier thresholds, admission estimates) to the
*analytic* constants, so every test runs with calibration reset to the
analytic defaults; the reference profile's own coverage lives in the
dedicated roundtrip tests (``tests/test_pools.py``), which opt back in
explicitly via ``planner.load_reference_calibration()``.
"""
import pytest

from repro.core import planner as P


@pytest.fixture(autouse=True)
def _analytic_calibration():
    """Pin the analytic planner constants around every test."""
    P.set_calibration(None)
    yield
    P.set_calibration(None)
