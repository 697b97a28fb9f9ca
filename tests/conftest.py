"""Shared test fixtures.  NOTE: XLA_FLAGS device-count tricks are deliberately
NOT set here — the main pytest process sees the 1 real CPU device; the
multi-device tests start subprocesses that set it themselves."""
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
