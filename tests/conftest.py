"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches must
see the single real CPU device."""
import os

# keep test threads polite on shared CI boxes
os.environ.setdefault("XLA_FLAGS", "")

import jax
import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def pytest_configure(config):
    np.set_printoptions(precision=4, suppress=True)
