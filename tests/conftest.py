"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests see 1 CPU device by
design (the 512-device flag belongs to launch.dryrun only)."""
import pytest


@pytest.fixture(scope="session")
def rmat_graph():
    from repro.graph.generators import rmat

    return rmat(300, 2400, seed=7)


@pytest.fixture(scope="session")
def small_powerlaw():
    from repro.graph.generators import rmat

    return rmat(64, 512, seed=3)
