"""Shared test fixtures. NOTE: do NOT set XLA_FLAGS device-count here —
smoke tests and benches must see the real single CPU device; only
launch/dryrun.py forces 512 placeholder devices (in its own process)."""
import jax
import pytest


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.PRNGKey(0)
