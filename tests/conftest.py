import jax
import pytest

# Smoke tests and benches see the single real CPU device: nothing here sets a
# host device count.


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration tests")
