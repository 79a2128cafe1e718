# NOTE: no XLA_FLAGS here on purpose — smoke tests must see the real single
# CPU device (the 512-fake-device setting belongs to repro.launch.dryrun only).
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
