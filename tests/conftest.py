# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# single real CPU device.  Only the dedicated multi-device subprocess tests
# force a fake device count, in their own processes.
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import pytest


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.key(0)
