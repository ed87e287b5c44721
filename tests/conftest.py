import os
import sys

# NOTE: do NOT set --xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device (the dry-run sets 512 itself, in-process).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.RandomState(0)
