"""Test configuration: force a pure-CPU JAX with 8 virtual devices so
sharding tests run without TPU hardware (SURVEY.md §4 item 5 — the reference
simulates clusters with Spark local[*]; XLA host devices play that role).

The platform-forcing dance lives in
deeplearning4j_tpu.util.virtual_devices.ensure_cpu_devices, shared with
__graft_entry__.dryrun_multichip. It must run before any jax backend
initialization (platform and device count are read once, at first use).
"""

from deeplearning4j_tpu.util.virtual_devices import ensure_cpu_devices

ensure_cpu_devices(8)

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

assert jax.default_backend() == "cpu"


@pytest.fixture
def rng():
    return np.random.default_rng(42)
