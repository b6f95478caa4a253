"""Test configuration.

Mirrors the reference's "every distributed behavior has an in-process seam"
strategy (SURVEY.md §4): all tests run on CPU with 8 virtual XLA devices so
mesh/collective paths are exercised without TPU hardware.

The CPU platform is pinned here, before any backend starts, so that a bare
`pytest` on a machine with a chip does not take the chip; `JAX_PLATFORMS=cpu`
in the environment does the same for any other entry point.
"""
import os

# subprocesses the tests spawn inherit the 8 virtual devices through XLA_FLAGS
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

# Adaptive gates (engine/autotune.py) default OFF under tests: many tests
# assert a SPECIFIC fast path engaged (np_fast_polls, wholeplan_native,
# device joins), and autotune's exploration probes deliberately flip
# individual queries onto the other arm — bit-equal results, different
# counters.  Autotune's own tests opt back in via
# flags.set_for_testing("PX_AUTOTUNE", True).
os.environ.setdefault("PX_AUTOTUNE", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(42)


#: shared marker for tests that read the reference pxl_scripts checkout
def _reference_mounted() -> bool:
    from pixie_tpu.scripts import REFERENCE_BUNDLE

    return REFERENCE_BUNDLE.is_dir()


requires_reference = pytest.mark.skipif(
    not _reference_mounted(),
    reason="reference pxl_scripts checkout not mounted")
