"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding tests run against
``--xla_force_host_platform_device_count=8`` on the CPU backend, which exercises
the same mesh/collective compilation paths XLA uses on a real pod.  Must run
before jax is first imported anywhere in the test session.
"""
import os

# Read by JAX at import, and by mysticeti_tpu.verifier_service as "the CPU
# was asked for by name" (it refuses a CPU that JAX fell back to by itself).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The Ed25519 scan kernel costs ~60s to compile on CPU; importing
# mysticeti_tpu.ops places JAX's persistent compilation cache (the checkout's
# .jax_cache/ unless JAX_COMPILATION_CACHE_DIR is set), so it is paid once.

import pytest


def pytest_collection_modifyitems(config, items):
    # pytest.ini declares kernel tests tier 2 ("JAX kernel/mesh compile-heavy
    # tests (minutes; run tier 2)"); the tier-1 gate selects `-m 'not slow'`.
    # Marking kernel items slow here enforces that declared tiering — a cold
    # compilation cache otherwise blows the tier-1 wall-time budget — while
    # `-m kernel` still selects them for the tier-2 run.
    for item in items:
        if "kernel" in item.keywords:
            item.add_marker(pytest.mark.slow)
