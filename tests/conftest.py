"""Tests run on the single real CPU device (the 512-device forcing is
confined to repro.launch.dryrun, which tests never import)."""
import os

# make sure nothing leaked the dry-run device forcing into the test env
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" in flags:
    os.environ["XLA_FLAGS"] = " ".join(
        f for f in flags.split() if "host_platform_device_count" not in f)

import jax
import pytest


# ---------------------------------------------------------------------------
# fast tier: `pytest -m fast` runs a sub-minute smoke subset (the default
# pre-commit check, see Makefile).  Membership is by module: these modules
# use stub engines / pure-python structures, not jitted model forwards.
# ---------------------------------------------------------------------------
_FAST_MODULES = {
    "test_configs", "test_stage_graph", "test_connector", "test_sharding",
    "test_scheduler", "test_worker_backend", "test_kv_prefix_cache",
    "test_replicas", "test_radix_index", "test_serve_config",
    "test_process_worker", "test_analyzer",
}


def pytest_collection_modifyitems(items):
    for item in items:
        if item.module.__name__ in _FAST_MODULES:
            item.add_marker(pytest.mark.fast)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
