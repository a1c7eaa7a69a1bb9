"""Test configuration.

The suite runs on the CPU, named so: ``JAX_PLATFORMS=cpu`` is exported
(servers the e2e tests launch inherit it) before jax is imported.
Multi-chip sharding tests run on a virtual 8-device CPU mesh
(``xla_force_host_platform_device_count``), per the reference's
"multi-node-without-a-cluster" test strategy (SURVEY.md §4): fake the fleet,
test the real algorithms.  The chip is reached only through the chip tool
(``chip_smoke.py``, ``tools/onchip_pallas_check.py``), never from here.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

# Arm the lock-order witness for the whole suite (lockwitness.py): every
# lock the concurrency registry wires through witness_lock records its
# per-thread acquisition order, and tests/test_concurrency.py asserts the
# observed graph acyclic AND covered by the static lock-order rule.  An
# armed acquisition costs a thread-local list append, so the whole suite
# runs witnessed.
os.environ.setdefault("LIG_LOCK_WITNESS", "1")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax  # noqa: E402

from llm_instance_gateway_tpu import runtime  # noqa: E402

# Persistent XLA compile cache for the suite AND the servers it launches
# (the helper exports the variable): single-core XLA:CPU compiles dominate
# the wall time, and most programs recur run over run.  The directory is
# GITIGNORED, so entries never leave the host that wrote them.
runtime.configure_compile_cache(".jax_cache_tests")
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


def pytest_configure(config):
    """Build the C++ hot-path libraries BEFORE collection so the
    native-scheduler parity fuzz (tests/test_native_scheduler.py) actually
    executes: on a fresh checkout the committed .so can look stale
    (arbitrary mtimes) and the first in-test build attempt races the
    collection-time skipif.  When the toolchain is genuinely absent the
    tests still skip — but with a LOUD warning here instead of a silent
    's' in the dots."""
    import warnings

    from llm_instance_gateway_tpu.gateway.scheduling import native

    if not native.available():
        warnings.warn(
            "native/libligsched.so could not be built or loaded — the "
            "native-scheduler parity fuzz (tests/test_native_scheduler.py) "
            "will be SKIPPED. Install g++/make or run `make native` and "
            "re-run.",
            stacklevel=1,
        )
