"""Test configuration: force a hermetic 8-device virtual CPU "cluster".

Mirrors the reference's DistributedQueryRunner idea (testing/trino-testing/.../
DistributedQueryRunner.java:108 — a multi-node cluster in one process): we get a
multi-"chip" TPU topology in one process via XLA's host-platform device count, so
sharding/collective paths are exercised without TPU hardware.

Must run before jax is imported anywhere.
"""

import os

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# a bare `pytest` must never take the chip (tier-1 also sets JAX_PLATFORMS=cpu)
jax.config.update("jax_platforms", "cpu")

# Places the persistent compile cache (<checkout>/.jax_cache_tpu unless
# JAX_COMPILATION_CACHE_DIR says otherwise) and keeps every program in it.
# The fixture below drops the jit caches at each module boundary, so modules
# find each other's programs there: cold, tier-1 ran in about 680 s with it against
# more than its 870 s limit when only programs of a second or more were kept.
import trino_tpu  # noqa: E402,F401

import pytest  # noqa: E402


@pytest.fixture(scope="module", autouse=True)
def _fresh_jit_caches_per_module():
    """XLA:CPU reproducibly SEGFAULTS in backend_compile_and_load after
    roughly ~600 in-process compiles (observed at different suite positions
    as tests were added — the trigger tracks the CUMULATIVE compile count,
    not any specific program; every module passes standalone). Dropping the
    accumulated executables at each module boundary keeps the compiler
    inside its working envelope; module-internal caching still amortizes
    the hot fixtures."""
    import jax

    jax.clear_caches()


@pytest.fixture(scope="session")
def tpch_tiny():
    """Tiny deterministic TPC-H runner shared across the test session."""
    from trino_tpu.runtime import LocalQueryRunner

    return LocalQueryRunner.tpch(scale=0.0005)


def pytest_configure(config):
    # "slow" excludes a test from the tier-1 sweep (`-m 'not slow'`)
    config.addinivalue_line("markers", "slow: excluded from the tier-1 run")
