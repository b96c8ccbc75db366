"""trino_tpu — a TPU-native distributed SQL query engine.

A ground-up reimplementation of the capabilities of trinodb/trino (the Java MPP SQL
engine) with a JAX/XLA/Pallas execution substrate: SQL is parsed/analyzed/planned in
Python (cold path, like Trino's coordinator), and query fragments execute as compiled
XLA programs over device-resident columnar Pages, sharded across a TPU mesh with XLA
collectives playing the role of Trino's HTTP shuffle.

See SURVEY.md at the repo root for the reference blueprint this build follows.
"""

import os as _os

import jax as _jax

# 64-bit types are part of the SQL contract (BIGINT/DOUBLE/DECIMAL sums). On TPU,
# int64/float64 are emulated but correct; hot kernels downcast where types allow.
_jax.config.update("jax_enable_x64", True)

# The persistent compile cache of every entry point (CLI, servers, tools,
# tests). JAX reads JAX_COMPILATION_CACHE_DIR itself, so where it is set no
# directory is set in code; otherwise one fixed directory in the checkout,
# because the path is part of what a later start has to find again.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(
            _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
            ".jax_cache_tpu",
        ),
    )
# Every program is kept, not only those that took JAX's default of a second
# to compile: a query is dozens of operator programs, half of which compile in
# under a quarter of a second on a v5e (CHANGES.md, PR 21), and a served
# process that restarts should compile none of them again.
_jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
# XLA's own sub-caches stay off. XLA:CPU's AOT entries pin host machine
# features, and loading them on a host without (e.g.) +prefer-no-gather
# segfaulted the test suite in backend_compile_and_load; JAX's executable
# cache is feature-safe and keeps most of the win. Nothing on a TPU uses them.
_jax.config.update("jax_persistent_cache_enable_xla_caches", "none")

__version__ = "0.1.0"

from .spi.types import (  # noqa: E402,F401
    BOOLEAN,
    TINYINT,
    SMALLINT,
    INTEGER,
    BIGINT,
    REAL,
    DOUBLE,
    VARCHAR,
    DATE,
    TIMESTAMP,
    UNKNOWN,
    Type,
    decimal_type,
    varchar_type,
    parse_type,
)
from .spi.page import Column, Dictionary, Page  # noqa: E402,F401
from . import native  # noqa: E402,F401
