"""Test configuration.

Tests run on a virtual 8-device CPU mesh (SURVEY.md §7 / driver contract):
multi-chip sharding semantics are validated without TPU hardware, the same
way Trino's DistributedQueryRunner boots a multi-node cluster inside one JVM
(testing/trino-testing/.../DistributedQueryRunner.java:107).

Environment must be set before jax is imported anywhere.
"""

import os
import sys

# tests are forced onto the CPU backend whatever the machine has:
# `import pytest` may already have imported jax via a plugin entrypoint,
# so env vars alone can be too late — the runtime config API below also
# takes effect (backends are still uninitialized at conftest time)
os.environ["JAX_PLATFORMS"] = "cpu"
# test assertions on executor stats (capacity retries, sync counts) assume
# a cold decision state; the on-disk decision cache would let a previous
# pytest session's runs leak in. Tests that exercise persistence opt back
# in with a tmp TRINO_TPU_DATA_CACHE.
os.environ.setdefault("TRINO_TPU_DECISION_CACHE", "0")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# XLA CPU compiler hygiene: one process compiling many hundreds of distinct
# programs (the full TPC-DS sweep) deterministically SEGFAULTS inside
# backend_compile_and_load around the ~80th jit-heavy test — reproduced on
# two unrelated commits, independent of stack size, with the persistent
# cache off, so it is backend-state accumulation, not this engine. Dropping
# the live executables every N tests keeps the compiler healthy; the
# recompiles cost seconds on CPU.
#
# The SLOW mesh tier (tests/test_distributed.py, -m slow) additionally hits
# an intermittent virtual-device collective rendezvous abort
# (rendezvous.cc "only 7 of 8 arrived") after ~44 jit-heavy mesh tests in
# one process — each test passes in isolation, and the tier passes under
# process isolation: run it as `pytest tests/test_distributed.py -m slow
# -n 2` (xdist). The quick tier (the CI gate) is unaffected.
# ---------------------------------------------------------------------------

_CLEAR_EVERY = 10
_test_count = [0]


def pytest_runtest_teardown(item, nextitem):
    _test_count[0] += 1
    if _test_count[0] % _CLEAR_EVERY == 0:
        import gc
        jax.clear_caches()
        gc.collect()      # drop executables whose last ref died mid-test
