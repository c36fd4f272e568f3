"""trino_tpu — a TPU-native distributed SQL query engine.

A brand-new framework with the capabilities of Trino (reference:
/root/reference, see SURVEY.md): SQL text in, cost-based planning into
fragmented distributed plans, and a columnar operator pipeline executed as
JAX/XLA programs sharded over a TPU mesh.

Where Trino generates JVM bytecode per query (sql/gen/ExpressionCompiler.java:38),
we trace per-stage array programs and let XLA fuse them; where Trino shuffles
serialized pages over HTTP (operator/HttpPageBufferClient.java:355), we use
lax.all_to_all / psum collectives over ICI inside jitted stage programs.
"""

import os

import jax

# SQL semantics need 64-bit integers (BIGINT, scaled DECIMAL arithmetic).
# This must run before any array is created anywhere in the package.
jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: query kernels are jitted per
# (operator, shape-bucket) and identical across processes, so first-run
# device compiles amortize to cache reads on every later run — the role
# of the reference's long-lived JVM keeping generated bytecode warm
# across queries.
#
# The directory is placed from OUTSIDE: where JAX_COMPILATION_CACHE_DIR
# is set, JAX itself reads it and this package sets no directory in code.
# Where it is unset, the cache sits at one fixed path inside the checkout
# (the path is part of the cache key, so a directory that moves never
# hits). That default covers accelerator runs only: on a CPU-only run
# (JAX_PLATFORMS names nothing but cpu) it stays inactive — CPU AOT
# artifacts are machine-feature-specific and CPU compiles are cheap.
# JAX's own JAX_ENABLE_COMPILATION_CACHE=false disables it everywhere.
_platforms = [p.strip().lower()
              for p in os.environ.get("JAX_PLATFORMS", "").split(",")
              if p.strip()]
_cpu_only = bool(_platforms) and all(p == "cpu" for p in _platforms)
_DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")
if not jax.config.jax_enable_compilation_cache:
    COMPILE_CACHE_DIR = None
elif os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    COMPILE_CACHE_DIR = jax.config.jax_compilation_cache_dir
elif _cpu_only:
    COMPILE_CACHE_DIR = None
else:
    jax.config.update("jax_compilation_cache_dir",
                      _DEFAULT_COMPILE_CACHE_DIR)
    COMPILE_CACHE_DIR = _DEFAULT_COMPILE_CACHE_DIR
if COMPILE_CACHE_DIR and _cpu_only:
    # CPU compiles finish in milliseconds; an explicit CPU opt-in with
    # the 1s floor would persist nothing and tests could never see a hit
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

import logging as _logging

_logging.getLogger(__name__).info(
    "persistent compile cache %s",
    f"active at {COMPILE_CACHE_DIR}" if COMPILE_CACHE_DIR else
    "inactive (set JAX_COMPILATION_CACHE_DIR to enable)")

__version__ = "0.1.0"
