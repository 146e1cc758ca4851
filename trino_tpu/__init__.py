"""trino_tpu — a TPU-native distributed SQL query engine.

A ground-up re-design of the capabilities of the reference engine (see /root/repo/SURVEY.md):
SQL -> analyzer -> cost-based planner -> fragmented distributed plan, executed as jit-compiled
XLA/Pallas kernels over fixed-capacity columnar pages in HBM, with hash-partitioned exchanges
mapped to all-to-all collectives on the ICI mesh.

int64/float64 columns require jax x64 mode; enable it before the first jax computation.
"""

import os as _os

import jax

# SQL semantics need 64-bit integers (bigint, short decimals) and float64 (double).
jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: query pipelines re-used across processes skip
# the (slow) TPU compile — the analog of the reference's bytecode caches surviving
# in a long-lived server JVM (sql/gen/PageFunctionCompiler.java:103).  This is the
# ONE place the repo sets the directory.  JAX_COMPILATION_CACHE_DIR places it from
# outside (JAX reads that variable itself); otherwise it is a FIXED path inside the
# checkout — the path is part of the cache key, so a directory that moves between
# runs never hits.  Opt out with TRINO_TPU_NO_COMPILE_CACHE=1.


def _default_cache_dir() -> str:
    root = _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))), ".jax_cache")
    if jax.config.jax_platforms != "cpu":
        return root
    # CPU AOT entries embed the compiling machine's features and can crash a
    # CPU that lacks them: keep each feature set (a property of the machine,
    # stable across boots and processes) in its own sub-directory.
    import hashlib

    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            flags = next((line for line in f if line.startswith("flags")), "")
    except OSError:
        pass
    return _os.path.join(root, "cpu-" + hashlib.sha1(flags.encode()).hexdigest()[:12])


if not _os.environ.get("TRINO_TPU_NO_COMPILE_CACHE"):
    if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _default_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

from .engine import Engine, Session  # noqa: E402

__all__ = ["Engine", "Session"]
__version__ = "0.1.0"
