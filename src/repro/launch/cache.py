"""Persistent JAX compilation cache for the launch/benchmark entry points.

The batched replay's first call costs seconds of XLA compilation and
every drain-engine configuration (backend × pool shape × compaction
flags) compiles its own while-loop.  Those compilations are
deterministic, so they should be paid once per machine, not once per
process: ``enable_persistent_cache`` turns JAX's persistent cache on
so repeat invocations of ``chip_smoke.py``, ``repro.launch.twin_loop``
and ``benchmarks.run`` start from warm executables.

Where the cache lives:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it at start-up and
    this module sets no directory in code;
  * otherwise one fixed directory inside the checkout,
    ``<repo>/.jax_cache`` (listed in ``.gitignore``).  The path is part
    of the cache key, so it never varies per process or per run.

Opt-out: pass ``--no-compile-cache`` on the CLIs (or call
``enable_persistent_cache(enabled=False)``), e.g. when benchmarking
cold-compile latency itself or on read-only filesystems.
"""
from __future__ import annotations

import logging
import os
from pathlib import Path
from typing import Optional

import jax

logger = logging.getLogger(__name__)

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_persistent_cache(enabled: bool = True) -> Optional[str]:
    """Turn JAX's persistent compilation cache on.

    Returns the cache path, or None when disabled or when the in-repo
    directory cannot be created (the run proceeds uncached — never
    fatal).  Thresholds are zeroed so even sub-second kernels (the
    engine's many small jits) are cached.
    """
    if not enabled:
        logger.info("persistent compilation cache disabled (opt-out)")
        return None
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        path = env_dir                      # JAX already points there
    else:
        try:
            REPO_CACHE_DIR.mkdir(exist_ok=True)
        except OSError as e:
            logger.warning("cannot create compilation cache dir %s (%s); "
                           "continuing uncached", REPO_CACHE_DIR, e)
            return None
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    logger.info("persistent compilation cache at %s", path)
    return path
