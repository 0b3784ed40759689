"""Where JAX's persistent compilation cache lives.

A 26-layer program takes the TPU compiler tens of seconds, and a serving
process compiles dozens of them; without a persistent cache every process
start pays all of it again.  The directory is part of the cache key, so it
must not move between runs: it is either the one the environment names or
one fixed path inside the checkout.
"""

from __future__ import annotations

import os
import pathlib

#: ``<repo>/.jax_cache`` — resolved from this file, never from the working
#: directory, a temporary name, a pid or the time.  Listed in .gitignore.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a stable directory and
    return it.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and no directory is configured here.  Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`, and the thresholds are lowered so that the
    many small programs are kept as well as the few large ones.  It also
    installs the process's compile record (``obs/backends.py``), before
    anything compiles: every caller enables the cache first."""
    import jax

    from consensus_tpu.obs.backends import install_compile_record

    install_compile_record()
    # An executable is kept under its program AND the names its operations
    # carry (``jax.named_scope``, the jitted functions' names): JAX's
    # default leaves the names out of the key, so a cache that an older
    # build filled hands back executables whose operations a profile shows
    # under the older build's names, or none.  The names alone: with file
    # and line in the operations' locations every edit that moves a line
    # would compile every program again.
    jax.config.update("jax_traceback_in_locations_limit", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return str(DEFAULT_CACHE_DIR)
