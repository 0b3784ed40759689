"""Test harness configuration.

Forces JAX onto the host CPU platform with 8 virtual devices so every
sharding/mesh test runs mesh-shape-faithfully without TPU hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Every TPUBackend points the persistent compilation cache at the checkout
# (utils/compile_cache.py).  The suite compiles thousands of tiny CPU
# programs; it neither reads nor writes that cache.
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Drop every compiled program when a test module ends.  Each XLA CPU
    executable holds several memory mappings, and a whole-suite process
    that keeps all of them runs into ``vm.max_map_count`` (65,530 here)
    near test 700 of ~1,030: the next compile then dies inside LLVM with a
    segmentation fault or an abort.  Jitted functions stay valid and
    recompile on their next call."""
    yield
    jax.clear_caches()
