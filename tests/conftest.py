"""Test bootstrap: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI/dev; collective semantics
(psum over ICI, shard_map sharding rules) are validated on XLA's host platform
with 8 virtual devices. Both settings are environment variables JAX reads at
import, so they are set here before anything imports jax.
"""

import os
import sys
import tempfile

# SPARKDL_TEST_PLATFORM=tpu runs the suite against the real backend instead
# of the virtual CPU mesh — the only way the TPU-gated compiled-kernel tests
# (tests/test_ops.py, test_flash_decode.py, test_paged_flash_decode.py) can
# ever unskip.
_platform = os.environ.get("SPARKDL_TEST_PLATFORM", "cpu")

_flags = os.environ.get("XLA_FLAGS", "")
if _platform == "cpu" and \
        "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = _platform
os.environ.setdefault("KERAS_BACKEND", "jax")
# The suite compiles ~3,400 programs (~400 MB of CPU executables): keep them
# out of <checkout>/.jax_cache, where the default would put them — the tree
# that gets copied to the chip must stay small. A fixed path, so a second
# run on the same machine hits.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(tempfile.gettempdir(), "sparkdl_tpu_test_jax_cache"))

import jax  # noqa: E402

if _platform == "cpu":
    assert len(jax.devices()) == 8, (
        f"expected 8 virtual CPU devices for sharding tests, "
        f"got {jax.devices()}")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
