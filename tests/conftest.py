"""Test env: 8 simulated devices on the CPU backend (SURVEY.md §4).

Only one physical TPU chip exists in this environment, so every distributed
test runs the real psum/shard_map code paths over XLA's fake host devices.
Must run before the first ``import jax`` anywhere in the test session.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# The config API wins over whatever JAX_PLATFORMS the shell exported.
jax.config.update("jax_platforms", "cpu")

# SURVEY.md §5.2: NaN debugging on in tests (functional model has no data
# races; NaN poisoning is the failure class that remains).
jax.config.update("jax_debug_nans", True)
# float64 available on the CPU test backend so parity bars of 1e-6..1e-9
# are meaningful; production TPU runs use float32 (configs' dtype field).
jax.config.update("jax_enable_x64", True)
