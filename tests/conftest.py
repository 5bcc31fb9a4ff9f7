"""Test environment: force an 8-device virtual CPU mesh.

The reference's dev story is N peers on one machine (SURVEY.md §4.1); ours is
the same plus N virtual devices in one process. Tests never need a real TPU —
Pallas kernels run in interpret mode on CPU, and the sharded/collective path
runs on the virtual device mesh. The identical tests pass unmodified on real
TPU hardware.

Must set env vars before jax is imported anywhere.
"""

import os

# Force, don't setdefault: on a chip host the ambient JAX_PLATFORMS (or its
# absence) selects the TPU; tests must run on the virtual CPU mesh
# regardless. ST_TEST_PLATFORM=tpu is the override that runs the suite
# compiled on a chip.
_platform = os.environ.get("ST_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (
        prev + " --xla_force_host_platform_device_count=8"
    ).strip()

# A pytest plugin may have imported jax before this conftest ran, in which
# case the env var alone is too late; the config update below still works as
# long as no backend has been initialized yet (they init lazily).
import jax  # noqa: E402

jax.config.update("jax_platforms", _platform)
