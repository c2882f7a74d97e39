"""Test configuration.

By default tests run on CPU with an 8-device virtual platform, the
analogue of the reference's oversubscribed single-node MPI tests
(``.github/workflows/test.yml``, ``#[mpi_test(N)]``): distributed code
paths execute on a real multi-device ``jax.sharding.Mesh`` without TPU
hardware.

The CPU platform is pinned through ``jax.config`` (it takes effect as
long as no backend has been initialized yet), so the suite never
reaches for an accelerator whatever the environment says.

Hardware tier: ``TNC_TPU_TEST_PLATFORM=tpu pytest -m tpu`` skips the CPU
pin and runs the ``tpu``-marked tests (tests/test_tpu_hardware.py) on
the real device — the analogue of the reference's real-MPI test tier
(``integration_tests.rs:121-167``).
"""

import os

TEST_PLATFORM = os.environ.get("TNC_TPU_TEST_PLATFORM", "cpu")

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

if TEST_PLATFORM == "cpu":
    jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
