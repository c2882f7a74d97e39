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

import numpy as np
import pytest


@pytest.fixture
def registry():
    """A fresh, enabled metrics registry for one test."""
    from tnc_tpu import obs
    from tnc_tpu.obs.core import MetricsRegistry

    obs.configure(enabled=True, registry=MetricsRegistry())
    yield obs.get_registry()
    obs.configure(enabled=False, registry=MetricsRegistry())


@pytest.fixture(scope="module")
def sycamore20():
    """A 20-qubit depth-8 Sycamore-layout amplitude network sliced to
    2^10 elements: 256 slices, a hoisted prelude of 7 steps and a
    residual of 36."""
    from tnc_tpu.builders.sycamore_circuit import sycamore_circuit
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.contractionpath.slicing import slice_and_reconfigure
    from tnc_tpu.ops.hoist import hoist_sliced_program
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.ops.sliced import build_sliced_program
    from tnc_tpu.tensornetwork.simplify import simplify_network

    raw, _ = sycamore_circuit(
        20, 8, np.random.default_rng(42)
    ).into_amplitude_network("0" * 20)
    tn = simplify_network(raw)
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    pairs, slicing = slice_and_reconfigure(
        list(tn.tensors), result.ssa_path.toplevel, 2.0**10
    )
    sp = build_sliced_program(tn, ContractionPath.simple(pairs), slicing)
    assert slicing.num_slices == 256
    assert not hoist_sliced_program(sp).is_noop
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]
    return sp, arrays
