"""Scaled multi-device correctness tier.

Two instances on the 8-virtual-device CPU mesh, both with amplitude
parity against the complex128 numpy oracle:

- an 8-cluster dense network (tests/_cluster_fixture.py), 8-way
  partitioned under an HBM budget tight enough that per-partition
  slicing, the chunked executor, and the batch clamp actually engage
  (>=16 slices per partition — not the 36-element toy of
  ``dryrun_multichip``);
- a Sycamore-30 m=10 amplitude through the partitioning × GLOBAL
  slicing composition (cut legs sliceable — the config-#5 pipeline; a
  circuit partition's peak is its open cut boundary, which local
  slicing cannot reduce by construction).

Mirrors the scale discipline of the reference's heaviest integration
test (``tnc/tests/integration_tests.rs:121-167``) on the virtual mesh.
"""

import random

import numpy as np
import pytest

import jax

from tests._cluster_fixture import cluster_chain
from tnc_tpu.builders.sycamore_circuit import sycamore_circuit
from tnc_tpu.contractionpath.paths import Greedy, OptMethod
from tnc_tpu.contractionpath.repartitioning import compute_solution
from tnc_tpu.ops.sliced import SlicedProgram
from tnc_tpu.tensornetwork.contraction import contract_tensor_network
from tnc_tpu.tensornetwork.partitioning import find_partitioning
from tnc_tpu.tensornetwork.simplify import simplify_network


def _amplitude(tn) -> complex:
    flat = Greedy(OptMethod.GREEDY).find_path(tn)
    oracle = contract_tensor_network(tn, flat.replace_path(), backend="numpy")
    return complex(np.asarray(oracle.data.into_data()).reshape(-1)[0])


@pytest.mark.slow
def test_cluster8_partitioned_budget_slices_and_matches():
    """Per-device HBM budget forces real local slicing (>=16 slices per
    cluster); the chunked executor (slice batches, budget clamp) runs
    them; amplitude parity <= 1e-5."""
    from tnc_tpu.parallel.partitioned import (
        distributed_partitioned_contraction,
        scatter_partitions,
    )

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-virtual-device mesh")
    tn = cluster_chain(k=8, m=7, bond=2, seed=0)
    parts = find_partitioning(tn, 8)
    ptn, ppath, _, _ = compute_solution(tn, parts, rng=random.Random(7))
    want = _amplitude(tn)

    devices = jax.devices()[:8]
    hbm = 1 << 18  # 256 KiB: every K7 cluster must slice internally
    comm, _ = scatter_partitions(
        ptn, ppath, devices, "complex64", False, hbm_bytes=hbm
    )
    sliced = [p for p in comm.programs if isinstance(p, SlicedProgram)]
    assert sliced, "budget did not force local slicing — scale too small"
    assert any(p.slicing.num_slices >= 16 for p in sliced), [
        p.slicing.num_slices for p in sliced
    ]

    out = distributed_partitioned_contraction(
        ptn,
        ppath,
        devices=devices,
        hbm_bytes=hbm,
        local_sliced_strategy="chunked",
        slice_batch=4,
        chunk_steps=8,
    )
    got = complex(np.asarray(out.data.into_data()).reshape(-1)[0])
    assert abs(got - want) <= 1e-5 * max(1.0, abs(want)), (got, want)


@pytest.mark.slow
def test_sycamore30_global_slicing_composition_matches():
    """Sycamore-30 m=10 through partitioning × global slicing at a real
    target: >=16 global slices, amplitude parity <= 1e-5."""
    from tnc_tpu.parallel.partitioned import (
        distributed_partitioned_sliced_contraction,
    )

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-virtual-device mesh")
    rng = np.random.default_rng(42)
    raw, _ = sycamore_circuit(30, 10, rng).into_amplitude_network("0" * 30)
    tn = simplify_network(raw)
    parts = find_partitioning(tn, 8)
    ptn, ppath, _, _ = compute_solution(tn, parts, rng=random.Random(7))
    want = _amplitude(tn)

    # 2^24-element target → 64 global slices on this plan; each slice
    # fans 8 local programs + the toplevel fan-in across the mesh
    out, slicing = distributed_partitioned_sliced_contraction(
        ptn, ppath, n_devices=8, target_size=2.0**24
    )
    assert slicing.num_slices >= 16
    got = complex(np.asarray(out.data.into_data()).reshape(-1)[0])
    assert abs(got - want) <= 1e-5 * max(1.0, abs(want)), (got, want)
