"""Randomized stress test for the step compiler: random tensor networks
with mixed bond dims, random greedy paths, numpy vs jax(cpu) parity, and
sliced-program consistency. Guards the layout machinery (run fusion,
k-order candidates, per-operand orientation, storage merging) against
silent mis-ordering — every case is an exact-value oracle."""

import numpy as np
import pytest

from tnc_tpu.contractionpath.paths import Greedy, OptMethod
from tnc_tpu.tensornetwork.contraction import contract_tensor_network
from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
from tnc_tpu.tensornetwork.tensordata import TensorData


def _random_network(rng: np.random.Generator, n_tensors: int):
    """Connected random network: tensors chained by shared legs plus
    random extra edges and some open legs; dims in {2, 3, 4}."""
    next_leg = 0
    legs_of: list[list[int]] = [[] for _ in range(n_tensors)]
    dims: dict[int, int] = {}

    def new_leg(dim):
        nonlocal next_leg
        leg = next_leg
        next_leg += 1
        dims[leg] = dim
        return leg

    # spanning chain keeps it connected
    for i in range(n_tensors - 1):
        leg = new_leg(int(rng.integers(2, 5)))
        legs_of[i].append(leg)
        legs_of[i + 1].append(leg)
    # extra shared edges
    for _ in range(n_tensors // 2):
        i, j = rng.choice(n_tensors, size=2, replace=False)
        leg = new_leg(int(rng.integers(2, 5)))
        legs_of[i].append(leg)
        legs_of[j].append(leg)
    # open legs
    for _ in range(2):
        i = int(rng.integers(0, n_tensors))
        legs_of[i].append(new_leg(2))

    tensors = []
    for legs in legs_of:
        shape = [dims[leg] for leg in legs]
        t = LeafTensor(list(legs), shape)
        data = rng.standard_normal(shape) + 1j * rng.standard_normal(shape)
        t.data = TensorData.matrix(data)
        tensors.append(t)
    return CompositeTensor(tensors)


@pytest.mark.parametrize("seed", range(8))
def test_random_network_numpy_jax_parity(seed):
    rng = np.random.default_rng(100 + seed)
    tn = _random_network(rng, int(rng.integers(4, 9)))
    path = Greedy(OptMethod.GREEDY).find_path(tn).replace_path()

    want = contract_tensor_network(tn, path, backend="numpy")
    got = contract_tensor_network(tn, path, backend="jax64")
    assert got.legs == want.legs
    wa = np.asarray(want.data.into_data())
    ga = np.asarray(got.data.into_data())
    denom = max(float(np.max(np.abs(wa))), 1e-30)
    assert float(np.max(np.abs(ga - wa))) / denom < 1e-10, seed


@pytest.mark.parametrize("seed", range(4))
def test_random_network_sliced_consistency(seed):
    from tnc_tpu.contractionpath.slicing import find_slicing
    from tnc_tpu.tensornetwork.contraction import (
        contract_tensor_network_sliced,
    )

    rng = np.random.default_rng(200 + seed)
    tn = _random_network(rng, 7)
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    path = result.replace_path()
    try:
        slicing = find_slicing(
            list(tn.tensors), path.toplevel, max(result.size / 4, 2.0)
        )
    except ValueError:
        pytest.skip("network not sliceable")
    if slicing.num_slices < 2:
        pytest.skip("network did not slice")

    want = contract_tensor_network(tn, path, backend="numpy")
    got = contract_tensor_network_sliced(tn, path, slicing, backend="numpy")
    assert got.legs == want.legs
    wa = np.asarray(want.data.into_data())
    ga = np.asarray(got.data.into_data())
    denom = max(float(np.max(np.abs(wa))), 1e-30)
    assert float(np.max(np.abs(ga - wa))) / denom < 1e-10, seed


@pytest.mark.parametrize("mode", ["gauss", "naive", "block"])
@pytest.mark.parametrize("seed", range(4))
def test_random_network_split_complex_mult_modes(seed, mode, monkeypatch):
    """Fuzz the complex-multiply lowerings (split-complex f32: three
    dots, four, and the one dot of the block form) against the
    complex128 oracle on random networks."""
    from tnc_tpu.ops.backends import JaxBackend
    from tnc_tpu.ops.program import build_program, flat_leaf_tensors

    monkeypatch.setenv("TNC_TPU_COMPLEX_MULT", mode)
    rng = np.random.default_rng(300 + seed)
    tn = _random_network(rng, int(rng.integers(4, 9)))
    path = Greedy(OptMethod.GREEDY).find_path(tn).replace_path()
    program = build_program(tn, path)
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]

    from tnc_tpu.ops.backends import NumpyBackend

    want = NumpyBackend(dtype=np.complex128).execute(program, arrays)
    got = JaxBackend(
        dtype="complex64", split_complex=True, precision="float32"
    ).execute(program, arrays)
    denom = max(float(np.max(np.abs(want))), 1e-30)
    assert float(np.max(np.abs(got - want))) / denom < 1e-5, (seed, mode)
