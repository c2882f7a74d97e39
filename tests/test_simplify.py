"""Host-side network preprocessing (``tnc_tpu.tensornetwork.simplify``)
and the slice-parallel SPMD executor — the bench pipeline's entry
stages, pinned against the unsimplified/single-device oracles."""

import numpy as np
import pytest

from tnc_tpu.builders.connectivity import ConnectivityLayout
from tnc_tpu.builders.random_circuit import random_circuit
from tnc_tpu.contractionpath.paths import Greedy, OptMethod
from tnc_tpu.tensornetwork.contraction import contract_tensor_network
from tnc_tpu.tensornetwork.simplify import simplify_network
from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
from tnc_tpu.tensornetwork.tensordata import TensorData


@pytest.fixture(scope="module")
def network():
    rng = np.random.default_rng(5)
    return random_circuit(10, 5, 0.8, 0.8, rng, ConnectivityLayout.LINE)


def _value(tn):
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    out = contract_tensor_network(tn, result.replace_path(), backend="numpy")
    return complex(np.asarray(out.data.into_data()).reshape(-1)[0])


def test_simplify_preserves_value_and_shrinks(network):
    flat = CompositeTensor(list(network.tensors))
    want = _value(flat)
    reduced = simplify_network(CompositeTensor(list(network.tensors)))
    assert len(reduced) < len(network)
    # every survivor has rank > 2 (or the network bottomed out)
    assert all(t.dims() > 2 for t in reduced.tensors) or len(reduced) <= 2
    got = _value(reduced)
    assert got == pytest.approx(want, rel=1e-10, abs=1e-13)


def test_simplify_rejects_nested():
    inner = CompositeTensor(
        [LeafTensor([0], [2], TensorData.matrix(np.ones(2)))]
    )
    with pytest.raises(ValueError):
        simplify_network(CompositeTensor([inner]))


def test_simplify_leaves_disconnected_scalars():
    # two disconnected rank-1 tensors: nothing shares a leg, so they stay
    a = LeafTensor([0], [2], TensorData.matrix(np.array([1.0, 2.0])))
    b = LeafTensor([1], [2], TensorData.matrix(np.array([3.0, 4.0])))
    out = simplify_network(CompositeTensor([a, b]))
    assert len(out) == 2


def test_distributed_sliced_matches_oracle(network):
    """SPMD slice-parallel executor over the 8-device virtual mesh
    (exercises shard_map + psum; parity vs the single-device oracle)."""
    from tnc_tpu.contractionpath.slicing import find_slicing
    from tnc_tpu.parallel import distributed_sliced_contraction, make_mesh

    flat = CompositeTensor(list(network.tensors))
    result = Greedy(OptMethod.GREEDY).find_path(flat)
    replace = result.replace_path()
    inputs = list(flat.tensors)
    target = result.size
    slicing = find_slicing(inputs, replace.toplevel, target)
    while slicing.num_slices < 8 and target > 1.0:
        target = max(1.0, target / 2)
        slicing = find_slicing(inputs, replace.toplevel, target)
    assert slicing.num_slices >= 8

    mesh = make_mesh(8)
    want = _value(flat)
    out = distributed_sliced_contraction(
        flat, replace, slicing, mesh=mesh, dtype="complex64"
    )
    got = complex(np.asarray(out.data.into_data()).reshape(-1)[0])
    assert abs(got - want) <= 1e-4 * max(1.0, abs(want))
