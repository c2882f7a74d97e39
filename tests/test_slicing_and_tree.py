"""Slicing, sliced execution, contraction trees, and the hyper-optimizer."""

import numpy as np
import pytest

from tnc_tpu import CompositeTensor, LeafTensor
from tnc_tpu.builders.sycamore_circuit import sycamore_circuit
from tnc_tpu.contractionpath.contraction_path import validate_path
from tnc_tpu.contractionpath.contraction_tree import ContractionTree
from tnc_tpu.contractionpath.paths import Greedy, OptMethod
from tnc_tpu.contractionpath.paths.hyper import Hyperoptimizer
from tnc_tpu.contractionpath.slicing import find_slicing, sliced_flops
from tnc_tpu.tensornetwork.contraction import (
    contract_tensor_network,
    contract_tensor_network_sliced,
)


def _sycamore_network(qubits=12, depth=6, seed=1):
    rng = np.random.default_rng(seed)
    circuit = sycamore_circuit(qubits, depth, rng)
    return circuit.into_amplitude_network("0" * qubits)[0]


def test_find_slicing_reduces_peak():
    tn = _sycamore_network()
    res = Greedy(OptMethod.GREEDY).find_path(tn)
    rp = res.replace_path()
    target = max(64.0, res.size / 8)
    slicing = find_slicing(list(tn.tensors), rp.toplevel, target)
    assert slicing.num_slices > 1
    # overhead is bounded by num_slices
    total = sliced_flops(list(tn.tensors), rp.toplevel, slicing)
    assert total <= res.flops * slicing.num_slices


def test_sliced_contraction_matches_unsliced():
    tn = _sycamore_network()
    res = Greedy(OptMethod.GREEDY).find_path(tn)
    rp = res.replace_path()
    want = complex(contract_tensor_network(tn, rp).data.into_data())

    slicing = find_slicing(list(tn.tensors), rp.toplevel, max(64.0, res.size / 8))
    for backend in ("numpy", "jax64"):
        got = complex(
            contract_tensor_network_sliced(tn, rp, slicing, backend=backend)
            .data.into_data()
        )
        assert got == pytest.approx(want, rel=1e-8, abs=1e-14), backend


def test_sliced_open_legs_preserved():
    """Slicing must never pick open (output) legs."""
    tn = _sycamore_network()
    # statevector-style: leave 2 legs open
    rng = np.random.default_rng(2)
    circuit = sycamore_circuit(6, 4, rng)
    tn, _ = circuit.into_amplitude_network("0000**")
    res = Greedy(OptMethod.GREEDY).find_path(tn)
    rp = res.replace_path()
    slicing = find_slicing(list(tn.tensors), rp.toplevel, max(64.0, res.size / 4))
    open_legs = set(tn.external_tensor().legs)
    assert not (set(slicing.legs) & open_legs)
    want = contract_tensor_network(tn, rp)
    got = contract_tensor_network_sliced(tn, rp, slicing)
    assert got.legs == want.legs
    np.testing.assert_allclose(
        got.data.into_data(), want.data.into_data(), atol=1e-10
    )


def test_contraction_tree_roundtrip():
    tn = _sycamore_network(8, 4)
    res = Greedy(OptMethod.GREEDY).find_path(tn)
    tree = ContractionTree.from_ssa_path(list(tn.tensors), res.ssa_path.toplevel)
    flops, peak = tree.total_cost()
    assert flops == res.flops
    assert peak <= res.size  # tree model: out+in1+in2 per step
    pairs = tree.to_ssa_path()
    # round-trip gives a valid full contraction with identical cost
    tree2 = ContractionTree.from_ssa_path(list(tn.tensors), pairs)
    assert tree2.total_cost()[0] == flops


def test_tree_weights_monotone():
    tn = _sycamore_network(8, 4)
    res = Greedy(OptMethod.GREEDY).find_path(tn)
    tree = ContractionTree.from_ssa_path(list(tn.tensors), res.ssa_path.toplevel)
    weights = tree.tree_weights()
    assert weights[tree.root] == pytest.approx(tree.total_cost()[0])
    for i, nd in enumerate(tree.nodes):
        if not nd.is_leaf and nd.parent >= 0:
            assert weights[i] <= weights[nd.parent] + 1e-9


def test_reconfigure_improves_or_keeps():
    tn = _sycamore_network(14, 8, seed=7)
    res = Greedy(OptMethod.GREEDY).find_path(tn)
    tree = ContractionTree.from_ssa_path(list(tn.tensors), res.ssa_path.toplevel)
    before, _ = tree.total_cost()
    tree.reconfigure(subtree_size=8, max_rounds=3)
    after, _ = tree.total_cost()
    assert after <= before
    # result is still a valid full contraction of all leaves
    pairs = tree.to_ssa_path()
    leaves_used = {a for a, b in pairs if a < tree.num_leaves} | {
        b for a, b in pairs if b < tree.num_leaves
    }
    assert leaves_used == set(range(tree.num_leaves))


def test_hyperoptimizer_beats_greedy_on_sycamore():
    tn = _sycamore_network(20, 10, seed=3)
    greedy = Greedy(OptMethod.GREEDY).find_path(tn)
    hyper = Hyperoptimizer(ntrials=8, reconfigure_rounds=2).find_path(tn)
    assert validate_path(hyper.replace_path(), len(tn))
    assert hyper.flops <= greedy.flops


def test_hyperoptimizer_correctness():
    tn = _sycamore_network(10, 5, seed=4)
    hyper = Hyperoptimizer(ntrials=4, reconfigure_rounds=1).find_path(tn)
    greedy = Greedy(OptMethod.GREEDY).find_path(tn)
    a = complex(contract_tensor_network(tn, hyper.replace_path()).data.into_data())
    b = complex(contract_tensor_network(tn, greedy.replace_path()).data.into_data())
    assert a == pytest.approx(b, rel=1e-10, abs=1e-13)


def test_deep_caterpillar_tree_no_recursion_limit():
    """A chain network's greedy path is a depth-n caterpillar; the tree
    walkers must be iterative (Python's recursion limit is ~1000)."""
    from tnc_tpu.contractionpath.contraction_tree import ContractionTree
    from tnc_tpu.tensornetwork.tensor import LeafTensor

    n = 1500
    bd = {i: 2 for i in range(n + 1)}
    inputs = [LeafTensor.from_map([i, i + 1], bd) for i in range(n)]
    ssa = [(0, 1)] + [(n + k, k + 2) for k in range(n - 2)]
    tree = ContractionTree.from_ssa_path(inputs, ssa)
    weights = tree.tree_weights()
    pairs = tree.to_ssa_path()
    assert len(pairs) == n - 1
    assert len(weights) == 2 * n - 1
    assert pairs == ssa  # round-trip preserves emission order


def test_sa_models_reject_single_partition():
    import pytest

    from tnc_tpu.contractionpath.repartitioning.simulated_annealing import (
        NaiveIntermediatePartitioningModel,
        NaivePartitioningModel,
    )
    from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor

    tn = CompositeTensor([LeafTensor.from_const([0], 2)])
    with pytest.raises(ValueError):
        NaivePartitioningModel(tn, 1)
    with pytest.raises(ValueError):
        NaiveIntermediatePartitioningModel(tn, 1)


def test_slice_and_reconfigure_meets_target_and_matches():
    """slice_and_reconfigure hits the peak target and the (path, slicing)
    it returns contracts to the same value as the unsliced network."""
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.slicing import (
        _replay_sizes,
        slice_and_reconfigure,
    )

    tn = _sycamore_network(qubits=18, depth=8, seed=3)
    res = Greedy(OptMethod.GREEDY).find_path(tn)
    inputs = list(tn.tensors)
    peak0, _ = _replay_sizes(inputs, res.replace_path().toplevel, set())
    assert peak0 > 4096
    target = peak0 / 16
    replace_pairs, slicing = slice_and_reconfigure(
        inputs,
        res.ssa_path.toplevel,
        target,
        step_budget=1.0,
        final_budget=2.0,
    )
    assert slicing.num_slices > 1
    peak, _ = _replay_sizes(inputs, replace_pairs, set(slicing.legs))
    assert peak <= target

    rp = ContractionPath.simple(replace_pairs)
    want = complex(
        contract_tensor_network(tn, res.replace_path()).data.into_data()
    )
    got = complex(
        contract_tensor_network_sliced(tn, rp, slicing).data.into_data()
    )
    assert got == pytest.approx(want, rel=1e-8, abs=1e-14)


def test_native_treedp_matches_python_dp():
    """The C++ subset-DP and the pure-Python DP agree on cost for random
    small networks, for both objectives."""
    import os
    import random

    import tnc_tpu.partitioning.native_binding as nb
    from tnc_tpu.partitioning.native_binding import native_optimal_order

    if nb.load_native() is None or not hasattr(
        nb.load_native(), "tnc_optimal_order"
    ):
        pytest.skip("native library unavailable")

    rng = random.Random(7)
    for _ in range(60):
        n = rng.randint(3, 8)
        nlegs = rng.randint(n, 3 * n)
        dims = {l: rng.choice([2, 2, 3, 4]) for l in range(nlegs)}
        leg_sets = [set() for _ in range(n)]
        for l in range(nlegs):
            for o in rng.sample(range(n), rng.choice([1, 2])):
                leg_sets[o].add(l)
        sets = [frozenset(s) for s in leg_sets]
        if any(not s for s in sets):
            continue
        tree = ContractionTree.__new__(ContractionTree)
        tree.dims = dims
        for minimize in ("flops", "size"):
            nat = native_optimal_order(sets, dims, minimize)
            assert nat is not None
            os.environ["TNC_TPU_NO_NATIVE"] = "1"
            nb._lib, nb._load_failed = None, False
            try:
                py = tree._optimal_order(list(sets), minimize)
            finally:
                del os.environ["TNC_TPU_NO_NATIVE"]
                nb._lib, nb._load_failed = None, False
            assert py is not None
            assert nat[0] == pytest.approx(py[0], rel=1e-9)
            # the native pair list must be a valid local SSA ordering
            seen = set(range(len(sets)))
            nxt = len(sets)
            for a, b in nat[1]:
                assert a in seen and b in seen and a != b
                seen.discard(a)
                seen.discard(b)
                seen.add(nxt)
                nxt += 1


def test_native_treedp_size_cap():
    """With a logsize cap the DP never forms an intermediate above the
    cap, and returns None when the cap is unsatisfiable."""
    import math as _math

    import tnc_tpu.partitioning.native_binding as nb
    from tnc_tpu.partitioning.native_binding import native_optimal_order

    lib = nb.load_native()
    if lib is None or not hasattr(lib, "tnc_optimal_order"):
        pytest.skip("native library unavailable")

    # chain a-b-c-d with bond dim 4: optimal order has intermediates of
    # size 16; capping at log2(16) is satisfiable, log2(4) is not
    # (every pairwise intermediate has >= 2 legs of dim 4).
    dims = {0: 4, 1: 4, 2: 4, 3: 4, 4: 4}
    sets = [
        frozenset({0, 1}),
        frozenset({1, 2}),
        frozenset({2, 3}),
        frozenset({3, 4}),
    ]
    ok = native_optimal_order(sets, dims, "flops", logsize_cap=4.0)
    assert ok is not None
    none = native_optimal_order(sets, dims, "flops", logsize_cap=_math.log2(4))
    assert none is not None and _math.isinf(none[0])


def test_chunked_batched_executor_matches_oracle():
    """Chunked slice-batched execution equals the numpy oracle for both
    complex and split-complex modes, batched and unbatched."""
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.slicing import (
        _replay_sizes,
        slice_and_reconfigure,
    )
    from tnc_tpu.ops.chunked import execute_sliced_batched_jax, split_program
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.ops.sliced import build_sliced_program, execute_sliced_numpy

    tn = _sycamore_network(qubits=16, depth=8, seed=5)
    res = Greedy(OptMethod.GREEDY).find_path(tn)
    inputs = list(tn.tensors)
    peak0, _ = _replay_sizes(inputs, res.replace_path().toplevel, set())
    rep, sl = slice_and_reconfigure(
        inputs, res.ssa_path.toplevel, peak0 / 32,
        step_budget=0.5, final_budget=1.0,
    )
    assert sl.num_slices > 1
    sp = build_sliced_program(tn, ContractionPath.simple(rep), sl)
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]

    chunks = split_program(sp.program, 16)
    assert sum(len(c.steps) for c in chunks) == len(sp.program.steps)

    want = complex(
        np.asarray(
            execute_sliced_numpy(sp, arrays, dtype=np.complex128)
        ).reshape(-1)[0]
    )
    for split in (False, True):
        batch = 2 if sl.num_slices % 2 == 0 else 1
        got = execute_sliced_batched_jax(
            sp, arrays, batch=batch, chunk_steps=16, split_complex=split
        )
        err = abs(complex(np.asarray(got).reshape(-1)[0]) - want)
        assert err <= 1e-3 * max(1e-30, abs(want)), (split, got, want)


def test_jax_backend_chunked_strategy():
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.slicing import find_slicing
    from tnc_tpu.ops.backends import JaxBackend
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.ops.sliced import build_sliced_program

    tn = _sycamore_network(qubits=12, depth=6, seed=1)
    res = Greedy(OptMethod.GREEDY).find_path(tn)
    rp = res.replace_path()
    slicing = find_slicing(list(tn.tensors), rp.toplevel, max(64.0, res.size / 8))
    sp = build_sliced_program(tn, rp, slicing)
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]

    # the on-device loop (the SPMD entry on one device) and the host
    # loop build their slices from one body: they agree
    from tnc_tpu.parallel.sliced_parallel import distributed_sliced_contraction

    loop = distributed_sliced_contraction(
        tn, rp, slicing, n_devices=1, dtype="complex64"
    )
    chunked = JaxBackend(dtype="complex64", slice_batch=1, chunk_steps=8)
    a = complex(loop.data.into_data().reshape(-1)[0])
    b = complex(np.asarray(chunked.execute_sliced(sp, arrays)).reshape(-1)[0])
    assert a == pytest.approx(b, rel=1e-4, abs=1e-7)


def test_chunked_zero_step_sliced_program():
    """A single-leaf network with a sliced leg compiles to a zero-step
    program; the chunked executor must sum the leaf's slices, not return
    the zero accumulator."""
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.slicing import Slicing
    from tnc_tpu.ops.chunked import execute_sliced_batched_jax
    from tnc_tpu.ops.sliced import build_sliced_program, execute_sliced_numpy
    from tnc_tpu.tensornetwork.tensor import CompositeTensor, LeafTensor
    from tnc_tpu.tensornetwork.tensordata import TensorData

    rng = np.random.default_rng(2)
    data = rng.standard_normal((4, 2)) + 1j * rng.standard_normal((4, 2))
    leaf = LeafTensor([0, 1], [4, 2], TensorData.matrix(data))
    tn = CompositeTensor()
    tn.push_tensor(leaf)
    slicing = Slicing(legs=(1,), dims=(2,))
    sp = build_sliced_program(tn, ContractionPath.simple([]), slicing)
    assert len(sp.program.steps) == 0
    want = execute_sliced_numpy(sp, [data], dtype=np.complex128)
    for split in (False, True):
        got = execute_sliced_batched_jax(
            sp, [data], batch=1, chunk_steps=8, split_complex=split
        )
        np.testing.assert_allclose(
            np.asarray(got), want, rtol=0, atol=1e-6
        )


def test_one_device_slice_loop_matches_oracle():
    """The whole slice loop in one program (the SPMD entry on a mesh of
    one device) must match the oracle, complex and split, hoisted and
    not."""
    from tnc_tpu.contractionpath.slicing import find_slicing
    from tnc_tpu.ops.backends import NumpyBackend
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.ops.sliced import build_sliced_program
    from tnc_tpu.parallel.sliced_parallel import distributed_sliced_contraction

    tn = _sycamore_network(qubits=12, depth=6, seed=3)
    res = Greedy(OptMethod.GREEDY).find_path(tn)
    rp = res.replace_path()
    slicing = find_slicing(
        list(tn.tensors), rp.toplevel, max(64.0, res.size / 32)
    )
    assert slicing.num_slices >= 4
    sp = build_sliced_program(tn, rp, slicing)
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]
    want = complex(
        np.asarray(NumpyBackend().execute_sliced(sp, arrays)).reshape(-1)[0]
    )
    for hoist in (False, True):
        for split in (False, True):
            out = distributed_sliced_contraction(
                tn, rp, slicing, n_devices=1, dtype="complex64",
                split_complex=split, hoist=hoist,
            )
            got = complex(out.data.into_data().reshape(-1)[0])
            assert got == pytest.approx(want, rel=1e-4, abs=1e-7), (
                hoist,
                split,
            )


def test_execute_sliced_host_false_device_resident():
    """host=False (the benchmark-timing contract: no device→host
    transfer inside timed regions) returns the device accumulator in
    stored shape for every backend, equal to the host result."""
    import jax

    from tnc_tpu.ops.backends import JaxBackend, NumpyBackend
    from tnc_tpu.contractionpath.slicing import find_slicing
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.ops.sliced import build_sliced_program

    tn = _sycamore_network(qubits=12, depth=6, seed=2)
    res = Greedy(OptMethod.GREEDY).find_path(tn)
    rp = res.replace_path()
    slicing = find_slicing(list(tn.tensors), rp.toplevel, max(64.0, res.size / 8))
    sp = build_sliced_program(tn, rp, slicing)
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]

    stored = sp.program.stored_result_shape
    want = complex(
        np.asarray(NumpyBackend().execute_sliced(sp, arrays)).reshape(-1)[0]
    )

    out_np = NumpyBackend().execute_sliced(sp, arrays, host=False)
    assert out_np.shape == tuple(stored)

    for split in (False, True):
        backend = JaxBackend(
            dtype="complex64",
            split_complex=split,
            slice_batch=1,
            chunk_steps=8,
        )
        dev = backend.execute_sliced(sp, arrays, host=False)
        if split:
            assert isinstance(dev, tuple) and len(dev) == 2
            got = np.asarray(dev[0]) + 1j * np.asarray(dev[1])
        else:
            assert isinstance(dev, jax.Array)
            got = np.asarray(dev)
        assert got.shape == tuple(stored), split
        assert complex(got.reshape(-1)[0]) == pytest.approx(
            want, rel=1e-4, abs=1e-7
        ), split
