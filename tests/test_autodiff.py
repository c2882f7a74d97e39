"""Differentiable contraction: gradients of expectation values through
the compiled program vs finite differences and the analytic formula —
the variational-circuit workflow the Rust reference cannot express."""

import numpy as np

from tnc_tpu.builders.circuit_builder import Circuit
from tnc_tpu.contractionpath.paths import Greedy, OptMethod
from tnc_tpu.ops.autodiff import contraction_value_and_grad
from tnc_tpu.ops.program import flat_leaf_tensors
from tnc_tpu.tensornetwork.tensordata import TensorData


def _rx_expectation_network(theta: float):
    """⟨0|Rx(θ)† Z Rx(θ)|0⟩ network; expectation = cos(θ)."""
    c = Circuit()
    reg = c.allocate_register(1)
    c.append_gate(TensorData.gate("rx", [theta]), [reg.qubit(0)])
    return c.into_expectation_value_network()


def _gate_slots(tn):
    """Flat slots holding gate tensors (the differentiable parameters)."""
    from tnc_tpu.tensornetwork.tensordata import DataKind

    return [
        i
        for i, leaf in enumerate(flat_leaf_tensors(tn))
        if leaf.data.kind in (DataKind.GATE, DataKind.MATRIX)
        and leaf.dims() == 2
    ]


def test_rx_expectation_gradient_matches_analytic():
    theta = 0.7
    tn = _rx_expectation_network(theta)
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    path = result.replace_path()

    value, grads = contraction_value_and_grad(tn, path, dtype="complex128")
    ev = complex(np.asarray(value).reshape(-1)[0])
    assert abs(ev - np.cos(theta)) < 1e-8

    assert grads  # gradient sweep ran
    # finite-difference check on θ: d cos(θ)/dθ = −sin(θ)
    eps = 1e-6
    tn2 = _rx_expectation_network(theta + eps)
    v2, _ = contraction_value_and_grad(
        tn2, Greedy(OptMethod.GREEDY).find_path(tn2).replace_path(),
        dtype="complex128",
    )
    fd = (complex(np.asarray(v2).reshape(-1)[0]).real - ev.real) / eps
    assert abs(fd - (-np.sin(theta))) < 1e-4


def test_gradient_matches_finite_difference_per_entry():
    """Cotangent of a gate leaf vs entrywise finite differences."""
    theta = 0.3
    tn = _rx_expectation_network(theta)
    path = Greedy(OptMethod.GREEDY).find_path(tn).replace_path()
    slots = _gate_slots(tn)
    slot = slots[0]

    value, grads = contraction_value_and_grad(
        tn, path, wrt=[slot], dtype="complex128"
    )
    grad = grads[0]

    leaves = flat_leaf_tensors(tn)
    base = np.asarray(leaves[slot].data.into_data()).astype(np.complex128)
    f0 = complex(np.asarray(value).reshape(-1)[0]).real

    eps = 1e-6
    for idx in np.ndindex(*base.shape):
        for direction in (1.0, 1.0j):
            pert = base.copy()
            pert[idx] += eps * direction
            leaves2 = flat_leaf_tensors(tn)
            arrays = [
                np.asarray(leaf.data.into_data()).astype(np.complex128)
                for leaf in leaves2
            ]
            arrays[slot] = pert
            from tnc_tpu.ops.backends import NumpyBackend
            from tnc_tpu.ops.program import build_program

            program = build_program(tn, path)
            out = NumpyBackend(np.complex128).execute(program, arrays)
            f1 = complex(np.asarray(out).reshape(-1)[0]).real
            fd = (f1 - f0) / eps
            # JAX convention for real f of complex G (empirically
            # validated here): df = Re(grad_entry · dG)
            want = np.real(grad[idx] * direction)
            assert abs(fd - want) < 1e-4, (idx, direction, fd, want)


def test_sliced_gradient_matches_unsliced():
    """Gradients through the slice loop == gradients of the whole
    program (the vjp of the slice sum is the sum of per-slice vjps);
    closes docs/future_work.md item 4's open half."""
    from tnc_tpu.builders.connectivity import ConnectivityLayout
    from tnc_tpu.builders.random_circuit import random_circuit
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.slicing import slice_and_reconfigure
    from tnc_tpu.ops.autodiff import sliced_contraction_value_and_grad

    rng = np.random.default_rng(5)
    tn = random_circuit(
        10, 5, 0.5, 0.4, rng, ConnectivityLayout.LINE, bitstring="0" * 10
    )
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    inputs = list(tn.tensors)
    for divisor in (8.0, 4.0, 2.0):
        try:
            pairs, slicing = slice_and_reconfigure(
                inputs, result.ssa_path.toplevel, max(result.size / divisor, 2.0),
                # the divisors rely on the slicer refusing slicings too
                # large for this test to run
                max_slices=1 << 26,
            )
            break
        except ValueError:
            continue
    else:
        import pytest

        pytest.skip("instance would not slice")
    assert slicing.num_slices > 1
    path = ContractionPath.simple(pairs)

    wrt = _gate_slots(tn)[:3]
    value_s, grads_s = sliced_contraction_value_and_grad(
        tn, path, slicing, wrt=wrt, dtype="complex64"
    )
    value_u, grads_u = contraction_value_and_grad(
        tn, path, wrt=wrt, dtype="complex64"
    )
    assert np.allclose(value_s, value_u, rtol=1e-5, atol=1e-7)
    for gs, gu in zip(grads_s, grads_u):
        assert gs.shape == gu.shape
        assert np.allclose(gs, gu, rtol=1e-4, atol=1e-6)


def test_sliced_gradient_matches_finite_difference():
    from tnc_tpu.builders.connectivity import ConnectivityLayout
    from tnc_tpu.builders.random_circuit import random_circuit
    from tnc_tpu.contractionpath.contraction_path import ContractionPath
    from tnc_tpu.contractionpath.slicing import slice_and_reconfigure
    from tnc_tpu.ops.autodiff import sliced_contraction_value_and_grad
    from tnc_tpu.ops.program import build_program
    from tnc_tpu.ops.backends import NumpyBackend

    rng = np.random.default_rng(9)
    tn = random_circuit(
        8, 4, 0.5, 0.4, rng, ConnectivityLayout.LINE, bitstring="0" * 8
    )
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    inputs = list(tn.tensors)
    for divisor in (4.0, 2.0):
        try:
            pairs, slicing = slice_and_reconfigure(
                inputs, result.ssa_path.toplevel, max(result.size / divisor, 2.0),
                # the divisors rely on the slicer refusing slicings too
                # large for this test to run
                max_slices=1 << 26,
            )
            break
        except ValueError:
            continue
    else:
        import pytest

        pytest.skip("instance would not slice")
    if slicing.num_slices <= 1:
        import pytest

        pytest.skip("instance did not slice")
    path = ContractionPath.simple(pairs)
    slot = _gate_slots(tn)[0]

    _, (grad,) = sliced_contraction_value_and_grad(
        tn, path, slicing, wrt=[slot], dtype="complex128"
    )

    # finite differences through the full (unsliced) numpy contraction
    program = build_program(tn, path)
    leaves = flat_leaf_tensors(tn)
    arrays = [leaf.data.into_data() for leaf in leaves]
    backend = NumpyBackend(dtype=np.complex128)

    def f(x):
        bufs = list(arrays)
        bufs[slot] = x
        return float(np.real(backend.execute(program, bufs).reshape(-1)[0]))

    eps = 1e-6
    x0 = np.asarray(arrays[slot], dtype=np.complex128)
    it = np.nditer(x0, flags=["multi_index"])
    while not it.finished:
        idx = it.multi_index
        for d in (eps, eps * 1j):
            xp = x0.copy(); xp[idx] += d
            xm = x0.copy(); xm[idx] -= d
            fd = (f(xp) - f(xm)) / (2 * eps)
            # convention: df = Re(g * dT) -> the i-direction derivative
            # is -Im(g) (matches the unsliced module contract)
            want = np.real(grad[idx]) if d == eps else -np.imag(grad[idx])
            assert abs(fd - want) < 1e-4, (idx, d, fd, want)
        it.iternext()
