"""Gate library: registry behavior and adjoint consistency
(mirrors ``tnc/src/gates.rs:586-608``).
"""

import math

import numpy as np
import pytest

from tnc_tpu.gates import (
    Gate,
    gate_names,
    is_gate_known,
    load_gate,
    load_gate_adjoint,
    register_gate,
)
from tnc_tpu.tensornetwork.tensordata import matrix_adjoint

GATE_PARAMS = {"u": 3, "rx": 1, "ry": 1, "rz": 1, "cp": 1, "fsim": 2, "rzz": 1}


def test_all_builtins_present():
    expected = {
        "x", "y", "z", "h", "t", "u", "sx", "sy", "sz",
        "rx", "ry", "rz", "cx", "cz", "swap", "cp", "iswap", "fsim",
    }
    assert expected.issubset(set(gate_names()))


def test_load_unknown_raises():
    with pytest.raises(KeyError):
        load_gate("foo")
    with pytest.raises(KeyError):
        load_gate_adjoint("foo")


def test_wrong_angle_count_raises():
    with pytest.raises(ValueError):
        load_gate("x", [1.0])
    with pytest.raises(ValueError):
        load_gate("u", [1.0])


def test_specialized_adjoints_match_generic():
    """Every gate's specialized adjoint equals the conjugate-transpose."""
    rng = np.random.default_rng(42)
    for name in gate_names():
        n = GATE_PARAMS.get(name, 0)
        angles = list(rng.uniform(-math.pi, math.pi, n))
        specialized = load_gate_adjoint(name, angles)
        generic = matrix_adjoint(load_gate(name, angles))
        np.testing.assert_allclose(specialized, generic, atol=1e-14, err_msg=name)


def test_gates_are_unitary():
    rng = np.random.default_rng(7)
    for name in gate_names():
        n = GATE_PARAMS.get(name, 0)
        angles = list(rng.uniform(-math.pi, math.pi, n))
        g = load_gate(name, angles)
        dim = int(round(math.sqrt(g.size)))
        m = g.reshape(dim, dim)
        np.testing.assert_allclose(m @ m.conj().T, np.eye(dim), atol=1e-14, err_msg=name)


def test_two_qubit_gates_shape():
    for name in ["cx", "cz", "swap", "iswap"]:
        assert load_gate(name).shape == (2, 2, 2, 2)
    assert load_gate("fsim", [0.3, 0.2]).shape == (2, 2, 2, 2)


def test_register_custom_gate():
    def my_gate(angles):
        return np.eye(2, dtype=np.complex128)

    register_gate(Gate("mygate_test", my_gate))
    assert is_gate_known("mygate_test")
    with pytest.raises(ValueError):
        register_gate(Gate("mygate_test", my_gate))
    with pytest.raises(ValueError):
        register_gate(Gate("BadCase", my_gate))


def test_three_qubit_adjoint_even_ndim():
    """matrix_adjoint accepts any even ndim (e.g. a 3-qubit gate in split
    (2,)*6 form), not just power-of-two."""
    rng = np.random.default_rng(0)
    g = rng.standard_normal((2,) * 6) + 1j * rng.standard_normal((2,) * 6)
    adj = matrix_adjoint(g)
    m = g.reshape(8, 8)
    np.testing.assert_allclose(adj.reshape(8, 8), m.conj().T)
