"""Kicked-Ising (Trotterized transverse-field Ising) circuits.

The circuit of Kim et al., "Evidence for the utility of quantum
computing before fault tolerance", Nature 618:500 (2023): from
``|0…0>``, ``steps`` Trotter steps, each a layer of ``rx(theta_h)`` on
every qubit followed by ``rzz(theta_zz)`` on every coupling
(``rzz(theta) = exp(-i theta/2 Z x Z)``; the paper runs ``theta_zz =
-pi/2`` on IBM's 127-qubit heavy-hex map). The couplings are GIVEN: the
builder knows no device (``ConnectivityLayout.EAGLE`` is the
reference's 142-coupling table, not IBM's map). The ZZ gates of a step
commute, so their order in ``couplings`` changes no value; one ``rzz``
leaf a coupling where the ``cx rz cx`` spelling is three.

A local observable of such a circuit is an expectation value by its
causal cone: :func:`tnc_tpu.queries.expectation.pauli_expectation`.
"""

from __future__ import annotations

import math
from typing import Iterable, Sequence

from tnc_tpu.builders.circuit_builder import Circuit
from tnc_tpu.tensornetwork.tensordata import TensorData


def kicked_ising_circuit(
    qubits: int,
    couplings: Iterable[Sequence[int]],
    steps: int,
    theta_h: float,
    theta_zz: float = -math.pi / 2.0,
    final_rx: bool = False,
) -> Circuit:
    """``steps`` rounds of (``rx(theta_h)`` on every qubit,
    ``rzz(theta_zz)`` on every pair of ``couplings``, 0-based), closed
    by one more ``rx`` layer when ``final_rx``.

    >>> from tnc_tpu.queries.expectation import pauli_expectation
    >>> c = kicked_ising_circuit(3, [(0, 1), (1, 2)], 1, 0.4)
    >>> abs(pauli_expectation(c, "zii") - math.cos(0.4)) < 1e-12  # ZZ commutes with Z
    True
    """
    couplings = [tuple(int(q) for q in pair) for pair in couplings]
    for pair in couplings:
        if len(pair) != 2 or pair[0] == pair[1] or not all(
            0 <= q < qubits for q in pair
        ):
            raise ValueError(
                f"coupling {pair} is not a pair of distinct qubits below {qubits}"
            )
    rx = TensorData.gate("rx", (float(theta_h),))
    rzz = TensorData.gate("rzz", (float(theta_zz),))

    circuit = Circuit()
    reg = circuit.allocate_register(qubits)
    for step in range(steps + (1 if final_rx else 0)):
        for q in range(qubits):
            circuit.append_gate(rx, [reg.qubit(q)])
        if step == steps:
            break
        for a, b in couplings:
            circuit.append_gate(rzz, [reg.qubit(a), reg.qubit(b)])
    return circuit
