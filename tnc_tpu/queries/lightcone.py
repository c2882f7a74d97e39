"""The causal cone of a Pauli string's support through a gate list.

⟨ψ|P|ψ⟩ with ``P`` on a few qubits of a wide, shallow circuit needs only
the gates that can reach ``P``. Walking the circuit backwards from the
observable (the Heisenberg picture), the evolved operator is kept track
of qubit by qubit, as one of three things:

- ``identity`` — the operator does not act there;
- ``diagonal`` — it acts there, and is diagonal in the computational
  basis on that qubit (a ``z`` letter, or what a diagonal gate spread
  from a neighbour);
- ``general`` — anything (an ``x`` or ``y`` letter, or a qubit that a
  gate which is not diagonal has been kept on).

A gate whose qubits are all ``identity`` cancels against its adjoint. So
does a **diagonal** gate whose qubits are all ``identity`` or
``diagonal``: it commutes with the operator as it stands. That second
rule is what keeps the cone of a layer of commuting gates (an Ising or
QAOA ZZ layer) from growing along chains of couplings by the accident of
their order in the list: a ZZ gate is kept only where it meets a qubit on
which something that is not diagonal follows, and the ZZ gates of one
layer give the same cone in any order. A kept diagonal gate makes its
``identity`` qubits ``diagonal``; any other kept gate makes all of its
qubits ``general``.

Whether a gate is diagonal is read from its **data**, never from its
name: the module knows nothing of any ansatz. A named gate with angles
is read at the angles it holds AND at a generic offset of them, so that
the cone holds at whatever angles the gate is rebound to later
(``rx(0)`` is the identity, ``rx`` is not diagonal).
"""

from __future__ import annotations

from typing import Mapping, Sequence

import numpy as np

from tnc_tpu.builders.circuit_builder import Circuit
from tnc_tpu.tensornetwork.tensordata import DataKind, TensorData

__all__ = ["circuit_gates", "is_diagonal", "lightcone", "support_letters"]

_IDENTITY, _DIAGONAL, _GENERAL = 0, 1, 2
_LETTER_STATE = {"i": _IDENTITY, "z": _DIAGONAL, "x": _GENERAL, "y": _GENERAL}
# a named gate's angles are also read here beside them: what is diagonal
# at both is diagonal at every angle, but for a set of measure zero
_PROBE_OFFSET = 0.7390851332151607


def support_letters(support, num_qubits: int) -> str:
    """One letter a qubit from what a caller may give as a support: a
    Pauli string (returned in lower case), a mapping ``{qubit: letter}``
    or a sequence of qubit numbers (each then ``x``: any operator)."""
    if isinstance(support, str):
        letters = support.lower()
    else:
        items = (
            support.items()
            if isinstance(support, Mapping)
            else ((q, "x") for q in support)
        )
        chars = ["i"] * num_qubits
        for q, letter in items:
            if not 0 <= int(q) < num_qubits:
                raise ValueError(
                    f"support qubit {q} is not a qubit of a "
                    f"{num_qubits}-qubit circuit"
                )
            chars[int(q)] = str(letter).lower()
        letters = "".join(chars)
    if len(letters) != num_qubits:
        raise ValueError(
            f"support has {len(letters)} letters, the circuit "
            f"{num_qubits} qubits"
        )
    for pos, c in enumerate(letters):
        if c not in _LETTER_STATE:
            raise ValueError(
                f"invalid support letter {c!r} at position {pos} "
                "(only 'i', 'x', 'y' and 'z' are allowed)"
            )
    return letters


def circuit_gates(
    circuit: Circuit,
) -> tuple[list[TensorData], list[tuple[TensorData, tuple[int, ...]]]]:
    """``circuit`` read back as ``(kets, gates)``: the kets' data in
    qubit order and ``(data, qubits)`` of every gate in the order they
    were appended. Read, not consumed."""
    if circuit._finalized:
        raise RuntimeError(
            "Circuit was already converted to a network; build a new Circuit"
        )
    owner: dict[int, int] = {}  # open edge -> qubit
    kets: list[TensorData] = []
    gates: list[tuple[TensorData, tuple[int, ...]]] = []
    for leaf in circuit.tensor_network.tensors:
        legs = leaf.legs
        if len(legs) == 1:
            owner[legs[0]] = len(kets)
            kets.append(leaf.data)
            continue
        half = len(legs) // 2
        qubits = tuple(owner.pop(edge) for edge in legs[half:])
        for q, edge in zip(qubits, legs[:half]):
            owner[edge] = q
        gates.append((leaf.data, qubits))
    return kets, gates


def _diagonal_matrix(tensor: np.ndarray) -> bool:
    dim = 1 << (tensor.ndim // 2)
    matrix = np.asarray(tensor).reshape(dim, dim)
    return not np.any(matrix[~np.eye(dim, dtype=bool)])


def is_diagonal(data: TensorData) -> bool:
    """Is the gate diagonal as a matrix ``[out…, in…]``, at the angles
    it holds and at a generic offset of them?"""
    if not _diagonal_matrix(data.into_data()):
        return False
    if data.kind is DataKind.GATE and data.payload[1]:
        name, angles, adjoint = data.payload
        probe = tuple(a + _PROBE_OFFSET * (k + 1) for k, a in enumerate(angles))
        return _diagonal_matrix(TensorData.gate(name, probe, adjoint).into_data())
    return True


def _data_key(data: TensorData):
    if data.kind is DataKind.MATRIX:
        return id(data)
    return (data.kind, data.payload)


def lightcone(
    circuit: Circuit, support: str | Mapping[int, str] | Sequence[int]
) -> tuple[Circuit, tuple[int, ...]]:
    """``circuit`` reduced to the causal cone of a Pauli string:
    ``(reduced circuit, kept_qubits)``. ``support`` is the string itself
    (one letter a qubit, ``i`` off the support), a mapping ``{qubit:
    letter}`` or a sequence of qubits (any operator there). Gates
    outside the cone are dropped; qubit ``i`` of the reduced circuit is
    ``kept_qubits[i]`` of ``circuit``, in ascending order. ⟨ψ|P|ψ⟩ of
    the two agree for ``P`` and for every string that has ``i`` where
    ``P`` has and ``i`` or ``z`` where ``P`` has ``z``. ``circuit`` is
    read, not consumed; gate data is shared. Works on any gate list.

    >>> from tnc_tpu.tensornetwork.tensordata import TensorData
    >>> c = Circuit(); reg = c.allocate_register(4)
    >>> for q in range(4):
    ...     c.append_gate(TensorData.gate("h"), [reg.qubit(q)])
    >>> for q in range(3):  # a chain of commuting diagonal gates
    ...     c.append_gate(TensorData.gate("cz"), [reg.qubit(q), reg.qubit(q + 1)])
    >>> lightcone(c, "xiii")[1]   # cz(0,1) is kept, cz(1,2) commutes
    (0, 1)
    >>> lightcone(c, "ziii")[1]   # a z letter lets the cz layer go
    (0,)
    >>> lightcone(c, [3])[1]
    (2, 3)
    """
    kets, gates = circuit_gates(circuit)
    n = len(kets)
    letters = support_letters(support, n)
    state = [_LETTER_STATE[c] for c in letters]
    diagonal_of: dict = {}
    kept: list[int] = []
    for g in range(len(gates) - 1, -1, -1):
        data, qubits = gates[g]
        reach = max(state[q] for q in qubits)
        if reach == _IDENTITY:
            continue
        key = _data_key(data)
        diagonal = diagonal_of.get(key)
        if diagonal is None:
            diagonal = diagonal_of[key] = is_diagonal(data)
        if diagonal:
            if reach == _DIAGONAL:
                continue  # commutes with the operator as it stands
            for q in qubits:
                state[q] = max(state[q], _DIAGONAL)
        else:
            for q in qubits:
                state[q] = _GENERAL
        kept.append(g)
    kept.reverse()

    kept_qubits = tuple(q for q in range(n) if state[q] != _IDENTITY)
    new = {q: i for i, q in enumerate(kept_qubits)}
    reduced = Circuit()
    reg = reduced.allocate_register(len(kept_qubits))
    for leaf, q in zip(reduced.tensor_network.tensors, kept_qubits):
        leaf.data = kets[q]
    for g in kept:
        data, qubits = gates[g]
        reduced.append_gate(data, [reg.qubit(new[q]) for q in qubits])
    return reduced, kept_qubits
