"""Circuit family ``kicked_ising``: the Trotterized transverse-field Ising
circuits of Kim et al., Nature 618:500 (2023), on IBM's 127-qubit
heavy-hex map.

The map is built, not listed: seven rows of qubits (14, then five of 15,
then 14; the first row has columns 0-13, the last columns 1-14, the
others 0-14), numbered row by row with the four bridge qubits between
two rows after the upper one; neighbours in a row are coupled, and a
bridge couples the qubits above and below it at columns 0, 4, 8, 12 and
2, 6, 10, 14 in turn. That is ``ibm_kyiv``'s numbering (0-13, bridges
14-17, 18-32, bridges 33-36, ...): 127 qubits, 144 couplings, degrees 1
to 3. It is NOT the program's ``ConnectivityLayout.EAGLE`` (the
reference's table of 142 couplings). A configuration says under
``assumed`` that map and operator were written from memory of the paper
and are checked by the size of the operator's lightcone (68 qubits).

A circuit is ``steps`` rounds of ``rx(theta_h)`` on every qubit and
``rzz(theta_zz)`` on every coupling, closed by one more ``rx`` layer
where ``final_rx``; the state starts in ``|0...0>``. ``theta_h`` is
drawn from the seed, uniform in (0, pi/2): the paper sweeps it.
"""

from __future__ import annotations

import math

import numpy as np

ROW_COLUMNS = [range(0, 14)] + [range(0, 15)] * 5 + [range(1, 15)]
BRIDGE_COLUMNS = [(0, 4, 8, 12), (2, 6, 10, 14)]


def heavy_hex_127() -> tuple[int, list]:
    """``(qubits, couplings)`` of the map above, 0-based."""
    label: dict = {}  # (row, column) -> qubit
    bridges = []  # (qubit, row above, column)
    n = 0
    for row, columns in enumerate(ROW_COLUMNS):
        for col in columns:
            label[row, col] = n
            n += 1
        if row + 1 < len(ROW_COLUMNS):
            for col in BRIDGE_COLUMNS[row % 2]:
                bridges.append((n, row, col))
                n += 1
    couplings = []
    for row, columns in enumerate(ROW_COLUMNS):
        couplings += [(label[row, c], label[row, c + 1]) for c in list(columns)[:-1]]
    for qubit, row, col in bridges:
        couplings += [(label[row, col], qubit), (qubit, label[row + 1, col])]
    return n, sorted(couplings)


def parse_angle(value) -> float:
    """A number, or a multiple of pi spelt ``"pi/2"``, ``"-pi/2"``, ``"pi"``."""
    if isinstance(value, (int, float)):
        return float(value)
    text = str(value).replace(" ", "")
    sign = -1.0 if text.startswith("-") else 1.0
    text = text.lstrip("+-")
    if not text.startswith("pi"):
        return sign * float(text)
    return sign * math.pi / (float(text[3:]) if text[2:3] == "/" else 1.0)


def theta_h(spec: dict, rng: np.random.Generator) -> float:
    return float(rng.uniform(0.0, math.pi / 2.0))


def gates(spec: dict, rng: np.random.Generator) -> list:
    """The configuration's circuit on the 127-qubit map, or on the patch
    a ``couplings`` entry lists (tests)."""
    if "couplings" in spec:
        qubits, couplings = int(spec["qubits"]), [tuple(p) for p in spec["couplings"]]
    else:
        qubits, couplings = heavy_hex_127()
        if int(spec["qubits"]) != qubits:
            raise ValueError(f"the heavy-hex map has {qubits} qubits, not {spec['qubits']}")
    return gates_on(qubits, couplings, int(spec["steps"]), theta_h(spec, rng),
                    parse_angle(spec["theta_zz"]), bool(spec.get("final_rx")))


def gates_on(qubits, couplings, steps, theta, theta_zz, final_rx) -> list:
    """The family's circuit on a coupling list."""
    out: list = []
    for step in range(steps + (1 if final_rx else 0)):
        out += [("rx", (theta,), (q,)) for q in range(qubits)]
        if step < steps:
            out += [("rzz", (theta_zz,), pair) for pair in couplings]
    return out


def observable(spec: dict) -> str:
    """The configuration's operator as one letter a qubit."""
    letters = ["i"] * int(spec["qubits"])
    for letter, sites in spec["observable"].items():
        for q in sites:
            letters[q] = letter.lower()
    return "".join(letters)
