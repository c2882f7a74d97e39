"""Circuit family ``sycamore``: the random circuits of Arute et al., Nature
574:505 (2019), on the first ``qubits`` labels of the 53-qubit device.

The layout below is a copy of the coupler activation patterns A-D of the
device (1-based labels, as in the program's
``tnc_tpu/builders/connectivity.py``; the original is listed in PERF.md
for a later PR to reconcile). A circuit on ``n < 53`` qubits keeps the
pairs whose two labels are both ``<= n``. Single-qubit gates are drawn
uniformly from sqrt X, sqrt Y, sqrt Z (without the paper's no-repeat rule:
a configuration says so under ``assumed``).
"""

from __future__ import annotations

import math

import numpy as np

SYCAMORE_PATTERNS = {
    "a": [
        (31, 32), (29, 24), (40, 26), (53, 44), (21, 22), (18, 7), (25, 15),
        (48, 42), (8, 11), (5, 1), (16, 6), (46, 51), (14, 4), (2, 3),
        (12, 10), (47, 41), (13, 27), (9, 17), (20, 19), (50, 43), (28, 39),
        (23, 30), (34, 33), (49, 45),
    ],
    "b": [
        (32, 37), (24, 21), (26, 18), (44, 25), (22, 35), (7, 8), (15, 5),
        (42, 16), (1, 4), (6, 2), (51, 12), (14, 36), (3, 13), (10, 9),
        (41, 20), (27, 38), (17, 28), (19, 23), (43, 34),
    ],
    "c": [
        (52, 32), (31, 24), (29, 26), (40, 44), (37, 22), (21, 7), (18, 15),
        (25, 42), (35, 11), (8, 1), (5, 6), (16, 51), (4, 3), (2, 10),
        (12, 41), (36, 27), (13, 17), (9, 19), (20, 43), (38, 39), (28, 30),
        (23, 33), (34, 45),
    ],
    "d": [
        (32, 21), (24, 18), (26, 25), (44, 48), (22, 8), (7, 5), (15, 16),
        (42, 46), (11, 4), (1, 2), (6, 12), (51, 47), (14, 13), (3, 9),
        (10, 20), (41, 50), (27, 28), (17, 23), (19, 34), (43, 49),
    ],
}
SYCAMORE_CYCLE_ORDER = "abcdcdab"
SINGLE_QUBIT_GATES = ("sx", "sy", "sz")
FSIM_PARAMS = (math.pi / 2.0, math.pi / 6.0)


def gates(spec: dict, rng: np.random.Generator) -> list:
    """``cycles`` rounds of (random single-qubit layer, fsim layer on the
    round's pattern), closed by one more single-qubit layer."""
    qubits, cycles = int(spec["qubits"]), int(spec["cycles"])
    if not 2 <= qubits <= 53:
        raise ValueError(f"sycamore circuits have 2..53 qubits, not {qubits}")
    out: list = []
    for cycle in range(cycles + 1):
        for q in range(qubits):
            name = SINGLE_QUBIT_GATES[int(rng.integers(0, 3))]
            out.append((name, (), (q,)))
        if cycle == cycles:
            break
        pattern = SYCAMORE_CYCLE_ORDER[cycle % len(SYCAMORE_CYCLE_ORDER)]
        for i, j in SYCAMORE_PATTERNS[pattern]:
            if i <= qubits and j <= qubits:
                out.append(("fsim", FSIM_PARAMS, (i - 1, j - 1)))
    return out
