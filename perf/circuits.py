"""Seeded inputs of the benchmark: circuits as plain gate lists.

The benchmark makes its inputs itself, from ``--seed``; the program and
the plain reference both receive the same list and nothing else. A gate
is ``(name, params, qubits)`` with 0-based qubits; names are those of
the published gate set (``sx``/``sy``/``sz`` = the square roots of the
Paulis, ``fsim(theta, phi)`` as in Arute et al. 2019).

A circuit family is one module ``perf/families/<family>.py`` with
``gates(spec, rng)``; a configuration's ``circuit`` entry names it.
"""

from __future__ import annotations

import importlib

import numpy as np


def circuit_gates(spec: dict, seed: int) -> list:
    """The gate list of a configuration's ``circuit`` entry for ``seed``."""
    family = importlib.import_module(f"perf.families.{spec['family']}")
    return family.gates(spec, np.random.default_rng([seed, 1]))


def seeded_bitstrings(n: int, qubits: int, seed: int) -> list[str]:
    """``n`` uniform bitstrings; a stream of its own, so that the gates do
    not shift when the number of requests does."""
    rng = np.random.default_rng([seed, 2])
    bits = rng.integers(0, 2, size=(n, qubits))
    return ["".join("01"[b] for b in row) for row in bits]
