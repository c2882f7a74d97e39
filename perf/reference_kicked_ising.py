"""Plain reference of the ``kicked_ising`` configurations: a Pauli
string's expectation value by its lightcone and a sandwich network.

Imports nothing of the program and takes no number from it. Its data are
the benchmark's own gate list (``perf/families/kicked_ising.py``), the
operator's letters, and the textbook matrices below. It finds the
lightcone by a backward walk of its OWN, knowing the two gates by name
(``rzz`` is diagonal, ``rx`` is not), numbers the cone's qubits in
ascending order of their labels, and builds the sandwich in the
published builder convention of legs (Rust ``tnc``
``circuit_builder.rs``):

- qubit ``q`` of the cone starts on leg ``q`` with a ``|0>`` ket; a gate
  takes one fresh leg per qubit, in the order of its qubits, and its
  tensor's legs are ``new ++ old`` (storage ``(out…, in…)``);
- the adjoint mirror of every tensor, in the same order, on legs
  ``old ++ new`` moved up by ``offset`` (the number of legs the circuit
  used), holding the conjugate with its halves swapped;
- one closure a qubit, in qubit order, on legs ``[open, open + offset]``:
  the TRANSPOSE of the site's Pauli matrix (the network sums ``psi_a
  T[a, b] conj(psi)_b``), the identity off the operator.

A program that found another cone or numbered its legs differently would
be asked a different partial sum and fail the comparison, which is the
safe side. From the program the reference takes the *question* only, as
``perf/reference.py`` does for an amplitude (whose grouping, ordering
and contraction it uses): the legs each leaf leaves open, the pair
order, the sliced legs and their dimensions. It contracts in numpy
complex128 on the host, one slice at a time, spread over host
processes.
"""

from __future__ import annotations

import cmath
import math
import multiprocessing
import os
import time

import numpy as np

from perf import common, compare, reference

_KET0 = np.array([1.0, 0.0], dtype=np.complex128)
PAULI = {
    "i": np.eye(2, dtype=np.complex128),
    "x": np.array([[0, 1], [1, 0]], dtype=np.complex128),
    "y": np.array([[0, -1j], [1j, 0]], dtype=np.complex128),
    "z": np.array([[1, 0], [0, -1]], dtype=np.complex128),
}
DIAGONAL_GATES = frozenset({"rzz"})


def gate_matrix(name: str, params) -> np.ndarray:
    """The gate as a ``(2,)*2k`` tensor, axes ``(out…, in…)``."""
    (theta,) = params
    if name == "rx":  # exp(-i theta/2 X)
        c, s = math.cos(theta / 2.0), math.sin(theta / 2.0)
        m = np.array([[c, -1j * s], [-1j * s, c]])
    elif name == "rzz":  # exp(-i theta/2 Z x Z)
        lo, hi = cmath.exp(-0.5j * theta), cmath.exp(0.5j * theta)
        m = np.diag([lo, hi, hi, lo])
    else:
        raise ValueError(f"the reference knows no gate {name!r}")
    m = np.asarray(m, dtype=np.complex128)
    k = int(round(math.log2(m.shape[0])))
    return m.reshape((2,) * (2 * k))


def cone(gates, n_qubits: int, letters: str):
    """``(kept gates, kept qubits)``: the gates that can reach the
    operator, in their order, and the qubits it can reach, ascending.

    Backwards from the operator, a qubit is *off* (the evolved operator
    is the identity there), *diagonal* (it is diagonal there: a ``z``
    site, or a neighbour that only ZZ gates reached) or *full*. A gate
    with every qubit off cancels against its adjoint; so does a diagonal
    gate with no full qubit, since it commutes with the operator as it
    stands. A kept diagonal gate makes its off qubits diagonal, a kept
    ``rx`` makes its qubit full."""
    if len(letters) != n_qubits or set(letters) - set(PAULI):
        raise ValueError(f"operator {letters!r} is not {n_qubits} of i/x/y/z")
    off, diag, full = 0, 1, 2
    state = [{"i": off, "z": diag}.get(c, full) for c in letters]
    kept = []
    for gate in reversed(gates):
        name, _, qubits = gate
        reach = max(state[q] for q in qubits)
        if reach == off or (name in DIAGONAL_GATES and reach == diag):
            continue
        for q in qubits:
            state[q] = max(state[q], diag) if name in DIAGONAL_GATES else full
        kept.append(gate)
    kept.reverse()
    return kept, [q for q in range(n_qubits) if state[q] != off]


def raw_network(gates, n_qubits: int, letters: str):
    """``[(legs, tensor)]`` of the cone's sandwich: kets, gates, their
    adjoint mirrors, closures in qubit order."""
    kept, qubits = cone(gates, n_qubits, letters)
    new = {q: i for i, q in enumerate(qubits)}
    open_leg = list(range(len(qubits)))
    net = [((q,), _KET0) for q in range(len(qubits))]
    next_leg = len(qubits)
    for name, params, on in kept:
        on = [new[q] for q in on]
        fresh = tuple(range(next_leg, next_leg + len(on)))
        next_leg += len(on)
        old = tuple(open_leg[q] for q in on)
        for q, leg in zip(on, fresh):
            open_leg[q] = leg
        net.append((fresh + old, gate_matrix(name, params)))
    offset = next_leg
    for legs, tensor in list(net):
        half = len(legs) // 2
        swapped = tuple(range(half, len(legs))) + tuple(range(half))
        net.append((
            tuple(leg + offset for leg in legs[half:] + legs[:half]),
            np.conj(np.transpose(tensor, swapped)),
        ))
    for i, q in enumerate(qubits):
        leg = open_leg[i]
        net.append(((leg, leg + offset), PAULI[letters[q]].T.copy()))
    return net


# -- slices over host processes (as perf/compare.py, with this network) ---

_WORKER: dict = {}


def _reference_for(gates, n_qubits, letters, question, precision):
    raw = raw_network(gates, n_qubits, letters)
    leaves = reference.group_leaves(raw, question["leaf_legs"])
    ref = reference.Reference(
        [legs for legs, _ in leaves], question["pairs"],
        question["sliced_legs"], question["sliced_dims"],
        (), question.get("leg_dims"), precision=precision,
    )
    return ref, [data for _, data in leaves]


def _init_worker(gates, n_qubits, letters, question, precision) -> None:
    ref, leaves = _reference_for(gates, n_qubits, letters, question, precision)
    _WORKER.update(ref=ref, placed=ref.place(leaves))


def _slice_value(s: int) -> complex:
    return complex(np.asarray(_WORKER["ref"].value(_WORKER["placed"], s)).reshape(-1)[0])


def slice_values(gates, n_qubits, letters, question, slices, precision="complex128") -> dict:
    """The reference's value of each slice in ``slices``."""
    slices = list(slices)
    # a question that does not fit this network fails HERE: an initializer
    # that raises in a worker leaves the pool respawning it for ever
    _reference_for(gates, n_qubits, letters, question, precision)
    n = compare._workers(len(slices))
    t0 = time.monotonic()
    saved = {k: os.environ.get(k) for k in compare._WORKER_ENV}
    os.environ.update(compare._WORKER_ENV)
    try:
        with multiprocessing.get_context("spawn").Pool(
            n, initializer=_init_worker,
            initargs=(gates, n_qubits, letters, question, precision),
        ) as pool:
            values = pool.map(_slice_value, slices, chunksize=1)
    finally:
        for k, v in saved.items():
            os.environ.pop(k) if v is None else os.environ.__setitem__(k, v)
    common.progress("reference", f"{len(slices)} slices in {precision} on {n} host processes", t0)
    return dict(zip(slices, values))


LOSS_FLOOR = 0.01  # of the root of the summed squares of the slices' own losses


def slice_sum_gap(gates, n_qubits, letters, question, answers):
    """``answers``: ``[(lo, hi, got)]``, ``got`` the timed call's sum over
    slices ``lo..hi``. The gap of a call is ``|got - want|`` over what
    the reference ITSELF loses on that sum in the nearest precision
    below the one the configuration states (float32 planes, every
    product in three bfloat16 passes, a TPU's ``high``:
    ``perf/reference.py``): the call's error in units of that
    precision's error on the same slices. A sound answer reads near the
    ratio of the two precisions' epsilons (0.01), the lower precision in
    the program's place reads of order 1, a call that dropped slices,
    ran at another angle or summed another network reads its slices'
    values over their rounding: hundreds and more.

    Not over the root of the summed squares of the slices' values, as
    ``compare.slice_sum_gap`` has it for an amplitude: with the Clifford
    ``rzz(-pi/2)`` most of this network's slices vanish, many only by
    cancellation (1e-24 beside neighbours of 1e-6, in complex128), and
    float32 leaves 1e-10 there; over the values' root of summed squares
    seven sound seeds read 1.5e-9 to 2.4e-5 where the control read
    7.3e-5 (PERF.md, PR 33). The lower precision's loss on the same sum
    measures how far THESE slices cancel. Slices come in pairs whose
    roundings cancel in the sum, in both precisions alike, so the unit
    is the loss of the sum, not of the slices one by one; it is never
    taken under ``LOSS_FLOOR`` of the slices' own losses' root of summed
    squares, lest a sum that happens to round well pass for a unit.
    Where nothing is lost at all (every slice an exact zero) a sound
    answer is zero too and reads 0, anything else infinite. Each call's
    numbers are printed (``rss`` is the amplitude cells' scale, for the
    record)."""
    wanted = sorted({s for lo, hi, _ in answers for s in range(lo, hi)})
    exact = slice_values(gates, n_qubits, letters, question, wanted)
    low = slice_values(gates, n_qubits, letters, question, wanted, "bf16x3")
    worst = 0.0
    for lo, hi, got in answers:
        vals = np.array([exact[s] for s in range(lo, hi)])
        loss = np.array([low[s] for s in range(lo, hi)]) - vals
        each = math.sqrt(float(np.sum(np.abs(loss) ** 2)))
        unit = max(abs(complex(loss.sum())), LOSS_FLOOR * each)
        got = complex(np.asarray(got).reshape(-1)[0])
        err = abs(got - complex(vals.sum()))
        gap = err / unit if unit > 0.0 else (0.0 if err == 0.0 else float("inf"))
        common.emit({"phase": "check", "slices": [lo, hi], "abs_error": err,
                     "low_precision_sum_loss": abs(complex(loss.sum())),
                     "low_precision_slice_losses_rss": each,
                     "rss": math.sqrt(float(np.sum(np.abs(vals) ** 2))),
                     "want": [vals.sum().real, vals.sum().imag],
                     "nonzero_slices": int(np.sum(np.abs(vals) > 0.0))})
        worst = max(worst, gap if math.isfinite(gap) else float("inf"))
    return worst


def expectation(gates, n_qubits: int, letters: str) -> complex:
    """Dense ``<0|U+ P U|0>`` of the WHOLE circuit in complex128 (tests,
    at sizes that fit): no cone, no network."""
    psi = np.zeros((2,) * n_qubits, dtype=np.complex128)
    psi[(0,) * n_qubits] = 1.0
    for name, params, qubits in gates:
        k = len(qubits)
        psi = np.tensordot(gate_matrix(name, params), psi,
                           axes=(list(range(k, 2 * k)), list(qubits)))
        psi = np.moveaxis(psi, list(range(k)), list(qubits))
    out = psi
    for q, c in enumerate(letters):
        if c != "i":
            out = np.moveaxis(np.tensordot(PAULI[c], out, axes=(1, q)), 0, q)
    return complex(np.vdot(psi, out))
