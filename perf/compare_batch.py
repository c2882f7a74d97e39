"""The comparison that decides ``correct`` for a correlated amplitude
batch: a call's ``(2,)*k`` partial sum against the plain reference
(``perf/reference.py``, used as it is), slice by slice in complex128.

The reference is told the *question* as for one amplitude (the legs of
each leaf, the pair order, the sliced legs) and builds its network from
the benchmark's own gate list: ``reference.raw_network`` of the closed
bits, with the bras of the open qubits left out. The leg a qubit's bra
would have closed is that qubit's open leg, so the reference knows by
itself which axis of its result is which qubit, and the comparison holds
the program to ``open_qubits`` order: a batch whose axes are permuted
sits at other bitstrings and reads of order 1, as does a call that
dropped slices or summed another prefix.

Slices are spread over host processes as ``perf/compare.py`` spreads
them (its worker count and worker environment); a slice's value here is
a tensor of ``2^k`` numbers, not one.
"""

from __future__ import annotations

import math
import multiprocessing
import os
import time

import numpy as np

from perf import common, compare, reference

_WORKER: dict = {}


def raw_network(gates, n_qubits: int, closed_bits: str, open_qubits):
    """``(raw, open_legs)``: the reference's network of the circuit closed
    with ``closed_bits`` (one per closed qubit, ascending) and open at
    ``open_qubits``; ``open_legs[j]`` is the leg of ``open_qubits[j]``."""
    taken = set(open_qubits)
    if len(taken) != len(open_qubits) or len(closed_bits) != n_qubits - len(taken):
        raise ValueError(
            f"{len(closed_bits)} closed bits and open qubits {list(open_qubits)} "
            f"do not make {n_qubits} qubits"
        )
    closed = iter(closed_bits)
    bits = "".join("0" if q in taken else next(closed) for q in range(n_qubits))
    raw = reference.raw_network(gates, n_qubits, bits)
    bras = raw[-n_qubits:]  # in qubit order: the builder convention
    kept = raw[:-n_qubits] + [bras[q] for q in range(n_qubits) if q not in taken]
    return kept, [bras[q][0][0] for q in open_qubits]


def _reference_for(gates, n_qubits, closed_bits, open_qubits, question, precision):
    """``(reference, leaves' data, axes)``: ``np.transpose(value.reshape(
    (2,)*k), axes)`` has axis ``j`` at ``open_qubits[j]``."""
    raw, open_legs = raw_network(gates, n_qubits, closed_bits, open_qubits)
    leaves = reference.group_leaves(raw, question["leaf_legs"])
    ref = reference.Reference(
        [legs for legs, _ in leaves], question["pairs"],
        question["sliced_legs"], question["sliced_dims"],
        (), question.get("leg_dims"), precision=precision,
    )
    if sorted(ref.result_legs) != sorted(open_legs):
        raise ValueError(
            f"the plan leaves legs {sorted(ref.result_legs)} open, the "
            f"question's open qubits have legs {sorted(open_legs)}"
        )
    axes = [ref.result_legs.index(leg) for leg in open_legs]
    return ref, [data for _, data in leaves], axes


def _init_worker(*args) -> None:
    ref, leaves, axes = _reference_for(*args)
    _WORKER.update(ref=ref, placed=ref.place(leaves), axes=axes)


def _slice_value(s: int) -> np.ndarray:
    axes = _WORKER["axes"]
    value = np.asarray(_WORKER["ref"].value(_WORKER["placed"], s))
    return np.transpose(value.reshape((2,) * len(axes)), axes).copy()


def slice_values(gates, n_qubits, closed_bits, open_qubits, question, slices,
                 precision="complex128") -> dict:
    """The reference's ``(2,)*k`` value of each slice in ``slices``, axes
    in ``open_qubits`` order."""
    slices = list(slices)
    args = (gates, n_qubits, closed_bits, tuple(open_qubits), question, precision)
    # a question that does not fit this network fails HERE: an initializer
    # that raises in a worker leaves the pool respawning it for ever
    _reference_for(*args)
    n = compare._workers(len(slices))
    t0 = time.monotonic()
    saved = {k: os.environ.get(k) for k in compare._WORKER_ENV}
    os.environ.update(compare._WORKER_ENV)
    try:
        with multiprocessing.get_context("spawn").Pool(
            n, initializer=_init_worker, initargs=args
        ) as pool:
            values = pool.map(_slice_value, slices, chunksize=1)
    finally:
        for k, v in saved.items():
            os.environ.pop(k) if v is None else os.environ.__setitem__(k, v)
    common.progress("reference", f"{len(slices)} slices in {precision} on {n} host processes", t0)
    return dict(zip(slices, values))


def batch_sum_gap(gates, n_qubits, closed_bits, open_qubits, question, answers,
                  precision="complex128"):
    """``answers``: ``[(lo, hi, got)]``, ``got`` the timed call's sum over
    slices ``lo..hi`` as a ``(2,)*k`` array in ``open_qubits`` order. The
    gap of a call is ``||got - want||_2`` over the root of the summed
    squared norms of its slices' reference tensors: the scale a sum of
    that many terms is accurate to, over the whole batch (one amplitude
    of 64 that vanishes does not move it). Where every amplitude of the
    batch vanishes in every slice the scale is a tenth of what a batch's
    share of that many slices typically is, ``2^((k - n)/2) sqrt(slices
    of the call / slices of the plan)``, as ``compare.slice_sum_gap`` has
    it for one amplitude: the comparison then only says both are zero.
    Each call's numbers are printed."""
    wanted = sorted({s for lo, hi, _ in answers for s in range(lo, hi)})
    values = slice_values(gates, n_qubits, closed_bits, open_qubits, question,
                          wanted, precision)
    num_slices = math.prod(question["sliced_dims"])
    k = len(open_qubits)
    worst = 0.0
    for lo, hi, got in answers:
        vals = np.stack([values[s] for s in range(lo, hi)])
        rss = math.sqrt(float(np.sum(np.abs(vals) ** 2)))
        typical = 2.0 ** ((k - n_qubits) / 2.0) * math.sqrt((hi - lo) / num_slices)
        want = vals.sum(axis=0)
        err = float(np.linalg.norm((np.asarray(got).reshape(want.shape) - want).reshape(-1)))
        gap = err / max(rss, 0.1 * typical)
        common.emit({"phase": "check", "slices": [lo, hi], "abs_error": err, "rss": rss,
                     "typical": typical, "want_norm": float(np.linalg.norm(want.reshape(-1))),
                     "nonzero_amplitudes": int(np.sum(np.abs(want) > 1e-3 * typical))})
        worst = max(worst, gap if math.isfinite(gap) else float("inf"))
    return worst
