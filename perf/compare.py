"""The comparison that decides ``correct``: what the timed path produced
against the plain reference (``perf/reference.py``), one number per kind
of answer. Each returns the widest gap over the answers compared.

The reference runs in numpy on the host, one slice or one request at a
time; here the slices and requests are spread over host processes
(spawned, numpy only: they never touch the chip)."""

from __future__ import annotations

import math
import multiprocessing
import os
import time

import numpy as np

from perf import common, reference

_WORKER: dict = {}
# What a worker process is started with (read when it loads libc and numpy):
# one BLAS thread to a process (n processes with a BLAS pool each are slower
# than one), and a malloc that keeps and reuses its heap. With the default,
# every 128 MB temporary is mapped and unmapped again; the chip's machine
# counts such memory long after it is freed, and twelve workers of 0.5 GB
# each met its 40 GiB limit in 20 s (PERF.md, PR 25).
_WORKER_ENV = {
    "OMP_NUM_THREADS": "1", "OPENBLAS_NUM_THREADS": "1", "MKL_NUM_THREADS": "1",
    "MALLOC_MMAP_MAX_": "0", "MALLOC_TRIM_THRESHOLD_": str(1 << 40),
    "MALLOC_TOP_PAD_": str(1 << 28),
}


MAX_WORKERS = 28  # a worker peaks at 0.6 GB: 12 fit a one-chip machine's 40 GiB, 28 a host's 140


def _workers(n_tasks: int) -> int:
    """Host processes for ``n_tasks``: the cores this process may use
    (13 on a one-chip machine, 30 on a four-chip host) less one."""
    return max(1, min(n_tasks, len(os.sched_getaffinity(0)) - 1, MAX_WORKERS))


def _reference_for(gates, n_qubits, bits, question, precision):
    raw = reference.raw_network(gates, n_qubits, bits)
    leaves = reference.group_leaves(raw, question["leaf_legs"])
    ref = reference.Reference(
        [legs for legs, _ in leaves], question["pairs"],
        question["sliced_legs"], question["sliced_dims"],
        question.get("varying_leaves", ()), question.get("leg_dims"),
        precision=precision,
    )
    return ref, [data for _, data in leaves]


def _init_worker(gates, n_qubits, bits, question, precision) -> None:
    ref, leaves = _reference_for(gates, n_qubits, bits, question, precision)
    _WORKER.update(ref=ref, placed=ref.place(leaves), gates=gates, n=n_qubits,
                   leaf_legs=question["leaf_legs"])


def _slice_value(s: int) -> complex:
    return complex(np.asarray(_WORKER["ref"].value(_WORKER["placed"], s)).reshape(-1)[0])


def _amplitude(bits: str) -> complex:
    # one reference for all requests: only the bras' data differ
    raw = reference.raw_network(_WORKER["gates"], _WORKER["n"], bits)
    leaves = [data for _, data in reference.group_leaves(raw, _WORKER["leaf_legs"])]
    ref = _WORKER["ref"]
    return complex(np.asarray(ref.value(ref.rebind(_WORKER["placed"], leaves))).reshape(-1)[0])


def _spread(task, items, init_args, what: str) -> list:
    """``[task(item)]`` over host processes that each hold the reference."""
    items = list(items)
    n = _workers(len(items))
    t0 = time.monotonic()
    ctx = multiprocessing.get_context("spawn")
    saved = {k: os.environ.get(k) for k in _WORKER_ENV}
    os.environ.update(_WORKER_ENV)
    try:
        with ctx.Pool(n, initializer=_init_worker, initargs=init_args) as pool:
            out = pool.map(task, items, chunksize=1)
    finally:
        for k, v in saved.items():
            os.environ.pop(k) if v is None else os.environ.__setitem__(k, v)
    common.progress("reference", f"{len(items)} {what} on {n} host processes", t0)
    return out


def slice_values(gates, n_qubits, bits, question, slices, precision="complex128") -> dict:
    """The reference's value of each slice in ``slices``."""
    slices = list(slices)
    values = _spread(_slice_value, slices, (gates, n_qubits, bits, question, precision), "slices")
    return dict(zip(slices, values))


def slice_sum_gap(gates, n_qubits, bits, question, answers, precision="complex128"):
    """``answers``: ``[(lo, hi, got)]``, ``got`` the timed call's sum over
    slices ``lo..hi``. The gap of a call is ``|got - want|`` over the
    root of the summed squares of its slices' reference values, the
    scale a sum of that many terms is accurate to; a call that summed
    other slices, or dropped some, reads of order 1. Where the circuit's
    amplitude vanishes (about one seed in twenty: the gate set is nearly
    Clifford, and every slice is then zero to rounding, 1e-59) the scale
    is a tenth of what an amplitude's share of that many slices
    typically is, ``2^(-n/2) sqrt(slices of the call / slices of the
    plan)``: the comparison then only says that both are zero."""
    wanted = sorted({s for lo, hi, _ in answers for s in range(lo, hi)})
    values = slice_values(gates, n_qubits, bits, question, wanted, precision)
    num_slices = math.prod(question["sliced_dims"])
    worst = 0.0
    for lo, hi, got in answers:
        vals = np.array([values[s] for s in range(lo, hi)])
        typical = 2.0 ** (-n_qubits / 2.0) * math.sqrt((hi - lo) / num_slices)
        scale = max(math.sqrt(float(np.sum(np.abs(vals) ** 2))), 0.1 * typical)
        got = complex(np.asarray(got).reshape(-1)[0])
        gap = abs(got - complex(vals.sum())) / scale
        worst = max(worst, gap if math.isfinite(gap) else float("inf"))
    return worst


def amplitudes(gates, n_qubits, question, bitstrings, precision="complex128") -> list:
    """The reference's amplitude of each bitstring, by an unsliced plan
    whose leaves are the raw tensors (``varying_leaves``: the bras)."""
    bitstrings = list(bitstrings)
    return _spread(_amplitude, bitstrings,
                   (gates, n_qubits, bitstrings[0], question, precision), "requests")


def amplitude_gap(gates, n_qubits, question, answers, precision="complex128"):
    """``answers``: ``[(bitstring, got)]``. The gap of a request is
    ``|got - want| / max(|want|, 2^(-n/2))``: relative to the amplitude,
    or to the typical magnitude where the amplitude is smaller."""
    floor = 2.0 ** (-n_qubits / 2.0)
    wants = amplitudes(gates, n_qubits, question, [b for b, _ in answers], precision)
    worst = 0.0
    for (_, got), want in zip(answers, wants):
        got = complex(np.asarray(got).reshape(-1)[0])
        gap = abs(got - want) / max(abs(want), floor)
        worst = max(worst, gap if math.isfinite(gap) else float("inf"))
    return worst
