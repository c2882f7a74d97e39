"""Correlated amplitude batches: the ``2^k`` amplitudes of the bitstrings
that share their closed bits, from ``k`` open qubits in ONE contraction,
with frugal rejection sampling and linear XEB on top.

How the field samples from, and XEB-checks, a Sycamore-class circuit
with a tensor-network simulator (qFlex, Villalonga et al., npj QI 5:86;
Huang et al., arXiv:2005.06787): leave a handful of output qubits OPEN,
so one contraction yields the amplitudes of ``2^k`` bitstrings for
little more than the price of one, then accept a bitstring of the batch
by frugal rejection sampling (Markov et al., arXiv:1807.10749).

The open qubits are open LEGS of an ordinary amplitude template
(:meth:`~tnc_tpu.builders.circuit_builder.Circuit.
into_amplitude_template` with ``'*'`` there): the batch rides the same
plan cache, ``plan_structure``, budgeted slicing and executors as every
other query (:func:`~tnc_tpu.serve.rebind.bind_template`), with no
batch axis anywhere; the slicer never takes an open leg. What this
module adds is the order of the answer: an executor returns the open
axes in its program's result-leg order, which is the planner's
business; :class:`AmplitudeBatchProgram` gives axis ``j`` to
``open_qubits[j]``, whatever the plan.

A batch at 53 qubits is hours of slices: ``slice_range=`` gives the
partial sum of a range (a partition of the slices adds up to the batch),
resumable and shardable like an amplitude's.

The service gets no handler for a batch (``queries/handlers.py``): a
batch is hours a request, and the service's sliced path is one slice
loop a request.
"""

from __future__ import annotations

from typing import Iterable, Sequence

import numpy as np

from tnc_tpu import obs
from tnc_tpu.builders.circuit_builder import (
    BASIS_STATES,
    Circuit,
    normalize_bitstring,
)

__all__ = [
    "AmplitudeBatchProgram",
    "bind_amplitude_batch",
    "frugal_rejection_sample",
    "linear_xeb",
    "sample_from_batches",
]


class AmplitudeBatchProgram:
    """A bound amplitude template with ``k`` open qubits and rebindable
    bras on the others: the counterpart of
    :class:`~tnc_tpu.queries.expectation.ExpectationProgram` for a
    correlated batch (it wraps a :class:`~tnc_tpu.serve.rebind.
    BoundProgram`: same planning, plan cache and slicing).

    ``open_qubits`` as given to :func:`bind_amplitude_batch` (any
    order); ``closed_qubits`` the others, ascending: a call's
    ``closed_bits`` are theirs, in that order. ``permutation`` takes the
    executor's result (``bound.result_legs`` order) to ``open_qubits``
    order: ``np.transpose(result, permutation)``."""

    def __init__(self, bound, open_qubits: Sequence[int]) -> None:
        template = bound.template
        self.bound = bound
        self.num_qubits = template.num_qubits
        self.open_qubits = tuple(int(q) for q in open_qubits)
        self.closed_qubits = tuple(template.determined)
        # the template's permutor lists the open legs in qubit order
        leg_of = dict(zip(sorted(self.open_qubits), template.permutor.target_leg_order))
        at = {leg: axis for axis, leg in enumerate(bound.result_legs)}
        self.permutation = tuple(at[leg_of[q]] for q in self.open_qubits)

    @property
    def num_slices(self) -> int:
        sliced = self.bound.sliced
        return 1 if sliced is None else sliced.slicing.num_slices

    def _closed(self, closed_bits: str | Iterable) -> str:
        bits = normalize_bitstring(closed_bits)
        if len(bits) != len(self.closed_qubits) or "*" in bits:
            raise ValueError(
                f"closed_bits must be {len(self.closed_qubits)} of 0/1, one "
                f"per closed qubit in ascending order, got {bits!r}"
            )
        return bits

    def bitstrings(self, closed_bits: str | Iterable) -> list[str]:
        """The ``2^k`` full bitstrings of a batch, in the flat
        (row-major) order of :meth:`amplitudes`' array: entry ``i`` has
        bit ``(i >> (k - 1 - j)) & 1`` at ``open_qubits[j]``."""
        bits = self._closed(closed_bits)
        full = ["0"] * self.num_qubits
        for q, c in zip(self.closed_qubits, bits):
            full[q] = c
        k = len(self.open_qubits)
        out = []
        for i in range(1 << k):
            for j, q in enumerate(self.open_qubits):
                full[q] = "01"[(i >> (k - 1 - j)) & 1]
            out.append("".join(full))
        return out

    def to_host(self, value) -> np.ndarray:
        """A ``host=False`` result fetched and ordered as ``host=True``
        returns it: the wait for the device, the copy, the permutation."""
        if isinstance(value, tuple):  # split mode: (real, imag) planes
            from tnc_tpu.ops.split_complex import combine_array

            value = combine_array(*value)
        shape = (2,) * len(self.open_qubits)
        return np.transpose(np.asarray(value).reshape(shape), self.permutation)

    def amplitudes(
        self,
        closed_bits: str | Iterable,
        backend=None,
        slice_range: tuple[int, int] | None = None,
        ckpt: str | None = None,
        on_slice=None,
        host: bool = True,
    ):
        """The batch's amplitudes as a ``(2,)*k`` complex array whose
        axis ``j`` is ``open_qubits[j]`` (index 0/1 = that qubit's bit).

        Budget-sliced structures only: ``slice_range=(lo, hi)`` gives
        the **partial sum** over that contiguous range of slices;
        ``ckpt`` / ``on_slice`` pass to backends with
        ``supports_slice_hooks`` (dropped elsewhere, as
        :meth:`~tnc_tpu.serve.rebind.BoundProgram.amplitudes_det`
        does); ``host=False`` returns the result as the backend's
        ``execute_sliced(..., host=False)`` leaves it (device-resident,
        stored shape, the PROGRAM's axis order; a (real, imag) pair in
        split mode) with no transfer, so a call can stay in flight:
        :meth:`to_host` (or ``np.transpose(fetched.reshape((2,)*k),
        program.permutation)``) then gives what ``host=True`` returns.
        """
        from tnc_tpu.ops.backends import NumpyBackend

        bits = self._closed(closed_bits)
        bound = self.bound
        if bound.sliced is None and (slice_range is not None or not host):
            raise ValueError(
                "slice_range and host=False only apply to sliced "
                "structures (this bound program has no slicing)"
            )
        if backend is None:
            backend = NumpyBackend()
        k = len(self.open_qubits)
        with obs.phase("ampbatch.amplitudes", open=k) as sp:
            with obs.phase("ampbatch.rebind") as rebind:
                buffers = list(bound.arrays)
                for slot, c in zip(bound.bra_slots, bits):
                    buffers[slot] = BASIS_STATES[c]
                rebind.add(
                    leaves=len(bound.bra_slots),
                    bytes=sum(buffers[s].nbytes for s in bound.bra_slots),
                )
            if bound.sliced is None:
                sp.add(slices=1)
                out = backend.execute(bound.program, buffers)
                whole = True
            else:
                obs.counter_add("ampbatch.sliced_calls")
                kw: dict = {}
                num = run = bound.sliced.slicing.num_slices
                if slice_range is not None:
                    kw["slice_range"] = tuple(slice_range)
                    run = max(0, min(slice_range[1], num) - max(slice_range[0], 0))
                if getattr(backend, "supports_slice_hooks", False):
                    if ckpt is not None:
                        kw["ckpt"] = ckpt
                    if on_slice is not None:
                        kw["on_slice"] = on_slice
                sp.add(slices=run)
                out = backend.execute_sliced(bound.sliced, buffers, host=host, **kw)
                whole = run == num
            if whole:
                obs.counter_add("ampbatch.amplitudes", 1 << k)
            return self.to_host(out) if host else out


def bind_amplitude_batch(
    circuit: Circuit,
    open_qubits: Sequence[int],
    pathfinder=None,
    plan_cache=None,
    target_size: float | None = None,
) -> AmplitudeBatchProgram:
    """Plan/compile the amplitude template of ``circuit`` with
    ``open_qubits`` left open (``circuit`` consumed — finalizer
    semantics; ``copy()`` first to keep it). Same plan cache,
    ``plan_structure`` and budgeted slicing as every other query
    (:func:`~tnc_tpu.serve.rebind.bind_template`)."""
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.serve.rebind import bind_template

    n = circuit.num_qubits()
    open_qubits = [int(q) for q in open_qubits]
    if not open_qubits:
        raise ValueError("an amplitude batch needs at least one open qubit")
    for q in open_qubits:
        if not 0 <= q < n:
            raise ValueError(f"open qubit {q} is not one of the circuit's {n}")
    if len(set(open_qubits)) != len(open_qubits):
        raise ValueError(f"open qubits repeat: {open_qubits}")
    taken = set(open_qubits)
    mask = "".join("*" if q in taken else "0" for q in range(n))
    with obs.phase("ampbatch.bind") as sp:
        template = circuit.into_amplitude_template(mask)
        sp.add(
            open=len(open_qubits),
            leaves=len(flat_leaf_tensors(template.network)),
        )
        bound = bind_template(template, pathfinder, plan_cache, target_size)
    obs.gauge_set("ampbatch.open_qubits", len(open_qubits))
    return AmplitudeBatchProgram(bound, open_qubits)


def frugal_rejection_sample(
    probabilities, n_qubits: int, rng: np.random.Generator, ceiling: float = 10.0
) -> int | None:
    """Frugal rejection sampling over one batch of candidates (Markov
    et al., arXiv:1807.10749): candidate ``x`` is accepted with
    probability ``min(1, p(x) 2^n / ceiling)``; the candidates are tried
    in an order drawn from ``rng`` and the first accepted one wins.
    Returns its index into ``probabilities`` (flattened), or ``None``
    when the batch yields no sample.

    With ``ceiling`` 10 a Porter-Thomas candidate is accepted with
    probability 1/10 and clipped (``p 2^n > 10``) with probability
    ``e^-10``; a batch of 64 yields a sample almost surely."""
    p = np.asarray(probabilities, dtype=np.float64).reshape(-1)
    accept = np.minimum(1.0, p * (2.0 ** n_qubits / float(ceiling)))
    order = rng.permutation(p.size)
    hits = np.flatnonzero(rng.random(p.size) < accept[order])
    return int(order[hits[0]]) if hits.size else None


def linear_xeb(probabilities, n_qubits: int) -> float:
    """The linear cross-entropy benchmark of sampled bitstrings from
    their ideal probabilities: ``2^n mean(p) - 1``; 0 for uniform
    samples, ``2^n sum(p^2) - 1`` in expectation for samples of ``p``
    itself (about 1 for a Porter-Thomas circuit)."""
    return float(2.0 ** n_qubits * np.mean(np.asarray(probabilities, dtype=np.float64)) - 1.0)


def sample_from_batches(
    program: AmplitudeBatchProgram,
    n_samples: int,
    backend=None,
    seed: int = 0,
) -> tuple[list[str], np.ndarray]:
    """``n_samples`` bitstrings by the loop a user of a supremacy-class
    simulator runs: a closed prefix drawn uniformly, ONE batch
    contracted for it, at most one sample accepted from it by
    :func:`frugal_rejection_sample`, until enough are found. Returns
    ``(bitstrings, their ideal probabilities)``; deterministic in
    ``seed``.

    The law of a sample: the closed bits are uniform among the batches
    that accept (nearly all of them do), the open bits follow ``p``
    within the batch (clipped at the ceiling, ``10 / 2^n``; a candidate tried
    after a likely one is a little less likely to be reached). It is
    ``p`` up to the batches' share of the mass, which a deep circuit
    spreads evenly (relative spread ``2^(-k/2)``): the approximation
    the sources take."""
    rng = np.random.default_rng(seed)
    n, closed = program.num_qubits, len(program.closed_qubits)
    samples: list[str] = []
    probs: list[float] = []
    with obs.phase("ampbatch.sample") as sp:
        batches = 0
        while len(samples) < n_samples:
            bits = "".join("01"[b] for b in rng.integers(0, 2, size=closed))
            p = np.abs(program.amplitudes(bits, backend)).reshape(-1) ** 2
            batches += 1
            hit = frugal_rejection_sample(p, n, rng)
            if hit is not None:
                samples.append(program.bitstrings(bits)[hit])
                probs.append(float(p[hit]))
        sp.add(candidates=batches * (1 << len(program.open_qubits)), accepted=len(samples))
    return samples, np.asarray(probs)
