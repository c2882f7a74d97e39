"""Pauli-string expectation values over sandwich networks.

⟨ψ|P|ψ⟩ for a Pauli string ``P = P₁⊗…⊗Pₙ`` is one contraction of the
circuit ++ adjoint sandwich with the Pauli operators inserted between
the layers (:meth:`~tnc_tpu.builders.circuit_builder.Circuit.
into_expectation_value_network`). Every Pauli string shares the SAME
network structure — only the 2×2 observable leaf values differ — so
this module treats the observable layer exactly like the serving
layer treats bras: the structure plans and compiles once
(:func:`~tnc_tpu.serve.rebind.bind_template` on an
observable-placeholder :class:`~tnc_tpu.builders.circuit_builder.
SandwichTemplate`, plan cache honored) and the terms of a Pauli sum
stack along a batch leg into ONE dispatch
(:mod:`tnc_tpu.ops.batched`).

**Local observables on wide circuits.** Where the causal cone of the
observable's support (:mod:`tnc_tpu.queries.lightcone`) is smaller than
the circuit, the sandwich is the cone's: gates outside it are dropped,
identity sites inside it are traced, and rebindable observable leaves
stand on the support only. The rule is read from the circuit, there is
no option. The bound program also names the leaves that carry a gate's
angles (``param_leaves``), so :meth:`ExpectationProgram.values` answers
new angles (``params=``) with no plan, build or compile, and, for a
budget-sliced structure, a RANGE of slices (``slice_range=``) as a
partial sum: a value at published width is hours of slices, resumable
and shardable like an amplitude's.

Gradients ride the existing autodiff-capable jax executors: the
sandwich is an ordinary contraction program, so
``jax.value_and_grad`` through :func:`~tnc_tpu.ops.backends._run_steps`
(or the batched step runner for Pauli sums) differentiates the
expectation w.r.t. any leaf tensor — both circuit layers carry a
parameterized gate (the ket-layer leaf and its adjoint mirror), and
the cotangent convention ``df = Re(sum(g * dT))`` composes them into
d/dθ via the chain rule (see ``tests/test_queries.py``).
"""

from __future__ import annotations

from typing import Mapping, Sequence

import numpy as np

from tnc_tpu import obs
from tnc_tpu.builders.circuit_builder import (
    PAULI_MATRICES,
    Circuit,
    SandwichTemplate,
)
from tnc_tpu.queries.lightcone import lightcone, support_letters
from tnc_tpu.queries.statevector import normalize_pauli
from tnc_tpu.tensornetwork.tensordata import DataKind

__all__ = [
    "ExpectationProgram",
    "bind_expectation",
    "normalize_terms",
    "pauli_expectation",
    "pauli_sum_expectation",
    "pauli_expectation_value_and_grad",
]


def stacked_observables(
    paulis: Sequence[str], sites: Sequence[int] | None = None
) -> np.ndarray:
    """Observable leaf values for a batch of Pauli strings:
    ``(B, n, 2, 2)`` in qubit order (``sites``: those qubits only, in
    the order given), in the sandwich leaf layout —
    values come from the ONE layout rule
    (:func:`~tnc_tpu.builders.circuit_builder.observable_leaf_data`,
    which stores the operator transpose), so the batched rebind path
    can never skew from the template networks."""
    from tnc_tpu.builders.circuit_builder import observable_leaf_data

    return np.stack(
        [
            np.stack(
                [
                    observable_leaf_data(PAULI_MATRICES[c]).into_data()
                    for c in (
                        pauli if sites is None else (pauli[q] for q in sites)
                    )
                ]
            )
            for pauli in paulis
        ]
    )


def normalize_terms(
    terms, num_qubits: int
) -> tuple[tuple[complex, str], ...]:
    """Canonicalize a Pauli-sum spec: an iterable of ``(coeff, pauli)``
    pairs (or a bare Pauli string = one unit-coefficient term)."""
    if isinstance(terms, str):
        terms = [(1.0, terms)]
    out = []
    for coeff, pauli in terms:
        out.append((complex(coeff), normalize_pauli(pauli, num_qubits)))
    if not out:
        raise ValueError("a Pauli sum needs at least one term")
    return tuple(out)


class ExpectationProgram:
    """A compiled sandwich program with rebindable observable leaves —
    the ⟨ψ|P|ψ⟩ counterpart of :class:`~tnc_tpu.serve.rebind.
    BoundProgram` (which it wraps: same planning, plan-cache and
    slicing machinery; only the rebound leaf values differ).

    ``num_qubits`` is the width of the circuit it was bound from; where
    the sandwich is a causal cone's, ``kept_qubits`` are the circuit's
    qubits the cone keeps (ascending), ``sites`` the circuit's qubit of
    each observable slot, and ``letters`` the support it was bound for
    (``None``: every qubit takes any letter). ``param_leaves`` lists
    the leaves that carry a named gate's angles, as ``(slot, name,
    angles, adjoint)``: what ``values(..., params=)`` rebinds."""

    def __init__(
        self,
        bound,
        num_qubits: int | None = None,
        kept_qubits: Sequence[int] | None = None,
        letters: str | None = None,
    ) -> None:
        from tnc_tpu.ops.program import flat_leaf_tensors

        template: SandwichTemplate = bound.template
        if "?" in template.spec:
            raise ValueError(
                "expectation programs rebind observables, not bras "
                "(template spec must be all 'p')"
            )
        self.bound = bound
        self.num_qubits = (
            template.num_qubits if num_qubits is None else int(num_qubits)
        )
        self.kept_qubits = (
            tuple(range(template.num_qubits))
            if kept_qubits is None
            else tuple(kept_qubits)
        )
        self.sites = tuple(self.kept_qubits[q] for q in template.determined)
        self.letters = letters
        self.param_leaves = tuple(
            (slot,) + tuple(leaf.data.payload)
            for slot, leaf in enumerate(flat_leaf_tensors(template.network))
            if leaf.data.kind is DataKind.GATE and leaf.data.payload[1]
        )
        self._rebound: tuple = (None, None)  # (params key, leaf arrays)

    def _normalize(self, pauli) -> str:
        """A request as a full-width string, checked against the
        support this program was bound for."""
        pauli = normalize_pauli(pauli, self.num_qubits)
        if self.letters is not None:
            for q, (c, bound_c) in enumerate(zip(pauli, self.letters)):
                if c != "i" and (bound_c == "i" or (bound_c == "z" and c != "z")):
                    raise ValueError(
                        f"Pauli letter {c!r} at position {q}: this program "
                        f"was bound for {bound_c!r} there (its lightcone "
                        "holds no other operator on that qubit)"
                    )
        return pauli

    def _leaf_arrays(self, params) -> tuple[list, int, int]:
        """The bound leaf data with the parameter leaves named by
        ``params`` (gate name -> new angles, for every leaf of that
        gate in both layers) recomputed: ``(arrays, leaves rebound,
        bytes)``. The last ``params`` stay made: a sweep point's calls
        over its slice ranges recompute nothing."""
        if not params:
            return list(self.bound.arrays), 0, 0
        wanted = {
            name: tuple(float(a) for a in angles)
            for name, angles in params.items()
        }
        key = tuple(sorted(wanted.items()))
        if self._rebound[0] != key:
            from tnc_tpu.gates import load_gate, load_gate_adjoint

            missing = set(wanted) - {name for _, name, _, _ in self.param_leaves}
            if missing:
                raise ValueError(
                    f"params {sorted(missing)} name no parameter leaf of "
                    "this program"
                )
            arrays = list(self.bound.arrays)
            made: dict = {}
            leaves = nbytes = 0
            for slot, name, _, adjoint in self.param_leaves:
                if name not in wanted:
                    continue
                if (name, adjoint) not in made:
                    load = load_gate_adjoint if adjoint else load_gate
                    made[name, adjoint] = load(name, wanted[name])
                arrays[slot] = made[name, adjoint]
                leaves += 1
                nbytes += arrays[slot].nbytes
            self._rebound = (key, (arrays, leaves, nbytes))
        arrays, leaves, nbytes = self._rebound[1]
        return list(arrays), leaves, nbytes

    def values(
        self,
        paulis: Sequence[str],
        backend=None,
        params: Mapping | None = None,
        slice_range: tuple[int, int] | None = None,
        ckpt: str | None = None,
        on_slice=None,
        host: bool = True,
    ):
        """⟨ψ|P|ψ⟩ for every Pauli string, one batched dispatch
        (complex ``(B,)``; imaginary parts are roundoff for the
        Hermitian Pauli alphabet).

        ``params``: new angles for the gates' parameter leaves, by gate
        name (``{"rx": (theta,)}``: every ``rx`` leaf and its adjoint
        mirror) — no plan, build or compile, the structure is
        unchanged.

        Budget-sliced structures only: ``slice_range=(lo, hi)`` gives
        each term's **partial sum** over that contiguous range of
        slices (the sum over a partition of the slices is the value);
        ``ckpt`` / ``on_slice`` pass to backends with
        ``supports_slice_hooks`` (dropped elsewhere, as
        :meth:`~tnc_tpu.serve.rebind.BoundProgram.amplitudes_det`
        does); ``host=False`` returns the list of per-term results as
        the backend's ``execute_sliced(..., host=False)`` leaves them
        (device-resident, stored shape; a (real, imag) pair in split
        mode) with no transfer."""
        from tnc_tpu.ops.backends import JaxBackend, NumpyBackend
        from tnc_tpu.ops.batched import stacked_rows

        paulis = [self._normalize(p) for p in paulis]
        bound = self.bound
        if bound.sliced is None and (slice_range is not None or not host):
            raise ValueError(
                "slice_range and host=False only apply to sliced "
                "structures (this bound program has no slicing)"
            )
        if not paulis:
            return np.zeros((0,), dtype=np.complex128) if host else []
        if backend is None:
            backend = NumpyBackend()
        slots = bound.bra_slots  # observable slots (shared slot contract)
        b = len(paulis)
        with obs.phase("expval.values") as sp:
            with obs.phase("expval.rebind") as rebind:
                buffers, leaves, nbytes = self._leaf_arrays(params)
                stacked = stacked_observables(paulis, self.sites)  # (B, s, 2, 2)
                for i, slot in enumerate(slots):
                    buffers[slot] = np.ascontiguousarray(stacked[:, i])
                rebind.add(
                    leaves=leaves + len(slots), bytes=nbytes + stacked.nbytes
                )
            sp.add(terms=b)
            if bound.sliced is not None:
                # budget-sliced structures run the slice loop per term
                obs.counter_add("queries.expectation.dispatch", mode="sliced")
                obs.counter_add("expval.sliced_values", b)
                kw: dict = {}
                num = bound.sliced.slicing.num_slices
                if slice_range is not None:
                    kw["slice_range"] = tuple(slice_range)
                    num = max(0, min(slice_range[1], num) - max(slice_range[0], 0))
                if getattr(backend, "supports_slice_hooks", False):
                    if ckpt is not None:
                        kw["ckpt"] = ckpt
                    if on_slice is not None:
                        kw["on_slice"] = on_slice
                sp.add(slices=b * num)
                if not host:
                    taken = set(slots)
                    return [
                        backend.execute_sliced(
                            bound.sliced,
                            [x[i] if s in taken else x for s, x in enumerate(buffers)],
                            host=False, **kw,
                        )
                        for i in range(b)
                    ]
                rows = stacked_rows(
                    lambda per: backend.execute_sliced(bound.sliced, per, **kw),
                    buffers, slots, b, bound.program.result_shape,
                )
            elif isinstance(backend, (NumpyBackend, JaxBackend)):
                obs.counter_add("queries.expectation.dispatch", mode="batched")
                rows = backend.execute_batched(bound.program, buffers, slots)
            else:
                obs.counter_add("queries.expectation.dispatch", mode="loop")
                rows = stacked_rows(
                    lambda per: backend.execute(bound.program, per),
                    buffers, slots, b, bound.program.result_shape,
                )
        return np.asarray(rows).reshape(b).astype(np.complex128)

    def pauli_sum(
        self, terms, backend=None, params: Mapping | None = None
    ) -> tuple[complex, np.ndarray]:
        """``(sum_t coeff_t ⟨ψ|P_t|ψ⟩, per-term values)`` — the terms
        share this one structure and batch like bras."""
        terms = normalize_terms(terms, self.num_qubits)
        vals = self.values([p for _, p in terms], backend, params)
        total = complex(sum(c * v for (c, _), v in zip(terms, vals)))
        return total, vals


def bind_expectation(
    circuit: Circuit,
    pathfinder=None,
    plan_cache=None,
    target_size: float | None = None,
    support=None,
) -> ExpectationProgram:
    """Plan/compile the observable-placeholder sandwich of ``circuit``
    (consumed — finalizer semantics; ``copy()`` first to keep it).

    ``support``: where the observables will act — a Pauli string (its
    ``i`` sites take no operator later, its ``z`` sites ``i`` or ``z``,
    the others any letter), a mapping ``{qubit: letter}`` or a sequence
    of qubits (any letter there). The sandwich is then the causal
    cone's (:func:`~tnc_tpu.queries.lightcone.lightcone`) wherever that
    is smaller than the circuit: identity sites of the cone are traced
    and placeholders stand on the support only. ``None`` (and a cone
    that is the whole circuit): a placeholder on every qubit."""
    from tnc_tpu.serve.rebind import bind_template

    n = circuit.num_qubits()
    kept = letters = None
    if support is not None:
        letters = support_letters(support, n)
        n_gates = len(circuit.tensor_network.tensors) - n
        with obs.phase("expval.lightcone") as sp:
            reduced, kept = lightcone(circuit, letters)
            kept_gates = len(reduced.tensor_network.tensors) - len(kept)
            sp.add(
                qubits=n, kept_qubits=len(kept),
                gates=n_gates, kept_gates=kept_gates,
            )
        obs.gauge_set("expval.cone_qubits", len(kept))
        if not kept or (len(kept) == n and kept_gates == n_gates):
            kept = letters = None  # the cone is the circuit (or nothing)
    if kept is None:
        template = circuit.into_sandwich_template("p" * n)
    else:
        circuit._finalize()  # consumed, as by any finalizer
        template = reduced.into_sandwich_template(
            "".join("*" if letters[q] == "i" else "p" for q in kept)
        )
    with obs.phase("expval.bind"):
        bound = bind_template(template, pathfinder, plan_cache, target_size)
    return ExpectationProgram(bound, n, kept, letters)


def _joint_support(terms) -> str:
    """The letters a program has to be bound for to answer every term:
    per qubit ``x`` (any operator) where some term has ``x`` or ``y``,
    else ``z`` where some term has ``z``, else ``i``."""
    order = "izx"
    return "".join(
        order[max(order.index("x" if c == "y" else c) for c in column)]
        for column in zip(*(pauli for _, pauli in terms))
    )


def pauli_expectation(
    circuit: Circuit,
    pauli: str,
    pathfinder=None,
    backend=None,
    plan_cache=None,
    target_size: float | None = None,
) -> complex:
    """⟨ψ|P|ψ⟩ for one Pauli string (``circuit`` consumed), by the
    causal cone of its non-identity sites where that is smaller than
    the circuit.

    >>> from tnc_tpu.tensornetwork.tensordata import TensorData
    >>> c = Circuit(); reg = c.allocate_register(2)
    >>> c.append_gate(TensorData.gate("x"), [reg.qubit(0)])
    >>> pauli_expectation(c, "zi")
    (-1+0j)
    """
    pauli = normalize_pauli(pauli, circuit.num_qubits())
    prog = bind_expectation(
        circuit, pathfinder, plan_cache, target_size, support=pauli
    )
    return complex(prog.values([pauli], backend)[0])


def pauli_sum_expectation(
    circuit: Circuit,
    terms,
    pathfinder=None,
    backend=None,
    plan_cache=None,
    target_size: float | None = None,
) -> complex:
    """``sum_t coeff_t ⟨ψ|P_t|ψ⟩`` with every term sharing one planned
    sandwich structure (the cone of the terms' joint support) and one
    batched dispatch (``circuit`` consumed)."""
    terms = normalize_terms(terms, circuit.num_qubits())
    prog = bind_expectation(
        circuit, pathfinder, plan_cache, target_size,
        support=_joint_support(terms),
    )
    total, _vals = prog.pauli_sum(terms, backend)
    return total


def pauli_expectation_value_and_grad(
    circuit: Circuit,
    terms,
    wrt: Sequence[int] | None = None,
    dtype: str = "complex64",
):
    """Value and gradient of ``f = Re(sum_t coeff_t ⟨ψ|P_t|ψ⟩)`` w.r.t.
    selected sandwich leaf tensors, through the existing
    autodiff-capable jax executors (``circuit`` consumed).

    The terms batch along the observable leaves exactly like the
    forward path (one structure, one traced program). ``wrt`` indexes
    the sandwich's flat leaf order — the first ``L`` slots are the
    circuit layer (kets then gates, build order), the next ``L`` their
    adjoint mirrors, and the trailing ``n`` the observable slots
    (which carry the batch leg and cannot be differentiated here); the
    default differentiates every circuit-layer AND adjoint-layer gate
    leaf. A parameterized gate θ appears in BOTH layers: with ``g_ket``
    and ``g_adj`` the two cotangents, ``df/dθ = Re(sum(g_ket * dG/dθ))
    + Re(sum(g_adj * d(G†)/dθ))`` (cotangent convention of
    :mod:`tnc_tpu.ops.autodiff`).

    Returns ``(value, per_term_values, grads)`` where ``value`` is the
    real scalar and ``grads[i]`` is the cotangent for ``wrt[i]``.
    """
    import jax
    import jax.numpy as jnp

    from tnc_tpu.ops.autodiff import _validate_wrt
    from tnc_tpu.ops.backends import _run_steps
    from tnc_tpu.ops.batched import run_steps_batched, thread_batch
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.serve.rebind import plan_structure

    n = circuit.num_qubits()
    n_circuit = len(circuit.tensor_network.tensors)
    terms = normalize_terms(terms, n)
    template = circuit.into_sandwich_template("p" * n)
    tn = template.network
    leaves = flat_leaf_tensors(tn)
    obs_slots = list(range(len(leaves) - n, len(leaves)))
    obs_set = set(obs_slots)

    path, _slicing, program, _sliced, _result = plan_structure(tn)
    arrays = [
        jnp.asarray(leaf.data.into_data(), dtype=dtype) for leaf in leaves
    ]

    if wrt is None:
        # every gate leaf, both layers (kets and observables excluded)
        wrt = [
            s
            for s in range(2 * n_circuit)
            if len(leaves[s].legs) > 1
        ]
    wrt = _validate_wrt(wrt, len(arrays))
    for s in wrt:
        if s in obs_set:
            raise ValueError(
                "observable slots carry the Pauli-term batch leg; "
                "not differentiable here"
            )

    coeffs = jnp.asarray([c for c, _ in terms], dtype=dtype)
    stacked = jnp.asarray(
        stacked_observables([p for _, p in terms]), dtype=dtype
    )  # (B, n, 2, 2)
    flags, threadable = thread_batch(program, obs_slots)

    def forward(diff_arrays):
        buffers = list(arrays)
        for slot, arr in zip(wrt, diff_arrays):
            buffers[slot] = arr
        for i, slot in enumerate(obs_slots):
            buffers[slot] = stacked[:, i]
        if threadable:
            vals = run_steps_batched(
                jnp, program, list(buffers), flags
            ).reshape(-1)
        else:

            def single(obs_values):
                per = list(buffers)
                for i, slot in enumerate(obs_slots):
                    per[slot] = obs_values[i]
                return _run_steps(jnp, program, per).reshape(-1)[0]

            vals = jax.vmap(single)(stacked)
        return jnp.sum(jnp.real(coeffs * vals)), vals

    diff_in = tuple(arrays[slot] for slot in wrt)
    (value, vals), grads = jax.value_and_grad(forward, has_aux=True)(
        diff_in
    )
    return (
        float(value),
        np.asarray(vals).reshape(len(terms)),
        [np.asarray(g) for g in grads],
    )
