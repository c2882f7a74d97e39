"""Plain reference: a quantum circuit's amplitude by pairwise contraction.

Imports nothing of the program and takes no number from it. Its data are
the benchmark's own gate list (``perf/circuits.py``) and the gates'
textbook matrices below. From the program it takes only the *question*,
which a sliced partial sum cannot be stated without:

- which legs each of the program's leaves leaves open (``leaf_legs``) —
  the reference merges its raw gate tensors into the same groups, by its
  own contractions in complex128 on the host;
- the order of pairwise contractions (``pairs``: slot ``lhs`` takes the
  product, slot ``rhs`` is freed) — any order gives the same number, and
  a good one is what makes a 53-qubit slice computable at all;
- the sliced legs with their dimensions, and the slice numbers wanted.
  Slice ``s`` fixes the sliced legs to the mixed-radix digits of ``s``,
  last leg fastest.

Leg numbering is that of the published builder convention (Rust ``tnc``
``circuit_builder.rs``): qubit ``q`` starts on leg ``q`` with a ``|0>``
ket; a gate takes one fresh leg per qubit, in the order of its qubits,
and its tensor's legs are ``new ++ old`` (storage ``(out…, in…)``); the
bras close the legs left open, in qubit order. A program that numbers
differently would be asked a different partial sum and fail the
comparison, which is the safe side.

The contraction itself runs in numpy complex128 on the host, one
pairwise product per step, one slice or one request at a time, nothing
batched, no kernel; a product that no sliced leg and no per-request leaf
enters is computed once. (On the chip the same steps in float32 planes
took its compiler 2 to 170 s EACH and crashed it as one program — the
many two-dimensional legs meet the TPU's tiled layouts badly — and
float32 on the host is itself 4e-6 off per slice: PERF.md, PR 25.)
``compare.py`` spreads slices and requests over host processes.
"""

from __future__ import annotations

import cmath
import math
from typing import Sequence

import numpy as np

# -- gates ---------------------------------------------------------------

_KET = {"0": np.array([1.0, 0.0], dtype=np.complex128),
        "1": np.array([0.0, 1.0], dtype=np.complex128)}


def gate_matrix(name: str, params: Sequence[float] = ()) -> np.ndarray:
    """The gate as a ``(2,)*2k`` tensor, axes ``(out…, in…)``."""
    if name == "sx":  # sqrt(X)
        m = 0.5 * np.array([[1 + 1j, 1 - 1j], [1 - 1j, 1 + 1j]])
    elif name == "sy":  # sqrt(Y)
        m = 0.5 * np.array([[1 + 1j, -1 - 1j], [1 + 1j, 1 + 1j]])
    elif name == "sz":  # sqrt(Z)
        m = np.array([[1, 0], [0, 1j]])
    elif name == "fsim":  # cirq.FSimGate(theta, phi)
        theta, phi = params
        a, b = math.cos(theta), -1j * math.sin(theta)
        m = np.array(
            [[1, 0, 0, 0], [0, a, b, 0], [0, b, a, 0],
             [0, 0, 0, cmath.exp(-1j * phi)]]
        )
    else:
        raise ValueError(f"the reference knows no gate {name!r}")
    m = np.asarray(m, dtype=np.complex128)
    k = int(round(math.log2(m.shape[0])))
    return m.reshape((2,) * (2 * k))


def raw_network(gates, n_qubits: int, bitstring: str):
    """``[(legs, tensor)]``: kets, gates in order, bras in qubit order."""
    if len(bitstring) != n_qubits or set(bitstring) - {"0", "1"}:
        raise ValueError(f"bitstring {bitstring!r} is not {n_qubits} of 0/1")
    open_leg = list(range(n_qubits))
    net = [((q,), _KET["0"]) for q in range(n_qubits)]
    next_leg = n_qubits
    for name, params, qubits in gates:
        new = tuple(range(next_leg, next_leg + len(qubits)))
        next_leg += len(qubits)
        old = tuple(open_leg[q] for q in qubits)
        for q, leg in zip(qubits, new):
            open_leg[q] = leg
        net.append((new + old, gate_matrix(name, params)))
    for q in range(n_qubits):
        net.append(((open_leg[q],), _KET[bitstring[q]]))
    return net


# -- bookkeeping: one pairwise contraction over named legs ---------------


def _pair_axes(a_legs, b_legs):
    shared = [leg for leg in a_legs if leg in set(b_legs)]
    a_ax = [a_legs.index(leg) for leg in shared]
    b_ax = [b_legs.index(leg) for leg in shared]
    out = tuple(l for l in a_legs if l not in shared) + tuple(
        l for l in b_legs if l not in shared
    )
    return a_ax, b_ax, out


def _np_pair(a, b, a_ax, b_ax):
    return np.tensordot(a, b, axes=(a_ax, b_ax))


def group_leaves(raw, leaf_legs):
    """Merge the raw tensors into the program's leaves: the raw tensors
    joined by a leg that no leaf keeps open form one group, contracted
    here in complex128. Returns ``[(legs, tensor)]`` in ``leaf_legs``
    order; the legs' order within a leaf is the reference's own."""
    kept = set()
    for legs in leaf_legs:
        kept.update(legs)
    parent = list(range(len(raw)))

    def find(i):
        while parent[i] != i:
            parent[i] = parent[parent[i]]
            i = parent[i]
        return i

    owner: dict[int, int] = {}
    for i, (legs, _) in enumerate(raw):
        for leg in legs:
            if leg in kept:
                continue
            if leg in owner:
                parent[find(i)] = find(owner[leg])
            else:
                owner[leg] = i
    groups: dict[int, list[int]] = {}
    for i in range(len(raw)):
        groups.setdefault(find(i), []).append(i)

    merged: dict[frozenset, list] = {}
    for members in groups.values():
        legs, data = raw[members[0]]
        rest = members[1:]
        while rest:
            # next member that touches what is merged so far
            j = next(
                (m for m in rest if set(raw[m][0]) & set(legs)), rest[0]
            )
            rest.remove(j)
            a_ax, b_ax, out = _pair_axes(legs, raw[j][0])
            data = _np_pair(data, raw[j][1], a_ax, b_ax)
            legs = out
        merged.setdefault(frozenset(legs), []).append((tuple(legs), data))
    out_leaves = []
    for want in leaf_legs:
        bucket = merged.get(frozenset(want))
        if not bucket:
            raise ValueError(
                f"no group of raw tensors leaves exactly legs {sorted(want)} open"
            )
        out_leaves.append(bucket.pop())
    if any(merged.values()):
        raise ValueError("raw tensors left over after grouping")
    return out_leaves


def slice_digits(s: int, dims: Sequence[int]) -> list[int]:
    """Mixed-radix digits of slice ``s``, last sliced leg fastest."""
    digits = [0] * len(dims)
    for pos in range(len(dims) - 1, -1, -1):
        digits[pos] = s % dims[pos]
        s //= dims[pos]
    return digits


def plan_shapes(leaf_legs, pairs, leg_dims, sliced_legs=(), varying_leaves=()):
    """Per step of the plan, with the sliced legs removed:
    ``{"k", "m", "n"}`` (contracted, left-free, right-free sizes),
    ``"a_leaf"``/``"b_leaf"`` (operand is a leaf), ``"varies"`` (an
    operand depends on a sliced leg or on a leaf of ``varying_leaves``,
    so the step runs once per slice or request) and ``"last"``. Reads
    leg names and sizes only: the roofline count's input."""
    gone = set(sliced_legs)
    legs, varies, is_leaf = {}, {}, {}
    for i, ls in enumerate(leaf_legs):
        legs[i] = tuple(l for l in ls if l not in gone)
        varies[i] = len(legs[i]) != len(ls) or i in set(varying_leaves)
        is_leaf[i] = True
    shapes = []
    for n_step, (lhs, rhs) in enumerate(pairs):
        _, _, out = _pair_axes(legs[lhs], legs[rhs])
        shared = set(legs[lhs]) & set(legs[rhs])
        shapes.append({
            "k": math.prod(leg_dims[l] for l in shared),
            "m": math.prod(leg_dims[l] for l in legs[lhs] if l not in shared),
            "n": math.prod(leg_dims[l] for l in legs[rhs] if l not in shared),
            "a_leaf": is_leaf[lhs], "b_leaf": is_leaf[rhs],
            "varies": varies[lhs] or varies[rhs],
            "last": n_step == len(pairs) - 1,
        })
        legs[lhs] = out
        varies[lhs] = varies[lhs] or varies[rhs]
        is_leaf[lhs] = False
        del legs[rhs]
    return shapes


# -- the contraction -----------------------------------------------------


def _fused_transpose(x, shape, perm):
    """``x`` (flat, holding ``shape``'s elements in row-major order) with
    its axes permuted by ``perm``, runs of axes that stay together merged
    first: a rank-23 transpose becomes a rank-5 one, which numpy copies
    several times faster. The result's shape is the merged one."""
    runs = []  # [first_src_axis, length] in target order
    for p in perm:
        if runs and runs[-1][0] + runs[-1][1] == p:
            runs[-1][1] += 1
        else:
            runs.append([p, 1])
    if len(runs) <= 1:
        return x
    by_src = sorted(range(len(runs)), key=lambda r: runs[r][0])
    src_shape = [
        math.prod(shape[runs[r][0]: runs[r][0] + runs[r][1]]) for r in by_src
    ]
    rank_of = {r: i for i, r in enumerate(by_src)}
    return np.transpose(x.reshape(src_shape), [rank_of[r] for r in range(len(runs))])


def _as_matrix(x, shape, contract_axes, contract_last: bool):
    """``x`` as the matrix ``(free, contracted)`` or ``(contracted, free)``."""
    free = [ax for ax in range(len(shape)) if ax not in contract_axes]
    perm = free + list(contract_axes) if contract_last else list(contract_axes) + free
    k = math.prod(shape[ax] for ax in contract_axes)
    y = _fused_transpose(x, shape, perm)
    return y.reshape(-1, k) if contract_last else y.reshape(k, -1)


def contract_order(leaf_legs, pairs, sliced_legs=(), varying_leaves=(), leg_dims=None):
    """The plan over the reference's own leaves: per step
    ``(out slot, a slot, b slot, a_axes, b_axes, varies)``; and per leaf the
    ``(axis, sliced position)`` pairs to index, highest axis first. A
    step ``varies`` when an operand depends on a sliced leg or on a leaf
    of ``varying_leaves``: the others give the same product for every
    slice and every request, and are computed once."""
    gone = {leg: pos for pos, leg in enumerate(sliced_legs)}
    legs, varies = {}, {}
    leaf_index = []
    for i, ls in enumerate(leaf_legs):
        leaf_index.append(
            sorted(
                ((ax, gone[l]) for ax, l in enumerate(ls) if l in gone),
                reverse=True,
            )
        )
        legs[i] = tuple(l for l in ls if l not in gone)
        varies[i] = bool(leaf_index[i]) or i in set(varying_leaves)
    def size(slot):
        return math.prod(leg_dims[l] for l in legs[slot]) if leg_dims else 0

    steps = []
    for lhs, rhs in pairs:
        # the larger operand on the left: its free legs stay in front, and
        # the matrix product has its long side as rows (any order of the
        # result's legs will do: the bookkeeping is the reference's own)
        a, b = (rhs, lhs) if size(rhs) > size(lhs) else (lhs, rhs)
        a_ax, b_ax, out = _pair_axes(legs[a], legs[b])
        varies[lhs] = varies[lhs] or varies[rhs]
        steps.append((lhs, a, b, tuple(a_ax), tuple(b_ax), varies[lhs]))
        del legs[rhs]
        legs[lhs] = out
    (result_slot, result_legs), = legs.items()
    return steps, leaf_index, result_slot, result_legs


def _bf16_parts(x):
    """``x`` (float32) as the two bfloat16 terms a TPU's ``high`` splits
    it into, each widened back to float32."""
    import ml_dtypes

    hi = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    lo = (x - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    return hi, lo


def _dot_bf16x3(a, b):
    """A float32 matrix product as a TPU computes it at precision
    ``high``: three bfloat16 passes (hi·hi + hi·lo + lo·hi), summed in
    float32."""
    a_hi, a_lo = _bf16_parts(a)
    b_hi, b_lo = _bf16_parts(b)
    return a_hi @ b_hi + a_hi @ b_lo + a_lo @ b_hi


class Reference:
    """The amplitude (or a slice's share of it) of one plan, in numpy on
    the host: complex128 (the reference), or at ``precision="bf16x3"``
    float32 planes with every product in three bfloat16 passes (the
    control: a TPU's ``high``, the nearest precision below the float32
    the configurations state).

    ``leaf_legs`` here are the legs of the reference's own leaves, in
    its own order (``group_leaves`` output). ``place`` takes the leaves'
    data; ``value`` one slice of them; ``rebind`` new data for the
    ``varying_leaves``."""

    def __init__(self, leaf_legs, pairs, sliced_legs=(), sliced_dims=(),
                 varying_leaves=(), leg_dims=None, precision: str = "complex128"):
        if precision not in ("complex128", "bf16x3"):
            raise ValueError(f"the reference computes in complex128 or bf16x3, not {precision!r}")
        self.sliced_dims = tuple(sliced_dims)
        (self.steps, self.leaf_index, self.result_slot, self.result_legs) = contract_order(
            leaf_legs, pairs, sliced_legs, varying_leaves, leg_dims)
        self.varying = [
            i for i in range(len(leaf_legs))
            if self.leaf_index[i] or i in set(varying_leaves)
        ]
        self.precision = precision

    def _pair(self, a, b, sa, sb, a_ax, b_ax):
        """One pairwise contraction on flat operands of shapes ``sa``, ``sb``."""
        am = [_as_matrix(x, sa, a_ax, True) for x in a]
        bm = [_as_matrix(x, sb, b_ax, False) for x in b]
        if self.precision == "complex128":
            return ((am[0] @ bm[0]).reshape(-1),)
        (ar, ai), (br, bi), dot = am, bm, _dot_bf16x3
        return ((dot(ar, br) - dot(ai, bi)).reshape(-1),
                (dot(ar, bi) + dot(ai, br)).reshape(-1))

    def _run(self, state, shapes, want_varying: bool):
        """The steps whose ``varies`` is ``want_varying``, in plan order,
        on ``state`` (slot -> flat data) and ``shapes`` (slot -> shape)."""
        for out, a, b, a_ax, b_ax, varies in self.steps:
            if varies != want_varying:
                continue
            sa, sb = shapes.pop(a), shapes.pop(b)
            state[out] = self._pair(state.pop(a), state.pop(b), sa, sb, a_ax, b_ax)
            shapes[out] = tuple(d for ax, d in enumerate(sa) if ax not in a_ax) + tuple(
                d for ax, d in enumerate(sb) if ax not in b_ax)

    def _data(self, x):
        """One leaf's data: complex128, or two float32 planes."""
        if self.precision == "complex128":
            return (np.asarray(x, dtype=np.complex128),)
        return (np.ascontiguousarray(np.real(x), dtype=np.float32),
                np.ascontiguousarray(np.imag(x), dtype=np.float32))

    def place(self, leaves) -> dict:
        """The leaves' data as ``value`` takes them, with the products
        that no slice and no varying leaf changes computed once."""
        data = [self._data(x) for x in leaves]
        fixed = [i for i in range(len(leaves)) if i not in set(self.varying)]
        state = {i: tuple(p.reshape(-1) for p in data[i]) for i in fixed}
        shapes = {i: tuple(np.shape(leaves[i])) for i in fixed}
        self._run(state, shapes, want_varying=False)
        return {"state": state, "shapes": shapes,
                "varying": {i: data[i] for i in self.varying}}

    def rebind(self, placed: dict, leaves) -> dict:
        """``placed`` with the varying leaves' data taken from ``leaves``."""
        return {**placed, "varying": {i: self._data(leaves[i]) for i in self.varying}}

    def value(self, placed: dict, slice_id: int = 0):
        """The plan's result for one slice, as a complex128 array."""
        digits = slice_digits(slice_id, self.sliced_dims)
        state, shapes = dict(placed["state"]), dict(placed["shapes"])
        for i, data in placed["varying"].items():
            for ax, pos in self.leaf_index[i]:
                data = tuple(np.take(p, digits[pos], axis=ax) for p in data)
            shapes[i] = tuple(data[0].shape)
            state[i] = tuple(p.reshape(-1) for p in data)
        self._run(state, shapes, want_varying=True)
        out = state[self.result_slot]
        if self.precision == "complex128":
            return np.asarray(out[0])
        return out[0].astype(np.float64) + 1j * out[1].astype(np.float64)


def statevector(gates, n_qubits: int) -> np.ndarray:
    """Dense ``U|0…0>`` in complex128 (tests, at sizes that fit): axis
    ``q`` of the result is qubit ``q``."""
    psi = np.zeros((2,) * n_qubits, dtype=np.complex128)
    psi[(0,) * n_qubits] = 1.0
    for name, params, qubits in gates:
        g = gate_matrix(name, params)
        k = len(qubits)
        psi = np.tensordot(g, psi, axes=(list(range(k, 2 * k)), list(qubits)))
        psi = np.moveaxis(psi, list(range(k)), list(qubits))
    return psi
