"""Local observables on wide circuits: ``rzz``, the kicked-Ising builder,
the lightcone of a Pauli string and the expectation value through it
(``tnc_tpu.queries.lightcone`` / ``.expectation``), pinned against the
dense statevector of the WHOLE circuit on heavy-hex patches."""

from __future__ import annotations

import math

import numpy as np
import pytest

import tnc_tpu.obs as obs
from tnc_tpu.builders import kicked_ising_circuit
from tnc_tpu.gates import load_gate, load_gate_adjoint
from tnc_tpu.obs.core import MetricsRegistry
from tnc_tpu.queries import statevector as sv
from tnc_tpu.queries.expectation import (
    bind_expectation,
    pauli_expectation,
    pauli_sum_expectation,
)
from tnc_tpu.queries.lightcone import circuit_gates, is_diagonal, lightcone
from tnc_tpu.tensornetwork.tensordata import TensorData

# one hexagon of a heavy-hex map (rows of 5 joined by two bridges), and the
# same with a tail on either side and a third bridge: degrees 1 to 3
HEX12 = [(0, 1), (1, 2), (2, 3), (3, 4), (0, 5), (5, 7), (4, 6), (6, 11),
         (7, 8), (8, 9), (9, 10), (10, 11)]
HEX16 = HEX12 + [(11, 12), (12, 13), (9, 14), (14, 15)]
PATCHES = {12: HEX12, 16: HEX16}


def _pauli(n: int, letters: dict) -> str:
    out = ["i"] * n
    for q, c in letters.items():
        out[q] = c
    return "".join(out)


def _ising(n, steps, theta, final_rx=False, couplings=None):
    return kicked_ising_circuit(
        n, PATCHES[n] if couplings is None else couplings, steps, theta,
        final_rx=final_rx,
    )


# -- (e) the gate ------------------------------------------------------------


@pytest.mark.parametrize("theta", [-math.pi / 2, 0.37, 2.9])
def test_rzz_is_cx_rz_cx(theta):
    cx = load_gate("cx").reshape(4, 4)
    rz = np.kron(np.eye(2), load_gate("rz", [theta]))
    want = cx @ rz @ cx
    got = load_gate("rzz", [theta]).reshape(4, 4)
    np.testing.assert_allclose(got, want, atol=1e-15)
    np.testing.assert_allclose(
        got, np.diag(np.exp(-0.5j * theta * np.array([1, -1, -1, 1]))), atol=1e-15
    )
    adj = load_gate_adjoint("rzz", [theta]).reshape(4, 4)
    np.testing.assert_allclose(adj @ got, np.eye(4), atol=1e-15)
    # symmetric in its two qubits, diagonal at every angle
    t = load_gate("rzz", [theta])
    np.testing.assert_array_equal(t, t.transpose(1, 0, 3, 2))
    assert is_diagonal(TensorData.gate("rzz", (theta,)))


def test_diagonal_is_read_at_every_angle():
    assert not is_diagonal(TensorData.gate("rx", (0.0,)))  # the identity, here
    assert is_diagonal(TensorData.gate("rz", (0.4,)))
    assert is_diagonal(TensorData.gate("cz")) and not is_diagonal(TensorData.gate("cx"))
    assert is_diagonal(TensorData.matrix(np.diag([1, 1j])))


# -- the builder ---------------------------------------------------------------


def test_kicked_ising_builder_counts_and_state():
    c = _ising(12, 3, 0.6, final_rx=True)
    kets, gates = circuit_gates(c)
    assert len(kets) == 12 and len(gates) == 4 * 12 + 3 * len(HEX12)
    assert [len(on) for _, on in gates[:12]] == [1] * 12
    assert abs(np.vdot(sv.statevector(c), sv.statevector(c)) - 1.0) < 1e-12
    with pytest.raises(ValueError, match="not a pair"):
        kicked_ising_circuit(3, [(0, 3)], 1, 0.1)


# -- (c) the cone --------------------------------------------------------------


def test_cone_walks_layers_not_the_list():
    # three steps on a line of 9: the cone of a z in the middle grows by
    # one neighbour a step on each side however the ZZ layer is listed
    line = [(q, q + 1) for q in range(8)]
    for couplings in (line, line[::-1], line[::2] + line[1::2]):
        c = kicked_ising_circuit(9, couplings, 3, 0.4)
        assert lightcone(c, {4: "z"})[1] == (2, 3, 4, 5, 6)
        assert lightcone(c, {4: "x"})[1] == (1, 2, 3, 4, 5, 6, 7)
        assert lightcone(c, [4])[1] == (1, 2, 3, 4, 5, 6, 7)


@pytest.mark.parametrize("order_seed", [0, 1, 2, 3])
def test_cone_through_a_zz_layer_listed_in_any_order(order_seed):
    letters = _pauli(16, {1: "x", 8: "z", 13: "y"})
    want_qubits = lightcone(_ising(16, 2, 0.3), letters)[1]
    shuffled = [tuple(int(q) for q in p)
                for p in np.random.default_rng(order_seed).permutation(HEX16)]
    reduced, kept = lightcone(_ising(16, 2, 0.3, couplings=shuffled), letters)
    assert kept == want_qubits and len(kept) < 16
    # the same gates, too: every kept ZZ touches a kept qubit pair
    zz = {tuple(sorted(kept[q] for q in on)) for _, on in circuit_gates(reduced)[1] if len(on) == 2}
    assert zz <= {tuple(sorted(p)) for p in HEX16}


def test_cone_reads_the_letters():
    c = _ising(12, 1, 0.5)  # rx layer, then ZZ layer
    assert lightcone(c, {2: "z"})[1] == (2,)  # a z lets the last ZZ layer go
    assert lightcone(c, {2: "x"})[1] == (1, 2, 3)
    reduced, kept = lightcone(c, {2: "y"})
    assert len(circuit_gates(reduced)[1]) == 3 + 2  # three rx, two rzz
    with pytest.raises(ValueError, match="support"):
        lightcone(c, "zz")
    with pytest.raises(ValueError, match="letter"):
        lightcone(c, "q" * 12)


def _heavy_hex_127():
    """IBM's 127-qubit heavy-hex map (``ibm_kyiv``'s numbering): rows of
    14, 15 x 5, 14 qubits, four bridges between two rows at columns 0, 4,
    8, 12 and 2, 6, 10, 14 in turn."""
    columns = [range(0, 14)] + [range(0, 15)] * 5 + [range(1, 15)]
    label, bridges, n = {}, [], 0
    for row, cols in enumerate(columns):
        for col in cols:
            label[row, col] = n
            n += 1
        if row < 6:
            for col in ((0, 4, 8, 12), (2, 6, 10, 14))[row % 2]:
                bridges.append((n, row, col))
                n += 1
    pairs = [(label[r, c], label[r, c + 1]) for r, cols in enumerate(columns)
             for c in list(cols)[:-1]]
    for q, row, col in bridges:
        pairs += [(label[row, col], q), (q, label[row + 1, col])]
    return n, pairs


FIG_4A = {**{q: "x" for q in (37, 41, 52, 56, 57, 58, 62, 79)}, 75: "y",
          **{q: "z" for q in (38, 40, 42, 63, 72, 80, 90, 91)}}


def test_kim_fig_4a_operator_has_a_68_qubit_cone():
    n, pairs = _heavy_hex_127()
    assert n == 127 and len(set(pairs)) == 144
    circuit = kicked_ising_circuit(n, pairs, 5, 0.7, final_rx=True)
    reduced, kept = lightcone(circuit, FIG_4A)
    assert len(kept) == 68 and set(FIG_4A) <= set(kept)
    assert len(circuit_gates(reduced)[1]) == 513  # of 6 * 127 + 5 * 144
    # layer by layer, not gate by gate: <Z62> after three steps
    assert len(lightcone(kicked_ising_circuit(n, pairs, 3, 0.7), {62: "z"})[1]) == 7
    # the sandwich is the cone's: placeholders on the 17 sites, 51 traces
    spec = "".join("p" if q in FIG_4A else "*" for q in kept)
    template = reduced.into_sandwich_template(spec)
    assert len(template.network.tensors) == 2 * (68 + 513) + 68
    assert len(template.determined) == 17


# -- (a) values against the dense state of the whole circuit ---------------------

CASES = [
    (12, 2, False, {3: "z"}),
    (12, 3, True, {0: "x", 5: "y"}),
    (12, 4, False, {7: "y", 8: "z", 9: "x"}),
    (16, 2, True, {1: "x", 8: "z", 13: "y", 15: "z"}),
    (16, 3, False, {14: "z"}),
    (16, 2, False, {0: "x", 2: "y", 4: "z", 10: "x", 12: "z"}),
]


@pytest.mark.parametrize("n, steps, final_rx, letters", CASES)
def test_value_is_the_dense_statevectors(n, steps, final_rx, letters):
    theta = 0.3 + 0.1 * steps
    pauli = _pauli(n, letters)
    want = sv.pauli_expectation(sv.statevector(_ising(n, steps, theta, final_rx)), pauli)
    got = pauli_expectation(_ising(n, steps, theta, final_rx), pauli)
    assert abs(got - want) < 1e-12


@pytest.mark.parametrize("backend_kind, tol", [
    ("numpy", 1e-12), ("jax64", 1e-10), ("jax", 2e-5), ("jax_split", 2e-5),
])
def test_backends_agree_on_a_cone(backend_kind, tol):
    from tnc_tpu.ops.backends import JaxBackend, NumpyBackend

    backend = {
        "numpy": lambda: NumpyBackend(),
        "jax64": lambda: JaxBackend(dtype="complex128"),
        "jax": lambda: JaxBackend(split_complex=False),
        "jax_split": lambda: JaxBackend(split_complex=True),
    }[backend_kind]()
    pauli = _pauli(16, {1: "x", 8: "z", 13: "y"})
    want = sv.pauli_expectation(sv.statevector(_ising(16, 2, 0.45, True)), pauli)
    prog = bind_expectation(_ising(16, 2, 0.45, True), support=pauli)
    assert len(prog.kept_qubits) < 16 and prog.num_qubits == 16
    assert prog.sites == (1, 8, 13)
    got = prog.values([pauli], backend)[0]
    assert abs(got - want) < tol


def test_program_answers_what_its_support_allows():
    pauli = _pauli(12, {2: "z", 7: "x"})
    state = sv.statevector(_ising(12, 2, 0.7))
    prog = bind_expectation(_ising(12, 2, 0.7), support=pauli)
    askable = [pauli, _pauli(12, {7: "y"}), _pauli(12, {2: "z"}), "i" * 12,
               _pauli(12, {2: "z", 7: "z"})]
    got = prog.values(askable)
    for g, p in zip(got, askable):
        assert abs(g - sv.pauli_expectation(state, p)) < 1e-12
    with pytest.raises(ValueError, match="bound for 'z'"):
        prog.values([_pauli(12, {2: "x", 7: "x"})])
    with pytest.raises(ValueError, match="bound for 'i'"):
        prog.values([_pauli(12, {3: "z"})])
    total = pauli_sum_expectation(_ising(12, 2, 0.7), [(0.5, askable[0]), (2.0, askable[1])])
    assert abs(total - (0.5 * got[0] + 2.0 * got[1])) < 1e-12


def test_a_cone_that_is_the_circuit_takes_the_old_path():
    # an operator on every qubit drops no gate: the sandwich of the circuit
    pauli = "x" * 12
    c = _ising(12, 3, 0.2)
    reduced, kept = lightcone(c, pauli)
    assert kept == tuple(range(12))
    assert len(circuit_gates(reduced)[1]) == len(circuit_gates(c)[1])
    prog = bind_expectation(c, support=pauli)
    assert prog.letters is None and prog.bound.template.spec == "p" * 12
    assert abs(prog.values(["z" * 12])[0]
               - sv.pauli_expectation(sv.statevector(_ising(12, 3, 0.2)), "z" * 12)) < 1e-12
    with pytest.raises(RuntimeError, match="already converted"):
        bind_expectation(c)  # consumed, cone or not
    c2 = _ising(12, 1, 0.2)
    bind_expectation(c2, support={2: "z"})
    with pytest.raises(RuntimeError, match="already converted"):
        lightcone(c2, {2: "z"})


# -- (b) the Clifford point --------------------------------------------------------


@pytest.mark.parametrize("letters", [
    {0: "x"}, {5: "y"}, {9: "z"}, {1: "x", 8: "z", 13: "y"}, {4: "z", 6: "z"},
    {2: "y", 3: "y"}, {10: "x", 11: "x", 12: "x"}, {7: "z", 14: "y", 15: "x"},
])
def test_clifford_point_values_are_signs(letters):
    pauli = _pauli(16, letters)
    want = sv.pauli_expectation(sv.statevector(_ising(16, 3, math.pi / 2, True)), pauli)
    got = pauli_expectation(_ising(16, 3, math.pi / 2, True), pauli)
    assert abs(got - want) < 1e-12
    assert min(abs(got - v) for v in (-1.0, 0.0, 1.0)) < 1e-12


# -- (d) slices and parameters -------------------------------------------------------


@pytest.fixture(scope="module")
def sliced():
    pauli = _pauli(16, {1: "x", 8: "z", 13: "y"})
    prog = bind_expectation(_ising(16, 3, 0.5, True), target_size=64, support=pauli)
    assert prog.bound.sliced is not None
    return prog, pauli, prog.bound.sliced.slicing.num_slices


def _dense(theta, pauli):
    return sv.pauli_expectation(sv.statevector(_ising(16, 3, theta, True)), pauli)


@pytest.mark.parametrize("parts", [1, 2, 3, 7])
def test_slice_ranges_sum_to_the_value(sliced, parts):
    prog, pauli, num = sliced
    assert num >= 8
    cuts = sorted({round(i * num / parts) for i in range(parts + 1)})
    total = sum(
        prog.values([pauli], slice_range=(lo, hi))[0] for lo, hi in zip(cuts, cuts[1:])
    )
    assert abs(total - _dense(0.5, pauli)) < 1e-12
    assert abs(prog.values([pauli])[0] - total) < 1e-12


@pytest.mark.parametrize("params", [
    {"rx": (0.9,)}, {"rx": [0.9], "rzz": [-math.pi / 2]},
])
def test_params_rebind_the_angle(sliced, params):
    prog, pauli, num = sliced
    assert len(prog.param_leaves) == 2 * len(
        circuit_gates(lightcone(_ising(16, 3, 0.5, True), pauli)[0])[1]
    )
    got = prog.values([pauli], params=params)[0]
    assert abs(got - _dense(0.9, pauli)) < 1e-12
    assert abs(prog.values([pauli])[0] - _dense(0.5, pauli)) < 1e-12  # unchanged
    half = prog.values([pauli], params=params, slice_range=(0, num // 2))[0] + prog.values(
        [pauli], params=params, slice_range=(num // 2, num))[0]
    assert abs(half - got) < 1e-12


def test_params_that_name_nothing_are_refused(sliced):
    prog, pauli, _ = sliced
    with pytest.raises(ValueError, match="no parameter leaf"):
        prog.values([pauli], params={"ry": (0.1,)})
    unsliced = bind_expectation(_ising(12, 1, 0.5), support={2: "x"})
    with pytest.raises(ValueError, match="sliced structures"):
        unsliced.values([_pauli(12, {2: "x"})], slice_range=(0, 1))
    assert abs(
        unsliced.values([_pauli(12, {2: "y"})], params={"rx": (1.1,)})[0]
        - sv.pauli_expectation(sv.statevector(_ising(12, 1, 1.1)), _pauli(12, {2: "y"}))
    ) < 1e-12


@pytest.mark.parametrize("split", [False, True])
def test_second_angle_builds_no_program(sliced, split):
    import jax
    from jax import monitoring

    from tnc_tpu.ops.backends import JaxBackend

    prog, pauli, num = sliced
    backend = JaxBackend(split_complex=split)
    first = prog.values([pauli], backend, params={"rx": (0.8,)}, slice_range=(0, 8))
    assert abs(first[0] - prog.values([pauli], params={"rx": (0.8,)}, slice_range=(0, 8))[0]) < 2e-5

    compiled = []
    monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: compiled.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None
    )
    obs.configure(enabled=True, registry=MetricsRegistry())
    try:
        (on_device,) = prog.values(
            [pauli], backend, params={"rx": (1.2,)}, slice_range=(8, 16), host=False
        )
        jax.block_until_ready(on_device)
        # the next range at the same angle: the stem's products are on the
        # device already, the prelude is not run again
        (again,) = prog.values(
            [pauli], backend, params={"rx": (1.2,)}, slice_range=(16, 24), host=False
        )
        jax.block_until_ready(again)
        counters = obs.get_registry().counters()
        spans = {r.name for r in obs.get_registry().span_records()}
    finally:
        obs.configure(enabled=False)
    assert not compiled
    preludes = {dict(k[1]).get("mode"): v for k, v in counters.items()
                if k[0] == "chunked.prelude"}
    assert preludes == {"run": 1, "reused": 1}
    if split:
        again = np.asarray(again[0]) + 1j * np.asarray(again[1])
    want_again = prog.values([pauli], params={"rx": (1.2,)}, slice_range=(16, 24))[0]
    assert abs(complex(np.asarray(again).reshape(-1)[0]) - want_again) < 2e-5
    misses = sum(v for k, v in counters.items()
                 if k[0] in ("jit_cache.miss", "chunk_plan_cache.miss"))
    assert misses == 0
    assert {"expval.values", "expval.rebind", "sliced.prelude", "sliced.residual",
            "backend.place_buffers"} <= spans
    got = on_device
    if split:
        got = np.asarray(got[0]) + 1j * np.asarray(got[1])
    want = prog.values([pauli], params={"rx": (1.2,)}, slice_range=(8, 16))[0]
    assert abs(complex(np.asarray(got).reshape(-1)[0]) - want) < 2e-5


def test_phases_total_without_tracing():
    pauli = _pauli(16, {1: "x", 8: "z"})
    with obs.collect_phases() as totals:
        prog = bind_expectation(_ising(16, 2, 0.4), target_size=64, support=pauli)
        prog.values([pauli, _pauli(16, {8: "z"})], params={"rx": (0.2,)}, slice_range=(0, 4))
    assert totals["expval.lightcone.qubits"] == 16
    assert totals["expval.lightcone.kept_qubits"] == len(prog.kept_qubits) < 16
    assert totals["expval.lightcone.kept_gates"] < totals["expval.lightcone.gates"]
    assert totals["expval.bind"] > 0 and totals["expval.values"] > 0
    in_range = min(4, prog.bound.sliced.slicing.num_slices)
    assert totals["expval.values.terms"] == 2
    assert totals["expval.values.slices"] == 2 * in_range
    rx_leaves = sum(1 for _, name, *_ in prog.param_leaves if name == "rx")
    assert totals["expval.rebind.leaves"] == rx_leaves + 2
    assert totals["expval.rebind.bytes"] == 64 * rx_leaves + 2 * 2 * 64


def test_budgeted_plan_of_a_large_sandwich_is_searched_on_its_cores():
    """More than ``CORE_PLAN_MIN_LEAVES`` raw leaves under a budget: the
    plan is found and sliced on the rank>=3 cores and lifted back; the
    value is the unbudgeted plan's."""
    from tnc_tpu.serve import rebind

    line = [(q, q + 1) for q in range(29)]
    pauli = _pauli(30, {3: "x", 12: "y", 21: "z", 27: "x"})

    def circuit():
        return kicked_ising_circuit(30, line, 3, 0.35, final_rx=True)

    want = pauli_expectation(circuit(), pauli)
    prog = bind_expectation(circuit(), target_size=256, support=pauli)
    network = prog.bound.template.network
    assert len(network.tensors) > rebind.CORE_PLAN_MIN_LEAVES
    prefix, core_ids, next_id, cores = rebind._budget_cores(network)
    assert len(prefix) + len(core_ids) == len(network.tensors) == next_id - len(prefix)
    assert min(len(t.legs) for t in cores.tensors) >= 3
    sliced = prog.bound.sliced
    assert sliced is not None and sliced.slicing.num_slices > 1
    # every sliced leg is a leg between two cores
    core_legs = {leg for t in cores.tensors for leg in t.legs}
    assert set(sliced.slicing.legs) <= core_legs
    assert abs(prog.values([pauli])[0] - want) < 1e-12
    # below the threshold nothing changes: the search runs on the leaves
    small = bind_expectation(_ising(12, 1, 0.5), target_size=4, support={2: "x"})
    assert rebind._budget_cores(small.bound.template.network) is None
