"""The ``kicked_ising`` family, its reference and the traffic kind
``expval_slice_calls``, on the CPU: the 127-qubit map and the operator's
lightcone, the reference against a dense statevector of the WHOLE
circuit on a heavy-hex patch, a rehearsal of the cell through
``perf.run.drive``, and the faults and the control that have to come out
as not correct."""

import collections
import copy
import math

import numpy as np
import pytest

from perf import circuits, common, reference
from perf import reference_kicked_ising as ref_ki
from perf import run as perf_run
from perf.families import kicked_ising
from perf.tests.test_perf import PEAKS, PLANNER

# a heavy-hex patch: one hexagon of the map (rows 0 and 1, columns 0-4, the
# bridges at columns 0 and 4) and a tail on either side of it
PATCH = [(0, 1), (1, 2), (2, 3), (3, 4), (0, 5), (5, 7), (4, 6), (6, 11),
         (7, 8), (8, 9), (9, 10), (10, 11), (11, 12), (12, 13), (9, 14), (14, 15)]
CIRCUIT = {"family": "kicked_ising", "qubits": 16, "steps": 3, "final_rx": True,
           "theta_zz": "-pi/2", "bind_theta_h": "pi/4", "couplings": PATCH,
           "observable": {"x": [1, 8], "y": [5], "z": [2, 10]}}
CONFIG = {"name": "patch", "circuit": CIRCUIT, "planner": PLANNER, "target_log2": 6}
CELL = {
    "name": "patch.expectation", "config": "patch",
    "traffic": {"kind": "expval_slice_calls", "slices_per_call": 16, "warmup_slices": 8,
                "check_calls": 1000},
    "limits": {"amp_gap": 0.3},
}
BENCH = {
    "workloads": [{"name": CELL["name"], "config": "patch", "chips": 1}],
    "end_to_end": [{"name": "amplitude_s", "unit": "s", "workloads": [CELL["name"]]},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}


def rehearse(seed: int = 11, seconds: float = 0.3, config=CONFIG):
    import jax

    run = perf_run.Run(
        workload=copy.deepcopy(CELL), config=copy.deepcopy(config),
        cell={"name": CELL["name"], "config": "patch", "chips": 1}, seed=seed,
        seconds=seconds, trace=False, chips=1,
        device=common.device_record(jax, 1), peaks=PEAKS,
        compiles=common.CompileCounter().install(),
    )
    return perf_run.drive(run, BENCH), run


def _smallest_first_pairs(leaf_legs, cap=1 << 22):
    """A valid order that keeps intermediates small: always the connected
    pair with the smallest result (a sandwich contracted in list order
    passes through the whole state)."""
    legs = {i: set(l) for i, l in enumerate(leaf_legs)}
    pairs = []
    while len(legs) > 1:
        size, a, b = min(
            (len(legs[a] ^ legs[b]), a, b)
            for a in legs for b in legs if a < b and legs[a] & legs[b]
        )
        assert 2 ** size <= cap, "the test's order needs too large an intermediate"
        pairs.append((a, b))
        legs[a] = legs[a] ^ legs.pop(b)
    return pairs


# -- the family ------------------------------------------------------------


def test_map_is_ibm_heavy_hex_127():
    qubits, couplings = kicked_ising.heavy_hex_127()
    assert qubits == 127 and len(couplings) == len(set(couplings)) == 144
    degree = collections.Counter(q for pair in couplings for q in pair)
    assert len(degree) == 127 and set(degree.values()) == {1, 2, 3}
    assert {(0, 14), (14, 18), (20, 33), (33, 39), (96, 109), (109, 114),
            (112, 126), (108, 112), (12, 13), (113, 114)} <= set(couplings)


def test_the_operators_lightcone_is_68_qubits():
    spec = common.load_json("configs", "kicked_ising127.json")["circuit"]
    letters = kicked_ising.observable(spec)
    assert len(letters) == 127 and 127 - letters.count("i") == 17
    gates = circuits.circuit_gates(spec, 5)
    kept, qubits = ref_ki.cone(gates, 127, letters)
    assert len(qubits) == 68 and len(kept) == 513
    assert all(0 < g[1][0] < math.pi / 2 for g in gates if g[0] == "rx")
    # the program finds the same cone, from the gates' data
    from perf import sut
    from tnc_tpu.queries.lightcone import lightcone

    reduced, program_qubits = lightcone(sut.build_circuit(gates, 127), letters)
    assert list(program_qubits) == qubits
    assert len(reduced.tensor_network.tensors) == 68 + 513


@pytest.mark.parametrize("order_seed", [0, 1, 2])
def test_cone_does_not_depend_on_the_order_of_a_zz_layer(order_seed):
    spec = {**CIRCUIT, "couplings": list(np.random.default_rng(order_seed).permutation(PATCH))}
    letters = kicked_ising.observable(spec)
    want = ref_ki.cone(circuits.circuit_gates(CIRCUIT, 3), 16, letters)[1]
    assert ref_ki.cone(circuits.circuit_gates(spec, 3), 16, letters)[1] == want


# -- the reference ----------------------------------------------------------


@pytest.mark.parametrize("observable", [
    {"z": [9]}, {"x": [1, 8], "y": [5], "z": [2, 10]}, {"y": [0], "z": [13, 15]},
])
def test_reference_agrees_with_dense_statevector(observable):
    spec = {**CIRCUIT, "observable": observable}
    gates = circuits.circuit_gates(spec, 7)
    letters = kicked_ising.observable(spec)
    want = ref_ki.expectation(gates, 16, letters)
    assert abs(want.imag) < 1e-12 and abs(want) > 1e-6
    raw = ref_ki.raw_network(gates, 16, letters)
    leaf_legs = [legs for legs, _ in raw]
    pairs = _smallest_first_pairs(leaf_legs)
    ref = reference.Reference(leaf_legs, pairs)
    got = complex(ref.value(ref.place([d for _, d in raw])).reshape(-1)[0])
    assert abs(got - want) < 1e-12
    # slicing two legs and summing the four slices gives the same number
    sliced = tuple(sorted({l for legs in leaf_legs for l in legs})[30:32])
    ref = reference.Reference(leaf_legs, pairs, sliced, (2, 2))
    placed = ref.place([d for _, d in raw])
    total = sum(complex(ref.value(placed, s).reshape(-1)[0]) for s in range(4))
    assert abs(total - want) < 1e-12
    # fewer qubits than the circuit wherever the operator is local
    assert len(ref_ki.cone(gates, 16, letters)[1]) <= 16


def test_reference_at_the_clifford_point():
    spec = {**CIRCUIT, "observable": {"x": [1, 8], "y": [5], "z": [2, 10]}}
    gates = kicked_ising.gates_on(16, PATCH, 3, math.pi / 2, -math.pi / 2, True)
    for letters in (kicked_ising.observable(spec), "i" * 9 + "z" + "i" * 6):
        value = ref_ki.expectation(gates, 16, letters)
        assert min(abs(value - v) for v in (-1, 0, 1)) < 1e-12


# -- the cell ----------------------------------------------------------------


def test_rehearsal_expval_slice_calls():
    result, run = rehearse()
    assert result["correct"] is True, result["numbers"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"amplitude_s", "setup_s"}
    w = run.window
    assert result["metrics"]["amplitude_s"]["value"] == pytest.approx(
        run.state["num_slices"] * w["window_s"] / w["slices"]
    )
    assert result["numbers"]["amp_gap"]["value"] < 0.3
    assert result["numbers"]["programs_built_in_window"] == {"value": 0, "limit": 0}
    assert run.state["num_slices"] >= 16 and w["units"] == w["slices"]
    phases = run.setup["expval_phases"]
    assert phases["lightcone.qubits"] == 16 and phases["lightcone.kept_qubits"] <= 16
    assert phases["lightcone"] > 0 and phases["bind"] > 0
    # another seed is another theta_h on the same structure and plan
    other = rehearse(seed=12)[1]
    assert other.state["params"] != run.state["params"]
    assert other.setup["structure_digest"] == run.setup["structure_digest"]
    assert other.setup["plan_digest"] == run.setup["plan_digest"]


def test_metrics_of_the_cell_read_the_setup():
    _, run = rehearse(seconds=0.05)
    cone = perf_run.load_metric("expval_cone_qubits").read(run)
    assert cone == run.setup["expval_phases"]["lightcone.kept_qubits"]
    assert perf_run.load_metric("expval_lightcone_s").read(run) > 0
    assert perf_run.load_metric("expval_values_wait_pct").read(run) is None  # no trace
    run.reduced = {"window_s": 20.0, "idle_gaps": [["call", 0.9], ["tnc.expval.values", 0.5]]}
    assert perf_run.load_metric("expval_values_wait_pct").read(run) == pytest.approx(2.5)
    # a program without the phases (the parent): nothing, no error
    run.setup.pop("expval_phases")
    assert perf_run.load_metric("expval_cone_qubits").read(run) is None
    assert perf_run.load_metric("expval_lightcone_s").read(run) is None


def test_fault_slices_left_out_is_not_correct(monkeypatch):
    from tnc_tpu.ops.backends import JaxBackend

    real = JaxBackend.execute_sliced

    def short(self, sp, arrays, slice_range=None, **kw):
        if slice_range is not None and slice_range[1] - slice_range[0] >= 16:
            lo, hi = slice_range
            slice_range = (lo, lo + (hi - lo) // 2)  # half of the call's slices
        return real(self, sp, arrays, slice_range=slice_range, **kw)

    monkeypatch.setattr(JaxBackend, "execute_sliced", short)
    result, _ = rehearse(seconds=0.05)
    assert result["correct"] is False
    assert result["numbers"]["amp_gap"]["value"] > 100


def test_fault_stale_theta_h_is_not_correct(monkeypatch):
    from tnc_tpu.queries.expectation import ExpectationProgram

    real = ExpectationProgram.values

    def stale(self, paulis, backend=None, params=None, **kw):
        return real(self, paulis, backend, params=None, **kw)  # the angle it was bound at

    monkeypatch.setattr(ExpectationProgram, "values", stale)
    result, _ = rehearse(seconds=0.05)
    assert result["correct"] is False
    assert result["numbers"]["amp_gap"]["value"] > 100


def test_fault_zz_gate_missing_from_the_cone_is_not_correct(monkeypatch):
    from tnc_tpu.queries import expectation, lightcone as lc

    def one_short(circuit, support):
        kets, gates = lc.circuit_gates(circuit)
        reduced, kept = lc.lightcone(circuit, support)
        cone_kets, cone_gates = lc.circuit_gates(reduced)
        last_zz = max(i for i, (data, on) in enumerate(cone_gates) if len(on) == 2)
        out = type(reduced)()
        reg = out.allocate_register(len(kept))
        for i, (data, on) in enumerate(cone_gates):
            if i != last_zz:
                out.append_gate(data, [reg.qubit(q) for q in on])
        return out, kept

    monkeypatch.setattr(expectation, "lightcone", one_short)
    # the network has other legs than the reference's: asked another question
    with pytest.raises(ValueError, match="no group of raw tensors|left over"):
        rehearse(seconds=0.05)


def test_control_lower_precision_is_not_correct():
    """The reference in three bfloat16 passes (a TPU's 'high'), put in the
    program's place, must read above the limit that the program's own
    answers pass (on the chip the control is the program itself under
    TNC_TPU_DOT_PRECISION=high: PERF.md)."""
    _, run = rehearse(seconds=0.05)
    q, gates, letters = run.state["question"], run.state["gates"], run.state["letters"]
    for lo in (0, 16, 288, 544):  # slices that vanish, and slices of 1e-7
        low = ref_ki.slice_values(gates, 16, letters, q, range(lo, lo + 16), "bf16x3")
        gap = ref_ki.slice_sum_gap(gates, 16, letters, q, [(lo, lo + 16, sum(low.values()))])
        assert gap > CELL["limits"]["amp_gap"], (lo, gap)
