"""The configuration ``sycamore53_m14_batch64``, the comparison of a
correlated batch (``perf/compare_batch.py``) and the traffic kind
``batch_slice_calls``, on the CPU: the cell's files, the reference with
open legs against a dense statevector, a rehearsal of the cell through
``perf.run.drive`` at 16 qubits, the three faults (permuted axes,
dropped slices, another prefix) and the control that have to come out
as not correct, the plan of the real size, and the metrics the cell has
to report."""

import copy
import json
import os

import numpy as np
import pytest

from perf import circuits, common, compare_batch, reference
from perf import run as perf_run
from perf.tests.test_perf import PEAKS, PLANNER

CELL_NAME = "sycamore53_m14_batch64.batch_slices"
OPEN = [3, 5, 8, 9, 12, 15]
CONFIG = {"name": "tiny_batch64", "circuit": {"family": "sycamore", "qubits": 16, "cycles": 8},
          "open_qubits": OPEN, "planner": PLANNER, "target_log2": 8}
CELL = {
    "name": "tiny_batch64.batch_slices", "config": "tiny_batch64",
    "traffic": {"kind": "batch_slice_calls", "slices_per_call": 16, "warmup_slices": 8,
                "check_calls": 4, "open_qubits": 6},
    "limits": {"amp_gap": 4e-6},  # float32 reads 3e-7 at this size, three bf16 passes 1.5e-5 and more
}
BENCH = {
    "workloads": [{"name": CELL["name"], "config": "tiny_batch64", "chips": 1}],
    "end_to_end": [{"name": "amplitude_s", "unit": "s", "workloads": [CELL["name"]]},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}


def rehearse(seed: int = 11, seconds: float = 0.3, config=CONFIG, cell=CELL):
    import jax

    run = perf_run.Run(
        workload=copy.deepcopy(cell), config=copy.deepcopy(config),
        cell={"name": cell["name"], "config": "tiny_batch64", "chips": 1}, seed=seed,
        seconds=seconds, trace=False, chips=1,
        device=common.device_record(jax, 1), peaks=PEAKS,
        compiles=common.CompileCounter().install(),
    )
    return perf_run.drive(run, BENCH), run


# -- the files -----------------------------------------------------------------


def test_the_cells_files_load_and_agree():
    benchmark = perf_run.load_benchmark()
    cell = perf_run.find_cell(benchmark, CELL_NAME)
    assert cell["chips"] == 1 and cell["config"] == "sycamore53_m14_batch64"
    workload = common.load_json("workloads", f"{CELL_NAME}.json")
    config = common.load_json("configs", "sycamore53_m14_batch64.json")
    single = common.load_json("configs", "sycamore53_m14.json")
    for key in ("circuit", "planner", "target_log2", "backend", "precision"):
        assert config[key] == single[key], key  # letter for letter
    assert workload["why"] == cell["why"] and len(cell["why"]) <= 200
    assert workload["traffic"] == {"kind": "batch_slice_calls", "slices_per_call": 128,
                                   "warmup_slices": 8, "check_calls": 1, "open_qubits": 6}
    assert len(config["open_qubits"]) == len(set(config["open_qubits"])) == 6
    assert sorted(config["reduced"]) == ["planner_trials", "slices_run", "target_log2"]
    entry = next(c for c in benchmark["configs"] if c["name"] == config["name"])
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert os.path.exists(os.path.join(common.CHECKOUT, entry["file"]))
    # the six open qubits are one connected patch of the device's couplers
    from perf.families.sycamore import SYCAMORE_PATTERNS

    patch = {q + 1 for q in config["open_qubits"]}
    pairs = [p for ps in SYCAMORE_PATTERNS.values() for p in ps if set(p) <= patch]
    reached, frontier = set(), {min(patch)}
    while frontier:
        reached |= frontier
        frontier = {b for p in pairs for a, b in (p, p[::-1]) if a in frontier} - reached
    assert reached == patch


def test_the_cell_reports_its_seventeen_per_layer_metrics_and_two_end_to_end():
    benchmark = perf_run.load_benchmark()
    per_layer = perf_run.metrics_of_cell(benchmark, "per_layer", CELL_NAME, {"amplitude_s", "setup_s"})
    assert [e["name"] for e in per_layer] == [
        "plan_s", "plan_sliced_cmacs", "first_call_s", "device_idle_pct.amp",
        "contraction_roofline.amp", "window_mfu.amp", "call_ms_per_slice_p50",
        "ampbatch_wait_pct", "ampbatch_cmacs_per_amplitude", "ampbatch_open_qubits",
        "step_attributed_pct.amp", "step_dot_share_pct.amp", "step_prep_share_pct.amp",
        "step_tiled_ps_per_elem", "step_staged_ps_per_elem", "step_matrix_ps_per_elem",
        "step_worst_ratio",
    ]
    for entry in per_layer:
        module = perf_run.load_metric(entry["name"])
        for key in ("name", "unit", "layer", "moves"):
            assert getattr(module, key) == entry[key], (entry["name"], key)
        assert module.workloads == entry.get("workloads")
    e2e = perf_run.metrics_of_cell(benchmark, "end_to_end", CELL_NAME, set())
    assert [e["name"] for e in e2e] == ["amplitude_s", "setup_s"]
    assert benchmark["workloads"][-1]["name"] == CELL_NAME


# -- the reference with open legs ------------------------------------------------


@pytest.mark.parametrize("open_qubits", [(2, 7), (9, 0, 5), (11, 3, 4, 8, 1, 6)])
def test_reference_with_open_legs_agrees_with_dense_statevector(open_qubits):
    from perf.tests.test_perf import _greedy_pairs

    n = 12
    gates = circuits.circuit_gates({"family": "sycamore", "qubits": n, "cycles": 6}, 7)
    psi = reference.statevector(gates, n)
    closed = "0110100101"[: n - len(open_qubits)]
    raw, open_legs = compare_batch.raw_network(gates, n, closed, open_qubits)
    assert len(raw) == len(reference.raw_network(gates, n, "0" * n)) - len(open_qubits)
    leaf_legs = [legs for legs, _ in raw]
    question = {"leaf_legs": leaf_legs, "pairs": _greedy_pairs(leaf_legs),
                "sliced_legs": (), "sliced_dims": ()}
    ref, leaves, axes = compare_batch._reference_for(
        gates, n, closed, open_qubits, question, "complex128")
    got = np.transpose(
        np.asarray(ref.value(ref.place(leaves))).reshape((2,) * len(open_qubits)), axes)
    index = [None] * n
    bits = iter(closed)
    for q in range(n):
        index[q] = slice(None) if q in open_qubits else int(next(bits))
    ascending = sorted(open_qubits)
    want = np.transpose(psi[tuple(index)], [ascending.index(q) for q in open_qubits])
    assert np.abs(want).max() > 1e-4
    np.testing.assert_allclose(got, want, atol=1e-14)
    with pytest.raises(ValueError, match="closed bits"):
        compare_batch.raw_network(gates, n, closed + "0", open_qubits)


# -- the cell ------------------------------------------------------------------


def test_rehearsal_batch_slice_calls():
    result, run = rehearse()
    assert result["correct"] is True, result["numbers"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"amplitude_s", "setup_s"}
    w = run.window
    assert result["metrics"]["amplitude_s"]["value"] == pytest.approx(
        run.state["num_slices"] * w["window_s"] / w["slices"]
    )
    assert result["numbers"]["amp_gap"]["value"] < 2e-6
    assert result["numbers"]["programs_built_in_window"] == {"value": 0, "limit": 0}
    assert run.state["num_slices"] >= 64 and w["units"] == w["slices"]
    phases = run.setup["ampbatch_phases"]
    assert phases["bind.open"] == 6 and phases["bind.leaves"] == len(run.state["question"]["leaf_legs"])
    assert phases["bind"] > 0
    assert len(run.state["closed_bits"]) == 10
    # another seed is another circuit and prefix on the same structure and plan
    other = rehearse(seed=12)[1]
    assert other.state["closed_bits"] != run.state["closed_bits"]
    assert other.setup["structure_digest"] == run.setup["structure_digest"]
    assert other.setup["plan_digest"] == run.setup["plan_digest"]
    # the cell's count of open qubits has to be the configuration's
    with pytest.raises(RuntimeError, match="opens 5 qubits"):
        rehearse(cell={**CELL, "traffic": {**CELL["traffic"], "open_qubits": 5}})


def test_metrics_of_the_cell_read_the_setup_and_the_window():
    _, run = rehearse(seconds=0.05)
    assert perf_run.load_metric("ampbatch_open_qubits").read(run) == 6
    per = perf_run.load_metric("ampbatch_cmacs_per_amplitude").read(run)
    assert per == pytest.approx(run.setup["sliced_cmacs"] / 64)
    assert perf_run.load_metric("plan_sliced_cmacs").read(run) == run.setup["sliced_cmacs"]
    assert perf_run.load_metric("plan_s").read(run) > 0
    assert perf_run.load_metric("first_call_s").read(run) > 0
    assert perf_run.load_metric("call_ms_per_slice_p50").read(run) > 0
    # real operations by step shapes, 8 a complex multiply-add, the result at its real size
    mfu = perf_run.load_metric("window_mfu.amp").read(run)
    assert 0 < mfu < 100
    assert perf_run.load_metric("ampbatch_wait_pct").read(run) is None  # no trace
    assert perf_run.load_metric("contraction_roofline.amp").read(run) is None
    run.reduced = {"window_s": 20.0, "op_s_max": 19.0, "idle_pct_idlest": 2.0,
                   "idle_gaps": [["call", 0.9], ["tnc.ampbatch.amplitudes", 0.5]]}
    assert perf_run.load_metric("ampbatch_wait_pct").read(run) == pytest.approx(2.5)
    assert 0 < perf_run.load_metric("contraction_roofline.amp").read(run) < 100
    assert perf_run.load_metric("device_idle_pct.amp").read(run) == 2.0
    # a program without the phases (the parent): nothing, no error
    run.setup.pop("ampbatch_phases")
    assert perf_run.load_metric("ampbatch_open_qubits").read(run) is None
    assert perf_run.load_metric("ampbatch_cmacs_per_amplitude").read(run) is None


def test_fault_permuted_axes_is_not_correct(monkeypatch):
    from tnc_tpu.queries.amplitude_batch import AmplitudeBatchProgram

    real = AmplitudeBatchProgram.to_host

    def as_the_executor_left_them(self, value):  # the Permutor applied by nobody
        saved, self.permutation = self.permutation, tuple(range(len(self.permutation)))
        try:
            return real(self, value)
        finally:
            self.permutation = saved

    monkeypatch.setattr(AmplitudeBatchProgram, "to_host", as_the_executor_left_them)
    # seed 12: no slice of the batch vanishes (seed 11's calls of slices 0-31
    # and 64-95 compare zeros, where no fault shows; which calls a window
    # checks depends on how many the host completes)
    result, run = rehearse(seed=12, seconds=0.05)
    assert run.state["question"] and result["correct"] is False
    assert result["numbers"]["amp_gap"]["value"] > 0.1


def test_fault_slices_left_out_is_not_correct(monkeypatch):
    from tnc_tpu.ops.backends import JaxBackend

    real = JaxBackend.execute_sliced

    def short(self, sp, arrays, slice_range=None, **kw):
        if slice_range is not None and slice_range[1] - slice_range[0] >= 16:
            lo, hi = slice_range
            slice_range = (lo, lo + (hi - lo) // 2)  # half of the call's slices
        return real(self, sp, arrays, slice_range=slice_range, **kw)

    monkeypatch.setattr(JaxBackend, "execute_sliced", short)
    result, _ = rehearse(seed=12, seconds=0.05)  # seed 12: no slice vanishes
    assert result["correct"] is False
    assert result["numbers"]["amp_gap"]["value"] > 0.1


def test_fault_another_prefix_is_not_correct(monkeypatch):
    from tnc_tpu.queries.amplitude_batch import AmplitudeBatchProgram

    real = AmplitudeBatchProgram.amplitudes

    def stale(self, closed_bits, backend=None, **kw):  # the bras the template was bound with
        return real(self, "0" * len(closed_bits), backend, **kw)

    monkeypatch.setattr(AmplitudeBatchProgram, "amplitudes", stale)
    result, run = rehearse(seed=12, seconds=0.05)  # seed 12: no slice vanishes
    assert "1" in run.state["closed_bits"]
    assert result["correct"] is False
    assert result["numbers"]["amp_gap"]["value"] > 0.1


def test_control_lower_precision_is_not_correct():
    """The reference in three bfloat16 passes (a TPU's 'high'), put in the
    program's place, must read above the limit that the program's own
    answers pass (on the chip the control is the program itself under
    TNC_TPU_DOT_PRECISION=high: PERF.md)."""
    # seed 11: the batch vanishes in slices 0-31 and 64-95 (1e-19: the
    # comparison there only says both are zero); seed 12: in none
    for seed, blocks in ((11, (32, 48)), (12, (0, 16))):
        _, run = rehearse(seed=seed, seconds=0.05)
        st = run.state
        n = run.config["circuit"]["qubits"]
        args = (st["gates"], n, st["closed_bits"], st["open_qubits"], st["question"])
        for lo in blocks:
            low = compare_batch.slice_values(*args, range(lo, lo + 16), "bf16x3")
            gap = compare_batch.batch_sum_gap(*args, [(lo, lo + 16, sum(low.values()))])
            assert gap > CELL["limits"]["amp_gap"], (seed, lo, gap)
    zero = rehearse(seed=11, seconds=0.05)[1].state
    args = (zero["gates"], n, zero["closed_bits"], zero["open_qubits"], zero["question"])
    low = compare_batch.slice_values(*args, range(16), "bf16x3")
    assert compare_batch.batch_sum_gap(*args, [(0, 16, sum(low.values()))]) < 1e-12


# -- the plan of the real size -----------------------------------------------------


def test_the_plan_that_runs_is_pinned():
    """Through the ENTRY (``bind_amplitude_batch``: ``plan_structure`` on
    the template's cores), the configuration's planner and target; the
    structure and the plan do not depend on the seed. Half a minute of
    planning; nothing is contracted."""
    import jax

    from perf.traffic import batch_slice_calls

    config = common.load_json("configs", "sycamore53_m14_batch64.json")
    run = perf_run.Run(
        workload=common.load_json("workloads", f"{CELL_NAME}.json"), config=config,
        cell={"name": CELL_NAME}, seed=5, seconds=0.0, trace=False, chips=1,
        device=common.device_record(jax, 1), peaks=PEAKS,
    )
    run.state.update(gates=circuits.circuit_gates(config["circuit"], run.seed),
                     open_qubits=config["open_qubits"])
    prog, info, phases, question = batch_slice_calls._bind(run, None)
    pinned = {k: info[k] for k in ("target_log2", "num_slices", "sliced_legs", "steps",
                                   "prelude_steps", "residual_steps", "plan_digest",
                                   "structure_digest", "result_axes")}
    print(json.dumps({**pinned, "sliced_cmacs": info["sliced_cmacs"]}))
    assert pinned == PINNED_PLAN
    assert info["sliced_cmacs"] == pytest.approx(3311668608106496.0, rel=1e-9)
    assert phases["bind.leaves"] == 1196 and phases["bind.open"] == 6
    open_legs = set(prog.bound.template.permutor.target_leg_order)
    assert not open_legs & set(question["sliced_legs"])  # the slicer takes no open leg


PINNED_PLAN = {
    "target_log2": 25, "num_slices": 1048576, "sliced_legs": 20, "steps": 1195,
    "prelude_steps": 1003, "residual_steps": 192, "plan_digest": "2e549759b81e9136",
    "structure_digest": "cfc059d5e11afb1d", "result_axes": [5, 2, 3, 4, 1, 0],
}
