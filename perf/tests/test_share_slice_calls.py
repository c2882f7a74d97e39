"""The traffic kind ``share_slice_calls`` on the CPU: a plan of 2^31 slices
(Sycamore layout, 12 qubits, 20 cycles, sliced to 2^5 elements) written
to a plan store, a rehearsal of the cell through ``perf.run.drive`` on
one chip's share of a fleet of 4096, and the faults and the control that
have to read a gap of order 1 or be refused."""

import copy
import math

import numpy as np

import pytest

from perf import common, compare, plan_store
from perf import run as perf_run
from perf.tests.test_perf import PEAKS

CONFIG = {
    "name": "sycamore12_m20", "circuit": {"family": "sycamore", "qubits": 12, "cycles": 20},
    "planner": {"finder": "Hyperoptimizer", "seed": 42, "ntrials": 2,
                "reconfigure_budget": None, "polish_rounds": 0},
    "target_log2": 5,
}
CELL = {
    "name": "sycamore12_m20.share", "config": "sycamore12_m20",
    "traffic": {"kind": "share_slice_calls", "chips": 4096, "slices_per_call": 4,
                "warmup_slices": 4, "check_calls": 1, "check_draws": 3},
    # at 12 qubits the program's float32 reads about 1e-8 and the control
    # (three bfloat16 passes) 1.2e-5 to 4.3e-5: the cell's own limit is
    # set on the chip at 53 qubits (PERF.md)
    "limits": {"amp_gap": 3e-6},
}
BENCH = {
    "workloads": [{"name": CELL["name"], "config": "sycamore12_m20", "chips": 1}],
    "end_to_end": [{"name": "amplitude_s", "unit": "s", "workloads": [CELL["name"]]},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}
SEED = 6  # chip 6, whose share does not vanish where it starts (most chips' do: PERF.md)


@pytest.fixture(scope="module")
def config(tmp_path_factory):
    """The configuration with its plan store made, as perf/plan_store.py
    makes the committed one."""
    from tnc_tpu.serve.plancache import PlanCache

    directory = tmp_path_factory.mktemp("plans")
    cache = PlanCache(directory)
    key, record = plan_store.plan_entry(CONFIG, plan_store.network(CONFIG)[2], cache)
    cache.store(key, record)
    return {**CONFIG, "plan_store": str(directory)}


def rehearse(config, seed: int = SEED, seconds: float = 0.3, cell=CELL):
    import jax

    run = perf_run.Run(
        workload=copy.deepcopy(cell), config=copy.deepcopy(config),
        cell={"name": cell["name"], "config": "sycamore12_m20", "chips": 1}, seed=seed,
        seconds=seconds, trace=False, chips=1,
        device=common.device_record(jax, 1), peaks=PEAKS,
        compiles=common.CompileCounter().install(),
    )
    return perf_run.drive(run, BENCH), run


def test_rehearsal_share_slice_calls(config):
    result, run = rehearse(config)
    assert result["correct"] is True, result["numbers"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"amplitude_s", "setup_s"}
    w = run.window
    num = run.state["num_slices"]
    assert num == 1 << 31
    assert result["metrics"]["amplitude_s"]["value"] == pytest.approx(num * w["window_s"] / w["slices"])
    assert result["numbers"]["programs_built_in_window"] == {"value": 0, "limit": 0}
    share = num // 4096
    assert run.state["share_lo"] == SEED * share
    assert all(SEED * share <= c[2] < c[3] <= (SEED + 1) * share for c in w["calls"])
    assert [c[2] for c in w["calls"][:2]] == [SEED * share, SEED * share + 4]
    store = run.setup["plan_store"]
    assert store["hit"] == 1 and store["sig_drift"] == 0 and store["seconds"] > 0
    assert store["num_slices"] == num and store["legs"] == 31 and store["bytes"] > 0
    # another seed is another chip and circuit on the same structure and plan
    other = rehearse(config, seed=4099, seconds=0.05)[1]
    assert other.state["share_lo"] == 3 * share
    assert other.setup["structure_digest"] == run.setup["structure_digest"]
    assert other.setup["plan_digest"] == run.setup["plan_digest"]


def test_metrics_of_the_cell(config):
    _, run = rehearse(config, seconds=0.05)
    assert perf_run.load_metric("plan_store_load_s").read(run) == run.setup["plan_store"]["seconds"]
    assert perf_run.load_metric("step_huge_ps_per_elem").read(run) is None  # no trace
    run.setup.pop("plan_store")  # a program without the phase: nothing, no error
    assert perf_run.load_metric("plan_store_load_s").read(run) is None


def test_step_huge_reads_the_steps_of_2_26_elements(monkeypatch):
    from perf import step_lib

    rows = {
        ("c", 1): {"size": "large", "runs": "row", "operand": 1 << 26, "elements": 3e8,
                   "seconds": 0.3, "mixed_s": 0.0},
        ("c", 2): {"size": "large", "runs": "row", "operand": 1 << 25, "elements": 1e8,
                   "seconds": 9.0, "mixed_s": 0.0},
        ("c", 3): {"size": "large", "runs": "once", "operand": 1 << 27, "elements": 1e9,
                   "seconds": 9.0, "mixed_s": 0.0},
    }
    run = perf_run.Run(workload={}, config={}, cell={}, seed=1, seconds=1.0, trace=True, chips=1)
    run.window["units"] = 10
    monkeypatch.setattr(step_lib, "join", lambda r: {"steps": rows})
    assert perf_run.load_metric("step_huge_ps_per_elem").read(run) == pytest.approx(1e12 * 0.3 / (10 * 3e8))
    rows[("c", 1)].pop("operand")  # a table from before the field: nothing
    assert perf_run.load_metric("step_huge_ps_per_elem").read(run) is None


def test_a_missing_store_fails_set_up(config, tmp_path):
    from tnc_tpu.serve.plancache import PlanStoreMiss

    with pytest.raises(PlanStoreMiss):
        rehearse({**config, "plan_store": str(tmp_path)}, seconds=0.05)


def _shifted(monkeypatch, shift):
    from tnc_tpu.ops.backends import JaxBackend

    real = JaxBackend.execute_sliced

    def moved(self, sp, arrays, slice_range=None, **kw):
        return real(self, sp, arrays, slice_range=shift(*slice_range), **kw)

    monkeypatch.setattr(JaxBackend, "execute_sliced", moved)


def test_fault_another_chips_offset_is_not_correct(config, monkeypatch):
    share = (1 << 31) // 4096
    _shifted(monkeypatch, lambda lo, hi: (lo + share, hi + share))
    result, _ = rehearse(config, seconds=0.05)
    assert result["correct"] is False
    assert result["numbers"]["amp_gap"]["value"] > 0.1


def test_fault_a_dropped_slice_is_not_correct(config, monkeypatch):
    """Every call leaves out one of its slices: the one of largest value
    (a slice that is zero, as many are, would leave the sum as it is)."""
    import jax

    from perf import sut
    from tnc_tpu.ops.backends import JaxBackend

    real = JaxBackend.execute_sliced

    def one_short(self, sp, arrays, slice_range=None, **kw):
        lo, hi = slice_range
        parts = [real(self, sp, arrays, slice_range=(s, s + 1), **kw) for s in range(lo, hi)]
        size = [abs(sut.result_to_complex(p, self.split_complex)).max() for p in parts]
        parts.pop(int(np.argmax(size)))
        return jax.tree.map(lambda *xs: sum(xs), *parts)

    monkeypatch.setattr(JaxBackend, "execute_sliced", one_short)
    result, _ = rehearse(config, seconds=0.05)
    assert result["correct"] is False
    assert result["numbers"]["amp_gap"]["value"] > 0.1


def test_a_call_whose_slices_vanish_gives_way_to_the_next_draw(config, monkeypatch):
    real = compare.slice_values
    asked = []  # the slices the check asked for, one at a time
    per_call = CELL["traffic"]["slices_per_call"]

    def first_vanishes(gates, n, bits, question, slices, precision="complex128"):
        values = real(gates, n, bits, question, slices, precision)
        asked.extend(slices)
        return {s: 0j for s in values} if len(asked) <= per_call else values

    monkeypatch.setattr(compare, "slice_values", first_vanishes)
    lines = []
    monkeypatch.setattr(common, "emit", lines.append)
    result, _ = rehearse(config, seconds=0.3)
    checks = [line for line in lines if "draw" in line]
    assert len(checks) >= 2 and [c["draw"] for c in checks] == list(range(1, len(checks) + 1))
    assert all(c["vanishes"] for c in checks[:-1])  # only a vanishing draw gives way
    assert asked[:per_call] != asked[per_call:2 * per_call] and result["correct"] is True


def test_control_lower_precision_is_not_correct(config):
    """The reference in three bfloat16 passes (a TPU's 'high') in the
    program's place reads above the limit the program's answers pass (on
    the chip the control is the program itself under
    TNC_TPU_DOT_PRECISION=high: PERF.md)."""
    from perf.traffic.share_slice_calls import call_gap

    _, run = rehearse(config, seconds=0.05)
    q, gates, bits = run.state["question"], run.state["gates"], run.state["bits"]
    typical = 2.0**-6 * math.sqrt(4 / (1 << 31))
    read = []
    for lo in range(run.state["share_lo"], run.state["share_lo"] + 64, 4):
        want = compare.slice_values(gates, 12, bits, q, range(lo, lo + 4))
        if math.sqrt(sum(abs(v) ** 2 for v in want.values())) < 0.1 * typical:
            continue  # a call this small is compared in units of the floor
        low = compare.slice_values(gates, 12, bits, q, range(lo, lo + 4), "bf16x3")
        gap, vanishes = call_gap(want, lo, lo + 4, sum(low.values()), 12, 1 << 31)
        assert not vanishes and math.isfinite(gap)
        read.append(gap)
    assert read and min(read) > CELL["limits"]["amp_gap"], read
