"""One chip's share of a plan a fleet makes once: slicing past 2^26
slices, the plan written to a plan store and read back read-only, a range
at any offset of it run by ``execute_sliced`` against the plain reference
(``perf/reference.py``, complex128), and the committed m=20 entry pinned
without planning.

At 12 qubits and 20 cycles of the Sycamore layout a target of 2^5
elements needs 2^31 slices, which the slicers refused above 2^26 before
(``contractionpath.sliced_cost.MAX_SLICES`` is the one rule now)."""

from __future__ import annotations

import json
import math
import os

import numpy as np
import pytest

from perf import circuits, common, plan_store, reference

N_QUBITS, SEED = 12, 6  # a seed whose ranges below do not vanish
CONFIG = {
    "name": "sycamore12_m20", "circuit": {"family": "sycamore", "qubits": N_QUBITS, "cycles": 20},
    "planner": {"finder": "Hyperoptimizer", "seed": 42, "ntrials": 2,
                "reconfigure_budget": None, "polish_rounds": 0},
    "target_log2": 5,
}
CHIPS = 4096
M20 = common.load_json("configs", "sycamore53_m20_share.json")
M20_STORE = os.path.join(common.CHECKOUT, M20["plan_store"])
# the committed m=20 entry (perf/plan_store.py, 8 trials): its key is the
# network's structure digest at 2^29; pairs and slicing by their digest
M20_KEY = "174023607655f43e914d9dca38e353ffebc176a6116f12bd031ed77542b96ad4"
M20_PLAN_DIGEST = "4ee302a590cd71d7"
M20_SLICES, M20_LEGS = 1 << 31, 31


def _network(seed: int = SEED, config=CONFIG):
    bits = circuits.seeded_bitstrings(1, config["circuit"]["qubits"], seed)[0]
    return plan_store.network(config, seed, bits)


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    """The 12-qubit plan, made once and stored: ``(directory, key,
    record, gates, bits, network)``."""
    from tnc_tpu.serve.plancache import PlanCache

    directory = tmp_path_factory.mktemp("plans")
    gates, bits, tn = _network()
    cache = PlanCache(directory)
    key, record = plan_store.plan_entry(CONFIG, tn, cache)
    cache.store(key, record)
    return directory, key, record, gates, bits, tn


def _reference(gates, bits, sp, leaves, slices):
    """The plain reference's value of each slice, told the question."""
    leg_dims = {}
    for leaf in leaves:
        leg_dims.update(dict(leaf.edges()))
    grouped = reference.group_leaves(
        reference.raw_network(gates, N_QUBITS, bits), [tuple(l.legs) for l in leaves]
    )
    ref = reference.Reference(
        [legs for legs, _ in grouped], [(st.lhs, st.rhs) for st in sp.program.steps],
        sp.slicing.legs, sp.slicing.dims, leg_dims=leg_dims,
    )
    placed = ref.place([data for _, data in grouped])
    return [complex(np.asarray(ref.value(placed, s)).reshape(-1)[0]) for s in slices]


def _rows(registry) -> float:
    return sum(v for k, v in registry.counters().items() if k[0] == "sliced.index_rows")


def test_slicers_reach_targets_past_2_26_slices():
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.contractionpath.sliced_cost import (
        MAX_SLICES, SlicedCostEvaluator, greedy_slice_to_target,
    )
    from tnc_tpu.contractionpath.slicing import slice_and_reconfigure

    _, _, tn = _network()
    inputs = list(tn.tensors)
    result = Greedy(OptMethod.GREEDY).find_path(tn)
    _, slicing = slice_and_reconfigure(inputs, result.ssa_path.toplevel, 2.0**5)
    assert slicing.num_slices > 1 << 26
    ev = SlicedCostEvaluator(inputs, result.replace_path().toplevel)
    greedy_slice_to_target(ev, 2.0**5)  # a search trial's rank
    assert ev.num_slices > 1 << 26 and math.isfinite(ev.cost())
    assert ev.peak() <= 2.0**5
    assert MAX_SLICES == (1 << 63) - 1  # ids and the last range's end are int64


def test_the_joint_search_engages_past_2_26_slices(store):
    directory, key, record, *_ = store
    assert record["joint_slicing"] is True  # `last_slicing` was handed on
    assert record["num_slices"] > 1 << 26
    assert record["structure"] == key and record["target_size"] == 2.0**5
    assert math.isfinite(record["sliced_cmacs"])


@pytest.mark.parametrize("where", ["last", "chip"])
def test_a_range_at_any_offset_matches_the_reference(store, registry, where):
    from tnc_tpu.ops.backends import JaxBackend
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.serve.plancache import load_stored_plan

    directory, key, _, gates, bits, tn = store
    stored = load_stored_plan(directory, tn, 2.0**5)
    assert stored.key == key and not stored.sig_drift
    sp = stored.sliced
    num = sp.slicing.num_slices
    share = num // CHIPS
    lo = num - 4 if where == "last" else (SEED % CHIPS) * share + share // 2
    leaves = flat_leaf_tensors(tn)
    arrays = [leaf.data.into_data() for leaf in leaves]
    before = _rows(registry)
    got = complex(np.asarray(
        JaxBackend().execute_sliced(sp, arrays, slice_range=(lo, lo + 4))
    ).reshape(-1)[0])
    assert _rows(registry) - before == 4  # the range's own rows, at any offset
    values = _reference(gates, bits, sp, leaves, range(lo, lo + 4))
    scale = math.sqrt(sum(abs(v) ** 2 for v in values))
    assert scale > 1e-9, "a range whose slices all vanish says little"
    assert abs(got - sum(values)) / scale < 1e-4
    gauges = {k[0]: v for k, v in registry.gauges().items()}
    assert gauges["plan.slice_peak_log2"] <= 5


def test_a_drifted_signature_still_runs_the_stored_plan(store, registry, tmp_path):
    from tnc_tpu.ops.backends import JaxBackend
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.serve.plancache import load_stored_plan

    directory, key, _, _, _, tn = store
    entry = json.loads((directory / f"{key}.json").read_text())
    entry["program_sig"] = "a program built another way"
    (tmp_path / f"{key}.json").write_text(json.dumps(entry))
    fresh = load_stored_plan(directory, tn, 2.0**5)
    drifted = load_stored_plan(tmp_path, tn, 2.0**5)
    assert drifted.sig_drift and not fresh.sig_drift
    assert drifted.path.toplevel == fresh.path.toplevel
    assert drifted.slicing == fresh.slicing
    assert (tmp_path / f"{key}.json").exists()  # drift never invalidates a shared entry
    arrays = [leaf.data.into_data() for leaf in flat_leaf_tensors(tn)]
    lo = 3 << 20
    a, b = (np.asarray(JaxBackend().execute_sliced(p.sliced, arrays, slice_range=(lo, lo + 2)))
            for p in (fresh, drifted))
    np.testing.assert_array_equal(a, b)
    spans = [r for r in registry.span_records() if r.name == "plan.store"]
    assert [r.args["sig_drift"] for r in spans] == [0, 1]
    assert all(r.args["hit"] == 1 and r.args["num_slices"] == fresh.slicing.num_slices
               for r in spans)


def test_a_miss_fails_and_the_store_stays_read_only(store, tmp_path):
    from tnc_tpu.serve.plancache import PlanCache, PlanStoreMiss, load_stored_plan

    directory, key, _, _, _, tn = store
    with pytest.raises(PlanStoreMiss, match="no entry"):
        load_stored_plan(tmp_path, tn, 2.0**5)
    with pytest.raises(PlanStoreMiss, match="no entry"):  # another budget is another key
        load_stored_plan(directory, tn, 2.0**6)
    entry = json.loads((directory / f"{key}.json").read_text())
    entry["structure"] = "another network"
    (tmp_path / f"{key}.json").write_text(json.dumps(entry))
    with pytest.raises(PlanStoreMiss, match="stored for structure"):
        load_stored_plan(tmp_path, tn, 2.0**5)
    mtime = os.stat(directory / f"{key}.json").st_mtime_ns
    shared = PlanCache(directory, read_only=True)
    assert shared.load(key)["structure"] == key
    assert os.stat(directory / f"{key}.json").st_mtime_ns == mtime  # no LRU touch
    with pytest.raises(PermissionError):
        shared.store(key, entry)
    with pytest.raises(PermissionError):
        shared.invalidate(key)
    (tmp_path / "bad.json").write_text("{not json")
    assert PlanCache(tmp_path, read_only=True).load("bad") is None
    assert (tmp_path / "bad.json").exists()  # a corrupt shared entry is not deleted


def test_slice_ids_are_exact_past_32_bits():
    from tnc_tpu.ops.sliced import slice_indices

    dims = (2,) * 40
    ids = np.arange((1 << 40) - 3, 1 << 40, dtype=np.int64)
    rows = np.stack(slice_indices(dims, ids), axis=1)
    assert rows.tolist() == [[1] * 38 + [0, 1], [1] * 39 + [0], [1] * 40]
    assert slice_indices(dims, (1 << 39) + 1) == [1] + [0] * 38 + [1]


def test_the_committed_m20_plan_is_pinned():
    """The stored m=20 plan, read without planning: structure digest,
    pairs and slicing, slices and legs, the per-slice peak at the
    configuration's target, and one v5e's 16 GB by the budget model."""
    from tnc_tpu.contractionpath.sliced_cost import SlicedCostEvaluator
    from tnc_tpu.ops.budget import fits_hbm
    from tnc_tpu.ops.hoist import hoist_sliced_program
    from tnc_tpu.serve.plancache import load_stored_plan

    target = 2.0 ** M20["target_log2"]
    assert M20["target_log2"] == 29
    _, _, tn = plan_store.network(M20, seed=3)
    stored = load_stored_plan(M20_STORE, tn, target)
    assert stored.key == M20_KEY
    # another seed is other gates and another bitstring on the same structure
    assert load_stored_plan(M20_STORE, _network(seed=11, config=M20)[2], target).key == M20_KEY
    slicing = stored.slicing
    assert (slicing.num_slices, len(slicing.legs)) == (M20_SLICES, M20_LEGS)
    assert common.digest(
        [stored.path.to_obj(), list(slicing.legs), list(slicing.dims)]
    ) == M20_PLAN_DIGEST
    ev = SlicedCostEvaluator(list(tn.tensors), stored.path.toplevel, removed=slicing.legs)
    assert ev.peak() <= target
    hp = hoist_sliced_program(stored.sliced)
    assert fits_hbm(hp.residual.program, batch=1, hbm_bytes=16 * 10**9)
