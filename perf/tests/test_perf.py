"""The yardstick checked against itself: the trace reduction on a recorded
trace and on events written by hand, the roofline count on a three-step
plan, the reference against a dense statevector, a CPU rehearsal of each
traffic kind through ``perf.run.drive`` (the harness without its look
for a chip), the faults and the lower-precision control that have to
come out as not correct, and the exit code without a TPU."""

import bisect
import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perf import circuits, common, compare, reference, roofline, trace_reduce
from perf import run as perf_run
from perf.trace_reduce import (
    COLLECTIVE, SPAN_PREFIX, WINDOW_SPAN, short_module_name, short_op_name,
)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PLANNER = {"finder": "Hyperoptimizer", "seed": 42, "ntrials": 2,
           "reconfigure_budget": None, "polish_rounds": 1}
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
         "on_chip_vector_bytes": 1 << 27}


# -- trace reduction -----------------------------------------------------


def test_trace_reduction_on_recorded_trace():
    # recorded on one TPU v5 lite (PR 25): two jitted functions, f and g,
    # three times each under perf:call, a 50 ms sleep under perf:reference,
    # then f once more under perf:call
    devices, spans = trace_reduce.read_planes(os.path.join(DATA, "probe.xplane.pb"))
    assert sorted(devices) == [0]
    assert len(devices[0]["modules"]) == 7 and len(devices[0]["ops"]) == 24
    assert [s[0] for s in spans] == ["perf:call", "perf:reference", "perf:call"]
    out = trace_reduce.reduce_events(devices, spans)
    assert out["devices"] == 1
    assert out["busy_s"] == pytest.approx(3.214e-3, rel=1e-3)
    assert out["op_s"] == pytest.approx(out["busy_s"])  # one op at a time
    assert 0.0 < out["busy_s"] < out["window_s"] < 0.06
    assert out["device_ops"][0][0] == "jit_f/fusion"
    assert out["device_ops"][0][1] == pytest.approx(2.2385e-3, rel=1e-3)
    name, seconds = out["idle_gaps"][0]
    assert name == "reference" and seconds == pytest.approx(0.04895, rel=0.01)
    assert out["collective_s_max"] == 0.0


def test_trace_reduction_by_hand():
    ms = 1e6  # ns
    spans = [("perf:window", 0.0, 100 * ms), ("perf:call", 0.0, 40 * ms),
             ("perf:fetch", 40 * ms, 100 * ms)]
    dev0 = {"modules": [("jit_a(1)", 10 * ms, 30 * ms), ("jit_b(2)", 50 * ms, 90 * ms)],
            "ops": [("%fusion.1 = f32[8] fusion(x)", 10 * ms, 30 * ms),
                    ("%all-reduce.2 = f32[] all-reduce(y)", 50 * ms, 60 * ms),
                    ("%fusion.3 = f32[8] fusion(z)", 55 * ms, 90 * ms),  # overlaps
                    ("%fusion.9 = f32[8] fusion(w)", 120 * ms, 130 * ms)]}  # outside
    dev1 = {"modules": [], "ops": [("%fusion.1 = f32[8] fusion(x)", 0.0, 10 * ms)]}
    out = trace_reduce.reduce_events({0: dev0, 1: dev1}, spans)
    assert out["window_s"] == pytest.approx(0.1)
    assert out["busy_s"] == pytest.approx((0.06 + 0.01) / 2)  # union, averaged
    assert out["idle_pct_idlest"] == pytest.approx(90.0)  # device 1
    assert out["op_s_max"] == pytest.approx(0.065)  # sum, overlap counted twice
    assert out["collective_s_max"] == pytest.approx(0.01)
    one = trace_reduce.reduce_events({0: dev0}, spans)
    assert dict(map(tuple, one["idle_gaps"])) == pytest.approx(
        {"call": 0.02, "fetch": 0.02}
    )  # 0-10 and 30-40 under call; 40-50 and 90-100 under fetch
    assert one["device_ops"][0] == ["jit_b/fusion.3", pytest.approx(0.035)]
    # a while op's event spans its body's: only the body is summed
    loop = {"modules": [], "ops": [("%while.9 = (s32[]) while(x)", 10 * ms, 50 * ms),
                                   ("%fusion.1 = f32[8] fusion(x)", 10 * ms, 30 * ms),
                                   ("%fusion.2 = f32[8] fusion(x)", 30 * ms, 45 * ms)]}
    looped = trace_reduce.reduce_events({0: loop}, spans)
    assert looped["op_s_max"] == pytest.approx(0.035)
    assert [nm for nm, _ in looped["device_ops"]] == ["fusion.1", "fusion.2"]
    with pytest.raises(ValueError):
        trace_reduce.reduce_events({}, spans)


# The oracle: the reduction as it stood before the sweep and the arrays, in
# plain Python, verbatim with the helpers it used.


def union(intervals):
    """Sorted, merged ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def complement(merged, lo, hi):
    gaps, at = [], lo
    for s, e in merged:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def leaves_only(events):
    """``events`` (name, start, end) without those that enclose another
    one: a ``while`` or ``conditional`` op's event spans its body's ops,
    and summing both would count the body twice."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    encloses = [False] * len(ordered)
    stack = []  # indices of events still open
    for i, (_, start, end) in enumerate(ordered):
        while stack and ordered[stack[-1]][2] <= start:
            stack.pop()
        if stack and end <= ordered[stack[-1]][2]:
            encloses[stack[-1]] = True
        stack.append(i)
    return [e for e, outer in zip(ordered, encloses) if not outer]


def _reduce_events_pairwise(devices: dict, spans: list, chips: int | None = None) -> dict:
    """The reduction as it was before the sweep, kept verbatim as the
    oracle: every idle gap walks the host spans from the first."""
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if windows:
        lo, hi = min(s[1] for s in windows), max(s[2] for s in windows)
    else:  # no window span: the extent of the device's work
        every = [e for d in devices.values() for e in (d["ops"] or d["modules"])]
        if not every:
            raise ValueError("the trace holds no device op")
        lo, hi = min(e[1] for e in every), max(e[2] for e in every)
    ordinals = sorted(devices)[: chips or len(devices)]
    if not ordinals:
        raise ValueError("the trace holds no /device:TPU plane")
    host = sorted((s for s in spans if s[0] != WINDOW_SPAN), key=lambda s: s[1])
    per_device = []
    for n in ordinals:
        dev = devices[n]
        events = dev["ops"] or dev["modules"]
        inside = [(nm, max(s, lo), min(e, hi)) for nm, s, e in leaves_only(events)
                  if e > lo and s < hi]
        busy = union((s, e) for _, s, e in inside)
        gaps = complement(busy, lo, hi)
        mods = sorted(dev["modules"], key=lambda m: m[1])
        mod_starts = [m[1] for m in mods]
        by_name: dict[str, float] = {}
        collective = 0.0
        for nm, s, e in inside:
            op = short_op_name(nm)
            if COLLECTIVE.match(op):
                collective += e - s
            i = bisect.bisect_right(mod_starts, s + 1.0) - 1
            if dev["ops"] and i >= 0 and mods[i][2] >= s:
                op = f"{short_module_name(mods[i][0])}/{op}"
            by_name[op] = by_name.get(op, 0.0) + (e - s)
        by_span: dict[str, float] = {}
        for gs, ge in gaps:
            covered = 0.0
            for nm, s, e in host:
                if s >= ge:
                    break
                o = min(e, ge) - max(s, gs)
                if o > 0:
                    key = nm[len(SPAN_PREFIX):]
                    by_span[key] = by_span.get(key, 0.0) + o
                    covered += o
            if ge - gs > covered:
                by_span["no span"] = by_span.get("no span", 0.0) + (ge - gs - covered)
        per_device.append({
            "ordinal": n, "busy_ns": total(busy), "op_ns": sum(by_name.values()),
            "collective_ns": collective, "ops": by_name, "gaps": by_span,
            "longest_gap_ns": max((ge - gs for gs, ge in gaps), default=0.0),
        })
    window_ns = hi - lo
    idlest = min(per_device, key=lambda d: d["busy_ns"])
    ops_total: dict[str, float] = {}
    for d in per_device:
        for nm, ns in d["ops"].items():
            ops_total[nm] = ops_total.get(nm, 0.0) + ns / len(per_device)
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": sum(d["busy_ns"] for d in per_device) / len(per_device) * 1e-9,
        "idle_pct_idlest": 100.0 * (1.0 - idlest["busy_ns"] / window_ns),
        "op_s": sum(d["op_ns"] for d in per_device) / len(per_device) * 1e-9,
        "op_s_max": max(d["op_ns"] for d in per_device) * 1e-9,
        "collective_s_max": max(d["collective_ns"] for d in per_device) * 1e-9,
        "devices": len(per_device),
        "device_ops": [[nm, ns * 1e-9] for nm, ns in
                       sorted(ops_total.items(), key=lambda kv: -kv[1])],
        "idle_gaps": [[nm, ns * 1e-9] for nm, ns in
                      sorted(idlest["gaps"].items(), key=lambda kv: -kv[1])],
        "longest_gap_s": idlest["longest_gap_ns"] * 1e-9,
    }


def _synthetic_window(rng, chips, ops, calls, *, window=True, edges=True, depth=True):
    """A traced window written as the reduction reads it: ``chips`` device
    planes of ``ops`` ops each in ``calls`` calls (one module event a call,
    ``while`` ops round parts of the body, an ``all-reduce`` now and then,
    short gaps between ops and longer ones between calls, a gap no span
    covers), and per call the nested host spans of a slice-SPMD call
    (``call`` > ``tnc.spmd.contract`` > ``build``, ``place`` >
    ``place_buffers``, ``execute``, ``fetch``), a second thread's spans
    that overlap them, spans that start or end exactly on an op's edge,
    and spans wholly before and after the window. Times are whole ns."""
    per_call = ops // calls
    dur = rng.integers(200, 20_000, size=(chips, calls, per_call))
    gap = rng.integers(0, 3_000, size=(chips, calls, per_call))
    gap[:, :, 0] += rng.integers(50_000, 400_000, size=(chips, calls))  # between calls
    devices, spans = {}, []
    call_edges = []
    for c in range(chips):
        starts = np.cumsum(gap[c] + dur[c], axis=None).reshape(calls, per_call) - dur[c]
        ends = starts + dur[c]
        names = rng.integers(0, 40, size=(calls, per_call))
        op_list, mods = [], []
        for k in range(calls):
            s_row, e_row, n_row = starts[k].tolist(), ends[k].tolist(), names[k].tolist()
            for s, e, nm in zip(s_row, e_row, n_row):
                kind = "all-reduce" if nm == 0 else "fusion"
                shape = "f32[8]" if nm % 3 else "f32[16]"  # one short name, two texts
                op_list.append((f"%{kind}.{nm % 20} = {shape} {kind}(x)", float(s), float(e)))
            if k % 7 == 3:  # a while op spans part of the body
                op_list.append(("%while.1 = (s32[]) while(x)", float(s_row[1]), float(e_row[-2])))
            if k % 5 == 2:  # an op outside every module, after the call
                op_list.append((f"%copy-start.{k} = f32[8] copy-start(x)",
                                float(e_row[-1] + 10_000), float(e_row[-1] + 15_000)))
            mods.append((f"jit_tnc_spmd_slices({1000 + k % 3})", float(s_row[0]), float(e_row[-1])))
            if c == 0:
                call_edges.append((s_row, e_row))
        devices[c] = {"ops": op_list, "modules": mods}
    first, last = call_edges[0][0][0], call_edges[-1][1][-1]
    if window:
        spans.append(("perf:window", float(first - 30_000), float(last + 30_000)))
    spans.append(("perf:plan", float(first - 90_000), float(first - 40_000)))  # before it
    spans.append(("perf:reference", float(last + 40_000), float(last + 90_000)))  # after it
    for k, (s_row, e_row) in enumerate(call_edges):
        lo, hi = s_row[0] - 40_000, e_row[-1] + 1_000
        if k == calls // 2:
            continue  # a call's gap that no span covers
        mid = s_row[len(s_row) // 2]
        spans.append(("perf:call", float(lo), float(hi)))
        if depth:
            spans += [("perf:tnc.spmd.contract", float(lo + 10), float(hi - 10)),
                      ("perf:tnc.spmd.build", float(lo + 10), float(lo + 20_000)),
                      ("perf:tnc.spmd.place", float(lo + 20_000), float(lo + 35_000)),
                      ("perf:tnc.backend.place_buffers", float(lo + 21_000), float(lo + 34_000)),
                      ("perf:tnc.spmd.execute", float(lo + 35_000), float(s_row[0])),
                      ("perf:tnc.spmd.fetch", float(s_row[0]), float(hi - 10))]
        if edges:  # ends on one op's end, starts on a later op's start
            spans.append(("perf:tnc.edge", float(e_row[2]), float(s_row[5])))
            spans.append(("perf:other_thread", float(mid), float(mid + 250_000)))
    rng.shuffle(spans)
    return devices, spans


def _assert_same_reduction(got, want):
    assert set(got) == set(want)
    for key in ("window_s", "busy_s", "idle_pct_idlest", "op_s", "op_s_max",
                "collective_s_max", "longest_gap_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9, abs=0.0), key
    assert got["devices"] == want["devices"]
    for key in ("device_ops", "idle_gaps"):
        assert [nm for nm, _ in got[key]] == [nm for nm, _ in want[key]], key
        assert [s for _, s in got[key]] == pytest.approx(
            [s for _, s in want[key]], rel=1e-9, abs=0.0), key


def test_sweep_agrees_with_the_pairwise_reduction_on_recorded_trace():
    devices, spans = trace_reduce.read_planes(os.path.join(DATA, "probe.xplane.pb"))
    got = trace_reduce.reduce_events(devices, spans)
    _assert_same_reduction(got, _reduce_events_pairwise(devices, spans))
    assert got == _reduce_events_pairwise(devices, spans)  # the same sums, in the same order


@pytest.mark.parametrize("seed", range(8))
def test_sweep_agrees_with_the_pairwise_reduction_on_random_windows(seed):
    rng = np.random.default_rng(seed)
    chips = int(rng.integers(1, 5))
    devices, spans = _synthetic_window(
        rng, chips, ops=int(rng.integers(200, 2_000)), calls=int(rng.integers(3, 12)),
        window=seed % 4 != 1, edges=seed % 3 != 2, depth=seed % 5 != 4)
    want = _reduce_events_pairwise(devices, spans)
    got = trace_reduce.reduce_events(devices, spans)
    _assert_same_reduction(got, want)
    assert got == want
    gaps = dict(map(tuple, got["idle_gaps"]))
    assert gaps["no span"] > 0
    if seed % 4 != 1:  # spans wholly outside the window read nothing
        assert "plan" not in gaps and "reference" not in gaps
    if seed % 3 != 2:
        assert gaps["tnc.edge"] > 0 and gaps["other_thread"] > 0
    # one chip at a time, as perf/chip_lib.py reduces it
    for n in devices:
        one = {n: devices[n]}
        _assert_same_reduction(trace_reduce.reduce_events(one, spans),
                               _reduce_events_pairwise(one, spans))


@pytest.mark.parametrize("seed", range(6))
def test_interval_steps_agree_with_the_plain_ones(seed):
    """Leaves, union and gaps by arrays against the loops above, on whole-ns
    events that tie, repeat, touch, nest, overlap in part and last 0 ns."""
    rng = np.random.default_rng(100 + seed)
    n = int(rng.integers(1, 400))
    starts = rng.integers(0, 60, size=n).astype(float)
    ends = starts + rng.choice([0, 1, 2, 5, 20], size=n)
    events = [(f"op{i}", s, e) for i, (s, e) in enumerate(zip(starts.tolist(), ends.tolist()))]
    leaves = trace_reduce.leaves_only(starts, ends)
    assert [events[i] for i in leaves.tolist()] == leaves_only(events)
    busy = trace_reduce.union(starts[leaves], ends[leaves])
    plain = union((s, e) for _, s, e in leaves_only(events))
    assert list(zip(*(b.tolist() for b in busy))) == plain
    for lo, hi in ((0.0, 90.0), (-5.0, 30.0), (float(starts.min()), float(ends.max()))):
        clipped = [(max(s, lo), min(e, hi)) for s, e in plain if e > lo and s < hi]
        got = trace_reduce.complement(*trace_reduce.union(
            np.array([c[0] for c in clipped]), np.array([c[1] for c in clipped])), lo, hi)
        assert list(zip(*(g.tolist() for g in got))) == complement(union(clipped), lo, hi)


def test_sweep_reduces_a_four_chip_window_in_linear_time():
    """4 chips x 250 000 ops, 1000 host spans: the gap-by-span loop needs
    some 75 s of one CPU core for this, so a reduction that walks every
    span for every gap cannot pass. Timed inside the test (no plugin for
    timeouts)."""
    import time

    devices, spans = _synthetic_window(np.random.default_rng(7), 4, ops=250_000, calls=143,
                                       edges=False)
    assert len(spans) == 3 + 7 * 142
    t0 = time.monotonic()
    out = trace_reduce.reduce_events(devices, spans)
    seconds = time.monotonic() - t0
    print("four-chip window reduced in", seconds, "s")
    assert seconds < 20.0
    assert out["devices"] == 4 and 0 < out["busy_s"] < out["window_s"]


# -- roofline count ------------------------------------------------------


def test_roofline_count_three_steps_by_hand():
    # leaves: A[a,b] B[b,c] C[c,d,s] D[d]; legs of size 4, s (sliced) of 2.
    # steps: A*B (no sliced leg: counted once), (AB)*C, ((AB)C)*D (last).
    dims = {"a": 4, "b": 4, "c": 4, "d": 4, "s": 2}
    leaf_legs = [("a", "b"), ("b", "c"), ("c", "d", "s"), ("d",)]
    pairs = [(0, 1), (0, 2), (0, 3)]
    shapes = reference.plan_shapes(leaf_legs, pairs, dims, sliced_legs=("s",))
    assert [(s["k"], s["m"], s["n"]) for s in shapes] == [(4, 4, 4), (4, 4, 4), (4, 4, 1)]
    assert [s["varies"] for s in shapes] == [False, True, True]
    # on-chip memory of 100 B: the 16-element (128 B) intermediates count,
    # with 200 B they do not
    counted = roofline.window_cost(shapes, units=2, on_chip_bytes=100)
    fused = roofline.window_cost(shapes, units=2, on_chip_bytes=200)
    assert counted["ops"] == fused["ops"] == 8 * (64 + 2 * 64 + 2 * 16)
    leaves = 8 * (16 + 16) + 2 * 8 * 16 + 2 * 8 * 4  # A, B once; C, D per slice
    result = 2 * 8 * 4
    assert fused["bytes"] == leaves + result
    # AB written once, read per slice; (AB)C written and read per slice
    assert counted["bytes"] == leaves + result + 128 * (1 + 2 + 2 + 2)
    least = roofline.least_seconds(counted, PEAKS)
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(counted["bytes"] / 819e9)


def test_peaks_table_refuses_an_unknown_device():
    assert common.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        common.peaks_for("cpu")


# -- the plain reference -------------------------------------------------


def test_reference_agrees_with_dense_statevector():
    n = 10
    gates = circuits.circuit_gates({"family": "sycamore", "qubits": n, "cycles": 6}, 5)
    psi = reference.statevector(gates, n)
    assert np.vdot(psi, psi).real == pytest.approx(1.0)
    bits = "0110100111"
    raw = reference.raw_network(gates, n, bits)
    leaf_legs = [legs for legs, _ in raw]
    pairs = _greedy_pairs(leaf_legs)
    want = psi[tuple(int(b) for b in bits)]
    for precision, tol in (("complex128", 1e-12), ("bf16x3", 1e-3)):
        ref = reference.Reference(leaf_legs, pairs, precision=precision)
        got = complex(ref.value(ref.place([d for _, d in raw])).reshape(-1)[0])
        assert abs(got - want) <= tol * abs(want)
    assert abs(got - want) > 1e-7 * abs(want)  # three bfloat16 passes are not float32
    # slicing two legs and summing the four slices gives the same number
    sliced = tuple(sorted({l for legs in leaf_legs for l in legs})[40:42])
    ref = reference.Reference(leaf_legs, pairs, sliced, (2, 2))
    placed = ref.place([d for _, d in raw])
    total = sum(complex(ref.value(placed, s).reshape(-1)[0]) for s in range(4))
    assert abs(total - want) <= 1e-12 * abs(want)


def _greedy_pairs(leaf_legs):
    """Any valid order: always contract the first slot with its first neighbour."""
    legs = {i: set(l) for i, l in enumerate(leaf_legs)}
    pairs = []
    while len(legs) > 1:
        a = min(legs)
        b = next((j for j in legs if j != a and legs[a] & legs[j]), None)
        if b is None:
            b = next(j for j in legs if j != a)
        pairs.append((a, b))
        legs[a] = legs[a] ^ legs.pop(b)
    return pairs


# -- CPU rehearsal of the harness ----------------------------------------

CONFIG = {"name": "tiny", "circuit": {"family": "sycamore", "qubits": 16, "cycles": 8},
          "planner": PLANNER, "target_log2": 8}
SERVED = {**CONFIG, "circuit": {"family": "sycamore", "qubits": 18, "cycles": 8},
          "target_log2": 25}  # unsliced, as the served configuration is
CELLS = {
    "tiny.amp_slices": {
        "name": "tiny.amp_slices", "config": "tiny",
        "traffic": {"kind": "slice_calls", "slices_per_call": 16, "warmup_slices": 8,
                    "check_calls": 1},
        "limits": {"amp_gap": 4e-6},
    },
    "tiny.amp_slices_spmd4": {
        "name": "tiny.amp_slices_spmd4", "config": "tiny",
        "traffic": {"kind": "spmd_calls", "slices_per_chip": 4},
        "limits": {"amp_gap": 4e-6},
    },
    "tiny.xeb_closed64": {
        "name": "tiny.xeb_closed64", "config": "tiny",
        "traffic": {"kind": "closed_loop", "in_flight": 64, "warmup_batches": 2,
                    "pool": 256, "check_requests": 8},
        "limits": {"amp_gap": 1e-4},
    },
}
BENCH = {
    "workloads": [{"name": n, "config": "tiny", "chips": 1} for n in CELLS],
    "end_to_end": [
        {"name": "amplitude_s", "unit": "s",
         "workloads": ["tiny.amp_slices", "tiny.amp_slices_spmd4"]},
        {"name": "amps_per_s", "unit": "amp/s", "workloads": ["tiny.xeb_closed64"]},
        {"name": "request_p95_ms", "unit": "ms", "workloads": ["tiny.xeb_closed64"]},
        {"name": "setup_s", "unit": "s"},
    ],
    "per_layer": [],
}


def rehearse(cell: str, seed: int = 11, seconds: float = 0.5, config=CONFIG, chips=1):
    import jax

    run = perf_run.Run(
        workload=copy.deepcopy(CELLS[cell]), config=copy.deepcopy(config),
        cell={"name": cell, "config": "tiny", "chips": chips}, seed=seed,
        seconds=seconds, trace=False, chips=chips,
        device=common.device_record(jax, chips), peaks=PEAKS,
        compiles=common.CompileCounter().install(),
    )
    return perf_run.drive(run, BENCH), run


def test_rehearsal_slice_calls():
    result, run = rehearse("tiny.amp_slices")
    assert result["correct"] is True, result["numbers"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"amplitude_s", "setup_s"}
    w = run.window
    assert result["metrics"]["amplitude_s"]["value"] == pytest.approx(
        run.state["num_slices"] * w["window_s"] / w["slices"]
    )
    assert result["numbers"]["amp_gap"]["value"] < 4e-6
    assert result["numbers"]["programs_built_in_window"] == {"value": 0, "limit": 0}
    # the same seed gives the same inputs; another seed, the same structure
    assert run.setup["structure_digest"] == rehearse("tiny.amp_slices", seed=12)[1].setup["structure_digest"]


def test_rehearsal_closed_loop():
    result, run = rehearse("tiny.xeb_closed64", seconds=1.0, config=SERVED)
    assert result["correct"] is True, result["numbers"]
    assert set(result["metrics"]) == {"amps_per_s", "request_p95_ms", "setup_s"}
    assert result["attempted"] == len(run.window["completed"]) >= 64
    # full batches only (a batch lasts milliseconds here, so the service's
    # counts are read up to a batch off the window's ends; on the chip: 32.0)
    stats = run.window["stats"]
    assert stats["completed"] / stats["batches"] == pytest.approx(32, rel=0.05)
    assert result["metrics"]["amps_per_s"]["value"] == pytest.approx(
        len(run.window["completed"]) / run.window["window_s"]
    )


def test_rehearsal_spmd_calls():
    result, run = rehearse("tiny.amp_slices_spmd4", seed=12, chips=4)
    assert result["correct"] is True, result["numbers"]
    assert result["device"]["count"] == 4
    assert set(result["metrics"]) == {"amplitude_s", "setup_s"}
    assert run.window["slices"] == 16 * result["attempted"]


def test_fault_exchange_left_out_is_not_correct(monkeypatch):
    import jax

    from tnc_tpu.parallel import sliced_parallel

    # the compiled function is cached by the plan's signature: build it anew
    monkeypatch.setattr(sliced_parallel, "_SPMD_FN_CACHE", {})
    monkeypatch.setattr(jax.lax, "psum", lambda x, axis_name, **kw: x)
    result, _ = rehearse("tiny.amp_slices_spmd4", seed=12, seconds=0.05, chips=4)
    assert result["correct"] is False
    assert result["numbers"]["amp_gap"]["value"] > 0.05


def test_fault_slices_left_out_is_not_correct(monkeypatch):
    from tnc_tpu.ops.backends import JaxBackend

    real = JaxBackend.execute_sliced

    def half(self, sp, arrays, slice_range=None, **kw):
        if slice_range is not None and slice_range[1] - slice_range[0] >= 16:
            lo, hi = slice_range
            slice_range = (lo, lo + (hi - lo) // 2)  # half of the call's slices
        return real(self, sp, arrays, slice_range=slice_range, **kw)

    monkeypatch.setattr(JaxBackend, "execute_sliced", half)
    # seed 12: the slices of the first calls are all non-zero (many slices of
    # so small a circuit are exactly zero); every call of the window compared
    monkeypatch.setitem(CELLS["tiny.amp_slices"]["traffic"], "check_calls", 1000)
    result, _ = rehearse("tiny.amp_slices", seed=12, seconds=0.05)
    assert result["correct"] is False
    assert result["numbers"]["amp_gap"]["value"] > 0.05


def test_fault_answer_altered_is_not_correct(monkeypatch):
    from tnc_tpu.serve.rebind import BoundProgram

    real = BoundProgram.amplitudes_det

    def altered(self, batch_bits, backend=None, **kw):
        out = np.array(real(self, batch_bits, backend, **kw))
        out *= 1.0 + 1e-3  # the answers of each batch, where they are produced
        return out

    monkeypatch.setattr(BoundProgram, "amplitudes_det", altered)
    result, _ = rehearse("tiny.xeb_closed64", seconds=1.0, config=SERVED)
    assert result["correct"] is False
    assert result["numbers"]["amp_gap"]["value"] > 1e-4


def test_fault_failed_requests_are_not_correct(monkeypatch):
    from tnc_tpu.serve.rebind import BoundProgram

    calls = {"n": 0}
    real = BoundProgram.amplitudes_det

    def flaky(self, batch_bits, backend=None, **kw):
        calls["n"] += 1
        if calls["n"] > 6 and calls["n"] % 3 == 0:
            raise ValueError("broken underneath")
        return real(self, batch_bits, backend, **kw)

    monkeypatch.setattr(BoundProgram, "amplitudes_det", flaky)
    result, _ = rehearse("tiny.xeb_closed64", seconds=1.0, config=SERVED)
    assert result["correct"] is False and result["failed"] > 0


def test_control_lower_precision_is_not_correct():
    """The reference in three bfloat16 passes (a TPU's 'high'), put in the
    program's place, must read above the limit that the program's own answers
    pass. (On the chip the control is the program itself under its own
    TNC_TPU_DOT_PRECISION=high: PERF.md; on the CPU that path is inert.)"""
    spec = CONFIG["circuit"]
    from perf import sut

    for seed in (21, 22, 23):
        gates = circuits.circuit_gates(spec, seed)
        bits = circuits.seeded_bitstrings(1, spec["qubits"], seed)[0]
        plan = sut.plan_sliced(gates, spec["qubits"], bits, CONFIG)
        q = plan.question()
        slices = list(range(16))
        low = compare.slice_values(gates, spec["qubits"], bits, q, slices, "bf16x3")
        control = [(0, 16, sum(low.values()))]
        gap = compare.slice_sum_gap(gates, spec["qubits"], bits, q, control)
        print("control gap", seed, gap)
        assert gap > CELLS["tiny.amp_slices"]["limits"]["amp_gap"], gap


def test_run_py_fails_without_a_tpu():
    root = common.CHECKOUT
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "perf", "run.py"), "--workload",
         "sycamore53_m14.amp_slices", "--seed", "1", "--seconds", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    assert "correct" not in last
    assert "needs a TPU" in proc.stderr


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(common.CHECKOUT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for cfg in bench["configs"]:
        assert os.path.exists(os.path.join(common.CHECKOUT, cfg["file"]))
    for cell in bench["workloads"]:
        w = common.load_json("workloads", cell["name"] + ".json")
        assert w["config"] == cell["config"]
        assert os.path.exists(os.path.join(common.PERF_DIR, "traffic", w["traffic"]["kind"] + ".py"))
    for entry in bench["per_layer"]:
        module = perf_run.load_metric(entry["name"])
        for key in ("name", "unit", "layer", "moves", "workloads"):
            assert getattr(module, key) == entry.get(key), (entry["name"], key)
