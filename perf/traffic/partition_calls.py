"""Traffic kind ``partition_calls``: one amplitude by the partition-parallel
pipeline, a whole call at a time.

Set-up follows the reference's ``distributed_contraction`` example line
for line (``examples/distributed_contraction.py``): the amplitude network
of the seed's gates and bitstring, ``simplify_network``,
``find_partitioning(tn, partitions)`` with its defaults,
``partition_tensor_network``, ``Greedy(OptMethod.GREEDY).find_path(grouped)
.replace_path()`` (nested local paths and the toplevel fan-in), timed as
``plan_s``; then ``warmup_calls`` whole calls (``first_call_s``).

The window calls ``distributed_partitioned_contraction(grouped, path,
n_devices=chips)`` with its defaults again and again, one in flight, each
awaited to the amplitude on the host, until ``--seconds`` have passed;
the call in flight then is finished and counted. Scatter, program
look-up, local contraction, chip-to-chip moves, pair contractions and the
fetch are what the user pays and stay in. ``amplitude_s`` is the window
over the calls completed: a call is a whole amplitude, nothing is scaled.

Two orders of one network. ``run.state["question"]`` holds the pair
order that RAN (the partitions' local paths, then the fan-in, over the
same leaves; every leaf ``varying``: the user hands the tensors over with
each call, so the roofline counts every call's steps). The plain
reference is told a FLAT order of the same leaves instead, found by the
configuration's ``reference_order`` planner (the accepted served
configuration's block): the partitioned order costs 2^39 multiply-adds
in complex128 on the host, the flat one 2^30, and the amplitude does not
depend on the order. EVERY call of the window is compared with it:
``amp_gap = |got - want| / max(|want|, 2^(-n/2))``.

Parameters (the cell's ``traffic`` object): ``warmup_calls``.
"""

from __future__ import annotations

import math
import time

import numpy as np

from perf import chip_lib, circuits, common, compare, sut
from perf.common import span

COUNTERS = "partitioned."  # the program's always-on seconds and counts of a call (obs.phase)


def _question(leaves, pairs, varying: bool) -> dict:
    leg_dims = {}
    for leaf in leaves:
        leg_dims.update(dict(leaf.edges()))
    return {
        "leaf_legs": [tuple(leaf.legs) for leaf in leaves], "pairs": list(pairs),
        "sliced_legs": (), "sliced_dims": (), "leg_dims": leg_dims,
        "varying_leaves": tuple(range(len(leaves))) if varying else (),
    }


def _plan(run) -> dict:
    """The example's recipe on the seed's circuit; prints the ``plan`` line."""
    from tnc_tpu import CompositeTensor
    from tnc_tpu.contractionpath.paths import Greedy, OptMethod
    from tnc_tpu.ops.program import build_program, step_flops
    from tnc_tpu.parallel.partitioned import flatten_partitioned_path, plan_fanin_pairs
    from tnc_tpu.tensornetwork.partitioning import (
        find_partitioning, partition_tensor_network,
    )
    from tnc_tpu.tensornetwork.simplify import simplify_network
    from tnc_tpu.tensornetwork.tensor import LeafTensor

    spec = run.config["circuit"]
    n, k = spec["qubits"], int(run.config["partitions"])
    gates = circuits.circuit_gates(spec, run.seed)
    bits = circuits.seeded_bitstrings(1, n, run.seed)[0]
    raw, _ = sut.build_circuit(gates, n).into_amplitude_network(bits)
    tn = simplify_network(raw)
    t0 = time.monotonic()
    with span("plan"):
        partitioning = find_partitioning(tn, k)
        grouped = partition_tensor_network(CompositeTensor(list(tn.tensors)), partitioning)
        found = Greedy(OptMethod.GREEDY).find_path(grouped)
        path = found.replace_path()
    plan_s = time.monotonic() - t0

    # what the plan is, for the plan line and PERF.md (names and sizes only)
    programs = [build_program(child, path.nested[i]) for i, child in enumerate(grouped.tensors)]
    metas = [LeafTensor(list(p.result_legs), list(p.result_shape)) for p in programs]
    _, moved, pair_cmacs, _ = plan_fanin_pairs(metas, path.toplevel)
    local_cmacs = [sum(step_flops(st) for st in p.steps) for p in programs]
    sizes = [math.prod(p.result_shape) for p in programs]
    pair_sizes = [math.prod(m.bond_dims) for m in moved]
    leaves, pairs = flatten_partitioned_path(grouped, path)
    question = _question(leaves, pairs, varying=True)
    info = {
        "network": f"{len(raw)} tensors -> {len(tn)} after simplify",
        "plan_s": plan_s,
        "partition_leaves": [len(child.tensors) for child in grouped.tensors],
        "partition_result_log2": [round(math.log2(max(s, 1)), 2) for s in sizes],
        "partition_cmacs_log2": [round(math.log2(max(c, 1)), 2) for c in local_cmacs],
        "fanin": [list(p) for p in path.toplevel],
        "fanin_cmacs_log2": [round(math.log2(max(c, 1)), 2) for c in pair_cmacs],
        "fanin_moved_bytes": int(8 * sum(pair_sizes)),
        "planner_peak_log2": round(math.log2(found.size), 2),
        "planner_cmacs_log2": round(math.log2(found.flops), 2),
        "plan_cmacs": float(sum(local_cmacs) + sum(pair_cmacs)),
        "structure_digest": common.digest(sorted(sorted(l) for l in question["leaf_legs"])),
        "plan_digest": common.digest([
            [[sorted(leaf.legs) for leaf in child.tensors] for child in grouped.tensors],
            [path.nested[i].toplevel for i in range(len(grouped.tensors))],
            path.toplevel,
        ]),
    }
    common.emit({"phase": "plan", **info})
    return {"gates": gates, "bits": bits, "tn": tn, "grouped": grouped, "path": path,
            "question": question, "info": info}


def _call(run):
    """One whole call, awaited to the amplitude on the host; the
    program's own counts of the call go to ``run.state["counters"]``."""
    from tnc_tpu import obs
    from tnc_tpu.parallel import distributed_partitioned_contraction

    with obs.collect_phases() as totals:
        out = distributed_partitioned_contraction(
            run.state["grouped"], run.state["path"], n_devices=run.chips
        )
        data = np.asarray(out.data.into_data())
    run.state["counters"] = {
        key[len(COUNTERS):]: value for key, value in totals.items() if key.startswith(COUNTERS)
    }
    return complex(data.reshape(-1)[0])


def prepare(run) -> None:
    import jax

    platforms = {d.platform for d in jax.devices()[: run.chips]}
    if platforms != {run.device["platform"]}:
        raise RuntimeError(f"the chips are {sorted(platforms)}, not {run.device['platform']}")
    plan = _plan(run)
    info = plan.pop("info")
    run.state.update(plan)
    t0 = time.monotonic()
    with span("build"):
        for _ in range(int(run.workload["traffic"]["warmup_calls"])):
            _call(run)
    first_call_s = time.monotonic() - t0
    run.setup.update(plan_s=info["plan_s"], first_call_s=first_call_s,
                     sliced_cmacs=info["plan_cmacs"],
                     structure_digest=info["structure_digest"],
                     plan_digest=info["plan_digest"],
                     call_counters=run.state.get("counters", {}))


def window(run) -> None:
    calls = []  # (t_start, t_end, 0, 1): a call is one unit
    answers = []
    failed = 0
    with span("window"):
        t0 = time.monotonic()
        while time.monotonic() - t0 < run.seconds:
            ts = time.monotonic()
            try:
                with span("call"):
                    answers.append(_call(run))
            except Exception as exc:  # noqa: BLE001 — a failed call is counted, the run goes on to report
                common.emit({"phase": "window", "step": "call failed", "error": repr(exc)[:300]})
                failed += 1
                break
            calls.append((ts, time.monotonic(), 0, 1))
        end = calls[-1][1] if calls else time.monotonic()
    run.window.update(calls=calls, answers=answers, failed=failed, t0=t0,
                      window_s=end - t0, units=len(calls))


def summary(run) -> dict:
    """The window's line. Of a traced run also the chips one by one
    (``perf/chip_lib.py``): this is the one hook between the end of the
    trace and the harness deleting it."""
    w = run.window
    out = {"calls": len(w["calls"]),
           "call_s": [round(c[1] - c[0], 4) for c in w["calls"]],
           "call_counters": run.state.get("counters", {})}
    if run.trace:
        w["per_chip"] = chip_lib.read_window(run.cell["name"], run.chips)
        out["per_chip"] = w["per_chip"]
    return out


def end_to_end(run) -> dict:
    w = run.window
    if not w["calls"]:
        return {}
    return {"amplitude_s": w["window_s"] / len(w["calls"])}


def _reference_question(run) -> dict:
    """A flat order of the same leaves for the plain reference, by the
    configuration's ``reference_order`` planner."""
    from tnc_tpu.ops.program import build_program, flat_leaf_tensors

    tn = run.state["tn"]
    planner = run.config["reference_order"]
    t0 = time.monotonic()
    result = sut.make_planner(planner, 2.0 ** planner["target_log2"]).find_path(tn)
    program = build_program(tn, result.replace_path())
    common.progress("reference", "flat order planned", t0,
                    reference_cmacs=float(result.flops))
    return _question(flat_leaf_tensors(tn),
                     [(st.lhs, st.rhs) for st in program.steps], varying=False)


def check(run):
    """Every call of the window against the plain reference's amplitude."""
    w = run.window
    n = run.config["circuit"]["qubits"]
    # free the program's state before the reference takes the host
    for key in ("grouped", "path"):
        run.state.pop(key)
    gap = float("inf")
    if w["answers"]:
        question = _reference_question(run)
        want = compare.amplitudes(run.state["gates"], n, question, [run.state["bits"]])[0]
        floor = 2.0 ** (-n / 2.0)
        gaps = [abs(got - want) / max(abs(want), floor) for got in w["answers"]]
        gap = max(g if math.isfinite(g) else float("inf") for g in gaps)
        common.emit({"phase": "check", "want": [want.real, want.imag],
                     "got_first": [w["answers"][0].real, w["answers"][0].imag],
                     "distinct_answers": len(set(w["answers"]))})
    run.state.pop("tn")
    numbers = {"amp_gap": {"value": gap, "limit": run.workload["limits"]["amp_gap"]}}
    return numbers, len(w["calls"]) + w["failed"], w["failed"]
