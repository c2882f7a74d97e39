"""Traffic kind ``share_slice_calls``: one chip's share of an amplitude
that a fleet slices.

A fleet plans the amplitude once and ships the plan; every chip then runs
a contiguous range of its slices, and the partial sums are added once at
the end. Set-up here is a chip's: the network of the seed's circuit and
bitstring, its structure digest, the entry of the configuration's plan
store read back READ-ONLY by the program
(``tnc_tpu.serve.plancache.load_stored_plan``: stored pairs and slicing,
``build_sliced_program``), the hoist, ``JaxBackend``. It never plans: a
missing entry, or one stored for another structure, fails set-up. The
chip is ``k = seed mod chips``, its share the ``num_slices // chips``
slices from ``lo = k * (num_slices // chips)``.

The window calls ``JaxBackend().execute_sliced(sp, arrays, host=False,
slice_range=(lo, lo + slices_per_call))`` on consecutive ranges of the
chip's share from its start, each awaited, one in flight, until
``--seconds`` have passed; the call in flight then is finished and
counted with its time. ``amplitude_s`` is ``slice_calls``' (every slice
of the plan at the window's pace, on one chip); a fleet of ``chips``
takes that over ``chips``.

Parameters (the cell's ``traffic`` object): ``chips``,
``slices_per_call``, ``warmup_slices`` (run at the start of the share in
set-up: as many as a call, so that the window's calls run the programs
set-up compiled), ``check_calls`` (calls compared, drawn from the seed
among the window's) and ``check_draws`` (a drawn call whose reference
slices vanish says only that both sides are zero: another takes its
place while draws are left, up to this many in all).
"""

from __future__ import annotations

import gc
import math
import os
import resource
import time

import numpy as np

from perf import circuits, common, compare, plan_store, sut
from perf.common import span
from perf.traffic.slice_calls import end_to_end  # noqa: F401 — the kind's own: a window of slice calls reads the same

PHASE = "plan.store"


def prepare(run) -> None:
    import jax

    from tnc_tpu import obs
    from tnc_tpu.contractionpath.slicing import sliced_flops
    from tnc_tpu.ops.backends import JaxBackend
    from tnc_tpu.ops.budget import fits_hbm
    from tnc_tpu.ops.hoist import hoist_sliced_program
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.serve.plancache import load_stored_plan

    params = run.workload["traffic"]
    config, spec = run.config, run.config["circuit"]
    backend = JaxBackend()
    bits = circuits.seeded_bitstrings(1, spec["qubits"], run.seed)[0]
    t0 = time.monotonic()
    with span("plan"):
        gates, bits, tn = plan_store.network(config, run.seed, bits)
        with obs.collect_phases() as totals:
            stored = load_stored_plan(
                os.path.join(common.CHECKOUT, config["plan_store"]), tn,
                2.0 ** config["target_log2"],
            )
        sp = stored.sliced
        hp = hoist_sliced_program(sp)
        fits = fits_hbm(hp.residual.program, batch=1, device=backend.device)
    if not fits:
        raise RuntimeError("the stored plan's residual does not fit this chip by the budget model")
    leaves = flat_leaf_tensors(tn)
    plan = sut.SlicedPlan(tn, stored.path, stored.slicing, sp, hp,
                          [leaf.data.into_data() for leaf in leaves])
    num_slices = plan.num_slices
    chips, per_call = int(params["chips"]), int(params["slices_per_call"])
    share = num_slices // chips
    if share < per_call:
        raise RuntimeError(f"a chip's share is {share} slices, a call takes {per_call}")
    lo = (run.seed % chips) * share
    info = {
        "network": f"{len(leaves)} leaves after simplify",
        "plan_s": time.monotonic() - t0,
        "plan_store": {"key": stored.key, "hit": 1, "sig_drift": int(stored.sig_drift),
                       "seconds": totals.get(PHASE), "bytes": totals.get(f"{PHASE}.bytes"),
                       "legs": len(stored.slicing.legs), "num_slices": num_slices},
        "target_log2": config["target_log2"],
        "slice_peak_log2": stored.slice_peak_log2,
        "sliced_cmacs": float(sliced_flops(list(tn.tensors), stored.path.toplevel,
                                           stored.slicing)),
        "num_slices": num_slices,
        "sliced_legs": len(stored.slicing.legs),
        "steps": len(sp.program.steps),
        "prelude_steps": len(hp.prelude_steps),
        "residual_steps": len(hp.residual.program.steps),
        "chip": run.seed % chips, "share_lo": lo, "share": share,
        "structure_digest": common.digest([sorted(leaf.legs) for leaf in leaves]),
        "plan_digest": common.digest(
            [[(st.lhs, st.rhs) for st in sp.program.steps],
             list(stored.slicing.legs), list(stored.slicing.dims)]
        ),
    }
    common.emit({"phase": "plan", **info})
    t0 = time.monotonic()
    with span("build"):
        warm = backend.execute_sliced(
            sp, plan.arrays, host=False,
            slice_range=(lo, lo + int(params["warmup_slices"])),
        )
        jax.block_until_ready(warm)
    first_call_s = time.monotonic() - t0
    platforms = {d.platform for leaf in jax.tree.leaves(warm) for d in leaf.devices()}
    if platforms != {run.device["platform"]}:
        raise RuntimeError(f"result lives on {sorted(platforms)}, not {run.device['platform']}")
    run.setup.update(plan_s=info["plan_s"], first_call_s=first_call_s,
                     sliced_cmacs=info["sliced_cmacs"], plan_store=info["plan_store"],
                     structure_digest=info["structure_digest"],
                     plan_digest=info["plan_digest"])
    run.state.update(plan=plan, backend=backend, gates=gates, bits=bits,
                     per_call=per_call, num_slices=num_slices, share_lo=lo,
                     share=share, question=plan.question())
    # every window starts from a collected heap: with some forty calls a
    # window, a full collection (0.1 s) fell inside some windows and not
    # others, 0.5 % of amplitude_s (PERF.md)
    gc.collect()


def window(run) -> None:
    import jax

    plan, backend = run.state["plan"], run.state["backend"]
    per_call, first, share = run.state["per_call"], run.state["share_lo"], run.state["share"]
    calls = []  # (t_start, t_end, lo, hi, result on device)
    failed = 0
    lo = first
    with span("window"):
        t0 = time.monotonic()
        while time.monotonic() - t0 < run.seconds:
            if lo + per_call > first + share:
                lo = first
            ts = time.monotonic()
            try:
                with span("call"):
                    out = backend.execute_sliced(
                        plan.sp, plan.arrays, host=False, slice_range=(lo, lo + per_call)
                    )
                    jax.block_until_ready(out)
            except Exception as exc:  # noqa: BLE001 — a failed call is counted, the run goes on to report
                common.emit({"phase": "window", "step": "call failed", "error": repr(exc)[:300]})
                failed += 1
                break
            calls.append((ts, time.monotonic(), lo, lo + per_call, out))
            lo += per_call
        end = calls[-1][1] if calls else time.monotonic()
    run.window.update(calls=calls, failed=failed, t0=t0, window_s=end - t0,
                      slices=sum(c[3] - c[2] for c in calls))
    run.window["units"] = run.window["slices"]


def summary(run) -> dict:
    w = run.window
    split = run.state["backend"].split_complex
    per = [1e3 * (c[1] - c[0]) / (c[3] - c[2]) for c in w["calls"]]
    zero = sum(1 for c in w["calls"] if not np.any(sut.result_to_complex(c[4], split)))
    return {"calls": len(w["calls"]), "slices": w["slices"], "zero_calls": zero,
            "first_slice": w["calls"][0][2] if w["calls"] else None,
            "call_ms_per_slice": [round(x, 4) for x in per]}


def call_gap(values, lo: int, hi: int, got, n_qubits: int, num_slices: int):
    """``(gap, vanishes)`` of one call from the reference's values of its
    slices, already computed. The gap is ``compare.slice_sum_gap``'s:
    ``|got - want|`` over the root of the summed squares of the slices'
    values, or over a tenth of an amplitude's typical share of that many
    slices where that root is smaller. The call's slices VANISH where the
    root is under a millionth of that typical share: exact zeros of the
    gate set (fSim(pi/2, phi) keeps the number of ones), which complex128
    leaves as rounding residue some 1e-16 of the terms' size; the gap then
    only says that both sides are zero."""
    vals = np.array([values[s] for s in range(lo, hi)])
    typical = 2.0 ** (-n_qubits / 2.0) * math.sqrt((hi - lo) / num_slices)
    root = math.sqrt(float(np.sum(np.abs(vals) ** 2)))
    scale = max(root, 0.1 * typical)
    gap = abs(complex(np.asarray(got).reshape(-1)[0]) - complex(vals.sum())) / scale
    return (gap if math.isfinite(gap) else float("inf")), root < 1e-6 * typical


def check(run):
    """One call of the window drawn from the seed against the plain
    reference's slices of the same range (``compare.slice_values``,
    complex128 on the host, one slice and one host process at a time); a
    drawn call whose reference slices vanish gives way to the next draw,
    up to ``check_draws`` draws."""
    w = run.window
    params = run.workload["traffic"]
    split = run.state["backend"].split_complex
    n_qubits = run.config["circuit"]["qubits"]
    rng = np.random.default_rng([run.seed, 3])
    draws = min(int(params["check_draws"]), len(w["calls"]))
    order = rng.permutation(len(w["calls"]))[:draws].tolist()
    answers = [
        (w["calls"][i][2], w["calls"][i][3],
         sut.result_to_complex(w["calls"][i][4], split).reshape(-1))
        for i in order
    ]
    # free the program's state before the reference takes the host
    w["calls"] = [c[:4] for c in w["calls"]]
    run.state.pop("plan"), run.state.pop("backend")
    want = int(params["check_calls"])
    worst, compared, drawn = 0.0, 0, 0
    for lo, hi, got in answers:
        values = {}
        for s in range(lo, hi):  # one host process at a time: 24.5 GB each (PERF.md)
            values.update(compare.slice_values(
                run.state["gates"], n_qubits, run.state["bits"], run.state["question"], [s],
            ))
        drawn += 1
        gap, vanishes = call_gap(values, lo, hi, got, n_qubits, run.state["num_slices"])
        common.emit({"phase": "check", "call": [lo, hi], "draw": drawn, "vanishes": vanishes,
                     "amp_gap": gap, "reference_maxrss_gb": round(
                         resource.getrusage(resource.RUSAGE_CHILDREN).ru_maxrss / 2**20, 3)})
        if vanishes and len(answers) - drawn >= want - compared:
            continue  # a later draw takes its place
        worst, compared = max(worst, gap), compared + 1
        if compared == want:
            break
    gap = worst if compared else float("inf")
    numbers = {"amp_gap": {"value": gap, "limit": run.workload["limits"]["amp_gap"]}}
    return numbers, len(w["calls"]) + w["failed"], w["failed"]
