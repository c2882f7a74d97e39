"""Traffic kind ``expval_slice_calls``: one Pauli string's expectation
value on a wide circuit, its slices in order.

Set-up binds the configuration's operator through
``tnc_tpu.queries.bind_expectation(circuit, pathfinder, None, target,
support=letters)``: the program finds the lightcone, plans the cone's
sandwich with the configuration's planner, slices it to the first target
from ``target_log2`` down that its HBM budget model accepts, and builds
the sliced program. The circuit is bound at the configuration's
``bind_theta_h``; the seed's ``theta_h`` reaches every call as
``params={"rx": (theta_h,)}``, the way a sweep over ``theta_h`` runs on
one plan and one compile. The window calls
``ExpectationProgram.values([letters], backend, params=...,
slice_range=(lo, lo + slices_per_call), host=False)`` on consecutive
ranges from slice 0, each call awaited on the device, one in flight,
until ``--seconds`` have passed; the call in flight then is finished and
counted with its time. ``amplitude_s`` is ``slice_calls``' (the plan's
slices at the window's pace): seconds to one exact value of the
operator. Parameters (the cell's ``traffic`` object):
``slices_per_call``, ``warmup_slices``, ``check_calls`` (answers compared
with the configuration's reference, ``perf/reference_<family>.py``, drawn
from the seed among the window's calls).
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from perf import circuits, common, sut
from perf.common import span
from perf.traffic.slice_calls import end_to_end, summary  # noqa: F401 — the kind's own: a window of slice calls reads the same

PHASES = "expval."


def _bind(run, device):
    """``(program, info, phases, question)``: the bound expectation
    program at the first target the budget model accepts, what the
    benchmark reads of its plan, the program's own phase totals of the
    bind, and what the reference is told."""
    from tnc_tpu import obs
    from tnc_tpu.contractionpath.slicing import sliced_flops
    from tnc_tpu.ops.budget import fits_hbm
    from tnc_tpu.ops.hoist import hoist_sliced_program
    from tnc_tpu.queries.expectation import bind_expectation

    spec = run.config["circuit"]
    state = run.state
    bind_theta = state["family"].parse_angle(spec["bind_theta_h"])
    bind_gates = [
        (name, (bind_theta,), on) if name == "rx" else (name, params, on)
        for name, params, on in state["gates"]
    ]
    target_log2 = run.config["target_log2"]
    t_plan = time.monotonic()
    while True:
        target = 2.0 ** target_log2
        t0 = time.monotonic()
        with obs.collect_phases() as totals:
            prog = bind_expectation(
                sut.build_circuit(bind_gates, spec["qubits"]),
                sut.make_planner(run.config["planner"], target), None, target,
                support=state["letters"],
            )
        sp = prog.bound.sliced
        if sp is None:
            raise RuntimeError(f"the plan fits 2^{target_log2} unsliced: not this traffic kind's cell")
        hp = hoist_sliced_program(sp)
        fits = fits_hbm(hp.residual.program, batch=1, device=device)
        common.progress("plan", f"target 2^{target_log2}", t0,
                        num_slices=sp.slicing.num_slices, fits_hbm=fits)
        if fits:
            break
        target_log2 -= 1
    question = _question(prog)
    pairs = question["pairs"]
    info = {
        "network": f"{len(question['leaf_legs'])} leaves, cone of {len(prog.kept_qubits)} of {prog.num_qubits} qubits",
        "target_log2": target_log2,
        "plan_s": time.monotonic() - t_plan,
        "sliced_cmacs": float(sliced_flops(
            list(prog.bound.template.network.tensors), pairs, sp.slicing)),
        "num_slices": sp.slicing.num_slices,
        "sliced_legs": len(sp.slicing.legs),
        "steps": len(pairs),
        "prelude_steps": len(hp.prelude_steps),
        "residual_steps": len(hp.residual.program.steps),
        "param_leaves": len(prog.param_leaves),
        "structure_digest": common.digest([sorted(legs) for legs in question["leaf_legs"]]),
        "plan_digest": common.digest([pairs, list(sp.slicing.legs), list(sp.slicing.dims)]),
    }
    phases = {k[len(PHASES):]: v for k, v in totals.items() if k.startswith(PHASES)}
    return prog, info, phases, question


def _question(prog) -> dict:
    """What the reference is told of the plan: names and sizes, no data."""
    from tnc_tpu.ops.program import flat_leaf_tensors

    leaves = flat_leaf_tensors(prog.bound.template.network)
    sp = prog.bound.sliced
    leg_dims = {}
    for leaf in leaves:
        leg_dims.update(dict(leaf.edges()))
    return {
        "leaf_legs": [tuple(leaf.legs) for leaf in leaves],
        "pairs": [(st.lhs, st.rhs) for st in sp.program.steps],
        "sliced_legs": tuple(sp.slicing.legs),
        "sliced_dims": tuple(sp.slicing.dims),
        "leg_dims": leg_dims,
    }


def _call(run, lo: int, hi: int):
    """One call of the entry point, left on the device."""
    (out,) = run.state["prog"].values(
        [run.state["letters"]], run.state["backend"], params=run.state["params"],
        slice_range=(lo, hi), host=False,
    )
    return out


def prepare(run) -> None:
    import jax

    from tnc_tpu.ops.backends import JaxBackend

    params = run.workload["traffic"]
    spec = run.config["circuit"]
    family = importlib.import_module(f"perf.families.{spec['family']}")
    gates = circuits.circuit_gates(spec, run.seed)
    theta_h = next(p[0] for name, p, _ in gates if name == "rx")
    backend = JaxBackend()
    run.state.update(family=family, gates=gates, letters=family.observable(spec),
                     backend=backend, params={"rx": (theta_h,)})
    with span("plan"):
        prog, info, phases, question = _bind(run, backend.device)
    common.emit({"phase": "plan", "theta_h": theta_h, **info, "expval_phases": phases})
    num_slices = info["num_slices"]
    per_call = int(params["slices_per_call"])
    if num_slices < per_call:
        raise RuntimeError(f"plan has {num_slices} slices, a call takes {per_call}")
    run.state.update(prog=prog, per_call=per_call, num_slices=num_slices,
                     question=question)
    t0 = time.monotonic()
    with span("build"):
        warm = _call(run, 0, int(params["warmup_slices"]))
        jax.block_until_ready(warm)
    first_call_s = time.monotonic() - t0
    platforms = {d.platform for leaf in jax.tree.leaves(warm) for d in leaf.devices()}
    if platforms != {run.device["platform"]}:
        raise RuntimeError(f"result lives on {sorted(platforms)}, not {run.device['platform']}")
    run.setup.update(plan_s=info["plan_s"], first_call_s=first_call_s,
                     sliced_cmacs=info["sliced_cmacs"],
                     structure_digest=info["structure_digest"],
                     plan_digest=info["plan_digest"], expval_phases=phases)


def window(run) -> None:
    import jax

    per_call, num_slices = run.state["per_call"], run.state["num_slices"]
    calls = []  # (t_start, t_end, lo, hi, result on device)
    failed = 0
    lo = 0
    with span("window"):
        t0 = time.monotonic()
        while time.monotonic() - t0 < run.seconds:
            if lo + per_call > num_slices:
                lo = 0
            ts = time.monotonic()
            try:
                with span("call"):
                    out = _call(run, lo, lo + per_call)
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


def check(run):
    """A sample of the window's calls, drawn from the seed, each against
    the reference's sum over the same slices at the seed's ``theta_h``."""
    w = run.window
    split = run.state["backend"].split_complex
    rng = np.random.default_rng([run.seed, 3])
    n = min(int(run.workload["traffic"]["check_calls"]), len(w["calls"]))
    picked = sorted(rng.choice(len(w["calls"]), size=n, replace=False).tolist())
    answers = [
        (w["calls"][i][2], w["calls"][i][3],
         sut.result_to_complex(w["calls"][i][4], split).reshape(-1))
        for i in picked
    ]
    # free the program's state before the reference takes the host
    w["calls"] = [c[:4] for c in w["calls"]]
    run.state.pop("prog"), run.state.pop("backend")
    spec = run.config["circuit"]
    reference = importlib.import_module(f"perf.reference_{spec['family']}")
    gap = reference.slice_sum_gap(
        run.state["gates"], spec["qubits"], run.state["letters"],
        run.state["question"], answers,
    )
    numbers = {"amp_gap": {"value": gap, "limit": run.workload["limits"]["amp_gap"]}}
    return numbers, len(w["calls"]) + w["failed"], w["failed"]
