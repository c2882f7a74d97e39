"""Traffic kind ``batch_slice_calls``: one correlated batch of ``2^k``
amplitudes (``k`` open qubits, one closed prefix), its slices in order.

Set-up binds the configuration's circuit through
``tnc_tpu.queries.bind_amplitude_batch(circuit, open_qubits, pathfinder,
None, target)``: the program builds the template with the open qubits'
legs left open, plans it with the configuration's planner
(``plan_structure``: on its rank>=3 cores), slices it to the first
target from ``target_log2`` down that its HBM budget model accepts, and
builds the sliced program. The closed prefix is drawn from the seed and
stays the same all window (a batch is hours: a window lies inside one
batch). The window calls ``AmplitudeBatchProgram.amplitudes(closed_bits,
backend, slice_range=(lo, lo + slices_per_call), host=False)`` on
consecutive ranges from slice 0, each call awaited on the device, one in
flight, until ``--seconds`` have passed; the call in flight then is
finished and counted with its time. ``amplitude_s`` is ``slice_calls``'
(the plan's slices at the window's pace): seconds to one batch of
``2^k`` amplitudes, which by the frugal rule is seconds to about one
sample. Parameters (the cell's ``traffic`` object): ``slices_per_call``,
``warmup_slices``, ``check_calls`` (answers compared with the plain
reference by ``perf/compare_batch.py``, drawn from the seed among the
window's calls), ``open_qubits`` (how many the configuration opens).
"""

from __future__ import annotations

import time

import numpy as np

from perf import circuits, common, compare_batch, sut
from perf.common import span
from perf.traffic import slice_calls
from perf.traffic.expval_slice_calls import _question  # what the reference is told of a bound program's plan: the same for any query
from perf.traffic.slice_calls import end_to_end  # noqa: F401 — the kind's own: a window of slice calls reads the same

PHASES = "ampbatch."


def _bind(run, device):
    """``(program, info, phases, question)``: the bound batch program at
    the first target the budget model accepts, what the benchmark reads
    of its plan, the program's own phase totals of the bind, and what the
    reference is told."""
    from tnc_tpu import obs
    from tnc_tpu.contractionpath.slicing import sliced_flops
    from tnc_tpu.ops.budget import fits_hbm
    from tnc_tpu.ops.hoist import hoist_sliced_program
    from tnc_tpu.queries.amplitude_batch import bind_amplitude_batch

    spec = run.config["circuit"]
    target_log2 = run.config["target_log2"]
    t_plan = time.monotonic()
    while True:
        target = 2.0 ** target_log2
        t0 = time.monotonic()
        with obs.collect_phases() as totals:
            prog = bind_amplitude_batch(
                sut.build_circuit(run.state["gates"], spec["qubits"]),
                run.state["open_qubits"],
                sut.make_planner(run.config["planner"], target), None, target,
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
        "network": f"{len(question['leaf_legs'])} leaves, {len(prog.open_qubits)} of {prog.num_qubits} qubits open",
        "target_log2": target_log2,
        "plan_s": time.monotonic() - t_plan,
        "sliced_cmacs": float(sliced_flops(
            list(prog.bound.template.network.tensors), pairs, sp.slicing)),
        "num_slices": sp.slicing.num_slices,
        "sliced_legs": len(sp.slicing.legs),
        "steps": len(pairs),
        "prelude_steps": len(hp.prelude_steps),
        "residual_steps": len(hp.residual.program.steps),
        "result_axes": list(prog.permutation),
        "structure_digest": common.digest([sorted(legs) for legs in question["leaf_legs"]]),
        "plan_digest": common.digest([pairs, list(sp.slicing.legs), list(sp.slicing.dims)]),
    }
    phases = {k[len(PHASES):]: v for k, v in totals.items() if k.startswith(PHASES)}
    return prog, info, phases, question


def _call(run, lo: int, hi: int):
    """One call of the entry point, left on the device."""
    return run.state["prog"].amplitudes(
        run.state["closed_bits"], run.state["backend"],
        slice_range=(lo, hi), host=False,
    )


def prepare(run) -> None:
    import jax

    from tnc_tpu.ops.backends import JaxBackend

    params = run.workload["traffic"]
    spec = run.config["circuit"]
    open_qubits = [int(q) for q in run.config["open_qubits"]]
    if len(open_qubits) != int(params["open_qubits"]):
        raise RuntimeError(
            f"the cell opens {params['open_qubits']} qubits, the configuration {open_qubits}"
        )
    taken = set(open_qubits)
    bits = circuits.seeded_bitstrings(1, spec["qubits"], run.seed)[0]
    closed_bits = "".join(c for q, c in enumerate(bits) if q not in taken)
    backend = JaxBackend()
    run.state.update(gates=circuits.circuit_gates(spec, run.seed), backend=backend,
                     open_qubits=open_qubits, closed_bits=closed_bits)
    with span("plan"):
        prog, info, phases, question = _bind(run, backend.device)
    common.emit({"phase": "plan", "closed_bits": closed_bits, "open_qubits": open_qubits,
                 **info, "ampbatch_phases": phases})
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
                     plan_digest=info["plan_digest"], ampbatch_phases=phases)


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


def summary(run) -> dict:
    """``slice_calls``' line, with the seconds to the batch beside the
    seconds to each of its amplitudes."""
    out = slice_calls.summary(run)
    batch_s = end_to_end(run).get("amplitude_s")
    if batch_s is not None:
        amplitudes = 1 << len(run.state["open_qubits"])
        out.update(batch_s=batch_s, amplitudes=amplitudes,
                   seconds_per_amplitude=batch_s / amplitudes)
    return out


def check(run):
    """A sample of the window's calls, drawn from the seed, each fetched
    and ordered as a user would (``AmplitudeBatchProgram.to_host``) and
    held against the reference's sum of the same slices' tensors."""
    w = run.window
    prog = run.state["prog"]
    rng = np.random.default_rng([run.seed, 3])
    n = min(int(run.workload["traffic"]["check_calls"]), len(w["calls"]))
    picked = sorted(rng.choice(len(w["calls"]), size=n, replace=False).tolist())
    answers = [
        (w["calls"][i][2], w["calls"][i][3],
         np.asarray(prog.to_host(w["calls"][i][4]), dtype=np.complex128))
        for i in picked
    ]
    # free the program's state before the reference takes the host
    w["calls"] = [c[:4] for c in w["calls"]]
    run.state.pop("prog"), run.state.pop("backend")
    del prog
    gap = compare_batch.batch_sum_gap(
        run.state["gates"], run.config["circuit"]["qubits"], run.state["closed_bits"],
        run.state["open_qubits"], run.state["question"], answers,
    )
    numbers = {"amp_gap": {"value": gap, "limit": run.workload["limits"]["amp_gap"]}}
    return numbers, len(w["calls"]) + w["failed"], w["failed"]
