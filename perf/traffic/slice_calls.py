"""Traffic kind ``slice_calls``: one amplitude, its slices in order.

The window calls ``JaxBackend().execute_sliced(sp, arrays, host=False,
slice_range=(lo, lo + slices_per_call))`` on consecutive ranges from
slice 0, each call awaited, until ``--seconds`` have passed; the call in
flight then is finished and counted with its time. Parameters (the
cell's ``traffic`` object): ``slices_per_call``, ``warmup_slices``,
``check_calls`` (answers compared with the reference, drawn from the
seed among the window's calls).
"""

from __future__ import annotations

import time

import numpy as np

from perf import common, compare, sut
from perf.common import span


def prepare(run) -> None:
    import jax

    from tnc_tpu.ops.backends import JaxBackend

    params = run.workload["traffic"]
    backend = JaxBackend()
    gates, bits, plan = sut.plan_for(run, backend.device)
    per_call = int(params["slices_per_call"])
    if plan.num_slices < per_call:
        raise RuntimeError(f"plan has {plan.num_slices} slices, a call takes {per_call}")
    t0 = time.monotonic()
    with span("build"):
        warm = backend.execute_sliced(
            plan.sp, plan.arrays, host=False,
            slice_range=(0, int(params["warmup_slices"])),
        )
        jax.block_until_ready(warm)
    first_call_s = time.monotonic() - t0
    platforms = {d.platform for leaf in jax.tree.leaves(warm) for d in leaf.devices()}
    if platforms != {run.device["platform"]}:
        raise RuntimeError(f"result lives on {sorted(platforms)}, not {run.device['platform']}")
    run.setup.update(plan_s=plan.info["plan_s"], first_call_s=first_call_s,
                     sliced_cmacs=plan.info["sliced_cmacs"],
                     structure_digest=plan.info["structure_digest"],
                     plan_digest=plan.info["plan_digest"])
    run.state.update(plan=plan, backend=backend, gates=gates, bits=bits,
                     per_call=per_call, num_slices=plan.num_slices,
                     question=plan.question())


def window(run) -> None:
    import jax

    plan, backend = run.state["plan"], run.state["backend"]
    per_call = run.state["per_call"]
    calls = []  # (t_start, t_end, lo, hi, result on device)
    failed = 0
    lo = 0
    with span("window"):
        t0 = time.monotonic()
        while time.monotonic() - t0 < run.seconds:
            if lo + per_call > plan.num_slices:
                lo = 0
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
    per = [1e3 * (c[1] - c[0]) / (c[3] - c[2]) for c in w["calls"]]
    return {"calls": len(w["calls"]), "slices": w["slices"],
            "call_ms_per_slice": [round(x, 4) for x in per]}


def end_to_end(run) -> dict:
    w = run.window
    if not w["slices"]:
        return {}
    num_slices = run.state["num_slices"]
    return {"amplitude_s": num_slices * w["window_s"] / w["slices"]}


def check(run):
    """A sample of the window's calls, drawn from the seed, each against
    the plain reference's sum over the same slices."""
    w = run.window
    plan, backend = run.state["plan"], run.state["backend"]
    split = backend.split_complex
    rng = np.random.default_rng([run.seed, 3])
    n = min(int(run.workload["traffic"]["check_calls"]), len(w["calls"]))
    picked = sorted(rng.choice(len(w["calls"]), size=n, replace=False).tolist())
    answers = [
        (w["calls"][i][2], w["calls"][i][3],
         sut.result_to_complex(w["calls"][i][4], split).reshape(-1))
        for i in picked
    ]
    # free the program's state before the reference takes the device
    w["calls"] = [c[:4] for c in w["calls"]]
    run.state.pop("plan"), run.state.pop("backend")
    del plan, backend
    gap = compare.slice_sum_gap(
        run.state["gates"], run.config["circuit"]["qubits"], run.state["bits"],
        run.state["question"], answers,
    )
    numbers = {"amp_gap": {"value": gap, "limit": run.workload["limits"]["amp_gap"]}}
    return numbers, len(w["calls"]) + w["failed"], w["failed"]

