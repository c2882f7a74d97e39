"""Traffic kind ``spmd_calls``: one amplitude's slices spread over the chips.

The window calls ``distributed_sliced_contraction(tn, path, slicing,
mesh=make_mesh(chips), hoist=True, max_slices=chips * slices_per_chip)``
again and again, each call awaited (the entry returns the summed result
on the host), until ``--seconds`` have passed; the call in flight then is
finished and counted with its time. The entry takes a prefix only, so
every call runs the first ``chips * slices_per_chip`` slices: a slice
costs the same whatever its index. The call's own program look-up, leaf
placement and fetch of the result are part of what its user pays and stay
in. Parameters (the cell's ``traffic`` object): ``slices_per_chip``.
Every call of the window is compared with the plain reference's sum over
the same slices.
"""

from __future__ import annotations

import time

import numpy as np

from perf import common, compare, sut
from perf.common import span


def _call(run):
    from tnc_tpu.parallel.sliced_parallel import distributed_sliced_contraction

    plan = run.state["plan"]
    out = distributed_sliced_contraction(
        plan.tn, plan.path, plan.slicing, mesh=run.state["mesh"], hoist=True,
        max_slices=run.state["per_call"],
    )
    return np.asarray(out.data.into_data()).reshape(-1)


def prepare(run) -> None:
    import jax

    from tnc_tpu.parallel.sliced_parallel import make_mesh

    gates, bits, plan = sut.plan_for(run, jax.devices()[0])
    per_call = run.chips * int(run.workload["traffic"]["slices_per_chip"])
    if plan.num_slices < per_call or plan.num_slices % run.chips:
        raise RuntimeError(
            f"plan has {plan.num_slices} slices; a call takes {per_call} over {run.chips} chips"
        )
    mesh = make_mesh(run.chips)
    platforms = {d.platform for d in mesh.devices.flat}
    if platforms != {run.device["platform"]}:
        raise RuntimeError(f"mesh is on {sorted(platforms)}, not {run.device['platform']}")
    run.state.update(plan=plan, mesh=mesh, gates=gates, bits=bits, per_call=per_call,
                     num_slices=plan.num_slices, question=plan.question())
    t0 = time.monotonic()
    with span("build"):
        _call(run)  # the window's own shape: the program is keyed by the chunk
    first_call_s = time.monotonic() - t0
    run.setup.update(plan_s=plan.info["plan_s"], first_call_s=first_call_s,
                     sliced_cmacs=plan.info["sliced_cmacs"],
                     structure_digest=plan.info["structure_digest"],
                     plan_digest=plan.info["plan_digest"])


def window(run) -> None:
    per_call = run.state["per_call"]
    calls = []  # (t_start, t_end, lo, hi, result on the host)
    failed = 0
    with span("window"):
        t0 = time.monotonic()
        while time.monotonic() - t0 < run.seconds:
            ts = time.monotonic()
            try:
                with span("call"):
                    out = _call(run)
            except Exception as exc:  # noqa: BLE001 — a failed call is counted, the run goes on to report
                common.emit({"phase": "window", "step": "call failed", "error": repr(exc)[:300]})
                failed += 1
                break
            calls.append((ts, time.monotonic(), 0, per_call, out))
        end = calls[-1][1] if calls else time.monotonic()
    run.window.update(calls=calls, failed=failed, t0=t0, window_s=end - t0,
                      slices=per_call * len(calls), units=per_call * len(calls))


def summary(run) -> dict:
    w = run.window
    per = [1e3 * (c[1] - c[0]) / (c[3] - c[2]) for c in w["calls"]]
    return {"calls": len(w["calls"]), "slices": w["slices"],
            "call_ms_per_slice": [round(x, 4) for x in per]}


def end_to_end(run) -> dict:
    w = run.window
    if not w["slices"]:
        return {}
    return {"amplitude_s": run.state["num_slices"] * w["window_s"] / w["slices"]}


def check(run):
    """Every call of the window against the plain reference's sum over
    the same slices (all calls cover the same prefix: one reference)."""
    w = run.window
    answers = [(c[2], c[3], c[4]) for c in w["calls"]]
    w["calls"] = [c[:4] for c in w["calls"]]
    # free the program's state before the reference takes the devices
    run.state.pop("plan"), run.state.pop("mesh")
    gap = compare.slice_sum_gap(
        run.state["gates"], run.config["circuit"]["qubits"], run.state["bits"],
        run.state["question"], answers,
    ) if answers else float("inf")
    numbers = {"amp_gap": {"value": gap, "limit": run.workload["limits"]["amp_gap"]}}
    return numbers, len(w["calls"]) + w["failed"], w["failed"]

