"""Traffic kind ``closed_loop``: amplitudes through the service.

One client keeps ``in_flight`` requests at ``ContractionService.submit``,
each a bitstring drawn uniformly from ``--seed``; the next is sent when
one completes (the caller of an XEB job waits for replies). The loop is
started in set-up by a ramp — one request alone, awaited (its program is
built); one more, and the rest once the service's batching wait has
closed on it and it is being dispatched — so that the service forms
batches of 1 and then full batches only: those two shapes are what
set-up compiles. The
window is an interval of the running loop, from the end of one batch's
replies to the end of another's. At its end the client stops
sending and the service is stopped without draining, so no odd-sized
last batch is formed (on a TPU the service compiles one program per
batch size; ``serve/rebind.py`` pads to powers of two only off it).

Parameters (the cell's ``traffic`` object): ``in_flight``,
``warmup_batches`` (full batches completed before the window may
start), ``pool`` (bitstrings drawn from the seed, cycled), and
``check_requests`` (answers compared with the reference).
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

from perf import circuits, common, compare, sut
from perf.common import span


class Client(threading.Thread):
    """The closed loop. Records ``(index, t_submit, t_done, value|None)``."""

    def __init__(self, svc, bitstrings, in_flight: int):
        super().__init__(name="perf-client", daemon=True)
        self.svc, self.bitstrings, self.in_flight = svc, bitstrings, in_flight
        self.done: queue.Queue = queue.Queue()
        self.records: list = []
        self.sent = 0
        self.last_done = 0.0  # when the newest reply came
        self.stopping = threading.Event()
        self.error: BaseException | None = None

    def _submit(self) -> None:
        i = self.sent
        self.sent += 1
        t_submit = time.monotonic()
        with span("submit"):
            fut = self.svc.submit(self.bitstrings[i % len(self.bitstrings)])

        def on_done(f, i=i, t_submit=t_submit):
            self.done.put((i, t_submit, time.monotonic(), f))

        fut.add_done_callback(on_done)

    def run(self) -> None:
        try:
            self._submit()  # the ramp: a batch of one …
            time.sleep(4.0 * self.svc.max_wait_s + 0.005)  # … closed and dispatching …
            for _ in range(self.in_flight - 1):  # … and the rest queued behind it
                self._submit()
            outstanding = self.in_flight
            while outstanding:
                with span("fetch"):
                    i, t_submit, t_done, fut = self.done.get()
                outstanding -= 1
                exc = fut.exception()
                value = None if exc is not None else fut.result()
                self.records.append((i, t_submit, t_done, value))
                self.last_done = t_done
                if not self.stopping.is_set():
                    try:
                        self._submit()
                        outstanding += 1
                    except Exception:  # noqa: BLE001 — the service closed between the test and the call
                        if not self.stopping.is_set():
                            raise
        except BaseException as exc:  # noqa: BLE001 — reported by the harness thread
            self.error = exc


def _serve(run):
    """``(svc, gates, bitstrings, question, info)``: the service of the
    cell's circuit for its seed, started, and what the reference is told."""
    from tnc_tpu.ops.backends import JaxBackend
    from tnc_tpu.ops.program import flat_leaf_tensors
    from tnc_tpu.serve.service import ContractionService

    spec = run.config["circuit"]
    n = spec["qubits"]
    gates = circuits.circuit_gates(spec, run.seed)
    bitstrings = circuits.seeded_bitstrings(int(run.workload["traffic"]["pool"]), n, run.seed)
    target = 2.0 ** run.config["target_log2"]
    t0 = time.monotonic()
    with span("plan"):
        svc = ContractionService.from_circuit(
            sut.build_circuit(gates, n),
            pathfinder=sut.make_planner(run.config["planner"], target),
            target_size=target, backend=JaxBackend(),
        )
    plan_s = time.monotonic() - t0
    bound = svc.bound
    if bound.sliced is not None:
        svc.stop(drain=False)
        raise RuntimeError("closed_loop serves unsliced structures; this plan is sliced")
    leaves = flat_leaf_tensors(bound.template.network)
    leg_dims = {}
    for leaf in leaves:
        leg_dims.update(dict(leaf.edges()))
    question = {
        "leaf_legs": [tuple(leaf.legs) for leaf in leaves],
        "pairs": [(st.lhs, st.rhs) for st in bound.program.steps],
        "sliced_legs": (), "sliced_dims": (), "leg_dims": leg_dims,
        "varying_leaves": tuple(bound.bra_slots),
    }
    info = {
        "plan_s": plan_s, "steps": len(bound.program.steps),
        "leaves": len(leaves), "max_batch": svc.max_batch,
        "structure_digest": common.digest([sorted(l) for l in question["leaf_legs"]]),
        "plan_digest": common.digest(question["pairs"]),
    }
    common.emit({"phase": "plan", **info})
    return svc, gates, bitstrings, question, info


def prepare(run) -> None:
    params = run.workload["traffic"]
    svc, gates, bitstrings, question, info = _serve(run)
    plan_s = info["plan_s"]

    client = Client(svc, bitstrings, int(params["in_flight"]))
    t0 = time.monotonic()
    with span("build"):
        try:
            svc.submit(bitstrings[-1]).result()  # builds the batch-of-one program
        except BaseException:
            svc.stop(drain=False)
            raise
        common.progress("build", "one request alone", t0)
        client.start()
        want = int(params["warmup_batches"]) * svc.max_batch + 1
        while len(client.records) < want:
            if client.error is not None or not client.is_alive():
                svc.stop(drain=False)
                raise RuntimeError(f"client stopped in warm-up: {client.error!r}")
            time.sleep(0.01)
    first_call_s = time.monotonic() - t0
    common.progress("build", "loop running", t0,
                    batch_sizes=svc.stats()["batch_size"])
    bad = [r for r in client.records if r[3] is None]
    if bad:
        svc.stop(drain=False)
        raise RuntimeError(f"{len(bad)} requests failed in warm-up")
    run.setup.update(plan_s=plan_s, first_call_s=first_call_s,
                     structure_digest=info["structure_digest"],
                     plan_digest=info["plan_digest"])
    run.state.update(svc=svc, client=client, gates=gates, question=question,
                     bitstrings=bitstrings)


def _stats_mark(svc) -> dict:
    s = svc.stats()
    return {
        "completed": s["counts"]["completed"], "failed": s["counts"]["failed"],
        "batches": s["counts"]["batches"],
        "degraded_batches": s["counts"]["degraded_batches"],
        "dispatch_s": s["by_tier"]["exact"]["dispatch"]["total_s"],
    }


def _burst_end(client, after: float, quiet_s: float = 0.02) -> float:
    """Wait for the first burst of replies that ends after ``after``: a
    batch's riders are answered within a millisecond or two of each
    other, then nothing comes until the next batch is done. Returns the
    time the burst's last reply came."""
    while client.is_alive():
        last = client.last_done
        if last > after and time.monotonic() - last >= quiet_s:
            return last
        time.sleep(0.001)
    raise RuntimeError(f"client stopped: {client.error!r}")


def window(run) -> None:
    """From the end of one batch's replies to the end of the first batch's
    replies that come ``--seconds`` later or more: a whole number of
    batches with all their time, so that the rate does not swing by a
    batch with where the window happens to fall (12 batches in 20 s)."""
    svc, client = run.state["svc"], run.state["client"]
    t0 = _burst_end(client, time.monotonic())
    with span("window"):
        before = _stats_mark(svc)
        time.sleep(max(0.0, t0 + run.seconds - time.monotonic()))
        t1 = _burst_end(client, t0 + run.seconds)
        after = _stats_mark(svc)
    client.stopping.set()
    svc.stop(drain=False)
    client.join(timeout=120.0)
    if client.is_alive() or client.error is not None:
        raise RuntimeError(f"client did not end cleanly: {client.error!r}")
    inside = [r for r in client.records if t0 < r[2] <= t1]
    run.window.update(
        t0=t0, t1=t1, window_s=t1 - t0,
        completed=[r for r in inside if r[3] is not None],
        failed=sum(1 for r in inside if r[3] is None),
        stats={k: after[k] - before[k] for k in after},
    )
    run.window["units"] = len(run.window["completed"])


def summary(run) -> dict:
    w = run.window
    return {"completed": len(w["completed"]), "failed": w["failed"], **w["stats"]}


def end_to_end(run) -> dict:
    w = run.window
    # every request that completed in the window, from its submit (which
    # may lie before the window: the loop was running) to its reply
    latency = [1e3 * (r[2] - r[1]) for r in w["completed"]]
    out = {"amps_per_s": len(w["completed"]) / w["window_s"]}
    if latency:
        out["request_p95_ms"] = float(np.quantile(latency, 0.95))
        out["request_p50_ms"] = float(np.quantile(latency, 0.50))
    return out


def check(run):
    """A sample of the window's completed requests, drawn from the seed,
    each against the plain reference's amplitude of its bitstring."""
    w = run.window
    rng = np.random.default_rng([run.seed, 3])
    n = min(int(run.workload["traffic"]["check_requests"]), len(w["completed"]))
    picked = sorted(rng.choice(len(w["completed"]), size=n, replace=False).tolist())
    bitstrings = run.state["bitstrings"]
    answers = [
        (bitstrings[w["completed"][i][0] % len(bitstrings)], w["completed"][i][3])
        for i in picked
    ]
    # free the program's state before the reference takes the device
    run.state.pop("svc"), run.state.pop("client")
    gap = compare.amplitude_gap(
        run.state["gates"], run.config["circuit"]["qubits"], run.state["question"],
        answers,
    ) if answers else float("inf")
    numbers = {
        "amp_gap": {"value": gap, "limit": run.workload["limits"]["amp_gap"]},
        "degraded_batches": {"value": w["stats"]["degraded_batches"], "limit": 0},
    }
    return numbers, len(w["completed"]) + w["failed"], w["failed"]

