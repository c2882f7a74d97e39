"""Per-chip readings of a traced window, for cells whose chips do
DIFFERENT work (one partition a chip, the fan-in on the survivor's).

``trace_reduce.reduce_events`` sums the ops over the chips and keeps the
idle gaps of the idlest chip alone: right where every chip runs the same
program, blind where one chip holds the survivor and three wait. Here the
same reduction runs once per chip (``reduce_events`` on that chip's plane
alone), and keeps, per chip, the op seconds by jitted program
(``jit_tnc_*``) and the idle seconds by host span.

The harness deletes the trace right after its own reduction, before any
metric is read; the one hook of a traffic kind between ``stop_trace`` and
that is ``summary(run)``. ``partition_calls.summary`` calls
:func:`read_window` there and leaves the result in
``run.window["per_chip"]``, where the readers below find it.
"""

from __future__ import annotations

import glob
import os

from perf import common, trace_reduce

FANIN_PAIR = "jit_tnc_fanin_pair"
PARTITION_LOCAL = "jit_tnc_partition_local"


def per_chip(devices: dict, spans: list, chips: int | None = None) -> list[dict]:
    """One record per chip (by ordinal): ``op_s`` by jitted program (an op
    outside any module under its own name), ``idle_s`` by host span, and
    the window's seconds. Plain tuples in, as ``reduce_events`` takes them."""
    out = []
    for n in sorted(devices)[: chips or len(devices)]:
        one = trace_reduce.reduce_events({n: devices[n]}, spans)
        by_module: dict[str, float] = {}
        for name, seconds in one["device_ops"]:
            module = name.split("/", 1)[0]
            by_module[module] = by_module.get(module, 0.0) + seconds
        out.append({"ordinal": n, "window_s": one["window_s"], "busy_s": one["busy_s"],
                    "op_s": by_module, "idle_s": dict(map(tuple, one["idle_gaps"]))})
    return out


def read_window(cell: str, chips: int) -> list[dict] | None:
    """:func:`per_chip` of the traced window the harness just closed
    (``perf/_trace/<cell>``), ``None`` where there is no such trace."""
    trace_dir = os.path.join(common.PERF_DIR, "_trace", cell)
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        return None
    devices, spans = trace_reduce.read_planes(paths[0])
    return per_chip(devices, spans, chips)


def survivor(chips: list[dict] | None):
    """The chip that ran the fan-in's pair contractions (most of them, in
    a tree): the program pins the fan-in's survivor there. ``None`` from a
    program that does not name its pair programs."""
    ran = [c for c in chips or [] if c["op_s"].get(FANIN_PAIR)]
    return max(ran, key=lambda c: c["op_s"][FANIN_PAIR]) if ran else None


def imbalance_pct(chips: list[dict] | None, module: str = PARTITION_LOCAL):
    """``100 (1 - mean / max)`` of the chips' op seconds in ``module``;
    ``None`` when none of them ran it."""
    seconds = [c["op_s"].get(module, 0.0) for c in chips or []]
    if not any(seconds):
        return None
    return 100.0 * (1.0 - sum(seconds) / len(seconds) / max(seconds))


def idle_pct(chip: dict | None, *spans: str):
    """Seconds ``chip`` was idle under the program's host spans ``spans``
    (give spans that do not overlap) over the window, in per cent;
    ``None`` when the trace holds none of them."""
    if chip is None:
        return None
    found = [chip["idle_s"][f"tnc.{s}"] for s in spans if f"tnc.{s}" in chip["idle_s"]]
    return 100.0 * sum(found) / chip["window_s"] if found else None
