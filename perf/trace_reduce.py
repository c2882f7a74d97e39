"""From a profiler trace (``.xplane.pb``) to device numbers.

Per device plane (``/device:TPU:<n>``): the union of the intervals in
which an XLA op ran inside the window (busy), its complement (idle
gaps), the summed op time by name (ops that enclose others, as a
``while`` does its body, left out), the time in collectives. The window
is the host span ``perf:window`` that the harness puts round the
measured window; the idle gaps are attributed to the benchmark's other
``perf:*`` host spans (``call``, ``submit``, ``fetch``, …) by overlap
(the benchmark's spans do not nest, so a gap's time is shared out once).
Host and device clocks of one trace agree to about a millisecond
(recorded trace in ``perf/tests/data``), so gaps shorter than that are
attributed loosely; their sum is exact.

Reads the trace with ``jax.profiler.ProfileData`` alone.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast)"
)
SPAN_PREFIX = "perf:"
WINDOW_SPAN = "perf:window"


def short_op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def short_module_name(name: str) -> str:
    """``jit_run(5161974800474407067)`` -> ``jit_run``."""
    return re.sub(r"\(\d+\)$", "", name)


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


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


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


def _events(line):
    return [(ev.name, float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns))
            for ev in line.events]


def read_planes(path: str):
    """``(devices, spans)``: per device ordinal its op and module events,
    and the ``perf:*`` host spans, all as ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            devices[int(m.group(1))] = {
                "ops": _events(lines[OPS_LINE]) if OPS_LINE in lines else [],
                "modules": _events(lines[MODULES_LINE]) if MODULES_LINE in lines else [],
            }
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(e for e in _events(line) if e[0].startswith(SPAN_PREFIX))
    return devices, spans


def reduce_events(devices: dict, spans: list, chips: int | None = None) -> dict:
    """The reduction proper, on plain tuples (tests feed it by hand)."""
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


def reduce_file(path: str, chips: int | None = None) -> dict:
    devices, spans = read_planes(path)
    return reduce_events(devices, spans, chips)


def reduce_dir(trace_dir: str, chips: int | None = None) -> dict:
    """The one ``.xplane.pb`` that a ``start_trace(trace_dir)`` wrote."""
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if len(paths) != 1:
        raise ValueError(f"{trace_dir} holds {len(paths)} .xplane.pb files, not one")
    return reduce_file(paths[0], chips)
