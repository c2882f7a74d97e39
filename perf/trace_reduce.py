"""From a profiler trace (``.xplane.pb``) to device numbers.

Per device plane (``/device:TPU:<n>``): the union of the intervals in
which an XLA op ran inside the window (busy), its complement (idle
gaps), the summed op time by name (ops that enclose others, as a
``while`` does its body, left out), the time in collectives. The window
is the host span ``perf:window`` that the harness puts round the
measured window; the idle gaps are attributed to the other ``perf:*``
host spans (the benchmark's ``call``, ``submit``, ``fetch``, …, and the
program's ``perf:tnc.*``) by overlap: a gap's time goes to EVERY span
that overlaps it, nested and concurrent spans each in full, so the
entries do not sum to the idle total (:func:`idle_by_span`).
Host and device clocks of one trace agree to about a millisecond
(recorded trace in ``perf/tests/data``), so gaps shorter than that are
attributed loosely; their sum is exact.

Reads the trace with ``jax.profiler.ProfileData`` alone. Every step
after the read is linear in the events, or a sort of them, and works on
arrays; each sum runs in the order a plain loop over the events would
take, so the numbers are a loop's to the last bit (``perf/tests`` keeps
such a loop as the oracle).
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

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


def leaves_only(starts, ends):
    """Indices of the events (``starts``, ``ends``: arrays) that enclose no
    other one, in the order of their start, the longer first where two
    start together: a ``while`` or ``conditional`` op's event spans its
    body's ops, and summing both would count the body twice. In that order
    an event encloses another exactly when the next one starts before it
    ends and ends no later, so one comparison of neighbours finds them."""
    order = np.lexsort((-ends, starts))
    s, e = starts[order], ends[order]
    encloses = np.zeros(len(order), dtype=bool)
    encloses[:-1] = (s[1:] < e[:-1]) & (e[1:] <= e[:-1])
    return order[~encloses]


def union(starts, ends):
    """The union of the intervals as sorted, disjoint blocks ``(starts,
    ends)``; intervals that touch merge."""
    if not len(starts):
        return starts, ends
    order = np.lexsort((ends, starts))
    s, reach = starts[order], np.maximum.accumulate(ends[order])
    first = np.ones(len(s), dtype=bool)
    first[1:] = s[1:] > reach[:-1]
    last = np.append(first[1:], True)
    return s[first], reach[last]


def complement(starts, ends, lo, hi):
    """The gaps ``(starts, ends)`` that the sorted, disjoint blocks leave
    in ``[lo, hi]``."""
    gap_starts = np.concatenate(([lo], ends))
    gap_ends = np.concatenate((starts, [hi]))
    keep = gap_ends > gap_starts
    return gap_starts[keep], gap_ends[keep]


def idle_by_span(gaps, host) -> dict:
    """Nanoseconds of the idle ``gaps`` (sorted, disjoint) under each span
    of ``host`` (``(name, start, end)`` sorted by start), keyed by the
    span's name without ``perf:``. A gap's time goes to EVERY span that
    overlaps it; what is left of the gap after the SUM of those overlaps
    goes to ``"no span"``.

    One sweep over both lists: the spans open at a gap are those begun
    before it ends and not ended before it starts, and a span that ends
    before a gap starts meets no later gap, so the work is the gaps times
    the spans open at once, not the gaps times all spans. Overlaps are
    added gap by gap, and within a gap in the order of ``host``."""
    by_span: dict[str, float] = {}
    live, at = [], 0
    for gs, ge in gaps:
        while at < len(host) and host[at][1] < ge:
            live.append(host[at])
            at += 1
        live = [sp for sp in live if sp[2] > gs]
        covered = 0.0
        for nm, s, e in live:
            o = min(e, ge) - max(s, gs)
            if o > 0:
                key = nm[len(SPAN_PREFIX):]
                by_span[key] = by_span.get(key, 0.0) + o
                covered += o
        if ge - gs > covered:
            by_span["no span"] = by_span.get("no span", 0.0) + (ge - gs - covered)
    return by_span


def _ids(values):
    """``values`` numbered by first appearance: ``(ids, distinct values)``."""
    index = {v: i for i, v in enumerate(dict.fromkeys(values))}
    return np.fromiter(map(index.__getitem__, values), np.intp, len(values)), list(index)


def op_seconds(name_ids, names, starts, ends, modules):
    """Op nanoseconds by short name (an op's name is ``names[name_ids[i]]``),
    as ``module/op`` where the op starts inside an event of ``modules``
    (with a ns of slack), keyed in the order of each key's first op and
    summed in the order of the ops; and the nanoseconds in collectives."""
    op_ids, ops = _ids([short_op_name(nm) for nm in names])
    op_ids = op_ids[name_ids]
    mods = sorted(modules, key=lambda m: m[1])
    mod_ids, mod_names = _ids([short_module_name(m[0]) for m in mods])
    module = np.full(len(starts), -1, dtype=np.intp)
    if mods:
        i = np.searchsorted([m[1] for m in mods], starts + 1.0, side="right") - 1
        hit = (i >= 0) & (np.array([m[2] for m in mods])[np.maximum(i, 0)] >= starts)
        module[hit] = mod_ids[i[hit]]
    width = len(mod_names) + 1
    pairs, first, pair_of = np.unique(op_ids * width + module + 1, return_index=True,
                                      return_inverse=True)
    labels = [f"{mod_names[m - 1]}/{ops[o]}" if m else ops[o]
              for o, m in (divmod(p, width) for p in pairs.tolist())]
    by_first = np.argsort(first, kind="stable")
    key_ids, keys = _ids([labels[k] for k in by_first.tolist()])  # two pairs may read alike
    key_of_pair = np.empty(len(pairs), dtype=np.intp)
    key_of_pair[by_first] = key_ids
    seconds = np.bincount(key_of_pair[pair_of], weights=ends - starts, minlength=len(keys))
    collective = np.array([bool(COLLECTIVE.match(op)) for op in ops], dtype=bool)
    in_collectives = np.bincount(collective[op_ids].astype(np.intp), weights=ends - starts,
                                 minlength=2)[1]
    return dict(zip(keys, seconds.tolist())), float(in_collectives)


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
            for line in plane.lines:  # the runtime's own events far outnumber the spans
                spans.extend((nm, float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns))
                             for ev in line.events if (nm := ev.name).startswith(SPAN_PREFIX))
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
        names, starts, ends = zip(*events) if events else ((), (), ())
        name_ids, names = _ids(names)
        starts, ends = np.array(starts, dtype=float), np.array(ends, dtype=float)
        leaves = leaves_only(starts, ends)
        leaves = leaves[(ends[leaves] > lo) & (starts[leaves] < hi)]
        starts, ends = np.maximum(starts[leaves], lo), np.minimum(ends[leaves], hi)
        busy = union(starts, ends)
        gaps = complement(*busy, lo, hi)
        by_name, collective = op_seconds(name_ids[leaves], names, starts, ends,
                                         dev["modules"] if dev["ops"] else [])
        per_device.append({
            "ordinal": n, "busy_ns": float(sum((busy[1] - busy[0]).tolist())),
            "op_ns": sum(by_name.values()), "collective_ns": collective, "ops": by_name,
            "gaps": idle_by_span(list(zip(gaps[0].tolist(), gaps[1].tolist())), host),
            "longest_gap_ns": float(np.max(gaps[1] - gaps[0], initial=0.0)),
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
