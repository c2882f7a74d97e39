"""Median over the window's calls of call time / slices in the call. Stands
beside the whole-window ``amplitude_s``, never in its place."""

import statistics

name = 'call_ms_per_slice_p50'
unit = 'ms'
layer = 'sliced executor'
moves = 'amplitude_s'
workloads = None  # every cell that reports `moves`


def read(run):
    calls = run.window.get("calls") or []
    if not calls:
        return None
    return statistics.median(1e3 * (c[1] - c[0]) / (c[3] - c[2]) for c in calls)
